"""Pallas TPU kernel: fused PS-DSF / rPS-DSF scoring + masked argmin.

THE PAPER's compute hot-spot at fleet scale: progressive filling evaluates

    K[n, j] = (x_n / phi_n) * max_r  d[n, r] / res[j, r]
    feasible[n, j] = all_r  d[n, r] <= res[j, r]
    winner = argmin over feasible (n, j)

once per grant — with 10k jobs x 10k slices x R resources per epoch this is
a dense O(N*J*R) pass.  The fusion matters: materializing the (N, J) score
matrix in HBM and then argmin-ing it reads/writes N*J floats twice; this
kernel keeps each (BN, BJ) score tile in VMEM and reduces it to a per-tile
(min, argmin) pair on the fly — one HBM pass over the inputs, outputs of
size #tiles only.

Tiling: grid (N/BN, J/BJ); the R axis (<= 8 resources) is unrolled in
registers, so tiles are clean (BN, BJ) = (128, 128) VPU shapes.  Each
tile's (min, argmin) pair is a scalar written to scalar memory (SMEM): a
(1, 1) block of a (tiles_n, tiles_j) array in VMEM is not (8, 128)-aligned,
and the TPU compiler refuses it.

Beyond the fully-fused rPS-DSF+pooled reduction, the family also covers the
other criterion x policy combinations of the device-resident epoch engine
(:mod:`repro.core.engine_jax`), which maintains scores/feasibility
incrementally and only needs the masked reductions:

  * ``masked_argmin1d_tiles`` — masked argmin over a score VECTOR: an RRR
    server visit (score column of the visited server) or DRF/TSF selection
    (server-agnostic scores broadcast against row feasibility);
  * ``masked_argmin2d_tiles`` — masked argmin over a maintained (N, J) score
    MATRIX: pooled selection for the PS-DSF family without recomputing
    scores from demands.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 3.4e38  # feasibility/overflow sentinel (~f32 max); python float so the
              # kernel body doesn't capture a traced constant
_IBIG = 2**31 - 1


def _tile_argmin(masked, cols: int):
    """(min, local row, local col) of a 2-D tile: the FIRST row-major
    index attaining the minimum, as ``jnp.argmin`` over the flattened tile
    picks it — computed from full reductions and an iota, which the TPU
    lowering supports (a flat reshape plus a dynamic gather is not)."""
    m = jnp.min(masked)
    lin = (jax.lax.broadcasted_iota(jnp.int32, masked.shape, 0) * cols
           + jax.lax.broadcasted_iota(jnp.int32, masked.shape, 1))
    first = jnp.min(jnp.where(masked == m, lin, _IBIG))
    return m, first // cols, first % cols


#: the whole (tiles_n, tiles_j) output stays resident in SMEM for the grid;
#: each cell writes its own scalar (see the module docstring)
_SCALAR_OUT = pl.BlockSpec(memory_space=pltpu.SMEM)


def _score_tile_kernel(x_ref, phi_ref, d_ref, res_ref, min_ref, arg_ref, *,
                       n_res: int, bn: int, bj: int):
    """One (BN, BJ) tile: score, mask, local argmin."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    x = x_ref[...]                     # (BN, 1) f32
    phi = phi_ref[...]                 # (BN, 1)
    dom = jnp.zeros((bn, bj), jnp.float32)
    feas = jnp.ones((bn, bj), jnp.bool_)
    # unrolled resource loop: everything stays (BN, BJ)
    for r in range(n_res):
        d_r = d_ref[:, r:r + 1]        # (BN, 1)
        res_r = res_ref[r:r + 1, :]    # (1, BJ): res enters transposed
        ok = res_r > 0.0
        frac = jnp.where(ok, d_r / jnp.where(ok, res_r, 1.0), BIG)
        frac = jnp.where((d_r == 0.0) & ~ok, 0.0, frac)
        dom = jnp.maximum(dom, frac)
        feas = feas & (d_r <= res_r)
    score = (x / phi) * dom
    score = jnp.where(feas, score, BIG)
    m, ln, lj = _tile_argmin(score, bj)
    min_ref[i, j] = m
    arg_ref[i, j] = (i * bn + ln) * jnp.int32(pl.num_programs(1) * bj) + (j * bj + lj)


def _masked_argmin1d_kernel(s_ref, ok_ref, min_ref, arg_ref, *, bn: int):
    """One (BN, 1) tile of a masked 1-D argmin (scores + validity mask).

    Serves two widened coverage cases of the fused allocator loop:
      * an RRR server visit — the visited server's score column s[:, j]
        masked by its feasibility column;
      * DRF/TSF selection — the server-agnostic (N,) score vector broadcast
        against row-level feasibility (does framework n fit ANYWHERE).
    """
    i = pl.program_id(0)
    masked = jnp.where(ok_ref[...] != 0, s_ref[...], BIG)   # (BN, 1)
    m, ln, _ = _tile_argmin(masked, 1)
    min_ref[i, 0] = m
    arg_ref[i, 0] = i * bn + ln


@functools.partial(jax.jit, static_argnames=("bn", "interpret"))
def masked_argmin1d_tiles(s, ok, *, bn: int = 128, interpret: bool = False):
    """-> (tile_mins (tn,), tile_args (tn,)).  s (N,) f32, ok (N,) mask;
    N % bn == 0.  Masked-out and padding entries must carry ok == 0."""
    N = s.shape[0]
    assert N % bn == 0, (N, bn)
    tn = N // bn
    kernel = functools.partial(_masked_argmin1d_kernel, bn=bn)
    mins, args = pl.pallas_call(
        kernel,
        grid=(tn,),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i: (i, 0)),
        ],
        out_specs=[_SCALAR_OUT, _SCALAR_OUT],
        out_shape=[
            jax.ShapeDtypeStruct((tn, 1), jnp.float32),
            jax.ShapeDtypeStruct((tn, 1), jnp.int32),
        ],
        interpret=interpret,
    )(s[:, None].astype(jnp.float32), ok[:, None].astype(jnp.int32))
    return mins[:, 0], args[:, 0]


def _masked_argmin2d_kernel(s_ref, feas_ref, min_ref, arg_ref, *,
                            bn: int, bj: int):
    """One (BN, BJ) tile of a masked 2-D argmin over a maintained score
    matrix (pooled selection for server-specific criteria: the incremental
    engine keeps s and feas consistent; this kernel only reduces them)."""
    i = pl.program_id(0)
    j = pl.program_id(1)
    masked = jnp.where(feas_ref[...] != 0, s_ref[...], BIG)
    m, ln, lj = _tile_argmin(masked, bj)
    min_ref[i, j] = m
    arg_ref[i, j] = (i * bn + ln) * jnp.int32(pl.num_programs(1) * bj) + (j * bj + lj)


@functools.partial(jax.jit, static_argnames=("bn", "bj", "interpret"))
def masked_argmin2d_tiles(s, feas, *, bn: int = 128, bj: int = 128,
                          interpret: bool = False):
    """-> (tile_mins (tn, tj), tile_args (tn, tj)); args encode n*Jpad + j.

    s (N, J) f32 scores, feas (N, J) mask; N % bn == 0, J % bj == 0.
    Cross-tile exact ties resolve in row-major TILE order, which coincides
    with lexicographic (n, j) order only within a single 128-wide tile —
    same caveat as ``psdsf_argmin_tiles``."""
    N, J = s.shape
    assert N % bn == 0 and J % bj == 0, (N, J, bn, bj)
    tn, tj = N // bn, J // bj
    kernel = functools.partial(_masked_argmin2d_kernel, bn=bn, bj=bj)
    return pl.pallas_call(
        kernel,
        grid=(tn, tj),
        in_specs=[
            pl.BlockSpec((bn, bj), lambda i, j: (i, j)),
            pl.BlockSpec((bn, bj), lambda i, j: (i, j)),
        ],
        out_specs=[_SCALAR_OUT, _SCALAR_OUT],
        out_shape=[
            jax.ShapeDtypeStruct((tn, tj), jnp.float32),
            jax.ShapeDtypeStruct((tn, tj), jnp.int32),
        ],
        interpret=interpret,
    )(s.astype(jnp.float32), feas.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("bn", "bj", "interpret"))
def psdsf_argmin_tiles(x, phi, d, res, *, bn: int = 128, bj: int = 128,
                       interpret: bool = False):
    """-> (tile_mins (tn, tj), tile_args (tn, tj)); args encode n*Jpad + j.

    Inputs: x (N,), phi (N,), d (N, R), res (J, R); N % bn == 0, J % bj == 0.
    """
    N, R = d.shape
    J = res.shape[0]
    assert N % bn == 0 and J % bj == 0, (N, J, bn, bj)
    tn, tj = N // bn, J // bj
    kernel = functools.partial(_score_tile_kernel, n_res=R, bn=bn, bj=bj)
    return pl.pallas_call(
        kernel,
        grid=(tn, tj),
        in_specs=[
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bn, R), lambda i, j: (i, 0)),
            pl.BlockSpec((R, bj), lambda i, j: (0, j)),
        ],
        out_specs=[_SCALAR_OUT, _SCALAR_OUT],
        out_shape=[
            jax.ShapeDtypeStruct((tn, tj), jnp.float32),
            jax.ShapeDtypeStruct((tn, tj), jnp.int32),
        ],
        interpret=interpret,
    )(x[:, None].astype(jnp.float32), phi[:, None].astype(jnp.float32),
      d.astype(jnp.float32), res.T.astype(jnp.float32))
