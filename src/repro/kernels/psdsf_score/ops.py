"""Public wrappers for the fused allocator kernels: pad to tile multiples,
run the Pallas kernel (interpret=True on CPU), reduce tile partials.

  * :func:`psdsf_argmin`    — fully fused score+feasibility+argmin over
    (frameworks x servers) from raw (x, phi, d, res) inputs;
  * :func:`masked_argmin1d` — masked argmin over a score vector (an RRR
    server visit, or DRF/TSF scores against row feasibility);
  * :func:`masked_argmin2d` — masked argmin over a maintained (N, J) score
    matrix (a pooled selection).

Only :func:`psdsf_argmin` has a caller (the per-grant kernel backend of
``repro.core.engine``); the fused device epoch reduces with jnp.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.psdsf_score.kernel import (
    BIG,
    masked_argmin1d_tiles,
    masked_argmin2d_tiles,
    psdsf_argmin_tiles,
)


def _pad_to(a, n, axis, value):
    pad = n - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def next_pow2(n: int, lo: int = 8) -> int:
    """Next power of two >= max(n, lo) — THE shape/tile rounding rule.

    Shared by these wrappers and by the device epoch engine
    (:mod:`repro.core.engine_jax`) so padded extents and tile sizes can
    never drift apart (the kernels require extent % tile == 0)."""
    return max(lo, 1 << (max(n, 1) - 1).bit_length())


def _block(n: int, b: int) -> int:
    """Effective tile size: pow2-clamped to the padded extent, >= 8."""
    return min(b, next_pow2(n))


def masked_argmin1d(s, ok, *, bn: int = 128, interpret: bool | None = None):
    """Masked argmin over a score vector.  s (N,), ok (N,) -> (val, i);
    i == -1 when no entry has ok True."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    N = s.shape[0]
    bn = _block(N, bn)
    Np = int(np.ceil(N / bn)) * bn
    sp = _pad_to(s.astype(jnp.float32), Np, 0, float(BIG))
    okp = _pad_to(ok.astype(jnp.int32), Np, 0, 0)
    mins, args = masked_argmin1d_tiles(sp, okp, bn=bn, interpret=interpret)
    k = jnp.argmin(mins)
    val = mins[k]
    i = args[k]
    bad = (val >= BIG) | (i >= N)
    return val, jnp.where(bad, -1, i).astype(jnp.int32)


def masked_argmin2d(s, feas, *, bn: int = 128, bj: int = 128,
                    interpret: bool | None = None):
    """Masked argmin over a score matrix.  s (N, J), feas (N, J) ->
    (val, n, j); n == -1 when no pair is feasible."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    N, J = s.shape
    bn = _block(N, bn)
    bj = _block(J, bj)
    Np = int(np.ceil(N / bn)) * bn
    Jp = int(np.ceil(J / bj)) * bj
    sp = _pad_to(_pad_to(s.astype(jnp.float32), Np, 0, float(BIG)),
                 Jp, 1, float(BIG))
    fp = _pad_to(_pad_to(feas.astype(jnp.int32), Np, 0, 0), Jp, 1, 0)
    mins, args = masked_argmin2d_tiles(sp, fp, bn=bn, bj=bj,
                                       interpret=interpret)
    k = jnp.argmin(mins.reshape(-1))
    val = mins.reshape(-1)[k]
    enc = args.reshape(-1)[k]
    n = enc // Jp
    j = enc % Jp
    bad = (val >= BIG) | (n >= N) | (j >= J)
    return (
        val,
        jnp.where(bad, -1, n).astype(jnp.int32),
        jnp.where(bad, -1, j).astype(jnp.int32),
    )


def psdsf_argmin(x, phi, d, res, *, bn: int = 128, bj: int = 128,
                 interpret: bool | None = None):
    """Fused feasibility-masked PS-DSF argmin over (frameworks x servers).

    x (N,), phi (N,), d (N, R), res (J, R) -> (min_value, n, j);
    n == -1 when no feasible pair exists.  Use residual capacities for
    rPS-DSF, full capacities for PS-DSF (the criterion difference is entirely
    in what you pass as `res`).
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    N, R = d.shape
    J = res.shape[0]
    bn = _block(N, bn)
    bj = _block(J, bj)
    Np = int(np.ceil(N / bn)) * bn
    Jp = int(np.ceil(J / bj)) * bj
    # padding rows: infeasible by construction (demand BIG, residual 0)
    xp = _pad_to(x.astype(jnp.float32), Np, 0, 1.0)
    pp = _pad_to(phi.astype(jnp.float32), Np, 0, 1.0)
    dp = _pad_to(d.astype(jnp.float32), Np, 0, float(BIG))
    rp = _pad_to(res.astype(jnp.float32), Jp, 0, 0.0)

    mins, args = psdsf_argmin_tiles(xp, pp, dp, rp, bn=bn, bj=bj,
                                    interpret=interpret)
    k = jnp.argmin(mins.reshape(-1))
    val = mins.reshape(-1)[k]
    enc = args.reshape(-1)[k]
    n = enc // Jp
    j = enc % Jp
    bad = (val >= BIG) | (n >= N) | (j >= J)
    return (
        val,
        jnp.where(bad, -1, n).astype(jnp.int32),
        jnp.where(bad, -1, j).astype(jnp.int32),
    )
