"""Progressive filling — vectorized JAX engine.

The reference engine (:mod:`repro.core.filling`) is numpy and exact; this one
is jit-compiled, runs entirely under ``jax.lax`` control flow, and vmaps over
trials (for the Monte-Carlo RRR studies) or over *scheduling epochs* in the
fleet-scale cluster layer, where N (jobs) x J (pod slices) is large enough
that scoring is a real compute kernel (see ``repro.kernels.psdsf_score`` for
the fused Pallas version of the inner score/argmin).

Criterion scores come from :mod:`repro.core.criteria` with ``xp=jax.numpy``
— the SAME formulas the numpy reference and the online allocator use; this
module owns only the lax control flow (while-loop, RRR permutation state,
masked argmin).  The deterministic (``tie="low"``) pooled path delegates to
the shared device-resident epoch loop
(:func:`repro.core.engine_jax.epoch_loop`) — one incremental-refresh
while-loop serves both progressive filling and the online allocator's fused
epochs; RRR, random-tie and best-fit keep the full-recompute body below
(RRR because it draws permutations in-loop rather than from a pre-drawn
stack).

Semantics match the reference engine:
  * one task granted per step;
  * RRR: servers visited in a per-round random permutation; the visited server
    grants to the feasible framework with the lowest criterion score;
  * pooled: all feasible (n, j) pairs compete (argmin over K for PS-DSF
    family; argmin over frameworks then low-index server for global criteria);
  * bestfit: framework first (global criterion), then best-fit server.

Tie-breaking: "low" (lexicographic argmin — matches numpy reference) or
"random" (uniform over the argmin set, via noise on a masked score).
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core import criteria

POL_RRR, POL_POOLED, POL_BESTFIT = 0, 1, 2
_POL = {"rrr": POL_RRR, "pooled": POL_POOLED, "bestfit": POL_BESTFIT}


class FillState(NamedTuple):
    x: jax.Array        # (N, J) int32 allocation
    key: jax.Array      # PRNG key
    perm: jax.Array     # (J,) int32 current round permutation (RRR)
    pos: jax.Array      # () int32 position within the round
    steps: jax.Array    # () int32


def _feasible(x, D, C, allowed):
    res = criteria.residual_capacities(x.astype(jnp.float32), D, C, xp=jnp)
    feas = jnp.all(D[:, None, :] <= res[None, :, :] + 1e-6, axis=-1)  # (N, J)
    if allowed is not None:
        feas = feas & allowed
    return feas


def _masked_argmin(scores, mask, key, random_tie: bool):
    """argmin over mask=True entries; random uniform over the argmin set."""
    s = jnp.where(mask, scores, jnp.inf)
    if random_tie:
        m = jnp.min(s)
        at_min = jnp.isclose(s, m, rtol=0.0, atol=1e-9) & mask
        noise = jax.random.uniform(key, s.shape)
        return jnp.argmax(at_min * (1.0 + noise))  # max noise among minima
    return jnp.argmin(s)


@functools.partial(
    jax.jit, static_argnames=("criterion", "policy", "lookahead", "tie",
                              "max_steps", "devices")
)
def progressive_fill_jax(
    D: jax.Array,            # (N, R) demands
    C: jax.Array,            # (J, R) capacities
    phi: jax.Array,          # (N,) weights
    key: jax.Array,
    *,
    criterion: str = "drf",
    policy: str = "rrr",
    lookahead: bool = False,
    tie: str = "low",
    max_steps: int = 4096,
    devices: int = 1,        # shard the delegated epoch over a device mesh
    x0: jax.Array | None = None,
    allowed: jax.Array | None = None,   # (N, J) bool placement constraints
) -> jax.Array:
    """Run progressive filling; returns the (N, J) int32 allocation.

    ``devices > 1`` delegates the deterministic pooled path to the
    device-mesh epoch (``engine_jax.epoch_loop_mesh`` — J must divide by
    the mesh size), parity-gated against the single-device loop (see the
    engine_jax module docstring); the legacy RRR/bestfit/random-tie bodies
    ignore it."""
    crit = criteria.get_criterion(criterion)
    pol = _POL[policy]
    random_tie = tie == "random"
    N, J = D.shape[0], C.shape[0]
    D = D.astype(jnp.float32)
    C = C.astype(jnp.float32)
    phi = phi.astype(jnp.float32)
    if allowed is not None:
        allowed = jnp.asarray(allowed, bool)

    x_init = jnp.zeros((N, J), jnp.int32) if x0 is None else x0.astype(jnp.int32)

    if tie == "low" and pol == POL_POOLED:
        # deterministic pooled select: reuse the device-resident epoch loop
        # (same incremental score/feasibility refresh the online allocator
        # fuses).  RRR stays on the legacy body below: it draws a fresh
        # permutation IN the loop whenever a round wraps, whereas the fused
        # loop consumes a pre-drawn stack — a fill-to-exhaustion tail can
        # wrap on nearly every grant, and inside jit there is no way to
        # grow the stack the way engine_jax.run_epoch replays on the host.
        from repro.core import engine_jax

        Xf = x_init.astype(jnp.float32)
        FREE = criteria.residual_capacities(Xf, D, C, xp=jnp)
        perms = jnp.arange(J, dtype=jnp.int32)[None, :]
        allowed_m = (jnp.ones((N, J), bool) if allowed is None else allowed)
        loop_args = (
            Xf, D, D, C, FREE, phi,
            jnp.full((N,), 3.0e38, jnp.float32),      # no wanted caps
            allowed_m, perms, jnp.zeros(J, jnp.int32),
            jnp.int32(0), jnp.int32(0),
            jnp.int32(J), jnp.int32(0), jnp.float32(1e-6))
        if devices > 1:
            _ns, _js, _cnt, x_fin, *_rest = engine_jax.epoch_loop_mesh(
                *loop_args, kind=crit.name, policy=policy,
                lookahead=lookahead, use_limit=False, max_steps=max_steps,
                devices=devices,
            )
        else:
            _ns, _js, _cnt, x_fin, *_rest = engine_jax.epoch_loop(
                *loop_args, kind=crit.name, policy=policy,
                lookahead=lookahead, use_limit=False, max_steps=max_steps,
            )
        return x_fin.astype(jnp.int32)

    key, pk = jax.random.split(key)
    state = FillState(
        x=x_init,
        key=key,
        perm=jax.random.permutation(pk, J),
        pos=jnp.int32(0),
        steps=jnp.int32(0),
    )

    def cond(st: FillState):
        return jnp.any(_feasible(st.x, D, C, allowed)) & (st.steps < max_steps)

    def body(st: FillState):
        feas = _feasible(st.x, D, C, allowed)
        sc = crit.matrix_scores(
            st.x, D, C, phi, lookahead=lookahead, xp=jnp, allowed=allowed
        )
        key, k1, k2, k3, k4 = jax.random.split(st.key, 5)

        if pol == POL_RRR:
            # rank of each server within the current round
            rank = jnp.zeros(J, jnp.int32).at[st.perm].set(jnp.arange(J, dtype=jnp.int32))
            server_ok = jnp.any(feas, axis=0)  # (J,)
            ahead = server_ok & (rank >= st.pos)
            # prefer servers later in this round; else wrap to a fresh permutation
            use_wrap = ~jnp.any(ahead)
            new_perm = jax.random.permutation(k1, J)
            new_rank = jnp.zeros(J, jnp.int32).at[new_perm].set(jnp.arange(J, dtype=jnp.int32))
            eff_rank = jnp.where(use_wrap, new_rank, rank)
            eff_mask = jnp.where(use_wrap, server_ok, ahead)
            j = _masked_argmin(eff_rank.astype(jnp.float32), eff_mask, k2, False)
            n = _masked_argmin(sc[:, j], feas[:, j], k3, random_tie)
            pos = eff_rank[j] + 1
            pos = jnp.where(pos >= J, 0, pos)
            # if we wrapped past the end, next round needs a fresh perm too;
            # approximate by re-permuting whenever pos returns to 0 (with its
            # OWN key: k1 already produced new_perm, so reusing it here would
            # replay the same server order on consecutive rounds)
            perm = jnp.where(use_wrap, new_perm, st.perm)
            perm = jnp.where(pos == 0, jax.random.permutation(k4, J), perm)
            return FillState(st.x.at[n, j].add(1), key, perm, pos, st.steps + 1)

        if pol == POL_POOLED:
            if crit.server_specific:
                flat = _masked_argmin(sc.ravel(), feas.ravel(), k2, random_tie)
                n, j = flat // J, flat % J
            else:
                n = _masked_argmin(sc[:, 0], jnp.any(feas, axis=1), k2, random_tie)
                j = _masked_argmin(jnp.arange(J, dtype=jnp.float32), feas[n], k3, False)
            return FillState(st.x.at[n, j].add(1), key, st.perm, st.pos, st.steps + 1)

        # POL_BESTFIT
        per_fw = jnp.min(jnp.where(feas, sc, jnp.inf), axis=1)
        n = _masked_argmin(per_fw, jnp.any(feas, axis=1), k2, random_tie)
        res = criteria.residual_capacities(st.x.astype(jnp.float32), D, C, xp=jnp)
        bf = criteria.bestfit_scores(res, D[n], metric="cosine", xp=jnp)
        j = _masked_argmin(bf, feas[n], k3, False)
        return FillState(st.x.at[n, j].add(1), key, st.perm, st.pos, st.steps + 1)

    final = jax.lax.while_loop(cond, body, state)
    return final.x


def fill_trials_jax(D, C, phi, keys, **kw):
    """vmap progressive filling over a batch of PRNG keys -> (T, N, J)."""
    fn = functools.partial(progressive_fill_jax, D, C, phi, **kw)
    return jax.vmap(fn)(keys)
