"""Device-resident allocation epochs: the whole select -> grant -> refresh
loop as ONE jitted ``lax.while_loop`` dispatch.

The numpy :class:`repro.core.engine.BatchedEpoch` already made epoch scoring
incremental, but its (opt-in) kernel backend still crossed the host<->device
boundary per grant: one kernel launch, one blocking ``int(n)`` readback and a
fresh upload of the score inputs for every single pick.  This module keeps
the ENTIRE epoch on device: loop state ``(X, tot, FREE, cap, scores,
feas-mask, used, RRR cursor)`` lives in device memory, each iteration selects
the next (framework, server) pair, applies the grant and restores score /
feasibility consistency with the same incremental formulas the numpy engine
uses (via :mod:`repro.core.criteria` with ``xp=jax.numpy``), and the grant
sequence ``(n_k, j_k)`` comes back in a single transfer when the loop ends.

Coverage: characterized mode, ``tie="low"``, every criterion (DRF / TSF /
PS-DSF / rPS-DSF) under the ``pooled`` and ``rrr`` server policies —
including phi != 1 priorities, placement constraints, ``per_agent_limit``
and mid-epoch exhaustion of ``wanted`` — and the global criteria (DRF,
TSF) under ``bestfit`` with the ``cosine`` metric (BF-DRF).  Oblivious
mode (inferred-demand drift), the other best-fit metrics and best-fit
after a server-specific criterion stay on the host paths; best-fit is
refused on the device mesh.

Best-fit on device
------------------
The framework is the pooled global select's (masked 1-D argmin of the
criterion over the rows with any feasible column).  The server is the
feasible column of that row whose free vector ``a`` (the loop's own
``FREE``) best matches the row's demand ``d``: the least ``1 - cos(a, d)``,
i.e. the greatest ``(a.d)**2 / (a.a)``.  f32 cannot order these: on the
Borg cell's integer units distinct scores lie as close as 1.9e-10, inside
the f32 tie band, and the TPU's f32 divide and sqrt are not correctly
rounded.  So the key is compared exactly, in int32, by cross
multiplication — ``u1**2 * v2`` against ``u2**2 * v1`` with ``u = a.d`` and
``v = a.a``, each product held in three 15-bit limbs — inside one variadic
reduction that breaks equal keys (collinear free vectors) toward the
lowest index, as the float64 host policy does.  It needs integer ``FREE``
and demands with ``u < 2**15`` and ``v < 2**30``; the host checks that
before the dispatch and raises where it does not hold
(:func:`check_bestfit_inputs`).  On the Borg grid (every executor demand
against every fitting free vector in [0, 256]^2) the exact order is the
float64 order (``tests/test_engine_parity.py``).

Randomized round-robin on device
--------------------------------
RRR consumes server permutations.  The host wrapper pre-draws them from the
SAME numpy Generator stream the numpy ``RRRPolicy`` would consume (the
policy's only rng use under ``tie="low"`` is ``rng.permutation(J)``), so a
single epoch's grant sequence is bit-for-bit comparable with the numpy
engine.  The wrapper draws a fixed budget of permutations up front (the
device loop cannot stop mid-epoch to ask for more), so ACROSS epochs the
allocator rng advances further than the numpy path would — fused-vs-numpy
stream parity is per-epoch, fused-vs-fused is exact.

Tie-break semantics vs the numpy path
-------------------------------------
The numpy engine scores in float64 and treats scores within ``atol=1e-12``
as tied, breaking ties toward the lowest (framework, server) index.  The
device loop scores in float32, so it reproduces that rule with a scaled
tolerance (``atol=1e-9 + 1e-6 * |min|``, a few f32 ULPs): exact rational
ties (equal-score frameworks, the all-zeros epoch start) resolve to the
same lowest index even when the two f32 score computations round
differently.  The residual boundary: scores whose TRUE relative gap is
below ~1e-6 are merged into a tie (numpy would order them), and above
fleet-scale totals f32 rounding may reorder near-equal scores outright —
bit-parity with the numpy engine is guaranteed on the parity suite's
binary-exact instances and small totals, and is best-effort beyond that.
Feasibility uses the numpy path's absolute ``eps`` against f32 ``FREE``
arithmetic, which is exact for the paper's quantized (quarter-multiple)
demand vectors; for non-dyadic demands the online allocator re-validates
every fused grant in f64 before applying it.

Shape bucketing: the host wrapper pads N and J up to powers of two (>= 8)
and ``max_steps`` to a power-of-two bucket, so growing a fleet within its
padded tile reuses the cached jit executable — a trace-count regression
test pins this.  On non-CPU backends the mutated buffers are donated
(``donate_argnums``) so XLA reuses the allocation across epochs; the RRR
grow-and-replay path re-uploads the segment-start state from a host-side
snapshot, so donation is safe under RRR too (the pre-drawn permutation
stack is never in the donated set).

Staging by content: the two (N, J) inputs go to the device at the size of
what they hold and are expanded there, bit-for-bit the padded f32 X and
padded bool ``allowed`` a dense upload would give.  X goes as the flat
(Np, Jp) index and f32 value of each non-zero, in fixed chunks of
:data:`STAGE_CHUNK` scattered into a zero buffer by one small program per
padded shape; ``allowed`` goes packed to bits and is unpacked on the
device.  The cost follows the support, which is tiny for a fleet's
allocation matrix; a fully dense X would send 8 bytes per cell, not 4.

Asynchronous epochs and commit-point semantics
----------------------------------------------
:func:`run_epoch_async` issues the SAME host prep + device dispatch as
:func:`run_epoch` but returns an :class:`EpochHandle` instead of blocking on
the grant-sequence readback — JAX's async dispatch returns as soon as the
while-loop is enqueued, so the host can stage the NEXT epoch's inputs (see
``OnlineAllocator.begin_epoch``'s double-buffered views) or pipeline epochs
of independent allocators while the device runs.  ``EpochHandle.result()``
is the COMMIT POINT: it blocks, drives any chained dispatches (overlong
epochs) and RRR grow-and-replay rounds, and returns the flat grant
sequence.  ``run_epoch`` is literally ``run_epoch_async(...).result()``, so
async-vs-sync grant sequences are bit-for-bit identical by construction.
The RRR permutation pre-draw consumes the allocator rng INSIDE
``run_epoch_async`` — at dispatch, not at commit — so interleaving
begin/commit pairs of DIFFERENT allocators cannot reorder rng streams.
The one exception is the rare grow-and-replay top-up, which draws at
``result()`` when the pre-drawn budget proves too small; it stays
correctly sequenced because a single allocator permits only one in-flight
epoch at a time (``OnlineAllocator.begin_epoch`` refuses overlap).  The
cross-epoch caveat above (the fused path drawing a fixed permutation
budget up front) applies to async epochs unchanged.

Preemption and the async protocol: the epoch-level preemption pass
(:mod:`repro.core.preemption`) runs inside ``begin_epoch`` BEFORE the
frozen ``epoch_view`` snapshot is taken and the dispatch issued, so the
device loop always scores the post-revocation state and the
``mutation_count`` staleness guard is armed after the pass — begin/commit
semantics are unchanged.  While an epoch is in flight, revocations are
REFUSED (``OnlineAllocator.revoke_executor`` raises; they are never
deferred), which is what keeps a dispatched epoch's inputs authoritative.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import criteria, tracing

tracing.watch_compiles()

# plain python scalars: this module may be imported lazily while another
# jit trace is active, so module level must not create jax values.
_BIG = 3.0e38
_IBIG = np.int32(2**31 - 1)

#: incremented every time the epoch loop is (re)traced — the no-recompilation
#: regression test asserts this stays flat across same-bucket epochs.
TRACE_COUNT = 0
#: incremented every time the MESH epoch loop is (re)traced — at most one
#: trace per (shape bucket, mesh size, static config), regression-pinned.
MESH_TRACE_COUNT = 0
#: incremented once per device dispatch by :func:`run_epoch` — the
#: one-dispatch-per-epoch acceptance test reads this.
DISPATCH_COUNT = 0

#: chaos hook (:mod:`repro.core.faults`): when set, called with no args
#: before EVERY fused dispatch — including chained grow-and-replay
#: segments — so a test can simulate an XLA/device failure at any dispatch
#: boundary by raising.  None in production.
fault_hook = None

COVERED_CRITERIA = ("drf", "tsf", "psdsf", "rpsdsf")
COVERED_POLICIES = ("pooled", "rrr", "bestfit")
#: best-fit on device: the criteria that score a framework, not a pair, and
#: the one metric whose order the exact key reproduces
BESTFIT_CRITERIA = ("drf", "tsf")
BESTFIT_METRICS = ("cosine",)
#: the exact best-fit key's bounds (exclusive) on ``u = a.d`` and
#: ``v = a.a``: ``u**2`` and ``v`` below 2**30 keep every limb product and
#: carry of :func:`_wide_mul` inside int32
BESTFIT_DOT_LIMIT = 2**15
BESTFIT_NORM_LIMIT = 2**30


def supports(criterion, policy: str, mode: str, tie: str, *,
             bf_metric: str = "cosine", devices: int = 1) -> bool:
    """Can the fused device epoch serve this configuration?"""
    try:
        name = criteria.get_criterion(criterion).name
    except ValueError:
        return False
    if policy == "bestfit" and (
            name not in BESTFIT_CRITERIA or bf_metric not in BESTFIT_METRICS
            or devices > 1):
        return False
    return (name in COVERED_CRITERIA and policy in COVERED_POLICIES
            and mode == "characterized" and tie == "low")


def check_bestfit_inputs(FREE, TD, wants, allowed, eps: float = 1e-9) -> None:
    """Refuse a best-fit epoch the exact device key cannot order: free
    vectors or demands that are not whole numbers, negative demands, or
    sizes past :data:`BESTFIT_DOT_LIMIT` / :data:`BESTFIT_NORM_LIMIT`.
    Only what the key can meet is checked: the demands of the rows that
    want executors, and the free vectors of the columns one of them may use
    and fits at the epoch's start (free resources only fall within an
    epoch, so no other column ever becomes feasible)."""
    wants = np.asarray(wants, bool)
    if not wants.any():
        return
    TD = np.asarray(TD, np.float64)[wants]
    FREE = np.asarray(FREE, np.float64)
    cols = (np.asarray(allowed, bool)[wants].any(axis=0)
            & (FREE + eps >= TD.min(axis=0)).all(axis=1))
    FREE = FREE[cols]
    if not (np.array_equal(FREE, np.round(FREE))
            and np.array_equal(TD, np.round(TD))):
        raise ValueError(
            "best-fit on the device needs free resources and demands in "
            "whole units: its exact key compares integers (give the "
            "capacities in units that make them whole, or run the epoch "
            "on the host)")
    if (TD < 0).any():
        raise ValueError("best-fit on the device needs demands >= 0")
    if not len(FREE):
        return
    dot = float(np.abs(FREE).max(axis=0) @ TD.max(axis=0))
    norm = float((FREE * FREE).sum(axis=1).max())
    if dot >= BESTFIT_DOT_LIMIT or norm >= BESTFIT_NORM_LIMIT:
        raise ValueError(
            f"best-fit on the device orders free.demand < "
            f"{BESTFIT_DOT_LIMIT} and free.free < {BESTFIT_NORM_LIMIT} "
            f"exactly; this epoch reaches {dot:.0f} and {norm:.0f}")


_LIMB = 15
_LIMB_MASK = (1 << _LIMB) - 1


def _wide_mul(p, q):
    """``p * q`` for int32 ``p, q`` in [0, 2**30), exactly, as three 15-bit
    limbs ``(hi, mid, lo)``: every partial product and carry stays below
    2**31."""
    ph, pl = p >> _LIMB, p & _LIMB_MASK
    qh, ql = q >> _LIMB, q & _LIMB_MASK
    lo = pl * ql
    mid = ph * ql + pl * qh + (lo >> _LIMB)
    hi = ph * qh + (mid >> _LIMB)
    return hi, mid & _LIMB_MASK, lo & _LIMB_MASK


def _wide_gt(a, b):
    return (a[0] > b[0]) | ((a[0] == b[0]) & (
        (a[1] > b[1]) | ((a[1] == b[1]) & (a[2] > b[2]))))


def _wide_eq(a, b):
    return (a[0] == b[0]) & (a[1] == b[1]) & (a[2] == b[2])


def bestfit_keys(free, d):
    """``(u**2, max(v, 1))`` per column, ``u = free.d``, ``v = free.free``:
    column ``j`` fits better than ``k`` iff ``u_j**2 v_k > u_k**2 v_j``.
    int32 ``free`` (J, R) and ``d`` (R,) in whole units, inside the bounds
    :func:`check_bestfit_inputs` holds.  ``v`` is 0 only for a zero free
    vector, which fits only a zero demand, whose ``u`` is 0 everywhere."""
    u = jnp.sum(free * d[None, :], axis=1)
    v = jnp.sum(free * free, axis=1)
    return u * u, jnp.maximum(v, 1)


def _bestfit_better(x, y):
    """The better of two ``(ok, u2, v, idx)`` candidates: a feasible one,
    then the greater ``u2 / v`` exactly, then the lower index."""
    okx, ux, vx, ix = x
    oky, uy, vy, iy = y
    l, r = _wide_mul(ux, vy), _wide_mul(uy, vx)
    take = (okx & ~oky) | ((okx == oky) & (
        _wide_gt(l, r) | (_wide_eq(l, r) & (ix < iy))))
    return tuple(jnp.where(take, a, b) for a, b in zip(x, y))


def bestfit_argmin(u2, v, ok):
    """The feasible column of least cosine best-fit score, equal keys to
    the lowest index.  One variadic reduction, integer arithmetic only."""
    idx = jnp.arange(u2.shape[0], dtype=jnp.int32)
    init = (jnp.bool_(False), jnp.int32(0), jnp.int32(1), _IBIG)
    return jax.lax.reduce((ok, u2, v, idx), init, _bestfit_better, (0,))[3]


def _tie_threshold(m, rtol=1e-6, atol=1e-9):
    """The largest masked score that ties with the minimum ``m``."""
    return m + (atol + rtol * jnp.abs(m))


def _argmin_tie_low(s, mask):
    """First index among near-minimal masked entries (numpy tie="low").

    The tolerance covers a few f32 ULPs of rounding (~3.6e-7 relative for
    the 2-3 flop score formulas), so mathematically-equal scores computed
    through different factorizations still resolve to the numpy engine's
    lowest-index winner; scores whose TRUE relative gap is below rtol are
    merged too — that is the residual f32 parity boundary documented in
    the module docstring."""
    masked = jnp.where(mask, s.astype(jnp.float32), _BIG)
    idx = jnp.arange(masked.shape[0], dtype=jnp.int32)
    return jnp.min(jnp.where(masked <= _tie_threshold(jnp.min(masked)), idx,
                             _IBIG))


def block_width(J: int) -> int:
    """Column width of one block of the pooled 2-D select's minima: about
    sqrt(J), a power of two of at least 128 lanes (128 at J = 16384).  A
    step rescans one block of every row and every block of one row, so
    this width keeps both near sqrt(N J) entries."""
    return max(128, 1 << (((J - 1).bit_length() + 1) // 2))


class _EpochState(NamedTuple):
    X: jax.Array        # (N, J) f32 allocation counts
    tot: jax.Array      # (N,) f32
    FREE: jax.Array     # (J, R) f32
    cap: jax.Array      # (J, R) f32 residuals (rpsdsf) or (1, 1) dummy
    dom: jax.Array      # (N, J) f32 dominant shares (psdsf family) or (1, 1)
    s: jax.Array        # (N,) f32 criterion scores; psdsf family: the row
    #                     factor (tot + la) / phi of pair scores s[n] dom[n, j]
    feas: jax.Array     # (N, J) bool, exact in the rows that still want
    used: jax.Array     # (J,) i32 grants per server this epoch
    fcnt: jax.Array     # (N,) i32 feasible columns per row
    ccnt: Optional[jax.Array]   # (J,) i32 feasible rows per column (rrr)
    bmin: Optional[jax.Array]   # (J/B, N) f32 least feasible dominant share
    #                             per block and row (pooled psdsf family)
    pidx: jax.Array     # () i32 RRR permutation cursor
    pos: jax.Array      # () i32 RRR position within the round
    count: jax.Array    # () i32 grants so far
    ns: jax.Array       # (max_steps,) i32 grant sequence (frameworks)
    js: jax.Array       # (max_steps,) i32 grant sequence (servers)


def epoch_loop(X, D, TD, C, FREE, phi, wanted, allowed, perms, used,
               pidx0, pos0, j_real, limit, eps, *, kind: str, policy: str,
               lookahead: bool, use_limit: bool, max_steps: int):
    """Traceable core: run one allocation epoch entirely under lax control
    flow.  Returns ``(ns, js, count, X, tot, FREE, used, pidx, pos,
    steps)``, ``steps`` the loop's iteration count.

    All array arguments may be padded; padded frameworks must carry
    ``wanted == 0`` / ``allowed == False`` and padded servers ``FREE == 0``
    so they are infeasible by construction.  ``j_real`` is the number of
    REAL servers (RRR round length); ``perms`` is a (K, J) stack of server
    permutations consumed by RRR starting at row ``pidx0`` / position
    ``pos0`` (rows beyond the budget repeat the last — the host wrapper
    detects that from the returned ``pidx`` and re-runs with a bigger
    budget, see :func:`run_epoch`).

    A grant at (n, j) changes the (N, J) state in column j only, and row
    n's total, so no step makes a pass over the whole matrix: every
    reduction a select needs is carried as an exact partial updated from
    that column and row.  ``fcnt`` counts each row's feasible columns (the
    DRF/TSF row select and the loop's liveness), ``ccnt`` each column's
    feasible rows (RRR).  A row that stops wanting is not cleared in
    ``feas``; its counts drop to zero and the selects ignore it.  Under
    PS-DSF / rPS-DSF a pair's score is ``s[n] * dom[n, j]``, computed where
    it is read (the same f32 product the scores were stored as), so row n's
    refresh is one scalar; ``bmin`` holds each row's least feasible share
    over each block of :func:`block_width` columns, and a block's least
    score is ``s[n]`` times it, since rounding is monotone and ``s >= 0``.
    The pooled pick is then the first row-major (row, block) whose least
    score ties the global minimum, and the first tying column inside that
    block: the same first flat index as :func:`_argmin_tie_low` over the
    whole matrix, since an f32 min is exact and the flat order is (row,
    block, column).
    """
    global TRACE_COUNT
    TRACE_COUNT += 1
    f32 = jnp.float32
    X = X.astype(f32)
    D = D.astype(f32)
    TD = TD.astype(f32)
    C = C.astype(f32)
    FREE = FREE.astype(f32)
    phi = phi.astype(f32)
    wanted = wanted.astype(f32)
    N, J = X.shape
    la = f32(1.0 if lookahead else 0.0)
    tot = jnp.sum(X, axis=1)
    server_specific = kind in ("psdsf", "rpsdsf")
    if policy == "bestfit":
        # whole units (checked on the host): the exact best-fit key's input
        TDi = TD.astype(jnp.int32)

    # -- X-independent score pieces (computed once per dispatch) ------------
    if kind == "drf":
        unit = criteria.drf_dominant(D, C, xp=jnp)            # (N,)
        s0 = (tot + la) * unit / phi
        cap0 = jnp.zeros((1, 1), f32)
        dom0 = jnp.zeros((1, 1), f32)
    elif kind == "tsf":
        monopoly = criteria.tsf_monopoly(D, C, allowed=allowed, xp=jnp)
        denom = phi * jnp.maximum(monopoly, 1e-30)            # (N,)
        s0 = (tot + la) / denom
        cap0 = jnp.zeros((1, 1), f32)
        dom0 = jnp.zeros((1, 1), f32)
    elif kind == "psdsf":
        dom0 = criteria.virtual_dominant(D, C, xp=jnp)        # (N, J)
        s0 = (tot + la) / phi
        cap0 = jnp.zeros((1, 1), f32)
    elif kind == "rpsdsf":
        cap0 = criteria.residual_capacities(X, D, C, xp=jnp)  # (J, R)
        dom0 = criteria.virtual_dominant(D, cap0, xp=jnp)     # (N, J)
        s0 = (tot + la) / phi
    else:
        raise ValueError(f"unsupported criterion kind {kind!r}")

    feas0 = criteria.feasible_mask(TD, FREE, allowed, tot < wanted,
                                   eps=eps, xp=jnp)
    if use_limit:
        feas0 = feas0 & (used < limit)[None, :]

    i32 = jnp.int32
    arangeJ = jnp.arange(J, dtype=i32)
    blocks = policy == "pooled" and server_specific
    if blocks:
        # block b covers columns [b B, b B + B); a step reads a block as the
        # W = min(B, J) columns from `start`, which only the last block of
        # a J that B does not divide moves left of b B
        B = block_width(J)
        nb = -(-J // B)
        W = min(B, J)
        ragged = J > B and J % B != 0

        def _block_cols(b):
            start = jnp.clip(b * B, 0, J - W)
            cols = start + jnp.arange(W, dtype=i32)
            return start, cols, (cols >= b * B) if ragged else True

        def _block_of_rows(dom, feas, wants, b):
            """(N,) least dominant share of every row over its feasible
            columns of block b (``_BIG`` for none, or a row that stops
            wanting)."""
            start, _cols, ok = _block_cols(b)
            db = jax.lax.dynamic_slice_in_dim(dom, start, W, axis=1)
            fb = jax.lax.dynamic_slice_in_dim(feas, start, W, axis=1)
            return jnp.min(jnp.where(fb & wants[:, None] & ok, db, _BIG),
                           axis=1)

    def _select(st: _EpochState):
        if policy == "pooled":
            if server_specific:
                # a block's least score is its least share times the row's
                # factor: rounding is monotone and the factor >= 0
                masked = jnp.where(st.bmin < _BIG, st.s[None, :] * st.bmin,
                                   _BIG)
                thr = _tie_threshold(jnp.min(masked))
                # row-major (row, block) key of each block minimum
                key = (jax.lax.broadcasted_iota(i32, masked.shape, 1) * nb
                       + jax.lax.broadcasted_iota(i32, masked.shape, 0))
                first = jnp.min(jnp.where(masked <= thr, key, _IBIG))
                n, b = first // nb, first % nb
                start, cols, ok = _block_cols(b)
                sb = st.s[n] * jax.lax.dynamic_slice(st.dom, (n, start),
                                                     (1, W))[0]
                fb = jax.lax.dynamic_slice(st.feas, (n, start), (1, W))[0]
                hit = fb & (sb <= thr) & ok
                return n, jnp.min(jnp.where(hit, cols, _IBIG)), st.pidx, \
                    st.pos
            n = _argmin_tie_low(st.s, st.fcnt > 0)
            j = jnp.min(jnp.where(st.feas[n], arangeJ, _IBIG))
            return n, j, st.pidx, st.pos
        if policy == "bestfit":
            n = _argmin_tie_low(st.s, st.fcnt > 0)
            u2, v = bestfit_keys(st.FREE.astype(jnp.int32), TDi[n])
            return n, bestfit_argmin(u2, v, st.feas[n]), st.pidx, st.pos
        # rrr: visit the first feasible server at-or-after `pos` in the
        # current round's permutation; wrap to a fresh permutation when the
        # remainder of the round has nothing feasible.  A grant at the LAST
        # position of a round also consumes a fresh permutation — both rules
        # mirror the numpy RRRPolicy's rng consumption exactly.
        K = perms.shape[0]
        perm = perms[jnp.minimum(st.pidx, K - 1)]
        rank = jnp.zeros(J, jnp.int32).at[perm].set(arangeJ)
        server_ok = st.ccnt > 0
        ahead = server_ok & (rank >= st.pos)
        wrap = ~jnp.any(ahead)
        perm2 = perms[jnp.minimum(st.pidx + 1, K - 1)]
        rank2 = jnp.zeros(J, jnp.int32).at[perm2].set(arangeJ)
        eff_rank = jnp.where(wrap, rank2, rank)
        eff_ok = jnp.where(wrap, server_ok, ahead)
        j = jnp.argmin(jnp.where(eff_ok, eff_rank, _IBIG))
        col = st.s * st.dom[:, j] if server_specific else st.s
        n = _argmin_tie_low(col, st.feas[:, j] & (st.tot < wanted))
        krank = eff_rank[j]
        last = krank == j_real - 1
        pidx = st.pidx + wrap.astype(jnp.int32) + last.astype(jnp.int32)
        pos = jnp.where(last, 0, krank + 1)
        return n, j, pidx, pos

    def _refresh(st: _EpochState, n, j):
        """Post-grant score refresh — the incremental formulas of the numpy
        BatchedEpoch, row n (and column j under rPS-DSF) only."""
        xt_n = st.tot[n] + la
        if kind == "drf":
            return st.cap, st.dom, st.s.at[n].set(xt_n * unit[n] / phi[n])
        if kind == "tsf":
            return st.cap, st.dom, st.s.at[n].set(xt_n / denom[n])
        s = st.s.at[n].set(xt_n / phi[n])
        if kind == "psdsf":
            return st.cap, st.dom, s
        # rpsdsf: only server j's residual changed -> refresh column j
        cap_j = C[j] - st.X[:, j] @ D                       # (R,)
        cap = st.cap.at[j].set(cap_j)
        dom_col = criteria.virtual_dominant(D, cap_j[None, :], xp=jnp)[:, 0]
        return cap, st.dom.at[:, j].set(dom_col), s

    def cond(st: _EpochState):
        return jnp.any(st.fcnt > 0) & (st.count < max_steps)

    def body(st: _EpochState):
        n, j, pidx, pos = _select(st)
        bundle = TD[n]                                      # (R,)
        X2 = st.X.at[n, j].add(1.0)
        tot2 = st.tot.at[n].add(1.0)
        FREE2 = st.FREE.at[j].add(-bundle)
        used2 = st.used.at[j].add(1)
        st2 = st._replace(X=X2, tot=tot2, FREE=FREE2, used=used2)
        # feasibility: column j saw FREE change.  A row that stops wanting
        # is not cleared: a row's entries count only while it wants.
        wants = tot2 < wanted
        wants_n = wants[n]
        col = wants & allowed[:, j] & jnp.all(TD <= FREE2[j][None, :] + eps,
                                              axis=1)
        if use_limit:
            col = col & (used2[j] < limit)
        old_col = st.feas[:, j] & (st.tot < wanted)
        feas = st.feas.at[:, j].set(col)
        cap, dom, s = _refresh(st2, n, j)
        # the partials, from column j and row n alone (col[n] is already
        # False when row n stops wanting, so nothing is counted twice)
        fcnt = st.fcnt + col.astype(i32) - old_col.astype(i32)
        fcnt = fcnt.at[n].set(jnp.where(wants_n, fcnt[n], 0))
        ccnt = bmin = None
        if policy == "rrr":
            ccnt = (st.ccnt - (feas[n] & ~wants_n).astype(i32)).at[j].set(
                jnp.sum(col, dtype=i32))
        if blocks:
            bj = j // B
            bmin = st.bmin.at[bj].set(_block_of_rows(dom, feas, wants, bj))
            bmin = bmin.at[:, n].set(jnp.where(wants_n, bmin[:, n], _BIG))
        return _EpochState(
            X=X2, tot=tot2, FREE=FREE2, cap=cap, dom=dom, s=s, feas=feas,
            used=used2, fcnt=fcnt, ccnt=ccnt, bmin=bmin, pidx=pidx, pos=pos,
            count=st.count + 1,
            ns=st.ns.at[st.count].set(n.astype(jnp.int32)),
            js=st.js.at[st.count].set(j.astype(jnp.int32)),
        )

    if blocks:
        masked0 = jnp.pad(jnp.where(feas0, dom0, _BIG),
                          ((0, 0), (0, nb * B - J)), constant_values=_BIG)
        bmin0 = jnp.min(masked0.reshape(N, nb, B), axis=2).T
    init = _EpochState(
        X=X, tot=tot, FREE=FREE, cap=cap0, dom=dom0, s=s0, feas=feas0,
        used=used.astype(i32), fcnt=jnp.sum(feas0, axis=1, dtype=i32),
        ccnt=(jnp.sum(feas0, axis=0, dtype=i32) if policy == "rrr"
              else None),
        bmin=bmin0 if blocks else None,
        pidx=jnp.asarray(pidx0, i32), pos=jnp.asarray(pos0, i32),
        count=i32(0),
        ns=jnp.full((max_steps,), -1, i32),
        js=jnp.full((max_steps,), -1, i32),
    )
    fin = jax.lax.while_loop(cond, body, init)
    # one grant per step, so the steps are the grants
    return (fin.ns, fin.js, fin.count, fin.X, fin.tot, fin.FREE, fin.used,
            fin.pidx, fin.pos, fin.count)


class _MeshState(NamedTuple):
    """Per-device block state of the mesh epoch (under ``shard_map``)."""
    X: jax.Array        # (N, Js) f32 local allocation block
    tot: jax.Array      # (N,) f32 replicated
    FREE: jax.Array     # (Js, R) f32 local
    cap: jax.Array      # (Js, R) f32 local residuals (rpsdsf) or zeros
    dom: jax.Array      # (N, Js) f32 local dominant shares or zeros
    s: jax.Array        # (N,) replicated or (N, Js) local criterion scores
    feas: jax.Array     # (N, Js) bool local
    used: jax.Array     # (Js,) i32 local
    fcnt: jax.Array     # (N,) i32 feasible-per-row counts of THIS block
    ccnt: jax.Array     # (Js,) i32 feasible-per-column counts
    rmin: jax.Array     # (N,) f32 per-row masked block minima (pooled 2-D)
    rarg: jax.Array     # (N,) i32 per-row argmin column, local (pooled 2-D)
    pidx: jax.Array     # () i32 RRR permutation cursor (replicated)
    pos: jax.Array      # () i32 RRR position within the round (replicated)
    count: jax.Array    # () i32 grants so far (replicated)
    alive: jax.Array    # () bool last select found a grant (replicated)
    ns: jax.Array       # (max_steps,) i32 grant sequence (replicated)
    js: jax.Array       # (max_steps,) i32


def epoch_loop_mesh(X, D, TD, C, FREE, phi, wanted, allowed, perms, used,
                    pidx0, pos0, j_real, limit, eps, *, kind: str,
                    policy: str, lookahead: bool, use_limit: bool,
                    max_steps: int, devices: int):
    """Multi-device fused epoch: the server (agent) axis sharded over a 1-D
    ``"agents"`` mesh of ``devices`` devices via ``shard_map``.  Same
    contract as :func:`epoch_loop` (padded inputs, identical grant
    sequences) — each device holds one block of the server axis.

    Each device keeps its ``(N, J/devices)`` score / feasibility / residual
    block resident for the whole epoch; per grant iteration only scalar and
    (N,)-sized partials cross the interconnect (``lax.pmin`` of per-block
    minima and first-within-tolerance keys, ``lax.psum`` of feasibility
    counts and the winner's score column).  The two-pass tolerance
    reduction applies exactly the same f32 comparisons as
    :func:`_argmin_tie_low` — f32 min is associative, the global threshold
    is computed from the global min, and per-block first-qualifying keys
    reduce by the global flat key — so grant sequences are bit-for-bit the
    single-device sequences (parity-gated).

    On top of the placement, each block maintains its select partials
    INCREMENTALLY as per-row masked minima (``rmin``/``rarg``): epoch
    score/feasibility updates are increase-only (totals and used only
    grow, residual FREE only shrinks, so masked scores never decrease),
    which means a grant at (n, j) can only invalidate cached row n (every
    shard re-scans that one row, O(J/devices)) and — on the owning shard —
    rows whose cached minimum sat in column j AND strictly increased; only
    then does the owner re-scan its block (``lax.cond``).  The value test
    matters: on the cold-start zero-score plateau the granted column's
    entries keep their tied value, so no shard re-scans at all.  The
    global select is then one ``pmin`` over the (N,) row minima plus one
    scalar first-qualifying-column reduce — two collectives per grant, and
    per-grant compute drops from two full matrix passes to O(N +
    J/devices).  The same bookkeeping replaces the full-matrix
    ``any(feas)`` loop guard (the select's own found flag drives liveness;
    the final probe iteration is a no-op by predication) and RRR's
    per-server feasibility scan with running counts.
    """
    if policy not in ("pooled", "rrr"):
        raise ValueError(f"the mesh epoch does not cover {policy!r}")
    global MESH_TRACE_COUNT
    MESH_TRACE_COUNT += 1
    from jax.sharding import PartitionSpec
    from repro.launch.mesh import make_agent_mesh

    f32 = jnp.float32
    i32 = jnp.int32
    X = X.astype(f32)
    D = D.astype(f32)
    TD = TD.astype(f32)
    C = C.astype(f32)
    FREE = FREE.astype(f32)
    phi = phi.astype(f32)
    wanted = wanted.astype(f32)
    N, J = X.shape
    R = C.shape[1]
    K = int(devices)
    if J % K:
        raise ValueError(f"padded J={J} not divisible by mesh size {K}")
    Js = J // K
    la = f32(1.0 if lookahead else 0.0)
    tot = jnp.sum(X, axis=1)
    server_specific = kind in ("psdsf", "rpsdsf")

    # -- global f32 score init: IDENTICAL reduction order to epoch_loop ----
    # (J-axis reductions like the DRF capacity total or the TSF monopoly
    # sum must NOT be computed per-shard + psum'd — that would reorder the
    # f32 sums; they are computed on the global arrays here and enter the
    # mesh replicated / pre-sharded.)
    if kind == "drf":
        aux = criteria.drf_dominant(D, C, xp=jnp)             # (N,)
        s0 = (tot + la) * aux / phi
    elif kind == "tsf":
        monopoly = criteria.tsf_monopoly(D, C, allowed=allowed, xp=jnp)
        aux = phi * jnp.maximum(monopoly, 1e-30)              # (N,)
        s0 = (tot + la) / aux
    elif kind == "psdsf":
        aux = jnp.zeros((N,), f32)
        dom0 = criteria.virtual_dominant(D, C, xp=jnp)        # (N, J)
        s0 = ((tot + la) / phi)[:, None] * dom0
    elif kind == "rpsdsf":
        aux = jnp.zeros((N,), f32)
        cap0 = criteria.residual_capacities(X, D, C, xp=jnp)  # (J, R)
        dom0 = criteria.virtual_dominant(D, cap0, xp=jnp)     # (N, J)
        s0 = ((tot + la) / phi)[:, None] * dom0
    else:
        raise ValueError(f"unsupported criterion kind {kind!r}")
    if kind != "rpsdsf":
        cap0 = jnp.zeros((J, R), f32)
    if not server_specific:
        dom0 = jnp.zeros((N, J), f32)

    feas0 = criteria.feasible_mask(TD, FREE, allowed, tot < wanted,
                                   eps=eps, xp=jnp)
    if use_limit:
        feas0 = feas0 & (used < limit)[None, :]

    rtol, atol = f32(1e-6), f32(1e-9)
    arangeN = jnp.arange(N, dtype=i32)
    arangeJs = jnp.arange(Js, dtype=i32)
    arangeJ = jnp.arange(J, dtype=i32)

    def shard_body(Xl, FREEl, capl, doml, sl, feasl, allowedl, Cl, usedl,
                   D, TD, phi, wanted, perms, tot, aux, pidx0, pos0,
                   j_real, limit, eps):
        ax = jax.lax.axis_index("agents").astype(i32)
        offs = ax * Js

        def gmin(x):
            return jax.lax.pmin(x, "agents")

        def gsum(x):
            return jax.lax.psum(x, "agents")

        def gany(x):
            return jax.lax.pmax(x.astype(i32), "agents") > 0

        def _row_scan(s, feas):
            """Exact per-row masked block minima + one attaining column."""
            masked = jnp.where(feas, s, _BIG)
            return (jnp.min(masked, axis=1),
                    jnp.argmin(masked, axis=1).astype(i32))

        def _select(st: _MeshState):
            if policy == "pooled" and server_specific:
                # (N,) elementwise pmin of exact per-block row minima IS
                # the global per-row minimum (f32 min is associative), so
                # the global threshold and the first-qualifying row match
                # _argmin_tie_low on the full matrix bit-for-bit; a row
                # holds a qualifying entry iff its row min qualifies.
                grmin = gmin(st.rmin)
                m = jnp.min(grmin)
                found = m < f32(_BIG)
                tol = atol + rtol * jnp.abs(m)
                n = jnp.min(jnp.where(grmin <= m + tol, arangeN, _IBIG))
                n = jnp.clip(n, 0, N - 1)
                row = jnp.where(st.feas[n], st.s[n], _BIG)     # (Js,)
                j = gmin(jnp.min(jnp.where(row <= m + tol,
                                           offs + arangeJs, _IBIG)))
                return n, j, st.pidx, st.pos, found
            if policy == "pooled":
                row_ok = gsum(st.fcnt) > 0
                found = jnp.any(row_ok)
                n = _argmin_tie_low(st.s, row_ok)
                n = jnp.clip(n, 0, N - 1)
                j = gmin(jnp.min(jnp.where(st.feas[n], offs + arangeJs,
                                           _IBIG)))
                return n, j, st.pidx, st.pos, found
            # rrr: pick the round's next feasible server from running
            # column counts, then the best framework on the owner's column
            # (broadcast via psum — exactly one owner contributes).
            Kp = perms.shape[0]
            perm = perms[jnp.minimum(st.pidx, Kp - 1)]
            rank = jax.lax.dynamic_slice(
                jnp.zeros(J, i32).at[perm].set(arangeJ), (offs,), (Js,))
            server_ok = st.ccnt > 0
            ahead = server_ok & (rank >= st.pos)
            wrap = ~gany(jnp.any(ahead))
            perm2 = perms[jnp.minimum(st.pidx + 1, Kp - 1)]
            rank2 = jax.lax.dynamic_slice(
                jnp.zeros(J, i32).at[perm2].set(arangeJ), (offs,), (Js,))
            eff_rank = jnp.where(wrap, rank2, rank)
            eff_ok = jnp.where(wrap, server_ok, ahead)
            # fused (rank, server) key — ranks are a permutation, so the
            # minimal key carries both the round's next rank and its server
            # in ONE scalar reduce.
            key = gmin(jnp.min(jnp.where(eff_ok,
                                         eff_rank * J + offs + arangeJs,
                                         _IBIG)))
            found = key < _IBIG
            mrank = key // J
            j = key % J
            ow = (j // Js) == ax
            jl = jnp.clip(j - offs, 0, Js - 1)
            fcolf = jnp.where(ow, st.feas[:, jl], False).astype(f32)
            if server_specific:
                colv = jnp.where(ow, st.s[:, jl], f32(0.0))
                pay = gsum(jnp.stack([colv, fcolf]))           # (2, N)
                col, fcol = pay[0], pay[1] > 0.5
            else:
                col = st.s
                fcol = gsum(fcolf) > 0.5
            n = _argmin_tie_low(col, fcol)
            n = jnp.clip(n, 0, N - 1)
            last = mrank == j_real - 1
            pidx = st.pidx + wrap.astype(i32) + last.astype(i32)
            pos = jnp.where(last, 0, mrank + 1)
            return n, j, pidx, pos, found

        def body(st: _MeshState):
            n, j, pidx, pos, found = _select(st)
            fnd = jnp.where(found, f32(1.0), f32(0.0))
            ow = ((j // Js) == ax) & found
            jl = jnp.clip(j - offs, 0, Js - 1)
            owf = jnp.where(ow, f32(1.0), f32(0.0))
            bundle = TD[n]                                     # (R,)
            # owner-predicated in-place block updates (adding 0 elsewhere
            # keeps non-owner buffers bit-identical: the state arrays are
            # all >= +0.0 so x + 0.0 == x exactly); the found=False probe
            # iteration that discovers exhaustion changes nothing.
            Xl2 = st.X.at[n, jl].add(owf)
            tot2 = st.tot.at[n].add(fnd)
            FREEl2 = st.FREE.at[jl].add(-bundle * owf)
            usedl2 = st.used.at[jl].add(ow.astype(i32))
            # feasibility: owner's column j, then row n if n is satisfied
            wants = tot2 < wanted
            colf = wants & allowedl[:, jl] & jnp.all(
                TD <= FREEl2[jl][None, :] + eps, axis=1)
            if use_limit:
                colf = colf & (usedl2[jl] < limit)
            old_col = st.feas[:, jl]
            new_col = jnp.where(ow, colf, old_col)
            feas2 = st.feas.at[:, jl].set(new_col)
            dcol = old_col.astype(i32) - new_col.astype(i32)   # removals
            fcnt2 = st.fcnt - dcol
            ccnt2 = st.ccnt.at[jl].add(-jnp.sum(dcol))
            dead = found & ~wants[n]
            old_row = feas2[n]                                 # (Js,)
            drow = jnp.where(dead, old_row.astype(i32),
                             jnp.zeros(Js, i32))
            feas3 = feas2.at[n].set(jnp.where(dead,
                                              jnp.zeros(Js, bool),
                                              old_row))
            fcnt3 = fcnt2.at[n].add(-jnp.sum(drow))
            ccnt3 = ccnt2 - drow
            # score refresh — the incremental formulas of epoch_loop, on
            # the owner's column slice and the (replicated) granted row
            xt_n = tot2[n] + la
            cap2, dom2 = st.cap, st.dom
            if kind == "drf":
                s2 = st.s.at[n].set(jnp.where(found,
                                              xt_n * aux[n] / phi[n],
                                              st.s[n]))
            elif kind == "tsf":
                s2 = st.s.at[n].set(jnp.where(found, xt_n / aux[n],
                                              st.s[n]))
            elif kind == "psdsf":
                s2 = st.s.at[n].set(jnp.where(found,
                                              xt_n / phi[n] * doml[n],
                                              st.s[n]))
            else:  # rpsdsf
                capj = Cl[jl] - Xl2[:, jl] @ D                 # (R,)
                capj = jnp.where(ow, capj, st.cap[jl])
                cap2 = st.cap.at[jl].set(capj)
                domc = criteria.virtual_dominant(D, capj[None, :],
                                                 xp=jnp)[:, 0]
                domc = jnp.where(ow, domc, st.dom[:, jl])
                dom2 = st.dom.at[:, jl].set(domc)
                xt = tot2 + la
                sc = jnp.where(ow, xt / phi * dom2[:, jl], st.s[:, jl])
                s2 = st.s.at[:, jl].set(sc)
                s2 = s2.at[n].set(jnp.where(found,
                                            xt_n / phi[n] * dom2[n],
                                            s2[n]))
            # per-row minima cache: every shard re-scans the granted row
            # (O(Js)); the owner re-scans its whole block ONLY when some
            # other row cached at column jl STRICTLY increased past its row
            # minimum — increase-only updates keep every other cached row
            # exact, and a tied update (the cold-start zero-score plateau)
            # invalidates nothing.
            rmin2, rarg2 = st.rmin, st.rarg
            if policy == "pooled" and server_specific:
                rowm = jnp.where(feas3[n], s2[n], _BIG)
                rmin2 = st.rmin.at[n].set(jnp.where(found, jnp.min(rowm),
                                                    st.rmin[n]))
                rarg2 = st.rarg.at[n].set(
                    jnp.where(found, jnp.argmin(rowm).astype(i32),
                              st.rarg[n]))
                newc = jnp.where(feas3[:, jl], s2[:, jl], _BIG)
                stale = ((st.rarg == jl) & (st.rmin < f32(_BIG))
                         & (arangeN != n) & (newc > st.rmin))
                rmin2, rarg2 = jax.lax.cond(
                    ow & jnp.any(stale),
                    lambda: _row_scan(s2, feas3),
                    lambda: (rmin2, rarg2))
            return _MeshState(
                X=Xl2, tot=tot2, FREE=FREEl2, cap=cap2, dom=dom2, s=s2,
                feas=feas3, used=usedl2, fcnt=fcnt3, ccnt=ccnt3,
                rmin=rmin2, rarg=rarg2,
                pidx=jnp.where(found, pidx, st.pidx),
                pos=jnp.where(found, pos, st.pos),
                count=st.count + found.astype(i32), alive=found,
                ns=st.ns.at[st.count].set(
                    jnp.where(found, n.astype(i32), st.ns[st.count])),
                js=st.js.at[st.count].set(
                    jnp.where(found, j.astype(i32), st.js[st.count])),
            )

        def cond(st: _MeshState):
            return st.alive & (st.count < max_steps)

        fcnt0 = jnp.sum(feasl, axis=1).astype(i32)
        ccnt0 = jnp.sum(feasl, axis=0).astype(i32)
        if policy == "pooled" and server_specific:
            rmin0, rarg0 = _row_scan(sl, feasl)
        else:
            rmin0 = jnp.zeros((N,), f32)
            rarg0 = jnp.zeros((N,), i32)
        init = _MeshState(
            X=Xl, tot=tot, FREE=FREEl, cap=capl, dom=doml, s=sl, feas=feasl,
            used=usedl.astype(i32), fcnt=fcnt0, ccnt=ccnt0,
            rmin=rmin0, rarg=rarg0,
            pidx=jnp.asarray(pidx0, i32), pos=jnp.asarray(pos0, i32),
            count=i32(0), alive=jnp.asarray(True),
            ns=jnp.full((max_steps,), -1, i32),
            js=jnp.full((max_steps,), -1, i32),
        )
        fin = jax.lax.while_loop(cond, body, init)
        # iterations: the grants, and the probe that found none, if any ran
        steps = fin.count + (~fin.alive).astype(i32)
        return (fin.ns, fin.js, fin.count, fin.X, fin.tot, fin.FREE,
                fin.used, fin.pidx, fin.pos, steps)

    P = PartitionSpec
    shard_j = P(None, "agents")      # (N, J) blocks, server axis sharded
    shard_row = P("agents", None)    # (J, R) blocks
    rep = P()
    s_spec = shard_j if server_specific else rep
    fn = jax.shard_map(
        shard_body, mesh=make_agent_mesh(K),
        in_specs=(shard_j, shard_row, shard_row, shard_j, s_spec, shard_j,
                  shard_j, shard_row, P("agents"),
                  # D, TD, phi, wanted, perms, tot, aux, pidx0, pos0,
                  # j_real, limit, eps — all replicated
                  rep, rep, rep, rep, rep, rep, rep, rep, rep, rep, rep,
                  rep),
        out_specs=(rep, rep, rep, shard_j, rep, shard_row, P("agents"),
                   rep, rep, rep),
        check_vma=False,
    )
    return fn(X, FREE, cap0, dom0, s0, feas0, allowed, C,
              used.astype(jnp.int32), D, TD, phi, wanted,
              jnp.asarray(perms), tot, aux,
              jnp.asarray(pidx0, i32), jnp.asarray(pos0, i32),
              jnp.asarray(j_real, i32), jnp.asarray(limit, i32),
              jnp.asarray(eps, f32))


_STATIC = ("kind", "policy", "lookahead", "use_limit", "max_steps")
_STATIC_MESH = ("kind", "policy", "lookahead", "use_limit", "max_steps",
                "devices")


@functools.lru_cache(maxsize=None)
def _jitted(donate: bool):
    if donate:
        # X (0), FREE (4) and used (9) are the mutated buffers: donating
        # them lets XLA reuse the epoch-state allocation across epochs.
        return jax.jit(epoch_loop, static_argnames=_STATIC,
                       donate_argnums=(0, 4, 9))
    return jax.jit(epoch_loop, static_argnames=_STATIC)


@functools.lru_cache(maxsize=None)
def _jitted_mesh():
    # no donation: the sharded buffers live per-device and the RRR replay
    # path re-dispatches from kept (non-invalidated) input references.
    return jax.jit(epoch_loop_mesh, static_argnames=_STATIC_MESH)


class EpochCompileError(RuntimeError):
    """The compiler refused an epoch program: a Mosaic kernel it cannot
    build, a program larger than device memory, a scoped-VMEM overflow.
    This happens before anything reaches the device, so it is a fault of
    the program, never of the device: the self-healing dispatch does not
    retry it or re-run the epoch on the host
    (:func:`repro.core.faults.is_device_fault`)."""


#: compiled epoch executables, keyed by jitted function, static arguments
#: and the type and placement of every array argument (shapes are bucketed,
#: so this stays as small as jit's own cache would)
_EXECUTABLES: dict = {}


def _executable(fn, args, static):
    """The compiled executable of ``fn`` for ``args``, compiled ahead of
    the launch.  Compiling apart from running is what tells a compiler
    refusal (an :class:`EpochCompileError`, or the tracing error itself)
    from a fault of the device, which can only show when the executable
    runs or its result is read back."""
    key = (fn, tuple(sorted(static.items())),
           tuple((jax.typeof(a), getattr(a, "sharding", None)) for a in args))
    exe = _EXECUTABLES.get(key)
    if exe is None:
        try:
            exe = fn.lower(*args, **static).compile()
        except jax.errors.JaxRuntimeError as exc:
            raise EpochCompileError(
                f"the compiler refused the epoch program: {exc}") from exc
        _EXECUTABLES[key] = exe
    return exe


def _bucket(n: int, lo: int = 8) -> int:
    """Next power of two >= max(n, lo) — the jit-cache shape bucket (the
    same rounding rule the kernel wrappers use for tiles)."""
    from repro.kernels.psdsf_score.ops import next_pow2

    return next_pow2(n, lo)


def _put(a, dtype=None):
    """``a`` on the device at ``dtype``, its bytes counted as uploaded."""
    out = jnp.asarray(a, dtype)
    tracing.count("engine_jax.upload_bytes", out.nbytes)
    return out


def _pad(a, n, axis, value):
    pad = n - a.shape[axis]
    if pad <= 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return np.pad(a, widths, constant_values=value)


#: entries of X's support sent per upload chunk, an int32 flat index and
#: an f32 value each.  The length is fixed per padded shape and every
#: staged X sends at least one chunk, so one scatter program per (Np, Jp)
#: stages every epoch of that shape, whatever its support.
STAGE_CHUNK = 1 << 16


def _scatter_chunk(buf, idx, val):
    """``buf`` with ``val`` written at the flat indices ``idx`` (sorted,
    distinct); indices past the end, a last chunk's padding, are dropped."""
    flat = buf.reshape(-1).at[idx].set(
        val, mode="drop", indices_are_sorted=True, unique_indices=True)
    return flat.reshape(buf.shape)


@functools.lru_cache(maxsize=None)
def _scatter_jit():
    # the buffer is the staging's own, so it is donated wherever the
    # backend reuses donated buffers
    donate = (0,) if jax.default_backend() != "cpu" else ()
    return jax.jit(_scatter_chunk, donate_argnums=donate)


@jax.jit
def _unpack_bits(bits):
    return jnp.unpackbits(bits, axis=1).astype(bool)


def _stage_x(X, Np, Jp):
    """Host ``X`` as the zero-padded f32 (Np, Jp) device array, sent as its
    support: the flat (Np, Jp) index and f32 value of each non-zero
    (``!= 0``: NaN is kept, -0.0 is zero), in chunks scattered into a zero
    buffer on the device.  ``X`` may be any (n, j) with n <= Np, j <= Jp."""
    flat = np.flatnonzero(X != 0)     # several times np.nonzero's speed
    rows, cols = np.divmod(flat, X.shape[1])
    n = flat.size
    tracing.count("engine_jax.staged_nonzeros", n)
    chunk = min(STAGE_CHUNK, Np * Jp)
    k = max(1, -(-n // chunk))
    idx = np.empty(k * chunk, np.int32)
    np.add(rows * Jp, cols, out=idx[:n], casting="unsafe")
    # distinct out-of-range indices keep the padding sorted and unique
    idx[n:] = np.arange(Np * Jp, Np * Jp + k * chunk - n, dtype=np.int32)
    val = np.zeros(k * chunk, np.float32)
    val[:n] = X[rows, cols]
    scatter = _scatter_jit()
    buf = jnp.zeros((Np, Jp), jnp.float32)
    for i in range(0, k * chunk, chunk):
        buf = scatter(buf, _put(idx[i:i + chunk]), _put(val[i:i + chunk]))
    return buf


def _stage_allowed(allowed, Np, Jp):
    """Host ``allowed`` (N, J) as the False-padded (Np, Jp) device mask,
    sent at one bit per cell (``np.packbits``, rows padded with zero bits)
    and unpacked on the device."""
    bits = np.zeros((Np, Jp // 8), np.uint8)
    packed = np.packbits(allowed, axis=1)
    bits[:packed.shape[0], :packed.shape[1]] = packed
    return _unpack_bits(_put(bits))


def grant_bound(TD, FREE, tot, wanted, per_agent_limit=None) -> int:
    """Upper bound on grants this epoch (sizes the device-side sequence).

    Every grant consumes at least ``min_n max_r TD[n, r]`` units of SOME
    resource on its server, so server j can absorb at most
    ``sum_r FREE[j, r] / that`` grants; the total is additionally capped by
    the outstanding wanted deficit and by J * per_agent_limit.  The
    wanted/limit caps apply even when a degenerate zero-demand framework
    voids the capacity argument."""
    wants = tot < wanted
    if not wants.any():
        return 0
    deficit = float(np.sum(wanted[wants] - tot[wants]))
    bound = int(min(deficit, 2**30))
    dmin = float(np.max(TD[wants], axis=1).min())
    if dmin > 0:
        bound = min(bound,
                    int(np.ceil(np.sum(np.maximum(FREE, 0.0)) / dmin)))
    if per_agent_limit is not None:
        bound = min(bound, FREE.shape[0] * int(per_agent_limit))
    return max(bound, 1)


def rrr_perm_budget(bound: int, J: int, max_steps_cap: int = 16384) -> int:
    """Initial RRR permutation-stack height for one dispatch segment.

    One permutation per round of ~J grants plus wrap slack, pow2-bucketed
    (stack shape is part of the jit key).  A pure function of the epoch
    profile — the epoch-cache layer calls this to pre-draw (and
    fingerprint) the exact prefix the dispatch would draw, keeping the rng
    stream position identical with and without a cache in front."""
    seg = min(bound, max_steps_cap)
    return _bucket(4 + 4 * ((seg + J - 1) // J))


class _EpochRun:
    """Continuation state of an in-flight fused epoch (one dispatch issued,
    readback deferred).  ``_finish`` drives RRR grow-and-replay rounds and
    chained overflow segments exactly like the old synchronous loop did."""

    def __init__(self, *, fn, kind, policy, lookahead, use_limit, J, shape,
                 limit, eps, draw, consts, perms, bound, max_steps_cap, snap,
                 donate=False, devices=1):
        self.fn = fn                # _jitted(donate) / _jitted_mesh()
        self.kind, self.policy = kind, policy
        self.lookahead, self.use_limit = lookahead, use_limit
        self.devices = devices      # >1: mesh dispatch (epoch_loop_mesh)
        self.donate = donate
        self.J, self.limit, self.eps = J, limit, eps
        self.shape = shape          # the padded (Np, Jp)
        self.draw = draw            # rng-stream permutation drawer (RRR)
        self.consts = consts        # (dD, dTD, dC, dphi, dwanted, dallowed)
        self.perms = perms
        self.pidx = self.pos = 0
        self.remaining = bound
        self.max_steps_cap = max_steps_cap
        # host-side snapshot of the segment-start state: with donation the
        # dispatch invalidates its input buffers, so a grow-and-replay round
        # stages X again from here (RRR only; pooled never replays).  WITHOUT
        # donation the dispatch inputs stay valid, so the replay path keeps
        # device-array references instead and no host copy is ever made —
        # the CPU backend (donation off) previously paid that O((N+J)*R)
        # snapshot for a replay path that never needed it.
        self.snap = snap if donate else None
        self._last_inputs = None
        self.pending = None

    def dispatch(self, X_cur, FREE_cur, used_cur):
        global DISPATCH_COUNT
        DISPATCH_COUNT += 1
        if fault_hook is not None:
            fault_hook()
        self.max_steps = _bucket(min(self.remaining, self.max_steps_cap),
                                 lo=16)
        if self.policy == "rrr" and not self.donate:
            # non-donated inputs survive the dispatch: keep references for
            # grow-and-replay instead of a host snapshot.
            self._last_inputs = (X_cur, FREE_cur, used_cur)
        dD, dTD, dC, dphi, dwanted, dallowed = self.consts
        perms = jnp.asarray(self.perms)
        scalars = (np.int32(self.pidx), np.int32(self.pos),
                   jnp.int32(self.J), self.limit, jnp.float32(self.eps))
        # the state and the constants are on the device already; the
        # permutation stack and the scalars go up with every dispatch
        tracing.count("engine_jax.upload_bytes",
                      perms.nbytes + sum(a.nbytes for a in scalars))
        args = (X_cur, dD, dTD, dC, FREE_cur, dphi, dwanted, dallowed,
                perms, used_cur) + scalars
        static = dict(kind=self.kind, policy=self.policy,
                      lookahead=self.lookahead, use_limit=self.use_limit,
                      max_steps=self.max_steps)
        if self.devices > 1:
            static["devices"] = self.devices
        self.pending = _executable(self.fn, args, static)(*args)

    def _wait(self):
        """The pending dispatch's outputs, once the device has run it."""
        with tracing.span("engine_jax.wait"):
            self.pending[2].block_until_ready()
        return self.pending

    def _finish(self) -> list[tuple[int, int]]:
        out: list[tuple[int, int]] = []
        while True:
            (ns, js, count, Xd, _totd, FREEd, usedd, pidx_d, pos_d,
             steps) = self._wait()
            if self.policy == "rrr":
                # a clamped permutation read implies the final cursor ran
                # past the stack (every used row index is <= the final
                # pidx), so ending ON the last row is still exact — only
                # pidx >= K is tainted: grow the stack (stream-append) and
                # replay from the segment-start state (host snapshot when
                # the failed dispatch donated its inputs; the still-valid
                # input references otherwise).
                while int(pidx_d) >= self.perms.shape[0]:
                    self.perms = np.concatenate(
                        [self.perms, self.draw(self.perms.shape[0])])
                    with tracing.span("engine_jax.upload"):
                        if self.donate:
                            Xs, FREEs, useds = self.snap
                            self.dispatch(_stage_x(Xs, *self.shape),
                                          _put(FREEs, jnp.float32),
                                          _put(useds, jnp.int32))
                        else:
                            self.dispatch(*self._last_inputs)
                    (ns, js, count, Xd, _totd, FREEd, usedd, pidx_d, pos_d,
                     steps) = self._wait()
            with tracing.span("engine_jax.readback"):
                k, n_steps = (int(v) for v in jax.device_get((count, steps)))
                tracing.count("engine_jax.loop_steps", n_steps)
                out.extend(zip(np.asarray(ns[:k]).tolist(),
                               np.asarray(js[:k]).tolist()))
                if k < self.max_steps or self.remaining - k <= 0:
                    return out
                # overflow: chain another dispatch from the final DEVICE
                # state (incl. the RRR cursor, so the chain equals one long
                # epoch)
                self.remaining -= k
                self.pidx, self.pos = int(pidx_d), int(pos_d)
                if self.policy == "rrr" and self.donate:
                    # snapshot BEFORE the arrays are donated into the next
                    # call
                    self.snap = (np.asarray(Xd), np.asarray(FREEd),
                                 np.asarray(usedd))
            with tracing.span("engine_jax.upload"):
                self.dispatch(Xd, FREEd, usedd)


class EpochHandle:
    """Handle to an in-flight fused epoch (see :func:`run_epoch_async`).

    ``result()`` is the commit point: it blocks until the device loop(s)
    finish, drives any chained/replayed dispatches, and returns the flat
    grant sequence.  Idempotent — repeated calls return the same list."""

    __slots__ = ("_seq", "_run", "perms")

    def __init__(self, seq=None, run=None):
        self._seq = seq
        self._run = run
        # final permutation stack (set at result(); None for empty epochs).
        # The epoch-cache layer reads it to record how many grow-and-replay
        # rows an RRR epoch drew PAST the pre-drawn prefix.
        self.perms = None

    @property
    def in_flight(self) -> bool:
        """True until ``result()`` has been driven to completion."""
        return self._seq is None

    @tracing.traced("engine_jax.result")
    def result(self) -> list[tuple[int, int]]:
        if self._seq is None:
            self._seq = self._run._finish()
            self.perms = self._run.perms
            self._run = None
        return self._seq


def run_epoch_async(criterion, policy: str, *, X, D, C, FREE, phi, allowed,
                    wanted, true_demands,
                    per_agent_limit: Optional[int] = None,
                    lookahead: bool = False,
                    rng: Optional[np.random.Generator] = None,
                    eps: float = 1e-9, devices: int = 1,
                    max_steps_cap: int = 16384,
                    preperms: Optional[np.ndarray] = None,
                    bf_metric: str = "cosine",
                    _perm_rows: Optional[int] = None,
                    _donate: Optional[bool] = None) -> EpochHandle:
    """Dispatch one allocation epoch on device WITHOUT blocking on readback.

    Performs the same host prep as the synchronous path — pads to
    power-of-two shape buckets (cached jit executables), pre-draws RRR
    permutations from the shared numpy rng (all rng consumption happens
    here, at dispatch) — issues the first jitted while-loop dispatch, and
    returns an :class:`EpochHandle`.  ``handle.result()`` blocks, drives
    chained dispatches (epochs whose :func:`grant_bound` exceeds
    ``max_steps_cap``) and RRR grow-and-replay rounds, and returns the
    grant sequence — bit-for-bit the sequence :func:`run_epoch` returns.

    ``devices > 1`` dispatches :func:`epoch_loop_mesh` instead of
    :func:`epoch_loop` — the server axis sharded over that many REAL
    devices (rounded down to a power of two; more devices than the process
    has is a ``ValueError``).
    ``_donate`` forces buffer donation on/off (test hook; default: donate
    on non-CPU single-device dispatches — safe for RRR because replay
    re-uploads from a host snapshot; without donation the replay keeps
    device-array references and skips the snapshot entirely).
    ``preperms`` supplies the RRR permutation prefix as a ``(k, J)`` int32
    array already drawn from the stream (the epoch-cache layer pre-draws
    :func:`rrr_perm_budget` rows so it can fingerprint them); the dispatch
    then draws nothing up front, only grow-and-replay top-ups — total
    stream consumption is identical to letting the dispatch draw.
    ``policy="bestfit"`` (``bf_metric`` ``"cosine"``, a global criterion,
    one device) first checks that ``FREE`` and the
    demands are whole units the exact key can order
    (:func:`check_bestfit_inputs`) and raises where they are not.
    """
    crit = criteria.get_criterion(criterion)
    kind = crit.name
    if kind not in COVERED_CRITERIA or policy not in COVERED_POLICIES:
        raise ValueError(f"fused epoch does not cover {kind}/{policy}")
    if policy == "bestfit":
        if not supports(crit, policy, "characterized", "low",
                        bf_metric=bf_metric, devices=int(devices)):
            raise ValueError(
                f"fused best-fit covers {'/'.join(BESTFIT_CRITERIA)} with "
                f"the {'/'.join(BESTFIT_METRICS)} metric on one device; "
                f"not {kind}/{bf_metric} with devices={devices}")
    if int(devices) > len(jax.devices()):
        raise ValueError(f"epoch asks for a {devices}-device mesh; this "
                         f"process has {len(jax.devices())} devices")
    devices = max(1, int(devices))
    devices = 1 << (devices.bit_length() - 1)    # floor to a power of two
    donate = (jax.default_backend() != "cpu" and devices <= 1) \
        if _donate is None else bool(_donate)

    X = np.asarray(X, np.float64)
    D = np.asarray(D, np.float64)
    TD = np.asarray(true_demands, np.float64)
    C = np.asarray(C, np.float64)
    FREE = np.array(FREE, np.float64)
    phi = np.asarray(phi, np.float64)
    wanted = np.asarray(wanted, np.float64)
    allowed = np.asarray(allowed, bool)
    N, J = X.shape
    tot = X.sum(axis=1)
    if policy == "bestfit":
        check_bestfit_inputs(FREE, TD, tot < wanted, allowed, eps)

    bound = grant_bound(TD, FREE, tot, wanted, per_agent_limit)
    if bound == 0:
        return EpochHandle(seq=[])
    # staging: pad, cast and upload the epoch's inputs (X and allowed by
    # their content), then launch
    with tracing.span("engine_jax.upload"):
        Np, Jp = _bucket(N), _bucket(J)
        limit = np.int32(0 if per_agent_limit is None else per_agent_limit)
        use_limit = per_agent_limit is not None
        devices = min(devices, Jp)                   # pow2s: divides Jp

        Dp = _pad(D, Np, 0, 0.0)
        TDp = _pad(TD, Np, 0, 0.0)
        Cp = _pad(C, Jp, 0, 0.0)
        FREEp = _pad(FREE, Jp, 0, 0.0)
        phip = _pad(phi, Np, 0, 1.0)
        wantedp = _pad(wanted, Np, 0, 0.0)   # padded frameworks want nothing
        usedp = np.zeros(Jp, np.int32)

        def _draw_perms(k: int) -> np.ndarray:
            """k permutation rows from the shared rng stream, padded to Jp."""
            rows = np.empty((k, Jp), np.int32)
            for i in range(k):
                rows[i, :J] = rng.permutation(J)
                rows[i, J:] = np.arange(J, Jp)
            return rows

        if policy == "rrr":
            if rng is None:
                raise ValueError("fused RRR epoch needs the allocator rng")
            # optimistic budget: one permutation per round of ~J grants
            # plus wrap slack, sized for one dispatch segment (the stack
            # persists across chained segments and grows on demand).  The
            # worst case is 2 per grant (every grant at the round's last
            # position after a wrap), so if the loop reports its cursor ran
            # PAST the stack we APPEND more rows — drawing more continues
            # the rng stream, the already-drawn prefix is unchanged — and
            # re-run the dispatch.  pow2-bucket the stack height so growing
            # `bound` within a bucket cannot retrace the loop (perms shape
            # is part of the jit key); _perm_rows is a test hook that
            # forces the grow-and-replay path.
            if preperms is not None:
                pp = np.asarray(preperms, np.int32)
                perms = np.empty((pp.shape[0], Jp), np.int32)
                perms[:, :J] = pp[:, :J]
                perms[:, J:] = np.arange(J, Jp)
            else:
                perms = _draw_perms(_perm_rows if _perm_rows is not None
                                    else rrr_perm_budget(bound, J,
                                                         max_steps_cap))
        else:
            perms = np.arange(Jp, dtype=np.int32)[None, :]

        fn = _jitted_mesh() if devices > 1 else _jitted(donate)
        f32 = jnp.float32
        # constant inputs upload once; the mutable state arrays stay on
        # device across chained segments (only the grant sequence is read
        # back).
        consts = (_put(Dp, f32), _put(TDp, f32), _put(Cp, f32),
                  _put(phip, f32), _put(wantedp, f32),
                  _stage_allowed(allowed, Np, Jp))
        run = _EpochRun(
            fn=fn, kind=kind, policy=policy, lookahead=lookahead,
            use_limit=use_limit, devices=devices, J=J, shape=(Np, Jp),
            limit=limit, eps=eps,
            draw=_draw_perms, consts=consts, perms=perms, bound=bound,
            max_steps_cap=max_steps_cap, donate=donate,
            snap=(X, FREEp, usedp) if policy == "rrr" and donate else None,
        )
        run.dispatch(_stage_x(X, Np, Jp), _put(FREEp, f32), _put(usedp))
    return EpochHandle(run=run)


def run_epoch(criterion, policy: str, **kw) -> list[tuple[int, int]]:
    """Run one allocation epoch on device; returns the grant sequence.

    Synchronous wrapper: ``run_epoch_async(...).result()`` — dispatch and
    commit back to back, so async and sync sequences are identical by
    construction (see :func:`run_epoch_async` for the knobs)."""
    return run_epoch_async(criterion, policy, **kw).result()
