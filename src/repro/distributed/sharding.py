"""Logical-axis sharding rules (MaxText-style) with divisibility fallback.

Parameters and activations are annotated with *logical* axis names
(``"embed"``, ``"heads"``, ``"batch"``...).  A rule table maps logical names
to mesh axes; the resolver drops any mesh axis that (a) is absent from the
active mesh or (b) does not divide the dimension — so the same model code
lowers on the single-pod ``(data=16, model=16)`` mesh, the multi-pod
``(pod=2, data=16, model=16)`` mesh, and the single CPU device used by smoke
tests (where every rule resolves to no-sharding).

Default placement strategy (the paper-faithful baseline; §Perf iterates):
  * batch          -> ("pod", "data")   pure DP across pods, DP within pod
  * embed (params) -> "data"            ZeRO-3/FSDP within a pod
  * vocab/heads/kv_heads/mlp/experts -> "model"  tensor/expert parallelism
  * decode-cache seq -> "data"          flash-decode style cache partition
"""
from __future__ import annotations

import contextlib
import contextvars
import dataclasses
from typing import Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.nn import param as pm

# logical axis -> mesh axis (str), tuple of mesh axes, or None
DEFAULT_RULES: dict = {
    # activations
    "batch": ("pod", "data"),
    "seq": None,
    # decode caches shard over seq on whatever axis batch left free —
    # attention against a seq-sharded cache is flash-decode (partial softmax
    # + small all-reduce), which GSPMD synthesizes from this constraint.
    "cache_seq": ("data", "model"),
    "embed_act": None,
    "heads_act": "model",
    "mlp_act": "model",
    "vocab_act": "model",
    # parameters
    "embed": "data",              # FSDP
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "mlp": "model",
    "experts": "model",
    "moe_cap": ("data", "model"),   # MoE dispatch-grid capacity dim
    "media": None,
    "layers": None,
    "q_lora": None,
    "kv_lora": None,
    "ssm": None,
    "conv": None,
}


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: dict

    def mesh_axes_for(self, logical: Optional[str], dim: int, mesh: Mesh,
                      used=()):
        """Resolve one logical axis to mesh axes, honoring divisibility and
        skipping mesh axes already consumed by an earlier dim of the same
        tensor (a mesh axis can shard at most one dim)."""
        if logical is None:
            return None
        target = self.rules.get(logical)
        if target is None:
            return None
        axes = (target,) if isinstance(target, str) else tuple(target)
        chosen = []
        prod = 1
        for ax in axes:
            if ax not in mesh.shape or ax in used:
                continue
            n = mesh.shape[ax]
            if dim % (prod * n) == 0:
                chosen.append(ax)
                prod *= n
        if not chosen:
            return None
        return chosen[0] if len(chosen) == 1 else tuple(chosen)

    def pspec(self, axes: tuple, shape: tuple, mesh: Mesh) -> P:
        used: list = []
        out = []
        for a, d in zip(axes, shape):
            r = self.mesh_axes_for(a, d, mesh, used=tuple(used))
            if r is not None:
                used.extend((r,) if isinstance(r, str) else r)
            out.append(r)
        return P(*out)

    def param_sharding(self, template, mesh: Mesh):
        """Template -> NamedSharding tree."""
        return pm.tree_map_specs(
            lambda p: NamedSharding(mesh, self.pspec(p.axes, p.shape, mesh)), template
        )

    def param_pspecs(self, template):
        """Template -> PartitionSpec tree (requires active mesh context)."""
        ctx = _CTX.get()
        if ctx is None:
            raise RuntimeError("param_pspecs needs use_mesh_rules()")
        mesh = ctx[0]
        return pm.tree_map_specs(lambda p: self.pspec(p.axes, p.shape, mesh), template)


# -- activation constraints --------------------------------------------------

_CTX: contextvars.ContextVar = contextvars.ContextVar("mesh_rules", default=None)


@contextlib.contextmanager
def use_mesh_rules(mesh: Mesh, rules: Optional[ShardingRules] = None):
    """Activate a mesh + rule table; layer code then honors `constrain`."""
    token = _CTX.set((mesh, rules or ShardingRules(DEFAULT_RULES)))
    try:
        yield
    finally:
        _CTX.reset(token)


def active_rules() -> Optional[ShardingRules]:
    ctx = _CTX.get()
    return None if ctx is None else ctx[1]


def constrain(x, logical_axes: tuple, override: Optional[dict] = None):
    """with_sharding_constraint against the active rules (no-op outside).
    `override` remaps logical axes for this call only."""
    ctx = _CTX.get()
    if ctx is None:
        return x
    mesh, rules = ctx
    if override:
        rules = ShardingRules({**rules.rules, **override})
    spec = rules.pspec(logical_axes, x.shape, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def weight_gather(w, logical_axes: tuple):
    """Weight-gather FSDP: force the FSDP ("embed"-over-data) shard blocks of a
    weight to all-gather BEFORE use, keeping TP axes intact.  Without this,
    GSPMD tends to keep weights sharded and psum the (much larger) activation
    partial sums — measured 2.6e12 B/step of all-reduce on deepseek train_4k
    vs ~2.4e11 B of weight all-gather (see EXPERIMENTS.md §Perf).

    Gated by the `_weight_gather` entry of the active rules (profiles:
    baseline=False, optimized=True); no-op outside a mesh context.
    """
    ctx = _CTX.get()
    if ctx is None or not ctx[1].rules.get("_weight_gather", True):
        return w
    return constrain(w, logical_axes, override={"embed": None, "vocab": None}
                     if "vocab" in logical_axes else {"embed": None})


def make_rules(**overrides) -> ShardingRules:
    r = dict(DEFAULT_RULES)
    r.update(overrides)
    return ShardingRules(r)
