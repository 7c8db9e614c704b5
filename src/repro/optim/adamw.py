"""AdamW with decoupled weight decay, fp32 moments, cosine schedule, global
gradient clipping — pure-JAX (no optax).  Optimizer state is sharded exactly like
the parameters (ZeRO: the FSDP rules apply to m/v too)."""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_ratio: float = 0.1


def schedule(cfg: AdamWConfig, step):
    step = step.astype(jnp.float32)
    warm = step / jnp.maximum(cfg.warmup_steps, 1)
    prog = (step - cfg.warmup_steps) / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1)
    prog = jnp.clip(prog, 0.0, 1.0)
    cos = cfg.min_lr_ratio + (1 - cfg.min_lr_ratio) * 0.5 * (1 + jnp.cos(jnp.pi * prog))
    return cfg.lr * jnp.where(step < cfg.warmup_steps, warm, cos)


def init(params):
    zeros = lambda p: jnp.zeros(p.shape, jnp.float32)
    return {"m": jax.tree.map(zeros, params), "v": jax.tree.map(zeros, params)}


def global_norm(tree):
    return jnp.sqrt(
        sum(jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree))
    )


def update(cfg: AdamWConfig, params, grads, opt, step):
    """One AdamW step. Returns (new_params, new_opt, metrics)."""
    gnorm = global_norm(grads)
    scale = jnp.minimum(1.0, cfg.clip_norm / (gnorm + 1e-9))
    lr = schedule(cfg, step)
    t = step.astype(jnp.float32) + 1.0
    bc1 = 1.0 - cfg.b1 ** t
    bc2 = 1.0 - cfg.b2 ** t

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * scale
        m = cfg.b1 * m + (1 - cfg.b1) * g
        v = cfg.b2 * v + (1 - cfg.b2) * jnp.square(g)
        mh = m / bc1
        vh = v / bc2
        step_ = mh / (jnp.sqrt(vh) + cfg.eps) + cfg.weight_decay * p.astype(jnp.float32)
        return (p.astype(jnp.float32) - lr * step_).astype(p.dtype), m, v

    flat_p, tdef = jax.tree.flatten(params)
    flat_g = jax.tree.leaves(grads)
    flat_m = jax.tree.leaves(opt["m"])
    flat_v = jax.tree.leaves(opt["v"])
    out = [upd(p, g, m, v) for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = jax.tree.unflatten(tdef, [o[0] for o in out])
    new_m = jax.tree.unflatten(tdef, [o[1] for o in out])
    new_v = jax.tree.unflatten(tdef, [o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v}, {"grad_norm": gnorm, "lr": lr}
