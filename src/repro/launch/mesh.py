"""Production meshes.

Defined as FUNCTIONS so importing this module never touches jax device
state.  The dry-run (and only the dry-run) forces 512 host platform devices
via XLA_FLAGS before any jax import — see launch/dryrun.py.
"""
from __future__ import annotations

import jax


def make_mesh(shape: tuple, axes: tuple):
    """Device mesh with ``Auto`` axes over the process devices.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which the model
    stack's sharding constraints and weight gathers no longer type-check;
    every mesh of the repo is built here with ``Auto`` axes instead."""
    from jax.sharding import AxisType

    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_smoke_mesh():
    """Single-device mesh with the production axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"))


def make_agent_mesh(n: int):
    """1-D mesh over the first ``n`` local devices, axis name ``"agents"``.

    The fused allocation epoch splits the server (Mesos agent) axis over
    this mesh (see ``repro.core.engine_jax.epoch_loop_mesh``): each device
    owns a contiguous block of server columns and only (min, argmin)
    partials cross the interconnect per grant iteration.  ``n`` may be
    smaller than the process device count (the remaining devices are left
    free for e.g. the async pipeline's other allocators)."""
    import numpy as np
    from jax.sharding import Mesh

    devs = jax.devices()
    if n > len(devs):
        raise ValueError(f"agent mesh wants {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), ("agents",))


def make_abstract_mesh(shape: tuple, axes: tuple):
    """Device-free mesh of the given shape (sharding-rule resolution only)."""
    from jax.sharding import AbstractMesh

    return AbstractMesh(tuple(shape), tuple(axes))
