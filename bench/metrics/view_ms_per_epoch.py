"""Milliseconds of ``ClusterState.epoch_view`` per epoch: the gather of the
name-sorted view of the cluster state that the epoch freezes (a memoised
return counts its near-zero call)."""

from bench.metrics import _program


def read(ctx):
    return _program.ms_per_epoch(ctx, "state.epoch_view")
