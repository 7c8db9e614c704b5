"""The least bytes an allocation epoch must move, and the chip's peaks.

An epoch over N frameworks, J machines and R resources reads its real,
unpadded inputs once, in the precision the algorithm needs, and writes its
grant pairs: the float32 allocation matrix X (N, J), the bool placement
mask (N, J), the demands D (N, R), the capacities C and free resources
FREE (J, R), the weights phi and executors wanted (N,), all float32, and
two int32 per grant.  The count ignores steps, padding, passes and
buckets, so an implementation can never move fewer bytes.
"""
from __future__ import annotations

import json
import os

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def epoch_bytes(n_frameworks: int, n_machines: int, n_resources: int,
                grants: int) -> int:
    N, J, R = n_frameworks, n_machines, n_resources
    return (4 * N * J          # X, float32
            + N * J            # allowed, bool
            + 4 * N * R        # D
            + 2 * 4 * J * R    # C, FREE
            + 2 * 4 * N        # phi, wanted
            + 2 * 4 * grants)  # grant pairs out


def peak(device_kind: str) -> dict:
    """The peaks of ``device_kind``; a kind not in the table is an error."""
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json")
    return table[device_kind]
