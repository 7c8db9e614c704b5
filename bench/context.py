"""What a per-layer metric reader is given: the spans, the counters and the
reduced device trace of one traced run."""
from __future__ import annotations

import dataclasses
from typing import Optional

from bench import roofline, trace_reduce

#: the device program of an allocation epoch, as the trace names it
EPOCH_PROGRAM = "epoch_loop"


@dataclasses.dataclass
class Context:
    spans: object                  # drive.Spans
    outcome: object                # drive.Outcome
    trace: Optional[trace_reduce.Trace]
    n_resources: int
    device_kind: str

    def per(self, span: str, count: float, scale: float) -> Optional[float]:
        """``scale`` x the span's total seconds per ``count`` (None where
        nothing was counted)."""
        if not count or not self.spans.count(span):
            return None
        return scale * self.spans.total(span) / count

    def _window(self):
        return trace_reduce.window(self.trace)

    def has_device(self) -> bool:
        return self.trace is not None and self.trace.n_devices > 0

    def window_s(self) -> Optional[float]:
        if self.trace is None:
            return None
        lo, hi = self._window()
        return (hi - lo) / 1e9

    def busy_s(self) -> Optional[float]:
        if not self.has_device():
            return None
        return trace_reduce.busy_ns(self.trace, *self._window()) / 1e9

    def epoch_device_s(self) -> Optional[float]:
        """Device seconds of the epoch programs in the window."""
        if not self.has_device():
            return None
        ns = trace_reduce.program_ns(self.trace, *self._window(),
                                     contains=EPOCH_PROGRAM)
        return sum(ns.values()) / 1e9 or None

    def epoch_min_bytes(self) -> int:
        return sum(roofline.epoch_bytes(n, j, self.n_resources, g)
                   for n, j, g in self.outcome.epoch_shapes)
