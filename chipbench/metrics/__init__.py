"""Per-layer metric readers, one module per metric, found by the metric's
name in ``BENCHMARK.json``.  Each has ``read(data) -> float | None``: it
takes the metric from the run's spans, counters or trace, and returns
``None`` where it finds nothing to read (the harness then leaves the metric
out of the result line).  No reader returns 0 for a share of a peak or of a
roofline that it could not measure.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any


@dataclass
class RunData:
    cell: Any            # chipbench.cells.Cell
    served: Any          # chipbench.run.Served
    device: dict         # the result line's device entry
    trace: Any = None    # chipbench.trace.TraceData, in the traced run

    @property
    def window(self):
        return self.served.t0_ns, self.served.t1_ns

    @property
    def tokens(self) -> int:
        return self.served.window_tokens

    def stat_delta(self, key: str) -> int:
        return self.served.stats1[key] - self.served.stats0[key]

    def decode_calls(self):
        """``(end_ns, contexts)`` of the decode steps that ended in the
        window, ``contexts`` one entry per request decoded."""
        t0, t1 = self.window
        return [c for c in self.served.rec.decode_calls if t0 < c[0] <= t1]

    def prefills(self):
        """Prompt lengths of the prefills that ended in the window."""
        t0, t1 = self.window
        return [n for t, n in self.served.rec.prefills if t0 < t <= t1]
