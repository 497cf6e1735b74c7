"""Spans of the serving engine, on the host's ``time.perf_counter_ns`` clock.

Each ``ValetServeEngine`` owns a ``Tracer`` (``eng.tracer``), off until
``start()``.  A span site in the engine reads

    with (tr.span("admit", req.rid) if tr.on else OFF):

so with the tracer off a site costs the one ``tr.on`` check: no clock read,
no record, no profiler annotation.  With it on, each span keeps a ``Span``
in memory and opens a ``jax.profiler.TraceAnnotation`` of the same name (the
scheduler step a ``StepTraceAnnotation``), so a profiler trace taken at the
same time shows the engine's spans in its host plane, on the device trace's
clock.

The tracer adds no device synchronisation: no ``block_until_ready``, no
read from the device, no array.  A span that contains a wait on the device
measures a wait the engine makes anyway (the ``lengths`` read, the argmax
read-backs, the tier moves).
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass
from typing import Iterator, List

import jax

# what a span site enters while the tracer is off
OFF = contextlib.nullcontext()


@dataclass(slots=True)
class Span:
    name: str
    start_ns: int
    end_ns: int = -1     # -1 while the span is open
    parent: int = -1     # index of the enclosing span in ``Tracer.spans``
    rid: int = -1        # the request served, where the span serves one
    n: int = 0           # work count: prompt tokens, rows decoded, pages


class Tracer:
    """Records the engine's spans while on.  ``spans`` holds them in the
    order they opened, so a span's ``parent`` index is below its own."""

    def __init__(self):
        self.on = False
        self.spans: List[Span] = []
        self._open: List[int] = []

    def start(self) -> None:
        self.on = True

    def stop(self) -> None:
        self.on = False

    def span(self, name: str, rid: int = -1, n: int = 0):
        return self._record(name, rid, n, jax.profiler.TraceAnnotation(name))

    def step(self, step_num: int):
        """The span of one scheduler step, a step of the profiler's too."""
        return self._record("step", -1, 0, jax.profiler.StepTraceAnnotation(
            "step", step_num=step_num))

    @contextlib.contextmanager
    def _record(self, name: str, rid: int, n: int, annotation
                ) -> Iterator[None]:
        rec = Span(name, 0, parent=self._open[-1] if self._open else -1,
                   rid=rid, n=n)
        self._open.append(len(self.spans))
        self.spans.append(rec)
        with annotation:
            rec.start_ns = time.perf_counter_ns()
            try:
                yield
            finally:
                rec.end_ns = time.perf_counter_ns()
                self._open.pop()
