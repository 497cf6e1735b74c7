"""What the harness records around the program, from its own files.

The program has no spans of its own, so the harness wraps methods on the
engine *instance* (never on its class, never in the program's files):

* always: when each output token reaches the host (``_admit`` yields a
  request's first token, ``_step_active`` one more for each request it
  decodes), and which restores repointed or streamed pages of which request;
* with spans on (the traced run): a span around each engine method of a
  layer, also written into the profiler's trace as a
  ``jax.profiler.TraceAnnotation`` so host spans and device events share one
  clock.  The model-step spans wait for their result
  (``block_until_ready``), so device time of the step is not booked to the
  scheduler that reads the result right after.

Compiles are counted from JAX's monitoring events.
"""
from __future__ import annotations

import time
from collections import defaultdict
from dataclasses import dataclass, field

# layer of each wrapped engine method
SPAN_LAYERS = {
    "step": "scheduler",
    "_admit": "scheduler",
    "_resume": "scheduler",
    "_step_active": "scheduler",
    "_make_room": "orchestration",
    "_preempt": "orchestration",
    "_flush_demoted": "orchestration",
    "_restore": "orchestration",
    "_note_allocated": "orchestration",
    "_prefill_one": "model",
    "_decode_jit": "model",
}
BLOCKING = {"_prefill_one", "_decode_jit"}


@dataclass
class Span:
    name: str
    start_ns: int
    end_ns: int
    depth: int


@dataclass
class Recorder:
    """Token times, restore records, spans and compiles of one run."""
    token_times: dict = field(default_factory=lambda: defaultdict(list))
    restores: list = field(default_factory=list)
    spans: list = field(default_factory=list)
    decode_calls: list = field(default_factory=list)
    prefills: list = field(default_factory=list)
    compiles: list = field(default_factory=list)
    _depth: int = 0

    # ---------------------------------------------------------- compiles
    def listen_compiles(self) -> None:
        import jax

        def on_duration(event, duration, **kw):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles.append((time.perf_counter_ns(),
                                      kw.get("fun_name", "?"), duration))
        jax.monitoring.register_event_duration_secs_listener(on_duration)

    def compiles_between(self, t0_ns: int, t1_ns: int) -> list:
        return [c for c in self.compiles if t0_ns <= c[0] <= t1_ns]

    # ------------------------------------------------------------ tokens
    def install(self, eng, spans: bool) -> None:
        """Wrap ``eng``'s methods; ``spans`` adds the layer spans."""
        if spans:
            for name in SPAN_LAYERS:
                self._wrap_span(eng, name)
        admit, step_active, restore = (eng._admit, eng._step_active,
                                       eng._restore)

        def _admit(req):
            n = len(req.tokens_out)
            ok = admit(req)
            if len(req.tokens_out) > n:
                self.token_times[req.rid].append(time.perf_counter_ns())
                self.prefills.append((time.perf_counter_ns(), len(req.prompt)))
            return ok

        def _step_active(active, greedy):
            before = [(r, len(r.tokens_out)) for r in active]
            out = step_active(active, greedy)
            now = time.perf_counter_ns()
            contexts = []
            for r, n in before:
                if len(r.tokens_out) > n:
                    self.token_times[r.rid].append(now)
                    # the step fed token n-1 of the output at position
                    # len(prompt) + n - 1 and attended over it and all
                    # before it
                    contexts.append(len(r.prompt) + n)
            if contexts:
                self.decode_calls.append((now, contexts))
            return out

        def _restore(req):
            st = eng.stats
            rp, sp = st.repointed_pages, st.streamed_pages
            ok = restore(req)
            drp, dsp = st.repointed_pages - rp, st.streamed_pages - sp
            if drp or dsp:
                self.restores.append((req.rid, len(req.tokens_out), drp, dsp))
            return ok

        eng._admit, eng._step_active, eng._restore = (_admit, _step_active,
                                                      _restore)

    # ------------------------------------------------------------- spans
    def _wrap_span(self, eng, name: str) -> None:
        import jax
        from jax.profiler import TraceAnnotation
        inner = getattr(eng, name)
        block = name in BLOCKING

        def wrapped(*args, **kw):
            self._depth += 1
            t0 = time.perf_counter_ns()
            try:
                with TraceAnnotation(name):
                    out = inner(*args, **kw)
                    if block:
                        jax.block_until_ready(out)
                return out
            finally:
                self._depth -= 1
                self.spans.append(Span(name, t0, time.perf_counter_ns(),
                                       self._depth))
        setattr(eng, name, wrapped)


def union_ns(intervals) -> int:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def layer_time_ns(spans, layer: str, t0: int, t1: int) -> int:
    """Self time of ``layer`` inside ``[t0, t1]``: the time its spans cover
    less the time covered by the spans nested directly in them (spans nest,
    since they wrap calls on one thread)."""
    iv = sorted(((max(s.start_ns, t0), min(s.end_ns, t1), s.name)
                 for s in spans if s.end_ns > t0 and s.start_ns < t1),
                key=lambda x: (x[0], -x[1]))
    self_ns = [e - s for s, e, _ in iv]
    stack: list = []
    for i, (s, e, _) in enumerate(iv):
        while stack and iv[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= e - s
        stack.append(i)
    return sum(t for t, (_, _, n) in zip(self_ns, iv)
               if SPAN_LAYERS.get(n) == layer)
