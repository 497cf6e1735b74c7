"""Reduction of the profiler's trace of the measured window.

``jax.profiler`` writes an ``.xplane.pb``; ``ProfileData`` reads it with
nothing but JAX.  A device plane (``/device:TPU:<n>``) has a line of XLA
modules (one event per program run) and a line of XLA ops (one event per
operation).  The host plane carries the harness's ``TraceAnnotation``
spans, among them ``window``, which ties the trace's clock to the
harness's: its start is the window's start.

The reduction works on plain tuples, so a small recorded trace (see
``tests/data``) checks it without a chip.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

from chipbench.spans import union_ns

# substring of a program's module name -> what the metrics call it
MODULES = {"decode": "_decode_fn", "prefill": "jit_fn",
           "read_pages": "_read_pages_jit", "stream_page": "_stream_page_jit"}


@dataclass
class Events:
    """Device events and the window annotation, in the trace's clock."""
    ops: list = field(default_factory=list)       # (name, start_ns, dur_ns)
    modules: list = field(default_factory=list)   # (name, start_ns, dur_ns)
    window_start_ns: float | None = None
    n_devices: int = 0
    layout: list = field(default_factory=list)


def load_events(trace_dir: str) -> Events:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(paths[-1])
    ev = Events()
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")]
    ev.n_devices = len(devices)
    # one chip's plane: the cells run on one device
    for plane in sorted(devices, key=lambda p: p.name)[:1]:
        for line in plane.lines:
            if line.name == "XLA Ops":
                ev.ops = [(e.name, e.start_ns, e.duration_ns)
                          for e in line.events]
            elif line.name == "XLA Modules":
                ev.modules = [(e.name, e.start_ns, e.duration_ns)
                              for e in line.events]
    ev.layout = [(p.name, [ln.name for ln in p.lines]) for p in pd.planes]
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "window":
                    ev.window_start_ns = e.start_ns
    return ev


def module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name)


@dataclass
class TraceData:
    busy_s: float | None
    modules: list            # (name, start_ns, dur_ns) inside the window
    breakdown: dict
    op_totals: list

    def module_durations(self, kind: str) -> list:
        key = MODULES[kind]
        return [d for n, _, d in self.modules if key in n]


def reduce_events(ev: Events, window_ns: float, spans=(),
                  span_offset_ns: float = 0.0) -> TraceData:
    """``window_ns``: the window's length.  ``spans``: the harness's spans
    (``chipbench.spans.Span``), whose clock is the trace's less
    ``span_offset_ns``."""
    if ev.window_start_ns is None:
        raise ValueError("the trace has no 'window' annotation")
    w0, w1 = ev.window_start_ns, ev.window_start_ns + window_ns

    def inside(events):
        return [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                for n, s, d in events if s + d > w0 and s < w1]

    ops, mods = inside(ev.ops), inside(ev.modules)
    busy = union_ns([(s, s + d) for _, s, d in (ops or mods)])
    by_module = defaultdict(float)
    for n, _, d in mods:
        by_module[module_name(n)] += d / 1e9
    by_op = defaultdict(float)
    for n, _, d in ops:
        by_op[n] += d / 1e9
    ordered = sorted(spans, key=lambda s: s.start_ns)
    starts = [s.start_ns for s in ordered]
    named = defaultdict(float)
    for g0, g1 in _idle_gaps(ops or mods, w0, w1):
        named[_span_at((g0 + g1) / 2 - span_offset_ns, starts, ordered)] += (
            (g1 - g0) / 1e9)

    def top(d):
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                ][:10]

    return TraceData(
        busy_s=busy / 1e9 if (ops or mods) else None,
        modules=mods,
        breakdown={"device_ops": top(by_module), "idle_gaps": top(named)},
        op_totals=top(by_op))


def _idle_gaps(events, w0, w1):
    iv = sorted((s, s + d) for _, s, d in events)
    gaps, cur = [], w0
    for s, e in iv:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if w1 > cur:
        gaps.append((cur, w1))
    return gaps


def _span_at(t: float, starts: list, ordered: list) -> str:
    """Name of the innermost span that covers ``t``: spans nest, so walking
    back from the last one that starts before ``t``, the first that has
    not ended is the innermost."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        if ordered[i].end_ns >= t:
            return ordered[i].name
        i -= 1
    return "(no span)"


def read_trace(trace_dir: str, served, keep: str | None = None) -> TraceData:
    import sys
    ev = load_events(trace_dir)
    offset = ev.window_start_ns - served.t0_ns
    data = reduce_events(ev, served.t1_ns - served.t0_ns,
                         served.rec.spans, offset)
    print(f"trace layout: {ev.layout}", file=sys.stderr, flush=True)
    if keep:
        _keep(ev, served, offset, keep)
    print(f"trace: {len(ev.ops)} op events, {len(ev.modules)} module events "
          f"on {ev.n_devices} device plane(s); busy {data.busy_s} s; "
          f"top ops {data.op_totals}", file=sys.stderr, flush=True)
    return data


def _keep(ev: Events, served, offset: float, path: str,
          half_ns: float = 0.25e9) -> None:
    """Write half a second of the window around its first decode step as a
    small JSON trace: device events (op names cut to 48 characters), the
    harness's spans that overlap it, all on the trace's clock from the
    slice's start."""
    import json
    dec = [s for n, s, _ in ev.modules if MODULES["decode"] in n
           and s >= ev.window_start_ns]
    mid = dec[0] if dec else ev.window_start_ns + half_ns
    w0, w1 = max(ev.window_start_ns, mid - half_ns), mid + half_ns

    def cut(events):
        return [[n[:48], s - w0, d] for n, s, d in events
                if s + d > w0 and s < w1]
    spans = [[s.name, max(s.start_ns + offset, w0) - w0,
              min(s.end_ns + offset, w1) - w0, s.depth]
             for s in served.rec.spans
             if s.end_ns + offset > w0 and s.start_ns + offset < w1]
    with open(path, "w") as f:
        json.dump({"window_ns": w1 - w0, "ops": cut(ev.ops),
                   "modules": cut(ev.modules), "spans": spans}, f)
