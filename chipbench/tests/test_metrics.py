"""The reduction from spans, counters and trace events to metrics."""
import json
import os

import pytest

from chipbench import spans as S
from chipbench import trace as TR
from conftest import DATA


def test_self_time_leaves_out_nested_spans_of_other_layers():
    sp = [S.Span("step", 0, 100, 0), S.Span("_admit", 10, 40, 1),
          S.Span("_make_room", 15, 35, 2), S.Span("_flush_demoted", 20, 30, 3),
          S.Span("_step_active", 50, 95, 1), S.Span("_decode_jit", 60, 90, 2)]
    # scheduler: 100 less orchestration 15..35 (20) less model 60..90 (30)
    assert S.layer_time_ns(sp, "scheduler", 0, 100) == 50
    assert S.layer_time_ns(sp, "orchestration", 0, 100) == 20
    assert S.layer_time_ns(sp, "model", 0, 100) == 30
    # clipped to a window
    assert S.layer_time_ns(sp, "model", 0, 70) == 10
    assert S.union_ns([(0, 10), (5, 20), (30, 40)]) == 30


def test_idle_gaps_are_named_by_the_innermost_span():
    ev = TR.Events(ops=[("fusion.1", 1000, 100), ("fusion.2", 1300, 100)],
                   modules=[("jit__decode_fn(3)", 1000, 400)],
                   window_start_ns=1000)
    sp = [S.Span("step", 0, 600, 0), S.Span("_restore", 150, 350, 1)]
    data = TR.reduce_events(ev, 600, sp, span_offset_ns=1000)
    assert data.busy_s == pytest.approx(200e-9)
    assert dict(data.breakdown["idle_gaps"]) == pytest.approx(
        {"_restore": 200e-9, "step": 200e-9})
    assert data.breakdown["device_ops"] == [["jit__decode_fn", 400e-9]]
    assert data.module_durations("decode") == [400]


RECORDED = os.path.join(DATA, "trace_small.json")


def test_recorded_trace_reduces():
    """Half a second around a decode step of the granite cell, recorded on
    one v5e with ``run.py --trace 1 --keep-trace``."""
    with open(RECORDED) as f:
        rec = json.load(f)
    ev = TR.Events(ops=[tuple(o) for o in rec["ops"]],
                   modules=[tuple(m) for m in rec["modules"]],
                   window_start_ns=0.0)
    sp = [S.Span(n, s, e, d) for n, s, e, d in rec["spans"]]
    data = TR.reduce_events(ev, rec["window_ns"], sp)
    assert 0 < data.busy_s <= rec["window_ns"] / 1e9
    assert data.module_durations("decode")
    names = {n for n, _ in data.breakdown["idle_gaps"]}
    assert names <= set(S.SPAN_LAYERS) | {"(no span)"}
