"""The harness end to end on the CPU at a tiny width: it refuses to run
without a TPU, it passes a sound run, and ``correct`` comes out false when
the timed path is broken underneath it."""
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT, tiny_cell


def _run(cell, monkeypatch, fault=None, seed=2 ** 31 + 3, trace=False):
    from chipbench import cells as C
    from chipbench import run as R
    if fault is not None:
        prepare = R.prepare

        def broken(*a, **kw):
            eng = prepare(*a, **kw)
            fault(eng)
            return eng
        monkeypatch.setattr(R, "prepare", broken)
    bench = C.load_benchmark()
    bench["per_layer"] = [dict(m, workloads=[cell.name])
                          for m in bench["per_layer"]]
    info = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}
    result, numbers = R.run_cell(cell, bench, seed, 1.5, trace, info)
    return result, numbers


def test_refuses_without_a_tpu(tmp_path):
    from chipbench import cells as C
    # a directory with BENCHMARK.json and the benchmark's files only
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for w in C.load_benchmark()["workloads"]:
        for cwd in (ROOT, str(tmp_path)):
            p = subprocess.run(
                [sys.executable, "chipbench/run.py", "--workload", w["name"],
                 "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=cwd,
                env=env, capture_output=True, text=True, timeout=300)
            assert p.returncode != 0
            assert p.stdout.strip() == ""
            assert "no TPU" in p.stderr, p.stderr[-2000:]


def test_sound_run_is_correct(no_cache, monkeypatch):
    result, numbers = _run(tiny_cell(), monkeypatch)
    assert result["correct"], numbers
    assert list(result)[-1] == "checks"
    assert set(result["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                      "setup_s"}
    assert result["attempted"] > 0 and result["failed"] == 0
    assert numbers["tokens_after_restore"][0] >= 1


def test_run_that_fits_the_pool_needs_no_restore(no_cache, monkeypatch):
    from chipbench import cells as C
    from conftest import DATA
    cell = tiny_cell()
    cell.traffic = C.load_traffic("tiny-fit", os.path.join(DATA, "traffic"))
    result, numbers = _run(cell, monkeypatch)
    assert result["correct"], numbers
    assert numbers["tokens_after_restore"][1] == 0


def test_traced_run_reports_host_metrics(no_cache, monkeypatch):
    result, _ = _run(tiny_cell(), monkeypatch, trace=True)
    m = result["metrics"]
    assert m["compiles_in_window"]["value"] == 0
    assert m["sched_self_ms_per_token"]["value"] > 0
    # no device plane on the CPU: the device readers find nothing
    assert "decode_roofline" not in m and "device_idle_share" not in m


def _alter_token(eng):
    decode = eng._decode_jit

    def wrong(*a):
        logits, caches = decode(*a)
        return np.roll(np.asarray(logits), 1, axis=-1), caches
    eng._decode_jit = wrong


def _state_unchanged(eng):
    decode = eng._decode_jit

    def frozen(params, caches, *a):
        logits, new = decode(params, caches, *a)
        return logits, dict(new, layers=caches["layers"])
    eng._decode_jit = frozen


def _half_batch(eng):
    decode = eng._decode_jit
    calls = [0]

    def half(params, caches, toks, bt, slot, off, act):
        # the other half on each call, so every slot is left out in turn
        act = np.asarray(act).copy()
        act[calls[0] % 2::2] = False
        calls[0] += 1
        return decode(params, caches, toks, bt, slot, off, act)
    eng._decode_jit = half


def _corrupt_restore(eng):
    from repro.core import device_ops as dev
    stream = dev.stream_page

    def zeros(pools, k, v, slot):
        return stream(pools, k * 0, v * 0, slot)
    dev.stream_page = zeros


@pytest.mark.parametrize("fault", [_alter_token, _state_unchanged,
                                   _half_batch, _corrupt_restore],
                         ids=["token_altered", "state_unchanged",
                              "half_batch_left_out", "restored_page_altered"])
def test_broken_timed_path_is_not_correct(no_cache, monkeypatch, fault):
    from repro.core import device_ops as dev
    monkeypatch.setattr(dev, "stream_page", dev.stream_page)
    result, numbers = _run(tiny_cell(), monkeypatch, fault)
    assert not result["correct"], numbers
    assert not numbers["widest_gap"][2]


def test_control_fails_the_limit_the_program_keeps(no_cache):
    """The float8 control against the limit of a bfloat16 configuration,
    at a tiny width (the chip's readings at the cells' own sizes are in
    PERF.md)."""
    from chipbench import control as K
    cell = tiny_cell("tiny-wide-bf16")
    limit = cell.config["gap_limit"]
    for seed in (1, 3, 2 ** 31 + 5):
        r = K.readings(cell, seed, 1.5)
        assert r["program_widest_gap"] <= limit < r["control_widest_gap"], r
        assert r["program_correct"] and not r["control_correct"], r
