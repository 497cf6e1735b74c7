#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell's configuration and traffic are found by the names in its
``BENCHMARK.json`` entry.  In order:

1. print the platform, device kind and count; exit non-zero without a TPU
   or with fewer chips than the cell asks for (there is no CPU fallback);
2. turn on the program's persistent compilation cache
   (``launch/compile_cache.py``: ``$JAX_COMPILATION_CACHE_DIR``, else the
   checkout's ``.jax_cache``);
3. make the weights on the device from the seed, in one jitted call of the
   program's ``init_params``, in the type they are served in;
4. build ``ValetServeEngine`` (policy valet, zero-restore) and warm every
   program the window can use: each prompt length's prefill, the decode
   step, ``read_pages`` at every power of two up to the pool and
   ``stream_page``; then run the traffic until every client's request has
   been admitted and a restore has happened;
5. measure for ``--seconds`` through ``submit`` and ``step()``;
6. read the peak device memory, free the program's state and compare a
   sample of the window's requests, drawn from the seed, over every token
   they were served, with the plain reference;
7. print the compared numbers with their limits on standard error, and one
   JSON line on standard output: the end-to-end metrics with ``--trace 0``,
   the per-layer ones (read by ``metrics/<name>.py``) with ``--trace 1``.
"""
from __future__ import annotations

import time

T_PROCESS_NS = time.perf_counter_ns()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench import cells as C  # noqa: E402
from chipbench.spans import Recorder  # noqa: E402
from chipbench.traffic import ClosedLoop  # noqa: E402

OUT_DIR = os.path.join(ROOT, ".chipbench_out")
WARM_CAP_S = 240.0           # warm-up traffic must reach steady state by then
SAMPLE_ROWS = 6              # sequences the reference reads per run


class NoChip(RuntimeError):
    pass


def weights_key(seed: int):
    import jax
    return jax.random.PRNGKey(seed % (1 << 32))


# ------------------------------------------------------------------ device

def require_chip(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", file=sys.stderr, flush=True)
    if info["platform"] != "tpu":
        raise NoChip(f"no TPU: JAX reports platform {info['platform']!r}; "
                     f"the benchmark has no CPU fallback")
    if info["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{info['count']}")
    return info


def memory_peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


# ----------------------------------------------------------------- serving

@dataclass
class Served:
    """What a run's window produced, for the metrics and the check."""
    t0_ns: int
    t1_ns: int
    setup_s: float
    stats0: dict
    stats1: dict
    rec: Recorder
    requests: dict                       # rid -> Request
    window_rids: list
    memory_peak_bytes: int | None
    trace_dir: str | None = None
    step_ns: list = field(default_factory=list)   # each window step's time
    gc_ns: list = field(default_factory=list)     # collector pauses in it

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def window_token_times(self):
        for rid, ts in self.rec.token_times.items():
            yield rid, [t for t in ts if self.t0_ns < t <= self.t1_ns]

    @property
    def window_tokens(self) -> int:
        return sum(len(ts) for _, ts in self.window_token_times())

    def gaps_ms(self) -> list:
        out = []
        for _, ts in self.window_token_times():
            out.extend((b - a) / 1e6 for a, b in zip(ts, ts[1:]))
        return out


def _stats(eng) -> dict:
    s = eng.stats
    return {k: getattr(s, k) for k in (
        "steps", "tokens", "pauses", "demoted_pages", "flushed_pages",
        "repointed_pages", "streamed_pages", "restored_pages")}


def build(cell: C.Cell, seed: int):
    """The program's model and engine for ``cell``, weights from ``seed``."""
    import jax
    import jax.numpy as jnp
    from repro.core.policies import VALET
    from repro.models import transformer as T
    from repro.serve import ValetServeEngine

    cfg = C.program_config(cell.config)
    dtype = {"bfloat16": jnp.bfloat16,
             "float32": jnp.float32}[cell.config["dtype"]]
    ctx = T.ParallelCtx(remat=False, compute_dtype=dtype,
                        **cell.config.get("ctx", {}))
    init = jax.jit(T.init_params, static_argnames=("cfg", "dtype"))
    params = init(weights_key(seed), cfg, dtype)
    jax.block_until_ready(params)
    geo = cell.geometry
    eng = ValetServeEngine(params, cfg, ctx, max_batch=geo.max_batch,
                           max_seq=geo.max_seq, page=geo.page,
                           pool_slots=geo.pool_slots, policy=VALET,
                           zero_restore=True, seed=seed % (1 << 31))
    return cfg, eng


def warm_programs(eng, cell: C.Cell) -> None:
    """Call each program the window can use once, at every shape it can be
    called with, through the program's own jitted functions."""
    import jax
    import numpy as np
    from repro.core import device_ops as dev

    geo = cell.geometry
    for plen in sorted(set(cell.traffic["prompt_lengths"])):
        bt = np.full((eng.max_pages,), -1, np.int32)
        n = -(-(plen + 1) // eng.page)
        bt[:n] = np.arange(n)
        jax.block_until_ready(
            eng._prefill_one(np.full((plen,), 2, np.int32), 0, bt))
    b = geo.max_batch
    logits, caches = eng._decode_jit(
        eng.params, eng.caches, jax.numpy.zeros((b,), jax.numpy.int32),
        jax.numpy.full((b, eng.max_pages), -1, jax.numpy.int32),
        jax.numpy.zeros((b,), jax.numpy.int32),
        jax.numpy.zeros((b,), jax.numpy.int32),
        jax.numpy.zeros((b,), bool))
    jax.block_until_ready(logits)
    del logits, caches
    pools = eng._paged_pools()
    n, top = 1, 1 << (geo.pool_slots - 1).bit_length()
    page = None
    while n <= top:
        pages = dev.read_pages(pools, list(range(min(n, geo.pool_slots))))
        if page is None:
            page = dev.from_host_tier(dev.to_host_tier(pages[:1]),
                                      pools[0].k)[0]
        jax.block_until_ready(pages)
        del pages
        n *= 2
    eng._set_paged_pools(dev.stream_page(pools, page[0], page[1], 0))
    jax.block_until_ready(eng._paged_pools())


def serve(cell: C.Cell, seed: int, seconds: float, trace: bool,
          eng, rec: Recorder, t_process_ns: int) -> Served:
    """Warm-up traffic and the measured window."""
    import jax

    gen = ClosedLoop(cell.traffic, eng.cfg.vocab, seed)
    client_of = {}

    def submit(client):
        spec = gen.next_request(client)
        rid = eng.submit(spec.prompt, spec.max_new)
        client_of[rid] = client

    for c in range(gen.clients):
        submit(c)
    reqs = eng._requests

    def resubmit():
        for rid, c in list(client_of.items()):
            if reqs[rid].status == "done":
                del client_of[rid]
                submit(c)

    # warm-up traffic: every client admitted and, where the clients' KV can
    # outgrow the pool, a restore made
    t_warm = time.monotonic()
    first = set(client_of)
    geo = cell.geometry
    longest = max(cell.traffic["prompt_lengths"]) + cell.traffic[
        "output_range"][1]
    oversub = gen.clients * -(-longest // geo.page) > geo.pool_slots
    while not (all(reqs[r].tokens_out for r in first) and (
            not oversub
            or eng.stats.repointed_pages + eng.stats.streamed_pages > 0)):
        if time.monotonic() - t_warm > WARM_CAP_S:
            raise RuntimeError("warm-up traffic made no restore in "
                               f"{WARM_CAP_S} s")
        eng.step()
        resubmit()

    trace_dir = None
    if trace:
        trace_dir = os.path.join(OUT_DIR, "trace")
        _rmtree(trace_dir)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    stats0 = _stats(eng)
    step_ns, pauses = [], GcPauses()
    t0 = time.perf_counter_ns()
    deadline = t0 + int(seconds * 1e9)
    with jax.profiler.TraceAnnotation("window"), pauses:
        while (a := time.perf_counter_ns()) < deadline:
            eng.step()
            resubmit()
            step_ns.append(time.perf_counter_ns() - a)
    t1 = time.perf_counter_ns()
    stats1 = _stats(eng)
    if trace:
        jax.profiler.stop_trace()
    peak = memory_peak_bytes()

    in_window = [rid for rid, ts in rec.token_times.items()
                 if any(t0 < t <= t1 for t in ts)]
    return Served(t0, t1, (t0 - t_process_ns) / 1e9, stats0, stats1, rec,
                  dict(reqs), sorted(in_window), peak, trace_dir, step_ns,
                  pauses.ns)


class GcPauses:
    """The collector's pauses while the context is open, in ns each."""

    def __init__(self):
        self.ns: list = []
        self._t = 0

    def _note(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter_ns()
        else:
            self.ns.append(time.perf_counter_ns() - self._t)

    def __enter__(self):
        gc.callbacks.append(self._note)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._note)


def free_engine(eng) -> None:
    import jax
    for a in jax.tree.leaves(eng.caches):
        a.delete()
    for a in jax.tree.leaves(eng.params):
        a.delete()
    eng.host.blobs.clear()
    eng.caches = eng.params = None


def _rmtree(path: str) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)


# ------------------------------------------------------------- correctness

def choose_sample(served: Served, seed: int) -> list:
    """Rids of the reference's sample, drawn from the seed among the
    requests that emitted a token in the window: the longest, one with
    streamed pages and one with repointed pages (where there are such),
    then others, up to ``SAMPLE_ROWS``.  Each is compared over every token
    it was served up to the window's close; at these rates no request runs
    from its admission to its end inside one window."""
    import numpy as np
    reqs = served.requests
    cands = list(served.window_rids)
    if not cands:
        return []
    rng = np.random.default_rng(np.random.SeedSequence([seed, 7]))
    order = [cands[i] for i in rng.permutation(len(cands))]
    pick = [max(order, key=lambda r: len(reqs[r].prompt)
                + len(reqs[r].tokens_out))]
    restored: dict = {}
    for rid, _, rp, sp in served.rec.restores:
        got = restored.setdefault(rid, [0, 0])
        got[0] += rp
        got[1] += sp
    for want in (1, 0):                # streamed first, then repointed
        if any(restored.get(r, [0, 0])[want] for r in pick):
            continue
        for rid in order:
            if rid not in pick and restored.get(rid, [0, 0])[want]:
                pick.append(rid)
                break
    pick += [r for r in order if r not in pick]
    return pick[:SAMPLE_ROWS]


def reference_module(cell: C.Cell):
    return importlib.import_module(
        f"chipbench.configs.{cell.config['reference']}")


def check(cell: C.Cell, seed: int, served: Served, control: bool = False):
    """Compare the sample with the reference; returns (numbers, details).
    ``numbers`` maps each compared quantity to ``(value, limit, ok)``."""
    import numpy as np
    reqs = served.requests
    sample = choose_sample(served, seed)
    rows = [np.concatenate([reqs[r].prompt, np.asarray(reqs[r].tokens_out,
                                                       np.int32)])
            for r in sample]
    plens = [len(reqs[r].prompt) for r in sample]
    ref = reference_module(cell)
    gaps, ctrl = ([], [])
    if rows:
        # a fixed number of rows, so the reference compiles one shape
        pad = SAMPLE_ROWS - len(rows)
        gaps, ctrl = ref.served_gaps(cell.config, weights_key(seed),
                                     rows + rows[:1] * pad,
                                     plens + plens[:1] * pad,
                                     cell.geometry.max_seq, control=control)
        gaps, ctrl = gaps[:len(rows)], ctrl[:len(rows)]
    # positions decoded after a restore of the request's pages
    need = int(served.stats1["restored_pages"] > served.stats0["restored_pages"])
    after = 0
    for r in sample:
        firsts = [n for rid, n, _, _ in served.rec.restores if rid == r]
        if firsts:
            after += len(reqs[r].tokens_out) - min(firsts)
    checked = sum(g.size for g in gaps)
    limit = cell.config.get("gap_limit")
    numbers = judge(_widest(gaps), limit, checked, after, need)
    details = {"sample": sample, "rows": len(rows),
               "gaps": [float(g.max()) if g.size else None for g in gaps]}
    if control:
        # the control in the program's place: the token it puts first is
        # the one served at each position, judged by the same comparison
        details["control"] = judge(_widest(ctrl), limit, checked, after,
                                   need)
        details["control_gaps"] = [float(g.max()) if g.size else None
                                   for g in ctrl]
    return numbers, details


def _widest(gaps):
    return max((float(g.max()) for g in gaps if g.size), default=None)


def judge(widest, limit, checked: int, after: int, need: int) -> dict:
    """Each compared quantity as ``(value, limit, ok)``."""
    return {
        "widest_gap": (widest, limit,
                       widest is not None and limit is not None
                       and widest <= limit),
        "tokens_checked": (checked, 1, checked >= 1),
        # where the window restored pages, the sample must hold tokens
        # decoded after a restore
        "tokens_after_restore": (after, need, after >= need),
    }


# ----------------------------------------------------------------- metrics

def end_to_end(served: Served) -> dict:
    import numpy as np
    gaps = served.gaps_ms()
    out = {"output_tokens_per_s": (served.window_tokens / served.window_s,
                                   "tokens/s"),
           "setup_s": (served.setup_s, "s")}
    if gaps:
        out["itl_p95_ms"] = (float(np.percentile(gaps, 95)), "ms")
    return out


def per_layer(cell: C.Cell, bench: dict, served: Served, device: dict,
              trace_data) -> dict:
    from chipbench.metrics import RunData
    data = RunData(cell=cell, served=served, device=device, trace=trace_data)
    out = {}
    for m in bench["per_layer"]:
        if cell.name not in m.get("workloads", [cell.name]):
            continue
        reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
        value = reader.read(data)
        if value is not None:
            out[m["name"]] = (value, m["unit"])
    return out


# -------------------------------------------------------------------- main

def enable_cache() -> str:
    """The program's persistent compilation cache, every program in it."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache


def prepare(cell: C.Cell, seed: int, spans: bool, rec: Recorder):
    """Weights, engine and every program warm: the set-up of a run."""
    import jax
    cfg, eng = build(cell, seed)
    rec.install(eng, spans=spans)
    # The engine makes its pools uncommitted and they become committed at
    # the first restore that streams a page (``from_host_tier`` places on a
    # device), which changes every program's argument mapping and compiles
    # decode and ``read_pages`` again mid-run.  Placing the caches on the
    # device first gives the warm-up the mapping the window sees.
    eng.caches = jax.device_put(eng.caches, jax.devices()[0])
    warm_programs(eng, cell)
    return eng


def run_cell(cell: C.Cell, bench: dict, seed: int, seconds: float,
             trace: bool, info: dict, t_process_ns: int = T_PROCESS_NS,
             keep_trace: str | None = None):
    """Everything after the device check; returns the result dict."""
    print(f"compile cache: {enable_cache()}", file=sys.stderr, flush=True)
    rec = Recorder()
    rec.listen_compiles()
    eng = prepare(cell, seed, trace, rec)
    served = serve(cell, seed, seconds, trace, eng, rec, t_process_ns)
    device = dict(info, memory_peak_bytes=served.memory_peak_bytes)
    _log_window(cell, served)

    trace_data = None
    if trace:
        from chipbench.trace import read_trace
        trace_data = read_trace(served.trace_dir, served, keep_trace)
        _rmtree(served.trace_dir)
        device["busy_s"] = trace_data.busy_s
        device["window_s"] = served.window_s
    free_engine(eng)
    del eng
    gc.collect()
    t_ref = time.monotonic()
    numbers, details = check(cell, seed, served)
    print(f"reference: {details} in {time.monotonic() - t_ref:.2f} s",
          file=sys.stderr, flush=True)

    metrics = (per_layer(cell, bench, served, device, trace_data) if trace
               else end_to_end(served))
    result = {
        "correct": all(ok for _, _, ok in numbers.values()),
        "attempted": len(served.window_rids),
        "failed": 0,
        "metrics": {k: {"value": v, "unit": u} for k, (v, u)
                    in metrics.items()},
        "device": device,
    }
    if trace_data is not None:
        result["breakdown"] = trace_data.breakdown
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim, _) in numbers.items()}
    return result, numbers


def _log_window(cell: C.Cell, served: Served) -> None:
    s0, s1 = served.stats0, served.stats1
    d = {k: s1[k] - s0[k] for k in s0}
    comp = served.rec.compiles_between(served.t0_ns, served.t1_ns)
    print(f"window: {cell.name} {served.window_s:.3f} s, "
          f"{served.window_tokens} tokens, {len(served.gaps_ms())} gaps, "
          f"{len(served.window_rids)} requests, {d['steps']} steps, "
          f"{d['pauses']} pauses, {d['demoted_pages']} demoted, "
          f"{d['flushed_pages']} flushed, {d['repointed_pages']} repointed, "
          f"{d['streamed_pages']} streamed pages, {len(comp)} compiles "
          f"{sorted({c[1] for c in comp})}, setup {served.setup_s:.2f} s",
          file=sys.stderr, flush=True)
    steps = sorted(served.step_ns)
    if steps:
        print(f"window steps: median {steps[len(steps) // 2] / 1e6:.2f} ms, "
              f"longest {[round(t / 1e6, 2) for t in steps[-6:][::-1]]} ms; "
              f"{len(served.gc_ns)} collector pauses, "
              f"{sum(served.gc_ns) / 1e6:.2f} ms in all",
              file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None,
                    help="with --trace 1: write the window's first seconds "
                         "as a small JSON trace to this path")
    args = ap.parse_args(argv)
    bench = C.load_benchmark()
    cell = C.find_cell(args.workload, bench)
    info = require_chip(cell.chips)
    result, numbers = run_cell(cell, bench, args.seed, args.seconds,
                               bool(args.trace), info,
                               keep_trace=args.keep_trace)
    for k, (v, lim, ok) in numbers.items():
        print(f"check {k}: {v} limit {lim} {'ok' if ok else 'FAILED'}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except (NoChip, C.CellError) as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(2)
