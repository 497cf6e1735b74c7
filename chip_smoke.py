#!/usr/bin/env python3
"""Chip smoke run: the Valet serve engine at phi3-mini-3.8b's published
widths on one TPU.

    python chip_smoke.py              # one chip: the serving phases
    python chip_smoke.py --chips 4    # four chips: the sharded train step only

One process drives the chip.  Phases run in order and any failure is fatal:

1. device: print what JAX reports; anything but a TPU exits non-zero.
2. load: phi3-mini-3.8b at its published widths, bfloat16 weights made from
   a seed (nothing is downloaded).
3. roomy run: 8 requests (prompt 256, 64 new tokens, greedy) through
   ``ValetServeEngine`` with a KV pool that holds all of them: no pauses.
4. pressured run: the same requests with a pool of about 40% of their
   pages (policy valet, zero-restore).  Sequences are preempted, their
   pages demoted, flushed to the pinned-host tier and restored; the greedy
   tokens must equal the roomy run's.
5. reference: the engine's first-token logits for one prompt against
   ``models.transformer.prefill_logits`` on the same weights in float32,
   computed before serving from the stacked weights, which are then split
   per layer once (``decode.take_layers``) for both engines.

``--chips 4`` runs one phase instead: a train step at phi3's widths, depth
cut to 2 layers, on a (data 2, model 2) mesh against the same step on one
device of the host.

Lines before the last are smoke output, not measurements.  The last line
is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time
from collections import defaultdict
from dataclasses import dataclass

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "phi3-mini-3.8b"
# Relative L2 bound on the engine's bfloat16 first-token logits against the
# float32 reference, set before the first chip run: a CPU rehearsal at all
# 32 layers and a quarter of the width gave 0.039-0.041 (PERF.md).  A wrong
# mask, position or page gives errors of order 1.
LOGIT_REL_L2_TOL = 0.1


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


@dataclass(frozen=True)
class Geometry:
    """Serving shape of the smoke run."""
    requests: int = 8
    prompt: int = 256
    new: int = 64
    batch: int = 8
    page: int = 16
    # 256 16-token slots per paged layer hold every request with room to
    # spare.  The v5e compiler's memory analysis puts the decode step from
    # the per-layer weights at 10.21 GiB (the KV pools count twice: the
    # step does not donate them), 5.5 GiB under the chip's 15.75;
    # tests/test_chip_compile.py keeps it at least 1 GiB under.
    roomy_slots: int = 256

    @property
    def pages_per_request(self) -> int:
        # the last generated token is never appended to the cache
        return -(-(self.prompt + self.new - 1) // self.page)

    @property
    def pressured_slots(self) -> int:
        """About 40% of the pages all requests need."""
        return math.ceil(0.4 * self.requests * self.pages_per_request)


# ---------------------------------------------------------------- device

def device_info() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def require_tpu(chips: int) -> dict:
    info = device_info()
    print(f"device: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        raise SmokeFailure(f"no TPU: JAX reports platform "
                           f"{info['platform']!r}; this smoke run needs the "
                           f"chip and has no CPU fallback")
    check(info["count"] >= chips,
          f"{chips} chips asked for, JAX sees {info['count']}")
    return info


class CompileLog:
    """Seconds per compiled program and persistent-cache hits and misses
    since the last ``report``, read from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.secs = defaultdict(float)
        self.count = defaultdict(int)
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        if event == "/jax/core/compile/backend_compile_duration":
            name = kw.get("fun_name", "?")
            self.secs[name] += duration
            self.count[name] += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def report(self, phase: str) -> None:
        for name in sorted(self.secs, key=self.secs.get, reverse=True)[:8]:
            print(f"  [{phase}] compile {name}: {self.secs[name]:.2f} s "
                  f"over {self.count[name]} compile(s)")
        print(f"  [{phase}] persistent cache: {self.cache_hits} hits, "
              f"{self.cache_misses} misses")
        self.secs.clear()
        self.count.clear()
        self.cache_hits = self.cache_misses = 0


def peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return str(stats.get("peak_bytes_in_use", "not reported"))


# ----------------------------------------------------------------- serve

def make_prompts(geo: Geometry, vocab: int, seed: int = 0):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, size=geo.prompt)
            for _ in range(geo.requests)]


def run_engine(params, cfg, ctx, prompts, geo: Geometry, pool_slots: int):
    """Serve ``prompts`` greedily; returns (engine, tokens per request)."""
    from repro.core.policies import VALET
    from repro.serve import ValetServeEngine
    eng = ValetServeEngine(params, cfg, ctx, max_batch=geo.batch,
                           max_seq=geo.prompt + geo.new, page=geo.page,
                           pool_slots=pool_slots, policy=VALET,
                           zero_restore=True)
    for p in prompts:
        eng.submit(p, geo.new)
    reqs = eng.run()
    check(all(r.status == "done" for r in reqs),
          f"unfinished requests: {[r.status for r in reqs]}")
    return eng, [r.tokens_out for r in sorted(reqs, key=lambda r: r.rid)]


def free_engine(eng) -> None:
    """Release an engine's device caches now, not when it is collected."""
    import jax
    for a in jax.tree.leaves(eng.caches):
        a.delete()
    eng.caches = None


def _print_stats(name: str, eng, secs: float) -> None:
    s = eng.stats
    print(f"  [{name}] pool_slots={eng.pool.size} steps={s.steps} "
          f"tokens={s.tokens} pauses={s.pauses} "
          f"demoted_pages={s.demoted_pages} flushed_pages={s.flushed_pages} "
          f"repointed_pages={s.repointed_pages} "
          f"streamed_pages={s.streamed_pages} spilled={s.spilled_pages} "
          f"restored={s.restored_pages} wall_s={secs:.2f}", flush=True)


def serve_check(params, cfg, ctx, geo: Geometry, prompts):
    """Roomy run, then pressured run; the pressured engine is returned
    (caches live) with both runs' tokens.  Raises ``SmokeFailure`` unless
    the roomy run never pauses, the pressured run preempts, demotes,
    flushes and restores, and both give the same greedy tokens."""
    t0 = time.monotonic()
    roomy, roomy_tokens = run_engine(params, cfg, ctx, prompts, geo,
                                     geo.roomy_slots)
    _print_stats("roomy", roomy, time.monotonic() - t0)
    check(roomy.stats.pauses == 0,
          f"roomy run paused {roomy.stats.pauses} times")
    free_engine(roomy)
    del roomy

    t0 = time.monotonic()
    eng, tokens = run_engine(params, cfg, ctx, prompts, geo,
                             geo.pressured_slots)
    _print_stats("pressured", eng, time.monotonic() - t0)
    s = eng.stats
    check(s.pauses > 0 and s.demoted_pages > 0 and s.flushed_pages > 0,
          f"pressured run did not preempt/demote/flush: pauses={s.pauses} "
          f"demoted={s.demoted_pages} flushed={s.flushed_pages}")
    check(s.repointed_pages + s.streamed_pages > 0,
          "pressured run restored no page")
    same = sum(a == b for a, b in zip(tokens, roomy_tokens))
    print(f"  [pressured] greedy tokens equal to roomy run for {same} of "
          f"{len(tokens)} requests", flush=True)
    check(tokens == roomy_tokens,
          "pressured run's greedy tokens differ from the roomy run's")
    return eng, roomy_tokens, tokens


def reference_logits(params, cfg, prompt):
    """``prefill_logits`` for ``prompt`` in float32 on the stacked weights:
    the first-token logits the engine's are held to, on the host."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as T

    ref_ctx = T.ParallelCtx(remat=False, compute_dtype=jnp.float32)
    with jax.default_matmul_precision("highest"):
        ref = jax.jit(lambda p, t: T.prefill_logits(p, t, cfg, ref_ctx))(
            params, jnp.asarray(prompt)[None])
    return np.asarray(ref[0], np.float32)[: cfg.vocab]


def reference_check(eng, ref, cfg, prompt) -> float:
    """Relative L2 error of the engine's first-token logits for ``prompt``
    against ``ref`` (``reference_logits``)."""
    import numpy as np

    # the engine's own prefill program (already compiled for this prompt
    # length) into slots 0.. of its now idle pool
    bt = np.full((eng.max_pages,), -1, np.int32)
    n = -(-(len(prompt) + 1) // eng.page)
    bt[:n] = np.arange(n)
    got = np.asarray(eng._prefill_one(prompt, 0, bt)[0], np.float32)
    got = got[: cfg.vocab]
    check(bool(np.isfinite(got).all()), "engine logits not finite")
    rel = float(np.linalg.norm(got - ref) / np.linalg.norm(ref))
    print(f"  [reference] logits {got.shape}: rel_l2={rel:.5f} "
          f"(bound {LOGIT_REL_L2_TOL}) max_abs={np.abs(got - ref).max():.4f} "
          f"argmax engine={int(got.argmax())} ref={int(ref.argmax())}",
          flush=True)
    check(rel <= LOGIT_REL_L2_TOL,
          f"first-token logits rel L2 {rel} above {LOGIT_REL_L2_TOL}")
    return rel


def serve_phases(clog: CompileLog) -> None:
    from repro.launch.serve import build_model
    from repro.models import decode as D

    t0 = time.monotonic()
    cfg, ctx, params = build_model(ARCH, local=False)
    import jax
    jax.block_until_ready(params)
    n = sum(a.size for a in jax.tree.leaves(params))
    print(f"load: {cfg.name} n_layers={cfg.n_layers} d_model={cfg.d_model} "
          f"n_heads={cfg.n_heads} n_kv_heads={cfg.n_kv_heads} "
          f"head_dim={cfg.resolved_head_dim} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab} params={n} dtype={ctx.compute_dtype.__name__} "
          f"wall_s={time.monotonic() - t0:.2f}", flush=True)
    clog.report("load")

    geo = Geometry()
    print(f"serve: requests={geo.requests} prompt={geo.prompt} "
          f"new={geo.new} batch={geo.batch} page={geo.page} "
          f"roomy_slots={geo.roomy_slots} "
          f"pressured_slots={geo.pressured_slots} "
          f"(pages needed {geo.requests * geo.pages_per_request})",
          flush=True)
    prompts = make_prompts(geo, cfg.vocab)
    # the float32 reference reads the stacked weights, so it comes first;
    # then they are split once, in place, and both engines serve from the
    # same per-layer arrays: the weights are never on the device twice
    ref = reference_logits(params, cfg, prompts[0])
    clog.report("reference")
    print(f"  [reference] peak_bytes_in_use={peak_bytes()}", flush=True)
    t0 = time.monotonic()
    D.take_layers(params)
    jax.block_until_ready(params)
    print(f"  [split] wall_s={time.monotonic() - t0:.2f} "
          f"peak_bytes_in_use={peak_bytes()}", flush=True)

    eng, _, _ = serve_check(params, cfg, ctx, geo, prompts)
    clog.report("serve")
    print(f"  [serve] peak_bytes_in_use={peak_bytes()}", flush=True)

    reference_check(eng, ref, cfg, prompts[0])
    free_engine(eng)


# ----------------------------------------------------------------- train

def train_check(cfg, clog: CompileLog, *, n_micro=2, mb=4, seq=256):
    """One train step of ``cfg`` on a (data 2, model 2) mesh against the
    same step on one device; raises unless loss and parameters agree."""
    import jax
    import jax.numpy as jnp
    from repro import optim
    from repro.launch.mesh import make_local_mesh
    from repro.models import transformer as T
    from repro.train import TrainConfig, make_shardings, make_train_step

    key = jax.random.PRNGKey(0)
    params = jax.jit(T.init_params, static_argnames=("cfg", "dtype"))(
        key, cfg, jnp.float32)
    toks = jax.random.randint(key, (n_micro, mb, seq), 0, cfg.vocab)
    labels = jax.random.randint(jax.random.PRNGKey(1), (n_micro, mb, seq),
                                0, cfg.vocab)
    tcfg = TrainConfig(microbatches=n_micro, compute_dtype=jnp.float32,
                       zero1=True, adamw=optim.AdamWConfig(lr=1e-3))
    blocks = dict(remat=False, q_block=128, kv_block=128, loss_chunk=128,
                  compute_dtype=jnp.float32)

    mesh = make_local_mesh(2, 2)
    ctx4 = T.ParallelCtx(mesh=mesh, dp_axes=("data",), **blocks)
    ins, outs = make_shardings(cfg, ctx4, tcfg, jax.eval_shape(lambda: params))
    opt = optim.init(params)
    with jax.default_matmul_precision("highest"):
        t0 = time.monotonic()
        p4, _, m4 = jax.jit(make_train_step(cfg, ctx4, tcfg),
                            in_shardings=ins, out_shardings=outs)(
            jax.device_put(params, ins[0]), jax.device_put(opt, ins[1]),
            toks, labels)
        jax.block_until_ready(p4)
        print(f"  [train] sharded step on mesh {dict(mesh.shape)}: "
              f"wall_s={time.monotonic() - t0:.2f}", flush=True)
        # the one-device step consumes its inputs, so it fits beside the
        # sharded result
        one = jax.devices()[0]
        t0 = time.monotonic()
        p1, _, m1 = jax.jit(make_train_step(cfg, T.ParallelCtx(**blocks),
                                            tcfg), donate_argnums=(0, 1))(
            jax.device_put(params, one), jax.device_put(opt, one),
            toks, labels)
        jax.block_until_ready(p1)
        print(f"  [train] one-device step on {one}: "
              f"wall_s={time.monotonic() - t0:.2f}", flush=True)
    loss1, loss4 = float(m1["loss"]), float(m4["loss"])
    maxdiff = max(jax.tree.leaves(jax.tree.map(
        lambda a, b: float(jnp.abs(a - jax.device_put(b, one)).max()),
        p1, p4)))
    print(f"  [train] loss one-device={loss1:.6f} sharded={loss4:.6f} "
          f"|diff|={abs(loss1 - loss4):.3e} max_param_diff={maxdiff:.3e}",
          flush=True)
    clog.report("train")
    print(f"  [train] peak_bytes_in_use(device 0)={peak_bytes()}", flush=True)
    check(math.isfinite(loss1) and abs(loss1 - loss4) < 1e-3,
          f"sharded loss {loss4} vs one-device {loss1}")
    check(maxdiff < 1e-3, f"max parameter difference {maxdiff}")


def train_phase(clog: CompileLog) -> None:
    from repro.configs import get_arch, replace
    cfg = replace(get_arch(ARCH), n_layers=2)
    print(f"train: {ARCH} at published widths, reduced: n_layers 32 -> 2 "
          f"(so the one-device reference fits one chip)", flush=True)
    train_check(cfg, clog)


# ------------------------------------------------------------------ main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded train step on four chips")
    args = ap.parse_args(argv)

    t0 = time.monotonic()
    info = require_tpu(args.chips)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    clog = CompileLog()
    if args.chips == 4:
        train_phase(clog)
    else:
        serve_phases(clog)
    print(f"done: wall_s={time.monotonic() - t0:.2f} (smoke output, not "
          f"measurements)", flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
