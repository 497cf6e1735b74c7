"""Serving launcher: the Valet engine over a batch of requests.

    PYTHONPATH=src python -m repro.launch.serve --arch gemma3-4b --local \
        --requests 8 --policy valet --pool-slots 16

Without ``--local`` the model runs at its published widths in bfloat16
(weights from a seed, nothing is downloaded); ``--local`` serves the
reduced float32 config, which fits a CPU.

``--dryrun`` lowers+compiles the sharded serve_step for the production mesh
(same path the dry-run sweep uses).
"""
from __future__ import annotations

import argparse
import os


def build_model(arch: str, *, local: bool):
    """``(cfg, ctx, params)`` for serving ``arch``, weights from seed 0.

    Full width: bfloat16 params and compute.  ``local``: the reduced config
    in float32.  Params are built under ``jax.jit`` so a segment's layers
    are generated straight into their stacked buffer, never held twice."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_arch, reduced
    from repro.models import transformer as T

    if local:
        cfg, dtype = reduced(get_arch(arch)), jnp.float32
        ctx = T.ParallelCtx(remat=False, q_block=16, kv_block=16)
    else:
        cfg, dtype = get_arch(arch), jnp.bfloat16
        ctx = T.ParallelCtx(remat=False, compute_dtype=dtype)
    init = jax.jit(T.init_params, static_argnames=("cfg", "dtype"))
    params = init(jax.random.PRNGKey(0), cfg, dtype)
    return cfg, ctx, params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="decode_32k")
    ap.add_argument("--local", action="store_true")
    ap.add_argument("--dryrun", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--policy", default="valet")
    ap.add_argument("--pool-slots", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--page", type=int, default=8)
    args = ap.parse_args()

    if args.dryrun:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
        from repro.launch.dryrun import run_cell, _artifact_dir
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh()
        rec = run_cell(args.arch, args.shape, "single", mesh,
                       _artifact_dir(), force=True)
        return 0 if rec.get("status") == "ok" else 1

    import numpy as np
    from repro.core.policies import POLICIES
    from repro.launch.compile_cache import enable_compile_cache
    from repro.serve import ValetServeEngine

    enable_compile_cache()
    cfg, ctx, params = build_model(args.arch, local=args.local)
    eng = ValetServeEngine(
        params, cfg, ctx, max_batch=args.max_batch,
        max_seq=args.prompt_len + args.max_new + args.page,
        page=args.page, pool_slots=args.pool_slots,
        policy=POLICIES[args.policy])
    # the engine splits the weights per layer at its first program call;
    # dropping the stacked ones here keeps one copy on the device
    del params
    rng = np.random.default_rng(0)
    for _ in range(args.requests):
        eng.submit(rng.integers(2, cfg.vocab, size=args.prompt_len),
                   args.max_new)
    reqs = eng.run()
    s = eng.stats
    print(f"policy={args.policy} requests={len(reqs)} "
          f"done={sum(r.status == 'done' for r in reqs)} tokens={s.tokens}")
    print(f"steps={s.steps} pauses={s.pauses} spilled={s.spilled_pages} "
          f"restored={s.restored_pages} recomputes={s.recomputes}")
    print(f"sim_time={s.sim_time_us / 1e3:.2f}ms "
          f"bg_time={s.bg_time_us / 1e3:.2f}ms wall={s.wall_time_s:.2f}s")
    for r in reqs[:4]:
        print(f"  req{r.rid}: {r.tokens_out[:8]}...")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
