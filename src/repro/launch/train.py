"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch gemma3-4b \
        --steps 200 --local            # CPU-scale smoke run
    PYTHONPATH=src python -m repro.launch.train --arch granite-3-8b \
        --dryrun                       # lower+compile on the production mesh

On one host with several devices (four TPU chips, say) the step runs on a
(data, model) mesh over all of them: model 2 when the device count is
even, data over the rest.  Checkpoints flow through ValetCheckpointer.
"""
from __future__ import annotations

import argparse
import os
from functools import partial


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--local", action="store_true",
                    help="reduced config on local devices")
    ap.add_argument("--dryrun", action="store_true",
                    help="lower+compile the full config on the 16x16 mesh")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=2)
    args = ap.parse_args()

    if args.dryrun:
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    import jax
    import jax.numpy as jnp
    from repro import optim
    from repro.configs import get_arch, reduced
    from repro.data import DataConfig, TrainDataset
    from repro.models import transformer as T
    from repro.train import (TrainConfig, ValetCheckpointer, fit,
                             make_shardings)

    if args.dryrun:
        from repro.launch.dryrun import run_cell, _artifact_dir
        from repro.launch.mesh import make_production_mesh
        mesh = make_production_mesh()
        rec = run_cell(args.arch, "train_4k", "single", mesh,
                       _artifact_dir(), force=True)
        return 0 if rec.get("status") == "ok" else 1

    cfg = reduced(get_arch(args.arch)) if args.local else get_arch(args.arch)
    mesh = None
    n_dev = jax.device_count()
    if n_dev > 1:
        from repro.launch.mesh import make_local_mesh
        mp = 2 if n_dev % 2 == 0 else 1
        mesh = make_local_mesh(n_dev // mp, mp)
    ctx = T.ParallelCtx(mesh=mesh, remat=False, q_block=32, kv_block=32,
                        loss_chunk=32, compute_dtype=jnp.float32)
    tcfg = TrainConfig(
        microbatches=args.microbatches, compute_dtype=jnp.float32,
        adamw=optim.AdamWConfig(lr=args.lr, warmup_steps=10,
                                total_steps=args.steps))
    key = jax.random.PRNGKey(0)
    init = partial(T.init_params, cfg=cfg)
    if mesh is None:
        params = jax.jit(init)(key)
    else:
        # generated straight into their shardings, never whole on one device
        ins, _ = make_shardings(cfg, ctx, tcfg, jax.eval_shape(init, key))
        params = jax.jit(init, out_shardings=ins[0])(key)
    ds = TrainDataset(DataConfig(vocab=cfg.vocab, seq_len=args.seq_len,
                                 global_batch=args.global_batch))
    ckpt = ValetCheckpointer(args.ckpt_dir, replicas=2)

    def cb(step, params, opt_state, metrics):
        if step and step % 50 == 0:
            ckpt.save(step, {"params": params, "opt": opt_state})

    params, opt_state, hist = fit(params, cfg, ctx, tcfg, ds,
                                  n_steps=args.steps, callback=cb)
    ckpt.save(args.steps, {"params": params, "opt": opt_state})
    ckpt.close()
    for h in hist:
        print(h)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
