#!/usr/bin/env python3
"""Compile a cell's programs for a described TPU v5e, with no chip attached,
and print what the compiler's memory analysis says of each.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 chipbench/rehearse_compile.py \\
        --workload phi3-mini-3.8b.docqa-fit [--pool N] [--batch B]

The programs are the ones the cell's window drives: the engine's decode
step, its prefill at the longest prompt, ``device_ops.read_pages`` at the
largest padded size a flush can ask for (the next power of two at or above
the pool, since ``_make_room`` flushes the whole demoted queue), and
``stream_page``.  The decode and prefill programs take the weights and the
pools as arguments; the two tier moves do not, so the weights are added to
theirs.  Each line gives arguments, output, temp, aliased and the total in
GiB, and whether the total keeps 1 GiB of headroom under the 15.75 GiB a
v5e leaves to programs.  Nothing runs; a compile that fits is not a chip
run.
"""
from __future__ import annotations

import argparse
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(os.path.dirname(HERE),
                                                    "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

GiB = 2 ** 30
CHIP_BYTES = 15.75 * GiB
HEADROOM = 1 * GiB


def _tree_bytes(tree) -> int:
    import jax
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def rehearse(workload: str, pool: int | None, batch: int | None) -> bool:
    import dataclasses

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench.cells import find_cell, program_config
    from repro.core import device_ops as dev
    from repro.models import decode as D
    from repro.models import transformer as T

    jax.config.update("jax_enable_compilation_cache", False)
    cell = find_cell(workload)
    geo = cell.geometry
    if pool:
        geo = dataclasses.replace(geo, pool_slots=pool)
    if batch:
        geo = dataclasses.replace(geo, max_batch=batch)
    cfg = program_config(cell.config)
    ctx = T.ParallelCtx(remat=False, compute_dtype=jnp.bfloat16)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def on(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one)

    params = on(jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)))
    caches = on(jax.eval_shape(
        lambda: D.init_caches(cfg, geo.max_batch, pool_slots=geo.pool_slots,
                              page=geo.page, dtype=jnp.bfloat16)))
    pbytes = _tree_bytes(params)
    paged = [i for i, inf in enumerate(D.layer_infos(cfg)) if inf.uses_paged]
    pools = tuple(caches["layers"][i]["pool"] for i in paged)
    print(f"{workload}: {cfg.name} n_layers={cfg.n_layers} "
          f"batch={geo.max_batch} max_seq={geo.max_seq} page={geo.page} "
          f"pool_slots={geo.pool_slots} params={pbytes / GiB:.3f} GiB "
          f"pool={_tree_bytes(pools) / GiB:.3f} GiB", flush=True)

    b, mp = geo.max_batch, geo.max_pages
    vec = spec((b,), jnp.int32)
    decode = jax.jit(lambda p, c, tok, bt, slot, off, act: D.decode_step(
        p, c, tok, cfg, ctx, bt, slot, off, active=act))

    def prefill(p, c, toks, bt):
        one_c = D.init_caches(cfg, 1, pool_slots=1, page=geo.page,
                              dtype=jnp.bfloat16)
        for li, lc in enumerate(one_c["layers"]):
            if "pool" in lc:
                lc["pool"] = c["layers"][li]["pool"]
        return D.prefill(p, toks, cfg, ctx, one_c, bt)

    n_read = 1 << (geo.pool_slots - 1).bit_length()
    page_shape = (len(pools), geo.page, cfg.n_kv_heads, cfg.resolved_head_dim)
    programs = [
        ("decode", decode, (params, caches, vec, spec((b, mp), jnp.int32),
                            vec, vec, spec((b,), jnp.bool_)), 0),
        (f"prefill[{max(cell.traffic['prompt_lengths'])}]", jax.jit(prefill),
         (params, caches,
          spec((1, max(cell.traffic["prompt_lengths"])), jnp.int32),
          spec((1, mp), jnp.int32)), 0),
        (f"read_pages[{n_read}]", dev._read_pages_jit,
         (pools, spec((n_read,), jnp.int32)), pbytes),
        ("stream_page", dev._stream_page_jit,
         (pools, spec(page_shape, jnp.bfloat16),
          spec(page_shape, jnp.bfloat16), spec((), jnp.int32)), pbytes),
    ]
    ok = True
    for name, fn, args, extra in programs:
        m = fn.lower(*args).compile().memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes + extra)
        fits = total < CHIP_BYTES - HEADROOM
        ok &= fits
        print(f"  {name}: arguments {m.argument_size_in_bytes / GiB:.3f} "
              f"output {m.output_size_in_bytes / GiB:.3f} "
              f"temp {m.temp_size_in_bytes / GiB:.3f} "
              f"aliased {m.alias_size_in_bytes / GiB:.3f} "
              f"weights beside {extra / GiB:.3f} total {total / GiB:.3f} GiB "
              f"{'fits' if fits else 'DOES NOT FIT'} with 1 GiB headroom",
              flush=True)
    return ok


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--pool", type=int, default=None,
                    help="pool slots instead of the configuration's")
    ap.add_argument("--batch", type=int, default=None,
                    help="batch instead of the traffic's clients")
    args = ap.parse_args(argv)
    return 0 if rehearse(args.workload, args.pool, args.batch) else 1


if __name__ == "__main__":
    sys.exit(main())
