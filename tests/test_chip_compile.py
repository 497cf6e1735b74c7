"""Compile-only checks for one TPU v5e chip, on a described ``v5e:2x2``
topology: nothing runs and no chip is needed.

The TPU compiler refuses here what the chip would refuse: Pallas blocks
that break the (8, 128) tiling, primitives Mosaic cannot lower, programs
that do not fit the chip's memory.  The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
every test worker imports this file.
"""
import os
import sys

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssd_scan import ssd_scan
from repro.models import decode as D
from repro.models import transformer as T
from repro.models.ssm import ssm_dims

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from chip_smoke import ARCH, Geometry  # noqa: E402

GiB = 2 ** 30
CHIP_BYTES = 15.75 * GiB        # what a v5e chip's 16 GB leaves to programs
HEADROOM = 1 * GiB


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # no skip: a TPU library that cannot describe the chip fails every gate
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _on(sharding, tree):
    return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), tree)


@pytest.mark.parametrize("d", [96, 128])
def test_flash_attention_compiles(one_chip, d):
    q = _spec(one_chip, (32, 1024, d))
    fn = lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=128,
                                         block_k=128)
    compiled = jax.jit(fn).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("arch", ["phi3-mini-3.8b", "granite-3-8b"])
def test_paged_attention_compiles(one_chip, arch):
    cfg, geo = get_arch(arch), Geometry()
    d = cfg.resolved_head_dim
    pool = _spec(one_chip, (geo.roomy_slots, geo.page, cfg.n_kv_heads, d))
    compiled = jax.jit(paged_attention).lower(
        _spec(one_chip, (geo.batch, cfg.n_heads, d)), pool, pool,
        _spec(one_chip, (geo.batch, geo.pages_per_request), jnp.int32),
        _spec(one_chip, (geo.batch,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles(one_chip):
    cfg = get_arch("mamba2-2.7b")
    ssm = cfg.ssm
    _, h, _ = ssm_dims(cfg.d_model, ssm)
    b, s = 1, 2 * ssm.chunk_size
    f32 = jnp.float32
    bc = _spec(one_chip, (b, s, ssm.n_groups, ssm.d_state), f32)
    fn = lambda x, dt, a, bm, cm: ssd_scan(x, dt, a, bm, cm, ssm.chunk_size)
    compiled = jax.jit(fn).lower(
        _spec(one_chip, (b, s, h, ssm.head_dim), f32),
        _spec(one_chip, (b, s, h), f32), _spec(one_chip, (h,), f32),
        bc, bc).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_phi3_decode_step_fits_one_chip(one_chip):
    """The engine's decode step at chip_smoke.py's geometry, full width."""
    cfg, geo = get_arch(ARCH), Geometry()
    ctx = T.ParallelCtx(remat=False, compute_dtype=jnp.bfloat16)
    params = _on(one_chip, jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16)))
    caches = _on(one_chip, jax.eval_shape(
        lambda: D.init_caches(cfg, geo.batch, pool_slots=geo.roomy_slots,
                              page=geo.page, dtype=jnp.bfloat16)))
    max_pages = -(-(geo.prompt + geo.new) // geo.page)
    vec = _spec(one_chip, (geo.batch,), jnp.int32)
    step = lambda p, c, tok, bt, slot, off, act: D.decode_step(
        p, c, tok, cfg, ctx, bt, slot, off, active=act)
    compiled = jax.jit(step).lower(
        params, caches, vec, _spec(one_chip, (geo.batch, max_pages),
                                   jnp.int32),
        vec, vec, _spec(one_chip, (geo.batch,), jnp.bool_)).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < CHIP_BYTES - HEADROOM, total / GiB
