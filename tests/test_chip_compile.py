"""Compile-only checks for one TPU v5e chip, on a described ``v5e:2x2``
topology: nothing runs and no chip is needed.

The TPU compiler refuses here what the chip would refuse: Pallas blocks
that break the (8, 128) tiling, primitives Mosaic cannot lower, programs
that do not fit the chip's memory.  The topology is described inside a
fixture, never at import: only one process may load the TPU library, and
every test worker imports this file.
"""
import os
import re
import sys
from dataclasses import dataclass

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch, replace
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


@dataclass(frozen=True)
class Served:
    """An engine's shape on one chip: the arch, its overrides, the batch,
    the longest sequence, the page and the KV pool's pages."""
    arch: str
    override: tuple
    max_batch: int
    max_seq: int
    page: int
    pool_slots: int

    @property
    def cfg(self):
        return replace(get_arch(self.arch), **dict(self.override))

    @property
    def max_pages(self) -> int:
        return -(-self.max_seq // self.page)


_SMOKE = Geometry()
SERVED = {
    # chip_smoke.py's roomy run
    "phi3-smoke": Served(ARCH, (), _SMOKE.batch, _SMOKE.prompt + _SMOKE.new,
                         _SMOKE.page, _SMOKE.roomy_slots),
    # phi3 at full depth: 4 sequences of at most 768 + 128 tokens
    "phi3-docqa": Served("phi3-mini-3.8b", (), 4, 896, 16, 256),
    # 20 of granite's 40 layers, tied head: 7 sequences of at most
    # 2048 + 128 tokens
    "granite-d20-docqa": Served(
        "granite-3-8b", (("n_layers", 20), ("tie_embeddings", True)),
        7, 2176, 16, 960),
}


def _shapes(srv: Served, split: bool):
    """bfloat16 weights, stacked or split per layer (``decode.take_layers``)
    and caches at ``srv``'s shape, as shapes only."""
    cfg = srv.cfg
    params = jax.eval_shape(
        lambda: T.init_params(jax.random.PRNGKey(0), cfg, jnp.bfloat16))
    if split:
        params = dict(params, segments=[
            [jax.tree.map(lambda a: a.update(shape=a.shape[1:]), stack)
             for _ in range(seg.count)]
            for stack, seg in zip(params["segments"], T.segments(cfg))])
    caches = jax.eval_shape(
        lambda: D.init_caches(cfg, srv.max_batch, pool_slots=srv.pool_slots,
                              page=srv.page, dtype=jnp.bfloat16))
    return params, caches


def _decode(one_chip, srv: Served, split: bool):
    """The engine's decode program (``ValetServeEngine._decode_fn``) at
    ``srv``'s shape, compiled for one chip."""
    cfg = srv.cfg
    ctx = T.ParallelCtx(remat=False, compute_dtype=jnp.bfloat16)
    params, caches = _shapes(srv, split)
    b = srv.max_batch
    vec = _spec(one_chip, (b,), jnp.int32)
    step = lambda p, c, tok, bt, slot, off, act: D.decode_step(
        p, c, tok, cfg, ctx, bt, slot, off, active=act)
    return jax.jit(step).lower(
        _on(one_chip, params), _on(one_chip, caches), vec,
        _spec(one_chip, (b, srv.max_pages), jnp.int32), vec, vec,
        _spec(one_chip, (b,), jnp.bool_)).compile()


def _nbytes(tree) -> int:
    return sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(tree))


def test_phi3_decode_step_fits_one_chip(one_chip):
    """The engine's decode step at chip_smoke.py's geometry, full width,
    from the per-layer weights the engine serves."""
    m = _decode(one_chip, SERVED["phi3-smoke"], split=True).memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < CHIP_BYTES - HEADROOM, total / GiB


@pytest.mark.parametrize("name", sorted(SERVED))
def test_weight_split_fits_one_chip(name):
    """The engine splits stacked weights on the device after its caches are
    made, one stacked leaf at a time: for a moment the chip holds the
    weights, the caches and a second copy of the largest stacked leaf
    (granite's gate and up projections at 20 layers: 3.91 GiB)."""
    params, caches = _shapes(SERVED[name], split=False)
    largest = max(_nbytes(a) for a in jax.tree.leaves(params["segments"]))
    peak = _nbytes(params) + _nbytes(caches) + largest
    assert peak < CHIP_BYTES - HEADROOM, peak / GiB


def _weight_copies(compiled, layer_shapes):
    """The ENTRY computation's copies whose result has a layer weight's
    shape (dims in either order): a weight copied inside the step."""
    text = compiled.as_text()
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}")]
    shapes = {tuple(s) for s in layer_shapes if len(s) > 1}
    shapes |= {s[::-1] for s in shapes}
    out = []
    for line in entry.splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* copy\(", line)
        if m and tuple(int(d) for d in m.group(1).split(",")) in shapes:
            out.append(line.strip())
    return out


def _layer_shapes(srv: Served):
    params, _ = _shapes(srv, split=False)
    return {a.shape[1:] for a in jax.tree.leaves(params["segments"])}


@pytest.mark.parametrize("name", ["phi3-docqa", "granite-d20-docqa"])
def test_split_decode_copies_no_weight(one_chip, name):
    """Given each layer's weights as its own arrays, the served decode step
    copies no weight and needs little scratch memory (stacked: 3.43 GiB on
    phi3, 4.11 GiB on granite, most of it the weight copies)."""
    srv = SERVED[name]
    compiled = _decode(one_chip, srv, split=True)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < 0.5 * GiB, temp / GiB
    assert _weight_copies(compiled, _layer_shapes(srv)) == []


def test_stacked_decode_copies_weights(one_chip):
    """The check above sees the copies: the decode step that slices its
    weights out of the ``[count, ...]`` stacks copies them."""
    srv = SERVED["granite-d20-docqa"]
    compiled = _decode(one_chip, srv, split=False)
    assert len(_weight_copies(compiled, _layer_shapes(srv))) >= 20
    assert compiled.memory_analysis().temp_size_in_bytes > 1 * GiB
