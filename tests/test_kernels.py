"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_lib
from repro.kernels.flash_attention import flash_attention
from repro.kernels.paged_attention import paged_attention
from repro.kernels.ssd_scan import ssd_scan


def rand(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
    return x.astype(dtype)


TOL = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (1, 128, 4, 4, 32),      # MHA
    (2, 256, 8, 2, 64),      # GQA 4:1
    (1, 256, 4, 1, 128),     # MQA
    (2, 128, 2, 2, 96),      # odd head_dim
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(b, s, hq, hkv, d, dtype, causal, window):
    q = rand(0, (b, s, hq, d), dtype)
    k = rand(1, (b, s, hkv, d), dtype)
    v = rand(2, (b, s, hkv, d), dtype)
    qk = q.transpose(0, 2, 1, 3).reshape(b * hq, s, d)
    kk = k.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    vk = v.transpose(0, 2, 1, 3).reshape(b * hkv, s, d)
    out = flash_attention(qk, kk, vk, causal=causal, window=window,
                          block_q=64, block_k=64, interpret=True)
    out = out.reshape(b, hq, s, d).transpose(0, 2, 1, 3)
    ref = ref_lib.flash_attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("b,hq,hkv,d,page,npages", [
    (2, 4, 2, 64, 16, 4),
    (3, 8, 8, 32, 8, 6),
    (1, 8, 1, 128, 32, 3),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_attention_sweep(b, hq, hkv, d, page, npages, dtype):
    n_slots = b * npages + 4
    q = rand(0, (b, hq, d), dtype)
    kp = rand(1, (n_slots, page, hkv, d), dtype)
    vp = rand(2, (n_slots, page, hkv, d), dtype)
    rng = np.random.default_rng(0)
    bt = np.full((b, npages), -1, np.int32)
    lens = rng.integers(1, npages * page, size=b).astype(np.int32)
    for i in range(b):
        used = int(np.ceil((lens[i] + 1) / page))
        bt[i, :used] = rng.choice(n_slots, used, replace=False)
    out = paged_attention(q, kp, vp, jnp.array(bt), jnp.array(lens),
                          interpret=True)
    ref = ref_lib.paged_attention_ref(q, kp, vp, jnp.array(bt),
                                      jnp.array(lens))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=TOL[dtype], rtol=TOL[dtype])


@pytest.mark.parametrize("b,s,h,p,g,n,chunk", [
    (1, 64, 2, 8, 1, 16, 16),
    (2, 64, 4, 16, 2, 8, 32),
    (1, 128, 8, 8, 2, 4, 16),
])
def test_ssd_scan_sweep(b, s, h, p, g, n, chunk):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)))
    Bm = jax.random.normal(ks[3], (b, s, g, n))
    Cm = jax.random.normal(ks[4], (b, s, g, n))
    y, hT = ssd_scan(x, dt, A, Bm, Cm, chunk, interpret=True)
    yr, hr = ref_lib.ssd_scan_ref(x, dt, A, Bm, Cm, chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               atol=3e-4, rtol=3e-4)
    np.testing.assert_allclose(np.asarray(hT), np.asarray(hr),
                               atol=3e-4, rtol=3e-4)


def test_paged_attention_skips_invalid_pages():
    """-1 block-table entries contribute nothing (Valet GPT miss -> pad)."""
    b, hq, hkv, d, page = 1, 2, 1, 16, 8
    kp = rand(1, (8, page, hkv, d), jnp.float32)
    vp = rand(2, (8, page, hkv, d), jnp.float32)
    q = rand(0, (b, hq, d), jnp.float32)
    bt_full = jnp.array([[0, 1, -1, -1]], jnp.int32)
    bt_short = jnp.array([[0, 1]], jnp.int32)
    lens = jnp.array([2 * page - 1], jnp.int32)
    a = paged_attention(q, kp, vp, bt_full, lens, interpret=True)
    b_ = paged_attention(q, kp, vp, bt_short, lens, interpret=True)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-6)


def test_local_write_batch_round_trip():
    """Bulk page scatter == sequential per-page appends for distinct slots
    (the data-plane half of a batched access_batch alloc run)."""
    import jax.numpy as jnp
    from repro.core import device_ops as dev
    n_slots, page, n_kv, hd = 8, 4, 2, 16
    pool = dev.make_kv_pool(n_slots, page, n_kv, hd, jnp.float32)
    k = rand(3, (3, page, n_kv, hd), jnp.float32)
    v = rand(4, (3, page, n_kv, hd), jnp.float32)
    slots = jnp.array([5, 1, 6], jnp.int32)
    out = dev.local_write_batch(pool, k, v, slots)
    ref = pool
    for i in range(3):
        ref = dev.insert_blocks(ref, k[i:i + 1], v[i:i + 1], slots[i:i + 1])
    np.testing.assert_array_equal(np.asarray(out.k), np.asarray(ref.k))
    np.testing.assert_array_equal(np.asarray(out.v), np.asarray(ref.v))
    # untouched slots stay zero
    assert float(jnp.abs(out.k[0]).sum()) == 0.0


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_host_tier_round_trip(dtype):
    """A spilled array sits in pinned host memory and comes back to device
    memory with the same bytes."""
    from repro.core import device_ops as dev
    x = rand(5, (3, 4, 2, 16), dtype)
    h = dev.to_host_tier(x)
    assert isinstance(h, jax.Array)
    assert h.sharding.memory_kind == "pinned_host"
    back = dev.from_host_tier(h, x)
    assert back.sharding.memory_kind == x.sharding.memory_kind
    assert back.dtype == x.dtype
    np.testing.assert_array_equal(np.asarray(back), np.asarray(x))


def test_host_tier_raises_instead_of_falling_back():
    """Nothing degrades to a numpy copy: what cannot be placed raises."""
    from repro.core import device_ops as dev
    with pytest.raises(AttributeError):
        dev.to_host_tier(np.ones((2, 3), np.float32))
    with pytest.raises(AttributeError):
        dev.from_host_tier(jnp.ones((2, 3)), like=np.ones((2, 3)))


def test_page_read_stream_round_trip():
    """Pages read across every layer's pool in one batch, spilled to the
    host tier in one transfer and streamed into other slots land
    bit-identically in every layer."""
    from repro.core import device_ops as dev
    n_slots, page, n_kv, hd = 8, 4, 2, 16
    pools = [dev.KVPool(rand(10 + i, (n_slots, page, n_kv, hd), jnp.bfloat16),
                        rand(20 + i, (n_slots, page, n_kv, hd), jnp.bfloat16))
             for i in range(3)]
    src, dst = [1, 5, 0], [4, 2, 7]      # three pages: padded to four
    want = [[(np.asarray(p.k[s]), np.asarray(p.v[s])) for p in pools]
            for s in src]
    pages = dev.read_pages(pools, src)
    assert len(pages) == len(src)
    assert pages[0][0].shape == (3, page, n_kv, hd)
    host = dev.to_host_tier(pages)
    assert all(a.sharding.memory_kind == "pinned_host"
               for a in jax.tree.leaves(host))
    out = pools
    for (k, v), d in zip(dev.from_host_tier(host, pools[0].k), dst):
        out = dev.stream_page(out, k, v, d)
    for w, d in zip(want, dst):
        for (wk, wv), p in zip(w, out):
            np.testing.assert_array_equal(np.asarray(p.k[d]), wk)
            np.testing.assert_array_equal(np.asarray(p.v[d]), wv)
            assert p.k.sharding.memory_kind == "device"
