"""Device-side data plane for the Valet page pools (pure jnp, jit-able).

The pool is a fixed array of page slots per layer:
  K/V pool: (n_slots, page_size, n_kv_heads, head_dim)

All ops are functional (return new arrays) and static-shaped so they compose
with jit/pjit; the control plane (pool.py/tiering.py) decides *which* slots,
the data plane only moves bytes.  On TPU the gather/append paths are the
Pallas kernels (``repro.kernels.paged_attention``); these jnp versions are
the oracle + CPU path.
"""
from __future__ import annotations

from functools import partial
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


class KVPool(NamedTuple):
    """One layer's paged KV storage."""
    k: jax.Array        # (n_slots, page, n_kv, hd)
    v: jax.Array


def make_kv_pool(n_slots, page, n_kv, hd, dtype=jnp.bfloat16) -> KVPool:
    shape = (n_slots, page, n_kv, hd)
    return KVPool(jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))


def append_token(pool: KVPool, k, v, slot, offset) -> KVPool:
    """Write one token's K/V into (slot, offset) per batch element.

    k, v: (B, n_kv, hd); slot, offset: (B,) int32.  The write completes into
    the *local pool* — the paper's critical-path contract: callers never wait
    for any remote traffic.
    """
    return KVPool(
        pool.k.at[slot, offset].set(k),
        pool.v.at[slot, offset].set(v),
    )


def append_token_masked(pool: KVPool, k, v, slot, offset, own_mask) -> KVPool:
    """Masked append for sharded pools: only the owning rank writes."""
    slot = jnp.where(own_mask, slot, pool.k.shape[0])      # OOB -> dropped
    return KVPool(
        pool.k.at[slot, offset].set(k, mode="drop"),
        pool.v.at[slot, offset].set(v, mode="drop"),
    )


def gather_pages(pool: KVPool, slots):
    """slots: (B, P) int32 (-1 = pad).  Returns k,v (B, P, page, n_kv, hd)
    and a page-valid mask (B, P)."""
    valid = slots >= 0
    safe = jnp.maximum(slots, 0)
    return pool.k[safe], pool.v[safe], valid


def write_prefill_pages(pool: KVPool, k_pages, v_pages, slots) -> KVPool:
    """Bulk-insert prefill KV.  k_pages: (B, P, page, n_kv, hd);
    slots: (B, P) int32 (-1 = skip)."""
    flat_slots = slots.reshape(-1)
    kf = k_pages.reshape((-1,) + k_pages.shape[2:])
    vf = v_pages.reshape((-1,) + v_pages.shape[2:])
    safe = jnp.where(flat_slots >= 0, flat_slots, pool.k.shape[0])
    return KVPool(
        pool.k.at[safe].set(kf, mode="drop"),
        pool.v.at[safe].set(vf, mode="drop"),
    )


def local_write_batch(pool: KVPool, k_pages, v_pages, slots) -> KVPool:
    """Bulk local-pool write: scatter ``n`` whole pages into their slots.

    k_pages/v_pages: (n, page, n_kv, hd); slots: (n,) int32.  This is the
    device-side primitive behind a ``TieredPageStore`` data plane's
    ``local_write_batch(pages, slots)`` hook: the adapter resolves its
    logical page ids to page data, then lands the whole alloc run with one
    ``.at[slots].set`` scatter instead of one device update per page (the
    critical-path contract is unchanged: the write completes into the
    local pool, no remote traffic).  ``slots`` must be distinct — an alloc
    run pops each pool slot at most once, and XLA scatter-set does not
    define an update order for duplicate indices."""
    return KVPool(
        pool.k.at[slots].set(k_pages),
        pool.v.at[slots].set(v_pages),
    )


@jax.jit
def _read_pages_jit(pools, slots):
    k = jnp.stack([p.k[slots] for p in pools], axis=1)
    v = jnp.stack([p.v[slots] for p in pools], axis=1)
    return tuple((k[i], v[i]) for i in range(slots.shape[0]))


def read_pages(pools, slots):
    """The pages in ``slots`` (non-empty) across every paged layer, read on
    the device in one dispatch: a list of ``(k, v)`` pairs, each
    ``(n_layers, page, n_kv, hd)``.  This is the spill-side slice: only
    whole per-page arrays go to the host tier.  The slot list is padded to
    a power of two, so a handful of compiled programs serve every batch
    size."""
    n = len(slots)
    idx = np.full(1 << (n - 1).bit_length(), slots[-1], np.int32)
    idx[:n] = slots
    return list(_read_pages_jit(tuple(pools), jnp.asarray(idx))[:n])


@partial(jax.jit, donate_argnums=(0,))
def _stream_page_jit(pools, k, v, slot):
    return tuple(KVPool(p.k.at[slot].set(k[j]), p.v.at[slot].set(v[j]))
                 for j, p in enumerate(pools))


def stream_page(pools, k, v, slot):
    """On-demand single-page stream-in (the zero-restore miss path).

    ``k``/``v``: one page of every paged layer, ``(n_layers, page, n_kv,
    hd)``, as ``read_pages`` produced it, back in device memory
    (``from_host_tier``); ``slot`` a scalar index.  Restore in the
    zero-restore engine is block-table repointing for every page whose
    slot survived preemption untouched; only pages whose slot was *reused*
    come back through here, one scatter each, instead of the legacy bulk
    per-layer ``local_write_batch`` scatter over the whole sequence.  The
    pool buffers are donated (in-place scatter, no pool-sized copy) and the
    slot is a traced argument, so every streamed page shares one compiled
    program."""
    return _stream_page_jit(tuple(pools), k, v, jnp.asarray(slot, jnp.int32))


def copy_block(pool: KVPool, src_slot: jax.Array, dst_slot: jax.Array) -> KVPool:
    """Migration data plane: copy one slot's page (same pool or after a
    cross-device transfer).  Functional; a few HBM reads+writes."""
    return KVPool(
        pool.k.at[dst_slot].set(pool.k[src_slot]),
        pool.v.at[dst_slot].set(pool.v[src_slot]),
    )


def extract_blocks(pool: KVPool, slots):
    """Read slots out of the pool (spill to host tier).  (n, page, kv, hd)."""
    return pool.k[slots], pool.v[slots]


def insert_blocks(pool: KVPool, ks, vs, slots) -> KVPool:
    """Insert blocks fetched from a slower tier back into the pool."""
    return KVPool(pool.k.at[slots].set(ks), pool.v.at[slots].set(vs))


# -- host tier ----------------------------------------------------------------

def to_host_tier(x):
    """Spill device arrays (an array or any pytree of them) to the host
    memory tier: one ``device_put`` into each array's ``pinned_host``
    memory (the jax memories API), which stays device-addressable and
    round-trips bit-exactly.  A backend that cannot place it raises;
    nothing falls back to a numpy copy."""
    return jax.device_put(x, jax.tree.map(
        lambda a: a.sharding.with_memory_kind("pinned_host"), x))


def from_host_tier(x, like):
    """Bring spilled arrays (an array or any pytree of them) back into the
    device memory ``like`` lives in, in one ``device_put`` (inverse of
    ``to_host_tier``)."""
    return jax.device_put(x, like.sharding)


# -- ring buffer for sliding-window layers -----------------------------------

class RingKV(NamedTuple):
    k: jax.Array        # (B, W, n_kv, hd)
    v: jax.Array


def make_ring(batch, window, n_kv, hd, dtype=jnp.bfloat16) -> RingKV:
    return RingKV(jnp.zeros((batch, window, n_kv, hd), dtype),
                  jnp.zeros((batch, window, n_kv, hd), dtype))


def ring_append(ring: RingKV, k, v, pos) -> RingKV:
    """k, v: (B, n_kv, hd); pos: scalar int (global step)."""
    w = ring.k.shape[1]
    idx = pos % w
    return RingKV(ring.k.at[:, idx].set(k), ring.v.at[:, idx].set(v))


def ring_valid(ring: RingKV, pos):
    """(B, W) validity mask after ``pos + 1`` tokens written."""
    w = ring.k.shape[1]
    b = ring.k.shape[0]
    filled = jnp.minimum(pos + 1, w)
    m = jnp.arange(w)[None, :] < filled
    return jnp.broadcast_to(m, (b, w))
