"""Paged decode attention TPU kernel — the Valet data plane hot spot.

One query token attends over KV pages scattered through the device page
pool.  The Global Page Table (block table) rides in SMEM via scalar
prefetch (``PrefetchScalarGridSpec``) and drives the HBM->VMEM page DMA per
grid step — i.e. the paper's GPT lookup + one-sided page read are fused into
the attention kernel, so no gathered KV copy is ever materialized in HBM.

This is also where the paper's "small block I/O, large RDMA message"
flexibility (§3.3) shows up on TPU: the *logical* page (tokens) is small for
allocator granularity, while the *physical* DMA per grid step is a full
page with all of its KV heads — aligned to the pool's (Hkv, D) tiling,
WQE-cache-miss-free in TPU terms (few, big DMA descriptors).

Layout:
  q:            (B, G, Hkv, D)   one token per sequence, grouped heads
  k/v pool:     (n_slots, page, Hkv, D)
  block_table:  (B, P) int32 pool slot per logical page (-1 pad)
  lengths:      (B,)   valid token count per sequence
Grid: (B, P) with the page axis innermost/sequential; softmax state per
(group, KV head) in VMEM scratch.

Zero-restore contract (PR 8): because the kernel reads KV *through* the
block table, restoring a preempted sequence needs no bulk KV copy — the
serve engine repoints block-table entries at pool slots whose bytes
survived preemption untouched (validated by the pool's per-slot generation
counter), and only pages whose slot was reused in the meantime are streamed
back one at a time via ``device_ops.stream_page`` before the next decode
step.  The kernel itself is unchanged either way: any (B, P) table whose
live entries index valid pool pages is a correct input.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_kernel(block_table, lengths, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, page, n_pages, group, scale):
    b = pl.program_id(0)
    pi = pl.program_id(1)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    slot = block_table[b, pi]
    length = lengths[b]

    @pl.when(slot >= 0)
    def _body():
        k = k_ref[0].astype(jnp.float32)                  # (page, Hkv, D)
        v = v_ref[0].astype(jnp.float32)
        # token validity within the page (ragged tail)
        pos = pi * page + jax.lax.broadcasted_iota(
            jnp.int32, (page, k.shape[1], 1), 0)
        mask = pos < length                               # (page, Hkv, 1)
        # every KV head of the page at once: per-head scores are a lane
        # reduction, so no head is ever sliced out of the (Hkv, D) tile
        for g in range(group):
            q = q_ref[0, g].astype(jnp.float32)           # (Hkv, D)
            s = jnp.sum(k * q[None], axis=-1, keepdims=True) * scale
            s = jnp.where(mask, s, NEG_INF)               # (page, Hkv, 1)
            m_prev = m_scr[g]                             # (Hkv, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
            p = jnp.where(mask, jnp.exp(s - m_new[None]), 0.0)
            corr = jnp.exp(m_prev - m_new)
            l_scr[g] = l_scr[g] * corr + jnp.sum(p, axis=0)
            acc_scr[g] = acc_scr[g] * corr + jnp.sum(p * v, axis=0)
            m_scr[g] = m_new

    @pl.when(pi == n_pages - 1)
    def _finish():
        for g in range(group):
            l = jnp.maximum(l_scr[g], 1e-20)
            o_ref[0, g] = (acc_scr[g] / l).astype(o_ref.dtype)


def paged_attention(q, k_pool, v_pool, block_table, lengths, *,
                    interpret=False):
    """q: (B, Hq, D); pools: (n_slots, page, Hkv, D); block_table: (B, P).

    Returns (B, Hq, D).  Pages with slot -1 are skipped (no compute; the
    safe slot-0 fetch is masked out).  Each grid step DMAs one whole page,
    all KV heads, so the block's trailing dims are the pool's ``(Hkv, D)``.
    """
    b, hq, d = q.shape
    n_slots, page, hkv, _ = k_pool.shape
    n_pages = block_table.shape[1]
    group = hq // hkv
    # head h = kv * group + g; group-major so q_ref[0, g] is an (Hkv, D) tile
    qg = q.reshape(b, hkv, group, d).transpose(0, 2, 1, 3)
    scale = 1.0 / math.sqrt(d)

    kernel = functools.partial(_paged_kernel, page=page, n_pages=n_pages,
                               group=group, scale=scale)

    def kv_index(bi, pi, block_table, lengths):
        slot = jnp.maximum(block_table[bi, pi], 0)        # pad -> slot 0
        return (slot, 0, 0, 0)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_pages),
            in_specs=[
                pl.BlockSpec((1, group, hkv, d),
                             lambda bi, pi, *refs: (bi, 0, 0, 0)),
                pl.BlockSpec((1, page, hkv, d), kv_index),
                pl.BlockSpec((1, page, hkv, d), kv_index),
            ],
            out_specs=pl.BlockSpec((1, group, hkv, d),
                                   lambda bi, pi, *refs: (bi, 0, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((group, hkv, 1), jnp.float32),
                pltpu.VMEM((group, hkv, 1), jnp.float32),
                pltpu.VMEM((group, hkv, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, group, hkv, d), q.dtype),
        interpret=interpret,
    )(block_table, lengths, qg, k_pool, v_pool)
    return out.transpose(0, 2, 1, 3).reshape(b, hq, d)
