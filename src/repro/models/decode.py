"""Decode path: per-layer caches (Valet paged pools / rings / SSM states)
plus an exact cache-building prefill.

Layers are unrolled (heterogeneous caches per layer kind), which keeps every
assigned arch on one code path:

  full-attention layer   -> paged KV pool (the Valet-managed working set)
  sliding-window layer   -> ring buffer (bounded; no paging needed)
  ssm layer              -> O(1) SSD + conv state
  hybrid layer           -> attention cache + SSD state
  cross-attn layer (vlm/audio) -> static per-request KV (pinned region)

The control plane (serve/engine.py) owns slot allocation; this module is the
pure data plane: given block tables + append targets it computes one decode
step.  All paged layers share one block table — a logical page allocation
spans every paged layer (slot i of each layer's pool).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.core import device_ops as dev
from repro.models import ssm as ssm_lib
from repro.models.attention import (blockwise_attention, decode_partial,
                                    combine_partials)
from repro.models.layers import apply_rope, rms_norm, swiglu, gelu_mlp
from repro.models.moe import moe_ffn
from repro.models.transformer import (ParallelCtx,
                                      segments,
                                      encoder_segments,
                                      unembed_matrix,
                                      mask_vocab_pad,
                                      _sinusoidal)


@dataclass(frozen=True)
class LayerInfo:
    kind: str
    window: int
    ffn: str
    d_ff: int
    seg: int
    idx: int

    @property
    def uses_paged(self):
        return self.kind in ("attn", "dec", "hybrid") and self.window == 0

    @property
    def uses_ring(self):
        return self.kind in ("attn", "hybrid") and self.window > 0

    @property
    def uses_ssm(self):
        return self.kind in ("ssm", "hybrid")

    @property
    def uses_cross(self):
        return self.kind in ("xattn", "dec")


def layer_infos(cfg: ArchConfig) -> List[LayerInfo]:
    out = []
    for si, seg in enumerate(segments(cfg)):
        for i in range(seg.count):
            out.append(LayerInfo(seg.kind, seg.window, seg.ffn,
                                 seg.d_ff or cfg.d_ff, si, i))
    return out


def layer_params(params, info: LayerInfo):
    """The weights of one layer: an element of a split segment (a list, see
    ``take_layers``), else a slice of each ``[count, ...]`` stacked leaf."""
    seg = params["segments"][info.seg]
    if isinstance(seg, list):
        return seg[info.idx]
    return jax.tree.map(lambda a: a[info.idx], seg)


def take_layers(params):
    """Split ``params`` in place: each stacked segment
    ``params["segments"][i]`` becomes the list of its layers' trees, holding
    the same values.  A segment already split is left as it is.  Returns
    ``params``.

    A program that takes the split form reads each layer's weights as its
    own arguments; one that takes the stacked form slices them out inside
    the program, which on TPU copies every weight of every layer on every
    call.

    Each stacked leaf is dropped as soon as its layers are made, so a caller
    that holds the only reference to the stacked leaves never has the
    weights on the device twice: the split needs room for one more copy of
    the largest leaf, not of every leaf.  To keep a tree whole, split a copy
    of its containers: ``take_layers(jax.tree.map(lambda a: a, params))``."""
    segs = params["segments"]
    # by index: a loop variable (or ``enumerate``'s cached tuple) would keep
    # the stacked segment, and so every leaf of it, alive to the loop's end
    for si in range(len(segs)):
        if isinstance(segs[si], list):
            continue
        leaves, treedef = jax.tree.flatten(segs[si])
        segs[si] = None
        cols = []
        for j in range(len(leaves)):
            a, leaves[j] = leaves[j], None
            cols.append(_unstack(a))
            del a
        segs[si] = [treedef.unflatten(layer) for layer in zip(*cols)]
    return params


@jax.jit
def _unstack(a):
    return [a[i] for i in range(a.shape[0])]


# --------------------------------------------------------------------------
# Cache init
# --------------------------------------------------------------------------

def init_caches(cfg: ArchConfig, batch: int, *, pool_slots: int, page: int,
                n_cross: int = 0, dtype=jnp.float32) -> Dict[str, Any]:
    hd = cfg.resolved_head_dim
    layers = []
    for info in layer_infos(cfg):
        c: Dict[str, Any] = {}
        if info.uses_paged:
            c["pool"] = dev.make_kv_pool(pool_slots, page, cfg.n_kv_heads,
                                         hd, dtype)
        if info.uses_ring:
            c["ring"] = dev.make_ring(batch, info.window, cfg.n_kv_heads,
                                      hd, dtype)
        if info.uses_ssm:
            c["ssm"] = ssm_lib.ssm_init_state(batch, cfg.d_model, cfg.ssm,
                                              dtype)
        if info.uses_cross:
            n = n_cross or cfg.n_frontend_tokens
            c["cross_k"] = jnp.zeros((batch, n, cfg.n_kv_heads, hd), dtype)
            c["cross_v"] = jnp.zeros((batch, n, cfg.n_kv_heads, hd), dtype)
        layers.append(c)
    return {"layers": layers, "lengths": jnp.zeros((batch,), jnp.int32)}


# --------------------------------------------------------------------------
# Per-layer decode compute
# --------------------------------------------------------------------------

def _qkv_one(p, x, cfg, positions):
    """x: (B, d) -> q (B,Hq,hd), k,v (B,Hkv,hd), roped at ``positions``."""
    b, d = x.shape
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bd,dh->bh", x, p["wq"]).reshape(b, cfg.n_heads, hd)
    k = jnp.einsum("bd,dh->bh", x, p["wk"]).reshape(b, cfg.n_kv_heads, hd)
    v = jnp.einsum("bd,dh->bh", x, p["wv"]).reshape(b, cfg.n_kv_heads, hd)
    if cfg.rope_theta > 0:
        q = apply_rope(q[:, None], positions[:, None],
                       cfg.rope_theta)[:, 0]
        k = apply_rope(k[:, None], positions[:, None],
                       cfg.rope_theta)[:, 0]
    return q, k, v


def _attn_out(p, out, b):
    return jnp.einsum("bh,hd->bd", out.reshape(b, -1), p["wo"])


def _paged_attn_step(p, x, cache, cfg, step_args):
    """Full-attention decode over the Valet page pool."""
    b = x.shape[0]
    lengths = step_args["lengths"]
    q, k, v = _qkv_one(p, x, cfg, lengths)
    pool = dev.append_token_masked(cache["pool"], k, v,
                                   step_args["append_slot"],
                                   step_args["append_off"],
                                   step_args["active"])
    keys, values, pvalid = dev.gather_pages(pool, step_args["block_table"])
    page = keys.shape[2]
    np_ = keys.shape[1]
    keys = keys.reshape(b, np_ * page, cfg.n_kv_heads, -1)
    values = values.reshape(b, np_ * page, cfg.n_kv_heads, -1)
    pos = jnp.arange(np_ * page)[None]
    valid = (pos <= lengths[:, None]) & jnp.repeat(pvalid, page, axis=1)
    m, l, acc = decode_partial(q, keys, values, valid)
    out = combine_partials((m[None], l[None], acc[None]), x.dtype)
    return _attn_out(p, out, b), {**cache, "pool": pool}


def _ring_attn_step(p, x, cache, cfg, step_args, window):
    b = x.shape[0]
    lengths = step_args["lengths"]
    q, k, v = _qkv_one(p, x, cfg, lengths)
    ring = cache["ring"]
    w = ring.k.shape[1]
    idx = lengths % w
    ring = dev.RingKV(ring.k.at[jnp.arange(b), idx].set(k),
                      ring.v.at[jnp.arange(b), idx].set(v))
    # validity: slot j holds absolute position p_j with p_j = j + w*floor(...)
    # valid iff p_j <= length and p_j > length - window
    slot = jnp.arange(w)[None]
    cur = lengths[:, None]
    abs_pos = cur - ((cur - slot) % w)          # latest absolute pos in slot j
    valid = (abs_pos >= 0) & (abs_pos <= cur) & (abs_pos > cur - window)
    m, l, acc = decode_partial(q, ring.k, ring.v, valid)
    out = combine_partials((m[None], l[None], acc[None]), x.dtype)
    return _attn_out(p, out, b), {**cache, "ring": ring}


def _cross_attn_step(p, x, cache, cfg):
    b = x.shape[0]
    hd = cfg.resolved_head_dim
    q = jnp.einsum("bd,dh->bh", x, p["wq"]).reshape(b, cfg.n_heads, hd)
    valid = jnp.ones(cache["cross_k"].shape[:2], bool)
    m, l, acc = decode_partial(q, cache["cross_k"], cache["cross_v"], valid)
    out = combine_partials((m[None], l[None], acc[None]), x.dtype)
    return _attn_out(p, out, b)


def _ffn_step(p, x, cfg, info: LayerInfo, ctx):
    if info.ffn == "moe":
        out, _ = moe_ffn(p["moe"], x[:, None, :], cfg.moe, mesh=ctx.mesh,
                         model_axis=ctx.model_axis)
        return out[:, 0, :]
    if info.ffn == "gelu":
        return gelu_mlp(p["mlp"], x)
    return swiglu(p["mlp"], x)


def decode_layer(p, x, info: LayerInfo, cache, cfg: ArchConfig,
                 ctx: ParallelCtx, step_args):
    h = rms_norm(p["ln1"], x, cfg.norm_eps)
    new_cache = dict(cache)

    if info.kind in ("attn", "dec"):
        if info.uses_paged:
            a, new_cache = _paged_attn_step(p["attn"], h, new_cache, cfg,
                                            step_args)
        else:
            a, new_cache = _ring_attn_step(p["attn"], h, new_cache, cfg,
                                           step_args, info.window)
        x = x + a
        if info.kind == "dec":
            hx = rms_norm(p["lnx"], x, cfg.norm_eps)
            x = x + _cross_attn_step(p["xattn"], hx, new_cache, cfg)
    elif info.kind == "xattn":
        gate = jnp.tanh(p["xgate"].astype(jnp.float32)).astype(x.dtype)
        x = x + gate * _cross_attn_step(p["xattn"], h, new_cache, cfg)
    elif info.kind == "ssm":
        y, st = ssm_lib.ssm_decode_step(p["ssm"], h, cache["ssm"],
                                        cfg.d_model, cfg.ssm)
        new_cache["ssm"] = st
        x = x + y
    elif info.kind == "hybrid":
        if info.uses_paged:
            a, new_cache = _paged_attn_step(p["attn"], h, new_cache, cfg,
                                            step_args)
        else:
            a, new_cache = _ring_attn_step(p["attn"], h, new_cache, cfg,
                                           step_args, info.window)
        y, st = ssm_lib.ssm_decode_step(p["ssm"], h, cache["ssm"],
                                        cfg.d_model, cfg.ssm)
        new_cache["ssm"] = st
        x = x + 0.5 * (rms_norm(p["attn_norm"], a, cfg.norm_eps)
                       + rms_norm(p["ssm_norm"], y, cfg.norm_eps))

    if info.ffn != "none":
        h2 = rms_norm(p["ln2"], x, cfg.norm_eps)
        x = x + _ffn_step(p, h2, cfg, info, ctx)
    return x, new_cache


def decode_step(params, caches, tokens, cfg: ArchConfig, ctx: ParallelCtx,
                block_table, append_slot, append_off, active=None):
    """One decode step.  tokens: (B,) int32.  Returns (logits, caches).

    ``active``: (B,) bool — inactive batch slots neither append KV nor
    advance their length (continuous batching with holes).
    """
    x = params["embed"][tokens].astype(ctx.compute_dtype)
    if cfg.family == "audio":
        # sinusoidal position at each sequence's current length
        d = cfg.d_model
        posf = caches["lengths"].astype(jnp.float32)[:, None]
        i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
        ang = posf / (10_000.0 ** (2 * i / d))
        x = x + jnp.concatenate([jnp.sin(ang), jnp.cos(ang)],
                                axis=-1).astype(x.dtype)

    if active is None:
        active = jnp.ones(tokens.shape, bool)
    step_args = {
        "lengths": caches["lengths"],
        "block_table": block_table,
        "append_slot": append_slot,
        "append_off": append_off,
        "active": active,
    }
    new_layers = []
    infos = layer_infos(cfg)
    for info, cache in zip(infos, caches["layers"]):
        p = layer_params(params, info)
        x, cache = decode_layer(p, x, info, cache, cfg, ctx, step_args)
        new_layers.append(cache)

    x = rms_norm(params["final_ln"], x, cfg.norm_eps)
    w = unembed_matrix(params, cfg).astype(x.dtype)
    logits = mask_vocab_pad(
        jnp.einsum("bd,dv->bv", x, w).astype(jnp.float32), cfg)
    new_len = caches["lengths"] + active.astype(jnp.int32)
    return logits, {"layers": new_layers, "lengths": new_len}


# --------------------------------------------------------------------------
# Cache-building prefill (exact, unrolled)
# --------------------------------------------------------------------------

def prefill(params, tokens, cfg: ArchConfig, ctx: ParallelCtx, caches,
            block_table, frontend=None):
    """Run the prompt through the model, filling every cache.

    tokens: (B, S) — equal prompt lengths per prefill batch (engine pads).
    block_table: (B, P) pre-allocated slots for ceil(S/page) pages (plus the
    current partial page).  Returns (last_logits, caches).
    """
    b, s = tokens.shape
    hd = cfg.resolved_head_dim
    x = params["embed"][tokens].astype(ctx.compute_dtype)
    if cfg.family == "audio":
        x = x + _sinusoidal(s, cfg.d_model).astype(x.dtype)

    enc_out = None
    if cfg.family == "audio":
        assert frontend is not None
        from repro.models.transformer import run_segments
        e = frontend.astype(ctx.compute_dtype)
        e = e + _sinusoidal(e.shape[1], cfg.d_model).astype(e.dtype)
        e, _ = run_segments(params["enc_segments"], encoder_segments(cfg),
                            e, cfg, ctx)
        enc_out = rms_norm(params["enc_ln"], e, cfg.norm_eps)
    elif frontend is not None:
        enc_out = frontend.astype(ctx.compute_dtype)

    positions = jnp.arange(s)[None]
    new_layers = []
    for info, cache in zip(layer_infos(cfg), caches["layers"]):
        p = layer_params(params, info)
        cache = dict(cache)
        h = rms_norm(p["ln1"], x, cfg.norm_eps)

        if info.kind in ("attn", "dec", "hybrid"):
            ap = p["attn"]
            q = jnp.einsum("bsd,dh->bsh", h, ap["wq"]).reshape(
                b, s, cfg.n_heads, hd)
            k = jnp.einsum("bsd,dh->bsh", h, ap["wk"]).reshape(
                b, s, cfg.n_kv_heads, hd)
            v = jnp.einsum("bsd,dh->bsh", h, ap["wv"]).reshape(
                b, s, cfg.n_kv_heads, hd)
            if cfg.rope_theta > 0:
                q = apply_rope(q, positions, cfg.rope_theta)
                k = apply_rope(k, positions, cfg.rope_theta)
            a = blockwise_attention(q, k, v, causal=True, window=info.window,
                                    q_block=ctx.q_block,
                                    kv_block=ctx.kv_block)
            a = jnp.einsum("bsh,hd->bsd", a.reshape(b, s, -1), ap["wo"])

            if info.uses_paged:
                page = cache["pool"].k.shape[1]
                pad = (-s) % page
                kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                npages = kp.shape[1] // page
                kp = kp.reshape(b, npages, page, cfg.n_kv_heads, hd)
                vp = vp.reshape(b, npages, page, cfg.n_kv_heads, hd)
                cache["pool"] = dev.write_prefill_pages(
                    cache["pool"], kp, vp, block_table[:, :npages])
            if info.uses_ring:
                w = cache["ring"].k.shape[1]
                ring = cache["ring"]
                take = min(w, s)
                tail = jnp.arange(s - take, s)
                ring = dev.RingKV(
                    ring.k.at[:, tail % w].set(k[:, tail]),
                    ring.v.at[:, tail % w].set(v[:, tail]))
                cache["ring"] = ring

        if info.kind in ("attn", "dec"):
            x = x + a
            if info.kind == "dec":
                hx = rms_norm(p["lnx"], x, cfg.norm_eps)
                xp = p["xattn"]
                cache["cross_k"] = jnp.einsum(
                    "bnd,dh->bnh", enc_out, xp["wk"]).reshape(
                        b, -1, cfg.n_kv_heads, hd)
                cache["cross_v"] = jnp.einsum(
                    "bnd,dh->bnh", enc_out, xp["wv"]).reshape(
                        b, -1, cfg.n_kv_heads, hd)
                qx = jnp.einsum("bsd,dh->bsh", hx, xp["wq"]).reshape(
                    b, s, cfg.n_heads, hd)
                ax = blockwise_attention(qx, cache["cross_k"],
                                         cache["cross_v"], causal=False,
                                         q_block=min(256, s))
                x = x + jnp.einsum("bsh,hd->bsd", ax.reshape(b, s, -1),
                                   xp["wo"])
        elif info.kind == "xattn":
            xp = p["xattn"]
            cache["cross_k"] = jnp.einsum(
                "bnd,dh->bnh", enc_out, xp["wk"]).reshape(
                    b, -1, cfg.n_kv_heads, hd)
            cache["cross_v"] = jnp.einsum(
                "bnd,dh->bnh", enc_out, xp["wv"]).reshape(
                    b, -1, cfg.n_kv_heads, hd)
            qx = jnp.einsum("bsd,dh->bsh", h, xp["wq"]).reshape(
                b, s, cfg.n_heads, hd)
            ax = blockwise_attention(qx, cache["cross_k"], cache["cross_v"],
                                     causal=False, q_block=min(256, s))
            gate = jnp.tanh(p["xgate"].astype(jnp.float32)).astype(x.dtype)
            x = x + gate * jnp.einsum("bsh,hd->bsd", ax.reshape(b, s, -1),
                                      xp["wo"])
        elif info.kind == "ssm":
            y, st = ssm_lib.ssm_forward(p["ssm"], h, cfg.d_model, cfg.ssm,
                                        return_state=True)
            cache["ssm"] = st
            x = x + y
        elif info.kind == "hybrid":
            y, st = ssm_lib.ssm_forward(p["ssm"], h, cfg.d_model, cfg.ssm,
                                        return_state=True)
            cache["ssm"] = st
            x = x + 0.5 * (rms_norm(p["attn_norm"], a, cfg.norm_eps)
                           + rms_norm(p["ssm_norm"], y, cfg.norm_eps))

        if info.ffn != "none":
            h2 = rms_norm(p["ln2"], x, cfg.norm_eps)
            x = x + _ffn_step(p, h2.reshape(b * s, -1), cfg, info,
                              ctx).reshape(b, s, -1)
        new_layers.append(cache)

    x = rms_norm(params["final_ln"], x, cfg.norm_eps)
    w = unembed_matrix(params, cfg).astype(x.dtype)
    logits = mask_vocab_pad(
        jnp.einsum("bd,dv->bv", x[:, -1], w).astype(jnp.float32), cfg)
    return logits, {"layers": new_layers,
                    "lengths": jnp.full((b,), s, jnp.int32)}
