"""Model assembly: segment-based layer stacks for all 10 assigned archs.

An architecture is a list of ``Segment``s — homogeneous runs of layers that
are scanned with ``lax.scan`` over stacked parameters.  Heterogeneous
patterns (gemma3's 5:1 local:global, hymba's 3 global layers, llama-vision's
every-5th cross-attention layer, whisper's enc/dec) become short segment
lists, so the compiled HLO stays O(#segments), not O(#layers).

Everything is a pure function of a parameter pytree; sharding is expressed
with ``PartitionSpec`` rules keyed on parameter paths (``param_pspecs``) plus
activation constraints at segment boundaries.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, List, Tuple

import jax
import jax.ad_checkpoint
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.models import attention as attn_lib
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib
from repro.models.layers import (
    KeyGen, normal_init, rms_norm, apply_rope, swiglu, init_swiglu,
    gelu_mlp, init_gelu_mlp,
)


# --------------------------------------------------------------------------
# Parallel context
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class ParallelCtx:
    """Mesh + axis names + model-execution knobs."""
    mesh: Any = None
    dp_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"
    remat: bool = True
    q_block: int = 512
    kv_block: int = 512
    loss_chunk: int = 256
    compute_dtype: Any = jnp.float32
    attn_impl: str = "reference"          # reference | pallas
    seq_parallel: bool = False            # shard residuals on S over model
                                          # (refuted for train: §Perf iter 2)
    save_collectives: bool = False        # remat policy: save attn/mlp
                                          # outputs so backward skips
                                          # re-running their collectives

    def residual_spec(self):
        """Layer-boundary activation sharding (B, S, d)."""
        return (self.dp, self.model_axis if self.seq_parallel else None,
                None)

    @property
    def dp(self):
        """Leading batch mesh axes as a PartitionSpec entry."""
        return self.dp_axes if len(self.dp_axes) > 1 else self.dp_axes[0]

    def dp_size(self):
        if self.mesh is None:
            return 1
        n = 1
        for a in self.dp_axes:
            n *= self.mesh.shape[a]
        return n


def shard(x, ctx: ParallelCtx, *spec):
    if ctx.mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(ctx.mesh, P(*spec)))


# --------------------------------------------------------------------------
# Segments
# --------------------------------------------------------------------------

@dataclass(frozen=True)
class Segment:
    kind: str            # attn | ssm | hybrid | xattn | enc | dec
    count: int
    window: int = 0      # 0 = full attention
    ffn: str = "swiglu"  # swiglu | moe | gelu | none
    d_ff: int = 0        # 0 -> cfg.d_ff


def segments(cfg: ArchConfig) -> List[Segment]:
    return [s for s in _segments(cfg) if s.count > 0]


def _segments(cfg: ArchConfig) -> List[Segment]:
    if cfg.family == "ssm":
        return [Segment("ssm", cfg.n_layers, ffn="none")]

    if cfg.family == "moe":
        segs = []
        if cfg.n_dense_layers:
            segs.append(Segment("attn", cfg.n_dense_layers, ffn="swiglu",
                                d_ff=cfg.dense_d_ff))
        segs.append(Segment("attn", cfg.n_layers - cfg.n_dense_layers,
                            ffn="moe"))
        return segs

    if cfg.family == "hybrid":
        # hymba: global full attention at layers {0, mid, last}, SWA elsewhere
        l = cfg.n_layers
        mid = l // 2 - 1
        segs = [Segment("hybrid", 1, window=0)]
        segs.append(Segment("hybrid", mid - 1, window=cfg.window))
        segs.append(Segment("hybrid", 1, window=0))
        segs.append(Segment("hybrid", l - mid - 2, window=cfg.window))
        segs.append(Segment("hybrid", 1, window=0))
        return segs

    if cfg.family == "vlm":
        # every 5th layer is a gated cross-attention layer
        segs = []
        n_groups = cfg.n_layers // cfg.xattn_every
        for _ in range(n_groups):
            segs.append(Segment("attn", cfg.xattn_every - 1))
            segs.append(Segment("xattn", 1))
        rem = cfg.n_layers - n_groups * cfg.xattn_every
        if rem:
            segs.append(Segment("attn", rem))
        return segs

    if cfg.family == "audio":
        return [Segment("dec", cfg.n_layers, ffn="gelu")]

    # dense: uniform or local:global interleave
    if cfg.global_every:
        per = cfg.global_every
        segs = []
        full_groups = cfg.n_layers // per
        for _ in range(full_groups):
            segs.append(Segment("attn", per - 1, window=cfg.window))
            segs.append(Segment("attn", 1, window=0))
        rem = cfg.n_layers - full_groups * per
        if rem > 1:
            segs.append(Segment("attn", rem - 1, window=cfg.window))
        if rem >= 1:
            segs.append(Segment("attn", 1, window=0))
        return segs
    return [Segment("attn", cfg.n_layers, window=cfg.window)]


def encoder_segments(cfg: ArchConfig) -> List[Segment]:
    assert cfg.family == "audio"
    return [Segment("enc", cfg.encoder_layers, ffn="gelu")]


# --------------------------------------------------------------------------
# Init (one layer), then stacked per segment
# --------------------------------------------------------------------------

def _init_attn_proj(kg, cfg: ArchConfig, dtype):
    d, hd = cfg.d_model, cfg.resolved_head_dim
    return {
        "wq": normal_init(kg(), (d, cfg.n_heads * hd), dtype=dtype),
        "wk": normal_init(kg(), (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wv": normal_init(kg(), (d, cfg.n_kv_heads * hd), dtype=dtype),
        "wo": normal_init(kg(), (cfg.n_heads * hd, d),
                          scale=0.02 / math.sqrt(2 * cfg.n_layers), dtype=dtype),
    }


def _init_ffn(kg, cfg: ArchConfig, seg: Segment, dtype):
    d = cfg.d_model
    if seg.ffn == "moe":
        return {"moe": moe_lib.init_moe(kg, d, cfg.moe, dtype)}
    if seg.ffn == "gelu":
        return {"mlp": init_gelu_mlp(kg, d, seg.d_ff or cfg.d_ff, dtype)}
    if seg.ffn == "none":
        return {}
    return {"mlp": init_swiglu(kg, d, seg.d_ff or cfg.d_ff, dtype)}


def init_layer(kg, cfg: ArchConfig, seg: Segment, dtype=jnp.float32):
    d = cfg.d_model
    p = {"ln1": jnp.zeros((d,), dtype)}
    if seg.kind in ("attn", "enc", "dec", "hybrid"):
        p["attn"] = _init_attn_proj(kg, cfg, dtype)
    if seg.kind == "dec":
        p["lnx"] = jnp.zeros((d,), dtype)
        p["xattn"] = _init_attn_proj(kg, cfg, dtype)
    if seg.kind == "xattn":
        p["xattn"] = _init_attn_proj(kg, cfg, dtype)
        p["xgate"] = jnp.zeros((), jnp.float32)
    if seg.kind in ("ssm", "hybrid"):
        p["ssm"] = ssm_lib.init_ssm(kg, d, cfg.ssm, dtype)
    if seg.kind == "hybrid":
        p["attn_norm"] = jnp.zeros((d,), dtype)
        p["ssm_norm"] = jnp.zeros((d,), dtype)
    if seg.ffn != "none":
        p["ln2"] = jnp.zeros((d,), dtype)
        p.update(_init_ffn(kg, cfg, seg, dtype))
    return p


def _init_stack(kg, cfg: ArchConfig, seg: Segment, dtype):
    """A segment's ``seg.count`` layers, generated directly in their stacked
    layout (one key per layer, vmapped): the per-layer trees never exist
    beside the stack, and a jitted init compiles one layer, not all."""
    keys = jax.random.split(kg(), seg.count)
    return jax.vmap(lambda k: init_layer(KeyGen(k), cfg, seg, dtype))(keys)


def init_params(key, cfg: ArchConfig, dtype=jnp.float32):
    kg = KeyGen(key)
    d = cfg.d_model
    params = {
        "embed": normal_init(kg(), (cfg.padded_vocab, d), dtype=dtype),
        "final_ln": jnp.zeros((d,), dtype),
        "segments": [_init_stack(kg, cfg, seg, dtype)
                     for seg in segments(cfg)],
    }
    if not cfg.tie_embeddings:
        params["unembed"] = normal_init(kg(), (d, cfg.padded_vocab),
                                        dtype=dtype)
    if cfg.family == "audio":
        params["enc_segments"] = [_init_stack(kg, cfg, seg, dtype)
                                  for seg in encoder_segments(cfg)]
        params["enc_ln"] = jnp.zeros((d,), dtype)
    return params


# --------------------------------------------------------------------------
# PartitionSpec rules (keyed on parameter path)
# --------------------------------------------------------------------------

_SPEC_RULES = [
    # (path fragment, spec for trailing dims)
    ("embed", P("model", None)),
    ("unembed", P(None, "model")),
    ("experts/wg", P("model", None, None)),
    ("experts/wu", P("model", None, None)),
    ("experts/wd", P("model", None, None)),
    ("router", P(None, None)),
    ("attn/wq", P(None, "model")),
    ("attn/wk", P(None, "model")),
    ("attn/wv", P(None, "model")),
    ("attn/wo", P("model", None)),
    ("xattn/wq", P(None, "model")),
    ("xattn/wk", P(None, "model")),
    ("xattn/wv", P(None, "model")),
    ("xattn/wo", P("model", None)),
    ("mlp/wgu", P(None, "model")),
    ("mlp/wd", P("model", None)),
    ("mlp/wi", P(None, "model")),
    ("mlp/wo", P("model", None)),
    ("shared/wgu", P(None, "model")),
    ("shared/wd", P("model", None)),
    ("ssm/wz", P(None, "model")),
    ("ssm/wx", P(None, "model")),
    ("ssm/wdt", P(None, "model")),
    ("ssm/wbc", P(None, None)),
    ("ssm/conv_x", P(None, "model")),
    ("ssm/out_proj", P("model", None)),
    ("ssm/gate_norm", P("model")),
    ("ssm/A_log", P("model")),
    ("ssm/D", P("model")),
    ("ssm/dt_bias", P("model")),
]


def _path_str(path):
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def param_pspecs(params_shape, cfg: ArchConfig, model_size: int = 16):
    """PartitionSpec tree matching a params (shape-)tree.

    Dimensions that don't divide the model-axis size fall back to
    replication (e.g. hymba's 50 SSD heads, 25 attention heads)."""

    kv_shardable = cfg.n_kv_heads % model_size == 0 if cfg.n_kv_heads else True

    def spec_for(path, leaf):
        ps = _path_str(path)
        ndim = len(leaf.shape)
        # K/V projections: replicate when kv heads don't divide TP — a
        # flat-sharded wk/wv costs a full (B,S,d) all-reduce in the backward
        # (dx contraction over the sharded kv dim); replicated weights make
        # fwd AND bwd collective-free (§Perf iteration 4)
        if not kv_shardable and (ps.endswith("attn/wk")
                                 or ps.endswith("attn/wv")
                                 or ps.endswith("xattn/wk")
                                 or ps.endswith("xattn/wv")):
            return P(*([None] * ndim))
        for frag, spec in _SPEC_RULES:
            if frag in ps:
                pad = ndim - len(spec)
                parts = [None] * pad + list(spec)
                for i, ax in enumerate(parts):
                    if ax == "model" and leaf.shape[i] % model_size != 0:
                        parts[i] = None
                return P(*parts)
        return P(*([None] * ndim))

    return jax.tree_util.tree_map_with_path(spec_for, params_shape)


# --------------------------------------------------------------------------
# Layer application (train / prefill)
# --------------------------------------------------------------------------

def _attend(p, x, cfg: ArchConfig, ctx: ParallelCtx, *, window, causal=True,
            kv=None, positions=None, q_block=None):
    """Projections + RoPE + blockwise attention + output proj.

    TP strategy: shard attention by query heads when ``n_heads`` divides the
    model axis.  When ``n_kv_heads`` does NOT divide it (granite 8, vlm 8,
    danube 8, gemma3 4), KV is computed replicated (tiny) and repeated to
    the query-head count before attention — a sharded-friendly MHA view.
    A KV-head sharding constraint there would trigger GSPMD's involuntary
    full-rematerialization (full replication of every attention tensor per
    layer) — the dominant collective cost in the baseline dry-run (§Perf
    iteration 1).
    """
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    src = kv if kv is not None else x
    tp = ctx.mesh.shape[ctx.model_axis] if ctx.mesh is not None else 1
    q_shardable = cfg.n_heads % tp == 0
    kv_shardable = cfg.n_kv_heads % tp == 0

    q = jnp.einsum("bsd,dh->bsh", x, p["wq"]).reshape(b, s, cfg.n_heads, hd)
    k = jnp.einsum("btd,dh->bth", src, p["wk"]).reshape(
        b, src.shape[1], cfg.n_kv_heads, hd)
    v = jnp.einsum("btd,dh->bth", src, p["wv"]).reshape(
        b, src.shape[1], cfg.n_kv_heads, hd)

    q_spec = "model" if q_shardable else None
    q = shard(q, ctx, ctx.dp, None, q_spec, None)
    if kv_shardable:
        k = shard(k, ctx, ctx.dp, None, "model", None)
        v = shard(v, ctx, ctx.dp, None, "model", None)
    else:
        k = shard(k, ctx, ctx.dp, None, None, None)
        v = shard(v, ctx, ctx.dp, None, None, None)

    if kv is None and cfg.rope_theta > 0:
        pos = positions if positions is not None else jnp.arange(s)[None]
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, pos, cfg.rope_theta)

    if not kv_shardable and q_shardable and cfg.n_kv_heads < cfg.n_heads:
        group = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        k = shard(k, ctx, ctx.dp, None, "model", None)
        v = shard(v, ctx, ctx.dp, None, "model", None)

    n_pad = 0
    if (not q_shardable and tp > 1 and cfg.n_heads == cfg.n_kv_heads):
        # MHA with heads ∤ TP (whisper 20H): transient zero-pad to the next
        # TP multiple so attention shards by heads.  Exact: padded q rows
        # are sliced off before the output projection (§Perf iteration 9).
        hpad = -(-cfg.n_heads // tp) * tp
        n_pad = hpad - cfg.n_heads
        padw = ((0, 0), (0, 0), (0, n_pad), (0, 0))
        q = shard(jnp.pad(q, padw), ctx, ctx.dp, None, "model", None)
        k = shard(jnp.pad(k, padw), ctx, ctx.dp, None, "model", None)
        v = shard(jnp.pad(v, padw), ctx, ctx.dp, None, "model", None)

    out = attn_lib.blockwise_attention(
        q, k, v, causal=causal, window=window,
        q_block=q_block or ctx.q_block, kv_block=ctx.kv_block)
    if n_pad:
        out = out[:, :, : cfg.n_heads]
    out = out.reshape(b, s, cfg.n_heads * hd)
    return jnp.einsum("bsh,hd->bsd", out, p["wo"])


def _apply_ffn(p, x, cfg: ArchConfig, ctx: ParallelCtx, seg: Segment):
    if seg.ffn == "moe":
        out, aux = moe_lib.moe_ffn(
            p["moe"], x, cfg.moe, mesh=ctx.mesh,
            model_axis=ctx.model_axis, dp_spec=P(ctx.dp, None, None))
        return out, aux
    if seg.ffn == "gelu":
        return gelu_mlp(p["mlp"], x), 0.0
    return swiglu(p["mlp"], x), 0.0


def apply_layer(p, x, seg: Segment, cfg: ArchConfig, ctx: ParallelCtx,
                frontend=None, positions=None):
    """One layer.  x: (B, S, d).  Returns (x, aux_loss)."""
    aux = 0.0
    h = rms_norm(p["ln1"], x, cfg.norm_eps)

    if seg.kind in ("attn", "enc", "dec"):
        causal = seg.kind != "enc"
        a_out = _attend(p["attn"], h, cfg, ctx, window=seg.window,
                        causal=causal, positions=positions)
        x = x + jax.ad_checkpoint.checkpoint_name(a_out, "attn_out")
        if seg.kind == "dec":
            hx = rms_norm(p["lnx"], x, cfg.norm_eps)
            x = x + _attend(p["xattn"], hx, cfg, ctx, window=0, causal=False,
                            kv=frontend, q_block=256)
    elif seg.kind == "xattn":
        gate = jnp.tanh(p["xgate"].astype(jnp.float32)).astype(x.dtype)
        x = x + gate * _attend(p["xattn"], h, cfg, ctx, window=0,
                               causal=False, kv=frontend, q_block=256)
    elif seg.kind == "ssm":
        x = x + ssm_lib.ssm_forward(p["ssm"], h, cfg.d_model, cfg.ssm)
    elif seg.kind == "hybrid":
        a = _attend(p["attn"], h, cfg, ctx, window=seg.window,
                    positions=positions)
        m = ssm_lib.ssm_forward(p["ssm"], h, cfg.d_model, cfg.ssm)
        x = x + 0.5 * (rms_norm(p["attn_norm"], a, cfg.norm_eps)
                       + rms_norm(p["ssm_norm"], m, cfg.norm_eps))
    else:
        raise ValueError(seg.kind)

    if seg.ffn != "none":
        h2 = rms_norm(p["ln2"], x, cfg.norm_eps)
        out, a = _apply_ffn(p, h2, cfg, ctx, seg)
        x = x + jax.ad_checkpoint.checkpoint_name(out, "mlp_out")
        aux = aux + a
    return shard(x, ctx, *ctx.residual_spec()), aux


def run_segments(seg_params, segs, x, cfg, ctx, frontend=None, positions=None):
    """Apply all segments; scan over stacked layers within each."""
    aux_total = jnp.zeros((), jnp.float32)
    for p_stack, seg in zip(seg_params, segs):
        def body(carry, p_layer, seg=seg):
            xc, auxc = carry
            xo, a = apply_layer(p_layer, xc, seg, cfg, ctx,
                                frontend=frontend, positions=positions)
            return (xo, auxc + jnp.asarray(a, jnp.float32)), None

        if ctx.remat:
            if ctx.save_collectives:
                policy = jax.checkpoint_policies.save_only_these_names(
                    "attn_out", "mlp_out")
                body = jax.checkpoint(body, policy=policy)
            else:
                body = jax.checkpoint(body)
        if seg.count == 1:
            p_layer = jax.tree.map(lambda a: a[0], p_stack)
            (x, aux_total), _ = body((x, aux_total), p_layer)
        else:
            (x, aux_total), _ = jax.lax.scan(body, (x, aux_total), p_stack)
    return x, aux_total


# --------------------------------------------------------------------------
# Forward + loss
# --------------------------------------------------------------------------

def _sinusoidal(s, d):
    pos = jnp.arange(s)[:, None].astype(jnp.float32)
    i = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    ang = pos / (10_000.0 ** (2 * i / d))
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def forward_hidden(params, tokens, cfg: ArchConfig, ctx: ParallelCtx,
                   frontend=None):
    """Token ids -> final hidden states (B, S, d)."""
    x = params["embed"][tokens].astype(ctx.compute_dtype)
    if cfg.family == "audio":
        x = x + _sinusoidal(tokens.shape[1], cfg.d_model).astype(x.dtype)
    x = shard(x, ctx, *ctx.residual_spec())

    enc_out = None
    if cfg.family == "audio":
        assert frontend is not None, "audio arch needs frame embeddings"
        e = frontend.astype(ctx.compute_dtype)
        e = e + _sinusoidal(e.shape[1], cfg.d_model).astype(e.dtype)
        e = shard(e, ctx, ctx.dp, None, None)
        e, _ = run_segments(params["enc_segments"], encoder_segments(cfg),
                            e, cfg, ctx)
        enc_out = rms_norm(params["enc_ln"], e, cfg.norm_eps)
    elif frontend is not None:
        enc_out = shard(frontend.astype(ctx.compute_dtype), ctx,
                        ctx.dp, None, None)

    x, aux = run_segments(params["segments"], segments(cfg), x, cfg, ctx,
                          frontend=enc_out)
    x = rms_norm(params["final_ln"], x, cfg.norm_eps)
    return x, aux


def unembed_matrix(params, cfg: ArchConfig):
    if cfg.tie_embeddings:
        return params["embed"].T
    return params["unembed"]


def mask_vocab_pad(logits, cfg: ArchConfig):
    """-inf the padded vocab tail (see ArchConfig.padded_vocab)."""
    if cfg.padded_vocab == cfg.vocab:
        return logits
    ids = jnp.arange(logits.shape[-1])
    return jnp.where(ids < cfg.vocab, logits, -1e30)


def lm_loss(params, tokens, labels, cfg: ArchConfig, ctx: ParallelCtx,
            frontend=None):
    """Mean next-token cross-entropy, vocab-chunked over the sequence.

    Never materializes (B, S, V) logits: the sequence is processed in
    ``ctx.loss_chunk`` slices with the chunk body rematerialized.
    """
    h, aux = forward_hidden(params, tokens, cfg, ctx, frontend=frontend)
    # one explicit gather of h per microbatch (instead of per loss chunk)
    h = shard(h, ctx, ctx.dp, None, None)
    w = unembed_matrix(params, cfg).astype(h.dtype)
    b, s, d = h.shape
    chunk = min(ctx.loss_chunk, s)
    assert s % chunk == 0
    nc = s // chunk

    def dense_chunk_nll(hs, ls):
        """Single-shard chunk NLL (no mesh)."""
        logits = jnp.einsum("bcd,dv->bcv", hs, w).astype(jnp.float32)
        logits = mask_vocab_pad(logits, cfg)
        lse = jax.nn.logsumexp(logits, axis=-1)
        onehot = jax.nn.one_hot(jnp.maximum(ls, 0), logits.shape[-1],
                                dtype=logits.dtype)
        picked = jnp.einsum("bcv,bcv->bc", logits, onehot)
        return lse - picked

    def sharded_chunk_nll(hs, ls):
        """Explicit vocab-sharded chunk NLL inside shard_map — GSPMD never
        materializes full-vocab logits (§Perf iteration 4)."""
        v_pad = w.shape[1]
        mp = ctx.mesh.shape[ctx.model_axis]
        v_loc = v_pad // mp

        def body(hs_l, w_l, ls_l):
            rank = jax.lax.axis_index(ctx.model_axis)
            logits = jnp.einsum("bcd,dv->bcv", hs_l,
                                w_l).astype(jnp.float32)
            ids = rank * v_loc + jnp.arange(v_loc)
            logits = jnp.where(ids[None, None, :] < cfg.vocab, logits, -1e30)
            m_loc = jax.lax.stop_gradient(logits.max(axis=-1))
            # all_gather of the tiny per-shard maxes (pmax lacks a JVP rule)
            m = jax.lax.all_gather(m_loc, ctx.model_axis).max(axis=0)
            sumexp = jax.lax.psum(
                jnp.exp(logits - m[..., None]).sum(-1), ctx.model_axis)
            lse = jnp.log(sumexp) + m
            onehot = jax.nn.one_hot(ls_l - rank * v_loc, v_loc,
                                    dtype=logits.dtype)   # OOB -> zeros
            picked = jax.lax.psum(
                jnp.einsum("bcv,bcv->bc", logits, onehot), ctx.model_axis)
            return lse - picked

        from repro.models.moe import _shard_map
        bspec = P(ctx.dp, None, None)
        return _shard_map(
            body, ctx.mesh,
            (bspec, P(None, ctx.model_axis), P(ctx.dp, None)),
            P(ctx.dp, None),
        )(hs, w, jnp.maximum(ls, 0))

    def chunk_body(carry, i):
        hs = jax.lax.dynamic_slice_in_dim(h, i * chunk, chunk, axis=1)
        ls = jax.lax.dynamic_slice_in_dim(labels, i * chunk, chunk, axis=1)
        if ctx.mesh is not None:
            nll = sharded_chunk_nll(hs, ls)
        else:
            nll = dense_chunk_nll(hs, ls)
        valid = ls >= 0
        nll = jnp.where(valid, nll, 0.0)
        return (carry[0] + nll.sum(), carry[1] + valid.sum()), None

    body = jax.checkpoint(chunk_body) if ctx.remat else chunk_body
    init = (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    (total, count), _ = jax.lax.scan(body, init, jnp.arange(nc))
    return total / jnp.maximum(count, 1) + aux


def prefill_logits(params, tokens, cfg: ArchConfig, ctx: ParallelCtx,
                   frontend=None):
    """Prefill forward returning last-position logits (B, V)."""
    h, _ = forward_hidden(params, tokens, cfg, ctx, frontend=frontend)
    w = unembed_matrix(params, cfg).astype(h.dtype)
    logits = jnp.einsum("bd,dv->bv", h[:, -1], w).astype(jnp.float32)
    return mask_vocab_pad(logits, cfg)
