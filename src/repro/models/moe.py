"""Mixture-of-experts FFN with expert parallelism.

Two paths:

* ``moe_ffn_reference`` — exact loop-over-experts oracle (no capacity drops).
* ``moe_ffn`` — capacity-bounded sort-based dispatch.  Under a mesh it runs
  inside ``shard_map`` with experts partitioned over the ``model`` axis (EP):
  tokens are TP-replicated, each rank dispatches only the tokens routed to
  *its* experts, and the combine ``psum`` doubles as the Megatron-TP
  all-reduce.  No all-to-all is needed because activations are already
  model-replicated at the FFN boundary.

Shared experts (deepseek/qwen style) run as one fused SwiGLU outside the
shard_map region; GSPMD shards them over d_ff like a dense FFN.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import MoEConfig
from repro.models.layers import KeyGen, normal_init, swiglu, init_swiglu


def padded_experts(moe: MoEConfig, ep_align: int = 16) -> int:
    """Expert-table size padded so EP shards cleanly (qwen: 60 -> 64).

    Padding experts are never routed to (router has n_experts logits)."""
    return -(-moe.n_experts // ep_align) * ep_align


def init_moe(kg: KeyGen, d: int, moe: MoEConfig, dtype=jnp.float32):
    e_pad = padded_experts(moe)
    params = {
        "router": normal_init(kg(), (d, moe.n_experts), scale=0.006, dtype=jnp.float32),
        "experts": {
            "wg": normal_init(kg(), (e_pad, d, moe.d_expert), dtype=dtype),
            "wu": normal_init(kg(), (e_pad, d, moe.d_expert), dtype=dtype),
            "wd": normal_init(kg(), (e_pad, moe.d_expert, d), dtype=dtype),
        },
    }
    if moe.n_shared:
        params["shared"] = init_swiglu(kg, d, moe.n_shared * moe.d_expert, dtype)
    return params


def router_topk(params, x, moe: MoEConfig):
    """Router probabilities + top-k selection + aux losses.

    x: (T, d).  Returns (eids (T,k) int32, gates (T,k) f32, aux_loss scalar).
    """
    logits = jnp.einsum("td,de->te", x.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eids = jax.lax.top_k(probs, moe.top_k)
    if moe.renorm_topk:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balance aux: E * sum_e (frac tokens to e) * (mean prob of e)
    e = moe.n_experts
    ind = jax.nn.one_hot(eids, e, dtype=jnp.float32).sum(1)          # (T,E)
    f_e = ind.mean(0) / moe.top_k
    p_e = probs.mean(0)
    aux = e * jnp.sum(f_e * p_e) * moe.router_aux_coef
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2) * moe.router_z_coef
    return eids, gates, aux + zloss


def moe_ffn_reference(params, x, moe: MoEConfig):
    """Exact oracle: every expert applied to every token, masked combine."""
    t, d = x.shape
    eids, gates, aux = router_topk(params, x, moe)
    out = jnp.zeros((t, d), jnp.float32)
    for e in range(moe.n_experts):
        g = jnp.einsum("td,df->tf", x, params["experts"]["wg"][e])
        u = jnp.einsum("td,df->tf", x, params["experts"]["wu"][e])
        he = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
        ye = jnp.einsum("tf,fd->td", he,
                        params["experts"]["wd"][e]).astype(jnp.float32)
        w = jnp.where(eids == e, gates, 0.0).sum(-1)                 # (T,)
        out = out + w[:, None] * ye
    if moe.n_shared:
        out = out + swiglu(params["shared"], x).astype(jnp.float32)
    return out.astype(x.dtype), aux


def _dispatch_local(x, eids, gates, wg, wu, wd, *, e_base, e_local, cap):
    """Capacity-bounded dispatch of tokens to the local expert shard.

    x: (T, d); eids/gates: (T, k); w*: (E_loc, ...); returns (T, d) partial.
    """
    t, d = x.shape
    k = eids.shape[1]
    flat_e = eids.reshape(-1) - e_base                               # (T*k,)
    flat_t = jnp.repeat(jnp.arange(t), k)
    flat_g = gates.reshape(-1)
    valid = (flat_e >= 0) & (flat_e < e_local)

    # stable sort by local expert; invalid entries pushed to the end
    order = jnp.argsort(jnp.where(valid, flat_e, e_local), stable=True)
    sel = order[: e_local * cap]
    e_sel = jnp.where(valid[sel], flat_e[sel], e_local)              # pad bin
    t_sel = flat_t[sel]
    g_sel = jnp.where(valid[sel], flat_g[sel], 0.0)

    # position within each expert group (entries already grouped)
    onehot = jax.nn.one_hot(e_sel, e_local, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot                        # (C, E_loc)
    pos_sel = jnp.take_along_axis(
        pos, jnp.minimum(e_sel, e_local - 1)[:, None], axis=1)[:, 0]
    keep = (e_sel < e_local) & (pos_sel < cap)
    g_sel = jnp.where(keep, g_sel, 0.0)
    slot_e = jnp.where(keep, e_sel, 0)
    slot_p = jnp.where(keep, pos_sel, cap)                           # cap = pad row

    buf = jnp.zeros((e_local, cap + 1, d), x.dtype)
    buf = buf.at[slot_e, slot_p].set(x[t_sel])

    h = jnp.einsum("ecd,edf->ecf", buf, wg)
    u = jnp.einsum("ecd,edf->ecf", buf, wu)
    h = jax.nn.silu(h.astype(jnp.float32)).astype(x.dtype) * u
    y = jnp.einsum("ecf,efd->ecd", h, wd)                            # (E_loc,C+1,d)

    out = jnp.zeros((t, d), jnp.float32)
    vals = y[slot_e, slot_p].astype(jnp.float32) * g_sel[:, None]
    out = out.at[t_sel].add(jnp.where(keep[:, None], vals, 0.0))
    return out


def _shard_map(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` without the replication check (the bodies use
    collectives whose replication the checker cannot infer)."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def moe_ffn(params, x, moe: MoEConfig, *, mesh=None, model_axis="model",
            dp_spec=P()):
    """Routed + shared expert FFN.  x: (B, S, d) (or (T, d)).

    With ``mesh``: experts are sharded over ``model_axis`` inside shard_map
    (EP).  Router + aux loss run *outside* under GSPMD (data-sharded, tiny);
    dispatch indices enter the shard_map region data-sharded.
    Without mesh: single-shard capacity-bounded dispatch (same code path).
    """
    squeeze = x.ndim == 2
    if squeeze:
        x = x[None]
    b, s, d = x.shape

    # Router (replicated weights, data-sharded activations) + aux loss.
    eids, gates, aux = router_topk(params, x.reshape(-1, d), moe)
    eids = eids.reshape(b, s, moe.top_k)
    gates = gates.reshape(b, s, moe.top_k)

    ep = 1 if mesh is None else mesh.shape[model_axis]
    dp = 1
    if mesh is not None and dp_spec and dp_spec[0] is not None:
        axes = dp_spec[0] if isinstance(dp_spec[0], tuple) else (dp_spec[0],)
        for a in axes:
            dp *= mesh.shape[a]
    t_local = (b // dp) * s
    cap = max(int(t_local * moe.top_k / moe.n_experts * moe.capacity_factor), 8)
    e_pad = params["experts"]["wg"].shape[0]
    e_local = e_pad // ep if e_pad % ep == 0 else -(-moe.n_experts // ep)

    def local_fn(xb, eb, gb, wg, wu, wd):
        # xb: (b_loc, s, d) model-replicated; w*: (E_loc, ...) local shard
        xt = xb.reshape(-1, d)
        rank = jax.lax.axis_index(model_axis) if mesh is not None else 0
        out = _dispatch_local(xt, eb.reshape(-1, moe.top_k),
                              gb.reshape(-1, moe.top_k), wg, wu, wd,
                              e_base=rank * e_local, e_local=e_local, cap=cap)
        if mesh is not None:
            out = jax.lax.psum(out, model_axis)
        return out.reshape(xb.shape).astype(xb.dtype)

    wg, wu, wd = (params["experts"][n] for n in ("wg", "wu", "wd"))
    if mesh is not None:
        pad = e_local * ep - wg.shape[0]
        if pad > 0:
            wg = jnp.pad(wg, ((0, pad), (0, 0), (0, 0)))
            wu = jnp.pad(wu, ((0, pad), (0, 0), (0, 0)))
            wd = jnp.pad(wd, ((0, pad), (0, 0), (0, 0)))
        kspec = P(dp_spec[0] if dp_spec else None, None, None)
        out = _shard_map(
            local_fn, mesh,
            (kspec, kspec, kspec, P(model_axis), P(model_axis), P(model_axis)),
            kspec,
        )(x, eids, gates, wg, wu, wd)
    else:
        out = local_fn(x, eids, gates, wg, wu, wd)

    if moe.n_shared:
        out = out + swiglu(params["shared"], x)
    if squeeze:
        out = out[0]
    return out, aux
