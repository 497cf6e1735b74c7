"""Plain reference of a dense decoder: RMSNorm, RoPE, grouped-query
attention, SwiGLU, in float32 at ``Precision.HIGHEST``.

It stands beside the configuration files that name it (``"reference":
"dense_reference"``) and imports nothing of the program.  Its weights are
the configuration's seeded random weights, made here again from the seed by
the scheme the configuration states: normal with standard deviation 0.02
(the attention output projection 0.02 / sqrt(2 n_layers)), drawn in float32
and rounded to the served precision, norms of unit gain.  The keys follow
one chain: ``k, sub = split(k)`` gives the embedding, then the layer stack
(``split(sub, n_layers)``, one key per layer whose own chain gives q, k, v,
o, gate-and-up and down), then the output head when it is not tied.
Gate and up are interleaved columns of one matrix (even columns gate, odd
columns up); RoPE rotates the two halves of each head.

The model runs layer by layer over a few sequences at once, so the float32
weights of one layer are on the device at a time.  ``precision="fp8"`` is
the control: every matmul's operands rounded to float8 e4m3 (per row for
activations, per output column for weights) before a float32 product.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
DTYPES = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}
NEG = -1e30


class Dims:
    def __init__(self, config: dict):
        c = config
        self.d = c["hidden_size"]
        self.h = c["num_attention_heads"]
        self.kv = c["num_key_value_heads"]
        self.hd = c.get("head_dim") or self.d // self.h
        self.f = c["intermediate_size"]
        self.v = c["vocab_size"]
        self.vp = -(-self.v // 256) * 256
        self.n = c["num_hidden_layers"]
        self.eps = c["rms_norm_eps"]
        self.theta = c["rope_theta"]
        self.tied = bool(c["tie_word_embeddings"])
        self.dtype = DTYPES[c.get("dtype", "bfloat16")]


def _normal(key, shape, scale, dtype):
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _chain(key):
    while True:
        key, sub = jax.random.split(key)
        yield sub


def top_keys(key, n_layers):
    """(embedding key, per-layer keys, output-head key)."""
    ks = _chain(key)
    k_embed, k_stack, k_head = next(ks), next(ks), next(ks)
    return k_embed, jax.random.split(k_stack, n_layers), k_head


def _layer_weights(key, dm: Dims):
    ks = _chain(key)
    d, hd = dm.d, dm.hd
    w = {
        "wq": _normal(next(ks), (d, dm.h * hd), 0.02, dm.dtype),
        "wk": _normal(next(ks), (d, dm.kv * hd), 0.02, dm.dtype),
        "wv": _normal(next(ks), (d, dm.kv * hd), 0.02, dm.dtype),
        "wo": _normal(next(ks), (dm.h * hd, d), 0.02 / math.sqrt(2 * dm.n),
                      dm.dtype),
        "wgu": _normal(next(ks), (d, 2 * dm.f), 0.02, dm.dtype),
        "wd": _normal(next(ks), (dm.f, d), 0.02, dm.dtype),
    }
    return {k: a.astype(jnp.float32) for k, a in w.items()}


def _q8(x, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(spec, a, b, fp8, a_axis=-1, b_axis=0):
    if fp8:
        a, b = _q8(a, a_axis), _q8(b, b_axis)
    return jnp.einsum(spec, a, b, precision=HI)


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos[:, None].astype(jnp.float32) * inv            # (S, hd/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _one_seq_layer(w, x, dm: Dims, fp8: bool):
    """One layer over one sequence ``x`` (S, d), causal from position 0."""
    s = x.shape[0]
    pos = jnp.arange(s)
    h = _rms(x, dm.eps)
    q = _mm("sd,dh->sh", h, w["wq"], fp8).reshape(s, dm.h, dm.hd)
    k = _mm("sd,dh->sh", h, w["wk"], fp8).reshape(s, dm.kv, dm.hd)
    v = _mm("sd,dh->sh", h, w["wv"], fp8).reshape(s, dm.kv, dm.hd)
    q, k = _rope(q, pos, dm.theta), _rope(k, pos, dm.theta)
    g = dm.h // dm.kv
    k = jnp.repeat(k, g, axis=1)                 # query head j reads kv j // g
    v = jnp.repeat(v, g, axis=1)
    sc = _mm("qhd,khd->hqk", q, k, fp8, -1, -1) / math.sqrt(dm.hd)
    sc = jnp.where(pos[None, :, None] >= pos[None, None, :], sc, NEG)
    p = jax.nn.softmax(sc, axis=-1)
    a = _mm("hqk,khd->qhd", p, v, fp8, -1, 0).reshape(s, dm.h * dm.hd)
    x = x + _mm("sh,hd->sd", a, w["wo"], fp8)
    h2 = _rms(x, dm.eps)
    gu = _mm("sd,df->sf", h2, w["wgu"], fp8)
    act = jax.nn.silu(gu[:, 0::2]) * gu[:, 1::2]
    return x + _mm("sf,fd->sd", act, w["wd"], fp8)


@partial(jax.jit, static_argnames=("dm", "fp8"))
def _layer(key, x, dm: Dims, fp8: bool):
    w = _layer_weights(key, dm)
    return jax.lax.map(lambda xi: _one_seq_layer(w, xi, dm, fp8), x)


@partial(jax.jit, static_argnames=("dm",))
def _embed(key, toks, dm: Dims):
    table = _normal(key, (dm.vp, dm.d), 0.02, dm.dtype)
    return table[toks].astype(jnp.float32)


@partial(jax.jit, static_argnames=("dm", "fp8"))
def _logits(k_embed, k_head, x, dm: Dims, fp8: bool):
    if dm.tied:
        head = _normal(k_embed, (dm.vp, dm.d), 0.02, dm.dtype).T
    else:
        head = _normal(k_head, (dm.d, dm.vp), 0.02, dm.dtype)
    head = head.astype(jnp.float32)[:, :dm.v]
    h = _rms(x, dm.eps)
    return jax.lax.map(lambda hi: _mm("sd,dv->sv", hi, head, fp8), h)


def forward(config: dict, key, toks: np.ndarray, fp8: bool = False):
    """Logits ``(n, S, vocab)`` on the device for token rows ``toks``
    ``(n, S)``, each a sequence from position 0 (padding at the end of a row
    does not reach the positions before it)."""
    dm = _dims_cached(config)
    k_embed, k_layers, k_head = top_keys(key, dm.n)
    x = _embed(k_embed, jnp.asarray(toks), dm)
    for i in range(dm.n):
        x = _layer(k_layers[i], x, dm, fp8)
    return _logits(k_embed, k_head, x, dm, fp8)


@partial(jax.jit, static_argnames=())
def _gaps(ref, targets, first_pick):
    """Per position: the reference's best logit less its logit of the token
    that was served (``targets``) and of the token another computation put
    first (``first_pick``)."""
    best = ref.max(axis=-1)
    served = jnp.take_along_axis(ref, targets[..., None], axis=-1)[..., 0]
    other = jnp.take_along_axis(ref, first_pick[..., None], axis=-1)[..., 0]
    return best - served, best - other


def served_gaps(config: dict, key, rows, prompt_lens, seq_len: int,
                control: bool = False):
    """For each row (prompt followed by its served tokens) the gap of every
    served token below the reference's best logit at its position, and with
    ``control`` also the gap of the token that the float8 computation puts
    first there.  Rows are padded to ``seq_len``; returns lists of numpy
    arrays, one per row (the control's list is empty without ``control``)."""
    n = len(rows)
    toks = np.zeros((n, seq_len), np.int32)
    targets = np.zeros((n, seq_len), np.int32)
    for i, r in enumerate(rows):
        r = np.asarray(r, np.int32)
        toks[i, :len(r) - 1] = r[:-1]
        targets[i, :len(r) - 1] = r[1:]
    ref = forward(config, key, toks)
    pick = (jnp.argmax(forward(config, key, toks, fp8=True), axis=-1)
            if control else jnp.asarray(targets))
    g_served, g_ctrl = (np.asarray(a) for a in _gaps(ref, jnp.asarray(targets),
                                                     pick.astype(jnp.int32)))
    out, ctrl = [], []
    for i, r in enumerate(rows):
        lo, hi = prompt_lens[i] - 1, len(r) - 1
        out.append(g_served[i, lo:hi])
        if control:
            ctrl.append(g_ctrl[i, lo:hi])
    return out, ctrl


_DIMS: dict = {}


def _dims_cached(config: dict) -> Dims:
    """One ``Dims`` per configuration, so jit sees the same static value."""
    name = config["name"]
    if name not in _DIMS:
        _DIMS[name] = Dims(config)
    return _DIMS[name]
