"""Operations and least bytes of a dense decoder, from its shapes alone.

Counted from the configuration file's published keys, never from the
program: a matmul of ``m x k`` by ``k x n`` is ``2 m k n`` operations, the
embedding lookup is none, and attention at a context of ``c`` tokens is
``4 c`` operations per query head and head dimension (scores and the
weighted sum).
"""
from __future__ import annotations


def _dims(cfg: dict):
    d = cfg["hidden_size"]
    hd = cfg.get("head_dim") or d // cfg["num_attention_heads"]
    return (d, hd, cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["vocab_size"],
            cfg["num_hidden_layers"])


def matmul_params(cfg: dict) -> int:
    """Weights that one token multiplies: q, k, v, o, gate, up and down of
    every layer, and the output head over the real vocabulary."""
    d, hd, h, kv, f, v, n = _dims(cfg)
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
    return n * per_layer + d * v


def token_flops(cfg: dict, context: int) -> int:
    """Model operations of one token that attends over ``context`` tokens
    (itself included)."""
    d, hd, h, kv, f, v, n = _dims(cfg)
    return 2 * matmul_params(cfg) + n * 4 * h * hd * context


def prefill_flops(cfg: dict, prompt: int) -> int:
    """A causal prefill of ``prompt`` tokens: token ``i`` attends over
    ``i + 1``."""
    d, hd, h, kv, f, v, n = _dims(cfg)
    return (prompt * 2 * matmul_params(cfg)
            + n * 4 * h * hd * prompt * (prompt + 1) // 2)


def kv_bytes_per_token(cfg: dict, itemsize: int = 2) -> int:
    d, hd, h, kv, f, v, n = _dims(cfg)
    return n * 2 * kv * hd * itemsize


def weight_bytes(cfg: dict, itemsize: int = 2) -> int:
    """Every weight a decode step must read once: the layers (with their two
    norms), the final norm and the output head.  The embedding table is
    read one row per token, counted in ``decode_step_bytes``."""
    d, hd, h, kv, f, v, n = _dims(cfg)
    per_layer = (d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f
                 + 2 * d)
    return itemsize * (n * per_layer + d + d * v)


def decode_step_bytes(cfg: dict, contexts, itemsize: int = 2) -> int:
    """Least bytes of one decode step over a batch whose requests attend
    over ``contexts`` tokens each (the new token included): every weight
    once, one embedding row per request, the K/V of every live token read
    and the new token's K/V written.  Padding, pool copies and gathered
    copies are not counted."""
    d = cfg["hidden_size"]
    kvb = kv_bytes_per_token(cfg, itemsize)
    return (weight_bytes(cfg, itemsize) + len(contexts) * d * itemsize
            + sum(contexts) * kvb)


def decode_step_flops(cfg: dict, contexts) -> int:
    return sum(token_flops(cfg, c) for c in contexts)
