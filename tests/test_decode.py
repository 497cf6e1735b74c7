"""Incremental decode (Valet paged caches) must match the full forward pass
position-by-position for every assigned architecture."""
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import ARCHS, reduced
from repro.models import transformer as T
from repro.models import decode as D

CTX = T.ParallelCtx(remat=False, q_block=8, kv_block=8, loss_chunk=8)


@pytest.mark.parametrize("name", sorted(ARCHS))
def test_incremental_decode_matches_forward(name):
    cfg = reduced(ARCHS[name])
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    B, S_prompt, n_dec, page = 2, 12, 6, 4
    S_total = S_prompt + n_dec
    toks = jax.random.randint(key, (B, S_total), 0, cfg.vocab)
    fe = None
    if cfg.n_frontend_tokens:
        fe = jax.random.normal(key, (B, cfg.n_frontend_tokens, cfg.d_model))

    h, _ = T.forward_hidden(params, toks, cfg, CTX, frontend=fe)
    w = T.unembed_matrix(params, cfg)
    ref_logits = jnp.einsum("bsd,dv->bsv", h, w)

    max_pages = (S_total + page - 1) // page + 1
    caches = D.init_caches(cfg, B, pool_slots=B * max_pages + 2, page=page)
    bt = np.arange(B * max_pages, dtype=np.int32).reshape(B, max_pages)
    bt_j = jnp.array(bt)
    logits, caches = D.prefill(params, toks[:, :S_prompt], cfg, CTX, caches,
                               bt_j, frontend=fe)
    np.testing.assert_allclose(
        np.asarray(logits[:, : cfg.vocab]),
        np.asarray(ref_logits[:, S_prompt - 1, : cfg.vocab]), atol=5e-2)

    for t in range(S_prompt, S_total - 1):
        app_slot = jnp.array(bt[:, t // page])
        app_off = jnp.full((B,), t % page, jnp.int32)
        logits, caches = D.decode_step(params, caches, toks[:, t], cfg, CTX,
                                       bt_j, app_slot, app_off)
        np.testing.assert_allclose(
            np.asarray(logits[:, : cfg.vocab]),
            np.asarray(ref_logits[:, t, : cfg.vocab]), atol=5e-2,
            err_msg=f"position {t}")


def test_inactive_slots_do_not_corrupt_state():
    """Masked decode: a hole in the batch neither appends nor advances."""
    cfg = reduced(ARCHS["granite-3-8b"])
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    B, S, page = 2, 8, 4
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 2), 0, cfg.vocab)
    max_pages = 4
    caches = D.init_caches(cfg, B, pool_slots=B * max_pages, page=page)
    bt = jnp.arange(B * max_pages, dtype=jnp.int32).reshape(B, max_pages)
    _, caches = D.prefill(params, toks[:, :S], cfg, CTX, caches, bt)

    # step only batch slot 0; slot 1 is a hole
    active = jnp.array([True, False])
    app_slot = bt[:, S // page]
    app_off = jnp.full((B,), S % page, jnp.int32)
    logits1, caches1 = D.decode_step(params, caches, toks[:, S], cfg, CTX,
                                     bt, app_slot, app_off, active=active)
    assert int(caches1["lengths"][0]) == S + 1
    assert int(caches1["lengths"][1]) == S       # hole did not advance

    # now step slot 1; it must produce the same logits as if no hole ran
    logits_both, caches_both = D.decode_step(
        params, caches, toks[:, S], cfg, CTX, bt, app_slot, app_off)
    active2 = jnp.array([False, True])
    logits2, _ = D.decode_step(params, caches1, toks[:, S], cfg, CTX,
                               bt, app_slot, app_off, active=active2)
    np.testing.assert_allclose(np.asarray(logits2[1]),
                               np.asarray(logits_both[1]), atol=1e-4)


def _prefill_and_decode(params, cfg, toks, fe, n_dec, page=4):
    """Jitted prefill of ``toks[:, :-n_dec]`` then ``n_dec`` decode steps:
    every step's logits and the final caches."""
    b, s_total = toks.shape
    s_prompt = s_total - n_dec
    max_pages = (s_total + page - 1) // page + 1
    caches = D.init_caches(cfg, b, pool_slots=b * max_pages + 2, page=page)
    bt = np.arange(b * max_pages, dtype=np.int32).reshape(b, max_pages)
    pre = jax.jit(lambda p, t, c, f: D.prefill(p, t, cfg, CTX, c,
                                               jnp.asarray(bt), frontend=f))
    step = jax.jit(lambda p, c, t, slot, off: D.decode_step(
        p, c, t, cfg, CTX, jnp.asarray(bt), slot, off))
    logits, caches = pre(params, toks[:, :s_prompt], caches, fe)
    out = [logits]
    for t in range(s_prompt, s_total):
        logits, caches = step(params, caches, toks[:, t],
                              jnp.asarray(bt[:, t // page]),
                              jnp.full((b,), t % page, jnp.int32))
        out.append(logits)
    return out, caches


@pytest.mark.parametrize("name", ["granite-3-8b", "gemma3-4b", "hymba-1.5b",
                                  "whisper-large-v3"])
def test_split_layers_matches_stacked_bit_for_bit(name):
    """Per-layer weight arrays are the stacked slices, so prefill and eight
    decode steps give the same logits and caches to the bit, on dense,
    ring, hybrid SSM and cross-attention layers alike."""
    cfg = reduced(ARCHS[name])
    key = jax.random.PRNGKey(0)
    params = T.init_params(key, cfg)
    split = D.take_layers(jax.tree.map(lambda a: a, params))
    for seg, stacked, s in zip(split["segments"], params["segments"],
                               T.segments(cfg)):
        assert isinstance(seg, list) and len(seg) == s.count
        assert isinstance(stacked, dict)        # the input is left as is
    assert not any(a.is_deleted() for a in jax.tree.leaves(params))
    toks = jax.random.randint(key, (2, 20), 0, cfg.vocab)
    fe = None
    if cfg.n_frontend_tokens:
        fe = jax.random.normal(key, (2, cfg.n_frontend_tokens, cfg.d_model))
    ref_logits, ref_caches = _prefill_and_decode(params, cfg, toks, fe, 8)
    got_logits, got_caches = _prefill_and_decode(split, cfg, toks, fe, 8)
    for r, g in zip(ref_logits, got_logits):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))
    assert jax.tree.structure(got_caches) == jax.tree.structure(ref_caches)
    for r, g in zip(jax.tree.leaves(ref_caches), jax.tree.leaves(got_caches)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(r))


@pytest.mark.parametrize("name", ["granite-3-8b", "gemma3-4b"])
def test_engine_split_tokens_match_stacked(name):
    """The engine serves from the split weights; handed the stacked ones in
    their place it emits the same tokens."""
    from repro.serve import ValetServeEngine
    cfg = reduced(ARCHS[name])
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.random.default_rng(0).integers(2, cfg.vocab, size=9)
    outs = []
    for stacked in (False, True):
        eng = ValetServeEngine(params, cfg, CTX, max_batch=2, max_seq=32,
                               page=4, pool_slots=16)
        if stacked:
            eng.params = params
        eng.submit(prompt, max_new=8)
        (req,) = eng.run(max_steps=50)
        assert isinstance(eng.params["segments"][0], dict) == stacked
        outs.append(req.tokens_out)
    assert len(outs[0]) == 8
    assert outs[0] == outs[1]


@pytest.mark.parametrize("name", ["granite-3-8b", "gemma3-4b"])
def test_take_layers_drops_each_stack_as_it_goes(name, monkeypatch):
    """Each stacked leaf is gone before the next one is split, so the split
    needs room for one more copy of a single leaf, not of the weights."""
    cfg = reduced(ARCHS[name])
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    refs = [weakref.ref(a) for a in jax.tree.leaves(params["segments"])]
    alive = []
    unstack = D._unstack

    def counting(a):
        alive.append(sum(r() is not None for r in refs))
        return unstack(a)

    monkeypatch.setattr(D, "_unstack", counting)
    D.take_layers(params)
    assert alive == list(range(len(refs), 0, -1))
    assert all(r() is None for r in refs)
