"""Serving-engine behaviour: exactness under memory pressure for every
policy, plus the cost separation the paper reports."""
import weakref

import numpy as np
import pytest

import jax

from repro.configs import ARCHS, reduced
from repro.core.policies import POLICIES
from repro.models import decode as D
from repro.models import transformer as T
from repro.serve import ValetServeEngine

CTX = T.ParallelCtx(remat=False, q_block=8, kv_block=8, loss_chunk=8)


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(ARCHS["granite-3-8b"])
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab, size=8) for _ in range(6)]
    ref = run_engine(params, cfg, prompts, "valet", slots=64)
    return cfg, params, prompts, ref


def run_engine(params, cfg, prompts, policy, slots):
    eng = ValetServeEngine(params, cfg, CTX, max_batch=3, max_seq=64,
                           page=4, pool_slots=slots,
                           policy=POLICIES[policy])
    for p in prompts:
        eng.submit(p, max_new=10)
    reqs = eng.run(max_steps=500)
    outs = [r.tokens_out for r in sorted(reqs, key=lambda r: r.rid)]
    return outs, eng.stats, reqs


@pytest.mark.parametrize("policy", ["valet", "valet-mass", "infiniswap",
                                    "os-swap"])
def test_constrained_pool_outputs_exact(setup, policy):
    cfg, params, prompts, (ref_outs, _, _) = setup
    outs, stats, reqs = run_engine(params, cfg, prompts, policy, slots=10)
    assert all(r.status == "done" for r in reqs)
    assert outs == ref_outs, f"{policy} diverged under memory pressure"


def test_cost_separation_matches_paper(setup):
    """Valet < os-swap << infiniswap on simulated critical-path time
    (Figures 19-21 relative ordering)."""
    cfg, params, prompts, _ = setup
    _, s_valet, _ = run_engine(params, cfg, prompts, "valet", slots=10)
    _, s_osswap, _ = run_engine(params, cfg, prompts, "os-swap", slots=10)
    _, s_inf, _ = run_engine(params, cfg, prompts, "infiniswap", slots=10)
    assert s_valet.sim_time_us < s_osswap.sim_time_us < s_inf.sim_time_us
    assert s_valet.recomputes == 0
    assert s_inf.recomputes > 0
    # valet spills are off the critical path (lazy sending)
    assert s_valet.bg_time_us > 0


def test_unconstrained_pool_never_preempts(setup):
    cfg, params, prompts, _ = setup
    _, stats, _ = run_engine(params, cfg, prompts, "valet", slots=64)
    assert stats.pauses == 0
    assert stats.spilled_pages == 0


@pytest.mark.parametrize("mode", ["zero", "legacy"])
def test_preempt_restore_roundtrips_kv_exactly(setup, mode):
    """Preempt then restore must return every KV page to the pool
    bit-identically.  Legacy mode spills to / drains from host blobs;
    zero-restore mode demotes in place (device tier) and comes back as a
    pure block-table repoint — same bytes, zero copies."""
    cfg, params, prompts, _ = setup
    eng = ValetServeEngine(params, cfg, CTX, max_batch=2, max_seq=64,
                           page=4, pool_slots=32, policy=POLICIES["valet"],
                           zero_restore=(mode == "zero"))
    rid = eng.submit(prompts[0], max_new=8)
    req = eng._requests[rid]
    assert eng._admit(req) and req.status == "active"
    assert req.pages

    slots = {pg: eng.gpt.local_slot(pg) for pg in req.pages}
    before = {}
    for li in eng.paged_layers:
        pool = eng.caches["layers"][li]["pool"]
        before[li] = {pg: (np.asarray(pool.k[s]), np.asarray(pool.v[s]))
                      for pg, s in slots.items()}

    eng._preempt(req)
    assert req.status == "paused"
    assert eng.stats.spilled_pages == len(req.pages)
    for pg in req.pages:
        assert eng.gpt.local_slot(pg) is None
        if mode == "zero":
            assert pg in eng.device                # demoted, bytes in place
            assert pg not in eng.host              # no copy made yet
        else:
            assert pg in eng.host                  # spilled, not deleted

    if mode == "zero":
        # the background flush secures host copies without losing device
        # residency (clean pages stay repointable)
        assert eng._flush_demoted(None) == len(req.pages)
        assert eng.stats.bg_time_us > 0
        for pg in req.pages:
            assert pg in eng.device and pg in eng.host

    assert eng._resume(req) and req.status == "active"
    for li in eng.paged_layers:
        pool = eng.caches["layers"][li]["pool"]
        for pg in req.pages:
            s = eng.gpt.local_slot(pg)
            assert s is not None
            np.testing.assert_array_equal(np.asarray(pool.k[s]),
                                          before[li][pg][0])
            np.testing.assert_array_equal(np.asarray(pool.v[s]),
                                          before[li][pg][1])
    for pg in req.pages:
        assert pg not in eng.host                  # blobs drained on restore
    assert eng.stats.restored_pages == eng.stats.spilled_pages
    if mode == "zero":
        # nothing was reallocated in between: every page repoints to its
        # exact old slot, zero streamed
        assert eng.stats.repointed_pages == len(req.pages)
        assert eng.stats.streamed_pages == 0
        for pg, s in slots.items():
            assert eng.gpt.local_slot(pg) == s


def test_engine_hybrid_arch_with_rings():
    """Engine also serves SWA/hybrid archs (ring + paged mixtures)."""
    cfg = reduced(ARCHS["gemma3-4b"])
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab, size=8) for _ in range(4)]
    ref, _, _ = run_engine(params, cfg, prompts, "valet", slots=64)
    out, _, reqs = run_engine(params, cfg, prompts, "valet", slots=8)
    assert all(r.status == "done" for r in reqs)
    assert out == ref


def _weight_bytes(tree) -> int:
    return sum(a.nbytes for a in jax.tree.leaves(tree))


def _new_arrays(before, engines):
    """The live arrays made since ``before``, the engines' caches left
    out."""
    old = {id(a) for a in before}
    old |= {id(a) for e in engines for a in jax.tree.leaves(e.caches)}
    return [a for a in jax.live_arrays() if id(a) not in old]


def test_engine_holds_the_weights_once():
    """The engine splits the weights into per-layer arrays at its first
    program call.  Once the caller has dropped its ``params``, the live
    arrays hold one copy of the weights, none of them stacked."""
    cfg = reduced(ARCHS["granite-3-8b"])
    before = jax.live_arrays()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    n_bytes = _weight_bytes(params)
    stacked_shapes = {a.shape for a in jax.tree.leaves(params["segments"])}
    eng = ValetServeEngine(params, cfg, CTX, max_batch=2, max_seq=32,
                           page=4, pool_slots=16)
    del params
    eng.submit(np.arange(2, 10), max_new=4)
    eng.run(max_steps=50)
    own = jax.tree.leaves(eng.params)
    assert _weight_bytes(own) == n_bytes
    new = _new_arrays(before, [eng])
    weights = [a for a in new if any(a is b for b in own)]
    assert _weight_bytes(weights) == n_bytes
    assert not any(a.shape in stacked_shapes for a in weights)
    others = [a for a in new if not any(a is b for b in own)]
    assert _weight_bytes(others) < n_bytes // 10, [
        a.shape for a in others]


def test_engine_drops_each_stack_as_it_splits(monkeypatch):
    """Handed stacked weights the caller then drops, the engine's split
    frees each stacked leaf before it splits the next: the device holds
    the weights plus one leaf at most, not the weights twice."""
    cfg = reduced(ARCHS["granite-3-8b"])
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    refs = [weakref.ref(a) for a in jax.tree.leaves(params["segments"])]
    eng = ValetServeEngine(params, cfg, CTX, max_batch=2, max_seq=32,
                           page=4, pool_slots=16)
    del params
    alive = []
    unstack = D._unstack

    def counting(a):
        alive.append(sum(r() is not None for r in refs))
        return unstack(a)

    monkeypatch.setattr(D, "_unstack", counting)
    eng.params
    assert alive == list(range(len(refs), 0, -1))
    assert all(r() is None for r in refs)


def test_engine_leaves_the_callers_weights_whole():
    """While the caller keeps its stacked ``params``, the engine's per-layer
    arrays are new ones with the same values, and the caller's are not
    deleted: the device holds the layers twice.  The leaves outside the
    layers (embedding, final norm) are shared."""
    cfg = reduced(ARCHS["granite-3-8b"])
    before = jax.live_arrays()
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    eng = ValetServeEngine(params, cfg, CTX, max_batch=2, max_seq=32,
                           page=4, pool_slots=16)
    split = eng.params
    theirs = jax.tree.leaves(params)
    assert not any(a.is_deleted() for a in theirs)
    assert not any(a is b for a in jax.tree.leaves(split["segments"])
                   for b in theirs)
    assert split["embed"] is params["embed"]
    assert isinstance(params["segments"][0], dict)
    for seg, layers in zip(params["segments"], split["segments"]):
        for i, layer in enumerate(layers):
            for a, b in zip(jax.tree.leaves(seg), jax.tree.leaves(layer)):
                np.testing.assert_array_equal(np.asarray(b),
                                              np.asarray(a[i]))
    live = _weight_bytes(_new_arrays(before, [eng]))
    assert live >= _weight_bytes(params) + _weight_bytes(split["segments"])


def test_engines_share_weights_handed_over_split():
    """Weights the caller splits first (``decode.take_layers``) are served
    as they are: the caller and two engines that have served from them
    hold one copy of the weights between them."""
    cfg = reduced(ARCHS["granite-3-8b"])
    before = jax.live_arrays()
    params = D.take_layers(T.init_params(jax.random.PRNGKey(0), cfg))
    theirs = jax.tree.leaves(params)
    n_bytes = _weight_bytes(theirs)
    engines, outs = [], []
    for _ in range(2):
        eng = ValetServeEngine(params, cfg, CTX, max_batch=2, max_seq=32,
                               page=4, pool_slots=16)
        eng.submit(np.arange(2, 10), max_new=4)
        (req,) = eng.run(max_steps=50)
        outs.append(req.tokens_out)
        engines.append(eng)
        own = jax.tree.leaves(eng.params)
        assert len(own) == len(theirs)
        assert all(a is b for a, b in zip(own, theirs))
    assert outs[0] == outs[1] and len(outs[0]) == 4
    new = _new_arrays(before, engines)
    weights = [a for a in new if any(a is b for b in theirs)]
    assert _weight_bytes(weights) == n_bytes
    assert _weight_bytes(new) - n_bytes < n_bytes // 10, [
        a.shape for a in new if not any(a is b for b in theirs)]
