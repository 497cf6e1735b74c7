"""The serve engine's own spans (``serve/tracing.py``): off by default and
then recording nothing, the same tokens on or off, spans that nest, decode
phases in order inside each step, request ids and work counts where the
work happens, and no wait on the device added by tracing."""
import numpy as np
import pytest

import jax
import jax._src.array as jax_array

from repro.configs import ARCHS, reduced
from repro.core.policies import POLICIES
from repro.models import transformer as T
from repro.serve import ValetServeEngine

CTX = T.ParallelCtx(remat=False, q_block=8, kv_block=8, loss_chunk=8)
DECODE = ("decode.prepare", "decode.dispatch", "decode.readback",
          "decode.emit")


@pytest.fixture(scope="module")
def setup():
    cfg = reduced(ARCHS["granite-3-8b"])
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab, size=n)
               for n in (8, 5, 8, 11, 5, 8)]
    return cfg, params, prompts


def serve(setup, traced, slots=10):
    cfg, params, prompts = setup
    eng = ValetServeEngine(params, cfg, CTX, max_batch=3, max_seq=64, page=4,
                           pool_slots=slots, policy=POLICIES["valet"])
    if traced:
        eng.tracer.start()
    for p in prompts:
        eng.submit(p, max_new=10)
    reqs = eng.run(max_steps=500)
    assert all(r.status == "done" for r in reqs)
    return eng, [r.tokens_out for r in sorted(reqs, key=lambda r: r.rid)]


@pytest.fixture(scope="module")
def traced(setup):
    return serve(setup, True)


def test_off_by_default_records_nothing_and_tokens_match(setup, traced):
    eng, outs = serve(setup, False)
    assert not eng.tracer.on and eng.tracer.spans == []
    on, on_outs = traced
    assert on.tracer.spans
    assert on_outs == outs


def test_spans_nest_and_close(traced):
    spans = traced[0].tracer.spans
    for i, s in enumerate(spans):
        assert s.start_ns <= s.end_ns
        assert -1 <= s.parent < i
        if s.parent >= 0:
            p = spans[s.parent]
            assert p.start_ns <= s.start_ns and s.end_ns <= p.end_ns


def test_decode_phases_in_order_inside_one_step(traced):
    eng = traced[0]
    spans = eng.tracer.spans
    phases = {}
    for s in spans:
        if s.name.startswith("decode."):
            assert spans[s.parent].name == "step"
            phases.setdefault(s.parent, []).append(s)
    complete = [ph for ph in phases.values() if len(ph) == 4]
    assert len(complete) == eng.stats.steps
    for ph in complete:
        assert tuple(s.name for s in ph) == DECODE
        for a, b in zip(ph, ph[1:]):
            assert a.end_ns <= b.start_ns
    rows = [s.n for s in spans if s.name == "decode.dispatch"]
    assert sum(rows) == eng.stats.tokens - len(traced[1])


def test_prefill_carries_rid_and_prompt_length(traced):
    eng = traced[0]
    spans = eng.tracer.spans
    pre = [s for s in spans if s.name == "prefill"]
    assert sorted(s.rid for s in pre) == list(range(len(eng._requests)))
    for s in pre:
        assert s.n == len(eng._requests[s.rid].prompt)
        admit = spans[s.parent]
        assert admit.name == "admit" and admit.rid == s.rid


def test_one_compile_span_per_new_prompt_length(setup):
    eng, _ = serve(setup, True, slots=64)
    comp = [s for s in eng.tracer.spans if s.name == "prefill.compile"]
    lengths = sorted({len(r.prompt) for r in eng._requests.values()})
    assert sorted(s.n for s in comp) == lengths
    for s in comp:
        assert eng.tracer.spans[s.parent].name == "prefill"
    # the same lengths again: no compile
    before = len(eng.tracer.spans)
    rng = np.random.default_rng(1)
    for n in lengths:
        eng.submit(rng.integers(2, eng.cfg.vocab, size=n), max_new=3)
    eng.run(max_steps=100)
    again = eng.tracer.spans[before:]
    assert sum(s.name == "prefill" for s in again) == len(lengths)
    assert not any(s.name == "prefill.compile" for s in again)


def test_page_moves_match_the_engine_counters(traced):
    eng = traced[0]
    st = eng.stats

    def pages(name):
        return sum(s.n for s in eng.tracer.spans if s.name == name)

    assert st.streamed_pages > 0 and st.flushed_pages > 0    # pressure hit
    assert pages("flush") == st.flushed_pages
    assert pages("restore") == st.repointed_pages + st.streamed_pages
    assert pages("stream") == pages("from_host") == st.streamed_pages
    assert pages("to_host") == st.flushed_pages
    preempted = [s.rid for s in eng.tracer.spans if s.name == "preempt"]
    assert len(preempted) == st.pauses


def test_forced_write_back_of_a_dirty_page_is_a_flush(setup):
    """A demoted page whose slot is reused before the background flush
    reached it is written back on the spot: a flush too."""
    cfg, params, prompts = setup
    eng = ValetServeEngine(params, cfg, CTX, max_batch=3, max_seq=64, page=4,
                           pool_slots=16)
    eng.submit(prompts[0], max_new=10)
    eng.step()
    req = eng._requests[0]
    pages = list(req.pages)
    eng._preempt(req)
    slots = [eng.device.slot_of(pg) for pg in pages]
    before = eng.stats.flushed_pages
    eng.tracer.start()
    eng._note_allocated(slots)
    spans = eng.tracer.spans
    assert [(s.name, s.n) for s in spans] == [("flush", len(pages)),
                                              ("to_host", len(pages))]
    assert spans[1].parent == 0
    assert eng.stats.flushed_pages - before == len(pages)


def test_tracing_adds_no_wait_and_no_read(setup, monkeypatch):
    """No ``block_until_ready`` with the tracer on or off, and as many
    reads of a device array's value on the host either way."""
    counts = {"block": 0, "read": 0}
    block = jax.block_until_ready
    array_block = jax_array.ArrayImpl.block_until_ready
    value = jax_array.ArrayImpl._value

    def counted(fn, key):
        def wrapped(*a, **kw):
            counts[key] += 1
            return fn(*a, **kw)
        return wrapped

    monkeypatch.setattr(jax, "block_until_ready", counted(block, "block"))
    monkeypatch.setattr(jax_array.ArrayImpl, "block_until_ready",
                        counted(array_block, "block"))
    monkeypatch.setattr(jax_array.ArrayImpl, "_value",
                        property(counted(value.fget, "read")))
    reads = []
    for on in (False, True):
        counts.update(block=0, read=0)
        serve(setup, on)
        assert counts["block"] == 0, on
        reads.append(counts["read"])
    assert reads[0] == reads[1] > 0


def test_prefill_program_has_a_stable_name(setup):
    """The trace finds the prefill program by this name."""
    cfg, params, _ = setup
    eng = ValetServeEngine(params, cfg, CTX, max_batch=3, max_seq=64, page=4,
                           pool_slots=16)
    toks = np.zeros((1, 5), np.int32)
    bt = np.full((1, eng.max_pages), -1, np.int32)
    lowered = eng._prefill_jit.lower(eng.params, eng.caches, toks, bt)
    assert lowered.as_text().splitlines()[0].startswith(
        "module @jit__prefill_fn")
