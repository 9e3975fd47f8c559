"""Generation subsystem (ISSUE 13): batched sampling lane semantics +
per-request RNG determinism, speculative decode ≡ greedy token parity
through the reused prefill program, prefix-cache COW/refcount
invariants under churn (shared pages prefilled once — allocator
accounting asserted), the priority scheduler policy's aging/no-
starvation rule, jaxpr stability (exactly TWO compiled programs with
every layer enabled), and the ledger/check-8 teeth for the new
serving-block fields."""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from apex_tpu.serving import (
    ContinuousBatchingScheduler,
    PageAllocator,
    PrefixCache,
    Request,
    SamplingParams,
    ServingEngine,
    speculative,
    synthetic_trace,
)
from apex_tpu.serving import prefix_cache as prefix_mod
from apex_tpu.serving import sampling as sampling_mod
from apex_tpu.telemetry import ledger as ledger_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg():
    from apex_tpu.transformer.testing import TransformerConfig

    return TransformerConfig(
        hidden_size=64, num_layers=2, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=64,
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=False)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    from apex_tpu.serving import model as smodel

    return cfg, smodel.init_gpt_params(cfg)


def _engine(cfg, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 48)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_len", 40)
    return ServingEngine(cfg, params=params, **kw)


def _drain(eng, reqs):
    for r in reqs:
        eng.submit(r)
    while any(not r.done() for r in reqs):
        eng.step()
    eng.step()  # final evict round


# ------------------------------------------------------------- sampling


def test_sample_tokens_semantics():
    """Unit semantics of the in-graph op: temp-0 = exact argmax;
    top_k=1 and tiny top_p collapse to argmax; a top-k draw's support
    is the top-k set; same (key, counter) -> same token regardless of
    the surrounding batch; inactive lanes return 0."""
    rs = np.random.RandomState(0)
    logits = jnp.asarray(rs.randn(4, 32).astype(np.float32))
    key = sampling_mod.request_key(7)

    def draw(temps, top_ks, top_ps, keys, counters,
             active=(True,) * 4, logits=logits):
        return np.asarray(sampling_mod.sample_tokens(
            logits, jnp.asarray(temps, jnp.float32),
            jnp.asarray(top_ks, jnp.int32),
            jnp.asarray(top_ps, jnp.float32),
            jnp.asarray(np.stack(keys).astype(np.uint32)),
            jnp.asarray(counters, jnp.int32),
            jnp.asarray(active)))

    greedy = np.argmax(np.asarray(logits), axis=-1)
    zero = [np.zeros(2, np.uint32)] * 4
    # temperature 0 lanes == argmax exactly
    assert (draw([0.0] * 4, [0] * 4, [1.0] * 4, zero, [0] * 4)
            == greedy).all()
    # top_k=1 / top_p ~ 0 collapse to argmax even at high temperature
    assert (draw([5.0] * 4, [1] * 4, [1.0] * 4, [key] * 4, [0] * 4)
            == greedy).all()
    assert (draw([5.0] * 4, [0] * 4, [1e-6] * 4, [key] * 4, [0] * 4)
            == greedy).all()
    # top-k support: many draws at high temp never leave the top-5 set
    top5 = np.argsort(-np.asarray(logits), axis=-1)[:, :5]
    for ctr in range(20):
        toks = draw([3.0] * 4, [5] * 4, [1.0] * 4, [key] * 4,
                    [ctr] * 4)
        for lane in range(4):
            assert toks[lane] in top5[lane], (ctr, lane)
    # lane-position independence: over the same logits a lane's token
    # depends on (key, counter) only — the RNG determinism property at
    # op level (every lane is given row 0: rows that differ draw
    # different tokens under one key, as they should)
    same = jnp.tile(logits[:1], (4, 1))
    a = draw([0.9] * 4, [0] * 4, [1.0] * 4, [key] * 4, [3, 0, 0, 0],
             logits=same)
    b = draw([0.9] * 4, [0] * 4, [1.0] * 4,
             [np.zeros(2, np.uint32), key, key, key], [0, 3, 5, 3],
             logits=same)
    assert a[0] == b[1] == b[3]
    # inactive lanes return 0
    toks = draw([0.0] * 4, [0] * 4, [1.0] * 4, zero, [0] * 4,
                active=(False, True, False, True))
    assert toks[0] == 0 and toks[2] == 0


def test_sampling_knob_asymmetry(monkeypatch):
    with pytest.raises(ValueError):
        sampling_mod.set_sampling("yes")
    with pytest.raises(ValueError):
        sampling_mod.resolve(per_call="on")
    from apex_tpu.dispatch import tiles

    tiles._warned_env.clear()
    monkeypatch.setenv("APEX_SERVE_SAMPLING", "maybe")
    with pytest.warns(UserWarning, match="maybe"):
        assert sampling_mod.resolve() is False
    monkeypatch.setenv("APEX_SERVE_SAMPLING", "1")
    assert sampling_mod.resolve() is True
    monkeypatch.delenv("APEX_SERVE_SAMPLING")
    sampling_mod.set_sampling(True)
    try:
        assert sampling_mod.resolve() is True
        assert sampling_mod.resolve(per_call=False) is False
    finally:
        sampling_mod.set_sampling(None)
    with pytest.raises(ValueError, match="temperature"):
        SamplingParams(temperature=-1.0).validate()
    with pytest.raises(ValueError, match="top_p"):
        SamplingParams(top_p=0.0).validate()


def test_sampling_off_engine_raises_on_stochastic_demand(setup):
    cfg, params = setup
    eng = _engine(cfg, params)
    with pytest.raises(ValueError, match="without sampling"):
        eng.submit(Request(rid=0, prompt=[1, 2], max_new_tokens=2,
                           sampling=SamplingParams(temperature=0.5)))
    # greedy params are honorable on a sampling-off engine
    eng.submit(Request(rid=1, prompt=[1, 2], max_new_tokens=2,
                       sampling=SamplingParams(temperature=0.0)))


def test_sampling_on_all_greedy_reproduces_greedy_engine(setup):
    """The temperature->0 acceptance parity: a sampling-enabled engine
    over default (greedy) requests emits the greedy engine's tokens
    token-for-token, and still compiles exactly one decode program."""
    cfg, params = setup
    trace_kw = dict(seed=5, n_requests=5, vocab=128, prompt_lo=2,
                    prompt_hi=8, new_lo=2, new_hi=8,
                    mean_interarrival=0.5)
    base, _ = synthetic_trace(**trace_kw)
    eng = _engine(cfg, params)
    done = eng.run_trace(base)
    want = {r.rid: r.out_tokens for r in done}
    reqs, _ = synthetic_trace(**trace_kw)
    eng2 = _engine(cfg, params, sampling=True)
    done2 = eng2.run_trace(reqs)
    assert {r.rid: r.out_tokens for r in done2} == want
    assert eng2.decode_cache_size() == 1
    assert eng2.prefill_cache_size() == 1


def test_per_request_rng_determinism_across_batches(setup):
    """THE determinism invariant: same seed + request -> identical
    token stream, whatever the batch composition, slot placement or
    evictions around it."""
    cfg, params = setup
    probe = dict(rid=100, prompt=[3, 5, 7, 9, 11], max_new_tokens=10,
                 sampling=SamplingParams(temperature=0.8, top_k=20,
                                         top_p=0.95, seed=42))

    def run(extra):
        eng = _engine(cfg, params, sampling=True, num_pages=64)
        x = Request(**probe)
        _drain(eng, [x] + extra)
        assert eng.decode_cache_size() == 1
        return x.out_tokens

    solo = run([])
    assert len(solo) == 10
    rs = np.random.RandomState(1)
    noisy = run([
        Request(rid=i, prompt=[int(t) for t in rs.randint(0, 128, 4)],
                max_new_tokens=2 + i,
                sampling=SamplingParams(temperature=1.2, seed=i))
        for i in range(1, 4)])
    assert noisy == solo, "batch composition perturbed a seeded stream"
    # a different seed must (overwhelmingly) give a different stream
    other = dict(probe, sampling=SamplingParams(temperature=0.8,
                                                top_k=20, top_p=0.95,
                                                seed=43))
    eng = _engine(cfg, params, sampling=True)
    y = Request(**other)
    _drain(eng, [y])
    assert y.out_tokens != solo


# ----------------------------------------------------------- speculative


def test_ngram_propose():
    assert speculative.propose([1, 2, 3], 0) == []
    assert speculative.propose([1, 2], 4) == []          # too short
    assert speculative.propose([1, 2, 3, 4, 5], 4) == []  # no repeat
    # period-1 loop: the full-k continuation wins over the short
    # most-recent match
    assert speculative.propose([9, 9, 9, 9, 9, 9], 3) == [9, 9, 9]
    # copies the continuation of the matched bigram
    hist = [1, 2, 3, 4, 1, 2]
    assert speculative.propose(hist, 2) == [3, 4]
    # truncated fallback when no full-k continuation exists
    assert speculative.propose([5, 6, 7, 5, 6], 4) == [7, 5, 6]


def test_accept_arithmetic():
    # all accepted + bonus
    assert speculative.accept([1, 2], [1, 2, 3]) == [1, 2, 3]
    # first rejection: bonus is the greedy correction
    assert speculative.accept([1, 2], [1, 9, 3]) == [1, 9]
    # all rejected: exactly the plain decode round's token
    assert speculative.accept([4], [8, 0]) == [8]
    assert speculative.accept([], [6]) == [6]


def test_resolve_k_asymmetry(monkeypatch):
    for bad in (0, -1, True, "4"):
        with pytest.raises(ValueError):
            speculative.resolve_k(bad)
    monkeypatch.delenv("APEX_SPEC_DECODE", raising=False)
    assert speculative.resolve_k() == 0
    monkeypatch.setenv("APEX_SPEC_DECODE", "0")  # the explicit off-pin
    assert speculative.resolve_k() == 0
    monkeypatch.setenv("APEX_SPEC_DECODE", "4")
    assert speculative.resolve_k() == 4
    assert speculative.resolve_k(2) == 2         # per-call wins
    from apex_tpu.dispatch import tiles

    tiles._warned_env.clear()
    monkeypatch.setenv("APEX_SPEC_DECODE", "many")
    with pytest.warns(UserWarning, match="many"):
        assert speculative.resolve_k() == 0


def test_spec_decode_unhonorable_per_call_raises(setup):
    cfg, params = setup
    with pytest.raises(ValueError, match="cannot be honored"):
        _engine(cfg, params, spec_decode=12, prefill_len=8)
    # env preference at the same depth falls back per shape instead
    os.environ["APEX_SPEC_DECODE"] = "12"
    try:
        eng = _engine(cfg, params, prefill_len=8)
        assert eng.spec_k == 0
    finally:
        del os.environ["APEX_SPEC_DECODE"]


def test_spec_equals_greedy_token_for_token(setup):
    """The acceptance parity: speculative output ≡ non-speculative
    greedy, token for token, over a churning trace — while the verify
    path demonstrably engaged (acceptance recorded) and the prefill
    program stayed ONE compiled program (no third program)."""
    cfg, params = setup
    trace_kw = dict(seed=11, n_requests=6, vocab=128, prompt_lo=4,
                    prompt_hi=10, new_lo=6, new_hi=14,
                    mean_interarrival=0.5)
    base, _ = synthetic_trace(**trace_kw)
    eng = _engine(cfg, params)
    want = {r.rid: r.out_tokens for r in eng.run_trace(base)}
    reqs, _ = synthetic_trace(**trace_kw)
    eng2 = _engine(cfg, params, spec_decode=4)
    done = eng2.run_trace(reqs)
    assert {r.rid: r.out_tokens for r in done} == want, \
        "speculative decode diverged from greedy"
    assert eng2.verify_calls > 0, "no verify batch ever dispatched"
    st = eng2.spec_stats
    assert st.drafted > 0 and 0 <= st.accepted <= st.drafted
    assert eng2.generation_stats()["spec_acceptance_rate"] is not None
    # the no-third-program proof: one prefill + one decode compile
    assert eng2.prefill_cache_size() == 1
    assert eng2.decode_cache_size() == 1
    eng2.allocator.check_invariants()


def test_spec_skips_stochastic_slots(setup):
    """Speculation is a greedy-path optimization: a stochastic slot
    never drafts, and its seeded stream matches the spec-off engine's
    (same lanes, same draws)."""
    cfg, params = setup
    mk = lambda: Request(  # noqa: E731
        rid=0, prompt=[2, 4, 6, 8], max_new_tokens=8,
        sampling=SamplingParams(temperature=0.9, seed=5))
    eng = _engine(cfg, params, sampling=True)
    a = mk()
    _drain(eng, [a])
    eng2 = _engine(cfg, params, sampling=True, spec_decode=4)
    b = mk()
    _drain(eng2, [b])
    assert b.out_tokens == a.out_tokens
    assert eng2.verify_calls == 0  # nothing drafted for the sampler


# ---------------------------------------------------------- prefix cache


def test_prefix_cache_knob_asymmetry(monkeypatch):
    with pytest.raises(ValueError):
        prefix_mod.set_prefix_cache(1)
    with pytest.raises(ValueError):
        prefix_mod.resolve(per_call="on")
    monkeypatch.setenv("APEX_SERVE_PREFIX_CACHE", "1")
    assert prefix_mod.resolve() is True
    monkeypatch.setenv("APEX_SERVE_PREFIX_CACHE", "0")
    assert prefix_mod.resolve() is False
    monkeypatch.delenv("APEX_SERVE_PREFIX_CACHE")
    prefix_mod.set_prefix_cache(True)
    try:
        assert prefix_mod.resolve() is True
        assert prefix_mod.resolve(per_call=False) is False
    finally:
        prefix_mod.set_prefix_cache(None)


def test_allocator_transfer():
    alloc = PageAllocator(8)
    pages = alloc.alloc(("req", 1), 3)
    alloc.transfer(("req", 1), ("prefix", pages[0]), [pages[0]])
    alloc.check_invariants()
    assert alloc.live_pages(("prefix", pages[0])) == [pages[0]]
    assert sorted(alloc.live_pages(("req", 1))) == sorted(pages[1:])
    with pytest.raises(ValueError, match="not owned"):
        alloc.transfer(("req", 1), ("x",), [pages[0]])
    alloc.check_invariants()
    # freeing each owner returns everything
    alloc.free(("req", 1))
    alloc.free(("prefix", pages[0]))
    assert alloc.free_count == 7


def test_prefix_cache_unit_lookup_register_reclaim():
    alloc = PageAllocator(16)
    pc = PrefixCache(alloc, 4)
    prompt = [1, 2, 3, 4, 5, 6, 7, 8, 9, 10]  # 2 full pages + tail 2
    pages = alloc.alloc(("req", 0), 3)
    adopted, copies = pc.register(prompt, pages, ("req", 0))
    assert adopted == pages[:2]
    assert len(copies) == 1 and copies[0][0] == pages[2]
    pc.acquire(adopted)
    pc.check_invariants()
    alloc.check_invariants()
    # a second registration of the same chain adopts nothing
    pages_b = alloc.alloc(("req", 1), 3)
    adopted_b, copies_b = pc.register(prompt, pages_b, ("req", 1))
    assert adopted_b == [] and copies_b == []
    alloc.free(("req", 1))
    # lookup covers 2 full pages + the 2-token tail of a longer prompt
    full, covered, tail = pc.lookup(prompt + [99, 98])
    assert full == pages[:2] and covered == 10 and tail is not None
    # an identical prompt never covers fully: the tail is dropped
    full, covered, tail = pc.lookup(list(prompt))
    assert covered == 8 and tail is None
    # a diverging page-2 misses past page 1
    full, covered, _ = pc.lookup([1, 2, 3, 4, 99, 6, 7, 8, 9])
    assert covered == 4 and full == pages[:1]
    # reclaim refuses referenced pages; releases unlock them
    live_before = len(alloc.live_pages())
    freed = pc.reclaim(8)
    assert freed == 1  # only the unreferenced tail snapshot
    pc.check_invariants()
    pc.release(adopted)
    assert pc.reclaim(8) == 2
    pc.check_invariants()
    alloc.check_invariants()
    assert len(alloc.live_pages()) == live_before - 3


def test_shared_prefix_prefilled_once_two_request_trace(setup):
    """THE acceptance trace: two requests sharing a system prompt —
    the shared pages are prefilled once (prefill dispatch count and
    allocator accounting asserted), the second request's tokens equal
    the cold oracle's, refcounts track the live holders."""
    cfg, params = setup
    rs = np.random.RandomState(3)
    shared = [int(t) for t in rs.randint(0, 128, 20)]  # 2.5 pages @ 8
    eng0 = _engine(cfg, params)
    o = Request(rid=0, prompt=list(shared), max_new_tokens=6)
    _drain(eng0, [o])

    eng = _engine(cfg, params, prefix_cache=True)
    a = Request(rid=0, prompt=list(shared), max_new_tokens=6)
    eng.submit(a)
    eng.step()
    # registrant live: its 2 full prompt pages are cache-owned with
    # refcount 1 (held by the registrant's own table)
    full_pages = [n["page"] for n in eng.prefix.nodes.values()]
    assert len(full_pages) == 2
    assert all(eng.prefix.refs[p] == 1 for p in full_pages)
    while not a.done():
        eng.step()
    eng.step()  # evict -> refs drop to 0, pages stay cached
    assert all(eng.prefix.refs[p] == 0 for p in full_pages)
    batches_before = eng.prefill_batches
    assert batches_before == 1

    b = Request(rid=1, prompt=list(shared), max_new_tokens=6)
    eng.submit(b)
    eng.step()
    # the hit re-references the SAME pages — shared prompt prefilled
    # once per engine, not once per request
    assert all(eng.prefix.refs[p] == 1 for p in full_pages)
    slot = next(s for s in eng.scheduler.slots if s is not None)
    assert slot.shared_pages == full_pages
    assert slot.prefix_hit > 0
    while not b.done():
        eng.step()
    eng.step()
    assert eng.prefill_batches == batches_before, \
        "the second request re-prefilled the shared prompt"
    assert b.out_tokens == o.out_tokens, \
        "cache-hit continuation diverged from the cold oracle"
    eng.prefix.check_invariants()
    eng.allocator.check_invariants()
    assert eng.generation_stats()["prefix_hit_rate"] > 0
    assert eng.decode_cache_size() == 1
    assert eng.prefill_cache_size() == 1


def test_prefix_cow_refcount_invariants_under_churn(setup):
    """Admit/evict/shared-prefix churn: many requests over a few
    shared system prompts through a small page pool (reclaim under
    pressure engaged) — allocator + prefix invariants hold at every
    round, every request completes, and every hit's tokens equal its
    prompt-twin's."""
    cfg, params = setup
    rs = np.random.RandomState(7)
    prefixes = [[int(t) for t in rs.randint(0, 128, n)]
                for n in (12, 20)]
    reqs = []
    for i in range(10):
        pre = prefixes[i % 2]
        suffix = [int(t) for t in rs.randint(0, 128, 1 + i % 4)]
        reqs.append(Request(rid=i, prompt=pre + suffix,
                            max_new_tokens=3 + i % 5,
                            arrival=float(i)))
    eng = _engine(cfg, params, prefix_cache=True, num_pages=32)
    pending = list(reqs)
    guard = 0
    while len(eng.scheduler.completed) < len(reqs):
        assert guard < 300
        due = [r for r in pending if r.arrival <= eng.tick]
        pending = [r for r in pending if r.arrival > eng.tick]
        eng.step(arrivals=due)
        eng.allocator.check_invariants()
        eng.prefix.check_invariants()
        guard += 1
    eng.step()
    eng.prefix.check_invariants()
    # all refs drained after the final evict
    assert all(n == 0 for n in eng.prefix.refs.values())
    # prompt-twins (same full prompt) must agree token-for-token
    by_prompt = {}
    for r in reqs:
        by_prompt.setdefault(tuple(r.prompt), []).append(r)
    for twins in by_prompt.values():
        n = min(r.max_new_tokens for r in twins)
        streams = {tuple(r.out_tokens[:n]) for r in twins}
        assert len(streams) == 1, "prompt twins diverged"
    assert eng.generation_stats()["prefix_hit_rate"] > 0


def test_admission_reclaim_never_frees_matched_cover():
    """Regression (review finding): under page pressure, the reclaim
    that admission triggers must NEVER free the very pages its own
    request just matched — the matched cover is fenced, so the
    admission either shares intact pages or blocks honestly."""
    alloc = PageAllocator(8)                     # 7 allocatable
    pc = PrefixCache(alloc, 4)
    sch = ContinuousBatchingScheduler(2, 8, 4, alloc, prefix=pc)
    hog = Request(rid=9, prompt=[7] * 8, max_new_tokens=8)  # 4 pages
    sch.submit(hog)
    assert sch.admit(0) == [0]
    # register a 1-full-page + 2-token-tail prefix, registrant gone
    pre = [1, 2, 3, 4, 5, 6]
    pages = alloc.alloc(("req", 0), 2)
    pc.register(pre, pages, ("req", 0))
    alloc.free(("req", 0))
    pc.check_invariants()
    alloc.check_invariants()
    assert alloc.free_count == 1
    chain_page = next(iter(pc.nodes.values()))["page"]
    snap_page = next(iter(pc.tails.values()))["page"]
    # same-prefix request needing 2 private pages over 1 free: the
    # reclaim path engages but must refuse the matched cover -> the
    # request BLOCKS instead of aliasing freed pages into itself
    b = Request(rid=1, prompt=pre + [9], max_new_tokens=4)
    sch.submit(b)
    assert sch.admit(1) == []
    pc.check_invariants()
    alloc.check_invariants()
    assert chain_page in [n["page"] for n in pc.nodes.values()]
    assert snap_page in [t["page"] for t in pc.tails.values()]
    # pressure released -> the admission shares the INTACT cover
    hog.out_tokens.extend([0] * 8)
    sch.evict_done(2)
    admitted = sch.admit(2)
    assert len(admitted) == 1
    slot = sch.slots[admitted[0]]
    assert len(set(slot.pages)) == len(slot.pages), "page aliased"
    assert slot.shared_pages == [chain_page]
    assert pc.refs[chain_page] == 1
    assert slot.cow_copies == [(snap_page, slot.pages[1])]
    pc.check_invariants()
    alloc.check_invariants()


# ------------------------------------------------------- priority policy


def test_priority_policy_orders_and_never_starves():
    """Same-arrival requests admit in priority order; a low-priority
    early request is never starved by a stream of high-priority
    arrivals (the aging rule) — and everything completes."""
    alloc = PageAllocator(16)
    sch = ContinuousBatchingScheduler(1, 8, 8, alloc,
                                      policy="priority")
    reqs = [Request(rid=i, prompt=[1] * 4, max_new_tokens=2,
                    priority=i, arrival=0) for i in range(4)]
    for r in reqs:
        sch.submit(r)
    order = []
    tick = 0
    while len(sch.completed) < len(reqs):
        assert tick < 100
        sch.evict_done(tick)
        for i in sch.admit(tick):
            order.append(sch.slots[i].request.rid)
        for i in sch.active_indices():
            slot = sch.slots[i]
            slot.pos += 1
            slot.request.out_tokens.append(0)
        tick += 1
    assert order == [3, 2, 1, 0], "priority order not honored"

    # aging: an old priority-0 request eventually beats priority-1
    # arrivals (AGING_TICKS=8 -> it outranks them after 8 ticks wait)
    alloc = PageAllocator(16)
    sch = ContinuousBatchingScheduler(1, 8, 8, alloc,
                                      policy="priority")
    old = Request(rid=100, prompt=[1] * 4, max_new_tokens=2,
                  priority=0, arrival=0)
    sch.submit(old)
    tick = 0
    admitted_old_at = None
    while admitted_old_at is None:
        assert tick < 60, "aging never admitted the old request"
        sch.evict_done(tick)
        # a fresh priority-1 competitor arrives every round
        sch.submit(Request(rid=tick, prompt=[1] * 4, max_new_tokens=2,
                           priority=1, arrival=tick))
        for i in sch.admit(tick):
            if sch.slots[i].request.rid == 100:
                admitted_old_at = tick
        for i in sch.active_indices():
            slot = sch.slots[i]
            slot.pos += 1
            slot.request.out_tokens.append(0)
        tick += 1
    assert admitted_old_at is not None
    alloc.check_invariants()


def test_priority_ages_waiting_time_not_absolute_tick():
    """Regression (review finding): the aging base is the tick the
    request ENTERED the queue, not its `arrival` field — a request
    submitted directly at a late engine tick (arrival left at its 0.0
    default) must get NO spurious boost over a waiting higher-priority
    request."""
    alloc = PageAllocator(32)
    sch = ContinuousBatchingScheduler(1, 8, 8, alloc,
                                      policy="priority")
    urgent = Request(rid=1, prompt=[1] * 4, max_new_tokens=2,
                     priority=5, arrival=78.0)
    sch.submit(urgent, tick=78)
    # a fresh zero-priority direct submission at tick 80: without the
    # queued_tick stamp its aging term would be 80/8 = 10 > 5
    late = Request(rid=2, prompt=[1] * 4, max_new_tokens=2, priority=0)
    sch.submit(late, tick=80)
    admitted = sch.admit(80)
    assert [sch.slots[i].request.rid for i in admitted] == [1], \
        "a newcomer's absolute tick outboosted a waiting priority-5"


# ------------------------------------------------- two-program stability


def test_two_compiled_programs_with_everything_enabled(setup):
    """The headline jaxpr-stability acceptance: sampling + speculative
    decode + prefix cache + priority policy all ON over a churning
    mixed trace — the engine still compiles EXACTLY two programs (one
    packed prefill serving admissions AND verifies, one decode), and
    every invariant surface stays clean."""
    cfg, params = setup
    rs = np.random.RandomState(9)
    shared = [int(t) for t in rs.randint(0, 128, 12)]
    reqs = []
    for i in range(8):
        suffix = [int(t) for t in rs.randint(0, 128, 1 + i % 3)]
        reqs.append(Request(
            rid=i, prompt=shared + suffix, max_new_tokens=3 + i % 6,
            arrival=float(i) * 0.7, priority=i % 3,
            sampling=SamplingParams(temperature=0.8, top_k=16, seed=i)
            if i % 2 else None))
    eng = _engine(cfg, params, num_slots=3, num_pages=64,
                  sampling=True, spec_decode=3, prefix_cache=True,
                  policy="priority")
    done = eng.run_trace(reqs)
    eng.step()
    assert len(done) == len(reqs)
    assert eng.decode_cache_size() == 1, \
        "decode recompiled with the generation layers on"
    assert eng.prefill_cache_size() == 1, \
        "prefill recompiled — the verify batch took a third program"
    assert eng.verify_calls > 0 and eng.prefill_batches > 0
    eng.allocator.check_invariants()
    eng.prefix.check_invariants()
    assert eng.generation_stats()["prefix_hit_rate"] > 0


# ------------------------------------------------------- ledger / checks


def _serving_block(**kw):
    blk = {"tokens_per_s": 10.0, "p50_ms": 1.0, "p99_ms": 2.0,
           "trace_id": "tr-0123456789", "kv_pages": 8,
           "spec_acceptance_rate": None, "draft_len": None,
           "prefix_hit_rate": None}
    blk.update(kw)
    return blk


def test_serving_block_generation_field_teeth():
    rec = ledger_mod.make_record(
        "profile_serving", "cpu", 0.1, 2,
        extra={"serving": _serving_block(spec_acceptance_rate=0.9,
                                         draft_len=2.5,
                                         prefix_hit_rate=0.4)})
    assert ledger_mod.validate_record(rec) == []
    for mut, needle in (
            ({"spec_acceptance_rate": 1.5}, "spec_acceptance_rate"),
            ({"spec_acceptance_rate": True}, "spec_acceptance_rate"),
            ({"prefix_hit_rate": -0.1}, "prefix_hit_rate"),
            ({"draft_len": -1}, "draft_len")):
        r = ledger_mod.make_record(
            "profile_serving", "cpu", 0.1, 2,
            extra={"serving": _serving_block(**mut)})
        assert any(needle in p for p in ledger_mod.validate_record(r)), \
            (mut, ledger_mod.validate_record(r))


BASE_PINS = {"APEX_SERVE_WEIGHT_QUANT": "0",
             # ISSUE 17: serving rows must also pin the decode block
             # size (check 8 — an unpinned K cannot be audited)
             "APEX_SERVE_DECODE_K": "1",
             # ISSUE 20: and the KV-tier knobs (int8 cache + swap
             # restore are different cache tiers)
             "APEX_SERVE_KV_QUANT": "0",
             "APEX_SERVE_KV_SWAP": "0"}


def _check8(tmp_path, knobs, block):
    rec = ledger_mod.make_record("profile_serving", "cpu", 0.1, 2,
                                 knobs=knobs,
                                 extra={"serving": block})
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text(json.dumps(rec) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(f"generation row cites ledger:{rec['id']}\n")
    table = tmp_path / "table.jsonl"
    table.write_text("")
    from tests.conftest import run_check_bench_labels

    return run_check_bench_labels(
        "--perf", str(perf), "--ledger", str(ledger),
        "--table", str(table))


def test_check8_speculative_row_must_pin_spec_decode(tmp_path):
    out = _check8(tmp_path, dict(BASE_PINS),
                  _serving_block(spec_acceptance_rate=0.9,
                                 draft_len=2.0))
    assert out.returncode == 1
    assert "APEX_SPEC_DECODE" in out.stdout
    # pinned OFF while the block claims a rate is drift too
    out = _check8(tmp_path, dict(BASE_PINS, APEX_SPEC_DECODE="0"),
                  _serving_block(spec_acceptance_rate=0.9,
                                 draft_len=2.0))
    assert out.returncode == 1
    assert "different programs" in out.stdout
    out = _check8(tmp_path, dict(BASE_PINS, APEX_SPEC_DECODE="4"),
                  _serving_block(spec_acceptance_rate=0.9,
                                 draft_len=2.0))
    assert out.returncode == 0, out.stdout


def test_check8_prefix_row_must_pin_prefix_cache(tmp_path):
    out = _check8(tmp_path, dict(BASE_PINS),
                  _serving_block(prefix_hit_rate=0.5))
    assert out.returncode == 1
    assert "APEX_SERVE_PREFIX_CACHE" in out.stdout
    out = _check8(tmp_path,
                  dict(BASE_PINS, APEX_SERVE_PREFIX_CACHE="1"),
                  _serving_block(prefix_hit_rate=0.5))
    assert out.returncode == 0, out.stdout
    # None-when-disabled needs no generation pins (legacy-compatible)
    out = _check8(tmp_path, dict(BASE_PINS), _serving_block())
    assert out.returncode == 0, out.stdout


def test_gauges_carry_generation_counters(setup):
    from apex_tpu.serving import lifecycle
    from apex_tpu.telemetry import metrics

    cfg, params = setup
    lifecycle.enable()
    try:
        eng = _engine(cfg, params, spec_decode=3)
    finally:
        lifecycle.reset_enabled()
    r = Request(rid=0, prompt=[2, 4, 6, 8], max_new_tokens=10)
    _drain(eng, [r])
    assert eng.events.gauges
    last = eng.events.gauges[-1]
    assert last["serve_spec_drafted"] >= last["serve_spec_accepted"] \
        >= 0
    assert last["serve_spec_drafted"] == eng.spec_stats.drafted
    assert last["serve_prefix_hit_tokens"] == 0
    # the names are registered metric specs (strict-writer contract)
    for name in ("serve_spec_drafted", "serve_spec_accepted",
                 "serve_prefix_hit_tokens"):
        assert metrics.spec(name) is not None
