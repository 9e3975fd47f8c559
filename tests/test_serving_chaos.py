"""Serving resilience chaos suite (ISSUE 15): every recovery path
driven through the REAL ServingEngine on CPU with deterministic fault
plans (``apex_tpu.resilience.faults`` serve_* sites), the same honesty
rules as the collection chaos suite — and the acceptance invariants:

* submit-reject is STRUCTURED (a ``Rejected`` return, never an
  exception escaping the loop) under a scripted burst overload;
* KV-exhaustion preempts and replays token-for-token (natural page
  pressure AND a scripted ``serve_alloc`` deny) with clean
  allocator/prefix invariants across the churn;
* a hung decode dispatch is timed out + classified (``wedged``), a
  crashing one classified ``degraded_relay``, and the engine finishes
  the remaining requests either way — bounded by the round-attempt
  budget (a persistently dead device still fails loudly);
* disabled mode (all four knobs off) is token-for-token identical to
  the all-knobs-on engine under no pressure;
* the one-compile contract (``decode_cache_size()==1``,
  ``prefill_cache_size()<=1``) holds under every enabled combination.
"""

import json

import pytest

from apex_tpu.resilience import faults
from apex_tpu.serving import (
    Rejected,
    Request,
    ServingEngine,
    lifecycle,
)
from apex_tpu.serving import resilience as serve_res


def _cfg():
    from apex_tpu.transformer.testing import TransformerConfig

    return TransformerConfig(
        hidden_size=32, num_layers=1, num_attention_heads=2,
        vocab_size=64, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=False)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    from apex_tpu.serving import model as smodel

    params = smodel.init_gpt_params(cfg)
    # the uncontended reference streams every parity test pins against
    ref = ServingEngine(cfg, params=params, num_slots=2, page_size=4,
                        num_pages=32, max_seq=32, prefill_len=16)
    reqs = _requests()
    _drive(ref, reqs)
    return cfg, params, {r.rid: list(r.out_tokens) for r in reqs}


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    """Plan isolation: no fault plan leaks in, and the per-plan
    ``times`` spend counters reset between tests (two tests sharing a
    plan string must each get the full budget)."""
    monkeypatch.delenv("APEX_FAULT_PLAN", raising=False)
    faults._cache["fired"] = {}
    yield
    faults._cache["fired"] = {}


def _requests():
    return [Request(rid=0, prompt=[1, 2, 3, 4, 5, 6],
                    max_new_tokens=10),
            Request(rid=1, prompt=[7, 8, 9, 10, 11, 12],
                    max_new_tokens=10)]


def _drive(eng, reqs, guard=300):
    for r in reqs:
        eng.submit(r)
    n = 0
    while not all(r.done() for r in reqs):
        eng.step()
        n += 1
        assert n < guard, ("engine did not drain",
                           [r.out_tokens for r in reqs])
    eng.step()


def _engine(cfg, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_len", 16)
    return ServingEngine(cfg, params=params, **kw)


def _assert_contract(eng):
    assert eng.decode_cache_size() == 1, eng.decode_cache_size()
    assert eng.prefill_cache_size() <= 1, eng.prefill_cache_size()
    eng.allocator.check_invariants()
    if eng.prefix is not None:
        eng.prefix.check_invariants()


def _plan(monkeypatch, plan):
    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps(plan))


# ---------------------------------------------------- disabled parity


def test_all_knobs_on_token_identical_without_pressure(setup):
    """The disabled-mode acceptance, stated as its strong converse:
    an engine with EVERY resilience layer armed but nothing
    triggering it (roomy pool, bounded-but-unfull queue, healthy
    dispatches) produces token-for-token the plain engine's streams —
    so the layers are pure additions, not behavior drift."""
    cfg, params, ref = setup
    lifecycle.enable()
    try:
        eng = _engine(cfg, params, admit=16, shed=True, preempt=True,
                      recover=True, dispatch_timeout_s=60,
                      round_retry_wait_s=0)
    finally:
        lifecycle.reset_enabled()
    reqs = _requests()
    _drive(eng, reqs)
    for r in reqs:
        assert r.out_tokens == ref[r.rid], (r.rid, r.out_tokens)
    stats = eng.resilience
    assert (stats.rejected, stats.shed, stats.preempted,
            stats.degraded_rounds) == (0, 0, 0, 0), stats
    assert eng.events.validate_order() == []
    _assert_contract(eng)
    # enabled-but-idle rates are 0.0 / None-never: the slo surface
    assert eng.resilience_rates() == {"shed_rate": 0.0,
                                      "preempt_rate": 0.0,
                                      "degraded_rounds": 0}


# ------------------------------------------- admission control / shed


def test_burst_overload_rejects_structurally(setup, monkeypatch):
    """A scripted submit storm (serve_burst site) against a bounded
    queue: the engine REJECTS the overflow with structured Rejected
    events — no exception ever escapes step(), and the original trace
    still drains to completion with parity."""
    cfg, params, ref = setup
    _plan(monkeypatch, [{"site": "serve_burst", "kind": "burst",
                         "count": 12, "prompt_len": 3, "max_new": 4,
                         "match_ctx": {"tick": 1}}])
    lifecycle.enable()
    try:
        eng = _engine(cfg, params, admit=3)
    finally:
        lifecycle.reset_enabled()
    reqs = _requests()
    _drive(eng, reqs)
    assert eng.resilience.rejected > 0
    for req, rej in eng.rejected:
        assert isinstance(rej, Rejected)
        assert rej.reason == "queue_full"
        assert rej.retry_after_ticks >= 1
        chain = [e["event"] for e in eng.events.request_events(req.rid)]
        assert chain == ["submitted", "rejected"], chain
    for r in reqs:
        assert r.out_tokens == ref[r.rid]
    assert eng.events.validate_order() == []
    _assert_contract(eng)


def test_direct_submit_reject_and_off_mode(setup):
    cfg, params, _ = setup
    eng = _engine(cfg, params, num_slots=1, admit=2)
    rs = [Request(rid=i, prompt=[1, 2, 3], max_new_tokens=4)
          for i in range(5)]
    results = [eng.submit(r) for r in rs]
    assert [isinstance(x, Rejected) for x in results] \
        == [False, False, True, True, True]
    # admission control must never mask a malformed request
    with pytest.raises(ValueError, match="max_new_tokens"):
        eng.submit(Request(rid=9, prompt=[1], max_new_tokens=0))
    # off mode: the unbounded queue serving always had
    off = _engine(cfg, params, num_slots=1)
    assert all(off.submit(Request(rid=i, prompt=[1, 2],
                                  max_new_tokens=2)) is None
               for i in range(10, 20))


def test_shed_drops_only_hopeless_requests(setup):
    """The deadline shedder: a queued request whose wait already
    exceeds the TTFT threshold is dropped (attainment impossible) —
    with a `shed` event, while requests that got their first token
    are never shed. run_trace counts shed requests as settled."""
    cfg, params, _ = setup
    lifecycle.enable()
    try:
        # 1 slot, long generations: rid 1/2 wait behind rid 0 past
        # the (tiny) threshold and must shed
        eng = _engine(cfg, params, num_slots=1, shed=True,
                      shed_ttft_ms=1.0)
    finally:
        lifecycle.reset_enabled()
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=12,
                    arrival=0) for i in range(3)]
    done = eng.run_trace(reqs)
    assert eng.resilience.shed > 0
    assert len(done) + len(eng.scheduler.shed) == 3
    for r in eng.scheduler.shed:
        assert not r.out_tokens  # only first-token-less requests shed
        chain = [e["event"] for e in eng.events.request_events(r.rid)]
        assert chain[-1] == "shed", chain
        assert r.shed_tick is not None
    assert eng.events.validate_order() == []
    assert eng.resilience_rates()["shed_rate"] > 0
    _assert_contract(eng)


# -------------------------------------------- KV-pressure preemption


def test_page_pressure_preempts_and_replays(setup):
    """Natural KV exhaustion: a pool too small for both streams'
    peaks forces a mid-stream refusal — the youngest slot is
    preempted (pages freed, stream requeued), replays through the
    SAME prefill program, and both streams land token-for-token on
    the uncontended reference. Allocator invariants hold across the
    churn and the preempted request's event chain walks the
    suspension cycle."""
    cfg, params, ref = setup
    lifecycle.enable()
    try:
        # 5 allocatable pages; each stream needs 4 at peak (16
        # positions / 4-token pages)
        eng = _engine(cfg, params, num_pages=6, max_seq=16,
                      preempt=True)
    finally:
        lifecycle.reset_enabled()
    reqs = _requests()
    _drive(eng, reqs)
    assert eng.resilience.preempted >= 1, eng.resilience
    for r in reqs:
        assert r.out_tokens == ref[r.rid], (r.rid, r.out_tokens)
    assert eng.events.validate_order() == []
    victim = next(r for r in reqs if r.preemptions)
    chain = [e["event"] for e in eng.events.request_events(victim.rid)]
    i = chain.index("preempted")
    assert chain[i + 1] == "resubmitted" \
        and "admitted" in chain[i + 2:], chain
    assert eng.resilience_rates()["preempt_rate"] > 0
    _assert_contract(eng)


def test_scripted_alloc_deny_preempts(setup, monkeypatch):
    """The serve_alloc chaos site: ONE scripted mid-stream refusal
    (times=1) in a roomy pool still walks the full preempt -> requeue
    -> replay chain — deterministic page pressure without shrinking
    the pool — and parity holds."""
    cfg, params, ref = setup
    _plan(monkeypatch, [{"site": "serve_alloc", "kind": "deny",
                         "times": 1,
                         "match_ctx": {"phase": "grow"}}])
    eng = _engine(cfg, params, preempt=True)
    reqs = _requests()
    _drive(eng, reqs)
    assert eng.resilience.preempted == 1, eng.resilience
    for r in reqs:
        assert r.out_tokens == ref[r.rid], (r.rid, r.out_tokens)
    _assert_contract(eng)


def test_preemption_composes_with_prefix_cache(setup):
    """Preemption must respect prefix-cache refcounts: shared pages
    decref at preemption (never freed under live refs) and the
    resumed stream replays without touching the cache chains."""
    cfg, params, _ = setup
    base = [5, 9, 13, 2]  # shared system-prompt-style prefix
    ref_eng = _engine(cfg, params, num_pages=32, max_seq=16,
                      prefix_cache=True)
    ref_reqs = [Request(rid=i, prompt=base + [20 + i, 30 + i],
                        max_new_tokens=10) for i in range(2)]
    _drive(ref_eng, ref_reqs)
    eng = _engine(cfg, params, num_pages=8, max_seq=16,
                  preempt=True, prefix_cache=True)
    reqs = [Request(rid=i, prompt=base + [20 + i, 30 + i],
                    max_new_tokens=10) for i in range(2)]
    _drive(eng, reqs)
    for r, rr in zip(reqs, ref_reqs):
        assert r.out_tokens == rr.out_tokens, (r.rid, r.out_tokens)
    _assert_contract(eng)


# ------------------------------------- dispatch watchdog / recovery


def _warmed_recover_engine(cfg, params, monkeypatch, plan, **kw):
    """Engine with the watchdog armed and its programs COMPILED
    before the tight timeout arms (compile time must not read as a
    wedge) — the plan is installed only after the warmup rounds."""
    lifecycle.enable()
    try:
        eng = _engine(cfg, params, recover=True,
                      dispatch_timeout_s=60, round_retry_wait_s=0,
                      **kw)
    finally:
        lifecycle.reset_enabled()
    reqs = _requests()
    for r in reqs:
        eng.submit(r)
    eng.step()          # prefill + decode compile (tick 0)
    eng.step()          # a steady-state round (tick 1)
    _plan(monkeypatch, plan)
    eng.dispatch_timeout_s = 0.25
    return eng, reqs


def test_decode_hang_timed_out_classified_and_recovered(
        setup, monkeypatch):
    """A decode dispatch that hangs (the relay wedge) is timed out by
    the watchdog, classified `wedged`, every in-flight request is
    requeued with a degraded_round event, and the engine finishes all
    requests token-for-token."""
    cfg, params, ref = setup
    eng, reqs = _warmed_recover_engine(
        cfg, params, monkeypatch,
        [{"site": "serve_decode", "kind": "hang", "seconds": 1.0,
          "match_ctx": {"tick": 2}}])
    degraded = []
    n = 0
    while not all(r.done() for r in reqs):
        out = eng.step()
        if out.get("degraded"):
            degraded.append(out["degraded"])
        n += 1
        assert n < 100
    eng.step()
    assert len(degraded) == 1
    assert degraded[0]["verdict"] == "wedged"
    assert degraded[0]["phase"] == "decode"
    assert eng.resilience.degraded_rounds == 1
    assert eng.resilience.last_verdict == "wedged"
    for r in reqs:
        assert r.out_tokens == ref[r.rid], (r.rid, r.out_tokens)
    assert eng.events.validate_order() == []
    rid = degraded[0]["requeued"][0]
    chain = [e["event"] for e in eng.events.request_events(rid)]
    i = chain.index("degraded_round")
    assert chain[i + 1] == "resubmitted", chain
    assert eng.resilience_rates()["degraded_rounds"] == 1
    _assert_contract(eng)


def test_decode_exception_classified_degraded_relay(setup, monkeypatch):
    cfg, params, ref = setup
    eng, reqs = _warmed_recover_engine(
        cfg, params, monkeypatch,
        [{"site": "serve_decode", "kind": "raise",
          "message": "relay reset by peer",
          "match_ctx": {"tick": 2}}])
    n = 0
    while not all(r.done() for r in reqs):
        eng.step()
        n += 1
        assert n < 100
    eng.step()
    assert eng.resilience.degraded_rounds == 1
    assert eng.resilience.last_verdict == "degraded_relay"
    for r in reqs:
        assert r.out_tokens == ref[r.rid]
    _assert_contract(eng)


def test_prefill_failure_mid_admission_recovered(setup, monkeypatch):
    """A prefill dispatch crash mid-admission: the admitted-but-
    unfilled requests are requeued (degraded round), re-admitted and
    prefilled on the retry — parity preserved."""
    cfg, params, ref = setup
    lifecycle.enable()
    try:
        eng = _engine(cfg, params, recover=True,
                      dispatch_timeout_s=60, round_retry_wait_s=0)
    finally:
        lifecycle.reset_enabled()
    _plan(monkeypatch, [{"site": "serve_prefill", "kind": "raise",
                         "message": "compile helper 500",
                         "match_ctx": {"tick": 0}}])
    reqs = _requests()
    _drive(eng, reqs)
    assert eng.resilience.degraded_rounds == 1
    assert eng.resilience.last_verdict == "degraded_relay"
    for r in reqs:
        assert r.out_tokens == ref[r.rid]
    assert eng.events.validate_order() == []
    _assert_contract(eng)


def test_round_attempt_budget_exhausts_loudly(setup, monkeypatch):
    """Bounded recovery: a PERSISTENTLY failing dispatch (every round)
    exhausts SERVE_ROUND_ATTEMPTS and raises — a dead device must
    never spin the engine forever."""
    cfg, params, _ = setup
    eng = _engine(cfg, params, recover=True, dispatch_timeout_s=60,
                  round_attempts=2, round_retry_wait_s=0)
    _plan(monkeypatch, [{"site": "serve_prefill", "kind": "raise",
                         "message": "device is gone"}])
    eng.submit(Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4))
    with pytest.raises(RuntimeError, match="budget is exhausted"):
        for _ in range(10):
            eng.step()
    assert eng.resilience.degraded_rounds == 2


def test_without_watchdog_the_engine_dies(setup, monkeypatch):
    """The A/B of the recovery knob: the same injected decode crash
    with recover OFF escapes step() and kills the loop — exactly the
    failure story ISSUE 15 exists to fix."""
    cfg, params, _ = setup
    eng = _engine(cfg, params)
    _plan(monkeypatch, [{"site": "serve_decode", "kind": "raise",
                         "message": "relay reset by peer",
                         "match_ctx": {"tick": 0}}])
    req = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4)
    eng.submit(req)
    eng.step()                  # the round returns at its first token
    assert len(req.out_tokens) == 1 and eng.tick == 0
    with pytest.raises(RuntimeError, match="relay reset"):
        eng.step()              # the same round's decode half


# ------------------------------------------------ combined / overlap


def test_all_layers_under_pressure_and_chaos(setup, monkeypatch):
    """Everything on at once under real pressure AND a scripted
    transient wedge: tight pool (preemption), bounded queue + burst
    (rejections), tiny shed threshold (sheds), one hung decode round
    (recovery) — the engine drains, the contract holds, and every
    surviving stream is greedy-correct vs the reference."""
    cfg, params, ref = setup
    _plan(monkeypatch, [
        {"site": "serve_burst", "kind": "burst", "count": 6,
         "prompt_len": 3, "max_new": 3, "match_ctx": {"tick": 3}},
        {"site": "serve_decode", "kind": "hang", "seconds": 1.0,
         "match_ctx": {"tick": 5}},
    ])
    lifecycle.enable()
    try:
        eng = _engine(cfg, params, num_pages=9, max_seq=16,
                      admit=4, shed=True, shed_ttft_ms=2000.0,
                      preempt=True, recover=True,
                      dispatch_timeout_s=60, round_retry_wait_s=0)
    finally:
        lifecycle.reset_enabled()
    reqs = _requests()
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.step()
    eng.dispatch_timeout_s = 0.25
    n = 0
    while not all(r.done() for r in reqs):
        eng.step()
        n += 1
        assert n < 200
    eng.step()
    for r in reqs:
        assert r.out_tokens == ref[r.rid], (r.rid, r.out_tokens)
    assert eng.resilience.degraded_rounds >= 1
    assert eng.events.validate_order() == []
    _assert_contract(eng)


def test_recovery_skips_finished_slots(setup, monkeypatch):
    """A request that FINISHED at this round's prefill (max_new=1)
    must not be requeued by the same round's decode failure: it needs
    no further compute — requeuing would stamp degraded_round after
    finished (forbidden) and replay a completed stream."""
    cfg, params, ref = setup
    # THREE slots: the third stays free through warmup, so `one` is
    # admitted + prefilled (and FINISHES — max_new=1) inside the very
    # round whose decode dispatch hangs
    eng, reqs = _warmed_recover_engine(
        cfg, params, monkeypatch,
        [{"site": "serve_decode", "kind": "hang", "seconds": 1.0,
          "match_ctx": {"tick": 2}}],
        num_slots=3)
    one = Request(rid=7, prompt=[3, 1, 4], max_new_tokens=1)
    eng.submit(one)
    n = 0
    while not (one.done() and all(r.done() for r in reqs)):
        eng.step()
        n += 1
        assert n < 100
    eng.step()
    assert eng.resilience.degraded_rounds == 1
    assert one.done() and len(one.out_tokens) == 1
    chain = [e["event"] for e in eng.events.request_events(7)]
    assert "degraded_round" not in chain, chain
    assert one.preemptions == 0
    assert eng.events.validate_order() == []
    for r in reqs:
        assert r.out_tokens == ref[r.rid]
    _assert_contract(eng)


def test_recovery_with_prefix_refs_on_finished_slot(setup, monkeypatch):
    """Round recovery with the prefix cache on while a FINISHED slot
    still holds shared-page references (a full-page prompt registered
    + acquired at its admission prefill, max_new=1): the recovery
    path must release those refs before flushing the cache — not
    crash on flush's live-reference refusal — and the engine keeps
    serving."""
    cfg, params, ref = setup
    lifecycle.enable()
    try:
        eng = _engine(cfg, params, num_slots=3, recover=True,
                      prefix_cache=True, dispatch_timeout_s=60,
                      round_retry_wait_s=0)
    finally:
        lifecycle.reset_enabled()
    reqs = _requests()
    for r in reqs:
        eng.submit(r)
    eng.step()
    eng.step()
    _plan(monkeypatch, [{"site": "serve_decode", "kind": "raise",
                         "message": "relay reset",
                         "match_ctx": {"tick": 2}}])
    # a FULL page of prompt (page_size=4) registers + acquires into
    # the prefix cache at this round's prefill; max_new=1 finishes it
    # in the same round — then the decode dispatch crashes
    one = Request(rid=7, prompt=[9, 9, 9, 9, 2], max_new_tokens=1)
    eng.submit(one)
    n = 0
    while not (one.done() and all(r.done() for r in reqs)):
        eng.step()
        n += 1
        assert n < 100
    eng.step()
    assert eng.resilience.degraded_rounds == 1
    chain = [e["event"] for e in eng.events.request_events(7)]
    assert "degraded_round" not in chain, chain
    for r in reqs:
        assert r.out_tokens == ref[r.rid]
    assert eng.events.validate_order() == []
    _assert_contract(eng)


def test_shed_composes_with_overlap(setup):
    """The deadline shedder runs in the OVERLAPPED round too (it
    touches queued requests only — no placeholder tokens exist before
    admission): a queue-stuck request sheds, the rest keep parity."""
    cfg, params, ref = setup
    lifecycle.enable()
    try:
        eng = _engine(cfg, params, num_slots=1, overlap=True,
                      shed=True, shed_ttft_ms=1.0)
    finally:
        lifecycle.reset_enabled()
    assert eng.overlap and eng.shed
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=12,
                    arrival=0) for i in range(3)]
    done = eng.run_trace(reqs)
    assert eng.resilience.shed > 0
    assert len(done) + len(eng.scheduler.shed) == 3
    assert eng.events.validate_order() == []
    _assert_contract(eng)


def test_overlap_interplay_asymmetry(setup):
    """overlap=True with preempt/recover demands raises; a demand
    drops the other side's env preference; env-vs-env falls back to
    the serial step (the spec-decode pairing precedent)."""
    cfg, params, _ = setup
    with pytest.raises(ValueError, match="overlap=True"):
        _engine(cfg, params, overlap=True, preempt=True)
    with pytest.raises(ValueError, match="overlap=True"):
        _engine(cfg, params, overlap=True, recover=True)
    # demand vs env preference: the demand wins, the preference drops
    import os
    os.environ["APEX_SERVE_PREEMPT"] = "1"
    try:
        eng = _engine(cfg, params, overlap=True)
        assert eng.overlap and not eng.preempt
        # env overlap vs preempt demand: overlap falls back
        os.environ["APEX_SERVE_OVERLAP"] = "1"
        eng2 = _engine(cfg, params, preempt=True)
        assert eng2.preempt and not eng2.overlap
        # env vs env: serial wins
        eng3 = _engine(cfg, params)
        assert eng3.preempt and not eng3.overlap
    finally:
        os.environ.pop("APEX_SERVE_PREEMPT", None)
        os.environ.pop("APEX_SERVE_OVERLAP", None)
