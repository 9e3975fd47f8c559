"""Multi-token decode blocks (ISSUE 17): K decode steps per device
dispatch in ONE ``lax.scan`` program. The headline invariant is
token-for-token parity with the K=1 engine under EVERY layer
combination — greedy serial + overlapped rounds, sampling-lane RNG
determinism (the counter folds inside the scan), prefix-cache sharing,
mid-block preemption requeue/replay, shed/admit at block boundaries,
and a chaos ``serve_decode`` hang recovering the whole K-block — plus
the one-compile contract (``decode_cache_size() == 1`` per engine; K
is a static key, budgets/warmup feeds are values), the knob-asymmetry
surface of ``resolve_decode_k`` × ``spec_decode``, and the ledger /
check-8 teeth for the ``decode_block_k`` field."""

import json
import os

import pytest

from apex_tpu.resilience import faults
from apex_tpu.serving import (
    Request,
    SamplingParams,
    ServingEngine,
    lifecycle,
    synthetic_trace,
)
from apex_tpu.serving import model as smodel

from apex_tpu.telemetry import ledger as ledger_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

KS = (2, 4, 8)


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
    params = smodel.init_gpt_params(cfg)
    return cfg, params


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("APEX_FAULT_PLAN", raising=False)
    faults._cache["fired"] = {}
    yield
    faults._cache["fired"] = {}


def _engine(cfg, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 8)
    kw.setdefault("num_pages", 48)
    kw.setdefault("max_seq", 64)
    kw.setdefault("prefill_len", 40)
    return ServingEngine(cfg, params=params, **kw)


def _run(cfg, params, k, trace_kw=None, **kw):
    eng = _engine(cfg, params, decode_k=k, **kw)
    tkw = dict(seed=3, n_requests=8, vocab=128, prompt_lo=4,
               prompt_hi=12, new_lo=3, new_hi=10)
    tkw.update(trace_kw or {})
    reqs, _ = synthetic_trace(**tkw)
    out = eng.run_trace(reqs)
    return {r.rid: list(r.out_tokens) for r in out}, eng


def _contract(eng):
    assert eng.decode_cache_size() == 1, eng.decode_cache_size()
    assert eng.prefill_cache_size() <= 1, eng.prefill_cache_size()
    eng.allocator.check_invariants()
    if eng.prefix is not None:
        eng.prefix.check_invariants()


# ------------------------------------------------------ knob asymmetry


def test_resolve_decode_k_knob_asymmetry(monkeypatch):
    """Per-call decode_k= is a DEMAND (raises on un-honorable);
    APEX_SERVE_DECODE_K is a PREFERENCE through the one-home
    positive-int parser (garbage warns once, falls back to 1)."""
    monkeypatch.delenv("APEX_SERVE_DECODE_K", raising=False)
    for bad in (True, False, 0, -1, 1.5, "4"):
        with pytest.raises(ValueError):
            smodel.resolve_decode_k(bad)
    assert smodel.resolve_decode_k(4) == 4
    assert smodel.resolve_decode_k() == 1
    monkeypatch.setenv("APEX_SERVE_DECODE_K", "4")
    assert smodel.resolve_decode_k() == 4
    # a per-call demand outranks the env preference
    assert smodel.resolve_decode_k(2) == 2
    from apex_tpu.dispatch import tiles

    tiles._warned_env.clear()
    monkeypatch.setenv("APEX_SERVE_DECODE_K", "fast")
    with pytest.warns(UserWarning, match="fast"):
        assert smodel.resolve_decode_k() == 1


def test_decode_k_times_spec_decode_pairing(setup, monkeypatch):
    """The established two-demands-raise / demand-drops-preference /
    env-falls-back asymmetry across the decode_k × spec_decode pair
    (both batch multiple tokens per dispatch; the verify rollback
    assumes ONE pending token per round)."""
    cfg, params = setup
    monkeypatch.delenv("APEX_SERVE_DECODE_K", raising=False)
    monkeypatch.delenv("APEX_SPEC_DECODE", raising=False)
    # two per-call demands: no honorable order -> raise
    with pytest.raises(ValueError, match="decode_k"):
        _engine(cfg, params, decode_k=4, spec_decode=3)
    # per-call K-block demand drops the env draft preference
    monkeypatch.setenv("APEX_SPEC_DECODE", "3")
    eng = _engine(cfg, params, decode_k=4)
    assert eng.decode_k == 4 and eng.spec_k == 0
    assert eng.spec_stats is None
    monkeypatch.delenv("APEX_SPEC_DECODE")
    # env K preference yields to a per-call spec demand
    monkeypatch.setenv("APEX_SERVE_DECODE_K", "4")
    eng = _engine(cfg, params, spec_decode=3)
    assert eng.decode_k == 1 and eng.spec_k == 3
    # env vs env: K falls back to 1 (the committed measurement backs
    # the spec layer; the K-block row is still queued in PERF.md §2)
    monkeypatch.setenv("APEX_SPEC_DECODE", "3")
    eng = _engine(cfg, params)
    assert eng.decode_k == 1 and eng.spec_k == 3


# --------------------------------------------------- parity vs K=1


def test_greedy_parity_and_dispatch_amortization(setup):
    """THE acceptance invariant: every K emits the K=1 engine's tokens
    token-for-token, with one compiled decode program, while
    ``decode_steps`` (DISPATCH count — the ~65 ms relay unit) drops."""
    cfg, params = setup
    base, e1 = _run(cfg, params, 1)
    for k in KS:
        got, ek = _run(cfg, params, k)
        assert got == base, k
        _contract(ek)
        assert ek.tokens_generated == e1.tokens_generated
        assert ek.decode_steps < e1.decode_steps, \
            (k, ek.decode_steps, e1.decode_steps)


def test_overlap_rounds_dispatch_k_blocks(setup):
    """The overlapped round defers the SAME K-block fetch: parity with
    the serial K=1 stream under overlap=True for every K."""
    cfg, params = setup
    base, _ = _run(cfg, params, 1)
    for k in KS:
        got, ek = _run(cfg, params, k, overlap=True)
        assert got == base, k
        _contract(ek)


def test_sampling_rng_determinism_across_k(setup):
    """Sampling lanes fold the per-step generation index inside the
    scan: seeded streams are identical whatever the block size (the
    (key, counter) draw depends on neither K nor batch shape)."""
    cfg, params = setup

    def run(k):
        eng = _engine(cfg, params, decode_k=k, sampling=True)
        reqs, _ = synthetic_trace(seed=5, n_requests=6, vocab=128,
                                  prompt_lo=4, prompt_hi=10,
                                  new_lo=3, new_hi=8)
        for r in reqs:
            r.sampling = SamplingParams(temperature=0.9, top_k=20,
                                        seed=100 + r.rid)
        out = eng.run_trace(reqs)
        assert eng.decode_cache_size() == 1
        return {r.rid: list(r.out_tokens) for r in out}

    base = run(1)
    for k in KS:
        assert run(k) == base, k


def test_prefix_cache_parity_across_k(setup):
    """Shared-prefix COW pages under K-block decode: the block's page
    writes land past the shared span, so hits/refcounts/streams all
    match the K=1 engine."""
    cfg, params = setup

    def run(k):
        return _run(cfg, params, k, prefix_cache=True, trace_kw=dict(
            system_prompt=[7, 9, 11, 13, 5, 3]))

    base, _ = run(1)
    for k in KS:
        got, ek = run(k)
        assert got == base, k
        _contract(ek)


def test_preemption_midblock_requeue_replay_parity(setup):
    """A pool too small for every stream's peak forces mid-block
    grant refusals: victims requeue with their partial tokens (the
    ordinary ``resume_tokens`` replay path) and every K's final
    streams are token-for-token the K=1 engine's — preemption never
    drops a request, so parity is over the FULL trace."""
    cfg, params = setup

    def run(k):
        return _run(cfg, params, k, preempt=True, page_size=4,
                    num_pages=9, max_seq=32, prefill_len=32,
                    trace_kw=dict(n_requests=10, new_lo=8, new_hi=24))

    base, e1 = run(1)
    assert e1.resilience.preempted > 0, \
        "trace did not exercise preemption — tighten the pool"
    for k in KS:
        got, ek = run(k)
        assert got == base, k
        _contract(ek)
        assert ek.resilience.preempted > 0, k


def test_shed_admit_armed_but_untriggered_is_pure_addition(setup):
    """The disabled-mode converse under K-blocks: admission control +
    shedding ARMED but never triggering (roomy queue bound, huge TTFT
    threshold) leave every K's streams token-for-token the K=1
    engine's — the queue layers are pure additions at every block
    size."""
    cfg, params = setup

    def run(k):
        return _run(cfg, params, k, shed=True, admit=16,
                    shed_ttft_ms=1e9, trace_kw=dict(
                        n_requests=12, mean_interarrival=0.1))

    base, e1 = run(1)
    assert e1.resilience.shed == 0 and e1.resilience.rejected == 0
    for k in KS:
        got, ek = run(k)
        assert got == base, k
        _contract(ek)


def test_shed_admit_trigger_at_block_boundaries(setup):
    """Queue-side layers under real overload act at K-tick (block)
    granularity: a one-slot K=4 engine with a bounded queue and a
    tiny TTFT threshold rejects the overflow at submit, sheds the
    queue-stuck requests between blocks (never mid-block — shed
    requests have NO tokens), and the survivors' streams stay
    token-for-token the uncontended engine's (per-request streams
    do not depend on the admission set)."""
    cfg, params = setup
    ref_reqs = [Request(rid=i, prompt=[1 + i, 2, 3],
                        max_new_tokens=12, arrival=0)
                for i in range(6)]
    ref_eng = _engine(cfg, params, decode_k=4)
    ref = {r.rid: list(r.out_tokens)
           for r in ref_eng.run_trace(ref_reqs)}
    lifecycle.enable()
    try:
        eng = _engine(cfg, params, num_slots=1, decode_k=4,
                      shed=True, shed_ttft_ms=1.0, admit=4)
    finally:
        lifecycle.reset_enabled()
    reqs = [Request(rid=i, prompt=[1 + i, 2, 3], max_new_tokens=12,
                    arrival=0) for i in range(6)]
    done = eng.run_trace(reqs)
    assert eng.resilience.rejected > 0      # admit bound at submit
    assert eng.resilience.shed > 0          # deadline shedder fired
    assert len(done) + len(eng.scheduler.shed) \
        + len(eng.rejected) == 6            # every request settles once
    for r in eng.scheduler.shed:
        assert not r.out_tokens             # shed only BETWEEN blocks
        assert r.shed_tick is not None
    for r in done:
        assert list(r.out_tokens) == ref[r.rid], r.rid
    assert eng.events.validate_order() == []
    _contract(eng)


# -------------------------------------------- chaos: whole-block unit


def test_chaos_decode_hang_recovers_whole_k_block(setup, monkeypatch):
    """The watchdog treats the K-block as its dispatch unit: a wedged
    K=4 block times out ONCE, every in-flight request requeues (no
    partial block tokens leak), and the replay finishes token-for-token
    the healthy K=1 streams."""
    cfg, params = setup
    reqs = [Request(rid=0, prompt=[1, 2, 3, 4, 5, 6],
                    max_new_tokens=10),
            Request(rid=1, prompt=[7, 8, 9, 10, 11, 12],
                    max_new_tokens=10)]
    ref_eng = _engine(cfg, params)
    for r in reqs:
        ref_eng.submit(r)
    while not all(r.done() for r in reqs):
        ref_eng.step()
    ref = {r.rid: list(r.out_tokens) for r in reqs}

    lifecycle.enable()
    try:
        eng = _engine(cfg, params, decode_k=4, recover=True,
                      dispatch_timeout_s=60, round_retry_wait_s=0)
    finally:
        lifecycle.reset_enabled()
    reqs = [Request(rid=0, prompt=[1, 2, 3, 4, 5, 6],
                    max_new_tokens=10),
            Request(rid=1, prompt=[7, 8, 9, 10, 11, 12],
                    max_new_tokens=10)]
    for r in reqs:
        eng.submit(r)
    eng.step()          # prefill + K-block decode compile (tick 0)
    eng.step()          # a steady-state block (tick 1)
    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps(
        [{"site": "serve_decode", "kind": "hang", "seconds": 1.0,
          "match_ctx": {"tick": 2}}]))
    eng.dispatch_timeout_s = 0.25
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
    for r in reqs:
        assert r.out_tokens == ref[r.rid], (r.rid, r.out_tokens)
    assert eng.events.validate_order() == []
    _contract(eng)


# ----------------------------------------------- one-compile contract


def test_one_compile_contract_with_layers_on(setup):
    """K is a STATIC program key; per-lane budgets, the warmup feed
    and sampling counters ride as values — so a K=4 engine with
    sampling + prefix cache on over a churning trace still compiles
    exactly ONE decode program and at most one prefill program."""
    cfg, params = setup
    eng = _engine(cfg, params, decode_k=4, sampling=True,
                  prefix_cache=True, num_pages=64)
    reqs, _ = synthetic_trace(seed=9, n_requests=8, vocab=128,
                              prompt_lo=4, prompt_hi=12, new_lo=2,
                              new_hi=9, system_prompt=[3, 1, 4, 1, 5])
    for i, r in enumerate(reqs):
        if i % 2:
            r.sampling = SamplingParams(temperature=0.8, top_k=16,
                                        seed=r.rid)
    eng.run_trace(reqs)
    eng.step()
    assert eng.decode_cache_size() == 1, \
        "the K-block program recompiled — a budget/warmup input " \
        "leaked into the compile key"
    assert eng.prefill_cache_size() <= 1
    _contract(eng)


# ------------------------------------------------- ledger / check 8


def _check8(tmp_path, knobs, extra):
    from tests.conftest import run_check_bench_labels

    rec = ledger_mod.make_record("profile_serving", "cpu", 0.1, 2,
                                 knobs=knobs, extra=extra)
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text(json.dumps(rec) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(f"multitok row cites ledger:{rec['id']}\n")
    table = tmp_path / "table.jsonl"
    table.write_text("")
    return run_check_bench_labels(
        "--perf", str(perf), "--ledger", str(ledger),
        "--table", str(table))


def _record(decode_block_k, **knobs):
    from tests.test_serving_slo import SLO_PINS, _good_slo

    pins = {"APEX_SERVE_WEIGHT_QUANT": "0",
            "APEX_SERVE_KV_QUANT": "0", "APEX_SERVE_KV_SWAP": "0",
            **SLO_PINS, **knobs}
    slo = dict(_good_slo(), decode_block_k=decode_block_k)
    serving = {"tokens_per_s": 10.0, "p50_ms": 1.0, "p99_ms": 2.0,
               "trace_id": "tr-0123456789", "kv_pages": 8}
    return pins, {"serving": serving, "slo": slo}


def test_check8_serving_row_must_pin_decode_k(tmp_path):
    pins, extra = _record(4)
    out = _check8(tmp_path, pins, extra)
    assert out.returncode == 1
    assert "APEX_SERVE_DECODE_K" in out.stdout


def test_check8_decode_k_pin_and_block_must_agree(tmp_path):
    # pin names K=4 but the engine ran K=1: different programs
    pins, extra = _record(1, APEX_SERVE_DECODE_K="4")
    out = _check8(tmp_path, pins, extra)
    assert out.returncode == 1
    assert "different decode programs" in out.stdout
    # the other direction: block claims K=4 under a K=1 pin
    pins, extra = _record(4, APEX_SERVE_DECODE_K="1")
    out = _check8(tmp_path, pins, extra)
    assert out.returncode == 1
    assert "different decode programs" in out.stdout
    # a corrupt pin is a FINDING, never a checker crash
    pins, extra = _record(4, APEX_SERVE_DECODE_K="turbo")
    out = _check8(tmp_path, pins, extra)
    assert out.returncode == 1
    assert "not a number" in out.stdout


def test_check8_matching_decode_k_row_clean(tmp_path):
    pins, extra = _record(4, APEX_SERVE_DECODE_K="4")
    out = _check8(tmp_path, pins, extra)
    assert out.returncode == 0, out.stdout
