"""Serving-resilience unit surfaces (ISSUE 15): knob asymmetry of the
four layers, the lifecycle transition machine's suspension cycles, the
slo block's resilience fields + ledger teeth, check 9's resilience
pin rules (both directions), the scheduler's growth/victim/requeue
arithmetic (stdlib-only — no engine), the prefix-cache flush, the
guarded-dispatch watchdog, and the window_report/gauge plumbing."""

import json
import os

import pytest

from apex_tpu import resilience as res_mod
from apex_tpu.serving import lifecycle
from apex_tpu.serving import resilience as serve_res
from apex_tpu.serving.kv_cache import PageAllocator
from apex_tpu.serving.prefix_cache import PrefixCache
from apex_tpu.serving.scheduler import (
    ContinuousBatchingScheduler,
    Request,
)
from apex_tpu.telemetry import ledger as ledger_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------ knob asymmetry


def test_resolve_admit_asymmetry(monkeypatch):
    monkeypatch.delenv("APEX_SERVE_ADMIT", raising=False)
    assert serve_res.resolve_admit() == 0          # built-in OFF
    assert serve_res.resolve_admit(4) == 4
    assert serve_res.resolve_admit(0) == 0         # explicit off
    assert serve_res.resolve_admit(False) == 0
    for bad in (-1, 2.5, "8", True):
        with pytest.raises(ValueError, match="admit="):
            serve_res.resolve_admit(bad)
    monkeypatch.setenv("APEX_SERVE_ADMIT", "16")
    assert serve_res.resolve_admit() == 16
    monkeypatch.setenv("APEX_SERVE_ADMIT", "0")
    assert serve_res.resolve_admit() == 0          # env off-pin
    monkeypatch.setenv("APEX_SERVE_ADMIT", "lots")
    assert serve_res.resolve_admit() == 0          # garbage ignored


@pytest.mark.parametrize("resolve,env", [
    (serve_res.resolve_shed, "APEX_SERVE_SHED"),
    (serve_res.resolve_preempt, "APEX_SERVE_PREEMPT"),
    (serve_res.resolve_recover, "APEX_SERVE_RECOVER"),
])
def test_resolve_flag_asymmetry(resolve, env, monkeypatch):
    monkeypatch.delenv(env, raising=False)
    assert resolve() is False
    assert resolve(True) is True
    assert resolve(False) is False
    with pytest.raises(ValueError):
        resolve("yes")                              # demand: raises
    monkeypatch.setenv(env, "1")
    assert resolve() is True
    monkeypatch.setenv(env, "0")
    assert resolve() is False
    monkeypatch.setenv(env, "on")                   # preference: falls
    assert resolve() is False


def test_rejected_is_frozen_structured():
    r = serve_res.Rejected("queue_full", 3)
    assert (r.reason, r.retry_after_ticks) == ("queue_full", 3)
    with pytest.raises((AttributeError, TypeError)):
        r.reason = "other"


# -------------------------------------------------- guarded dispatch


def test_guarded_dispatch_passes_result_through():
    assert serve_res.guarded_dispatch(lambda: 41 + 1, 5.0, "decode") \
        == 42


def test_guarded_dispatch_timeout_is_wedged():
    import time

    with pytest.raises(serve_res.DispatchFailure) as ei:
        serve_res.guarded_dispatch(lambda: time.sleep(1.0), 0.05,
                                   "decode")
    assert ei.value.verdict == res_mod.WEDGED
    assert ei.value.phase == "decode"


def test_guarded_dispatch_crash_is_degraded_relay():
    def boom():
        raise OSError("connection reset")

    with pytest.raises(serve_res.DispatchFailure) as ei:
        serve_res.guarded_dispatch(boom, 5.0, "prefill")
    assert ei.value.verdict == res_mod.DEGRADED_RELAY
    assert "connection reset" in ei.value.detail
    assert isinstance(ei.value.__cause__, OSError)


def test_serving_envelope_constants_exist():
    """The §6 serving entries live in the ONE envelope home."""
    assert res_mod.SERVE_DISPATCH_TIMEOUT_S > 0
    assert res_mod.SERVE_ROUND_ATTEMPTS >= 1
    assert res_mod.SERVE_ROUND_RETRY_WAIT_S >= 0


# --------------------------------------------- lifecycle order machine


def _log(chain, rid=0):
    log = lifecycle.EventLog()
    for i, ev in enumerate(chain):
        log.record(ev, rid, tick=i, wall=float(i))
    return log


def test_validate_order_accepts_suspension_cycles():
    for chain in (
        # preempted mid-stream, re-admitted, finishes
        ("submitted", "admitted", "prefill_done", "first_token",
         "preempted", "resubmitted", "admitted", "finished",
         "evicted"),
        # degraded round before any token; prefill seam after
        ("submitted", "admitted", "degraded_round", "resubmitted",
         "admitted", "prefill_done", "first_token", "finished",
         "evicted"),
        # two suspension cycles
        ("submitted", "admitted", "prefill_done", "first_token",
         "preempted", "resubmitted", "admitted", "degraded_round",
         "resubmitted", "admitted", "finished", "evicted"),
        # terminal paths
        ("submitted", "rejected"),
        ("submitted", "shed"),
        ("submitted", "admitted", "preempted", "resubmitted", "shed"),
    ):
        assert _log(chain).validate_order() == [], chain


def test_validate_order_rejects_bad_resilience_chains():
    cases = [
        # a suspension must be followed by resubmitted
        (("submitted", "admitted", "preempted", "admitted"),
         "out of order"),
        # the first-token seam fires once across cycles
        (("submitted", "admitted", "prefill_done", "first_token",
          "preempted", "resubmitted", "admitted", "prefill_done"),
         "duplicate"),
        # nothing after a terminal reject
        (("submitted", "rejected", "admitted"), "out of order"),
        # finished needs a first token
        (("submitted", "admitted", "finished"), "'finished' before"),
        # shed is once-only
        (("submitted", "shed", "shed"), "duplicate"),
    ]
    for chain, needle in cases:
        probs = _log(chain).validate_order()
        assert any(needle in p for p in probs), (chain, probs)


def test_core_events_is_the_happy_path():
    assert _log(lifecycle.CORE_EVENTS).validate_order() == []
    assert set(lifecycle.CORE_EVENTS) < set(lifecycle.EVENTS)


def test_gauges_carry_resilience_counters():
    log = lifecycle.EventLog()
    log.sample_gauges(tick=0, wall=0.0, slots_active=1, num_slots=2,
                      queue_depth=0, kv_pages_live=1, kv_pages_total=8,
                      hol_wait_s=0.0, rejected=2, shed=1, preempted=3,
                      resubmitted=4, degraded_rounds=1)
    row = log.gauge_rows()[0]
    assert row["serve_rejected"] == 2
    assert row["serve_shed"] == 1
    assert row["serve_preempted"] == 3
    assert row["serve_resubmitted"] == 4
    assert row["serve_degraded_rounds"] == 1
    from apex_tpu.telemetry import metrics

    for name in ("serve_rejected", "serve_shed", "serve_preempted",
                 "serve_resubmitted", "serve_degraded_rounds"):
        assert metrics.spec(name) is not None, name


# -------------------------------------------- slo block + ledger teeth


def _slo(**resilience):
    return lifecycle.slo_block(
        [], 1.0, ttft_ms=100.0, tpot_ms=10.0,
        arrival_process="poisson", offered_load=1.0,
        resilience=resilience or None)


def test_slo_block_resilience_fields_none_when_disabled():
    blk = _slo()
    assert blk["shed_rate"] is None
    assert blk["preempt_rate"] is None
    assert blk["degraded_rounds"] is None
    blk = _slo(shed_rate=0.25, preempt_rate=0.125, degraded_rounds=2)
    assert blk["shed_rate"] == 0.25
    assert blk["preempt_rate"] == 0.125
    assert blk["degraded_rounds"] == 2
    for f in ("shed_rate", "preempt_rate", "degraded_rounds"):
        assert f in ledger_mod.SLO_FIELDS


def test_ledger_validates_resilience_fields():
    good = _slo(shed_rate=0.5, preempt_rate=0.0, degraded_rounds=0)
    rec = ledger_mod.make_record("profile_serving", "cpu", 0.1, 2,
                                 extra={"slo": good})
    assert ledger_mod.validate_record(rec) == []
    cases = [
        ({"shed_rate": 1.5}, "shed_rate"),
        ({"preempt_rate": -0.1}, "preempt_rate"),
        ({"preempt_rate": True}, "preempt_rate"),
        ({"degraded_rounds": -1}, "degraded_rounds"),
        ({"degraded_rounds": 2.5}, "degraded_rounds"),
    ]
    for mut, needle in cases:
        r = ledger_mod.make_record(
            "profile_serving", "cpu", 0.1, 2,
            extra={"slo": dict(good, **mut)})
        probs = ledger_mod.validate_record(r)
        assert any(needle in p for p in probs), (mut, probs)
    # a missing resilience field is a finding (presence teeth)
    bad = dict(good)
    del bad["shed_rate"]
    r = ledger_mod.make_record("profile_serving", "cpu", 0.1, 2,
                               extra={"slo": bad})
    assert any("shed_rate" in p
               for p in ledger_mod.validate_record(r))


def test_resilience_stats_rates():
    st = serve_res.ResilienceStats(shed=1, preempted=2,
                                   submit_attempts=4, admissions=8,
                                   degraded_rounds=3)
    on = st.rates(shed_on=True, preempt_on=True, recover_on=True)
    assert on == {"shed_rate": 0.25, "preempt_rate": 0.25,
                  "degraded_rounds": 3}
    off = st.rates(shed_on=False, preempt_on=False, recover_on=False)
    assert off == {"shed_rate": None, "preempt_rate": None,
                   "degraded_rounds": None}


# ----------------------------------------------------- check 9 teeth


def _check9(tmp_path, knobs, slo):
    from tests.conftest import run_check_bench_labels

    rec = ledger_mod.make_record("profile_serving", "cpu", 0.1, 2,
                                 knobs=knobs, extra={"slo": slo})
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text(json.dumps(rec) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(f"| row | 1 ms | x |\n\nledger:{rec['id']}\n")
    table = tmp_path / "table.jsonl"
    table.write_text("")
    return run_check_bench_labels(
        "--perf", str(perf), "--ledger", str(ledger),
        "--table", str(table))


BASE_PINS = {"APEX_SERVE_SLO_TTFT_MS": "100.0",
             "APEX_SERVE_SLO_TPOT_MS": "10.0",
             "APEX_SERVE_ARRIVALS": "poisson",
             "APEX_SERVE_SCHED": "fifo"}


def test_check9_resilience_pin_teeth(tmp_path):
    engaged = _slo(shed_rate=0.2, preempt_rate=0.1, degraded_rounds=1)
    # engaged rates + all pins non-off: clean
    pins = dict(BASE_PINS, APEX_SERVE_SHED="1", APEX_SERVE_PREEMPT="1",
                APEX_SERVE_RECOVER="1")
    out = _check9(tmp_path, pins, engaged)
    assert out.returncode == 0, out.stdout
    # a non-None rate with the pin MISSING is drift
    out = _check9(tmp_path, BASE_PINS, engaged)
    assert out.returncode == 1
    assert "does not pin APEX_SERVE_SHED" in out.stdout
    assert "does not pin APEX_SERVE_PREEMPT" in out.stdout
    assert "does not pin APEX_SERVE_RECOVER" in out.stdout
    # a non-None rate under an OFF pin is drift the other way
    out = _check9(tmp_path, dict(pins, APEX_SERVE_SHED="0"), engaged)
    assert out.returncode == 1
    assert "APEX_SERVE_SHED='0' (off)" in out.stdout
    # disabled block (all None) needs no resilience pins at all
    out = _check9(tmp_path, BASE_PINS, _slo())
    assert out.returncode == 0, out.stdout


# ------------------------------------- scheduler growth / requeue unit


def _sched(num_pages=8, preempt=True, policy=None):
    alloc = PageAllocator(num_pages)
    return ContinuousBatchingScheduler(2, 4, 4, alloc, policy=policy,
                                       preempt=preempt)


def test_overcommit_reserves_prompt_pages_only():
    sch = _sched(num_pages=16)
    r = Request(rid=0, prompt=[1] * 6, max_new_tokens=10)  # 4 total
    sch.submit(r, tick=0)
    [i] = sch.admit(0)
    assert len(sch.slots[i].pages) == 2          # ceil(6/4), not 4
    assert sch.slots[i].known == [1] * 6
    full = _sched(num_pages=16, preempt=False)
    full.submit(Request(rid=0, prompt=[1] * 6, max_new_tokens=10),
                tick=0)
    [j] = full.admit(0)
    assert len(full.slots[j].pages) == 4         # the full reservation


def test_grow_extends_then_preempts_youngest():
    sch = _sched(num_pages=6)                    # 5 allocatable
    a = Request(rid=0, prompt=[1] * 6, max_new_tokens=10)
    b = Request(rid=1, prompt=[2] * 6, max_new_tokens=10)
    sch.submit(a, tick=0)
    sch.submit(b, tick=0)
    ia, ib = sch.admit(0)
    assert sch.allocator.free_count == 1
    assert sch.grow(ia, 3, tick=1)               # takes the last page
    assert sch.allocator.free_count == 0
    # b's growth must preempt — the youngest (b itself is youngest:
    # same tick, higher rid) gets requeued and grow reports False
    b_pages = list(sch.slots[ib].pages)
    assert sch.grow(ib, 3, tick=2) is False
    assert sch.slots[ib] is None
    assert [r.rid for r in sch.take_preempted()] == [1]
    assert b.resume_tokens is None               # no tokens yet: fresh
    assert b in sch.queue
    assert sch.allocator.free_count == len(b_pages)
    sch.allocator.check_invariants()
    # a's further growth now succeeds from the freed pages
    assert sch.grow(ia, 4, tick=3)


def test_grow_prefers_lowest_priority_victim():
    sch = _sched(num_pages=6, policy="priority")
    hi = Request(rid=0, prompt=[1] * 6, max_new_tokens=10, priority=5)
    lo = Request(rid=1, prompt=[2] * 6, max_new_tokens=10, priority=0)
    sch.submit(hi, tick=0)
    sch.submit(lo, tick=0)
    admitted = sch.admit(0)
    i_hi = next(i for i in admitted
                if sch.slots[i].request.rid == 0)
    sch.grow(i_hi, 3, tick=1)
    # hi needs a 4th page: the LOW-priority slot is the victim even
    # though it is not the youngest admission order
    assert sch.grow(i_hi, 4, tick=2) is True
    assert [r.rid for r in sch.take_preempted()] == [1]
    sch.allocator.check_invariants()


def test_requeue_stashes_stream_and_respects_prefix_refs():
    alloc = PageAllocator(16)
    prefix = PrefixCache(alloc, 4)
    sch = ContinuousBatchingScheduler(2, 4, 4, alloc, prefix=prefix,
                                      preempt=True)
    r = Request(rid=0, prompt=[1, 2, 3, 4, 5, 6], max_new_tokens=8)
    sch.submit(r, tick=0)
    [i] = sch.admit(0)
    # simulate generated tokens, then a mid-stream requeue
    r.out_tokens = [10, 11, 12]
    req = sch.requeue_slot(i, tick=3)
    assert req is r
    assert r.resume_tokens == [1, 2, 3, 4, 5, 6, 10, 11, 12]
    assert r.preemptions == 1
    assert sch.slots[i] is None and r in sch.queue
    alloc.check_invariants()
    # re-admission: known = the resumed stream, prefix lookup skipped
    [j] = sch.admit(4)
    assert sch.slots[j].known == r.resume_tokens
    assert sch.slots[j].prefix_hit == 0


def test_prefix_flush_refuses_live_refs_then_frees_all():
    alloc = PageAllocator(16)
    pc = PrefixCache(alloc, 4)
    owner = ("req", 0)
    pages = alloc.alloc(owner, 2)
    adopted, _ = pc.register([1, 2, 3, 4, 5, 6, 7, 8], pages, owner)
    pc.acquire(adopted)
    with pytest.raises(AssertionError, match="live references"):
        pc.flush()
    pc.release(adopted)
    freed = pc.flush()
    assert freed == len(adopted)
    assert pc.nodes == {} and pc.tails == {} and pc.refs == {}
    alloc.free(owner)
    alloc.check_invariants()
    assert alloc.free_count == 15


def test_scripted_alloc_deny_times_budget(monkeypatch):
    from apex_tpu.resilience import faults

    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps(
        [{"site": "serve_alloc", "kind": "deny", "times": 2}]))
    faults._cache["fired"] = {}
    sch = _sched(num_pages=16)
    r = Request(rid=0, prompt=[1] * 4, max_new_tokens=4)
    sch.submit(r, tick=0)
    assert sch.admit(0) == []        # denied (1/2)
    assert sch.admit(1) == []        # denied (2/2)
    [i] = sch.admit(2)               # budget spent: grant resumes
    assert sch.slots[i] is not None
    faults._cache["fired"] = {}


def test_finished_slot_is_never_a_victim():
    """A slot whose request already finished (awaiting next round's
    evict) must not be preempted: its pages free at the evict anyway,
    and a preempted-after-finished chain is forbidden by the
    lifecycle machine — the grower self-preempts instead."""
    sch = _sched(num_pages=6)                    # 5 allocatable
    a = Request(rid=0, prompt=[1] * 6, max_new_tokens=1)
    b = Request(rid=1, prompt=[2] * 6, max_new_tokens=10)
    sch.submit(a, tick=0)
    sch.submit(b, tick=0)
    ia, ib = sch.admit(0)
    a.out_tokens = [7]                           # a finished at prefill
    assert sch.grow(ib, 3, tick=1)               # drains the free list
    assert sch.grow(ib, 4, tick=1) is False      # pressure: b needs more
    preempted = sch.take_preempted()
    assert [r.rid for r in preempted] == [1]     # b self-preempted
    assert sch.slots[ia] is not None             # a kept its seat
    assert a.preemptions == 0
    sch.allocator.check_invariants()


# -------------------------------------------- slow overload e2e twin


@pytest.mark.slow
def test_serving_resilience_rung_e2e(tmp_path, shared_smoke_cache_dir):
    """The `serving_resilience` rung end-to-end at smoke shapes on the
    session-shared smoke compile cache: one profile_serving run under
    the rung's exact env (diurnal trace, admission bound, shedder,
    preemption) emits ONE validated ledger record whose slo block
    carries non-None shed/preempt rates, whose knobs pin all four
    resilience knobs at the resolved values, and which is check-9
    clean against the produced artifacts — the heavy overload twin of
    the fast chaos suite."""
    import subprocess
    import sys

    from tests.conftest import run_check_bench_labels

    ledger = tmp_path / "ledger.jsonl"
    env = dict(os.environ, APEX_BENCH_SMOKE="1",
               APEX_TELEMETRY_LEDGER=str(ledger),
               JAX_COMPILATION_CACHE_DIR=shared_smoke_cache_dir,
               APEX_SERVE_ARRIVALS="diurnal", APEX_SERVE_ADMIT="32",
               APEX_SERVE_SHED="1", APEX_SERVE_PREEMPT="1")
    env.pop("APEX_FAULT_PLAN", None)
    env.pop("APEX_SERVE_RECOVER", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "profile_serving.py"),
         "--smoke"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = ledger_mod.read_ledger(str(ledger))[-1]
    assert ledger_mod.validate_record(rec) == []
    slo = rec["slo"]
    assert slo["arrival_process"] == "diurnal"
    assert slo["shed_rate"] is not None and 0 <= slo["shed_rate"] <= 1
    assert slo["preempt_rate"] is not None \
        and 0 <= slo["preempt_rate"] <= 1
    assert slo["degraded_rounds"] is None    # recover stays off
    knobs = rec["knobs"]
    assert knobs["APEX_SERVE_ADMIT"] == "32"
    assert knobs["APEX_SERVE_SHED"] == "1"
    assert knobs["APEX_SERVE_PREEMPT"] == "1"
    assert knobs["APEX_SERVE_RECOVER"] == "0"
    # check 9 (incl. the resilience teeth) clean on the produced row
    perf = tmp_path / "PERF.md"
    perf.write_text(f"| row | 1 ms | x |\n\nledger:{rec['id']}\n")
    table = tmp_path / "table.jsonl"
    table.write_text("")
    out = run_check_bench_labels(
        "--perf", str(perf), "--ledger", str(ledger),
        "--table", str(table))
    assert out.returncode == 0, out.stdout


# ------------------------------------------------------ window_report


def test_window_report_prints_resilience_counts(tmp_path, capsys):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "window_report", os.path.join(REPO, "tools",
                                      "window_report.py"))
    wr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(wr)
    slo = _slo(shed_rate=0.2, preempt_rate=0.05, degraded_rounds=2)
    rec = ledger_mod.make_record(
        "profile_serving", "cpu", 0.1, 2,
        extra={"serving": {"tokens_per_s": 10.0, "p50_ms": 1.0,
                           "p99_ms": 2.0, "trace_id": "tr-abc",
                           "kv_pages": 8},
               "slo": slo})
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text(json.dumps(rec) + "\n")
    report = wr.build_report(ledger_path=str(ledger))
    wr.print_report(report)
    out = capsys.readouterr().out
    assert "shed=20%" in out
    assert "preempt=5%" in out
    assert "degraded_rounds=2" in out
    # the --json line carries the whole slo dict wholesale
    assert report["ledger"]["serving"][0]["slo"]["shed_rate"] == 0.2
