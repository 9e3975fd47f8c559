"""The driver-facing bench.py contract: one parseable JSON line with the
required fields, produced end-to-end in CPU smoke mode. A broken bench at
driver time means no headline measurement for the round, so this is
regression-tested like any other interface."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.slow
def test_bench_smoke_json_contract(tmp_path):
    # one attempt with a sub-test-timeout budget: bench's own timeout
    # path then fires first on a slow box, yielding a deterministic
    # error-JSON line instead of subprocess.run SIGKILLing the watchdog
    # (which would bypass its SIGTERM flush and orphan the inner child)
    ledger_path = str(tmp_path / "ledger.jsonl")
    env = dict(os.environ, APEX_BENCH_SMOKE="1", APEX_BENCH_ATTEMPTS="1",
               APEX_BENCH_TIMEOUT="420", APEX_TELEMETRY="1",
               APEX_TELEMETRY_LEDGER=ledger_path,
               APEX_TELEMETRY_PATH=str(tmp_path / "metrics.jsonl"))
    env.pop("JAX_PLATFORMS", None)  # smoke_mode forces CPU itself
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
    # the driver reads ONE JSON line — a second (e.g. per-attempt debug
    # record) is a contract break even if the last line is well-formed
    assert len(lines) == 1, out.stdout[-2000:]
    rec = json.loads(lines[-1])
    for field in ("metric", "value", "unit", "vs_baseline", "mfu",
                  "dispatch_overhead_ms", "relay_degraded", "ledger_id",
                  "compile_cache"):
        assert field in rec, rec
    # warm-start telemetry block, well-formed whatever the knob state
    assert set(rec["compile_cache"]) == {"enabled", "dir", "hits",
                                         "misses", "warm_age_s"}
    assert rec["unit"] == "tokens/s"
    assert rec["value"] > 0, rec
    assert "error" not in rec, rec
    assert rec["relay_degraded"] is False, rec
    # the invocation landed in the run ledger, and the printed line
    # points at exactly that record
    sys.path.insert(0, REPO)
    from apex_tpu.telemetry import ledger as tledger

    records = tledger.read_ledger(ledger_path)
    assert rec["ledger_id"] in {r["id"] for r in records}, records
    for r in records:
        assert tledger.validate_record(r) == [], r
    # the in-step metrics (APEX_TELEMETRY=1) reached the JSONL sink
    from apex_tpu.telemetry import read_metrics

    rows = read_metrics(str(tmp_path / "metrics.jsonl"))
    step_rows = [r for r in rows if "loss_scale" in r]
    assert len(step_rows) >= 3, rows  # smoke runs a 3-iteration scan
    assert all(r.get("run") == rec["ledger_id"] for r in step_rows)


def _fake_rec(value, b16):
    return {"metric": "gpt2s_train_tokens_per_sec (tpu)", "value": value,
            "unit": "tokens/s", "vs_baseline": 1.0, "mfu": 0.4,
            "config": {"batch": 16 if b16 else 8, "fused_lm_head": False}}


def test_ladder_attempt_one_is_default_config(monkeypatch):
    """Attempt 1 is ALWAYS the plain measured-default config — a one-run
    relay window must yield the clean headline, with A/Bs riding the later
    attempts (VERDICT r4 #7). Pinned directly on _config_ladder so a
    ladder reorder cannot slip past the behavioral tests below."""
    sys.path.insert(0, REPO)
    import bench

    for k in ("APEX_FUSED_LM_HEAD", "APEX_ATTN_IMPL", "APEX_LN_PALLAS",
              "APEX_BENCH_BATCH", "APEX_BENCH_SMOKE"):
        monkeypatch.delenv(k, raising=False)
    for attempts in (1, 2, 3, 5):
        ladder = bench._config_ladder(attempts, smoke=False)
        assert len(ladder) == attempts
        assert ladder[0] == {}, (
            f"attempt 1 must be the default config, got {ladder[0]}")


def test_watchdog_single_healthy_attempt_is_clean_headline(monkeypatch,
                                                           capsys):
    """A window exactly one attempt long (APEX_BENCH_ATTEMPTS=1) with a
    healthy default-config measurement prints that line as the headline —
    valid JSON, no 'note'/'error', default config, rc 0."""
    sys.path.insert(0, REPO)
    import bench

    calls = []

    def fake_attempt(state, extra_env=None, **kw):
        calls.append(dict(extra_env or {}))
        rec = _fake_rec(100.0, False)
        return json.dumps(rec), rec, 0

    monkeypatch.setattr(bench, "_attempt_once", fake_attempt)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setenv("APEX_BENCH_ATTEMPTS", "1")
    monkeypatch.delenv("APEX_BENCH_SMOKE", raising=False)
    for k in ("APEX_FUSED_LM_HEAD", "APEX_ATTN_IMPL", "APEX_LN_PALLAS",
              "APEX_REMAT", "APEX_BENCH_BATCH"):
        monkeypatch.delenv(k, raising=False)
    rc = bench._watchdog()
    out = [l for l in capsys.readouterr().out.splitlines()
           if l.startswith("{")]
    assert rc == 0
    assert calls == [{}]  # the one attempt ran the default config
    assert len(out) == 1
    rec = json.loads(out[0])
    assert rec["value"] == 100.0
    assert "note" not in rec and "error" not in rec
    assert rec["config"]["batch"] == 8


def test_watchdog_config_ladder(monkeypatch, capsys):
    """The retry ladder A/Bs the b=16 amortization config: both configs
    get a healthy attempt, the higher-throughput line wins, exactly one
    JSON line is printed."""
    sys.path.insert(0, REPO)
    import bench

    calls = []

    def fake_attempt(state, extra_env=None, **kw):
        b16 = (extra_env or {}).get("APEX_BENCH_BATCH") == "16"
        calls.append(b16)
        rec = _fake_rec(120.0 if b16 else 100.0, b16)
        return json.dumps(rec), rec, 0

    monkeypatch.setattr(bench, "_attempt_once", fake_attempt)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setenv("APEX_BENCH_ATTEMPTS", "3")
    monkeypatch.delenv("APEX_BENCH_SMOKE", raising=False)
    for k in ("APEX_FUSED_LM_HEAD", "APEX_ATTN_IMPL", "APEX_LN_PALLAS",
              "APEX_REMAT", "APEX_BENCH_BATCH"):
        monkeypatch.delenv(k, raising=False)
    rc = bench._watchdog()
    out = [l for l in capsys.readouterr().out.splitlines()
           if l.startswith("{")]
    assert rc == 0
    assert calls == [False, True]  # both configs, then early stop
    assert len(out) == 1
    rec = json.loads(out[0])
    assert rec["value"] == 120.0 and rec["config"]["batch"] == 16


def test_watchdog_ladder_retries_unhealthy_config(monkeypatch, capsys):
    """A degraded base attempt gets retried on the flap-retry slot after
    the b=16 attempt lands healthy; an explicit knob pin disables the
    ladder entirely."""
    sys.path.insert(0, REPO)
    import bench

    calls = []

    def fake_attempt(state, extra_env=None, **kw):
        b16 = (extra_env or {}).get("APEX_BENCH_BATCH") == "16"
        calls.append(b16)
        if len(calls) == 1:
            rec = dict(_fake_rec(5.0, b16), note="relay degraded",
                       degraded_kind="relay")
        else:
            rec = _fake_rec(120.0 if b16 else 100.0, b16)
        return json.dumps(rec), rec, 0

    monkeypatch.setattr(bench, "_attempt_once", fake_attempt)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setenv("APEX_BENCH_ATTEMPTS", "3")
    monkeypatch.delenv("APEX_BENCH_SMOKE", raising=False)
    for k in ("APEX_FUSED_LM_HEAD", "APEX_ATTN_IMPL", "APEX_LN_PALLAS",
              "APEX_REMAT", "APEX_BENCH_BATCH"):
        monkeypatch.delenv(k, raising=False)
    rc = bench._watchdog()
    out = [l for l in capsys.readouterr().out.splitlines()
           if l.startswith("{")]
    assert rc == 0
    assert calls == [False, True, False]  # degraded b=8 base retried last
    assert json.loads(out[0])["value"] == 120.0

    # explicit pin: the ladder collapses to the caller's env verbatim
    calls.clear()
    monkeypatch.setenv("APEX_FUSED_LM_HEAD", "1")

    def fake_pinned(state, extra_env=None, **kw):
        merged = dict(os.environ, **(extra_env or {}))
        fused = merged.get("APEX_FUSED_LM_HEAD") == "1"
        calls.append(fused)
        # the pin is a fused-head pin, not a batch pin: the fabricated
        # record keeps the default batch
        rec = dict(_fake_rec(120.0, False))
        rec["config"]["fused_lm_head"] = fused
        return json.dumps(rec), rec, 0

    monkeypatch.setattr(bench, "_attempt_once", fake_pinned)
    rc = bench._watchdog()
    capsys.readouterr()
    assert rc == 0
    assert calls == [True]  # pinned config, healthy first attempt, done


def test_watchdog_ladder_retries_degraded_b16_config(monkeypatch, capsys):
    """The spare attempt goes to whichever config lacks a healthy line —
    including one whose original slot already ran (b=16 degraded on
    attempt 2 gets attempt 3)."""
    sys.path.insert(0, REPO)
    import bench

    calls = []

    def fake_attempt(state, extra_env=None, **kw):
        b16 = (extra_env or {}).get("APEX_BENCH_BATCH") == "16"
        calls.append(b16)
        if len(calls) == 2:  # the b=16 slot flaps
            rec = dict(_fake_rec(5.0, b16), note="relay degraded",
                       degraded_kind="relay")
        else:
            rec = _fake_rec(130.0 if b16 else 100.0, b16)
        return json.dumps(rec), rec, 0

    monkeypatch.setattr(bench, "_attempt_once", fake_attempt)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setenv("APEX_BENCH_ATTEMPTS", "3")
    monkeypatch.delenv("APEX_BENCH_SMOKE", raising=False)
    for k in ("APEX_FUSED_LM_HEAD", "APEX_ATTN_IMPL", "APEX_LN_PALLAS",
              "APEX_REMAT", "APEX_BENCH_BATCH"):
        monkeypatch.delenv(k, raising=False)
    rc = bench._watchdog()
    out = [l for l in capsys.readouterr().out.splitlines()
           if l.startswith("{")]
    assert rc == 0
    assert calls == [False, True, True]  # b=16 retried on the spare slot
    assert json.loads(out[0])["value"] == 130.0


def test_watchdog_without_a_chip_prints_nothing(monkeypatch, capsys):
    """A child that finds no TPU (NO_CHIP_RC, no JSON) ends the run at
    once: no retry, no ladder, no line on stdout, that exit code — a
    box without a chip gets no throughput under the metric's name."""
    sys.path.insert(0, REPO)
    import bench

    calls = []

    def fake_attempt(state, extra_env=None, **kw):
        calls.append(extra_env)
        return None, None, bench.NO_CHIP_RC

    monkeypatch.setattr(bench, "_attempt_once", fake_attempt)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setenv("APEX_BENCH_ATTEMPTS", "3")
    monkeypatch.delenv("APEX_BENCH_SMOKE", raising=False)
    for k in ("APEX_FUSED_LM_HEAD", "APEX_ATTN_IMPL", "APEX_LN_PALLAS",
              "APEX_REMAT", "APEX_BENCH_BATCH"):
        monkeypatch.delenv(k, raising=False)
    rc = bench._watchdog()
    assert rc == bench.NO_CHIP_RC != 0
    assert len(calls) == 1
    assert capsys.readouterr().out.strip() == ""


def test_watchdog_cpu_line_is_never_the_headline(monkeypatch, capsys):
    """A child line that claims the CPU outside --smoke is not the
    requested backend: it can only be the error-path fallback, and the
    run fails."""
    sys.path.insert(0, REPO)
    import bench

    def fake_attempt(state, extra_env=None, **kw):
        rec = dict(_fake_rec(90.0, False),
                   metric="gpt2s_train_tokens_per_sec (cpu)")
        return json.dumps(rec), rec, 0

    monkeypatch.setattr(bench, "_attempt_once", fake_attempt)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setenv("APEX_BENCH_ATTEMPTS", "1")
    monkeypatch.delenv("APEX_BENCH_SMOKE", raising=False)
    assert bench._watchdog() != 0


def test_watchdog_lazy_cap_after_timeout(monkeypatch, capsys):
    """A first attempt that rides its entire budget without a JSON line
    (rc None + fabricated timed_out record — the wedge signature) arms a
    900s cap for the remaining attempts; completed attempts (healthy or
    degraded, any length) never arm it."""
    sys.path.insert(0, REPO)
    import bench

    caps = []

    def fake_timeout_attempt(state, extra_env=None, timeout_cap=None, **kw):
        caps.append(timeout_cap)
        rec = {"metric": "gpt2s_train_tokens_per_sec (tpu)", "value": 0,
               "unit": "tokens/s", "vs_baseline": 0, "mfu": None,
               "timed_out": True, "relay_degraded": True,
               "error": "bench timed out after 1800s"}
        return json.dumps(rec), rec, None   # rc None = timeout path

    monkeypatch.setattr(bench, "_attempt_once", fake_timeout_attempt)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setenv("APEX_BENCH_ATTEMPTS", "3")
    monkeypatch.delenv("APEX_BENCH_SMOKE", raising=False)
    for k in ("APEX_FUSED_LM_HEAD", "APEX_ATTN_IMPL", "APEX_LN_PALLAS",
              "APEX_REMAT", "APEX_BENCH_BATCH"):
        monkeypatch.delenv(k, raising=False)
    rc = bench._watchdog()
    capsys.readouterr()
    assert rc == 1  # error line only: no real measurement
    assert caps == [None, 900, 900]

    # a COMPLETED degraded attempt (rc 0) must not arm the cap
    caps.clear()

    def fake_degraded_attempt(state, extra_env=None, timeout_cap=None, **kw):
        caps.append(timeout_cap)
        rec = dict(_fake_rec(5.0, False), note="relay degraded",
                   degraded_kind="relay")
        return json.dumps(rec), rec, 0

    monkeypatch.setattr(bench, "_attempt_once", fake_degraded_attempt)
    rc = bench._watchdog()
    capsys.readouterr()
    assert rc == 0
    assert caps == [None, None, None]


def test_watchdog_real_error_record_does_not_arm_cap(monkeypatch, capsys):
    """A REAL error record forwarded after a teardown wedge (rc None,
    no timed_out stamp — e.g. the calibration-flap line printed before
    the child wedged) must NOT arm the lazy cap: the attempt completed
    its measurement; only riding the whole budget with no JSON line is
    wedge evidence (ADVICE r5 on the old any-rc-None-error condition)."""
    sys.path.insert(0, REPO)
    import bench

    caps = []

    def fake_teardown_wedge(state, extra_env=None, timeout_cap=None, **kw):
        caps.append(timeout_cap)
        rec = {"metric": "gpt2s_train_tokens_per_sec (tpu)", "value": 0,
               "unit": "tokens/s", "vs_baseline": 0, "mfu": None,
               "error": "non-positive step time after overhead "
                        "subtraction (relay flap straddled the "
                        "calibration); measurement unusable"}
        # rc None: the child printed the record, then wedged in teardown
        return json.dumps(rec), rec, None

    monkeypatch.setattr(bench, "_attempt_once", fake_teardown_wedge)
    monkeypatch.setattr(bench.time, "sleep", lambda s: None)
    monkeypatch.setenv("APEX_BENCH_ATTEMPTS", "3")
    monkeypatch.delenv("APEX_BENCH_SMOKE", raising=False)
    for k in ("APEX_FUSED_LM_HEAD", "APEX_ATTN_IMPL", "APEX_LN_PALLAS",
              "APEX_REMAT", "APEX_BENCH_BATCH"):
        monkeypatch.delenv(k, raising=False)
    rc = bench._watchdog()
    capsys.readouterr()
    assert rc == 1  # error line only: no real measurement
    assert caps == [None, None, None]
