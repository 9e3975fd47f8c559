"""Flight recorder + heartbeat supervisor (ISSUE 16).

Three layers, mirroring the subsystem:

* unit — `apex_tpu.telemetry.flight` (disabled no-op, beat fields,
  stream merge, torn-line tolerance, status line),
  `resilience.classify_inflight` verdicts, the `flight_reap` ledger
  validator's teeth, and the supervisor's pool-restore / threshold
  helpers;
* supervisor — `apex_tpu.resilience.flight_watch` run in-process over
  tiny stdlib children: a heartbeat-silent child is reaped at the
  silence threshold (way under its cap, classified record banked), a
  slow-but-beating child is never reaped early, a beat-free child
  keeps pre-PR full-cap semantics;
* e2e chaos — bench.py under the real supervisor with the scripted
  `flight_silent` wedge (reaped early, emergency partial banked, row
  stays owed) and the `heartbeat`-hang slow twin (completes, no reap),
  riding the session smoke compile cache; plus the jaxpr-identity
  assertion for the disabled mode (the zero-cost contract).

window_report's flight-primary attribution is tested here too; the
round-5 golden (fallback path unchanged) stays in
tests/test_window_report.py.
"""

import contextlib
import io
import json
import os
import signal
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu import resilience  # noqa: E402
from apex_tpu.resilience import flight_watch  # noqa: E402
from apex_tpu.telemetry import flight  # noqa: E402
from apex_tpu.telemetry import ledger as tledger  # noqa: E402

BENCH = os.path.join(REPO, "bench.py")
PROBE_SH = os.path.join(REPO, "benchmarks", "probe_and_collect.sh")
RUN_ALL_SH = os.path.join(REPO, "benchmarks", "run_all_tpu.sh")


@pytest.fixture(autouse=True)
def _clean_flight_env(monkeypatch):
    """Every test starts with the recorder disarmed and no stale
    supervisor knobs — the disabled default IS the contract."""
    for k in ("APEX_FLIGHT_DIR", "APEX_FLIGHT_ROW", "APEX_FLIGHT_SILENCE",
              "APEX_FLIGHT_GRACE",
              "APEX_BENCH_ATTEMPT", "APEX_FAULT_PLAN"):
        monkeypatch.delenv(k, raising=False)


# ----------------------------------------------------- recorder unit


def test_disabled_is_noop(monkeypatch, tmp_path):
    assert not flight.enabled() and flight.flight_dir() is None
    assert flight.beat("proc_start") is None
    assert flight.newest_beat() is None
    assert flight.status_line() == "flight: disabled (APEX_FLIGHT_DIR unset)"
    assert list(tmp_path.iterdir()) == []  # nothing written anywhere


def test_phase_vocabulary_is_pinned():
    """window_report's attribution pairs and the supervisor's wedge
    signature are keyed on these exact names."""
    assert flight.PHASES == (
        "proc_start", "backend_init", "compile_start", "compile_done",
        "dispatch", "fetch", "attempt_start", "attempt_done", "flush")


def test_beat_fields_env_defaults_and_overrides(monkeypatch, tmp_path):
    monkeypatch.setenv("APEX_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("APEX_FLIGHT_ROW", "gpt_rows")
    monkeypatch.setenv("APEX_BENCH_ATTEMPT", "2")
    rec = flight.beat("dispatch", batch=8)
    assert rec["phase"] == "dispatch" and rec["pid"] == os.getpid()
    assert isinstance(rec["ts"], float) and isinstance(rec["mono"], float)
    assert rec["label"] == "gpt_rows" and rec["attempt"] == 2
    assert rec["batch"] == 8
    # explicit args beat the env defaults
    rec2 = flight.beat("fetch", label="xent", attempt=5)
    assert rec2["label"] == "xent" and rec2["attempt"] == 5
    # a malformed attempt env NEVER raises — the beat still lands
    monkeypatch.setenv("APEX_BENCH_ATTEMPT", "bogus")
    rec3 = flight.beat("flush")
    assert rec3 is not None and "attempt" not in rec3
    beats = flight.read_beats(str(tmp_path))
    assert [b["phase"] for b in beats] == ["dispatch", "fetch", "flush"]
    assert all(b["pid"] == os.getpid() for b in beats)


def test_unwritable_dir_degrades_to_missing_beat(monkeypatch, tmp_path):
    """The recorder must not be able to kill the flight it records."""
    blocker = tmp_path / "not-a-dir"
    blocker.write_text("file where the dir should go")
    monkeypatch.setenv("APEX_FLIGHT_DIR", str(blocker))
    assert flight.beat("dispatch") is None  # degraded, not raised


def test_read_beats_merges_sorts_and_skips_torn_lines(tmp_path):
    a = tmp_path / "flight-11.jsonl"
    a.write_text(
        json.dumps({"mono": 5.0, "phase": "fetch", "pid": 11}) + "\n"
        + '{"mono": 9.0, "phase": "tr')  # torn final line (reaped writer)
    b = tmp_path / "flight-22.jsonl"
    b.write_text(
        json.dumps({"mono": 1.0, "phase": "proc_start", "pid": 22}) + "\n"
        + json.dumps({"mono": "?", "phase": "noclock"}) + "\n")
    (tmp_path / "other.log").write_text("not a flight stream\n")
    beats = flight.read_beats(str(tmp_path))
    # non-numeric mono sorts first (-inf), numeric ascending; torn line
    # and the non-flight file are invisible
    assert [x.get("phase") for x in beats] == ["noclock", "proc_start",
                                               "fetch"]
    assert flight.newest_beat(str(tmp_path))["phase"] == "fetch"


def test_status_line_and_cli(monkeypatch, tmp_path, capsys):
    d = str(tmp_path / "fl")
    assert flight.status_line(d) == f"flight: no heartbeats under {d}"
    monkeypatch.setenv("APEX_FLIGHT_DIR", d)
    flight.beat("compile_start", label="bench_first", attempt=1)
    line = flight.status_line(d)
    assert line.startswith("flight: compile_start (")
    assert "row=bench_first" in line and "attempt=1" in line
    assert flight.main(["status", "--dir", d]) == 0
    assert "flight: compile_start" in capsys.readouterr().out


def test_ledger_status_rides_the_heartbeat_line(monkeypatch, tmp_path,
                                                capsys):
    """`python -m apex_tpu.telemetry.ledger status` answers "is anything
    alive RIGHT NOW" when a flight dir is armed."""
    d = str(tmp_path / "fl")
    lp = str(tmp_path / "ledger.jsonl")
    monkeypatch.setenv("APEX_TELEMETRY_LEDGER", lp)
    tledger.append_record("bench", "cpu", 0.5, 2, path=lp)
    monkeypatch.setenv("APEX_FLIGHT_DIR", d)
    flight.beat("dispatch", label="bench")
    assert tledger.main(["--ledger", lp, "status"]) == 0
    out = capsys.readouterr().out
    assert "flight: dispatch" in out and "row=bench" in out


def test_heartbeat_fault_slows_but_never_silences(monkeypatch, tmp_path):
    """The chaos hook fires AFTER the beat lands: a scripted per-beat
    hang stretches wall time while beats keep arriving — the
    slow-but-beating shape the supervisor must not reap."""
    monkeypatch.setenv("APEX_FLIGHT_DIR", str(tmp_path))
    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps(
        [{"site": "heartbeat", "kind": "hang", "seconds": 0.5}]))
    t0 = time.perf_counter()
    rec = flight.beat("dispatch")
    assert time.perf_counter() - t0 >= 0.5
    assert rec is not None
    assert [b["phase"] for b in flight.read_beats(str(tmp_path))] \
        == ["dispatch"]


# ----------------------------------------- in-flight classification


def test_classify_inflight_verdicts():
    ci = resilience.classify_inflight
    now = 1000.0
    # no beats / no numeric mono stamps: nothing proves life = silent
    assert ci([], now) == resilience.SILENT
    assert ci([{"mono": "x"}, {"mono": True}], now) == resilience.SILENT
    # §6 defaults: advancing under FLIGHT_ADVANCE_S, silent at
    # FLIGHT_SILENCE_S, slow in between
    assert ci([{"mono": now - 10}], now) == resilience.ADVANCING
    assert ci([{"mono": now - resilience.FLIGHT_ADVANCE_S - 40}], now) \
        == resilience.SLOW
    assert ci([{"mono": now - resilience.FLIGHT_SILENCE_S}], now) \
        == resilience.SILENT
    # overrides: chaos tests pin seconds-scale thresholds
    assert ci([{"mono": now - 2}], now, silence_s=1.0) == resilience.SILENT
    assert ci([{"mono": now - 0.5}], now, advance_s=0.2) == resilience.SLOW
    # the newest stamp decides, wherever it sits in the list
    assert ci([{"mono": now - 500}, {"mono": now - 1}], now) \
        == resilience.ADVANCING


def test_inflight_verdict_vocabulary():
    assert resilience.INFLIGHT_VERDICTS == (
        resilience.ADVANCING, resilience.SLOW, resilience.SILENT)
    assert 143 in resilience.TIMEOUT_RCS  # the supervisor's reap rc


# ------------------------------------------- flight_reap validation


def _reap_block(**over):
    block = {"row": "bench_first", "verdict": resilience.SILENT,
             "reason": "silence", "silence_s": 300.0, "timeout_s": 1500.0,
             "elapsed_s": 420.0, "beats": 7, "age_s": 310.2,
             "last_phase": "compile_start"}
    block.update(over)
    return block


def _reap_rec(**over):
    return tledger.make_record(
        "flight_reap", "shell", None, None, git="abc", ts=1.0,
        extra={"flight_reap": _reap_block(**over)})


def test_flight_reap_record_validates_clean():
    assert tledger.validate_record(_reap_rec()) == []
    # null age/last_phase = a beat-free child reaped at cap: legal
    assert tledger.validate_record(
        _reap_rec(reason="cap", beats=0, age_s=None,
                  last_phase=None)) == []


def test_flight_reap_validator_teeth():
    """Each malformed field is a named finding — a record that claims
    the wrong reap story must not pass the ledger gate
    (check_bench_labels runs validate_record over every record)."""
    cases = [
        (dict(verdict="speedy"), "flight_reap.verdict"),
        (dict(reason="boredom"), "flight_reap.reason"),
        (dict(row=""), "flight_reap.row"),
        (dict(elapsed_s=-1), "flight_reap.elapsed_s"),
        (dict(silence_s=None), "flight_reap.silence_s"),
        (dict(timeout_s=True), "flight_reap.timeout_s"),
        (dict(beats="7"), "flight_reap.beats"),
        (dict(age_s=-2.0), "flight_reap.age_s"),
        (dict(last_phase=3), "flight_reap.last_phase"),
    ]
    for over, needle in cases:
        problems = tledger.validate_record(_reap_rec(**over))
        assert any(needle in p for p in problems), (over, problems)
    rec = tledger.make_record("flight_reap", "shell", None, None,
                              git="abc", ts=1.0,
                              extra={"flight_reap": "reaped"})
    assert any("not a dict" in p for p in tledger.validate_record(rec))


# --------------------------------------------- supervisor unit layer


def test_threshold_precedence():
    th = flight_watch._threshold
    assert th(2.0, "5", 300) == 2.0       # CLI wins
    assert th(None, "5", 300) == 5.0      # then the raw env value
    assert th(None, "bogus", 300) == 300.0  # unparseable -> constant
    assert th(None, None, 300) == 300.0
    assert th(0.0, "5", 300) == 0.0       # zero is a LEGAL threshold
    assert th(None, "0.25", 300) == 0.25  # fractional seconds too


def test_child_env_carries_flight_dir_and_row(tmp_path):
    env = flight_watch._child_env(str(tmp_path), "bench_first")
    assert env["APEX_FLIGHT_DIR"] == str(tmp_path)
    assert env["APEX_FLIGHT_ROW"] == "bench_first"
    env = flight_watch._child_env(None, None)
    assert "APEX_FLIGHT_DIR" not in env and "APEX_FLIGHT_ROW" not in env


# ------------------------------------------ supervisor over children
# (tiny stdlib children; seconds-scale thresholds keep these fast)

_SILENT_CHILD = """\
import json, os, time
d = os.environ["APEX_FLIGHT_DIR"]
os.makedirs(d, exist_ok=True)
with open(os.path.join(d, "flight-%d.jsonl" % os.getpid()), "a") as f:
    f.write(json.dumps({"ts": time.time(), "mono": time.monotonic(),
                        "phase": "compile_start",
                        "pid": os.getpid()}) + "\\n")
time.sleep(600)
"""

_BEATING_CHILD = """\
import json, os, time
d = os.environ["APEX_FLIGHT_DIR"]
os.makedirs(d, exist_ok=True)
p = os.path.join(d, "flight-%d.jsonl" % os.getpid())
for i in range(8):
    with open(p, "a") as f:
        f.write(json.dumps({"ts": time.time(), "mono": time.monotonic(),
                            "phase": "dispatch",
                            "pid": os.getpid()}) + "\\n")
    time.sleep(0.4)
"""


@contextlib.contextmanager
def _restored_signals():
    """flight_watch.main installs SIGTERM/SIGINT handlers; the pytest
    process must get its own back."""
    old = {s: signal.getsignal(s) for s in (signal.SIGTERM, signal.SIGINT)}
    try:
        yield
    finally:
        for s, h in old.items():
            signal.signal(s, h)


def _supervise(tmp_path, monkeypatch, child_src, timeout, silence,
               row="row_under_test", grace="5"):
    monkeypatch.setenv("APEX_TELEMETRY_LEDGER",
                       str(tmp_path / "ledger.jsonl"))
    fdir = str(tmp_path / "flight")
    t0 = time.perf_counter()
    with _restored_signals():
        rc = flight_watch.main(
            ["--timeout", str(timeout), "--row", row, "--flight-dir", fdir,
             "--silence", str(silence), "--grace", grace, "--",
             sys.executable, "-c", child_src])
    wall = time.perf_counter() - t0
    path = tmp_path / "ledger.jsonl"
    records = tledger.read_ledger(str(path)) if path.exists() else []
    return rc, wall, [r for r in records
                      if r.get("harness") == "flight_reap"]


def test_silent_child_reaped_at_silence_threshold(tmp_path, monkeypatch):
    """One beat, then the stream stops: reaped at ~silence_s, nowhere
    near the 120 s cap, with a classified + validated flight_reap
    record banked and the TIMEOUT_RCS exit that keeps the row owed."""
    rc, wall, reaps = _supervise(tmp_path, monkeypatch, _SILENT_CHILD,
                                 timeout=120, silence=1.5,
                                 row="wedge_row")
    assert rc == 143 and rc in resilience.TIMEOUT_RCS
    assert wall < 30, f"reap took {wall:.1f}s — not an early reap"
    assert len(reaps) == 1
    fr = reaps[0]["flight_reap"]
    assert fr["row"] == "wedge_row" and fr["reason"] == "silence"
    assert fr["verdict"] == resilience.SILENT
    assert fr["beats"] >= 1 and fr["last_phase"] == "compile_start"
    assert fr["age_s"] >= 1.5 and fr["timeout_s"] == 120.0
    assert tledger.validate_record(reaps[0]) == []


def test_slow_beating_child_is_never_reaped_early(tmp_path, monkeypatch):
    """Beats arriving under the silence threshold keep the run alive to
    its own exit — a degraded-relay crawl is supervised, not killed."""
    rc, wall, reaps = _supervise(tmp_path, monkeypatch, _BEATING_CHILD,
                                 timeout=60, silence=1.5, row="slow_row")
    assert rc == 0 and reaps == []
    assert wall >= 2.5  # it genuinely ran its slow course


def test_beat_free_child_keeps_the_full_cap(tmp_path, monkeypatch):
    """No beats ever: pre-PR semantics. Only a stream that STOPPED
    proves instrumentation was there to go quiet — an uninstrumented
    child is reaped at its cap (reason=cap), never at the silence
    threshold."""
    rc, wall, reaps = _supervise(tmp_path, monkeypatch,
                                 "import time; time.sleep(600)",
                                 timeout=2, silence=0.5, row="bare_row")
    assert rc == 143
    assert wall >= 2, "a beat-free child must keep its full cap"
    assert len(reaps) == 1
    fr = reaps[0]["flight_reap"]
    assert fr["reason"] == "cap" and fr["beats"] == 0
    assert fr["age_s"] is None and fr["last_phase"] is None
    assert tledger.validate_record(reaps[0]) == []


def test_unlaunchable_command_is_127(tmp_path, monkeypatch):
    monkeypatch.setenv("APEX_TELEMETRY_LEDGER",
                       str(tmp_path / "ledger.jsonl"))
    with _restored_signals():
        rc = flight_watch.main(
            ["--timeout", "5", "--flight-dir", str(tmp_path / "fl"),
             "--", "/nonexistent-cmd-apex-flight-test"])
    assert rc == 127


def test_shell_wiring_for_flight_surfaces():
    """run_all_tpu.sh rungs go through the supervisor; the --status
    surface prints the newest heartbeat (bash -n sits in
    tests/test_resilience.py)."""
    run_all = open(RUN_ALL_SH).read()
    assert "apex_tpu.resilience.flight_watch" in run_all
    assert "--flight-dir" in run_all
    probe = open(PROBE_SH).read()
    assert "apex_tpu.telemetry.flight status" in probe
    assert "APEX_FLIGHT_DIR" in probe


# ------------------------------------- window_report flight primary


def _wr():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "window_report_flight", os.path.join(REPO, "tools",
                                             "window_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_window_report_flight_primary_attribution(tmp_path):
    """Exact minute attribution from mono deltas (compile_start ->
    compile_done, dispatch -> fetch) plus the reap account's
    reclaimed minutes."""
    wr = _wr()
    d = tmp_path / "flight"
    d.mkdir()
    base = 1754000000.0
    beats = [
        {"ts": base + m, "mono": m, "phase": ph, "pid": 11,
         "label": "bench_first"}
        for m, ph in ((10, "proc_start"), (20, "compile_start"),
                      (80, "compile_done"), (90, "dispatch"),
                      (120, "fetch"), (121, "flush"))]
    (d / "flight-11.jsonl").write_text(
        "".join(json.dumps(b) + "\n" for b in beats))
    lp = str(tmp_path / "ledger.jsonl")
    tledger.append_record(
        "flight_reap", "shell", None, None, path=lp,
        extra={"flight_reap": _reap_block(
            row="gpt_rows", timeout_s=600.0, elapsed_s=30.0,
            silence_s=20.0, beats=4, age_s=21.0,
            last_phase="compile_done")})
    rep = wr.build_report(ledger_path=lp, flight_dir=str(d))
    fl = rep["flight"]
    (proc,) = fl["processes"]
    assert proc["label"] == "bench_first" and proc["pid"] == 11
    assert proc["compile_minutes"] == 1.0    # 60 s compile
    assert proc["measure_minutes"] == 0.5    # 30 s dispatch->fetch
    assert proc["last_phase"] == "flush" and not proc["compile_open"]
    assert fl["by_label"]["bench_first"]["compile_minutes"] == 1.0
    (reap,) = fl["reaps"]
    assert reap["row"] == "gpt_rows"
    assert reap["reclaimed_minutes"] == 9.5  # (600-30)/60
    assert fl["reclaimed_minutes"] == 9.5
    buf = io.StringIO()
    wr.print_report(rep, out=buf)
    text = buf.getvalue()
    assert "primary timeline" in text
    assert "reclaimed 9.5 min" in text and "gpt_rows" in text


def test_window_report_watch_is_bounded(tmp_path, capsys, monkeypatch):
    wr = _wr()
    d = tmp_path / "flight"
    monkeypatch.setenv("APEX_FLIGHT_DIR", str(d))
    flight.beat("dispatch", label="bench_first")
    rc = wr.main(["--flight", str(d), "--watch", "--iterations", "1",
                  "--interval", "0.01"])
    assert rc == 0
    out = capsys.readouterr().out
    assert "flight: dispatch" in out


# --------------------------------------------------- bench e2e chaos
# (real CPU smoke runs; shared suite smoke compile cache)


@pytest.fixture
def chaos_cache_dir(shared_smoke_cache_dir):
    return shared_smoke_cache_dir


def _bench_under_watch(tmp_path, chaos_cache_dir, plan, silence,
                       timeout=600):
    env = dict(os.environ)
    for k in ("APEX_WARM_ONLY", "APEX_CKPT_RESUME", "APEX_FLIGHT_DIR",
              "APEX_FLIGHT_ROW", "APEX_BENCH_ATTEMPT"):
        env.pop(k, None)
    env.update(
        JAX_PLATFORMS="cpu",
        APEX_BENCH_SMOKE="1", APEX_BENCH_INNER="1",
        JAX_COMPILATION_CACHE_DIR=chaos_cache_dir,
        APEX_CKPT_DIR=str(tmp_path / "ckpt"),
        APEX_TELEMETRY_LEDGER=str(tmp_path / "ledger.jsonl"),
        APEX_BENCH_BASELINE=str(tmp_path / "baseline.json"),
        APEX_FAULT_PLAN=json.dumps(plan))
    fdir = str(tmp_path / "flight")
    t0 = time.perf_counter()
    out = subprocess.run(
        [sys.executable, "-m", "apex_tpu.resilience.flight_watch",
         "--timeout", str(timeout), "--row", "bench_first",
         "--flight-dir", fdir, "--silence", str(silence), "--grace", "20",
         "--", sys.executable, BENCH],
        capture_output=True, text=True, timeout=300, env=env, cwd=REPO)
    wall = time.perf_counter() - t0
    path = tmp_path / "ledger.jsonl"
    records = tledger.read_ledger(str(path)) if path.exists() else []
    return out, wall, records, fdir


def test_chaos_flight_silent_wedge_reaped_early_partial_banked(
        tmp_path, chaos_cache_dir):
    """The round-5 gpt_rows shape, end-to-end: beats flowed
    (proc_start..compile_done), then the process went quiet with the
    scan-boundary partial already committed. The supervisor reaps at
    the silence threshold — way under the 600 s cap — the SIGTERM
    grace lets the emergency flush bank the partial, the classified
    flight_reap record is fault-stamped and valid, and exit 143 keeps
    the manifest row owed."""
    from apex_tpu import checkpoint as ckpt

    plan = [{"site": "flight_silent", "kind": "hang"}]
    out, wall, records, fdir = _bench_under_watch(
        tmp_path, chaos_cache_dir, plan, silence=20)
    assert out.returncode == 143, (out.stdout, out.stderr[-2000:])
    assert out.returncode in resilience.TIMEOUT_RCS  # row stays owed
    assert wall < 240, f"{wall:.0f}s — the 600s slot was burnt, not saved"
    # the heartbeat stream shows the flight up to the wedge
    phases = [b["phase"] for b in flight.read_beats(fdir)]
    assert "proc_start" in phases and "compile_done" in phases
    assert "fetch" not in phases  # it never reached the timed region
    # the emergency flush banked the scan-boundary partial (step 3 in
    # smoke: step0 + iters)
    assert "emergency checkpoint committed" in out.stderr
    steps = ckpt.durable_steps(str(tmp_path / "ckpt"))
    assert steps and steps[-1] == 3
    # the classified, fault-stamped, schema-valid reap record
    reaps = [r for r in records if r.get("harness") == "flight_reap"]
    assert len(reaps) == 1, out.stderr[-2000:]
    fr = reaps[0]["flight_reap"]
    assert fr["row"] == "bench_first" and fr["reason"] == "silence"
    assert fr["verdict"] == resilience.SILENT
    assert fr["last_phase"] == "compile_done" and fr["age_s"] >= 20
    assert reaps[0]["fault_plan"].startswith("fp-")
    assert tledger.validate_record(reaps[0]) == []


def test_chaos_slow_beating_bench_survives_to_completion(
        tmp_path, chaos_cache_dir):
    """The twin: every beat hangs 1 s (wall time stretches, beats keep
    arriving) — the supervisor must NOT reap before the cap; the run
    completes with its one JSON line and no reap record."""
    plan = [{"site": "heartbeat", "kind": "hang", "seconds": 1}]
    out, wall, records, fdir = _bench_under_watch(
        tmp_path, chaos_cache_dir, plan, silence=20)
    assert out.returncode == 0, (out.stdout, out.stderr[-2000:])
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec.get("metric", "").startswith("gpt2s_train_tokens_per_sec")
    assert [r for r in records if r.get("harness") == "flight_reap"] == []
    phases = [b["phase"] for b in flight.read_beats(fdir)]
    assert "flush" in phases  # the full flight landed


def test_flight_enabled_is_jaxpr_byte_identical(monkeypatch, tmp_path):
    """The zero-cost contract: beats are host-side file appends that
    never touch a traced program — tracing the bench training step with
    the recorder armed (beats emitted) yields a jaxpr byte-identical to
    the disabled trace."""
    import jax

    import bench
    from apex_tpu import telemetry
    from tests.test_telemetry import _bench_fixture

    (model, scaler, tx, params, opt_state, scaler_state,
     ids, pos, labels) = _bench_fixture()
    args = (params, opt_state, scaler_state, ids, pos, labels)

    telemetry.disable()
    monkeypatch.delenv("APEX_FLIGHT_DIR", raising=False)
    want = str(jax.make_jaxpr(bench.make_one_step(model, scaler, tx))(
        *args))

    monkeypatch.setenv("APEX_FLIGHT_DIR", str(tmp_path))
    assert flight.beat("compile_start") is not None  # recorder live
    got = str(jax.make_jaxpr(bench.make_one_step(model, scaler, tx))(
        *args))
    assert got == want, "an armed flight recorder changed the jaxpr"
