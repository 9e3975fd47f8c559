"""tools/check_bench_labels.py — the PERF.md-caption/ledger cross-check
runs in the tier-1 suite (like tools/check_api_parity.py) and passes on
the repo's own corrected PERF.md + seeded ledger; a seeded drift
fixture (the §10 "68–75 ms over an 82.6 ms log" class) must fail."""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu.telemetry import ledger
from tests.conftest import run_check_bench_labels

TOOL = os.path.join(REPO, "tools", "check_bench_labels.py")


# the checker runs IN-PROCESS (conftest.run_check_bench_labels — module
# loaded once): each of the ~20 invocations below used to be a fresh
# subprocess (~4s of python + apex_tpu import apiece — the fast tier's
# single biggest fixed cost); the CLI entry itself keeps one real
# subprocess test (test_repo_perf_and_ledger_are_clean_via_cli)
def _run(*args):
    if "--ledger" in args and "--table" not in args:
        # fixture ledgers can't resolve the COMMITTED dispatch table's
        # citations — point the table check at an empty file so these
        # tests exercise exactly the caption/ledger checks they seed
        args = (*args, "--table", os.devnull)
    return run_check_bench_labels(*args)


def _seed(tmp_path, overhead_ms=82.6):
    rec = ledger.make_record(
        harness="profile_attention", platform="tpu",
        dispatch_overhead_ms=overhead_ms, k=128,
        relay={"degraded": False, "kind": None}, knobs={}, git="abc",
        ts=1000.0)
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n")
    return rec, str(lpath)


def test_repo_perf_and_ledger_are_clean():
    """The tier-1 gate: the committed PERF.md + benchmarks/ledger.jsonl
    pass (the §10 caption now states the cited log's 82.6 ms)."""
    out = _run("--verbose")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


def test_repo_perf_and_ledger_are_clean_via_cli():
    """The same gate through the real CLI entry (the one subprocess
    invocation this file keeps — the in-process `_run` above covers the
    logic; this covers the script surface the driver calls)."""
    env = dict(os.environ)
    out = subprocess.run([sys.executable, TOOL, "--verbose"],
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "OK" in out.stdout


def test_seeded_drift_fixture_fails(tmp_path):
    rec, lpath = _seed(tmp_path)
    perf = tmp_path / "PERF.md"
    perf.write_text(
        "# fixture\n\nAttention rows (dispatch overhead 68–75 ms "
        f"subtracted; ledger:{rec['id']}):\n\n| a | b |\n")
    out = _run("--perf", str(perf), "--ledger", lpath)
    assert out.returncode == 1, out.stdout
    assert "label drift" in out.stdout


def test_matching_caption_passes(tmp_path):
    rec, lpath = _seed(tmp_path)
    perf = tmp_path / "PERF.md"
    perf.write_text(
        "# fixture\n\nAttention rows (dispatch overhead 82.6 ms "
        f"subtracted; ledger:{rec['id']}):\n")
    out = _run("--perf", str(perf), "--ledger", lpath)
    assert out.returncode == 0, out.stdout
    # a range caption passes only when it brackets the measured value
    perf.write_text(
        "# fixture\n\nrows (dispatch overhead 80–85 ms subtracted; "
        f"ledger:{rec['id']}):\n")
    assert _run("--perf", str(perf), "--ledger", lpath).returncode == 0


def test_ab_paragraph_with_two_citations_passes(tmp_path):
    """A comparison paragraph citing TWO records with different
    overheads is legitimate: each stated overhead must match at least
    one cited record, not all of them."""
    rec_a = ledger.make_record("profile_attention", "tpu", 68.3, 128,
                               git="abc", ts=1000.0, knobs={})
    rec_b = ledger.make_record("profile_attention", "tpu", 82.6, 128,
                               git="abc", ts=2000.0, knobs={})
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text("".join(json.dumps(r, sort_keys=True) + "\n"
                             for r in (rec_a, rec_b)))
    perf = tmp_path / "PERF.md"
    perf.write_text(
        "# fixture\n\npre-fix run (dispatch overhead 68.3 ms; "
        f"ledger:{rec_a['id']}) vs post-fix (dispatch overhead 82.6 ms; "
        f"ledger:{rec_b['id']}):\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 0, out.stdout
    # ...but an overhead NEITHER record measured still fails
    perf.write_text(
        f"# fixture\n\nrows (dispatch overhead 75.0 ms; "
        f"ledger:{rec_a['id']} ledger:{rec_b['id']}):\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 1 and "label drift" in out.stdout


def test_unresolved_citation_fails(tmp_path):
    _, lpath = _seed(tmp_path)
    perf = tmp_path / "PERF.md"
    perf.write_text("# fixture\n\nrows (ledger:lg-ffffffffff):\n")
    out = _run("--perf", str(perf), "--ledger", lpath)
    assert out.returncode == 1
    assert "no ledger record" in out.stdout


def test_tampered_record_fails(tmp_path):
    rec, _ = _seed(tmp_path)
    tampered = dict(rec, dispatch_overhead_ms=68.0)  # id now stale
    lpath = tmp_path / "tampered.jsonl"
    lpath.write_text(json.dumps(tampered, sort_keys=True) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text("# fixture\n\nno citations here\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 1
    assert "does not match record content" in out.stdout


def test_corrupt_ledger_fails(tmp_path):
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text("not json\n")
    perf = tmp_path / "PERF.md"
    perf.write_text("# fixture\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 1
    assert "unparseable" in out.stdout


def test_truncated_ledger_line_fails_with_line_number(tmp_path):
    """A line truncated mid-record (a SIGTERM/flap landing mid-append)
    must FAIL the tier-1 check naming file:lineno — never crash the
    checker with a raw JSONDecodeError traceback."""
    rec, _ = _seed(tmp_path)
    good = json.dumps(rec, sort_keys=True)
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(good + "\n" + good[:37] + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text("# fixture\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 1, out.stdout
    assert f"{lpath}:2:" in out.stdout, out.stdout
    assert "Traceback" not in out.stderr and "Traceback" not in out.stdout


def test_scalar_truncated_ledger_line_fails_not_crashes(tmp_path):
    """The nastier truncation: a line cut down to a bare JSON scalar
    still PARSES (`42`), and used to reach the validators as a non-dict
    and crash with an AttributeError — it must be a line-numbered
    finding instead."""
    rec, _ = _seed(tmp_path)
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n42\n")
    perf = tmp_path / "PERF.md"
    perf.write_text("# fixture\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 1, out.stdout + out.stderr
    assert f"{lpath}:2:" in out.stdout
    assert "not a JSON object" in out.stdout
    assert "Traceback" not in out.stderr and "Traceback" not in out.stdout


def test_fault_stamped_record_citation_is_drift(tmp_path, monkeypatch):
    """A PERF.md caption citing a record produced under APEX_FAULT_PLAN
    (chaos injection) is label drift: injected runs are not
    measurements."""
    monkeypatch.setenv(
        "APEX_FAULT_PLAN",
        json.dumps([{"site": "verdict", "kind": "degraded"}]))
    rec = ledger.make_record(
        harness="bench", platform="tpu", dispatch_overhead_ms=80.0,
        k=16, knobs={}, git="abc", ts=1000.0)
    monkeypatch.delenv("APEX_FAULT_PLAN")
    assert rec["fault_plan"].startswith("fp-")
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(f"# fixture\n\nrows (ledger:{rec['id']}):\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 1, out.stdout
    assert "FAULT-INJECTED" in out.stdout


# ------------------------------------------------ check 5: resume provenance

def _resumed_record(knobs, saved_pins, **extra):
    return ledger.make_record(
        harness="bench", platform="tpu", dispatch_overhead_ms=80.0,
        k=16, knobs=knobs, git="abc", ts=1000.0,
        extra=dict({"resumed_from": {"ckpt": "ck-0123456789ab"[:13],
                                     "step": 32, "pins": saved_pins}},
                   **extra))


def test_resumed_record_with_matching_pins_passes(tmp_path):
    """A resumed run whose measurement pins equal its checkpoint's is
    citable — resume provenance alone is not drift."""
    rec = _resumed_record({"APEX_REMAT": "selective"},
                          {"APEX_REMAT": "selective"})
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(f"# fixture\n\nresumed row (ledger:{rec['id']}):\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 0, out.stdout


def test_resumed_record_with_pin_drift_is_refused(tmp_path):
    """check 5: the restored run's knobs differ from the checkpoint's
    saved pins — the timing row mixes two configs under one label."""
    rec = _resumed_record({"APEX_REMAT": "none"},
                          {"APEX_REMAT": "selective"})
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(f"# fixture\n\nresumed row (ledger:{rec['id']}):\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 1, out.stdout
    assert "DIFFERENT measurement pins" in out.stdout
    assert "APEX_REMAT" in out.stdout


def test_infra_knob_difference_is_not_pin_drift(tmp_path):
    """Paths and arming switches (ledger.INFRA_KNOB_PREFIXES)
    legitimately differ between the saving and the resuming run — not
    drift."""
    rec = _resumed_record(
        {"APEX_CKPT_RESUME": "1", "APEX_TELEMETRY_LEDGER": "/tmp/b.jsonl"},
        {"APEX_COMPILE_CACHE": "off"})
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(f"# fixture\n\nresumed row (ledger:{rec['id']}):\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 0, out.stdout


def test_cold_start_claim_refuses_resumed_record(tmp_path):
    """check 5: a paragraph claiming a cold start must not cite a
    record that restored checkpointed state, whatever its
    compile-cache counters say."""
    rec = _resumed_record({}, {})
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(
        f"# fixture\n\nCold-start compile tax row "
        f"(ledger:{rec['id']}):\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 1, out.stdout
    assert "not a cold start" in out.stdout
    # ...and the same citation in a non-cold paragraph is fine
    perf.write_text(f"# fixture\n\nresumed row (ledger:{rec['id']}):\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 0, out.stdout


def test_malformed_resume_provenance_is_a_finding(tmp_path):
    rec = ledger.make_record(
        harness="bench", platform="tpu", dispatch_overhead_ms=80.0,
        k=16, knobs={}, git="abc", ts=1000.0,
        extra={"resumed_from": {"ckpt": "ck-0123456789", "step": 32,
                                "pins": "not-a-dict"}})
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(f"# fixture\n\nrow (ledger:{rec['id']}):\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 1, out.stdout


def _seed_mfu(tmp_path, mfu, value=102196.0, b=8, s=1024,
              model_flops=None, peak=197e12):
    """A bench-style record carrying an MFU claim + cost block (check 6:
    the MFU must be arithmetically consistent with the block's flops)."""
    from apex_tpu.telemetry import costs

    if model_flops is None:
        # the consistent value: mfu = model_flops * value / (b*s*peak)
        model_flops = mfu * b * s * peak / value
    cost = dict(costs.null_block(), source="compiled", steps=128,
                model_flops_per_step=model_flops, peak_flops=peak)
    rec = ledger.make_record(
        harness="bench", platform="tpu", dispatch_overhead_ms=82.6,
        k=128, relay={"degraded": False, "kind": None}, knobs={},
        git="abc", ts=1000.0,
        extra={"value": value, "mfu": mfu, "cost": cost,
               "config": {"batch": b, "s": s}})
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(f"# fixture\n\nbench b={b} (ledger:{rec['id']}):\n")
    return rec, str(lpath), str(perf)


def test_check6_consistent_mfu_passes(tmp_path):
    rec, lpath, perf = _seed_mfu(tmp_path, mfu=0.387)
    out = _run("--perf", perf, "--ledger", lpath)
    assert out.returncode == 0, out.stdout


def test_check6_mfu_cost_drift_fails(tmp_path):
    """A headline MFU that disagrees with its own record's flops
    accounting is the label-drift class in an attribution costume —
    check 6 fails tier-1 on it."""
    rec, lpath, perf = _seed_mfu(tmp_path, mfu=0.45,
                                 model_flops=0.387 * 8 * 1024 * 197e12
                                 / 102196.0)
    out = _run("--perf", perf, "--ledger", lpath)
    assert out.returncode == 1, out.stdout
    assert "MFU/cost arithmetic drift" in out.stdout


def test_check6_null_degraded_block_is_skipped(tmp_path):
    """No block, no claim to check: a null-degraded cost block (the
    backend couldn't report) never fails check 6."""
    from apex_tpu.telemetry import costs

    rec = ledger.make_record(
        harness="bench", platform="tpu", dispatch_overhead_ms=82.6,
        k=128, knobs={}, git="abc", ts=1000.0,
        extra={"value": 102196.0, "mfu": 0.387,
               "cost": costs.null_block(),
               "config": {"batch": 8, "s": 1024}})
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(f"# fixture\n\nbench b=8 (ledger:{rec['id']}):\n")
    out = _run("--perf", str(perf), "--ledger", str(lpath))
    assert out.returncode == 0, out.stdout


def test_check6_applies_to_dispatch_table_citations(tmp_path):
    """The table side carries the same arithmetic teeth as PERF.md
    captions."""
    rec, lpath, _ = _seed_mfu(tmp_path, mfu=0.45,
                              model_flops=0.387 * 8 * 1024 * 197e12
                              / 102196.0)
    perf = tmp_path / "PERF.md"
    perf.write_text("# no citations\n")
    table = tmp_path / "table.jsonl"
    table.write_text(json.dumps({
        "op": "bench_batch", "bucket": "b8", "dtype": "bfloat16",
        "backend": "tpu", "choice": "8",
        "ledger": rec["id"], "pins": {}}) + "\n")
    out = _run("--perf", str(perf), "--ledger", lpath,
               "--table", str(table))
    assert out.returncode == 1, out.stdout
    assert "MFU/cost arithmetic drift" in out.stdout
