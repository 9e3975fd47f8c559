"""tools/window_report.py — the ledger-economics reporter. The
ledger summaries get synthetic fixtures so the test doesn't chase the
live ledger. Jax-free and subprocess-free (the tool
itself never touches a backend)."""

import contextlib
import importlib.util
import io
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu.telemetry import costs, ledger

_spec = importlib.util.spec_from_file_location(
    "window_report", os.path.join(REPO, "tools", "window_report.py"))
wr = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(wr)


# ------------------------------------------------------------ ledger


def _seed(path, **extra):
    return ledger.append_record("bench", "cpu", 0.5, 2, path=path,
                                extra=extra)


def test_ledger_summary_counts_and_attribution(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    cost = costs.build(xla_flops=2e12, hbm_bytes=1e10, steps=2,
                       model_flops_per_step=1.2e12,
                       device_kind=costs.V5E_KIND, source="compiled")
    _seed(path, value=1000.0, mfu=0.30, cost=cost,
          compile_cache={"enabled": True, "hits": 5, "misses": 2})
    _seed(path, cost=costs.null_block())
    records = ledger.read_ledger(path)
    led = wr.ledger_summary(records)
    assert led["records"] == 2
    assert led["cost_blocks"] == {"present": 2, "reporting": 1}
    assert led["compile_cache"]["hits"] == 5
    assert len(led["attribution"]) == 1
    a = led["attribution"][0]
    assert a["mfu"] == 0.30 and a["mfu_bound"] == cost["mfu_bound"]
    # and the text report names the measured-vs-bound gap
    buf = io.StringIO()
    wr.print_report({"ledger": led}, out=buf)
    assert "attribution" in buf.getvalue()
    assert "cost blocks: 2 present, 1 with XLA numbers" in buf.getvalue()


def test_committed_ledger_is_summarizable():
    """The real committed ledger always produces a summary (the
    acceptance criterion's 'from committed artifacts alone') — loose
    assertions only; later rounds append records."""
    led = wr.ledger_summary(ledger.read_ledger(
        os.path.join(REPO, "benchmarks", "ledger.jsonl")))
    assert led["records"] >= 34
    assert led["injected"] == 0
    assert "bench" in led["by_harness"]


def test_empty_round_is_a_report_not_an_error(tmp_path):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = wr.main(["--ledger", str(tmp_path / "none.jsonl")])
    assert rc == 0
    assert "nothing to report" in buf.getvalue()


# ------------------------------- serving economics + overlap (ISSUE 11)


def test_serving_economics_and_overlap_sections(tmp_path):
    """A ledger carrying serving/slo blocks and an overlap_bound stamp
    renders the serving-economics section: trace + arrival process,
    goodput vs the decode-scan line, attainment, occupancy
    high-waters, and the overlap column."""
    slo = {"ttft_p50_ms": 5.0, "ttft_p99_ms": 9.0,
           "per_token_p50_ms": 1.0, "per_token_p99_ms": 2.0,
           "goodput_tok_s": 90.0, "slo_attainment": 0.75,
           "slo_ttft_ms": 1000.0, "slo_tpot_ms": 100.0,
           "arrival_process": "diurnal", "offered_load": 2.0,
           "max_queue_depth": 3, "kv_page_high_water": 10,
           # multi-token decode blocks (ISSUE 17)
           "decode_block_k": 4}
    cost = costs.attach_overlap(costs.null_block(), host_ms=0.25)
    rec = ledger.make_record(
        "profile_serving", "cpu", 0.1, 2,
        extra={"serving": {"tokens_per_s": 100.0,
                           "scan_tokens_per_s": 900.0, "p50_ms": 1.0,
                           "p99_ms": 2.0, "trace_id": "tr-abcdef1234",
                           "kv_pages": 24,
                           # dispatch economics (ISSUE 17): 200 tokens
                           # over 50 K-block dispatches = 4.00/dispatch
                           "decode_steps": 50,
                           "tokens_generated": 200},
               "slo": slo, "cost": cost})
    path = tmp_path / "ledger.jsonl"
    path.write_text(json.dumps(rec) + "\n")
    report = wr.build_report(ledger_path=str(path))
    led = report["ledger"]
    assert len(led["serving"]) == 1
    row = led["serving"][0]
    assert row["trace_id"] == "tr-abcdef1234"
    assert row["slo"]["slo_attainment"] == 0.75
    assert len(led["overlap"]) == 1
    assert led["overlap"][0]["host_ms"] == 0.25

    buf = io.StringIO()
    wr.print_report(report, out=buf)
    text = buf.getvalue()
    assert "serving economics:" in text
    assert "tr-abcdef1234" in text
    assert "arrival=diurnal" in text and "attainment=75%" in text
    # goodput 90 vs scan 900 -> 90% under the scan line
    assert "90% under the scan line" in text
    assert "max queue 3, kv high-water 10/24 pages" in text
    # dispatch economics (ISSUE 17): tokens-per-dispatch readout names
    # the program K it was measured at
    assert ("dispatch economics: 4.00 tokens/dispatch "
            "(200 tok / 50 decode dispatches, decode_block_k=4)") in text
    assert "overlap" in text and "comm+host 0.25 ms" in text


def test_serving_section_absent_without_serving_rows(tmp_path):
    rec = ledger.make_record("bench", "cpu", 0.1, 2)
    path = tmp_path / "ledger.jsonl"
    path.write_text(json.dumps(rec) + "\n")
    report = wr.build_report(ledger_path=str(path))
    assert report["ledger"]["serving"] == []
    assert report["ledger"]["overlap"] == []
    buf = io.StringIO()
    wr.print_report(report, out=buf)
    assert "serving economics" not in buf.getvalue()
