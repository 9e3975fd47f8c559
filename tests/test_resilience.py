"""The resilience package (`apex_tpu/resilience/`): the retry budget,
the no-record verdict, the durable small-file write, and the fault
plan's parsing, hash and matchers. The sites themselves are held to
their callers by tests/test_fault_sites.py and exercised by the chaos
suites (checkpoint, serving, router, kv tier).
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu import resilience  # noqa: E402
from apex_tpu.resilience import faults  # noqa: E402
from apex_tpu.telemetry import ledger as tledger  # noqa: E402


def test_retry_policy_attempts_and_pacing():
    p = resilience.RetryPolicy(attempts=3, retry_wait_s=100)
    assert p.attempts == 3
    assert p.pop_wait() == 100 and p.pop_wait() == 100
    # a budget is at least one attempt; the chaos tests pin a zero wait
    z = resilience.RetryPolicy(attempts=0, retry_wait_s=0)
    assert z.attempts == 1 and z.pop_wait() == 0


def test_classify_subprocess_verdicts():
    assert resilience.classify_subprocess(0) == resilience.HEALTHY
    assert resilience.classify_subprocess(1) == resilience.DEGRADED_RELAY
    assert resilience.classify_subprocess(None, timed_out=True) \
        == resilience.WEDGED


def test_atomic_write_replaces_whole_file(tmp_path):
    path = str(tmp_path / "state.json")
    resilience.atomic_write(path, "old")
    resilience.atomic_write(path, "new text")
    assert open(path).read() == "new text"
    assert os.listdir(tmp_path) == ["state.json"]   # no tmp left behind


def test_fault_plan_parsing_hash_and_matchers(monkeypatch, tmp_path):
    monkeypatch.delenv("APEX_FAULT_PLAN", raising=False)
    assert not faults.active() and faults.plan_hash() is None
    plan = [{"site": "serve_alloc", "kind": "deny",
             "match_env": {"APEX_CHAOS_MARK": "1"},
             "match_ctx": {"tick": 3}}]
    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps(plan))
    h = faults.plan_hash()
    assert h and h.startswith("fp-")
    # env matcher gates the fault
    monkeypatch.delenv("APEX_CHAOS_MARK", raising=False)
    assert not faults.denied("serve_alloc", tick=3)
    monkeypatch.setenv("APEX_CHAOS_MARK", "1")
    # ... and so do the hook's own keyword arguments, and the site
    assert not faults.denied("serve_alloc", tick=2)
    assert not faults.denied("serve_swap", tick=3)
    assert faults.denied("serve_alloc", tick=3)
    # a path-valued plan parses to the same hash as the inline text
    p = tmp_path / "plan.json"
    p.write_text(json.dumps({"faults": plan}))
    monkeypatch.setenv("APEX_FAULT_PLAN", str(p))
    assert faults.plan_hash() == h
    # a plan that is not a list of faults raises: a broken chaos plan
    # must fail its test, not run healthy
    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps({"faults": "x"}))
    with pytest.raises(ValueError):
        faults.plan()
    # value faults: set_field returns a tampered copy
    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps(
        [{"site": "ckpt_manifest", "kind": "set_field",
          "field": "step", "value": 9}]))
    manifest = {"step": 2, "id": "ck-1"}
    assert faults.transform_json("ckpt_manifest", manifest) \
        == {"step": 9, "id": "ck-1"}
    assert manifest["step"] == 2


def test_ledger_stamps_sentinel_for_unresolvable_plan(monkeypatch,
                                                      tmp_path):
    """An ACTIVE-but-unresolvable APEX_FAULT_PLAN (deleted plan file,
    malformed JSON) must still stamp the record — a sentinel, never a
    silent omission that would let a record written under injection
    masquerade as clean."""
    monkeypatch.setenv("APEX_FAULT_PLAN", str(tmp_path / "gone.json"))
    rec = tledger.make_record("bench", "cpu", 1.0, 3, git="abc", ts=1.0)
    assert rec["fault_plan"] == "fp-unresolvable"
    monkeypatch.setenv("APEX_FAULT_PLAN", "{not json")
    rec = tledger.make_record("bench", "cpu", 1.0, 3, git="abc", ts=1.0)
    assert rec["fault_plan"] == "fp-unresolvable"


def test_ledger_stamps_fault_plan_inside_content_id(monkeypatch):
    """The stamp is computed BEFORE the content hash: stripping it (or
    adding it after the fact) breaks the record's own id — the checker
    flags exactly that as tampering."""
    monkeypatch.setenv("APEX_FAULT_PLAN",
                       json.dumps([{"site": "serve_decode", "kind": "raise"}]))
    rec = tledger.make_record("bench", "cpu", 1.0, 3, git="abc", ts=1.0)
    assert rec["fault_plan"] == faults.plan_hash()
    assert tledger.validate_record(rec) == []
    stripped = {k: v for k, v in rec.items() if k != "fault_plan"}
    assert any("does not match record content" in p
               for p in tledger.validate_record(stripped))
