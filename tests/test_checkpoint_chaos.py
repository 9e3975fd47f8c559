"""Chaos twins for the checkpoint durability invariants (ISSUE 6).

Every new fault mode is scripted through ``APEX_FAULT_PLAN``
(apex_tpu.resilience.faults) and fired inside the REAL commit path
(tests/ckpt_chaos_worker.py subprocesses; bench.py itself for the
emergency-save path), asserting the committed behaviors:

* SIGKILL mid-commit (between the data rename and the manifest rename)
  leaves a torn file that is NEVER restored — the prior checkpoint
  stays the newest valid one, bitwise intact,
* SIGKILL before the data rename leaves no visible artifact at all,
* a post-commit corrupted/truncated data file fails the manifest hash
  check and the restore walk falls back one step,
* a stale-step manifest tamper (step field vs filename) is refused,
* bench.py's SIGTERM path (the watchdog's terminate-with-grace)
  flushes an emergency checkpoint + a ``bench_emergency_save`` ledger
  record next to its best JSON line,
* the watchdog's own SIGTERM record (``bench_watchdog``) reports the
  newest committed checkpoint on disk, so a terminated window
  self-describes what ``--resume`` will pick up.

Fast-keeping rule: the worker subprocesses never touch a backend
beyond jax import (~3-4 s each); only the bench emergency-save twin
pays a real CPU smoke run, and it shares the suite-wide smoke compile
cache (tests/conftest.py).
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu import checkpoint as ckpt  # noqa: E402
from apex_tpu.telemetry import ledger as tledger  # noqa: E402
from tests.ckpt_chaos_worker import state_at  # noqa: E402

WORKER = os.path.join(REPO, "tests", "ckpt_chaos_worker.py")
BENCH = os.path.join(REPO, "bench.py")


def _run_worker(ckpt_dir, steps, plan):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               APEX_FAULT_PLAN=json.dumps(plan))
    return subprocess.run(
        [sys.executable, WORKER, str(ckpt_dir)] + [str(s) for s in steps],
        env=env, capture_output=True, text=True, timeout=120)


def _assert_restores_step(ckpt_dir, template_step, want_step):
    restored, manifest = ckpt.restore_durable(
        str(ckpt_dir), state_at(template_step))
    assert manifest is not None, "no valid checkpoint survived"
    assert manifest["step"] == want_step
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        restored, state_at(want_step))


def test_chaos_sigkill_between_renames_never_tears_a_restore(tmp_path):
    """The torn window: SIGKILL lands after the data rename, before the
    manifest rename. The step-2 data file exists on disk but is
    invisible to the restore walk; step 1 restores bitwise intact."""
    plan = [{"site": "ckpt_commit", "kind": "sigkill",
             "match_ctx": {"phase": "data_visible", "step": 2}}]
    out = _run_worker(tmp_path, [1, 2], plan)
    assert out.returncode == -signal.SIGKILL
    assert "committed 1" in out.stdout and "DONE" not in out.stdout
    # the torn artifact is there — and ignored
    assert os.path.exists(ckpt._data_path(str(tmp_path), 2))
    assert not os.path.exists(ckpt._manifest_path(str(tmp_path), 2))
    assert ckpt.durable_steps(str(tmp_path)) == [1]
    _assert_restores_step(tmp_path, 1, want_step=1)


def test_chaos_sigkill_before_data_rename_leaves_prior_intact(tmp_path):
    """SIGKILL during serialization (pre-rename): no step-2 artifact
    becomes visible at all; the prior checkpoint is untouched."""
    plan = [{"site": "ckpt_commit", "kind": "sigkill",
             "match_ctx": {"phase": "serialized", "step": 2}}]
    out = _run_worker(tmp_path, [1, 2], plan)
    assert out.returncode == -signal.SIGKILL
    assert not os.path.exists(ckpt._data_path(str(tmp_path), 2))
    _assert_restores_step(tmp_path, 1, want_step=1)


def test_chaos_damaged_and_stale_checkpoints_chain_fallback(tmp_path):
    """The three post-commit damage modes in ONE worker run (each fault
    targets its own step, so one subprocess proves all three AND that
    the fallback walk chains): step 4's manifest is stale-tampered
    (claims step 1), step 3's data file is corrupted, step 2's is
    truncated — restore refuses 4, 3 and 2 in turn and lands on the
    intact step 1, bitwise."""
    plan = [
        {"site": "ckpt_data", "kind": "truncate_file", "keep_bytes": 32,
         "match_ctx": {"step": 2}},
        {"site": "ckpt_data", "kind": "corrupt_file", "offset": 64,
         "match_ctx": {"step": 3}},
        {"site": "ckpt_manifest", "kind": "set_field", "field": "step",
         "value": 1, "match_ctx": {"step": 4}},
    ]
    out = _run_worker(tmp_path, [1, 2, 3, 4], plan)
    assert out.returncode == 0, out.stderr[-2000:]
    assert ckpt.durable_steps(str(tmp_path)) == [1, 2, 3, 4]  # committed
    _assert_restores_step(tmp_path, 1, want_step=1)  # ...4, 3, 2 refused


def test_chaos_slow_disk_stall_still_commits(tmp_path):
    """The slow-disk commit stall: the commit takes the injected stall
    but COMMITS — durability degrades to latency, never to loss — and
    the stall is visible in the worker's commit telemetry."""
    plan = [{"site": "ckpt_commit", "kind": "hang", "seconds": 1.0,
             "match_ctx": {"phase": "serialized", "step": 2}}]
    t0 = time.perf_counter()
    out = _run_worker(tmp_path, [1, 2], plan)
    wall = time.perf_counter() - t0
    assert out.returncode == 0, out.stderr[-2000:]
    assert wall >= 1.0
    assert ckpt.durable_steps(str(tmp_path)) == [1, 2]
    _assert_restores_step(tmp_path, 2, want_step=2)


# --------------------------------------------------- bench e2e twins
# (one real CPU smoke run each; shared suite smoke compile cache)

@pytest.fixture
def chaos_cache_dir(shared_smoke_cache_dir):
    return shared_smoke_cache_dir


def _bench_env(tmp_path, chaos_cache_dir, plan=None, **extra):
    env = dict(os.environ)
    for k in ("APEX_WARM_ONLY", "APEX_FAULT_PLAN", "APEX_CKPT_RESUME"):
        env.pop(k, None)
    env.update(
        JAX_PLATFORMS="cpu",
        APEX_BENCH_SMOKE="1",
        JAX_COMPILATION_CACHE_DIR=chaos_cache_dir,
        APEX_CKPT_DIR=str(tmp_path / "ckpt"),
        APEX_TELEMETRY_LEDGER=str(tmp_path / "ledger.jsonl"),
        APEX_BENCH_BASELINE=str(tmp_path / "baseline.json"),
        **extra)
    if plan is not None:
        env["APEX_FAULT_PLAN"] = json.dumps(plan)
    return env


def test_chaos_sigterm_during_final_save_flushes_emergency_ckpt(
        tmp_path, chaos_cache_dir):
    """The watchdog-terminate path end-to-end: a wedge strikes at the
    final save (injected hang), the outer SIGTERM lands — the inner
    bench commits its staged scan-boundary state as an emergency
    checkpoint and appends a ``bench_emergency_save`` ledger record,
    then exits 143. Nothing that ran in the window is lost."""
    plan = [{"site": "final_save", "kind": "hang"}]
    env = _bench_env(tmp_path, chaos_cache_dir, plan,
                     APEX_BENCH_INNER="1")
    err_path = tmp_path / "stderr.log"
    with open(err_path, "w") as errf:
        proc = subprocess.Popen([sys.executable, BENCH], env=env,
                                stdout=subprocess.PIPE, stderr=errf,
                                text=True)
    try:
        deadline = time.time() + 240
        while time.time() < deadline:
            if proc.poll() is not None:
                break
            if "site=final_save" in err_path.read_text():
                break
            time.sleep(0.25)
        assert proc.poll() is None, (
            f"bench exited early rc={proc.returncode}: "
            f"{err_path.read_text()[-2000:]}")
        proc.terminate()
        proc.communicate(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.communicate()
    assert proc.returncode == 143
    assert "emergency checkpoint committed" in err_path.read_text()
    # the staged scan-boundary state (warm scan's output: step0+iters
    # = 3 in smoke) was committed with a valid manifest
    ckpt_dir = str(tmp_path / "ckpt")
    steps = ckpt.durable_steps(ckpt_dir)
    assert steps and steps[-1] == 3
    manifest = ckpt.read_durable_manifest(ckpt_dir, 3)
    assert ckpt._verify_durable(ckpt_dir, 3, manifest) is None
    records = tledger.read_ledger(str(tmp_path / "ledger.jsonl"))
    es = [r for r in records
          if r.get("harness") == "bench_emergency_save"]
    assert len(es) == 1
    assert es[0]["terminated"] == "SIGTERM" and es[0]["ckpt_step"] == 3
    # two commits: the scan-boundary save + the emergency recommit
    assert es[0]["checkpoint"]["saves"] == 2
    assert es[0]["fault_plan"].startswith("fp-")
    assert tledger.validate_record(es[0]) == []


# re-promoted to tier-1 (ISSUE 7 fast-tier trim): rides the session
# smoke compile cache (chaos_cache_dir), ~5s warm — the watchdog-side
# ckpt_on_disk reporting comes back under tier-1 teeth instead of
# staying demoted
def test_chaos_watchdog_sigterm_record_reports_disk_checkpoint(
        tmp_path, chaos_cache_dir):
    """The watchdog's own termination record (``bench_watchdog``) must
    name the newest COMMITTED checkpoint on disk — what `--resume`
    will pick up next window — even when the in-flight child hangs
    before any backend work."""
    ckpt_dir = tmp_path / "ckpt"
    seeded = ckpt.DurableCheckpointer(ckpt_dir, async_save=False)
    manifest = seeded.save(7, {"w": jnp.ones((4,))}, meta={"step": 7})
    plan = [{"site": "backend_init", "kind": "sigterm_parent"}]
    env = _bench_env(tmp_path, chaos_cache_dir, plan,
                     APEX_BENCH_ATTEMPTS="1", APEX_BENCH_TIMEOUT="60")
    out = subprocess.run([sys.executable, BENCH], env=env,
                         capture_output=True, text=True, timeout=300)
    records = tledger.read_ledger(str(tmp_path / "ledger.jsonl"))
    wd = [r for r in records if r.get("harness") == "bench_watchdog"]
    assert len(wd) == 1, (out.stdout, out.stderr[-2000:])
    assert wd[0]["terminated"] == "SIGTERM"
    assert wd[0]["ckpt_on_disk"] == {"last_step": 7,
                                     "id": manifest["id"]}
    assert tledger.validate_record(wd[0]) == []
