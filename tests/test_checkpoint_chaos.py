"""Chaos twins for the checkpoint durability invariants (ISSUE 6).

Every new fault mode is scripted through ``APEX_FAULT_PLAN``
(apex_tpu.resilience.faults) and fired inside the REAL commit path
(tests/ckpt_chaos_worker.py subprocesses), asserting the committed
behaviors:

* SIGKILL mid-commit (between the data rename and the manifest rename)
  leaves a torn file that is NEVER restored — the prior checkpoint
  stays the newest valid one, bitwise intact,
* SIGKILL before the data rename leaves no visible artifact at all,
* a post-commit corrupted/truncated data file fails the manifest hash
  check and the restore walk falls back one step,
* a stale-step manifest tamper (step field vs filename) is refused.

Fast-keeping rule: the worker subprocesses never touch a backend
beyond jax import (~3-4 s each).
"""

import json
import os
import signal
import subprocess
import sys
import time

import jax
import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu import checkpoint as ckpt  # noqa: E402
from tests.ckpt_chaos_worker import state_at  # noqa: E402

WORKER = os.path.join(REPO, "tests", "ckpt_chaos_worker.py")


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
