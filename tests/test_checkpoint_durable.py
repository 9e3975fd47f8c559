"""Durability layer unit tests (`apex_tpu.checkpoint.DurableCheckpointer`).

The commit protocol's invariants (atomic tmp+rename, content-hash
manifest, torn/corrupt/stale fallback), the bounded-queue async mode
with backpressure, the telemetry block, and the zero-cost rule: the
checkpoint layer lives entirely at the scan boundary on the host, so
an enabled writer never changes the jitted training step's jaxpr.
Chaos twins driving the same invariants through scripted fault plans
and real subprocesses live in tests/test_checkpoint_chaos.py; the
bitwise resume-parity runs live in tests/test_resume_parity.py.
"""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu import checkpoint as ckpt


def _mesh():
    return Mesh(np.asarray(jax.devices()).reshape(4, 2), ("dp", "tp"))


def _state(mesh=None):
    rs = np.random.RandomState(0)
    w = jnp.asarray(rs.randn(16, 8), jnp.float32)
    if mesh is not None:
        w = jax.device_put(w, NamedSharding(mesh, P("dp", "tp")))
    return {
        "params": {"w": w,
                   "emb": jnp.asarray(rs.randn(8, 4) * 0.1, jnp.bfloat16)},
        "count": jnp.asarray(3, jnp.int32),
        "overflow": jnp.asarray(False),
        "rng": jax.random.PRNGKey(7),
    }


def _assert_tree_equal(a, b):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(np.asarray(x),
                                                   np.asarray(y)), a, b)


def test_sync_roundtrip_values_shardings_and_dtypes(tmp_path):
    """One sync save commits atomically; restore reproduces every leaf
    bitwise (incl. bf16, bool, int scalars, PRNGKey) and places sharded
    leaves back onto the template's shardings."""
    mesh = _mesh()
    state = _state(mesh)
    w = ckpt.DurableCheckpointer(tmp_path, async_save=False)
    manifest = w.save(5, state, meta={"step": 5, "knob_pins": {}})
    assert manifest["step"] == 5
    assert manifest["id"] == ckpt.manifest_id(manifest)
    # the committed data file hashes to the manifest's sha256
    assert ckpt._sha256_file(ckpt._data_path(str(tmp_path), 5)) \
        == manifest["sha256"]
    restored, m = w.restore_latest(state)
    assert m["id"] == manifest["id"]
    _assert_tree_equal(restored, state)
    assert restored["params"]["w"].sharding == state["params"]["w"].sharding
    assert restored["params"]["emb"].dtype == jnp.bfloat16
    assert (m.get("meta") or {}).get("step") == 5


def test_retention_keeps_newest(tmp_path):
    state = _state()
    w = ckpt.DurableCheckpointer(tmp_path, max_to_keep=2,
                                 async_save=False)
    for step in (1, 2, 3, 4):
        w.save(step, state)
    assert w.all_steps() == [3, 4]
    snap = w.snapshot()
    assert snap["saves"] == 4 and snap["last_step"] == 4
    assert snap["commit_ms"] is not None and snap["queue_depth"] == 0


def test_torn_data_file_is_never_a_candidate(tmp_path):
    """A data file without a manifest (crash between the two renames)
    is invisible: latest_step and the restore walk skip it."""
    state = _state()
    w = ckpt.DurableCheckpointer(tmp_path, async_save=False)
    w.save(1, state)
    w.save(2, state)
    os.remove(ckpt._manifest_path(str(tmp_path), 2))  # torn step 2
    assert w.latest_step() == 1
    restored, m = w.restore_latest(state)
    assert m["step"] == 1
    _assert_tree_equal(restored, state)


def test_corrupt_latest_falls_back_one_step(tmp_path, capsys):
    """Bytes that no longer hash to the manifest (truncation/disk rot)
    are never restored — the walk falls back to the previous retained
    step and says why on stderr."""
    state = _state()
    w = ckpt.DurableCheckpointer(tmp_path, async_save=False)
    w.save(1, state)
    scaled = jax.tree_util.tree_map(
        lambda x: (x * 2).astype(x.dtype)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, state)
    w.save(2, scaled)
    with open(ckpt._data_path(str(tmp_path), 2), "r+b") as f:
        f.seek(40)
        f.write(b"\x00\x00")
    restored, m = w.restore_latest(state)
    assert m["step"] == 1
    _assert_tree_equal(restored, state)
    assert "hash mismatch" in capsys.readouterr().err


def test_truncated_data_file_falls_back(tmp_path):
    state = _state()
    w = ckpt.DurableCheckpointer(tmp_path, async_save=False)
    w.save(1, state)
    w.save(2, state)
    with open(ckpt._data_path(str(tmp_path), 2), "r+b") as f:
        f.truncate(16)
    _, m = w.restore_latest(state)
    assert m["step"] == 1


def test_stale_manifest_step_is_refused(tmp_path):
    """A manifest whose step field disagrees with its filename (the
    stale-step tamper mode) must not restore as the filename's step —
    trajectory provenance would silently lie."""
    state = _state()
    w = ckpt.DurableCheckpointer(tmp_path, async_save=False)
    w.save(1, state)
    w.save(2, state)
    mpath = ckpt._manifest_path(str(tmp_path), 2)
    with open(mpath) as f:
        m = json.load(f)
    m["step"] = 1  # tamper
    with open(mpath, "w") as f:
        json.dump(m, f)
    _, got = ckpt.restore_durable(str(tmp_path), state)
    assert got["step"] == 1
    assert ckpt.read_durable_manifest(str(tmp_path), 1)["id"] == got["id"]


def test_pinned_step_restore_raises_on_invalid(tmp_path):
    """Explicit request ≠ preference: a pinned-step restore of an
    invalid checkpoint raises instead of silently restoring another."""
    state = _state()
    w = ckpt.DurableCheckpointer(tmp_path, async_save=False)
    w.save(1, state)
    w.save(2, state)
    with open(ckpt._data_path(str(tmp_path), 2), "r+b") as f:
        f.truncate(10)
    with pytest.raises(ValueError, match="hash mismatch"):
        w.restore(2, state)
    # ...while the valid pinned step restores fine
    restored, m = w.restore(1, state)
    assert m["step"] == 1


def test_template_mismatch_is_skipped_not_misrestored(tmp_path):
    """A checkpoint whose tree does not match the restore template
    (different run shape) is skipped, never force-fit."""
    w = ckpt.DurableCheckpointer(tmp_path, async_save=False)
    w.save(1, {"a": jnp.ones((4,))})
    other = {"a": jnp.ones((8,))}
    restored, m = w.restore_latest(other)
    assert restored is None and m is None


def test_async_commits_drain_on_flush(tmp_path):
    state = _state()
    w = ckpt.DurableCheckpointer(tmp_path, max_to_keep=5,
                                 async_save=True, queue_size=2)
    for step in (1, 2, 3):
        w.save(step, state)
    w.flush()
    assert w.all_steps() == [1, 2, 3]
    snap = w.snapshot()
    assert snap["saves"] == 3 and snap["errors"] == 0
    assert snap["async"] is True
    w.close()
    restored, m = ckpt.restore_durable(str(tmp_path), state)
    assert m["step"] == 3
    _assert_tree_equal(restored, state)


def test_commit_now_bypasses_a_stalled_async_queue(tmp_path, monkeypatch):
    """The emergency-save path (a SIGTERM handler's last act): with the
    background writer stalled inside step 1's commit and step 2 queued
    behind it, ``commit_now`` lands step 3 from the calling thread
    without touching the queue, once the writer's commit (bounded by
    one commit, under the writer lock) is through; nothing queued is
    lost and the newest restore is the emergency one."""
    stall = 0.4
    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps([
        {"site": "ckpt_commit", "kind": "hang", "seconds": stall,
         "match_ctx": {"phase": "serialized", "step": 1}}]))
    state = _state()
    w = ckpt.DurableCheckpointer(tmp_path, max_to_keep=5,
                                 async_save=True, queue_size=2)
    w.save(1, state)   # the worker stalls in this commit
    w.save(2, state)   # waits in the queue
    manifest = w.commit_now(3, jax.device_get(state), meta={"why": "term"})
    assert manifest["step"] == 3
    assert 3 in w.all_steps()            # on disk before any flush
    w.flush()
    assert w.all_steps() == [1, 2, 3]
    assert w.snapshot()["errors"] == 0
    w.close()
    restored, m = ckpt.restore_durable(str(tmp_path), state)
    assert m["step"] == 3 and m["meta"]["why"] == "term"
    _assert_tree_equal(restored, state)


def test_async_bounded_queue_applies_backpressure(tmp_path, monkeypatch):
    """A serializer that cannot keep up BLOCKS the caller (bounded
    queue) instead of growing host memory or dropping checkpoints:
    with a 1-deep queue and a stalled commit (the slow-disk fault,
    via the real APEX_FAULT_PLAN path), the third save cannot return
    before the first commit finishes."""
    stall = 0.4
    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps([
        {"site": "ckpt_commit", "kind": "hang", "seconds": stall,
         "match_ctx": {"phase": "serialized", "step": 1}}]))
    state = {"a": jnp.ones((4,))}
    w = ckpt.DurableCheckpointer(tmp_path, max_to_keep=5,
                                 async_save=True, queue_size=1)
    t0 = time.perf_counter()
    w.save(1, state)   # worker picks this up and stalls in commit
    w.save(2, state)   # fills the 1-deep queue
    w.save(3, state)   # must BLOCK until the stalled commit drains
    blocked = time.perf_counter() - t0
    w.flush()
    assert blocked >= stall * 0.5, \
        f"third save returned in {blocked:.3f}s — no backpressure"
    assert w.all_steps() == [1, 2, 3]
    # the stall is visible in telemetry: the slow commit's commit_ms
    assert w.snapshot()["saves"] == 3
    w.close()


def test_async_commit_error_is_telemetry_not_crash(tmp_path,
                                                   monkeypatch):
    """A failing background commit must never kill the training
    process or the writer thread — the failure lands in the telemetry
    block and the NEXT save still commits."""
    state = {"a": jnp.ones((4,))}
    w = ckpt.DurableCheckpointer(tmp_path, async_save=True, queue_size=2)
    real_commit = w._commit
    calls = {"n": 0}

    def flaky(*a, **k):
        calls["n"] += 1
        if calls["n"] == 1:
            raise OSError("disk full")
        return real_commit(*a, **k)

    monkeypatch.setattr(w, "_commit", flaky)
    w.save(1, state)
    w.save(2, state)
    w.flush()
    snap = w.snapshot()
    assert snap["errors"] == 1 and "disk full" in snap["last_error"]
    assert snap["saves"] == 1
    assert w.all_steps() == [2]
    w.close()


def test_enabled_checkpointing_is_jaxpr_byte_identical(monkeypatch,
                                                       tmp_path):
    """The zero-cost rule for the durability layer: the writer lives
    entirely at the scan boundary (host side), so tracing the
    training step with checkpointing armed — writer constructed, a
    save committed — yields a jaxpr byte-identical to the
    checkpointing-disabled trace."""
    from tests.one_step import make_one_step
    from tests.test_telemetry import _bench_fixture

    (model, scaler, tx, params, opt_state, scaler_state,
     ids, pos, labels) = _bench_fixture()
    args = (params, opt_state, scaler_state, ids, pos, labels)

    from apex_tpu import telemetry

    telemetry.disable()
    monkeypatch.delenv("APEX_CKPT_DIR", raising=False)
    want = str(jax.make_jaxpr(make_one_step(model, scaler, tx))(
        *args))

    monkeypatch.setenv("APEX_CKPT_DIR", str(tmp_path))
    w = ckpt.DurableCheckpointer(tmp_path, async_save=False)
    w.save(1, {"params": params, "opt": opt_state})
    got = str(jax.make_jaxpr(make_one_step(model, scaler, tx))(
        *args))
    assert got == want, \
        "enabled checkpointing changed the training step's jaxpr"


def test_snapshot_block_shape_matches_ledger_validation(tmp_path):
    """The writer's telemetry block passes the ledger's checkpoint-
    block validation — the schema profile_gpt stamps into records."""
    from apex_tpu.telemetry import ledger

    w = ckpt.DurableCheckpointer(tmp_path, async_save=False)
    w.save(1, {"a": jnp.ones((2,))})
    rec = ledger.make_record(
        harness="bench", platform="cpu", dispatch_overhead_ms=1.0, k=3,
        knobs={}, git="abc", ts=1.0,
        extra={"checkpoint": w.snapshot(),
               "resumed_from": {"ckpt": "ck-0123456789", "step": 3,
                                "pins": {}}})
    assert ledger.validate_record(rec) == []


def test_concurrent_saves_from_training_thread_are_ordered(tmp_path):
    """Saves issued while earlier commits are still queued land in
    step order (one worker drains the queue FIFO)."""
    state = {"a": jnp.ones((2,))}
    w = ckpt.DurableCheckpointer(tmp_path, max_to_keep=10,
                                 async_save=True, queue_size=2)
    done = threading.Event()

    def trainer():
        for s in range(1, 6):
            w.save(s, state)
        done.set()

    t = threading.Thread(target=trainer)
    t.start()
    t.join(timeout=30)
    assert done.is_set()
    w.close()
    assert w.all_steps() == [1, 2, 3, 4, 5]
