"""Bitwise resume parity (ISSUE 6): training resumed from a durable
checkpoint at step k is trajectory-identical to the uninterrupted run.

The state surface is the full TrainState the durability layer claims
to cover: params, ZeRO-sharded DistributedFusedAdam optimizer state
(per-rank flat shards on the 8-device CPU mesh's dp axis), GradScaler
state, and the RNG stream (keyed on the GLOBAL step, so a resumed run
draws exactly the noise the uninterrupted run would have drawn).
Plus the end-to-end twin: ``bench.py --resume`` restores and continues
with provenance stamped in its JSON line and content-hashed ledger
record.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu import checkpoint as ckpt  # noqa: E402
from apex_tpu.contrib.optimizers.distributed_fused_adam import (  # noqa: E402
    DistAdamState, distributed_fused_adam)
from apex_tpu.transformer.amp.grad_scaler import GradScaler  # noqa: E402
from apex_tpu.telemetry import ledger as tledger  # noqa: E402

BENCH = os.path.join(REPO, "bench.py")


def _harness():
    """The mini amp+ZeRO training harness: one jitted k-step advance
    whose RNG stream is keyed on the global step."""
    n = len(jax.devices())
    mesh = Mesh(np.asarray(jax.devices()), ("dp",))
    rs = np.random.RandomState(3)
    params = {"w": jnp.asarray(rs.randn(24, 4), jnp.float32),
              "b": jnp.asarray(rs.randn(8), jnp.float32)}
    tx = distributed_fused_adam(learning_rate=0.05, num_shards=n,
                                axis_name="dp")
    scaler = GradScaler(axis_names=())
    state_specs = DistAdamState(count=P(), m=P("dp"), v=P("dp"),
                                master=P("dp"))
    init = shard_map(lambda p: tx.init(p), mesh=mesh, in_specs=(P(),),
                     out_specs=state_specs, check_vma=False)

    def k_steps(k):
        def body(params, opt_state, ss, rng, t0):
            for i in range(k):
                key = jax.random.fold_in(rng, t0 + i)  # global-step RNG
                grads = {
                    name: jax.random.normal(
                        jax.random.fold_in(key, j), p.shape, p.dtype)
                    * 0.1 * ss.loss_scale
                    for j, (name, p) in enumerate(sorted(params.items()))
                }
                g, found = scaler.unscale(grads, ss)
                ss = scaler.update(ss, found)
                updates, opt_state = tx.update(g, opt_state, params)
                params = jax.tree_util.tree_map(
                    lambda a, u: jnp.where(found, a,
                                           a + u.astype(a.dtype)),
                    params, updates)
            return params, opt_state, ss

        return jax.jit(shard_map(
            body, mesh=mesh,
            in_specs=(P(), state_specs, P(), P(), P()),
            out_specs=(P(), state_specs, P()), check_vma=False))

    return params, init, scaler, k_steps, state_specs


def _assert_bitwise(a, b, what):
    jax.tree_util.tree_map(
        lambda x, y: np.testing.assert_array_equal(
            np.asarray(x), np.asarray(y),
            err_msg=f"{what}: resumed trajectory diverged"), a, b)


def test_bitwise_resume_parity_zero_gradscaler_rng(tmp_path):
    """4 uninterrupted steps == 2 steps → durable save → restore (into
    a freshly built template, as a new process would) → 2 more steps,
    bitwise, across params + ZeRO-sharded opt state + GradScaler state
    + the RNG stream."""
    params0, init, scaler, k_steps, _ = _harness()
    rng = jax.random.PRNGKey(42)
    opt0 = init(params0)
    ss0 = scaler.init()
    step2 = k_steps(2)

    # uninterrupted: 4 steps
    p_a, o_a, ss_a = step2(params0, opt0, ss0, rng, jnp.int32(0))
    p_a, o_a, ss_a = step2(p_a, o_a, ss_a, rng, jnp.int32(2))

    # interrupted twin: 2 steps, durable save at k=2
    p_b, o_b, ss_b = step2(params0, opt0, ss0, rng, jnp.int32(0))
    writer = ckpt.DurableCheckpointer(tmp_path, async_save=False)
    manifest = writer.save(
        2, {"params": p_b, "opt": o_b, "scaler": ss_b, "rng": rng},
        meta={"step": 2, "knob_pins": {}})
    assert manifest["step"] == 2

    # resume: a FRESH template (what a new process builds from init),
    # restored through a fresh writer — nothing rides process state
    tmpl = {"params": params0, "opt": init(params0),
            "scaler": scaler.init(), "rng": jax.random.PRNGKey(0)}
    restored, m = ckpt.DurableCheckpointer(
        tmp_path, async_save=False).restore_latest(tmpl)
    assert m["id"] == manifest["id"]
    # ZeRO shards restored onto their dp sharding
    assert restored["opt"].m.sharding.spec == o_b.m.sharding.spec
    p_c, o_c, ss_c = step2(restored["params"], restored["opt"],
                           restored["scaler"], restored["rng"],
                           jnp.int32(2))

    _assert_bitwise(p_a, p_c, "params")
    _assert_bitwise(
        {"m": o_a.m, "v": o_a.v, "master": o_a.master,
         "count": o_a.count},
        {"m": o_c.m, "v": o_c.v, "master": o_c.master,
         "count": o_c.count}, "ZeRO opt state")
    _assert_bitwise(ss_a, ss_c, "GradScaler state")


def test_resume_after_corrupt_latest_matches_shorter_uninterrupted(
        tmp_path):
    """Composition with the durability walk: when the NEWEST checkpoint
    is corrupt, resume falls back one retained step and the trajectory
    from there still matches the uninterrupted run bitwise — stale
    progress, never wrong progress."""
    params0, init, scaler, k_steps, _ = _harness()
    rng = jax.random.PRNGKey(42)
    opt0, ss0 = init(params0), scaler.init()
    step2 = k_steps(2)

    p, o, ss = step2(params0, opt0, ss0, rng, jnp.int32(0))
    writer = ckpt.DurableCheckpointer(tmp_path, max_to_keep=3,
                                      async_save=False)
    writer.save(2, {"params": p, "opt": o, "scaler": ss, "rng": rng},
                meta={"step": 2})
    p4, o4, ss4 = step2(p, o, ss, rng, jnp.int32(2))
    writer.save(4, {"params": p4, "opt": o4, "scaler": ss4, "rng": rng},
                meta={"step": 4})
    with open(ckpt._data_path(str(tmp_path), 4), "r+b") as f:
        f.truncate(64)  # the wedge tore the newest checkpoint

    tmpl = {"params": params0, "opt": init(params0),
            "scaler": scaler.init(), "rng": jax.random.PRNGKey(0)}
    restored, m = writer.restore_latest(tmpl)
    assert m["step"] == 2  # fell back past the torn step 4
    p_r, o_r, ss_r = step2(restored["params"], restored["opt"],
                           restored["scaler"], restored["rng"],
                           jnp.int32(2))
    _assert_bitwise(p4, p_r, "params (resumed from fallback step)")
    _assert_bitwise(ss4, ss_r, "scaler state")


# ------------------------------------------------------ bench e2e twin

@pytest.fixture
def chaos_cache_dir(shared_smoke_cache_dir):
    return shared_smoke_cache_dir


def _bench_smoke(tmp_path, chaos_cache_dir, resume=False, extra=None):
    env = dict(os.environ)
    for k in ("APEX_WARM_ONLY", "APEX_FAULT_PLAN", "APEX_CKPT_RESUME"):
        env.pop(k, None)
    env.update(
        JAX_PLATFORMS="cpu",
        APEX_BENCH_SMOKE="1", APEX_BENCH_INNER="1",
        JAX_COMPILATION_CACHE_DIR=chaos_cache_dir,
        APEX_CKPT_DIR=str(tmp_path / "ckpt"),
        APEX_TELEMETRY_LEDGER=str(tmp_path / "ledger.jsonl"),
        APEX_BENCH_BASELINE=str(tmp_path / "baseline.json"),
        **(extra or {}))
    if resume:
        env["APEX_CKPT_RESUME"] = "1"
    out = subprocess.run([sys.executable, BENCH], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    line = [ln for ln in out.stdout.splitlines()
            if ln.startswith("{")][-1]
    return json.loads(line), out


@pytest.mark.slow  # 3 full bench subprocess runs (~33s): the producer-
#                    side e2e twin. Its invariants keep fast coverage —
#                    resume/restore via the library-level parity tests
#                    above, the checker side via check 5's unit tests —
#                    so the fast tier holds the ~5-min convention.
def test_bench_resume_e2e_provenance_in_line_and_ledger(
        tmp_path, chaos_cache_dir):
    """Run 1 banks a final checkpoint (telemetry block in the JSON
    line); run 2 under --resume semantics restores it, continues from
    its step, and stamps ``resumed_from`` (ckpt id + step + pins)
    into both the JSON line and the content-hashed ledger record."""
    rec1, _ = _bench_smoke(tmp_path, chaos_cache_dir)
    # two commits: the scan-boundary save (step 3 — banked BEFORE the
    # timed dispatch, so a hard wedge there loses nothing) + the final
    assert rec1["checkpoint"]["saves"] == 2
    assert rec1["checkpoint"]["last_step"] == 6  # 2 scans x smoke K=3
    assert "resumed_from" not in rec1
    ckpt_dir = str(tmp_path / "ckpt")
    manifest = ckpt.latest_durable_manifest(ckpt_dir)
    assert manifest["step"] == 6

    rec2, out2 = _bench_smoke(tmp_path, chaos_cache_dir, resume=True)
    prov = rec2["resumed_from"]
    assert prov["ckpt"] == manifest["id"]
    assert prov["step"] == 6
    assert "pin_drift" not in prov
    assert rec2["checkpoint"]["last_step"] == 12  # continued, not reset
    assert f"resumed from {manifest['id']}" in out2.stderr

    records = tledger.read_ledger(str(tmp_path / "ledger.jsonl"))
    bench_recs = [r for r in records if r.get("harness") == "bench"]
    assert bench_recs[-1]["resumed_from"] == prov
    # provenance is INSIDE the content-hashed id: the record validates,
    # and stripping the provenance breaks its own id
    assert tledger.validate_record(bench_recs[-1]) == []
    stripped = {k: v for k, v in bench_recs[-1].items()
                if k != "resumed_from"}
    assert tledger.record_id(stripped) != bench_recs[-1]["id"]

    # ...and a THIRD run resuming under a different measurement pin
    # (APEX_REMAT=none vs the checkpoint's unset): the run proceeds but
    # the provenance names the drift — the hook check_bench_labels
    # check 5 refuses citations on
    rec3, _ = _bench_smoke(tmp_path, chaos_cache_dir, resume=True,
                           extra={"APEX_REMAT": "none"})
    prov3 = rec3["resumed_from"]
    assert prov3["pins"].get("APEX_REMAT") is None
    assert prov3["pin_drift"]["APEX_REMAT"] == [None, "none"]
