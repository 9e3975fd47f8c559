"""Bitwise resume parity (ISSUE 6): training resumed from a durable
checkpoint at step k is trajectory-identical to the uninterrupted run.

The state surface is the full TrainState the durability layer claims
to cover: params, ZeRO-sharded DistributedFusedAdam optimizer state
(per-rank flat shards on the 8-device CPU mesh's dp axis), GradScaler
state, and the RNG stream (keyed on the GLOBAL step, so a resumed run
draws exactly the noise the uninterrupted run would have drawn).
"""

import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu import checkpoint as ckpt  # noqa: E402
from apex_tpu.contrib.optimizers.distributed_fused_adam import (  # noqa: E402
    DistAdamState, distributed_fused_adam)
from apex_tpu.transformer.amp.grad_scaler import GradScaler  # noqa: E402



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
