"""Pipeline-parallel schedule tests on the 8-device CPU mesh.

Port of tests/L0/run_transformer/test_pipeline_parallel_fwd_bwd.py — the
analytic-loss pattern: deterministic weight fill, closed-form expected loss
computed in fp64-equivalent numpy, schedules compared against it (and
against each other) with no data or tolerance fuzz. Plus test_microbatches.py
and p2p smoke.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.transformer.microbatches import (
    ConstantNumMicroBatches,
    RampupBatchsizeNumMicroBatches,
)
from apex_tpu.transformer.pipeline_parallel import (
    forward_backward_no_pipelining,
    forward_backward_pipelining_with_interleaving,
    forward_backward_pipelining_without_interleaving,
    get_forward_backward_func,
    p2p_communication,
)
from apex_tpu.transformer.pipeline_parallel.utils import (
    get_ltor_masks_and_position_ids,
)

NDEV = 8
PP = 4
HID = 6
M = 5  # microbatches


def pp_mesh(pp=PP):
    return Mesh(np.array(jax.devices()[:pp]), ("pp",))


# The deterministic model (reference pattern: weight fill (rank+1)/k):
#   embed:  h = x * e
#   stage p: h = h @ W_p     with W_p = ((p+1)/8) * I + 0.01
#   loss:   mean(h * c)
def stage_weight(p, chunks=1):
    # [chunks, HID, HID] when interleaved
    ws = []
    for v in range(chunks):
        s = p + v * PP
        ws.append(((s + 1) / 8.0) * np.eye(HID) + 0.01)
    w = np.stack(ws).astype(np.float32)
    return w if chunks > 1 else w[0]


def stage_fn(w, h, v):
    return h @ w


def embed_fn(e, mb):
    return mb * e


def loss_fn(c, h, mb):
    return jnp.mean(h * c)


def closed_form(xs, e, ws, c):
    """Sequential reference in numpy float64."""
    losses = []
    for m in range(xs.shape[0]):
        h = xs[m].astype(np.float64) * e
        for w in ws:
            h = h @ w.astype(np.float64)
        losses.append((h * c).mean())
    return np.mean(losses)


@pytest.fixture
def batch():
    rng = np.random.RandomState(0)
    return rng.randn(M, 2, HID).astype(np.float32)


def run_pipeline(batch, chunks=1, forward_only=False, impl=None,
                 num_microbatches=M):
    mesh = pp_mesh()
    stacked = np.stack([stage_weight(p, chunks) for p in range(PP)])
    e = jnp.asarray(1.5)
    c = jnp.asarray(2.0)

    fwd_bwd = (forward_backward_pipelining_without_interleaving if chunks == 1
               else forward_backward_pipelining_with_interleaving)

    def run(mbs, sp):
        sp = sp[0]  # drop the sharded singleton: local stage params
        kwargs = dict(num_microbatches=num_microbatches, axis_name="pp",
                      forward_only=forward_only)
        if chunks > 1:
            kwargs["num_model_chunks"] = chunks
        if impl is not None:
            kwargs["impl"] = impl
        loss, grads = fwd_bwd(
            (stage_fn, embed_fn, loss_fn), mbs, (sp, e, c), **kwargs)
        if grads is None:
            return loss, sp[None], e, c
        return loss, grads[0][None], grads[1], grads[2]

    f = shard_map(run, mesh=mesh, in_specs=(P(), P("pp")),
                  out_specs=(P(), P("pp"), P(), P()),
                  check_vma=False)
    loss, gs, ge, gc = jax.jit(f)(jnp.asarray(batch), jnp.asarray(stacked))
    return np.asarray(loss), np.asarray(gs), np.asarray(ge), np.asarray(gc)


def sequential_reference_grads(batch, chunks=1, num_microbatches=M):
    """jax.grad of the closed-form sequential composition."""
    stacked = jnp.asarray(
        np.stack([stage_weight(p, chunks) for p in range(PP)]))

    def loss_of(args):
        sp, e, c = args
        # virtual stage order: chunk-major — v0p0..v0p3, v1p0..v1p3
        total = 0.0
        for m in range(num_microbatches):
            h = embed_fn(e, jnp.asarray(batch[m]))
            for v in range(chunks):
                for p in range(PP):
                    w = sp[p, v] if chunks > 1 else sp[p]
                    h = stage_fn(w, h, v)
            total = total + loss_fn(c, h, jnp.asarray(batch[m]))
        return total / num_microbatches

    args = (stacked, jnp.asarray(1.5), jnp.asarray(2.0))
    loss, grads = jax.value_and_grad(loss_of)(args)
    return np.asarray(loss), tuple(np.asarray(g) for g in grads)


def test_pipeline_1f1b_loss_matches_closed_form(batch):
    ws = [stage_weight(p) for p in range(PP)]
    want = closed_form(batch, 1.5, ws, 2.0)
    loss, _, _, _ = run_pipeline(batch)
    np.testing.assert_allclose(loss.item(), want, rtol=1e-5)


def test_pipeline_1f1b_grads_match_sequential(batch):
    loss, gs, ge, gc = run_pipeline(batch)
    ref_loss, (rgs, rge, rgc) = sequential_reference_grads(batch)
    np.testing.assert_allclose(loss.item(), ref_loss.item(), rtol=1e-5)
    np.testing.assert_allclose(gs, rgs, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(ge, rge, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gc, rgc, rtol=1e-4, atol=1e-6)


def test_pipeline_interleaved_matches_sequential(batch):
    loss, gs, ge, gc = run_pipeline(batch, chunks=2)
    ref_loss, (rgs, rge, rgc) = sequential_reference_grads(batch, chunks=2)
    np.testing.assert_allclose(loss.item(), ref_loss.item(), rtol=1e-5)
    np.testing.assert_allclose(gs, rgs, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(ge, rge, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gc, rgc, rtol=1e-4, atol=1e-6)


def test_pipeline_1f1b_matches_adscan(batch):
    """The O(pp)-memory 1f1b core and the AD-of-scan core are the same
    function: identical loss and all three grad trees."""
    a = run_pipeline(batch, impl="1f1b")
    b = run_pipeline(batch, impl="adscan")
    for got, want in zip(a, b):
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-7)


@pytest.mark.parametrize("m", [1, 2])
def test_pipeline_1f1b_fewer_microbatches_than_stages(m):
    """M < pp exercises a pipeline that never reaches steady state —
    every tick is warmup/cooldown masking."""
    rng = np.random.RandomState(1)
    small = rng.randn(m, 2, HID).astype(np.float32)
    loss, gs, ge, gc = run_pipeline(small, impl="1f1b", num_microbatches=m)
    ref_loss, (rgs, rge, rgc) = sequential_reference_grads(
        small, num_microbatches=m)
    np.testing.assert_allclose(loss.item(), ref_loss.item(), rtol=1e-5)
    np.testing.assert_allclose(gs, rgs, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(ge, rge, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(gc, rgc, rtol=1e-4, atol=1e-6)


def test_pipeline_impl_knob_validation(batch):
    with pytest.raises(ValueError, match="unknown pipeline impl"):
        run_pipeline(batch, impl="bogus")
    # validation applies on the forward-only path too
    with pytest.raises(ValueError, match="unknown pipeline impl"):
        run_pipeline(batch, impl="bogus", forward_only=True)


def test_pipeline_interleaved_1f1b_matches_sequential(batch):
    """The 1f1b core's virtual-chunk rings (per-chunk save/replay with
    the mirrored cotangent chunk-wrap) against the closed-form
    sequential composition — and against the AD-scan interleaved core."""
    mesh = pp_mesh()
    stacked = np.stack([stage_weight(p, 2) for p in range(PP)])

    def run(impl):
        def body(mbs, sp):
            loss, grads = forward_backward_pipelining_with_interleaving(
                (stage_fn, embed_fn, loss_fn), mbs,
                (sp[0], jnp.asarray(1.5), jnp.asarray(2.0)),
                num_microbatches=M, num_model_chunks=2, axis_name="pp",
                impl=impl)
            return loss, grads[0][None], grads[1], grads[2]

        f = shard_map(body, mesh=mesh, in_specs=(P(), P("pp")),
                      out_specs=(P(), P("pp"), P(), P()), check_vma=False)
        out = jax.jit(f)(jnp.asarray(batch), jnp.asarray(stacked))
        return tuple(np.asarray(o) for o in out)

    got = run("1f1b")
    ref_loss, (rgs, rge, rgc) = sequential_reference_grads(batch, chunks=2)
    np.testing.assert_allclose(got[0].item(), ref_loss.item(), rtol=1e-5)
    np.testing.assert_allclose(got[1], rgs, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(got[2], rge, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(got[3], rgc, rtol=1e-4, atol=1e-6)
    ad = run("adscan")
    for g, w in zip(got, ad):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)


def test_pipeline_forward_only(batch):
    ws = [stage_weight(p) for p in range(PP)]
    want = closed_form(batch, 1.5, ws, 2.0)
    loss, _, _, _ = run_pipeline(batch, forward_only=True)
    np.testing.assert_allclose(loss.item(), want, rtol=1e-5)


def test_no_pipelining_matches_sequential(batch):
    """no-pipelining grad accumulation == mean of per-microbatch grads
    (reference: fwd_bwd_no_pipelining.py:31)."""
    stacked = jnp.asarray(np.stack([stage_weight(p) for p in range(PP)]))

    def full_loss(params, mb):
        sp, e, c = params
        h = embed_fn(e, mb)
        for p in range(PP):
            h = stage_fn(sp[p], h, 0)
        return loss_fn(c, h, mb)

    params = (stacked, jnp.asarray(1.5), jnp.asarray(2.0))
    losses, grads = forward_backward_no_pipelining(
        full_loss, jnp.asarray(batch), params)

    ref_loss, (rgs, rge, rgc) = sequential_reference_grads(batch)
    np.testing.assert_allclose(np.mean(np.asarray(losses)), ref_loss,
                               rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grads[0]), rgs, rtol=1e-4,
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(grads[1]), rge, rtol=1e-4,
                               atol=1e-6)


def test_get_forward_backward_func_dispatch():
    """Reference: schedules/__init__.py:19-35."""
    assert (get_forward_backward_func(None, 1)
            is forward_backward_no_pipelining)
    assert (get_forward_backward_func(None, 4)
            is forward_backward_pipelining_without_interleaving)
    f = get_forward_backward_func(2, 4)
    assert f.func is forward_backward_pipelining_with_interleaving
    assert f.keywords == {"num_model_chunks": 2}


# ------------------------------ microbatches -------------------------------

def test_constant_microbatches():
    """Port of test_microbatches.py."""
    calc = ConstantNumMicroBatches(32, 2, 4)
    assert calc.get() == 4
    assert calc.get_current_global_batch_size() == 32
    with pytest.raises(AssertionError):
        ConstantNumMicroBatches(33, 2, 4)


def test_rampup_zero_ramp_samples():
    """ramp_samples=0 with start < final is an instant ramp, not a
    division by zero (the constructor itself admits ramp_samples >= 0)."""
    calc = RampupBatchsizeNumMicroBatches(
        start_batch_size=4, batch_size_increment=4, ramup_samples=0,
        global_batch_size=8, micro_batch_size=1, data_parallel_size=1)
    assert calc.get_current_global_batch_size() == 8
    assert calc.get() == 8


def test_rampup_microbatches():
    calc = RampupBatchsizeNumMicroBatches(
        start_batch_size=8, batch_size_increment=8, ramup_samples=80,
        global_batch_size=32, micro_batch_size=2, data_parallel_size=2)
    assert calc.get_current_global_batch_size() == 8
    assert calc.get() == 2
    calc.update(40, True)
    assert calc.get_current_global_batch_size() == 8 + 8
    calc.update(100, True)
    assert calc.get_current_global_batch_size() == 32
    assert calc.get() == 8


# ---------------------------------- p2p ------------------------------------

def test_p2p_send_forward_recv_forward():
    """Port of test_p2p_comm.py: each stage receives the previous stage's
    tensor; stage 0 receives zeros."""
    mesh = pp_mesh(NDEV)
    xs = jnp.arange(NDEV, dtype=jnp.float32).reshape(NDEV, 1)

    f = shard_map(
        lambda x: p2p_communication.send_forward_recv_forward(x, "pp"),
        mesh=mesh, in_specs=(P("pp"),), out_specs=P("pp"), check_vma=False)
    out = np.asarray(f(xs)).ravel()
    np.testing.assert_array_equal(out, [0.0] + list(range(NDEV - 1)))


def test_p2p_send_backward_recv_backward():
    mesh = pp_mesh(NDEV)
    xs = jnp.arange(NDEV, dtype=jnp.float32).reshape(NDEV, 1)
    f = shard_map(
        lambda x: p2p_communication.send_backward_recv_backward(x, "pp"),
        mesh=mesh, in_specs=(P("pp"),), out_specs=P("pp"), check_vma=False)
    out = np.asarray(f(xs)).ravel()
    np.testing.assert_array_equal(out, list(range(1, NDEV)) + [0.0])


# ------------------------------- ltor masks --------------------------------

def test_ltor_masks_and_position_ids():
    data = jnp.asarray([[5, 1, 7, 1, 3]])  # eod = 1
    mask, loss_mask, pos = get_ltor_masks_and_position_ids(
        data, eod_token=1, eod_mask_loss=True)
    assert mask.shape == (1, 1, 5, 5)
    # causal: position 0 can only see itself → masked True above diagonal
    assert bool(mask[0, 0, 0, 1])
    assert not bool(mask[0, 0, 1, 0])
    np.testing.assert_array_equal(np.asarray(loss_mask[0]),
                                  [1, 0, 1, 0, 1])
    np.testing.assert_array_equal(np.asarray(pos[0]), np.arange(5))


def test_ltor_reset_position_ids():
    data = jnp.asarray([[5, 1, 7, 2, 3]])  # eod at index 1
    _, _, pos = get_ltor_masks_and_position_ids(
        data, eod_token=1, reset_position_ids=True)
    np.testing.assert_array_equal(np.asarray(pos[0]), [0, 1, 0, 1, 2])


# ------------------------ deep-factor topologies ---------------------------
# tp=4 and pp=4 programs have size-dependent behaviour (_sharded_init
# slicing, ring wraps, per-stage layer counts) that a (2, 2, 2) mesh never
# compiles — exercise the full factor grid on the 8-device CPU mesh
# (reference: parallel_state.py initialize grid tests).

@pytest.mark.parametrize("topology", [
    # all slow-tier: deep-pp scheduling is covered fast by the analytic
    # PP=4 schedule tests above, and the driver's dryrun_multichip runs
    # the full 3D GPT step (with loss parity) every round
    pytest.param((4, 1, 2), marks=pytest.mark.slow),
    pytest.param((2, 1, 4), marks=pytest.mark.slow),
    pytest.param((4, 2, 1), marks=pytest.mark.slow),
    pytest.param((1, 2, 4), marks=pytest.mark.slow),
])
def test_minimal_gpt_training_deep_topologies(topology):
    from apex_tpu.transformer.testing.minimal import run_minimal_gpt_training

    losses = run_minimal_gpt_training(
        n_devices=8, topology=topology, num_microbatches=4,
        micro_batch_size=1, seq_len=16, num_steps=2)
    assert len(losses) == 2
    assert all(np.isfinite(l) for l in losses)


@pytest.mark.slow  # the driver runs this exact assertion every round via
# __graft_entry__.dryrun_multichip; the slow tier keeps it pytest-visible
def test_minimal_gpt_loss_parity_vs_single_device():
    """The 8-device (pp, dp, tp) first-step loss must equal a sequential
    1-device replay of the same model/init/batch — the same check
    __graft_entry__.dryrun_multichip asserts for the driver."""
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.minimal import (
        reference_first_step_loss,
        run_minimal_gpt_training,
        toy_batch,
    )

    pp, dp, tp = 2, 2, 2
    cfg = TransformerConfig(
        hidden_size=64, num_layers=2 * pp, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=16,
        hidden_dropout=0.0, attention_dropout=0.0, bf16=True,
        apply_query_key_layer_scaling=False)
    losses = run_minimal_gpt_training(
        n_devices=8, cfg=cfg, topology=(pp, dp, tp), num_microbatches=4,
        micro_batch_size=2, seq_len=16, num_steps=1)
    ref = reference_first_step_loss(
        cfg, pp, toy_batch(cfg.vocab_size, 4, 2 * dp, 16))
    assert abs(losses[0] - ref) <= 0.05, (losses[0], ref)


@pytest.mark.slow  # pytest twin of the round-5 dryrun_multichip check
def test_minimal_gpt_trajectory_and_grad_norm_parity():
    """3 training steps of the (2, 2, 2) run track the sequential
    1-device replay in BOTH per-step loss and unscaled global grad norm
    — the trajectory version of the parity above (a wrong-but-small
    gradient error passes a single-step loss check but not this)."""
    from apex_tpu.transformer.testing import TransformerConfig
    from apex_tpu.transformer.testing.minimal import (
        reference_training,
        run_minimal_gpt_training,
        toy_batch,
    )

    pp, dp, tp = 2, 2, 2
    cfg = TransformerConfig(
        hidden_size=64, num_layers=2 * pp, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=16,
        hidden_dropout=0.0, attention_dropout=0.0, bf16=True,
        apply_query_key_layer_scaling=False)
    losses, gnorms = run_minimal_gpt_training(
        n_devices=8, cfg=cfg, topology=(pp, dp, tp), num_microbatches=4,
        micro_batch_size=2, seq_len=16, num_steps=3,
        return_grad_norms=True)
    ref_losses, ref_gnorms = reference_training(
        cfg, pp, toy_batch(cfg.vocab_size, 4, 2 * dp, 16), num_steps=3)
    for l, rl in zip(losses, ref_losses):
        assert abs(l - rl) <= 0.05, (losses, ref_losses)
    for g, rg in zip(gnorms, ref_gnorms):
        assert abs(g - rg) <= 0.05 * max(rg, 1e-6), (gnorms, ref_gnorms)


def test_dryrun_multichip_topology_plan_includes_16_way():
    """__graft_entry__.dryrun_multichip(16) (VERDICT #6 remainder) must
    drive the capped factorization (2, 4, 2) AND the deeper explicit
    pp=4/dp=2/tp=2 mesh — asserted on the topology plan here (fast);
    the full 16-way parity run is the slow twin below."""
    import __graft_entry__
    from apex_tpu.transformer.testing.minimal import factorize_mesh

    assert factorize_mesh(16) == (2, 4, 2)
    assert __graft_entry__.dryrun_topologies(16) == [(2, 4, 2), (4, 2, 2)]
    # every plan factorizes its device count exactly (the 32/64 plans
    # may declare dp as an (inner, outer) pair — ISSUE 8; the
    # hierarchical-plan content asserts live in tests/test_collectives)
    from apex_tpu.transformer.testing.minimal import dp_axes_of

    for n in (1, 2, 4, 8, 16, 32, 64):
        for pp, dp, tp in __graft_entry__.dryrun_topologies(n):
            dp_size = dp_axes_of(dp)[0]
            assert pp * dp_size * tp == n, (n, pp, dp, tp)


@pytest.mark.slow  # pytest twin of the driver's dryrun_multichip(16):
# own subprocess because it needs 16 virtual devices (conftest pins 8)
def test_dryrun_multichip_16_parity_subprocess():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(16)"],
        capture_output=True, text=True, timeout=1800, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "trajectory + grad-norm parity ok across 2 topologies" \
        in out.stdout
    assert "pp=4/dp=2/tp=2" in out.stdout


@pytest.mark.slow  # the ISSUE-8 widened twin: 32 virtual devices, pp=8
# and a hierarchically factored dp pair under the same parity oracle +
# compressed-vs-uncompressed comm accounting in the MULTICHIP tail
def test_dryrun_multichip_32_parity_subprocess():
    import os
    import subprocess
    import sys

    env = dict(os.environ, JAX_PLATFORMS="cpu")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        [sys.executable, "-c",
         "import __graft_entry__; __graft_entry__.dryrun_multichip(32)"],
        capture_output=True, text=True, timeout=3500, env=env, cwd=repo)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "trajectory + grad-norm parity ok across 4 topologies" \
        in out.stdout
    assert "pp=8/dp=2/tp=2" in out.stdout
    assert "dp=(2, 4)" in out.stdout        # the hierarchical mesh ran
    assert "comm_int8[" in out.stdout       # compressed twin stamped
