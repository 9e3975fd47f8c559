"""Overlap subsystem (apex_tpu.overlap, ISSUE 14) — the proof surface.

All on the conftest 8-device CPU mesh, no TPU window required:

* knob home (CLAUDE.md asymmetry): per-call raises on un-honorable
  requests; setter/env preferences fall back; bucket count resolves
  per-call > setter > env > dispatch table > built-in;
* jaxpr-level schedule proof: with ``APEX_OVERLAP_GRAD=bucketed`` the
  per-bucket dp collectives INTERLEAVE with remaining-backward compute
  (``costs.collective_schedule`` verdict), terminal with it off — and
  with every knob off the emitted programs are byte-identical to the
  pre-overlap pair;
* 20-step trajectory parity bucketed-vs-terminal on the dp mesh,
  plain (exact) and composed with the int8 + hierarchical collectives
  (tolerance band — per-bucket quantization boundaries differ);
* prefetch determinism / order / backpressure / error propagation;
* serving overlap: token-for-token parity vs the serial engine under
  admit/evict churn (prefix cache + sampling composed), lifecycle
  event order + the one-compile contract preserved, ``flush()``
  semantics, the spec-decode raise/fallback;
* check 10 (tools/check_bench_labels.overlap_problems) both
  directions, and the profile_overlap smoke CLI end-to-end (on the
  session-shared smoke compile cache — the PR 6 fast-tier rule:
  deeper cache sharing, not demotion).
"""

import importlib.util
import json
import os
import subprocess
import sys
import threading
import time
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import dispatch
from apex_tpu import overlap as overlap_mod
from apex_tpu.overlap import bucketed as bucketed_mod
from apex_tpu.overlap import prefetch as prefetch_mod
from apex_tpu.parallel.distributed import (
    DistributedDataParallel,
    allreduce_gradients,
)
from apex_tpu.telemetry import costs
from apex_tpu.transformer.parallel_state import (
    PIPELINE_AXIS,
    TENSOR_AXIS,
)
from apex_tpu.transformer.testing import TransformerConfig
from apex_tpu.transformer.testing.minimal import (
    dp_axes_of,
    dp_axis_arg,
    gpt_train_step_fn,
    make_gpt_fns,
    toy_batch,
    training_collective_schedule,
    training_comm_bytes,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_knobs(monkeypatch):
    for k in ("APEX_OVERLAP_GRAD", "APEX_OVERLAP_BUCKETS",
              "APEX_PREFETCH", "APEX_SERVE_OVERLAP", "APEX_DISPATCH",
              "APEX_DISPATCH_TABLE", "APEX_GRAD_COMPRESS",
              "APEX_HIER_ALLREDUCE", "APEX_SPEC_DECODE"):
        monkeypatch.delenv(k, raising=False)
    overlap_mod._reset_for_tests()
    dispatch._reset_for_tests()
    yield
    overlap_mod._reset_for_tests()
    dispatch._reset_for_tests()


def _jx(fn, *args):
    """Trace with a FRESH function object (jax trace caches key on
    identity; knob resolution is trace-time)."""
    return str(jax.make_jaxpr(lambda *a: fn(*a))(*args))


def _mesh(n, names=("dp",), shape=None):
    return Mesh(np.array(jax.devices()[:n]).reshape(shape or (n,)), names)


MINI_CFG = TransformerConfig(
    hidden_size=32, num_layers=2, num_attention_heads=4,
    vocab_size=64, max_position_embeddings=16,
    hidden_dropout=0.0, attention_dropout=0.0, bf16=True,
    apply_query_key_layer_scaling=False)


# ------------------------------------------------------------- knobs

def test_grad_overlap_resolution(monkeypatch):
    with pytest.raises(ValueError, match="unknown grad-overlap"):
        overlap_mod.resolve_grad_overlap("greedy")
    with pytest.raises(ValueError, match="unknown grad-overlap"):
        overlap_mod.set_grad_overlap("greedy")
    assert overlap_mod.resolve_grad_overlap() == "off"
    monkeypatch.setenv("APEX_OVERLAP_GRAD", "bucketed")
    assert overlap_mod.resolve_grad_overlap() == "bucketed"
    # an unknown env value is a preference: warn once, stay off
    monkeypatch.setenv("APEX_OVERLAP_GRAD", "sideways")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert overlap_mod.resolve_grad_overlap() == "off"
    assert any("sideways" in str(x.message) for x in w)
    # setter beats env; per-call beats setter
    monkeypatch.setenv("APEX_OVERLAP_GRAD", "bucketed")
    overlap_mod.set_grad_overlap("off")
    assert overlap_mod.resolve_grad_overlap() == "off"
    assert overlap_mod.resolve_grad_overlap("bucketed") == "bucketed"


def test_buckets_resolution_precedence(tmp_path, monkeypatch):
    for bad in (0, -1, True, 2.5):
        with pytest.raises(ValueError):
            overlap_mod.resolve_buckets(bad)
    assert overlap_mod.resolve_buckets() == overlap_mod.DEFAULT_BUCKETS
    # dispatch-table tier (op "overlap_buckets", keyed on the payload)
    table = tmp_path / "table.jsonl"
    entry = dispatch.make_entry("overlap_buckets", {"n": 1000},
                                "float32", "cpu", "8", "lg-x")
    table.write_text(json.dumps(entry) + "\n")
    monkeypatch.setenv("APEX_DISPATCH_TABLE", str(table))
    assert overlap_mod.resolve_buckets(nelems=1000) == 8
    # non-digit table choice degrades to the built-in default
    entry["choice"] = "many"
    table.write_text(json.dumps(entry) + "\n")
    dispatch._reset_for_tests()
    assert overlap_mod.resolve_buckets(nelems=1000) == \
        overlap_mod.DEFAULT_BUCKETS
    # env beats table, setter beats env, per-call beats setter
    entry["choice"] = "8"
    table.write_text(json.dumps(entry) + "\n")
    dispatch._reset_for_tests()
    monkeypatch.setenv("APEX_OVERLAP_BUCKETS", "6")
    assert overlap_mod.resolve_buckets(nelems=1000) == 6
    overlap_mod.set_overlap_buckets(5)
    assert overlap_mod.resolve_buckets(nelems=1000) == 5
    assert overlap_mod.resolve_buckets(3, nelems=1000) == 3
    with pytest.raises(ValueError):
        overlap_mod.set_overlap_buckets(-2)


def test_prefetch_resolution(monkeypatch):
    assert overlap_mod.resolve_prefetch() == 0
    monkeypatch.setenv("APEX_PREFETCH", "3")
    assert overlap_mod.resolve_prefetch() == 3
    monkeypatch.setenv("APEX_PREFETCH", "0")
    assert overlap_mod.resolve_prefetch() == 0
    monkeypatch.setenv("APEX_PREFETCH", "deep")
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        assert overlap_mod.resolve_prefetch() == 0
    assert any("deep" in str(x.message) for x in w)
    assert overlap_mod.resolve_prefetch(2) == 2
    assert overlap_mod.resolve_prefetch(0) == 0
    for bad in (-1, True, 1.5):
        with pytest.raises(ValueError):
            overlap_mod.resolve_prefetch(bad)


def test_serve_overlap_resolution(monkeypatch):
    assert overlap_mod.resolve_serve_overlap() is False
    monkeypatch.setenv("APEX_SERVE_OVERLAP", "1")
    assert overlap_mod.resolve_serve_overlap() is True
    # preference falls back when speculation is engaged; a per-call
    # demand raises instead (the count-function contract)
    assert overlap_mod.resolve_serve_overlap(spec_k=3) is False
    with pytest.raises(ValueError, match="speculative"):
        overlap_mod.resolve_serve_overlap(True, spec_k=3)
    with pytest.raises(ValueError):
        overlap_mod.resolve_serve_overlap("yes")
    assert overlap_mod.resolve_serve_overlap(False, spec_k=3) is False


# ----------------------------------------------------- bucketed core

def test_bucket_partition_properties():
    leaves = [jnp.zeros((s,)) for s in (100, 1, 1, 50, 200, 3, 7)]
    for nb in (1, 2, 3, len(leaves), len(leaves) + 5):
        bounds = bucketed_mod._partition(leaves, nb)
        # contiguous, covering, ordered
        assert bounds[0][0] == 0 and bounds[-1][1] == len(leaves)
        for (a, b), (c, d) in zip(bounds, bounds[1:]):
            assert b == c and a < b
        assert len(bounds) == min(nb, len(leaves))


def test_bucketed_value_and_grad_off_is_byte_identical():
    """Knobs off, the helper emits the EXACT historical program —
    jax.value_and_grad + one terminal allreduce_gradients (the ISSUE
    14 byte-identity acceptance criterion)."""
    mesh = _mesh(4)
    params = {"a": jnp.ones((8, 4), jnp.float32),
              "b": jnp.ones((4,), jnp.float32)}
    x = jnp.ones((2, 8), jnp.float32)

    def loss_fn(p, x):
        return jnp.sum(jnp.tanh(x @ p["a"]) + p["b"])

    def manual(p, x):
        loss, grads = jax.value_and_grad(loss_fn)(p, x)
        return loss, allreduce_gradients(grads, "dp")

    helper = bucketed_mod.bucketed_value_and_grad(loss_fn, "dp")
    sm = lambda f: shard_map(f, mesh=mesh, in_specs=(P(), P()),
                             out_specs=(P(), P()), check_vma=False)
    off_jx = _jx(sm(helper), params, x)
    assert off_jx == _jx(sm(manual), params, x)
    bucketed = bucketed_mod.bucketed_value_and_grad(
        loss_fn, "dp", overlap="bucketed", buckets=2)
    assert _jx(sm(bucketed), params, x) != off_jx


def test_bucketed_grads_match_and_interleave():
    """The core schedule claim on a layered model: bucketed grads ==
    terminal grads numerically, and the jaxpr-order verdict flips
    terminal -> interleaved (later-layer buckets reduce first)."""
    mesh = _mesh(8)
    ws = {f"layer_{i}": jnp.eye(8) * 0.3 + 0.01 for i in range(4)}
    x = jnp.arange(16, dtype=jnp.float32).reshape(2, 8) / 16.0

    def loss_fn(ws, x):
        h = x
        for i in range(4):
            h = jnp.tanh(h @ ws[f"layer_{i}"])
        return jnp.sum(h)

    def run(fn):
        g = shard_map(fn, mesh=mesh, in_specs=(P(), P()),
                      out_specs=(P(), P()), check_vma=False)
        verdict = costs.collective_schedule(
            jax.make_jaxpr(g)(ws, x), axes=("dp",))
        loss, grads = jax.jit(g)(ws, x)
        return verdict, np.asarray(loss), grads

    v_t, l_t, g_t = run(bucketed_mod.bucketed_value_and_grad(
        loss_fn, "dp"))
    v_b, l_b, g_b = run(bucketed_mod.bucketed_value_and_grad(
        loss_fn, "dp", overlap="bucketed", buckets=4))
    assert v_t["verdict"] == "terminal"
    assert v_b["verdict"] == "interleaved"
    assert v_b["compute_after_first_collective"] > 0
    assert np.allclose(l_t, l_b)
    for k in g_t:
        assert np.allclose(np.asarray(g_t[k]), np.asarray(g_b[k]),
                           rtol=1e-6, atol=1e-6), k


def test_minimal_step_schedule_verdicts_and_comm(monkeypatch):
    """The committed acceptance proof: the minimal-GPT dp train step's
    per-bucket collectives interleave with remaining-backward compute
    under APEX_OVERLAP_GRAD=bucketed and stay terminal off — judged on
    the dp axes (costs.collective_schedule) — including composed with
    int8 + the hierarchical dp pair; the bucketed per-microbatch
    reduction's M-times dp payload is counted honestly."""
    devs = jax.devices()[:8]
    term = training_collective_schedule(devs, MINI_CFG, (1, 8, 1),
                                        num_microbatches=2)
    buck = training_collective_schedule(devs, MINI_CFG, (1, 8, 1),
                                        num_microbatches=2,
                                        overlap_grad="bucketed")
    assert term["verdict"] == "terminal"
    assert buck["verdict"] == "interleaved"
    assert buck["compute_after_first_collective"] > 0
    # ...the env preference selects the same program as the per-call
    monkeypatch.setenv("APEX_OVERLAP_GRAD", "bucketed")
    via_env = training_collective_schedule(devs, MINI_CFG, (1, 8, 1),
                                           num_microbatches=2)
    assert via_env["verdict"] == "interleaved"
    monkeypatch.delenv("APEX_OVERLAP_GRAD")
    # composed with the PR 8 collectives over a factored dp pair
    both = training_collective_schedule(
        devs, MINI_CFG, (1, (2, 4), 1), num_microbatches=2,
        overlap_grad="bucketed", compress="int8", hierarchical=True)
    assert both["verdict"] == "interleaved"
    # hook-per-backward semantics: M microbatches -> M reductions
    c_t = training_comm_bytes(devs, MINI_CFG, (1, 8, 1),
                              num_microbatches=2)
    c_b = training_comm_bytes(devs, MINI_CFG, (1, 8, 1),
                              num_microbatches=2,
                              overlap_grad="bucketed")
    assert c_b["dp"] > 1.9 * c_t["dp"]


def test_minimal_step_off_knob_leaves_jaxpr_unchanged(monkeypatch):
    """APEX_OVERLAP_GRAD=off (and unset) emit byte-identical minimal
    train-step programs — the knob's disabled mode costs nothing.
    (The model's pre-existing custom_vjp equations print live object
    ADDRESSES in their params, so the comparison scrubs `0x...` — the
    program structure and every literal must still match byte for
    byte.)"""
    import re

    devs = jax.devices()[:8]
    from apex_tpu.transformer.testing.minimal import \
        _traced_training_jaxpr

    def scrub(jx):
        return re.sub(r"0x[0-9a-f]+", "0xADDR", str(jx))

    default, _, _, _ = _traced_training_jaxpr(devs, MINI_CFG, (1, 8, 1),
                                              num_microbatches=2)
    monkeypatch.setenv("APEX_OVERLAP_GRAD", "off")
    explicit_off, _, _, _ = _traced_training_jaxpr(
        devs, MINI_CFG, (1, 8, 1), num_microbatches=2)
    assert scrub(default) == scrub(explicit_off)


def test_pp_pipeline_demand_raises_preference_falls_back(monkeypatch):
    with pytest.raises(ValueError, match="pp=2"):
        gpt_train_step_fn(MINI_CFG, 2, 2, overlap_grad="bucketed")
    # the env preference falls back silently (still builds)
    monkeypatch.setenv("APEX_OVERLAP_GRAD", "bucketed")
    step, _, _ = gpt_train_step_fn(
        TransformerConfig(
            hidden_size=32, num_layers=4, num_attention_heads=4,
            vocab_size=64, max_position_embeddings=16,
            hidden_dropout=0.0, attention_dropout=0.0, bf16=True,
            apply_query_key_layer_scaling=False), 2, 2)
    assert step is not None


def test_ddp_ctor_overlap_knobs():
    with pytest.raises(ValueError, match="unknown grad-overlap"):
        DistributedDataParallel(overlap_grad="greedy")
    with pytest.raises(ValueError):
        DistributedDataParallel(overlap_buckets=0)
    mesh = _mesh(4)
    params = {"w": jnp.ones((6, 2), jnp.float32)}
    x = jnp.ones((3, 6), jnp.float32)

    def loss_fn(p, x):
        return jnp.sum(x @ p["w"])

    ddp = DistributedDataParallel(axis_name="dp")

    def manual(p, x):
        loss, grads = jax.value_and_grad(loss_fn)(p, x)
        return loss, allreduce_gradients(grads, "dp")

    sm = lambda f: shard_map(f, mesh=mesh, in_specs=(P(), P()),
                             out_specs=(P(), P()), check_vma=False)
    assert _jx(sm(ddp.value_and_grad(loss_fn)), params, x) \
        == _jx(sm(manual), params, x)


def _run_traj(overlap, steps, compress=None, hier=None, dp_decl=8):
    devs = jax.devices()[:8]
    dp_size, dp_names, dp_sizes = dp_axes_of(dp_decl)
    mesh = Mesh(np.asarray(devs).reshape(1, *dp_sizes, 1),
                (PIPELINE_AXIS, *dp_names, TENSOR_AXIS))
    dp_axes = dp_axis_arg(dp_names)
    _, init_params = make_gpt_fns(MINI_CFG, 1)
    step, tx, scaler = gpt_train_step_fn(
        MINI_CFG, 1, 2, dp_axes=dp_axes, compress=compress,
        hierarchical=hier, overlap_grad=overlap)
    batch = toy_batch(MINI_CFG.vocab_size, 2, 2 * dp_size, 16)
    spec = P(None, dp_axes)

    def whole(batch):
        params = init_params(jax.random.PRNGKey(0),
                             {k: v[0] for k, v in batch.items()})
        o, ss = tx.init(params), scaler.init()

        def body(carry, _):
            p, o, ss = carry
            p, o, ss, loss = step(p, o, ss, batch)[:4]
            return (p, o, ss), lax.pmean(loss, dp_axes)

        _, losses = lax.scan(body, (params, o, ss), jnp.arange(steps))
        return losses

    f = jax.jit(shard_map(whole, mesh=mesh,
                          in_specs=({"ids": spec, "labels": spec},),
                          out_specs=P(), check_vma=False))
    return np.asarray(jax.block_until_ready(f(batch)))


def test_trajectory_parity_bucketed_vs_terminal_20_steps():
    """Bucketed-vs-terminal over 20 steps on the 8-device dp mesh:
    the plain path is EXACT (per-microbatch psum-then-accumulate is
    the same float program as accumulate-then-psum here); composed
    with int8 + the hierarchical dp pair the trajectories track
    inside a tolerance band (per-bucket quantization block boundaries
    differ from the one-flat-buffer terminal path)."""
    t = _run_traj("off", 20)
    b = _run_traj("bucketed", 20)
    assert np.allclose(t, b, rtol=0, atol=0), np.abs(t - b).max()
    tq = _run_traj("off", 20, compress="int8", hier=True,
                   dp_decl=(2, 4))
    bq = _run_traj("bucketed", 20, compress="int8", hier=True,
                   dp_decl=(2, 4))
    assert np.all(np.isfinite(tq)) and np.all(np.isfinite(bq))
    assert np.allclose(tq, bq, rtol=2e-3, atol=2e-3), \
        np.abs(tq - bq).max()


# ----------------------------------------------------- costs helpers

def test_collective_schedule_axes_and_degradation():
    mesh = _mesh(8, names=("dp",))

    def with_fwd_psum(w, x):
        # a forward collective over another axis must not drown the
        # dp grad verdict when the axes filter names dp only
        h = jnp.tanh(x @ w)
        loss = jnp.sum(h)
        g = jax.grad(lambda w: jnp.sum(jnp.tanh(x @ w)))(w)
        return loss, lax.psum(g, "dp")

    jx = jax.make_jaxpr(shard_map(
        with_fwd_psum, mesh=mesh, in_specs=(P(), P()),
        out_specs=(P(), P()), check_vma=False))(
            jnp.ones((4, 4)), jnp.ones((2, 4)))
    assert costs.collective_schedule(jx, axes=("dp",))["verdict"] \
        == "terminal"
    # no collectives / unwalkable input degrade, never raise
    none = costs.collective_schedule(
        jax.make_jaxpr(lambda x: x * 2)(jnp.ones(3)))
    assert none["verdict"] == "no-collectives"
    assert costs.collective_schedule(object())["verdict"] \
        == "no-collectives"


def test_comm_ms_from_axis_bytes():
    assert costs.comm_ms_from_axis_bytes(None, costs.V5E_KIND) is None
    assert costs.comm_ms_from_axis_bytes({}, costs.V5E_KIND) == 0.0
    assert costs.comm_ms_from_axis_bytes({"dp": 1}, "cpu") is None
    ms = costs.comm_ms_from_axis_bytes(
        {"dp": costs.V5E_ICI_BYTES_PER_S_ENVELOPE}, costs.V5E_KIND)
    assert abs(ms - 1e3) < 1e-6


def test_capture_overlap_bound_passthrough():
    block = costs.capture(steps=2, device_kind=costs.V5E_KIND, host_ms=0.5,
                          comm_ms=0.25)
    ob = block["overlap_bound"]
    assert ob["host_ms"] == 0.5 and ob["comm_ms"] == 0.25
    assert ob["comm_host_ms"] == 0.75
    assert not costs.validate(block)
    from apex_tpu.telemetry import ledger
    rec = ledger.make_record("t", "cpu", None, None,
                             extra={"cost": block})
    assert not ledger.validate_record(rec)


# ----------------------------------------------------------- prefetch

def test_prefetch_order_and_determinism(monkeypatch):
    batches = [np.full((4,), i, np.int32) for i in range(7)]
    want = [list(b) for b in batches]
    for depth in (0, 1, 2, 5):
        got = [list(np.asarray(x))
               for x in prefetch_mod.prefetch(iter(batches),
                                              depth=depth)]
        assert got == want, depth
    # env resolution drives the same path
    monkeypatch.setenv("APEX_PREFETCH", "2")
    got = [list(np.asarray(x)) for x in
           prefetch_mod.prefetch(iter(batches))]
    assert got == want


def test_prefetch_backpressure_bounded():
    produced = []

    def gen():
        for i in range(8):
            produced.append(i)
            yield np.full((2,), i, np.int32)

    it = prefetch_mod.prefetch(gen(), depth=2)
    first = next(it)
    deadline = time.time() + 5.0
    # producer may run at most depth ahead of the consumer (+1 for
    # the item blocked in q.put)
    while len(produced) < 4 and time.time() < deadline:
        time.sleep(0.01)
    time.sleep(0.1)
    assert len(produced) <= 4, produced  # 1 consumed + 2 queued + 1 blocked
    rest = [int(np.asarray(x)[0]) for x in it]
    assert [int(np.asarray(first)[0])] + rest == list(range(8))


def test_prefetch_error_propagates_and_early_close():
    def bad():
        yield np.zeros((2,), np.int32)
        raise RuntimeError("decode exploded")

    it = prefetch_mod.prefetch(bad(), depth=2)
    next(it)
    with pytest.raises(RuntimeError, match="decode exploded"):
        next(it)
    # a consumer that stops early must not leave a blocked producer
    n_threads = threading.active_count()
    it2 = prefetch_mod.prefetch(
        (np.full((2,), i, np.int32) for i in range(100)), depth=1)
    next(it2)
    it2.close()
    deadline = time.time() + 5.0
    while threading.active_count() > n_threads and time.time() < deadline:
        time.sleep(0.01)
    assert threading.active_count() <= n_threads


def test_staging_seconds_measures():
    s = prefetch_mod.staging_seconds(np.zeros((64, 64), np.float32),
                                     reps=2)
    assert isinstance(s, float) and s > 0


# ------------------------------------------------------------ serving

SERVE_CFG = TransformerConfig(
    hidden_size=64, num_layers=2, num_attention_heads=4,
    vocab_size=128, max_position_embeddings=64,
    hidden_dropout=0.0, attention_dropout=0.0,
    apply_query_key_layer_scaling=False, bf16=True)


@pytest.fixture(scope="module")
def serve_params():
    from apex_tpu.serving import model as smodel

    return smodel.init_gpt_params(SERVE_CFG, 0)


def _clone(reqs):
    from apex_tpu.serving import Request

    return [Request(rid=r.rid, prompt=list(r.prompt),
                    max_new_tokens=r.max_new_tokens, arrival=r.arrival)
            for r in reqs]


def test_serve_overlap_token_parity_and_lifecycle(serve_params):
    from apex_tpu.serving import ServingEngine, lifecycle
    from apex_tpu.serving.scheduler import synthetic_trace

    reqs, _ = synthetic_trace(seed=3, n_requests=10, vocab=128,
                              prompt_lo=4, prompt_hi=16, new_lo=2,
                              new_hi=12, mean_interarrival=0.7)
    lifecycle.enable()
    try:
        serial = ServingEngine(SERVE_CFG, params=serve_params,
                               num_slots=3, page_size=8, num_pages=48,
                               max_seq=64, prefill_len=32,
                               overlap=False)
        done_s = serial.run_trace(_clone(reqs))
        ov = ServingEngine(SERVE_CFG, params=serve_params, num_slots=3,
                           page_size=8, num_pages=48, max_seq=64,
                           prefill_len=32, overlap=True)
        done_o = ov.run_trace(_clone(reqs))
    finally:
        lifecycle.reset_enabled()
    assert ov.overlap and not serial.overlap
    s = {r.rid: r.out_tokens for r in done_s}
    o = {r.rid: r.out_tokens for r in done_o}
    assert s == o
    assert None not in [t for ts in o.values() for t in ts]
    assert ov.tick == serial.tick  # same per-round schedule
    assert ov.decode_cache_size() == 1
    assert not ov.events.validate_order()
    for r in done_o:
        got = [e["event"] for e in ov.events.request_events(r.rid)]
        assert got == list(lifecycle.CORE_EVENTS), (r.rid, got)
    ov.allocator.check_invariants()


def test_serve_overlap_composes_with_prefix_and_sampling(serve_params):
    from apex_tpu.serving import ServingEngine
    from apex_tpu.serving.scheduler import synthetic_trace

    reqs, _ = synthetic_trace(seed=5, n_requests=8, vocab=128,
                              prompt_lo=4, prompt_hi=14, new_lo=2,
                              new_hi=10, mean_interarrival=0.6,
                              system_prompt=[7] * 9)
    a = ServingEngine(SERVE_CFG, params=serve_params, num_slots=3,
                      page_size=8, num_pages=48, max_seq=64,
                      prefill_len=32, prefix_cache=True, sampling=True,
                      overlap=False)
    da = a.run_trace(_clone(reqs))
    b = ServingEngine(SERVE_CFG, params=serve_params, num_slots=3,
                      page_size=8, num_pages=48, max_seq=64,
                      prefill_len=32, prefix_cache=True, sampling=True,
                      overlap=True)
    db = b.run_trace(_clone(reqs))
    assert {r.rid: r.out_tokens for r in da} \
        == {r.rid: r.out_tokens for r in db}
    assert b.generation_stats()["prefix_hit_rate"] > 0
    b.allocator.check_invariants()
    b.prefix.check_invariants()
    assert b.decode_cache_size() == 1 and b.prefill_cache_size() == 1


def test_serve_overlap_flush_fills_placeholders(serve_params):
    from apex_tpu.serving import Request, ServingEngine

    eng = ServingEngine(SERVE_CFG, params=serve_params, num_slots=2,
                        page_size=8, num_pages=32, max_seq=64,
                        prefill_len=32, overlap=True)
    req = Request(rid=0, prompt=[3, 1, 4, 1, 5], max_new_tokens=4)
    eng.submit(req)
    eng.step()   # admit + prefill + dispatch decode (in flight)
    assert req.out_tokens[0] is not None  # prefill's token is real
    eng.step()   # round 2: resolves round 1, dispatches round 2
    assert req.out_tokens[1] is not None
    assert req.out_tokens[-1] is None     # round 2 still in flight
    eng.flush()
    assert None not in req.out_tokens
    eng.flush()  # idempotent
    # done() is count-based: stepping to completion then flushing
    while not req.done():
        eng.step()
    eng.flush()
    assert len(req.out_tokens) == 4
    assert None not in req.out_tokens


def test_serve_overlap_spec_raises_env_falls_back(serve_params, monkeypatch):
    from apex_tpu.serving import ServingEngine

    # two per-call DEMANDS conflict: no honorable order, raise
    with pytest.raises(ValueError, match="speculative"):
        ServingEngine(SERVE_CFG, params=serve_params, num_slots=2,
                      page_size=8, num_pages=32, max_seq=64,
                      prefill_len=32, spec_decode=3, overlap=True)
    # overlap env PREFERENCE vs spec demand: overlap falls back
    monkeypatch.setenv("APEX_SERVE_OVERLAP", "1")
    eng = ServingEngine(SERVE_CFG, params=serve_params, num_slots=2,
                        page_size=8, num_pages=32, max_seq=64,
                        prefill_len=32, spec_decode=3)
    assert eng.overlap is False  # preference fell back, spec kept
    assert eng.spec_k == 3
    # overlap DEMAND vs spec env preference: the preference falls back
    # (speculation is token-identical to plain decode, so the demand
    # is honorable), overlap engages
    monkeypatch.delenv("APEX_SERVE_OVERLAP")
    monkeypatch.setenv("APEX_SPEC_DECODE", "3")
    eng2 = ServingEngine(SERVE_CFG, params=serve_params, num_slots=2,
                         page_size=8, num_pages=32, max_seq=64,
                         prefill_len=32, overlap=True)
    assert eng2.overlap is True and eng2.spec_k == 0


# ------------------------------------------------- check 10 + the CLI

def _cbl():
    tool = os.path.join(REPO, "tools", "check_bench_labels.py")
    spec = importlib.util.spec_from_file_location("cbl_overlap", tool)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check10_overlap_pin_match_both_directions():
    cbl = _cbl()
    ob_cost = {"overlap_bound": {"host_ms": 1.0, "comm_ms": None}}

    def rec(knobs, claim, cost=ob_cost):
        r = {"id": "lg-t", "knobs": knobs, "cost": cost}
        if claim is not None:
            r["overlap"] = claim
        return r

    claim = {"grad": "bucketed", "buckets": 4, "prefetch": "2",
             "serve": "1"}
    pins = {"APEX_OVERLAP_GRAD": "bucketed", "APEX_OVERLAP_BUCKETS": "4",
            "APEX_PREFETCH": "2", "APEX_SERVE_OVERLAP": "1"}
    assert cbl.overlap_problems(rec(pins, claim), "lg-t") == []
    # claimed but unpinned
    probs = cbl.overlap_problems(rec({}, claim), "lg-t")
    assert len(probs) == 4 and all("does not pin" in p for p in probs)
    # claimed one thing, pinned another
    drift = dict(pins, APEX_OVERLAP_GRAD="off")
    assert any("different schedules" in p for p in
               cbl.overlap_problems(rec(drift, claim), "lg-t"))
    # reverse direction: engaged pin, silent claim — including the
    # bucket count, which has no off value (any pin is engaged)
    probs = cbl.overlap_problems(
        rec({"APEX_PREFETCH": "2"}, {"grad": "off"}), "lg-t")
    assert any("omits" in p for p in probs)
    probs = cbl.overlap_problems(
        rec({"APEX_OVERLAP_BUCKETS": "8"}, {"grad": "off"}), "lg-t")
    assert any("omits 'buckets'" in p for p in probs)
    # legacy rows (no claim block) are skipped; so are rows whose
    # overlap_bound carries no measured host/comm side
    assert cbl.overlap_problems(rec({}, None), "lg-t") == []
    assert cbl.overlap_problems(
        rec({}, claim, cost={"overlap_bound": {"host_ms": None,
                                               "comm_ms": None}}),
        "lg-t") == []
    # span-level cost blocks trigger the teeth too
    span_rec = {"id": "lg-t", "knobs": {}, "overlap": claim,
                "spans": [{"extra": {"cost": ob_cost}}]}
    assert cbl.overlap_problems(span_rec, "lg-t")


def test_profile_overlap_smoke_cli(tmp_path, shared_smoke_cache_dir):
    """The harness contract end-to-end at smoke shapes, on the
    session-shared smoke compile cache (the PR 6 fast-tier rule):
    one run, one validated ledger record carrying the overlap claim,
    the collective-schedule verdict, and a check-10-clean pin set."""
    ledger_path = tmp_path / "ledger.jsonl"
    env = dict(os.environ, APEX_BENCH_SMOKE="1",
               APEX_TELEMETRY_LEDGER=str(ledger_path),
               JAX_COMPILATION_CACHE_DIR=shared_smoke_cache_dir,
               APEX_OVERLAP_GRAD="bucketed", APEX_PREFETCH="1",
               APEX_SERVE_OVERLAP="1")
    env.pop("APEX_FAULT_PLAN", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "profile_overlap.py")],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=560)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "collective schedule          interleaved" in proc.stdout
    from apex_tpu.telemetry import ledger as ledger_mod

    recs = ledger_mod.read_ledger(str(ledger_path))
    assert len(recs) == 1
    rec = recs[0]
    assert not ledger_mod.validate_record(rec)
    assert rec["overlap"]["grad"] == "bucketed"
    assert rec["collective_schedule"]["verdict"] == "interleaved"
    assert rec["knobs"]["APEX_OVERLAP_GRAD"] == "bucketed"
    assert _cbl().overlap_problems(rec, rec["id"]) == []
