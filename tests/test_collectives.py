"""Quantized + hierarchical collectives (apex_tpu.parallel.collectives).

The ISSUE-8 proof surface, all on the CPU backend (conftest's 8-device
mesh) — no TPU window required:

* codec + error feedback: the residual recovers sub-quantum signal a
  plain int8 path drops (and the optimization-level twin: GD converges
  with EF where stateless int8 stalls);
* knob asymmetry: per-call raises, setter/env preferences fall back;
* byte-identity: with every knob off, DDP's ``allreduce_gradients``
  emits the exact pre-collectives jaxpr, and the ZeRO update jaxpr
  carries no quantization artifacts;
* the dispatch-table "grad_comm" consult sits strictly below
  per-call/setter/env;
* payload accounting: ``costs.comm_from_jaxpr`` proves the >=3.5x
  dp-axis cut with int8 on, and the hierarchical inter-slice cut;
* ZeRO trajectory parity over >=20 steps of a real objective:
  uncompressed matches the unsharded optimizer bitwise, compressed
  tracks inside the tolerance band;
* the ledger/checker/report plumbing for the ``comm_compression``
  cost-block stamp (costs.validate, check_bench_labels check 7,
  window_report comm rows).
"""

import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel import collectives as C
from apex_tpu.parallel.distributed import allreduce_gradients
from apex_tpu import dispatch

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_knobs(monkeypatch):
    for k in ("APEX_GRAD_COMPRESS", "APEX_HIER_ALLREDUCE",
              "APEX_DISPATCH", "APEX_DISPATCH_TABLE"):
        monkeypatch.delenv(k, raising=False)
    C._reset_for_tests()
    dispatch._reset_for_tests()
    yield
    C._reset_for_tests()
    dispatch._reset_for_tests()


def _jx(fn, *args):
    """Trace with a FRESH function object (jax trace caches key on
    identity; knob resolution is trace-time)."""
    return str(jax.make_jaxpr(lambda *a: fn(*a))(*args))


def _mesh(n, names=("dp",), shape=None):
    return Mesh(np.array(jax.devices()[:n]).reshape(shape or (n,)), names)


# ------------------------------------------------------------- codec

def test_quantize_dequantize_roundtrip_properties():
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(300) * 10, jnp.float32)  # pads 300 -> 384
    q, s = C.quantize_blocks(x, block=128)
    assert q.dtype == jnp.int8 and s.dtype == jnp.bfloat16
    assert q.shape == (3, 128) and s.shape == (3,)
    dq = C.dequantize_blocks(q, s, 300)
    assert dq.shape == (300,)
    # error bounded by half a quantum per element (amax/127 per block,
    # + bf16 scale rounding headroom)
    amax = np.abs(np.asarray(x)).reshape(-1)
    err = np.abs(np.asarray(dq) - np.asarray(x))
    assert err.max() <= (np.abs(np.asarray(x)).max() / 127.0) * 0.6

    # values that are exact multiples of a bf16-exact quantum roundtrip
    # exactly: block max 127.0 -> scale 1.0
    v = jnp.asarray([127.0, -127.0, 3.0, -5.0] + [0.0] * 124, jnp.float32)
    q2, s2 = C.quantize_blocks(v, block=128)
    np.testing.assert_array_equal(np.asarray(C.dequantize_blocks(q2, s2, 128)),
                                  np.asarray(v))

    # a non-finite block poisons to non-finite (found_inf survives the
    # wire) instead of flushing to zero — for inf AND for NaN (a NaN
    # amax fails the `> 0` scale test and int8-casts to 0, so without
    # the isfinite guard the block would flush to FINITE zero and the
    # EF residual would turn NaN forever)
    for poison in (jnp.inf, jnp.nan):
        bad = v.at[1].set(poison)
        qb, sb = C.quantize_blocks(bad, block=128)
        dq = np.asarray(C.dequantize_blocks(qb, sb, 128))
        assert not np.isfinite(dq).all(), poison
        # ...and the EF residual stays finite (sanitized to 0 where
        # the dequantized value went non-finite)
        comp, emit = C._compensate(bad, jnp.zeros((128,), jnp.float32))
        res = emit(*C.quantize_blocks(comp, block=128))
        assert np.isfinite(np.asarray(res)).all(), poison


def test_error_feedback_recovers_subquantum_signal():
    """The EF property: a 0.3 signal in a block whose quantum is ~0.79
    (max 100) quantizes to 0 EVERY step without feedback; with the
    residual carried, the emitted sum over N steps approaches N*0.3."""
    x = jnp.zeros((128,), jnp.float32).at[0].set(100.0).at[1].set(0.3)
    n_steps = 16

    def run(residual):
        emitted = np.zeros(128, np.float64)
        res = residual
        for _ in range(n_steps):
            comp, emit = C._compensate(x, res)
            q, s = C.quantize_blocks(comp, block=128)
            dq = C.dequantize_blocks(q, s, 128)
            emitted += np.asarray(dq, np.float64)
            res = emit(q, s) if res is not None else None
        return emitted

    no_ef = run(None)
    with_ef = run(jnp.zeros((128,), jnp.float32))
    assert no_ef[1] == 0.0  # dropped forever
    want = n_steps * 0.3
    assert abs(with_ef[1] - want) <= 100.0 / 127.0 + 0.05, with_ef[1]


def test_error_feedback_converges_where_plain_int8_stalls():
    """Optimization-level EF twin: gradient descent through the
    quantized allreduce on a mesh. The loss surface puts a large
    gradient coordinate in the same block as small ones, so the
    stateless int8 path drops the small coordinates' updates; the
    EF-threaded path recovers them."""
    n = 2
    mesh = _mesh(n)
    w0 = jnp.full((128,), 0.6)
    lr = 0.05

    def make_run(use_ef):
        def run(w):
            res = jnp.zeros((128,), jnp.float32) if use_ef else None
            # each rank adds a PERSISTENT +/-200 to coordinate 0 of its
            # local gradient — antisymmetric across the 2 ranks, so the
            # mean (and w[0]'s trajectory) is untouched, but every
            # sender's block scale stays ~200/127 forever: the true
            # gradient (0.6, decaying) is sub-HALF-quantum from step 0
            sign = 1.0 - 2.0 * lax.axis_index("dp").astype(jnp.float32)

            def body(carry, _):
                w, res = carry
                g = w.at[0].add(sign * 200.0)  # quadratic grad + bias
                rg, new_res = C.quantized_allreduce_flat(
                    g, ("dp",), mean=True, residual=res)
                return (w - lr * rg,
                        new_res if use_ef else res), jnp.sum(w ** 2)

            (w, _), losses = lax.scan(body, (w, res), jnp.arange(40))
            return w, losses
        return run

    def go(use_ef):
        f = shard_map(make_run(use_ef), mesh=mesh, in_specs=(P(),),
                      out_specs=(P(), P()), check_vma=False)
        return jax.jit(f)(w0)

    w_ef, _ = go(True)
    w_plain, _ = go(False)
    small_ef = float(jnp.max(jnp.abs(w_ef[1:])))
    small_plain = float(jnp.min(jnp.abs(w_plain[1:])))
    # EF: the sub-quantum coordinates still descend toward 0; plain
    # int8: they quantize to 0 every step and NEVER move
    assert small_ef < 0.3, small_ef
    assert abs(small_plain - 0.6) < 1e-6, small_plain  # f32 0.6


# ------------------------------------------------------------- knobs

def test_per_call_raises_preferences_fall_back():
    # per-call: explicit request != preference
    with pytest.raises(ValueError):
        C.resolve_compress("fp4")
    with pytest.raises(ValueError):
        C.resolve_hier(True, ("dp",))
    # a setter CALL with an unknown scheme raises too
    with pytest.raises(ValueError):
        C.set_grad_compress("fp4")
    with pytest.raises(ValueError):
        C.set_hier_allreduce("yes")
    # ...but the pinned hier PREFERENCE falls back on an unfactored axis
    C.set_hier_allreduce(True)
    assert C.resolve_hier(None, ("dp",)) is False
    assert C.resolve_hier(None, ("dp_in", "dp_out")) is True
    C.set_hier_allreduce(None)
    # env is a preference: unknown scheme warns once and stays off
    os.environ["APEX_GRAD_COMPRESS"] = "fp4"
    try:
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            assert C.resolve_compress(None) is None
            assert C.resolve_compress(None) is None
        assert len([w for w in rec
                    if "APEX_GRAD_COMPRESS" in str(w.message)]) == 1
    finally:
        del os.environ["APEX_GRAD_COMPRESS"]
        C._reset_for_tests()
    # same convention for the hier env knob: "true"/"yes" would
    # silently measure the FLAT path under a hierarchical label
    os.environ["APEX_HIER_ALLREDUCE"] = "true"
    try:
        with warnings.catch_warnings(record=True) as rec:
            warnings.simplefilter("always")
            assert C.resolve_hier(None, ("a", "b")) is False
        assert any("APEX_HIER_ALLREDUCE" in str(w.message) for w in rec)
    finally:
        del os.environ["APEX_HIER_ALLREDUCE"]
        C._reset_for_tests()
    # per-call False/"off" pins off over any preference
    C.set_grad_compress("int8")
    assert C.resolve_compress(False) is None
    assert C.resolve_compress("off") is None
    assert C.resolve_compress(None) == "int8"
    C.set_grad_compress(None)


def test_snapshot_and_disabled(monkeypatch):
    assert C.snapshot() == {"scheme": None, "hierarchical": False,
                            "block": C.DEFAULT_BLOCK}
    monkeypatch.setenv("APEX_GRAD_COMPRESS", "int8")
    monkeypatch.setenv("APEX_HIER_ALLREDUCE", "1")
    assert C.snapshot()["scheme"] == "int8"
    assert C.snapshot()["hierarchical"] is True
    with C.disabled():
        assert C.resolve_compress(None) is None
        assert C.resolve_hier(None, ("a", "b")) is False
        # explicit per-call demands still honor themselves
        assert C.resolve_compress("int8") == "int8"
    assert C.resolve_compress(None) == "int8"


# ------------------------------------------------- jaxpr byte-identity

def test_ddp_knob_off_jaxpr_byte_identical():
    """With every knob off, allreduce_gradients emits the exact
    pre-collectives jaxpr (the PR-1 invariant class): one psum per
    leaf, same dtype casts, same pre/post scaling."""
    mesh = _mesh(4)
    grads = {"w": jnp.ones((5, 3), jnp.bfloat16),
             "b": jnp.ones((7,), jnp.float32)}

    def legacy(grads, axis_name="dp", gradient_average=True,
               allreduce_always_fp32=False, gradient_predivide_factor=1.0):
        # the pre-ISSUE-8 implementation, verbatim
        world = jax.lax.psum(1, axis_name)

        def reduce_one(g):
            orig = g.dtype
            if allreduce_always_fp32:
                g = g.astype(jnp.float32)
            if gradient_predivide_factor != 1.0:
                g = g / gradient_predivide_factor
            g = jax.lax.psum(g, axis_name)
            if gradient_average:
                post = world / gradient_predivide_factor \
                    if gradient_predivide_factor != 1.0 else world
                g = g / post
            elif gradient_predivide_factor != 1.0:
                g = g * gradient_predivide_factor
            return g.astype(orig) if allreduce_always_fp32 else g

        return jax.tree_util.tree_map(reduce_one, grads)

    for kw in ({}, {"allreduce_always_fp32": True},
               {"gradient_predivide_factor": 2.0},
               {"gradient_average": False}):
        def new_fn(g):
            return allreduce_gradients(g, "dp", **kw)

        def old_fn(g):
            return legacy(g, "dp", **kw)

        sm = lambda f: shard_map(f, mesh=mesh, in_specs=(P(),),
                                 out_specs=P(), check_vma=False)
        assert _jx(sm(new_fn), grads) == _jx(sm(old_fn), grads), kw


def test_zero_knob_off_jaxpr_has_no_quantization_artifacts():
    from apex_tpu.contrib.optimizers import distributed_fused_adam

    mesh = _mesh(4)
    params = {"w": jnp.ones((37,), jnp.float32)}
    grads = {"w": jnp.full((37,), 0.1, jnp.float32)}

    def run_with(**kw):
        tx = distributed_fused_adam(learning_rate=0.1, num_shards=4,
                                    axis_name="dp", **kw)

        def one(p, g):
            st = tx.init(p)
            upd, st = tx.update(g, st, p)
            return upd

        return _jx(shard_map(one, mesh=mesh, in_specs=(P(), P()),
                             out_specs=P(), check_vma=False),
                   params, grads)

    off_default = run_with()
    off_explicit = run_with(grad_compress="off", hier_allreduce=False)
    assert off_default == off_explicit
    assert "int8" not in off_default and "all_to_all" not in off_default
    on = run_with(grad_compress="int8")
    assert "int8" in on and "all_to_all" in on


def test_ef_state_threading_and_ef_init():
    mesh = _mesh(4, names=("dp_in", "dp_out"), shape=(2, 2))
    grads = {"w": jnp.ones((100,), jnp.float32)}

    def probe(g):
        off = C.ef_init(g, ("dp_in", "dp_out"))
        flat = C.ef_init(g, ("dp_in", "dp_out"), compress="int8")
        hier = C.ef_init(g, ("dp_in", "dp_out"), compress="int8",
                         hierarchical=True)
        # threading through allreduce_gradients: returns (tree, state)
        red, new_state = allreduce_gradients(
            g, ("dp_in", "dp_out"), compress="int8", ef_state=flat)
        return (jnp.asarray(0 if off is None else 1),
                jnp.asarray(flat.shape[0]), jnp.asarray(hier.shape[0]),
                new_state, red["w"][0])

    out = jax.jit(shard_map(probe, mesh=mesh, in_specs=(P(),),
                            out_specs=(P(), P(), P(), P(), P()),
                            check_vma=False))(grads)
    assert int(out[0]) == 0          # off -> None (free when off)
    assert int(out[1]) == 100        # flat residual: full payload
    assert int(out[2]) == 50         # hier: the 1/inner piece
    assert out[3].shape == (100,)    # new residual, same shape
    np.testing.assert_allclose(float(out[4]), 1.0, rtol=1e-2)


# -------------------------------------------------- dispatch consult

def _grad_comm_entry(tmp_path, monkeypatch, nelems, choice):
    entry = {"op": "grad_comm", "bucket": dispatch.bucket(n=nelems),
             "dtype": "float32", "backend": "cpu", "choice": choice,
             "ledger": "lg-" + "0" * 10}
    path = tmp_path / "table.jsonl"
    path.write_text(json.dumps(entry) + "\n")
    monkeypatch.setenv("APEX_DISPATCH_TABLE", str(path))
    dispatch._reset_for_tests()


def test_dispatch_table_consult_strictly_below_knobs(tmp_path,
                                                     monkeypatch):
    mesh = _mesh(4)
    grads = {"w": jnp.ones((100,), jnp.float32)}
    _grad_comm_entry(tmp_path, monkeypatch, 100, "int8")

    def trace(**kw):
        def f(g):
            t, _ = C.allreduce_tree(g, ("dp",), **kw)
            return t

        return _jx(shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P(),
                             check_vma=False), grads)

    # unpinned: the table's int8 choice resolves
    assert "int8" in trace()
    # ...and lands in the consult log (pin-the-label)
    log = {(r["op"], r["bucket"]): r["choice"]
           for r in dispatch.consulted()}
    assert log.get(("grad_comm", dispatch.bucket(n=100))) == "int8"
    # per-call beats the table
    assert "int8" not in trace(compress=False)
    # setter beats the table
    C.set_grad_compress("off")
    assert "int8" not in trace()
    C.set_grad_compress(None)
    # an explicit env off-pin (present but empty/off) blocks the consult
    monkeypatch.setenv("APEX_GRAD_COMPRESS", "off")
    assert "int8" not in trace()
    monkeypatch.delenv("APEX_GRAD_COMPRESS")
    # APEX_DISPATCH=off kills the consult tier entirely
    monkeypatch.setenv("APEX_DISPATCH", "off")
    dispatch._reset_for_tests()
    assert "int8" not in trace()


def test_dispatch_table_hier_choice_needs_factored_axes(tmp_path,
                                                        monkeypatch):
    _grad_comm_entry(tmp_path, monkeypatch, 100, "int8_hier")
    mesh = _mesh(4, names=("dp_in", "dp_out"), shape=(2, 2))
    grads = {"w": jnp.ones((100,), jnp.float32)}

    def trace(axes, mesh):
        def f(g):
            t, _ = C.allreduce_tree(g, axes)
            return t

        return _jx(shard_map(f, mesh=mesh, in_specs=(P(),), out_specs=P(),
                             check_vma=False), grads)

    # factored declaration: the int8_hier choice stages the reduction
    # (reduce_scatter on the inner axis) AND quantizes the outer hop
    jx = trace(("dp_in", "dp_out"), mesh)
    assert "int8" in jx and "reduce_scatter" in jx
    # flat axis: the hier half of the choice falls back, int8 still on
    # (the one-shot gather-based quantized allreduce — no staging)
    jx_flat = trace(("dp",), _mesh(4))
    assert "int8" in jx_flat and "reduce_scatter" not in jx_flat
    # snapshot with nelems sees the table tier: a table-driven
    # compressed run stamps its cost block (check-7 visibility)
    snap = C.snapshot(nelems=100)
    assert snap["scheme"] == "int8" and snap["hierarchical"] is True
    # without nelems only setter/env tiers are visible
    assert C.snapshot()["scheme"] is None


# ---------------------------------------------- payload accounting

def _toy_cfg():
    from apex_tpu.transformer.testing.minimal import TransformerConfig

    return TransformerConfig(
        hidden_size=64, num_layers=2, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=16,
        hidden_dropout=0.0, attention_dropout=0.0, bf16=True,
        apply_query_key_layer_scaling=False)


def test_comm_bytes_int8_dp_reduction_at_least_3_5x():
    """The acceptance-criterion assert: comm_from_jaxpr measures a
    >=3.5x dp-axis gradient-payload cut with int8 on (trace-time, no
    device). block=128 int8+bf16 scales is 4/(1+2/128) ~ 3.94x."""
    from apex_tpu.transformer.testing.minimal import training_comm_bytes

    devs = jax.devices()[:8]
    cfg = _toy_cfg()
    base = training_comm_bytes(devs, cfg, (2, 4, 1), num_microbatches=2,
                               micro_batch_size=2, seq_len=16,
                               compress=False, hierarchical=False)
    q = training_comm_bytes(devs, cfg, (2, 4, 1), num_microbatches=2,
                            micro_batch_size=2, seq_len=16,
                            compress="int8", hierarchical=False)
    assert base["dp"] / q["dp"] >= 3.5, (base, q)
    # pp traffic untouched: the knob compresses the grad sync only
    assert base["pp"] == q["pp"]


def test_comm_bytes_hierarchical_cuts_inter_slice_hop():
    from apex_tpu.transformer.testing.minimal import training_comm_bytes

    devs = jax.devices()[:8]
    cfg = _toy_cfg()
    kw = dict(num_microbatches=2, micro_batch_size=2, seq_len=16)
    base = training_comm_bytes(devs, cfg, (2, (2, 2), 1),
                               compress=False, hierarchical=False, **kw)
    hier = training_comm_bytes(devs, cfg, (2, (2, 2), 1),
                               compress=False, hierarchical=True, **kw)
    both = training_comm_bytes(devs, cfg, (2, (2, 2), 1),
                               compress="int8", hierarchical=True, **kw)
    # flat tuple-axis allreduce moves the full payload over BOTH axes;
    # the two-stage reduction moves 1/inner (+gather) over the outer
    assert hier["dp_out"] <= base["dp_out"] * 0.76, (base, hier)
    # composed: the inter-slice hop additionally rides int8 (~3.9x)
    assert both["dp_out"] <= hier["dp_out"] / 3.5, (hier, both)


def test_dryrun_32_64_topology_plans():
    """The widened virtual-topology plans (ISSUE 8): pp=8 and tp=4
    finally exercised, plus hierarchically factored dp pairs."""
    import __graft_entry__
    from apex_tpu.transformer.testing.minimal import dp_axes_of

    t32 = __graft_entry__.dryrun_topologies(32)
    t64 = __graft_entry__.dryrun_topologies(64)
    assert (8, 2, 2) in t32 and (2, 4, 4) in t32
    assert (8, 2, 4) in t64
    assert any(isinstance(dp, tuple) for _, dp, _t in t32)
    assert any(isinstance(dp, tuple) for _, dp, _t in t64)
    for n, topos in ((32, t32), (64, t64)):
        for pp, dp, tp in topos:
            dp_size, dp_names, dp_sizes = dp_axes_of(dp)
            assert pp * dp_size * tp == n, (n, pp, dp, tp)
            if isinstance(dp, tuple):
                assert len(dp_names) == 2 and dp_sizes == tuple(dp)


# ------------------------------------------- ZeRO trajectory parity

def _regression_problem():
    rs = np.random.RandomState(3)
    X = jnp.asarray(rs.randn(32, 40), jnp.float32)
    w_true = jnp.asarray(rs.randn(40), jnp.float32)
    y = X @ w_true
    params = {"w": jnp.zeros((40,), jnp.float32),
              "b": jnp.zeros((1,), jnp.float32)}

    def loss_fn(p):
        pred = X @ p["w"] + p["b"][0]
        return jnp.mean((pred - y) ** 2)

    return params, loss_fn


def _zero_trajectory(steps=20, topology=8, **tx_kw):
    """Per-step losses of `steps` distributed_fused_adam steps on the
    regression objective; grads computed per rank (replicated batch)."""
    from apex_tpu.contrib.optimizers import distributed_fused_adam

    params, loss_fn = _regression_problem()
    if isinstance(topology, tuple):
        mesh = _mesh(topology[0] * topology[1],
                     names=("dp_in", "dp_out"), shape=topology)
        axis = ("dp_in", "dp_out")
        n = topology[0] * topology[1]
    else:
        mesh = _mesh(topology)
        axis, n = "dp", topology
    tx = distributed_fused_adam(learning_rate=0.05, num_shards=n,
                                axis_name=axis, **tx_kw)

    def run(p):
        st = tx.init(p)

        def body(carry, _):
            p, st = carry
            loss, g = jax.value_and_grad(loss_fn)(p)
            upd, st = tx.update(g, st, p)
            p = jax.tree_util.tree_map(jnp.add, p, upd)
            return (p, st), loss

        (_, _), losses = lax.scan(body, (p, st), jnp.arange(steps))
        return losses

    f = jax.jit(shard_map(run, mesh=mesh, in_specs=(P(),), out_specs=P(),
                          check_vma=False))
    return np.asarray(f(params), np.float64)


def _reference_trajectory(steps=20):
    from apex_tpu.optimizers.fused_adam import fused_adam

    params, loss_fn = _regression_problem()
    tx = fused_adam(learning_rate=0.05)

    def run(p):
        st = tx.init(p)

        def body(carry, _):
            p, st = carry
            loss, g = jax.value_and_grad(loss_fn)(p)
            upd, st = tx.update(g, st, p)
            p = jax.tree_util.tree_map(jnp.add, p, upd)
            return (p, st), loss

        (_, _), losses = lax.scan(body, (p, st), jnp.arange(steps))
        return losses

    return np.asarray(jax.jit(run)(params), np.float64)


def test_zero_trajectory_parity_20_steps():
    """ISSUE-8 acceptance: compressed trajectory inside the tolerance
    band of uncompressed over >=20 steps on the 8-device mesh. With
    the knobs off the trajectory is bitwise THE pre-ISSUE-8 ZeRO run
    (byte-identical jaxpr, asserted above — same program, same bits);
    vs the UNSHARDED optimizer the only drift is ZeRO's pre-existing
    flatten/concat reduction-order (last-ulp)."""
    ref = _reference_trajectory()
    flat = _zero_trajectory()
    np.testing.assert_allclose(flat, ref, rtol=2e-6, atol=1e-7)
    comp = _zero_trajectory(grad_compress="int8")
    # tolerance band: per-step relative deviation + both converge
    dev = np.abs(comp - flat) / np.maximum(np.abs(flat), 1e-8)
    assert dev.max() <= 0.06, (dev.max(), comp[-5:], flat[-5:])
    assert comp[-1] < comp[0] * 0.2  # converging (20 adam steps)
    # EF keeps the error from compounding: the last-5 window tracks
    assert np.abs(comp[-5:] - flat[-5:]).mean() <= \
        0.05 * max(flat[0], 1e-3)


@pytest.mark.slow  # second mesh shape = second compile of the same
# program family; the flat-axis twin above keeps the mechanism fast
def test_zero_trajectory_parity_hierarchical_composed():
    flat = _zero_trajectory()
    hier = _zero_trajectory(topology=(2, 4), hier_allreduce=True)
    np.testing.assert_allclose(hier, flat, rtol=1e-5, atol=1e-7)
    both = _zero_trajectory(topology=(2, 4), hier_allreduce=True,
                            grad_compress="int8")
    dev = np.abs(both - flat) / np.maximum(np.abs(flat), 1e-8)
    assert dev.max() <= 0.06, dev.max()
    assert both[-1] < both[0] * 0.2


# ------------------------------------------ ledger/checker plumbing

def test_costs_comm_compression_block_and_validate():
    from apex_tpu.telemetry import costs, ledger

    # nothing compressed -> no stamp (old records stay valid)
    assert costs.comm_compression_block(
        {"scheme": None, "hierarchical": False, "block": 128}) is None
    cc = costs.comm_compression_block(
        {"scheme": "int8", "hierarchical": True, "block": 128},
        {"dp": 400.0})
    block = costs.build(comm={"dp": 100.0}, comm_compression=cc)
    assert block["comm_compression"]["scheme"] == "int8"
    assert block["comm_compression"]["uncompressed_bytes_per_axis"] == \
        {"dp": 400.0}
    assert costs.validate(block) == []
    # malformed stamps are findings (ledger.validate_record teeth)
    for broken, frag in (
            ({"scheme": 5, "hierarchical": False}, "scheme"),
            ({"scheme": "int8", "hierarchical": "yes"}, "hierarchical"),
            ({"scheme": "int8", "hierarchical": True, "block": -1},
             "block"),
            ({"scheme": "int8", "hierarchical": True,
              "uncompressed_bytes_per_axis": {"dp": -4}},
             "uncompressed_bytes_per_axis")):
        bad = dict(block, comm_compression=broken)
        assert any(frag in p for p in costs.validate(bad)), (broken,
                                                             frag)
        rec = ledger.make_record("t", "cpu", 1.0, 4)
        rec["cost"] = bad
        assert any("comm_compression" in p
                   for p in ledger.validate_record(rec))


def test_check7_comm_compression_pin_match():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import check_bench_labels as cbl
    finally:
        sys.path.pop(0)
    stamp = {"scheme": "int8", "hierarchical": True, "block": 128}
    rec = {"id": "lg-" + "a" * 10, "knobs": {},
           "cost": {"comm_compression": stamp}}
    probs = cbl.comm_compress_problems(rec, rec["id"])
    assert len(probs) == 2  # unpinned scheme AND unpinned hier
    assert any("APEX_GRAD_COMPRESS" in p for p in probs)
    assert any("APEX_HIER_ALLREDUCE" in p for p in probs)
    rec["knobs"] = {"APEX_GRAD_COMPRESS": "int8",
                    "APEX_HIER_ALLREDUCE": "1"}
    assert cbl.comm_compress_problems(rec, rec["id"]) == []
    # span-level blocks are checked too
    rec2 = {"id": "lg-" + "b" * 10, "knobs": {},
            "spans": [{"name": "s", "cost": {"comm_compression": {
                "scheme": "int8", "hierarchical": False}}}]}
    assert any("APEX_GRAD_COMPRESS" in p
               for p in cbl.comm_compress_problems(rec2, rec2["id"]))
    # no stamp, no claim to check
    assert cbl.comm_compress_problems({"id": "x", "cost": {}}, "x") == []


def test_window_report_comm_rows():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import window_report as wr
    finally:
        sys.path.pop(0)
    recs = [{"harness": "profile_comm", "platform": "cpu", "id": "lg-1",
             "cost": {"source": "compiled",
                      "comm_bytes_per_axis": {"dp": 120.0},
                      "comm_compression": {
                          "scheme": "int8", "hierarchical": False,
                          "block": 128,
                          "uncompressed_bytes_per_axis": {"dp": 470.0}}}},
            {"harness": "bench", "platform": "cpu", "id": "lg-2",
             "cost": {"source": None}}]
    led = wr.ledger_summary(recs)
    assert len(led["comm"]) == 1
    row = led["comm"][0]
    assert row["bytes_per_axis"] == {"dp": 120.0}
    assert row["scheme"] == "int8"
    assert row["uncompressed_bytes_per_axis"] == {"dp": 470.0}


@pytest.mark.slow  # one real harness subprocess (~60-90s on this box)
def test_profile_comm_smoke_subprocess_e2e(tmp_path):
    from apex_tpu.telemetry import ledger as ledger_mod

    led = tmp_path / "ledger.jsonl"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               APEX_BENCH_SMOKE="1", APEX_GRAD_COMPRESS="int8",
               APEX_TELEMETRY_LEDGER=str(led), APEX_COST_ANALYSIS="1")
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "profile_comm.py")],
        capture_output=True, text=True, timeout=600, env=env, cwd=REPO)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "dp grad sync step" in out.stdout
    recs = ledger_mod.read_ledger(str(led))
    rec = next(r for r in recs if r.get("harness") == "profile_comm")
    assert ledger_mod.validate_record(rec) == []
    span = next(s for s in rec["spans"]
                if s["name"] == "dp grad sync step")
    cc = span["cost"]["comm_compression"]
    assert cc["scheme"] == "int8"
    unc = cc["uncompressed_bytes_per_axis"]
    comp = span["cost"]["comm_bytes_per_axis"]
    assert unc["dp"] / comp["dp"] >= 3.5
    # the knob pin rode into the record: check 7 is clean
    assert rec["knobs"].get("APEX_GRAD_COMPRESS") == "int8"
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import check_bench_labels as cbl
    finally:
        sys.path.pop(0)
    assert cbl.comm_compress_problems(rec, rec["id"]) == []
