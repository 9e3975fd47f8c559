"""Decode-attention family (ops/decode_attention_pallas.py, ISSUE 10):
interpret-mode parity vs the jnp gather reference, tile legality, the
rule that picks the program (the kernel on a TPU where it supports the
geometry, the reference otherwise), and what the engine reports."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import dispatch
from apex_tpu.dispatch import tiles
from apex_tpu.ops import decode_attention_pallas as dap
from apex_tpu.serving import kv_tier

B, H, P, PS, D, MAXP = 4, 4, 16, 32, 64, 4
SCALE = 1.0 / np.sqrt(D)


def _data(dtype=jnp.float32, seed=0, h=H, layers=None):
    """q, K, V (``[h, P, PS, D]``, or stacked under ``layers``), page
    table, lengths."""
    rs = np.random.RandomState(seed)
    lead = () if layers is None else (layers,)
    q = jnp.asarray(rs.randn(B, h, D), dtype)
    k = jnp.asarray(rs.randn(*lead, h, P, PS, D), dtype)
    v = jnp.asarray(rs.randn(*lead, h, P, PS, D), dtype)
    # distinct non-contiguous pages per slot; page 0 stays null
    pt = jnp.asarray(np.stack([
        rs.permutation(np.arange(1, P))[:MAXP] for _ in range(B)]),
        jnp.int32)
    # lengths cover: mid-page, page-aligned, full, inactive
    lens = jnp.asarray([5, PS, MAXP * PS, 0], jnp.int32)
    return q, k, v, pt, lens


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_matches_reference(dtype):
    q, k, v, pt, lens = _data(dtype)
    want = dap.decode_attention_reference(q, k, v, pt, lens, SCALE)
    got = dap.decode_attention_pallas(q, k, v, pt, lens, SCALE,
                                      interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=1e-5 if dtype == jnp.float32 else 5e-2)
    # inactive slot -> exact zeros (the fully-masked-row contract)
    assert np.all(np.asarray(got, np.float32)[3] == 0.0)


@pytest.mark.parametrize("h,bh,dtype", [
    (20, 20, jnp.bfloat16), (32, 16, jnp.bfloat16), (32, 32, jnp.bfloat16),
    (32, 8, jnp.float32), (12, 12, jnp.float32)],
    ids=["h20-bh20-bf16", "h32-bh16-bf16", "h32-bh32-bf16", "h32-bh8-f32",
         "h12-bh12-f32"])
def test_block_h_sweep_parity(h, bh, dtype):
    q, k, v, pt, lens = _data(dtype, h=h)
    want = dap.decode_attention_reference(q, k, v, pt, lens, SCALE)
    got = dap.decode_attention_pallas(q, k, v, pt, lens, SCALE,
                                      block_h=bh, interpret=True)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=1e-5 if dtype == jnp.float32 else 5e-2)


def _quantized(k):
    scale = jnp.asarray(np.max(np.abs(np.asarray(k, np.float32)),
                               axis=(-2, -1)) / kv_tier.QMAX,
                        kv_tier.SCALE_DTYPE)
    return kv_tier.quantize(k.astype(jnp.float32), scale), scale


@pytest.mark.parametrize("stacked", [True, False],
                         ids=["layer-indexed", "one-layer"])
@pytest.mark.parametrize("pages", ["bf16", "int8"])
def test_gpt2_large_heads_both_operand_forms(pages, stacked):
    """h = 20 (the serve cell's), all heads a block, float and int8
    pages, the engine's two operand forms: the stacked cache with the
    layer as an index, and one layer's arrays. Lengths hold 0, a
    mid-page end and a full table."""
    layer = 1
    q, k, v, pt, lens = _data(jnp.bfloat16, seed=4, h=20, layers=3)
    kw = {}
    if pages == "int8":
        (k, ks), (v, vs) = _quantized(k), _quantized(v)
        kw = dict(k_scale=ks[layer], v_scale=vs[layer])
    want = dap.decode_attention_reference(q, k[layer], v[layer], pt, lens,
                                          SCALE, **kw)
    if stacked:
        if pages == "int8":
            kw = dict(k_scale=ks, v_scale=vs)
        got = dap.decode_attention(q, k, v, pt, lens, sm_scale=SCALE,
                                   layer=layer, impl="pallas", block_h=20,
                                   interpret=True, **kw)
        # the same call on the reference path slices the layer itself
        ref = dap.decode_attention(q, k, v, pt, lens, sm_scale=SCALE,
                                   layer=layer, **kw)
        np.testing.assert_array_equal(np.asarray(ref, np.float32),
                                      np.asarray(want, np.float32))
    else:
        got = dap.decode_attention(q, k[layer], v[layer], pt, lens,
                                   sm_scale=SCALE, impl="pallas",
                                   interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=5e-2)
    assert np.all(np.asarray(got, np.float32)[3] == 0.0)


def test_per_call_tile_raises_setter_falls_back():
    q, k, v, pt, lens = _data()
    # per-call demand on an illegal tile raises with the model verdict
    with pytest.raises(ValueError, match="does not divide"):
        dap.decode_attention_pallas(q, k, v, pt, lens, SCALE,
                                    block_h=3, interpret=True)
    # heads are the page block's second-minor axis: a block that is
    # not all of h has to be whole sublane tiles
    with pytest.raises(ValueError, match="sublane tile"):
        dap.decode_attention_pallas(q, k, v, pt, lens, SCALE,
                                    block_h=2, interpret=True)
    # the process-wide setter is a preference: an illegal pin falls
    # back to the heuristic silently (parity still holds)
    dap.set_block_h(3)
    try:
        want = dap.decode_attention_reference(q, k, v, pt, lens, SCALE)
        got = dap.decode_attention_pallas(q, k, v, pt, lens, SCALE,
                                          interpret=True)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)
    finally:
        dap.set_block_h(None)
    with pytest.raises(ValueError):
        dap.set_block_h(-2)


def test_impl_demand_asymmetry():
    q, k, v, pt, lens = _data()
    with pytest.raises(ValueError, match="unknown decode-attention"):
        dap.decode_attention(q, k, v, pt, lens, impl="dense")
    # jnp demand with a pallas tile knob is un-honorable
    with pytest.raises(ValueError, match="block_h"):
        dap.decode_attention(q, k, v, pt, lens, impl="jnp", block_h=4)
    # ... and so is the tile knob where the RULE took the jnp path
    with pytest.raises(ValueError, match="jnp path"):
        dap.decode_attention(q, k, v, pt, lens, block_h=4)


def _jaxpr(*args, **kw):
    return str(jax.make_jaxpr(
        lambda *a: dap.decode_attention(*a, sm_scale=SCALE, **kw))(*args))


def test_rule_cpu_runs_the_reference():
    """No knob: on the CPU platform the default is the reference (the
    suite does not start interpreting a Pallas grid in every serving
    test); a per-call demand still gets the kernel, interpreted."""
    args = _data()
    assert jax.default_backend() == "cpu"
    assert dap._effective_impl(None, H, P, PS, D, jnp.float32) == "jnp"
    assert "pallas_call" not in _jaxpr(*args)
    assert "pallas_call" in _jaxpr(*args, impl="pallas")


def test_rule_tpu_runs_the_kernel_where_supported(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert dap._effective_impl(None, 20, 96, 128, 64,
                               jnp.bfloat16) == "pallas"
    assert dap.resolved(20, 96, 128, 64, jnp.bfloat16) == ("pallas", 20)
    # the demand wins over the rule, either way
    assert dap.resolved(20, 96, 128, 64, jnp.bfloat16,
                        impl="jnp") == ("jnp", None)
    # interpret=True here only because no TPU is attached to lower for
    assert "pallas_call" in _jaxpr(*_data(), interpret=True)


def test_rule_unsupported_geometry_falls_back(monkeypatch):
    """d too large for the kernel: the rule takes the reference even
    on a TPU; a per-call demand for the kernel raises."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    big_d = 1024
    assert not dap.supported(2, 4, 8, big_d, jnp.float32)
    assert dap._effective_impl(None, 2, 4, 8, big_d, jnp.float32) == "jnp"
    qb = jnp.zeros((2, 2, big_d), jnp.float32)
    kb = jnp.zeros((2, 4, 8, big_d), jnp.float32)
    ptb = jnp.zeros((2, 2), jnp.int32)
    lb = jnp.zeros((2,), jnp.int32)
    out = dap.decode_attention(qb, kb, kb, ptb, lb)
    assert out.shape == qb.shape
    with pytest.raises(ValueError, match="unsupported geometry"):
        dap.decode_attention(qb, kb, kb, ptb, lb, impl="pallas")


@pytest.mark.parametrize("h,itembytes,want", [
    (12, 2, 12), (16, 2, 16), (20, 2, 20), (25, 2, 25), (20, 1, 20),
    (64, 4, 16)])
def test_decode_block_h_largest_fitting_block(h, itembytes, want):
    """All of h where it fits (GPT-2 small 12, medium 16, large 20,
    an odd 25; int8 pages too); whole sublane tiles of h where it does
    not (64 fp32 heads of 128-token pages overflow, and 32; 16 fit)."""
    assert tiles.decode_block_h(h, 128, 64, itembytes) == want


def test_decode_block_h_zero_when_nothing_fits():
    # 16k-token pages: all 7 heads overflow and no smaller block is legal
    assert tiles.decode_block_h(7, 16384, 128, 2) == 0
    assert not dap.supported(7, 4, 16384, 128, jnp.bfloat16)


def test_tile_model_surface():
    """The fifth family in the shared tile model: legality verdicts,
    heuristic default, the model's bytes."""
    dims = dict(b=B, h=32, pages=MAXP, ps=PS, d=D)
    assert tiles.legal("decode_attention", dims, jnp.bfloat16,
                       {"block_h": 5})  # does not divide 32
    assert tiles.legal("decode_attention", dims, jnp.bfloat16,
                       {"block_h": 8})  # bf16 sublane tile is 16 rows
    assert not tiles.legal("decode_attention", dims, jnp.bfloat16,
                           {"block_h": 16})
    assert not tiles.legal("decode_attention", dims, jnp.float32,
                           {"block_h": 8})
    assert tiles.default_params("decode_attention", dims,
                                jnp.bfloat16) == {"block_h": 32}
    for c in tiles.candidates("decode_attention", dims, jnp.bfloat16):
        assert not tiles.legal("decode_attention", dims, jnp.bfloat16,
                               c), c
    assert tiles.model_vmem_bytes(
        "decode_attention", dims, jnp.bfloat16,
        {"block_h": 16}) == tiles.decode_vmem_bytes(16, PS, D, 2)
    # the serve cell's block: K and V [128, 20 -> 32, 64 -> 128] bf16,
    # each held twice
    assert tiles.decode_vmem_bytes(20, 128, 64, 2) \
        >= 4 * 128 * 32 * 128 * 2


def test_no_table_op_and_tile_vocabulary():
    """The impl is a rule in code: no dispatch-table op, no table row;
    the tile axis stays in the shared model for the per-call demand."""
    assert "decode_attention" not in dispatch.OP_CHOICES
    entries, problems = dispatch.load_table(dispatch.default_path())
    assert not problems
    assert not [k for k in entries if k[0] == "decode_attention"]
    assert tiles.PARAM_KEYS["decode_attention"] == ("block_h",)
    assert tiles.DIM_KEYS["decode_attention"] == (
        "b", "h", "pages", "ps", "d")


# ------------------------------------------- what the engine reports


def _engine(**kw):
    from apex_tpu.serving import model as smodel
    from tests.test_serving_tp import _cfg, _engine as build

    cfg = _cfg()
    return build(cfg, smodel.init_gpt_params(cfg), **kw)


def _drive(eng):
    from tests.test_serving_tp import _drive as drive, _requests

    return drive(eng, _requests())


def test_engine_reports_its_decode_attention_program():
    """``decode_attn_impl`` / ``decode_attn_block_h`` say what the
    decode program was built with, and the ``decode.dispatch`` span
    carries both: "jnp" for the default engine on the CPU, "pallas"
    for a demanded (interpreted) kernel, same tokens from both."""
    from apex_tpu.telemetry import spans

    def last_dispatch():
        return [r for r in spans.snapshot()
                if r.name == "decode.dispatch"][-1].attrs

    eng = _engine()
    assert (eng.decode_attn_impl, eng.decode_attn_block_h) == ("jnp", None)
    want = _drive(eng)
    assert last_dispatch() == {"attn_impl": "jnp", "block_h": None}

    eng = _engine(decode_impl="pallas", interpret=True)
    assert (eng.decode_attn_impl, eng.decode_attn_block_h) == ("pallas", 4)
    assert _drive(eng) == want
    assert last_dispatch() == {"attn_impl": "pallas", "block_h": 4}
    assert eng.decode_cache_size() == 1


def test_tp_engine_takes_the_reference(monkeypatch):
    """A tensor-parallel engine partitions its decode jaxpr by GSPMD,
    which a pallas_call does not survive: ``tp > 1`` builds with the
    jnp reference even where the rule alone would pick the kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = _engine(tp=2)
    assert eng.tp == 2 and eng.decode_impl == "jnp"
    assert eng.decode_attn_impl == "jnp"
