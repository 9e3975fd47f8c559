"""Decode-attention family (ops/decode_attention_pallas.py): interpret-mode
parity of the one paged kernel against its jnp form at the GPT-2
family's shapes (``n_kv = h``, 64 wide: every head's banded query meets
the page whole), query groups below a sublane tile, the int8 tier's
pages through the reference, the rule that picks the program (the kernel
on a TPU where it supports the geometry, the reference otherwise), and
the engine on the ``[pages, page_size, h * d]`` cache: what it reports,
its page hops, its tensor-parallel sharding."""

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


def _data(dtype=jnp.float32, seed=0, h=H, hq=None, dk=D, dv=D):
    """q ``[B, hq, dk]``, K / V pages ``[P, PS, h * width]``, page table,
    lengths: a mid-page end behind a table whose tail is padding (null
    page 0), a page-aligned end, a full table, an inactive slot."""
    rs = np.random.RandomState(seed)
    q = jnp.asarray(rs.randn(B, hq or h, dk), dtype)
    k = jnp.asarray(rs.randn(P, PS, h * dk), dtype)
    v = jnp.asarray(rs.randn(P, PS, h * dv), dtype)
    # distinct non-contiguous pages per slot; page 0 stays null
    pt = np.stack([rs.permutation(np.arange(1, P))[:MAXP]
                   for _ in range(B)])
    pt[0, 1:] = 0
    lens = jnp.asarray([5, PS, MAXP * PS, 0], jnp.int32)
    return q, k, v, jnp.asarray(pt, jnp.int32), lens


def _close(got, want, dtype):
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        atol=1e-5 if dtype == jnp.float32 else 5e-2)
    # inactive slot -> exact zeros (the fully-masked-row contract)
    assert np.all(np.asarray(got, np.float32)[3] == 0.0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_kernel_matches_reference(dtype):
    q, k, v, pt, lens = _data(dtype)
    want = dap.grouped_decode_attention_reference(q, k, v, pt, lens, SCALE,
                                                  n_kv=H)
    got = dap.grouped_decode_attention_pallas(q, k, v, pt, lens, SCALE,
                                              n_kv=H, interpret=True)
    assert got.shape == want.shape == (B, H, D)
    _close(got, want, dtype)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("h", [12, 16, 20, 25, 32])
def test_multi_head_pages_met_whole(h, dtype):
    """``n_kv = h`` at GPT-2 small, medium, large, XL and a 32-head
    model: a group of 1 and 64 columns leave no aligned slice, so every
    head's banded query (rows padded to 16, 24, 32) meets the page in one
    product and its own columns are taken outside the kernel."""
    assert dap._whole_page(1, D, D)
    q, k, v, pt, lens = _data(dtype, seed=h, h=h)
    want = dap.grouped_decode_attention_reference(q, k, v, pt, lens, SCALE,
                                                  n_kv=h)
    got = dap.grouped_decode_attention_pallas(q, k, v, pt, lens, SCALE,
                                              n_kv=h, interpret=True)
    assert got.shape == (B, h, D)
    _close(got, want, dtype)


@pytest.mark.parametrize("group", [1, 2, 4])
def test_small_query_groups_pad_to_a_sublane_tile(group):
    """Query groups below 8 rows over 3 KV heads of K 32 / V 128 wide
    (V whole lane tiles: the group alone decides), with a sink logit and
    a window: the rows pad to a sublane tile and the padding is
    dropped."""
    n_kv, dk, dv = 3, 32, 128
    hq = n_kv * group
    assert dap._whole_page(group, dk, dv) and dap._rows(hq) % 8 == 0
    q, k, v, pt, lens = _data(seed=group, h=n_kv, hq=hq, dk=dk, dv=dv)
    rs = np.random.RandomState(7)
    kw = dict(n_kv=n_kv, sink=jnp.asarray(rs.randn(hq), jnp.float32),
              starts=jnp.asarray([2, 0, 40, 0], jnp.int32))
    want = dap.grouped_decode_attention_reference(q, k, v, pt, lens, SCALE,
                                                  **kw)
    got = dap.grouped_decode_attention_pallas(q, k, v, pt, lens, SCALE,
                                              interpret=True, **kw)
    assert got.shape == (B, hq, dv)
    _close(got, want, jnp.float32)


def test_whole_sublane_groups_keep_the_chunked_form():
    """MiMo's geometry (groups of 16 and 8, K 192 / V 128 wide) takes
    chunks of KV heads as before; only what has no aligned slice meets
    the page whole."""
    assert not dap._whole_page(16, 192, 128)
    assert not dap._whole_page(8, 192, 128)
    assert dap._kv_chunk(4, 192, 128) == 2
    assert dap._whole_page(8, 192, 64) and dap._whole_page(4, 128, 128)


def _quantized(x, h):
    """Codes and ``[P, h]`` scales of float pages ``[P, PS, h * D]``."""
    amax = np.abs(np.asarray(x, np.float32)).reshape(P, PS, h, -1).max(
        axis=(1, 3))
    scale = jnp.asarray(amax / kv_tier.QMAX, kv_tier.SCALE_DTYPE)
    return kv_tier.quantize(x.astype(jnp.float32), scale), scale


@pytest.mark.parametrize("h", [4, 12, 20])
def test_int8_pages_dequantize_at_read_in_the_reference(h):
    """The int8 tier: codes ``[P, PS, h * D]`` with ``[P, h]`` scales
    through the dispatched call (which takes the jnp form for them)
    equal the same call on the dequantized pages, and lie within the
    codec's error of the float pages'."""
    q, k, v, pt, lens = _data(seed=h, h=h)
    (k8, ks), (v8, vs) = _quantized(k, h), _quantized(v, h)
    got = dap.grouped_decode_attention(q, k8, v8, pt, lens, n_kv=h,
                                       sm_scale=SCALE, k_scale=ks,
                                       v_scale=vs)
    dense = dap.grouped_decode_attention_reference(
        q, kv_tier.dequantize(k8, ks), kv_tier.dequantize(v8, vs), pt, lens,
        SCALE, n_kv=h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(dense),
                               atol=1e-5)
    exact = dap.grouped_decode_attention_reference(q, k, v, pt, lens, SCALE,
                                                   n_kv=h)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exact),
                               atol=0.1)
    assert np.all(np.asarray(got)[3] == 0.0)


def test_impl_demand_asymmetry():
    q, k, v, pt, lens = _data()
    with pytest.raises(ValueError, match="unknown decode-attention"):
        dap.grouped_decode_attention(q, k, v, pt, lens, n_kv=H,
                                     impl="dense")
    # scales come as a pair, and with int8 pages only
    (k8, ks), (v8, vs) = _quantized(k, H), _quantized(v, H)
    with pytest.raises(ValueError, match="come as a pair"):
        dap.grouped_decode_attention(q, k8, v8, pt, lens, n_kv=H,
                                     k_scale=ks)
    with pytest.raises(ValueError, match="come together"):
        dap.grouped_decode_attention(q, k8, v8, pt, lens, n_kv=H)
    with pytest.raises(ValueError, match="come together"):
        dap.grouped_decode_attention(q, k, v, pt, lens, n_kv=H,
                                     k_scale=ks, v_scale=vs)
    # the kernel cannot be demanded of the int8 tier's pages
    with pytest.raises(ValueError, match="int8 pages"):
        dap.grouped_decode_attention(q, k8, v8, pt, lens, n_kv=H,
                                     k_scale=ks, v_scale=vs, impl="pallas")
    # pages that do not make n_kv heads of the query's width
    with pytest.raises(ValueError, match="do not make"):
        dap.grouped_decode_attention(q, k, v, pt, lens, n_kv=2,
                                     impl="pallas")


def _jaxpr(*args, **kw):
    return str(jax.make_jaxpr(
        lambda *a: dap.grouped_decode_attention(
            *a, n_kv=H, sm_scale=SCALE, **kw))(*args))


def test_rule_cpu_runs_the_reference():
    """No knob: on the CPU platform the default is the reference (the
    suite does not start interpreting a Pallas grid in every serving
    test); a per-call demand still gets the kernel, interpreted."""
    args = _data()
    assert jax.default_backend() == "cpu"
    assert dap.grouped_resolved(H, H, D, D, PS, jnp.float32) == "jnp"
    assert "pallas_call" not in _jaxpr(*args)
    assert "pallas_call" in _jaxpr(*args, impl="pallas")


def test_rule_tpu_runs_the_kernel_where_supported(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for h in (12, 16, 20, 32):     # GPT-2 small, medium, large; 32 heads
        assert dap.grouped_supported(h, h, 64, 64, 128, jnp.bfloat16)
        assert dap.grouped_resolved(h, h, 64, 64, 128,
                                    jnp.bfloat16) == "pallas"
    # the demand wins over the rule; the int8 tier's pages take the
    # jnp form whatever the backend
    assert dap.grouped_resolved(20, 20, 64, 64, 128, jnp.bfloat16,
                                impl="jnp") == "jnp"
    assert dap.grouped_resolved(20, 20, 64, 64, 128, jnp.int8) == "jnp"
    # interpret=True here only because no TPU is attached to lower for
    assert "pallas_call" in _jaxpr(*_data(), interpret=True)


def test_rule_unsupported_geometry_falls_back(monkeypatch):
    """A page too large for VMEM, or one that is not whole lane tiles
    (25 heads of 64): the rule takes the reference even on a TPU; a
    per-call demand for the compiled kernel raises."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert not dap.grouped_supported(25, 25, 64, 64, 128, jnp.bfloat16)
    assert not dap.grouped_supported(64, 64, 128, 128, 512, jnp.float32)
    assert dap.grouped_resolved(25, 25, 64, 64, 128, jnp.bfloat16) == "jnp"
    q = jnp.zeros((2, 25, 64), jnp.bfloat16)
    pages = jnp.zeros((4, 128, 25 * 64), jnp.bfloat16)
    pt, lens = jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32)
    out = dap.grouped_decode_attention(q, pages, pages, pt, lens, n_kv=25)
    assert out.shape == q.shape
    with pytest.raises(ValueError, match="unsupported geometry"):
        dap.grouped_decode_attention(q, pages, pages, pt, lens, n_kv=25,
                                     impl="pallas", interpret=False)


def test_no_table_op_and_no_tile():
    """The impl is a rule in code: no dispatch-table op, no table row;
    the kernel takes whole pages, so the shared tile model has no
    decode-attention entry and the module no tile setter."""
    assert "decode_attention" not in dispatch.OP_CHOICES
    entries, problems = dispatch.load_table(dispatch.default_path())
    assert not problems
    assert not [k for k in entries if k[0] == "decode_attention"]
    assert "decode_attention" not in tiles.PARAM_KEYS
    assert "decode_attention" not in tiles.DIM_KEYS
    assert not hasattr(dap, "set_block_h")
    assert not hasattr(tiles, "decode_block_h")


# ------------------------------------------- the engine on this layout


def _engine(**kw):
    from apex_tpu.serving import model as smodel
    from tests.test_serving_tp import _cfg, _engine as build

    cfg = _cfg()
    return build(cfg, smodel.init_gpt_params(cfg), **kw)


def _drive(eng, **kw):
    from tests.test_serving_tp import _drive as drive, _requests

    return drive(eng, _requests(**kw))


def test_engine_reports_its_decode_attention_program():
    """``decode_attn_impl`` says what the decode program was built
    with: "jnp" for the default engine on the CPU, "pallas" for a
    demanded (interpreted) kernel, same tokens from both."""
    eng = _engine()
    assert eng.decode_attn_impl == "jnp"
    want = _drive(eng)

    eng = _engine(decode_impl="pallas", interpret=True)
    assert eng.decode_attn_impl == "pallas"
    assert _drive(eng) == want
    assert eng.decode_cache_size() == 1


def test_tp_engine_takes_the_reference(monkeypatch):
    """A tensor-parallel engine partitions its decode jaxpr by GSPMD,
    which a pallas_call does not survive: ``tp > 1`` builds with the
    jnp reference even where the rule alone would pick the kernel."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = _engine(tp=2)
    assert eng.tp == 2 and eng.decode_impl == "jnp"
    assert eng.decode_attn_impl == "jnp"


def test_engine_cache_is_a_page_major_leaf_a_layer():
    """K and V are one ``[pages, page_size, h * d]`` array a layer, and
    ``_copy_page`` moves one page of every leaf and nothing else."""
    eng = _engine()
    cfg = eng.cfg
    width = cfg.num_attention_heads * cfg.head_dim
    assert set(eng.cache) == {"k", "v"}
    for part in ("k", "v"):
        assert len(eng.cache[part]) == cfg.num_layers
        assert {a.shape for a in eng.cache[part]} == {(48, 8, width)}
    _drive(eng)
    before = jax.tree.map(np.asarray, eng.cache)
    src = int(np.argmax([np.abs(before["k"][0][p]).sum()
                         for p in range(48)]))
    assert np.abs(before["k"][0][src]).sum() > 0
    eng._copy_page(src, 47)
    after = jax.tree.map(np.asarray, eng.cache)
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        assert np.array_equal(a[47], b[src])
        assert np.array_equal(np.delete(a, 47, axis=0),
                              np.delete(b, 47, axis=0))


def test_engine_swap_out_and_in_restores_every_leaf():
    """The host swap tier's two device hops on this layout: a slot's
    pages are gathered as ``[layers, max_pages, page_size, h * d]`` a
    leaf name (the banked wire format), and scattered back into other
    pages bit for bit, every other page untouched."""
    eng = _engine()
    _drive(eng)
    cfg, before = eng.cfg, jax.tree.map(np.asarray, eng.cache)
    live = [p for p in range(1, 48) if np.abs(before["k"][0][p]).sum() > 0]
    assert len(live) >= 3
    src = np.zeros((eng.max_pages,), np.int32)
    src[:3] = live[:3]
    banked = jax.device_get(eng._swap_gather_fn(eng.cache,
                                                jnp.asarray(src)))
    assert {a.shape for a in banked.values()} == {
        (cfg.num_layers, eng.max_pages, 8,
         cfg.num_attention_heads * cfg.head_dim)}
    dst = np.zeros((eng.max_pages,), np.int32)
    dst[:3] = [45, 46, 47]
    eng.cache = eng._swap_scatter_fn(
        eng.cache, jnp.asarray(dst),
        {name: jnp.asarray(a) for name, a in banked.items()})
    after = jax.tree.map(np.asarray, eng.cache)
    for name in ("k", "v"):
        for layer in range(cfg.num_layers):
            a, b = after[name][layer], before[name][layer]
            assert np.array_equal(a[[45, 46, 47]], b[live[:3]])
            assert np.array_equal(a[:45], b[:45])


def test_tp_cache_is_sharded_on_its_heads_axis():
    """``tp=2``: every cache leaf is split on its LAST axis, whose
    contiguous halves are whole heads; the int8 tier's ``[pages, h]``
    scales ride the same split."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.transformer.parallel_state import TENSOR_AXIS

    eng = _engine(tp=2, kv_quant=True)
    for name, leaves in eng.cache.items():
        for leaf in leaves:
            want = P(None, TENSOR_AXIS) if name.endswith("_scale") \
                else P(None, None, TENSOR_AXIS)
            assert leaf.sharding.spec == want, (name, leaf.sharding.spec)
            assert leaf.addressable_shards[0].data.shape[-1] \
                == leaf.shape[-1] // 2
    _drive(eng)
    assert eng.decode_cache_size() == 1


def test_kernel_engine_emits_the_jnp_engines_tokens_for_64_steps():
    """Greedy decode through the interpreted kernel and through the jnp
    form, float32, one slot's context crossing seven page edges of the
    64 positions: the same 56 + 8 tokens."""
    from apex_tpu.serving import Request

    def tokens(**kw):
        eng = _engine(**kw)
        reqs = [Request(rid=0, prompt=[5, 9, 2], max_new_tokens=56),
                Request(rid=1, prompt=list(range(20, 30)),
                        max_new_tokens=8)]
        for r in reqs:
            eng.submit(r)
        steps = 0
        while any(not r.done() for r in reqs):
            eng.step()
            steps += 1
        assert steps >= 55   # the prefill round gave the first token
        return [list(r.out_tokens) for r in reqs]

    want = tokens(decode_impl="jnp")
    assert [len(t) for t in want] == [56, 8]
    assert tokens(decode_impl="pallas", interpret=True) == want
