"""KV-cache memory hierarchy suite (serving/kv_tier.py, ISSUE 20):
the int8 codec's numeric contract (roundtrip band, non-finite
poisoning, the null-page-0 invariant, scatter-quantize vs the dense
reference) on the ``[pages, page_size, h * d]`` layout with ``[pages,
h]`` scales, the three-legged ``kv_restore`` resolver, and the
engine acceptance — quant greedy parity, swap-restore streams
token-for-token identical to BOTH the recompute-restored and the
never-preempted streams (greedy AND sampled), the serve_swap chaos
fallbacks, knob asymmetry, and the one-compile contract under every
enabled combination."""

import json

import numpy as np
import pytest

import jax.numpy as jnp

from apex_tpu import dispatch
from apex_tpu.ops import decode_attention_pallas as dap
from apex_tpu.resilience import faults
from apex_tpu.serving import Request, ServingEngine, kv_cache, kv_tier
from apex_tpu.serving import lifecycle
from apex_tpu.serving.sampling import SamplingParams


# ---------------------------------------------------------- the codec


def _scales(x, h):
    """Per-(page, head) amax/127 scales ``[..., h]`` of pages ``[...,
    page_size, h * width]``, in the wire dtype (bf16) — what both
    scatter paths derive."""
    x = np.asarray(x, np.float32)
    amax = np.max(np.abs(x.reshape(*x.shape[:-1], h, -1)), axis=(-3, -1))
    return jnp.asarray(amax / kv_tier.QMAX, kv_tier.SCALE_DTYPE)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
def test_roundtrip_stays_in_the_quantization_band(dtype):
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 5, 4, 3 * 8) * 3.0, dtype)  # 3 heads of 8
    scale = _scales(x, 3)
    assert scale.shape == (2, 5, 3)
    q = kv_tier.quantize(x, scale)
    assert q.dtype == kv_tier.CODE_DTYPE and q.shape == x.shape
    y = kv_tier.dequantize(q, scale, dtype)
    assert y.dtype == dtype and y.shape == x.shape
    # error ≤ one code step per (page, head) (0.5 rounding + the bf16
    # scale's own representation error), against the fp32 original
    band = np.repeat(np.asarray(scale, np.float32), 8,
                     axis=-1)[..., None, :] * 1.0 + 1e-6
    err = np.abs(np.asarray(y, np.float32) - np.asarray(x, np.float32))
    assert np.all(err <= band), float(np.max(err - band))


def test_nonfinite_inputs_poison_to_zero_codes():
    x = np.ones((1, 2, 4, 2 * 4), np.float32)    # 2 pages, 2 heads of 4
    x[0, 0, 1, 2] = np.nan
    x[0, 1, 0, 4] = np.inf
    xj = jnp.asarray(x)
    scale = _scales(kv_tier.finite(xj), 2)
    q = np.asarray(kv_tier.quantize(xj, scale))
    # the poisoned entries became exact-zero codes, their neighbors
    # quantized normally — one NaN never zeroed (or NaN'd) a page
    assert q[0, 0, 1, 2] == 0 and q[0, 1, 0, 4] == 0
    assert np.all(q[0, 0, 0] != 0)
    assert np.all(np.isfinite(np.asarray(scale, np.float32)))


def test_zero_scale_is_a_dead_page_not_a_nan_factory():
    # inv_scale guards the reciprocal: 0 scale -> 0 inverse
    inv = np.asarray(kv_tier.inv_scale(jnp.asarray([0.0, 2.0])))
    assert inv[0] == 0.0 and inv[1] == pytest.approx(0.5)
    # quantizing real content under a zero scale emits exact zeros
    # (the null-page route), and dequantizing returns exact zeros
    x = jnp.ones((2, 4, 2 * 2))
    z = jnp.zeros((2, 2), kv_tier.SCALE_DTYPE)
    assert np.all(np.asarray(kv_tier.quantize(x, z)) == 0)
    q = jnp.full((2, 4, 2 * 2), 7, kv_tier.CODE_DTYPE)
    assert np.all(np.asarray(kv_tier.dequantize(q, z)) == 0.0)


def _quant_cache(layers=1, heads=2, pages=6, ps=4, d=8):
    return kv_cache.init_cache(layers, heads, pages, ps, d,
                               kv_quant=True)


def _pages(cache, part, layer=0, heads=2):
    """One layer's dequantized pages as ``[P, ps, h, d]`` and its
    ``[P, h]`` scales, float32."""
    got = np.asarray(kv_tier.dequantize(
        cache[part][layer], cache[part + "_scale"][layer]), np.float32)
    return got.reshape(*got.shape[:2], heads, -1), \
        np.asarray(cache[part + "_scale"][layer], np.float32)


def test_quant_cache_layout():
    cache = _quant_cache(layers=3)
    assert set(cache) == {"k", "v", "k_scale", "v_scale"}
    for name, leaves in cache.items():
        assert len(leaves) == 3
        want = ((6, 2), jnp.dtype(kv_tier.SCALE_DTYPE)) \
            if name.endswith("_scale") \
            else ((6, 4, 2 * 8), jnp.dtype(kv_tier.CODE_DTYPE))
        assert {(a.shape, a.dtype) for a in leaves} == {want}
    assert kv_tier.is_quantized(cache)
    assert not kv_tier.is_quantized(kv_cache.init_cache(1, 2, 6, 4, 8))


def test_prefill_scatter_quant_matches_dense_and_pins_page0():
    rs = np.random.RandomState(1)
    cache = _quant_cache()
    # 6 packed rows: 4 fill page 1, 2 start page 2; rows routed to
    # page 0 are the packer's padding lanes and must stay dead
    val = jnp.asarray(rs.randn(8, 2, 8), jnp.float32)
    dest_page = jnp.asarray([1, 1, 1, 1, 2, 2, 0, 0], jnp.int32)
    dest_off = jnp.asarray([0, 1, 2, 3, 0, 1, 0, 0], jnp.int32)
    keep = jnp.zeros((6,), jnp.float32).at[jnp.asarray([3, 4, 5])].set(1.0)
    fresh = _quant_cache()
    cache = kv_tier.prefill_scatter_quant(
        cache, 0, "k", val, dest_page, dest_off, keep)
    # the caller's cache is left as it was (a new dict, new lists)
    assert np.all(np.asarray(fresh["k"][0]) == 0)
    got, scale = _pages(cache, "k")
    want = np.asarray(val, np.float32)
    for r in range(6):
        p, o = int(dest_page[r]), int(dest_off[r])
        err = np.abs(got[p, o] - want[r])                  # [h, d]
        assert np.all(err <= scale[p, :, None] + 1e-6), (r, err.max())
    # null page 0 stays all-zero with a pinned-zero scale, even though
    # two padding rows were "scattered" there
    assert np.all(np.asarray(cache["k"][0])[0] == 0)
    assert np.all(scale[0] == 0)
    # untouched pages never grew a scale
    assert np.all(scale[[3, 4, 5]] == 0)
    # a verify re-cover of page 2 (keep=1 there now) preserves page 1
    # verbatim: same scale -> ratio 1 -> bit-identical codes
    before = np.asarray(cache["k"][0])[1].copy()
    val2 = jnp.asarray(rs.randn(2, 2, 8) * 0.1, jnp.float32)
    keep2 = jnp.ones((6,), jnp.float32).at[0].set(0.0)
    cache = kv_tier.prefill_scatter_quant(
        cache, 0, "k", val2, jnp.asarray([2, 2], jnp.int32),
        jnp.asarray([2, 3], jnp.int32), keep2)
    assert np.array_equal(np.asarray(cache["k"][0])[1], before)
    # the small rows landed without blowing up page 2's earlier rows
    got2, scale2 = _pages(cache, "k")
    err = np.abs(got2[2, :2] - want[4:6])                  # [2, h, d]
    assert np.all(err <= scale2[2][None, :, None] + 1e-6)


def test_decode_scatter_quant_rmw_preserves_and_zeroes():
    rs = np.random.RandomState(2)
    cache = _quant_cache()
    seedrows = jnp.asarray(rs.randn(2, 2, 8), jnp.float32)
    cache = kv_tier.prefill_scatter_quant(
        cache, 0, "v", seedrows, jnp.asarray([3, 3], jnp.int32),
        jnp.asarray([0, 1], jnp.int32), jnp.zeros((6,), jnp.float32))
    # two decode lanes: lane 0 appends row 2 of page 3; lane 1 is an
    # inactive slot routed to page 0
    new = jnp.asarray(rs.randn(2, 2, 8), jnp.float32)
    cache = kv_tier.decode_scatter_quant(
        cache, 0, "v", new, jnp.asarray([3, 0], jnp.int32),
        jnp.asarray([2, 0], jnp.int32))
    got, scale = _pages(cache, "v")
    band = scale[3][:, None] + 1e-6                        # [h, 1]
    # earlier rows survived the read-modify-write, the new row landed
    want = np.asarray(seedrows, np.float32)
    for o in range(2):
        assert np.all(np.abs(got[3, o] - want[o]) <= band)
    assert np.all(np.abs(got[3, 2] - np.asarray(new)[0]) <= band)
    # rows at/beyond the write offset were zeroed (stale garbage dies)
    assert np.all(got[3, 3] == 0)
    # the inactive lane re-wrote page 0 with exact zeros
    assert np.all(np.asarray(cache["v"][0])[0] == 0)
    assert np.all(scale[0] == 0)
    # the layer not written is the array it was
    two = _quant_cache(layers=2)
    out = kv_tier.decode_scatter_quant(
        two, 1, "v", new, jnp.asarray([3, 0], jnp.int32),
        jnp.asarray([2, 0], jnp.int32))
    assert out["v"][0] is two["v"][0] and out["k"] is two["k"]


def test_int8_pages_without_scales_raise():
    """Codes are meaningless without their scales: the dispatched call
    refuses int8 pages that come with one scale or none (the attention
    parity of the tier is tests/test_decode_attention_pallas.py's)."""
    h, P, PS, D = 2, 6, 4, 8
    rs = np.random.RandomState(3)
    q = jnp.asarray(rs.randn(2, h, D), jnp.float32)
    kf = jnp.asarray(rs.randn(P, PS, h * D), jnp.float32)
    ks = _scales(kf, h)
    k8 = kv_tier.quantize(kf, ks)
    pt = jnp.asarray([[1, 2], [3, 0]], jnp.int32)
    lens = jnp.asarray([6, 2], jnp.int32)
    with pytest.raises(ValueError, match="come as a pair"):
        dap.grouped_decode_attention(q, k8, k8, pt, lens, n_kv=h,
                                     k_scale=ks)
    with pytest.raises(ValueError, match="int8"):
        dap.grouped_decode_attention(q, k8, k8, pt, lens, n_kv=h)
    out = dap.grouped_decode_attention(q, k8, k8, pt, lens, n_kv=h,
                                       k_scale=ks, v_scale=ks)
    assert out.shape == (2, h, D) and np.all(np.isfinite(np.asarray(out)))


# ------------------------------------------- the kv_restore resolver


def test_resolver_demand_legs_raise_unhonorable(monkeypatch):
    r = kv_tier.resolve_kv_restore
    with pytest.raises(ValueError, match="unknown kv_restore"):
        r("mmap", swap_enabled=True, tokens=8, dtype=jnp.bfloat16)
    with pytest.raises(ValueError, match="never banked"):
        r("swap", swap_enabled=False, tokens=8, dtype=jnp.bfloat16)
    # honorable demands pass through untouched
    assert r("recompute", swap_enabled=True, tokens=8,
             dtype=jnp.bfloat16) == "recompute"
    # tier off: every preference leg collapses to recompute
    monkeypatch.setenv("APEX_SERVE_KV_RESTORE", "swap")
    assert r(None, swap_enabled=False, tokens=8,
             dtype=jnp.bfloat16) == "recompute"


def test_resolver_env_table_builtin_legs(tmp_path, monkeypatch):
    r = kv_tier.resolve_kv_restore
    path = tmp_path / "table.jsonl"
    path.write_text(json.dumps(dispatch.make_entry(
        "kv_restore", {"s": 10}, jnp.bfloat16, "cpu", "recompute",
        "lg-" + "0" * 10)) + "\n")
    monkeypatch.setenv("APEX_DISPATCH_TABLE", str(path))
    dispatch._reset_for_tests()
    try:
        # table leg: bucket s16 has a committed recompute crossover
        assert r(None, swap_enabled=True, tokens=10, dtype=jnp.bfloat16,
                 backend="cpu") == "recompute"
        # table miss (s128): the tier's built-in is swap
        assert r(None, swap_enabled=True, tokens=100,
                 dtype=jnp.bfloat16, backend="cpu") == "swap"
        # env preference outranks the table
        monkeypatch.setenv("APEX_SERVE_KV_RESTORE", "swap")
        assert r(None, swap_enabled=True, tokens=10, dtype=jnp.bfloat16,
                 backend="cpu") == "swap"
    finally:
        monkeypatch.delenv("APEX_DISPATCH_TABLE")
        dispatch._reset_for_tests()


# ------------------------------------------------- engine acceptance


def _cfg():
    from apex_tpu.transformer.testing import TransformerConfig

    return TransformerConfig(
        hidden_size=32, num_layers=1, num_attention_heads=2,
        vocab_size=64, max_position_embeddings=32,
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=False)


@pytest.fixture(scope="module")
def setup():
    cfg = _cfg()
    from apex_tpu.serving import model as smodel

    params = smodel.init_gpt_params(cfg)
    ref = _engine(cfg, params)  # the never-preempted reference
    reqs = _requests()
    _drive(ref, reqs)
    return cfg, params, {r.rid: list(r.out_tokens) for r in reqs}


@pytest.fixture(autouse=True)
def _clean_faults(monkeypatch):
    monkeypatch.delenv("APEX_FAULT_PLAN", raising=False)
    faults._cache["fired"] = {}
    yield
    faults._cache["fired"] = {}


def _requests():
    return [Request(rid=0, prompt=[1, 2, 3, 4, 5, 6],
                    max_new_tokens=10),
            Request(rid=1, prompt=[7, 8, 9, 10, 11, 12],
                    max_new_tokens=10)]


def _drive(eng, reqs, guard=300):
    for r in reqs:
        eng.submit(r)
    n = 0
    while not all(r.done() for r in reqs):
        eng.step()
        n += 1
        assert n < guard, ("engine did not drain",
                           [r.out_tokens for r in reqs])
    eng.step()


def _engine(cfg, params, **kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("num_pages", 32)
    kw.setdefault("max_seq", 32)
    kw.setdefault("prefill_len", 16)
    if kw.get("preempt") or kw.get("kv_swap"):
        lifecycle.enable()
        try:
            return ServingEngine(cfg, params=params, **kw)
        finally:
            lifecycle.reset_enabled()
    return ServingEngine(cfg, params=params, **kw)


def _contract(eng):
    assert eng.decode_cache_size() == 1, eng.decode_cache_size()
    assert eng.prefill_cache_size() <= 1, eng.prefill_cache_size()
    eng.allocator.check_invariants()


def test_kv_quant_greedy_parity_one_compile(setup):
    cfg, params, ref = setup
    eng = _engine(cfg, params, kv_quant=True)
    assert eng.kv_quant and kv_tier.is_quantized(eng.cache)
    reqs = _requests()
    _drive(eng, reqs)
    for r in reqs:
        assert r.out_tokens == ref[r.rid], (r.rid, r.out_tokens)
    _contract(eng)


def test_swap_restore_token_identical_to_both_references(setup):
    """THE swap acceptance: under real KV pressure the swap-restored
    streams match token-for-token BOTH the recompute-restored engine
    and the never-preempted reference — restore is a pure latency
    decision, never a numerics one — and the handle economics close
    (live pages drain to 0, high-water recorded, rates surfaced)."""
    cfg, params, ref = setup
    pool = dict(num_pages=6, max_seq=16, preempt=True)
    rec_eng = _engine(cfg, params, **pool)
    rec_reqs = _requests()
    _drive(rec_eng, rec_reqs)
    assert rec_eng.resilience.preempted >= 1
    eng = _engine(cfg, params, kv_swap=True, **pool)
    reqs = _requests()
    _drive(eng, reqs)
    assert eng.resilience.preempted >= 1
    st = eng.kv_stats
    assert st.swap_outs >= 1 and st.swap_ins >= 1, vars(st)
    assert st.restores_swap >= 1 and st.swap_in_failures == 0, vars(st)
    assert st.swapped_pages_live == 0 and st.swapped_bytes_live == 0
    assert st.swapped_pages_high_water >= 1
    for r, rr in zip(reqs, rec_reqs):
        assert r.out_tokens == rr.out_tokens, (r.rid, r.out_tokens)
        assert r.out_tokens == ref[r.rid], (r.rid, r.out_tokens)
    assert eng.events.validate_order() == []
    rates = eng.kv_tier_rates()
    assert rates["swap_rate"] and 0 < rates["swap_rate"] <= 1
    assert rates["swapped_pages_high_water"] >= 1
    _contract(eng)


def test_quant_swap_composed_parity(setup):
    cfg, params, ref = setup
    eng = _engine(cfg, params, num_pages=6, max_seq=16, preempt=True,
                  kv_swap=True, kv_quant=True)
    reqs = _requests()
    _drive(eng, reqs)
    assert eng.resilience.preempted >= 1
    assert eng.kv_stats.swap_ins >= 1, vars(eng.kv_stats)
    for r in reqs:
        assert r.out_tokens == ref[r.rid], (r.rid, r.out_tokens)
    _contract(eng)


def test_swap_restore_sampled_parity(setup):
    """The sampled half of the acceptance: a seeded stochastic stream
    swap-restores to the SAME tokens it draws never-preempted — the
    sampling counter is the request's own generation index and
    ``resume_tokens`` carries the pending draw, so the restored slot
    re-enters the decode program at an identical lane state."""
    cfg, params, _ = setup

    def _sampled():
        return [Request(rid=0, prompt=[1, 2, 3, 4, 5, 6],
                        max_new_tokens=10,
                        sampling=SamplingParams(temperature=0.9,
                                                top_k=20, seed=7)),
                Request(rid=1, prompt=[7, 8, 9, 10, 11, 12],
                        max_new_tokens=10,
                        sampling=SamplingParams(temperature=1.1,
                                                seed=11))]

    ref_eng = _engine(cfg, params, sampling=True)
    ref_reqs = _sampled()
    _drive(ref_eng, ref_reqs)
    eng = _engine(cfg, params, num_pages=6, max_seq=16, preempt=True,
                  kv_swap=True, sampling=True)
    reqs = _sampled()
    _drive(eng, reqs)
    assert eng.resilience.preempted >= 1
    assert eng.kv_stats.restores_swap >= 1, vars(eng.kv_stats)
    for r, rr in zip(reqs, ref_reqs):
        assert r.out_tokens == rr.out_tokens, (r.rid, r.out_tokens)
    _contract(eng)


def test_swap_out_fault_falls_back_to_recompute(setup, monkeypatch):
    """serve_swap chaos, swap-out leg: the banking copy raises ONCE —
    the victim restores by recompute instead (degraded latency, same
    tokens), the failure is counted AND classified (a ``swap_failed``
    event between preempted and resubmitted), order stays valid."""
    cfg, params, ref = setup
    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps(
        [{"site": "serve_swap", "kind": "raise", "times": 1,
          "match_ctx": {"phase": "swap_out"}}]))
    eng = _engine(cfg, params, num_pages=6, max_seq=16, preempt=True,
                  kv_swap=True)
    reqs = _requests()
    _drive(eng, reqs)
    assert eng.kv_stats.swap_out_failures >= 1, vars(eng.kv_stats)
    for r in reqs:
        assert r.out_tokens == ref[r.rid], (r.rid, r.out_tokens)
    victim = next(r for r in reqs if r.preemptions)
    chain = [e["event"] for e in eng.events.request_events(victim.rid)]
    i = chain.index("swap_failed")
    assert chain[i - 1] == "preempted" and chain[i + 1] == "resubmitted"
    assert eng.events.validate_order() == []
    _contract(eng)


def test_corrupt_banked_bytes_caught_by_checksum(setup, monkeypatch):
    """serve_swap chaos, swap-in leg: a bit flipped in the banked host
    bytes is caught by the handle's seal BEFORE any page lands on
    device — the stream falls back to recompute with the same tokens,
    never a corrupted cache."""
    cfg, params, ref = setup
    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps(
        [{"site": "serve_swap", "kind": "corrupt", "times": 1,
          "match_ctx": {"phase": "swap_in"}}]))
    eng = _engine(cfg, params, num_pages=6, max_seq=16, preempt=True,
                  kv_swap=True)
    reqs = _requests()
    _drive(eng, reqs)
    assert eng.kv_stats.swap_in_failures >= 1, vars(eng.kv_stats)
    assert eng.kv_stats.restores_recompute >= 1
    for r in reqs:
        assert r.out_tokens == ref[r.rid], (r.rid, r.out_tokens)
    assert eng.events.validate_order() == []
    assert eng.kv_stats.swapped_pages_live == 0  # failed handle freed
    _contract(eng)


def test_handle_seal_detects_tampering():
    h = kv_tier.SwappedPages(
        leaves={"k": np.arange(16, dtype=np.int8).reshape(2, 8)},
        page_count=1, tokens=3, quant=True).seal()
    assert h.intact() and h.nbytes() == 16
    h.leaves["k"].view(np.uint8).ravel()[5] ^= 0xFF
    assert not h.intact()


def test_kv_knob_asymmetry(setup, monkeypatch):
    cfg, params, _ = setup
    # kv_swap demand without preemption: no honorable answer
    with pytest.raises(ValueError, match="preempt"):
        _engine(cfg, params, kv_swap=True)
    # kv_restore='swap' demand on a swap-less engine raises at build
    with pytest.raises(ValueError, match="never banked"):
        _engine(cfg, params, kv_restore="swap")
    # env preferences fall back / engage without raising
    monkeypatch.setenv("APEX_SERVE_KV_SWAP", "1")
    eng = _engine(cfg, params)
    assert not eng.kv_swap  # pref dropped: preemption is off
    monkeypatch.setenv("APEX_SERVE_KV_QUANT", "1")
    eng2 = _engine(cfg, params)
    assert eng2.kv_quant and kv_tier.is_quantized(eng2.cache)
    # the resolver legs behind the engine knobs
    monkeypatch.delenv("APEX_SERVE_KV_QUANT")
    assert kv_tier.resolve_kv_quant() is False
    assert kv_tier.resolve_kv_quant(True) is True
    assert kv_tier.resolve_kv_swap() is True  # env still set
    monkeypatch.delenv("APEX_SERVE_KV_SWAP")
    assert kv_tier.resolve_kv_swap() is False


def test_one_compile_contract_under_every_combination(setup):
    cfg, params, ref = setup
    combos = [
        dict(kv_quant=True, decode_k=2),
        dict(kv_quant=True, num_pages=6, max_seq=16, preempt=True,
             kv_swap=True),
        dict(num_pages=6, max_seq=16, preempt=True, kv_swap=True,
             kv_restore="recompute"),
    ]
    for kw in combos:
        eng = _engine(cfg, params, **kw)
        reqs = _requests()
        _drive(eng, reqs)
        for r in reqs:
            assert r.out_tokens == ref[r.rid], (kw, r.rid, r.out_tokens)
        _contract(eng)
