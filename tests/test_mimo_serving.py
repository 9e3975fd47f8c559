"""The MiMo-V2 serving family against its plain reference
(``perf/references/mimo_v2.py``, which imports nothing from
``apex_tpu``), through ``ServingEngine.step``: the scheduler, the page
allocator, both KV caches (paged global pool, per-slot window rings) and
both programs, at a toy size with every mechanism of the real one
(``mimo_toy.TOY``: window 8 over pages of 4, 8 heads on 1 and 2 KV
heads, K 48 / V 32 wide, 16 experts top-4 of which 4 are held).

Two runs, five requests each (prompts of 3-20 tokens: shorter and
longer than a page and than the window; 24-30 decode steps each):

* float32 weights and cache: the program and the reference then differ
  by summation order alone (measured 1.6e-6 at logits of size ~2.5), so
  the comparison is held to 1e-4: sixty times the reading, and thirty
  times below the smallest thing it has to catch (the correction bias
  used as a weight moves the nearest position by 3.2e-3, experts rounded
  to fp8 by 4.7e-3, every weight rounded to bfloat16 by 7.6e-3). Every
  negative control runs through THIS comparison and must fail it.
* bfloat16 as deployed: weights, activations and cache round to 8 bits
  of significand through 7 layers; measured median 0.012-0.014 and
  largest 0.053-0.070 over three seeds. A top-4 choice that flips on a
  near-tie swaps one expert's weighted output for another's (about 1/4
  of one layer's expert sum), which is what the largest readings are:
  positions over 0.04 are counted as flips, and bounded (at most one
  position in ten, none over 0.15).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mimo_toy as T
from round_halves import whole_round
from apex_tpu.serving import ServingEngine
from apex_tpu.serving import family as family_mod
from apex_tpu.serving import kv_cache
from apex_tpu.serving.scheduler import Request
from apex_tpu.transformer import moe

ref = T.reference
SIZES = [(3, 26), (11, 24), (20, 30), (5, 28), (9, 25)]   # prompt, answer
F32_TOL = 1e-4


def _run(cfg, params, seed=3):
    engine = ServingEngine(cfg, params=params, num_slots=4, page_size=4,
                           num_pages=64, max_seq=64, prefill_len=32)
    tap = T.LogitsTap(engine)
    rs = np.random.RandomState(seed)
    requests = [Request(rid=i, prompt=rs.randint(0, 512, n).tolist(),
                        max_new_tokens=m) for i, (n, m) in enumerate(SIZES)]
    T.serve(engine, requests)
    assert (tap._prefill._cache_size(), tap._decode._cache_size()) == (1, 1)
    return engine, tap, requests


@pytest.fixture(scope="module")
def f32_run():
    cfg = T.toy_config(cache_dtype="float32")
    params = T.toy_params(cfg)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return (cfg, params) + _run(cfg, params)


@pytest.fixture(scope="module")
def bf16_run():
    cfg = T.toy_config()
    params = T.toy_params(cfg)
    return (cfg, params) + _run(cfg, params)


def _errors(run, config=None, params=None, fault=None):
    cfg, own, _, tap, requests = run
    config = cfg.to_dict() if config is None else config
    params = own if params is None else params
    return T.compare(tap, requests, lambda seq: ref.logits(
        config, params, seq, _fault=fault))[0]


def test_float32_engine_matches_reference_through_both_caches(f32_run):
    errors = _errors(f32_run)
    assert len(errors) == sum(m for _, m in SIZES)   # every position
    assert errors.max() <= F32_TOL, errors.max()


def test_bfloat16_engine_matches_reference_with_bounded_flips(bf16_run):
    errors = _errors(bf16_run)
    flips = int((errors > 0.04).sum())
    assert np.median(errors) <= 0.03, np.median(errors)
    assert flips <= len(errors) // 10 and errors.max() <= 0.15, \
        (flips, errors.max())


def test_engine_spans_carry_the_expert_and_cache_counts(bf16_run):
    from apex_tpu.telemetry import spans

    rounds = [r for r in spans.snapshot() if r.name == "engine.round"
              and r.attrs and "experts_touched" in r.attrs]
    assert rounds
    a = rounds[-1].attrs
    assert a["experts_held"] == 6 * 4      # expert layers x held experts
    assert 0 < a["experts_touched"] <= a["experts_held"]
    assert a["expert_tokens_sum"] >= a["expert_tokens_max"] >= 1
    # window 8 over pages of 4: a slot's window lies in 2 or 3 ring pages
    assert a["global_pages_live"] >= a["window_pages"] >= 2


def test_prefill_fetch_span_carries_the_row_bound_account(bound_at_toy_sizes):
    """The expert layers' row bound (``moe.held_row_bound``) engages in
    a 512-row trunk and not in a 64-row one; either way the float32
    program stays on the reference, and ``prefill.fetch`` says what the
    bound was, how near the held assignments came, and that no layer
    took the form over every row (the toy routes uniformly)."""
    cfg = T.toy_config(cache_dtype="float32")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    T.toy_params(cfg))
    errors, (long, short) = T.long_prompt_run(
        cfg, params, lambda seq: ref.logits(cfg.to_dict(), params, seq))
    assert len(errors) == 2 * 3 and errors.max() <= F32_TOL, errors.max()
    assert long["expert_rows"] == moe.held_row_bound(512, 4, 4, 16) == 1024
    assert short["expert_rows"] == 64 * 4              # every row: no bound
    for attrs, tokens in ((long, 300), (short, 20)):
        assert 0 < attrs["held_rows_max"] <= tokens * 4
        assert attrs["expert_rows_full"] == 0
    assert long["held_rows_max"] < long["expert_rows"]


CONTROLS = {
    "sink_dropped": (dict(add_swa_attention_sink_bias=False), None),
    "window_7_instead_of_8": (dict(sliding_window=7), None),
    "value_scale_omitted": (dict(attention_value_scale=1.0), None),
    "correction_bias_used_as_a_weight": ({}, "bias_as_weight"),
    "topk_weights_not_renormalised": (dict(norm_topk_prob=False), None),
    "rotary_base_of_the_other_layer_kind": (
        dict(rope_theta=T.toy_config().swa_rope_theta,
             swa_rope_theta=T.toy_config().rope_theta), None),
    "kv_head_mapping_off_by_one": ({}, "kv_map_off_by_one"),
}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_negative_control_fails_the_comparison(f32_run, name):
    changes, fault = CONTROLS[name]
    errors = _errors(f32_run, {**f32_run[0].to_dict(), **changes},
                     fault=fault)
    assert errors.max() > F32_TOL


def _rounded_experts(params, rounding):
    out = jax.tree_util.tree_map(lambda a: a, params)
    for lp in out["layers"]:
        if "router" in lp:
            for name in ("w_gate", "w_up", "w_down"):
                lp[name] = rounding(lp[name])
    return out


def _fp8(w):
    import ml_dtypes

    return jnp.asarray(np.asarray(w, np.float32).astype(
        ml_dtypes.float8_e4m3fn).astype(np.float32))


def _int8(w):
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    return jnp.round(w / scale) * scale


@pytest.mark.parametrize("rounding", [_fp8, _int8], ids=["fp8", "int8"])
def test_lower_precision_experts_fail_the_comparison(f32_run, rounding):
    errors = _errors(f32_run,
                     params=_rounded_experts(f32_run[1], rounding))
    assert errors.max() > F32_TOL


# ----------------------------------------------------------- the share

def _moe_layer(cfg_dict, seed=0, tokens=24):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    H, F, E = (cfg_dict["hidden_size"], cfg_dict["moe_intermediate_size"],
               cfg_dict["n_routed_experts"])
    lp = {"router": jax.random.normal(keys[0], (E, H)) * 0.1,
          "router_bias": jax.random.normal(keys[1], (E,)) * 0.1,
          "w_gate": jax.random.normal(keys[2], (E, H, F)) * 0.05,
          "w_up": jax.random.normal(keys[3], (E, H, F)) * 0.05,
          "w_down": jax.random.normal(keys[4], (E, F, H)) * 0.05}
    return lp, jax.random.normal(keys[5], (tokens, H))


def _share(lp, first, count):
    return {**lp, **{n: lp[n][first:first + count]
                     for n in ("w_gate", "w_up", "w_down")}}


def test_the_four_shares_add_up_to_the_uncut_layer():
    d = T.toy_config().to_dict()
    lp, x = _moe_layer(d)
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe({**d, "held_experts": (0, 16)}, lp, x)
        experts, weights = moe.route_sigmoid_topk(
            x, lp["router"], lp["router_bias"], d["num_experts_per_tok"])
        total, total_ref, assigned = 0.0, 0.0, 0
        for first in (0, 4, 8, 12):
            part = _share(lp, first, 4)
            y, counts = moe.held_experts_mlp(
                x, experts, weights, part["w_gate"], part["w_up"],
                part["w_down"], first)
            total, assigned = total + y, assigned + int(counts.sum())
            total_ref = total_ref + ref.moe(
                {**d, "held_experts": (first, 4)}, part, x)
    assert assigned == x.shape[0] * d["num_experts_per_tok"]   # dropless
    assert float(jnp.max(jnp.abs(uncut))) > 0.05
    np.testing.assert_allclose(total, uncut, atol=2e-6)
    np.testing.assert_allclose(total_ref, uncut, atol=2e-6)


def test_padding_rows_reach_no_expert():
    d = T.toy_config().to_dict()
    lp, x = _moe_layer(d, seed=3, tokens=16)
    experts, weights = moe.route_sigmoid_topk(
        x, lp["router"], lp["router_bias"], 4)
    part = _share(lp, 4, 4)
    args = (x, experts, weights, part["w_gate"], part["w_up"],
            part["w_down"], 4)
    valid = jnp.arange(16) < 10                     # six rows of padding
    y_all, n_all = moe.held_experts_mlp(*args)
    y, n = moe.held_experts_mlp(*args, valid=valid)
    held = (np.asarray(experts) >= 4) & (np.asarray(experts) < 8)
    assert int(n.sum()) == int(held[:10].sum()) < int(n_all.sum())
    np.testing.assert_allclose(y[:10], y_all[:10], atol=1e-6)
    assert not np.asarray(y[10:]).any()


def test_routing_matches_the_reference_and_the_bias_only_selects():
    d = T.toy_config().to_dict()
    lp, x = _moe_layer(d, seed=1)
    with jax.default_matmul_precision("highest"):
        chosen, w = ref.route(d, lp, x)
    experts, weights = moe.route_sigmoid_topk(
        x, lp["router"], lp["router_bias"], d["num_experts_per_tok"])
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    np.testing.assert_allclose(np.sort(weights, -1), np.sort(w, -1),
                               atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 1.0, atol=1e-6)
    # a bias that reverses the choice leaves the chosen scores' ratio
    far = moe.route_sigmoid_topk(x, lp["router"], lp["router_bias"] * 0
                                 + jnp.arange(16.0), 4)[0]
    assert set(np.asarray(far).ravel()) == {12, 13, 14, 15}


def test_grouped_matmul_kernel_matches_ragged_dot_in_interpret_mode():
    d = T.toy_config().to_dict()
    lp, x = _moe_layer(d, seed=2, tokens=32)     # 32 x 4 = one row tile
    experts, weights = moe.route_sigmoid_topk(
        x, lp["router"], lp["router_bias"], 4)
    part = _share(lp, 4, 4)
    args = (x, experts, weights, part["w_gate"], part["w_up"],
            part["w_down"], 4)
    plain, counts = moe.held_experts_mlp(*args, impl="ragged_dot")
    kernel, counts2 = moe.held_experts_mlp(*args, impl="pallas",
                                           interpret=True)
    np.testing.assert_array_equal(counts, counts2)
    np.testing.assert_allclose(kernel, plain, atol=1e-6)


# -------------------------------------------------- the held rows' bound
#
# 512 tokens x top 4 of 16 experts with 4 held (4..7): 2,048 assignment
# rows, of which a uniform router sends 512 here; the bound is twice
# that, 1,024 rows, and spares 8 row tiles, so the layer's cond engages.

BOUND = dict(T=512, k=4, E=16, first=4, count=4, rows=1024)


@pytest.fixture
def bound_at_toy_sizes(monkeypatch):
    """The program engages the bound where it spares 96 row tiles (a
    2,048-row trunk at top 8); the toy layers spare 8."""
    monkeypatch.setattr(moe, "HELD_ROWS_SPARED_MIN", 8)


def _bound_case(routing, dtype=jnp.float32, seed=5):
    """``(x, experts, weights, w_gate, w_up, w_down, valid)`` with
    ``routing``'s number of held assignments: ``uniform`` ~512,
    ``skewed`` 1,536 (over the bound), ``exactly`` 1,024 (the bound),
    ``none`` 0, ``padding`` 3 held a token as ``skewed`` but only 340
    rows are tokens (1,020: padding must not count)."""
    b = BOUND
    rs = np.random.RandomState(seed)
    held = np.arange(b["first"], b["first"] + b["count"])
    others = np.setdiff1d(np.arange(b["E"]), held)
    n_held = {"uniform": None, "skewed": 3, "exactly": 2, "none": 0,
              "padding": 3}[routing]
    experts = np.empty((b["T"], b["k"]), np.int32)
    for t in range(b["T"]):
        if n_held is None:
            experts[t] = rs.permutation(b["E"])[:b["k"]]
        else:
            experts[t] = rs.permutation(np.concatenate(
                [rs.permutation(held)[:n_held],
                 rs.permutation(others)[:b["k"] - n_held]]))
    weights = rs.uniform(0.1, 1.0, (b["T"], b["k"])).astype(np.float32)
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    H, F = 128, 64
    x = jax.random.normal(keys[0], (b["T"], H), dtype)
    mats = [(jax.random.normal(key, shape) * 0.05).astype(dtype)
            for key, shape in zip(keys[1:], [(b["count"], H, F),
                                             (b["count"], H, F),
                                             (b["count"], F, H)])]
    valid = jnp.arange(b["T"]) < 340 if routing == "padding" else None
    return (x, jnp.asarray(experts), jnp.asarray(weights), *mats, valid)


def _both_forms(case, **kernel):
    """The layer with every row (no router width given: today's path)
    and with the bound engaged."""
    *args, valid = case
    kw = dict(valid=valid, **kernel)
    return (moe.held_experts_mlp(*args, BOUND["first"], **kw),
            moe.held_experts_mlp(*args, BOUND["first"], **kw,
                                 num_experts=BOUND["E"]))


@pytest.mark.parametrize("T_,k,count,E,rows", [
    (4096, 8, 12, 192, 4096), (2048, 8, 12, 192, 2048),
    (2048, 8, 16, 256, 2048),                  # long prefill trunks: T*k / 8
    (1024, 8, 12, 192, 8192), (512, 8, 12, 192, 4096),
    (256, 8, 16, 256, 2048),                   # spare under 96 tiles: every row
    (64, 8, 12, 192, 512), (32, 8, 12, 192, 256),
    (64, 8, 16, 256, 512),                     # decode lanes: every row
    (4096, 8, 192, 192, 32768), (4096, 8, 16, None, 32768),   # uncut
    (4096, 8, 100, 192, 32768),                # twice the share is all
    (8192, 4, 4, 16, 16384), (8200, 4, 3, 16, 12416),   # whole row tiles
])
def test_held_row_bound_is_twice_the_uniform_share_in_row_tiles(
        T_, k, count, E, rows):
    assert moe.held_row_bound(T_, k, count, E) == rows


@pytest.mark.parametrize("impl", ["ragged_dot", "pallas"])
@pytest.mark.parametrize("routing", ["uniform", "skewed", "exactly", "none",
                                     "padding"])
def test_bounded_layer_equals_the_layer_over_every_row(bound_at_toy_sizes, routing, impl):
    case = _bound_case(routing)
    (y_all, n_all), (y, n) = _both_forms(case, impl=impl, interpret=True)
    held = {"skewed": 1536, "exactly": BOUND["rows"], "none": 0,
            "padding": 3 * 340}.get(routing)
    if held is not None:
        assert int(n_all.sum()) == held
    else:
        assert 400 < int(n_all.sum()) < 640
    np.testing.assert_array_equal(n, n_all)
    assert routing == "none" or float(jnp.abs(y_all).max()) > 0.01
    # float32: the same terms in another order
    np.testing.assert_allclose(y, y_all, atol=1e-6)
    if routing == "padding":
        assert not np.asarray(y[340:]).any()
    if routing == "none":
        assert not np.asarray(y).any()


@pytest.mark.parametrize("impl", ["ragged_dot", "pallas"])
def test_bounded_layer_in_bfloat16_equals_the_layer_over_every_row(bound_at_toy_sizes, impl):
    case = _bound_case("uniform", jnp.bfloat16)
    (y_all, n_all), (y, n) = _both_forms(case, impl=impl, interpret=True)
    np.testing.assert_array_equal(n, n_all)
    assert y.dtype == jnp.bfloat16 and float(jnp.abs(y_all).max()) > 0.01
    # both round ONE float32 sum of the same bfloat16 rows to bfloat16
    np.testing.assert_allclose(y.astype(jnp.float32),
                               y_all.astype(jnp.float32), atol=2 ** -9)


@pytest.mark.parametrize("routing,poisoned", [
    ("uniform", "_every_row"), ("exactly", "_every_row"),
    ("none", "_every_row"), ("padding", "_every_row"),
    ("skewed", "_held_rows")])
def test_the_count_on_the_device_picks_the_branch(bound_at_toy_sizes,
                                                  monkeypatch, routing,
                                                  poisoned):
    """The branch that must NOT run is made to answer NaN: at or under
    the bound (padding not counted) the held rows' form runs, over it
    the form over every row, so nothing is ever dropped."""
    *args, valid = _bound_case(routing)
    want, _ = moe.held_experts_mlp(*args, BOUND["first"], valid=valid)
    real = getattr(moe, poisoned)

    def nan(*a, **kw):
        out = real(*a, **kw)
        if isinstance(out, tuple):
            return jnp.full_like(out[0], jnp.nan), out[1]
        return jnp.full_like(out, jnp.nan)

    monkeypatch.setattr(moe, poisoned, nan)
    moe._held_rows_or_every_row.clear_cache()
    try:
        got, _ = moe.held_experts_mlp(*args, BOUND["first"], valid=valid,
                                      num_experts=BOUND["E"])
    finally:
        monkeypatch.undo()
        moe._held_rows_or_every_row.clear_cache()
    np.testing.assert_allclose(got, want, atol=1e-6)


@pytest.mark.parametrize("T_,count,E,conds", [
    (32, 16, 256, 0), (64, 16, 256, 0),        # MiMo's decode: T*k 256, 512
    (32, 12, 192, 0), (64, 12, 192, 0),        # A.X-K1's
    (2048, 16, 16, 0), (2048, 16, None, 0),    # an uncut layer
    (1024, 16, 256, 0), (512, 12, 192, 0),     # short prefill trunks
    (2048, 16, 256, 1), (4096, 12, 192, 1),    # long ones
])
def test_only_a_long_cut_prefill_layer_holds_a_cond(T_, count, E, conds):
    k, H, F = 8, 32, 16
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    text = str(jax.make_jaxpr(
        lambda x, e, w, g, u, d: moe.held_experts_mlp(
            x, e, w, g, u, d, 0, impl="ragged_dot", num_experts=E))(
        f32(T_, H), jax.ShapeDtypeStruct((T_, k), jnp.int32), f32(T_, k),
        f32(count, H, F), f32(count, H, F), f32(count, F, H)))
    assert text.count(" cond[") == conds
    # ... inside a jit of its own, traced once for a program's layers
    assert ("name=_held_rows_or_every_row" in text) == bool(conds)


# ------------------------------------------------------------ the kernels

def _pages(key, pages, ps, n_kv, dk, dv, dtype):
    k1, k2 = jax.random.split(key)
    return (jax.random.normal(k1, (pages, ps, n_kv * dk), dtype),
            jax.random.normal(k2, (pages, ps, n_kv * dv), dtype))


@pytest.mark.parametrize("hq,n_kv,dk,dv,ps,ring,sink", [
    (8, 1, 48, 32, 4, False, False),     # toy global: one chunk of all
    (8, 2, 48, 32, 4, True, True),       # toy window: ring + sink
    (16, 2, 192, 128, 16, False, False),  # published widths, global
    (16, 2, 192, 128, 16, True, True),    # published widths, window
], ids=["toy-global", "toy-window", "wide-global", "wide-window"])
def test_grouped_decode_kernel_matches_jnp_in_interpret_mode(
        hq, n_kv, dk, dv, ps, ring, sink):
    from apex_tpu.ops import decode_attention_pallas as dap

    b, window = 5, 2 * ps
    n = kv_cache.ring_pages(window, ps) if ring else 6
    pages = 1 + b * n if ring else 24
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(keys[0], (b, hq, dk))
    k_pages, v_pages = _pages(keys[1], pages, ps, n_kv, dk, dv, jnp.float32)
    lengths = jnp.asarray([0, n * ps if not ring else 7 * ps + 3, 1, ps,
                           2 * ps + 1], jnp.int32)
    kw = dict(n_kv=n_kv, sink=jax.random.normal(keys[2], (hq,))
              if sink else None)
    if ring:
        table = kv_cache.ring_table(b, n)
        base, starts = kv_cache.ring_view(lengths, n, ps, window)
        kw.update(page_base=base, starts=starts)
    else:
        table = jnp.asarray(np.random.RandomState(1).randint(
            1, pages, (b, n)), jnp.int32)
    plain = dap.grouped_decode_attention(q, k_pages, v_pages, table,
                                         lengths, impl="jnp", **kw)
    kernel = dap.grouped_decode_attention(q, k_pages, v_pages, table,
                                          lengths, impl="pallas",
                                          interpret=True, **kw)
    assert float(jnp.abs(plain[1]).max()) > 0 and not plain[0].any()
    np.testing.assert_allclose(kernel, plain, atol=2e-6)


def test_ring_arithmetic_against_brute_force():
    ps, window = 4, 8
    ring = kv_cache.ring_pages(window, ps)
    assert ring == 3
    held = {}                       # (ring page, row) -> position
    for t in range(40):             # one slot writing positions 0..39
        page, row = kv_cache.ring_write(jnp.asarray([2]), jnp.asarray([t]),
                                        jnp.asarray([True]), ring, ps)
        held[(int(page[0]), int(row[0]))] = t
        base, starts = kv_cache.ring_view(jnp.asarray([t + 1]), ring, ps,
                                          window)
        seen = set()
        for r in range(ring):
            for o in range(ps):
                pos = int(base[0, r]) + o
                if int(starts[0]) <= pos <= t:
                    assert held[(int(kv_cache.ring_table(3, ring)[2, r]),
                                 o)] == pos
                    seen.add(pos)
        assert seen == set(range(max(0, t + 1 - window), t + 1))


@pytest.mark.parametrize("hq,n_kv,dk,dv,S,window,sink", [
    (8, 1, 48, 32, 64, None, False),
    (8, 2, 48, 32, 64, 8, True),
    (4, 2, 192, 128, 512, 128, True),
    (4, 1, 192, 128, 768, None, False),
], ids=["toy-global", "toy-window", "wide-window", "wide-global"])
def test_packed_prefill_kernel_matches_jnp_in_interpret_mode(
        hq, n_kv, dk, dv, S, window, sink):
    from apex_tpu.ops.attention import packed_gqa_attention

    keys = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(keys[0], (hq, S, dk))
    k = jax.random.normal(keys[1], (n_kv, S, dk))
    v = jax.random.normal(keys[2], (n_kv, S, dv))
    cuts = [S // 2 - 3, S // 4 + 5, S // 8]
    seg = jnp.asarray(np.repeat([1, 2, 3, 0], cuts + [S - sum(cuts)]),
                      jnp.int32)
    kw = dict(window=window,
              sink=jax.random.normal(keys[3], (hq,)) if sink else None)
    plain = packed_gqa_attention(q, k, v, seg, impl="jnp", **kw)
    kernel = packed_gqa_attention(q, k, v, seg, impl="pallas",
                                  interpret=True, **kw)
    np.testing.assert_allclose(kernel, plain, atol=2e-6)


def test_packed_prefill_attention_has_no_backward():
    from apex_tpu.ops.attention import packed_gqa_attention

    with pytest.raises(NotImplementedError, match="backward"):
        jax.grad(lambda q: packed_gqa_attention(
            q, jnp.ones((1, 8, 4)), jnp.ones((1, 8, 4)),
            jnp.ones(8, jnp.int32)).sum())(jnp.ones((2, 8, 4)))


# ------------------------------------------- the prefill's row counts

def test_prefill_row_counts_are_halvings_in_whole_tiles():
    from apex_tpu.serving.family import prefill_rows

    assert prefill_rows(2048) == (256, 512, 1024, 2048)
    assert prefill_rows(32) == (8, 16, 32) and prefill_rows(8) == (8,)


@pytest.mark.parametrize("tokens,rows", [(5, 8), (8, 8), (9, 16), (27, 32)])
def test_prefill_does_not_depend_on_where_its_trunk_stops(
        monkeypatch, tokens, rows):
    """Two prompts packed into 32 rows: the program that stops at the
    smallest row count holding them gives the logits and both caches
    (the null page apart, which takes the padding's rows) of the program
    that runs all 32 rows, in float32 to summation order."""
    from apex_tpu.serving import mimo

    cfg = T.toy_config(cache_dtype="float32")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    T.toy_params(cfg))
    S, ps, slots = 32, 4, 2
    assert next(r for r in mimo.prefill_rows(S) if tokens <= r) == rows
    first = tokens // 2 or 1
    ids = np.zeros(S, np.int32)
    ids[:tokens] = np.random.RandomState(tokens).randint(0, 512, tokens)
    positions, seg = np.zeros(S, np.int32), np.zeros(S, np.int32)
    token_rows = np.full(S, slots, np.int32)
    for slot, (a, b) in enumerate(((0, first), (first, tokens))):
        positions[a:b] = np.arange(b - a)
        seg[a:b], token_rows[a:b] = slot + 1, slot
    table = np.zeros((slots + 1, 8), np.int32)
    table[:slots] = 1 + np.arange(slots * 8).reshape(slots, 8)
    last = np.asarray([first - 1, tokens - 1], np.int32)

    def run():
        cache = mimo.init_cache(cfg, slots, 1 + slots * 8, ps, jnp.float32)
        return jax.jit(lambda c: mimo.prefill(
            params, c, ids, positions, seg, token_rows, table, last,
            cfg=cfg))(cache)

    got = run()
    monkeypatch.setattr(mimo, "prefill_rows", lambda S: (S,))
    want = run()
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)
    for name in want[0]:
        for a, b in zip(got[0][name], want[0][name]):
            np.testing.assert_allclose(a[1:], b[1:], atol=1e-5)


# --------------------------------------------------------------- the seam

@pytest.mark.parametrize("option,value", [
    ("tp", 2), ("weight_quant", True), ("kv_quant", True),
    ("kv_swap", True), ("prefix_cache", True), ("spec_decode", 2),
    ("decode_k", 2), ("overlap", True)])
def test_mimo_family_refuses_by_name_what_it_cannot_honour(option, value):
    cfg = T.toy_config()
    with pytest.raises(ValueError, match=f"mimo .*{option}="):
        ServingEngine(cfg, params={}, num_slots=2, page_size=4,
                      num_pages=8, max_seq=16, prefill_len=8,
                      **{option: value})


def test_mimo_family_drops_environment_preferences(monkeypatch):
    for name in ("APEX_SERVE_PREFIX_CACHE", "APEX_SERVE_KV_QUANT",
                 "APEX_SERVE_OVERLAP", "APEX_SERVE_WEIGHT_QUANT"):
        monkeypatch.setenv(name, "1")
    monkeypatch.setenv("APEX_SPEC_DECODE", "2")
    monkeypatch.setenv("APEX_SERVE_DECODE_K", "4")
    cfg = T.toy_config()
    engine = ServingEngine(cfg, params=T.toy_params(cfg), num_slots=2,
                           page_size=4, num_pages=8, max_seq=16,
                           prefill_len=8)
    assert engine.prefix is None and not engine.kv_quant
    assert not engine.overlap and not engine.weight_quant
    assert engine.spec_k == 0 and engine.decode_k == 1 and engine.tp == 1


def test_the_family_is_chosen_by_the_config_object_alone():
    from apex_tpu.transformer.testing import TransformerConfig

    gpt2 = family_mod.family_of(TransformerConfig(
        hidden_size=32, num_layers=1, num_attention_heads=2,
        vocab_size=128, max_position_embeddings=16))
    mimo = family_mod.family_of(T.toy_config())
    assert (gpt2.name, mimo.name) == ("gpt2", "mimo")
    assert gpt2.refused == () and set(mimo.refused) == set(
        family_mod.OPTIONS_OFF)

    class Other:
        serving_family = "nothing"

    with pytest.raises(ValueError, match="no serving family"):
        family_mod.family_of(Other())


def test_mimo_config_reads_a_cut_configuration_file():
    from apex_tpu.serving.mimo import MiMoConfig, check_config

    cfg = MiMoConfig.from_dict({
        **{k: (list(v) if isinstance(v, tuple) else v)
           for k, v in T.TOY.items() if k != "held_experts"},
        "n_routed_experts": 4, "published_n_routed_experts": 16,
        "model_type": "mimo_v2"})
    assert cfg.n_routed_experts == 16 and cfg.held_experts == (0, 4)
    check_config(cfg)
    with pytest.raises(ValueError, match="held_experts"):
        check_config(T.toy_config(held_experts=(14, 4)))


# ------------------------------------------- one prefill dispatch a round

def test_admission_stops_at_a_token_budget():
    from apex_tpu.serving.kv_cache import PageAllocator
    from apex_tpu.serving.scheduler import ContinuousBatchingScheduler

    def queued(budget):
        sch = ContinuousBatchingScheduler(8, 8, 4, PageAllocator(128))
        for i, n in enumerate((5, 9, 3, 12, 2)):
            sch.submit(Request(rid=i, prompt=[1] * n, max_new_tokens=2))
        return sch, sch.admit(0, token_budget=budget)

    assert len(queued(None)[1]) == 5                 # no bound: all five
    sch, first = queued(16)
    assert [sch.slots[i].request.rid for i in first] == [0, 1]   # 5 + 9
    assert [sch.slots[i].request.rid for i in sch.admit(1, token_budget=16)] \
        == [2, 3]                                    # 3 + 12, then 2 waits
    sch, first = queued(4)                           # the first always enters
    assert [sch.slots[i].request.rid for i in first] == [0]


def test_mimo_engine_prefills_one_dispatch_a_round():
    """Six prompts of 20 tokens against ``prefill_len`` 32: the MiMo
    family's round admits one dispatch's worth (one prompt here), so
    every round between holds one prefill batch and the earlier
    requests decode meanwhile; the GPT-2 family admits all at once."""
    cfg = T.toy_config()
    engine = ServingEngine(cfg, params=T.toy_params(cfg), num_slots=8,
                           page_size=4, num_pages=96, max_seq=40,
                           prefill_len=32)
    assert engine.family.one_prefill_a_round
    assert not family_mod.family_of(object()).one_prefill_a_round
    requests = [Request(rid=i, prompt=[7 + i] * 20, max_new_tokens=8)
                for i in range(6)]
    info = whole_round(engine, arrivals=requests)
    batches = [engine.prefill_batches]
    assert len(info["prefilled"]) == 1 and engine.scheduler.queue_depth() == 5
    for _ in range(5):
        info = whole_round(engine)      # its prefill half, its decode half
        batches.append(engine.prefill_batches)
        assert len(info["prefilled"]) == 1
    assert batches == [1, 2, 3, 4, 5, 6] and engine.scheduler.queue_depth() == 0
    # the first request decoded in each of those rounds (its own round
    # gave it the prefill's token and a decode step's)
    assert len(requests[0].out_tokens) == 7
