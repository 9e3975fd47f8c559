"""The A.X-K1 serving family against its plain reference
(``perf/references/axk1.py``: the EXPANDED form of latent attention,
nothing of ``apex_tpu``), through ``ServingEngine.step``: the scheduler,
the page allocator, the latent cache (one row a token a layer, no head
axis) and both programs, whose attention takes two forms (prefill
expands K and V per head from the rows, decode absorbs ``wkv_b`` into
query and output and reads the rows back through the paged cache), at a
toy size with every mechanism of the real one (``axk1_toy.TOY``).

Two runs, five requests each (prompts of 3-20 tokens: shorter and longer
than a page of 4 and than YaRN's 16 original positions; 24-30 decode
steps each):

* float32 weights and cache: the program and the reference then differ
  by summation order and by the absorbed form's other association of
  the same products (measured 2.0e-6 at logits of size ~2.5), so the
  comparison is held to 1e-4: fifty times the reading, and below the
  smallest thing it has to catch (measured, nearest first: the softmax
  scale without ``m^2`` 2.5e-3, the cache in fp8 4e-3; the others move
  it by 1e-2 to 1). Every negative control runs through THIS comparison
  and must fail it.
* bfloat16 as deployed: measured median 0.015 and largest 0.28 on this
  seed. A top-4 choice that flips on a near-tie swaps one expert's
  output, weighted ~2.5 / 4, for another's, which is what the largest
  readings are: positions over 0.1 are counted as flips, and bounded.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import axk1_toy as A
import mimo_toy as T
from round_halves import whole_round
from apex_tpu.ops import decode_attention_pallas as dap
from apex_tpu.serving import ServingEngine, axk1
from apex_tpu.serving import family as family_mod
from apex_tpu.serving import kv_cache
from apex_tpu.serving.scheduler import Request
from apex_tpu.transformer import moe

ref = A.reference
SIZES = [(3, 26), (11, 24), (20, 30), (5, 28), (9, 25)]   # prompt, answer
F32_TOL = 1e-4


def _f32(params):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def _run(cfg, params, sizes=SIZES, seed=3):
    engine = ServingEngine(cfg, params=params, num_slots=4, page_size=4,
                           num_pages=64, max_seq=64, prefill_len=32)
    tap = T.LogitsTap(engine)
    rs = np.random.RandomState(seed)
    requests = [Request(rid=i, prompt=rs.randint(0, 512, n).tolist(),
                        max_new_tokens=m) for i, (n, m) in enumerate(sizes)]
    T.serve(engine, requests)
    assert (tap._prefill._cache_size(), tap._decode._cache_size()) == (1, 1)
    return engine, tap, requests


@pytest.fixture(scope="module")
def f32_run():
    cfg = A.toy_config(cache_dtype="float32")
    params = _f32(A.toy_params(cfg))
    return (cfg, params) + _run(cfg, params)


@pytest.fixture(scope="module")
def bf16_run():
    cfg = A.toy_config()
    params = A.toy_params(cfg)
    return (cfg, params) + _run(cfg, params)


def _errors(run, config=None, fault=None):
    cfg, params, _, tap, requests = run
    config = cfg.to_dict() if config is None else config
    return T.compare(tap, requests, lambda seq: ref.logits(
        config, params, seq, _fault=fault))[0]


def test_float32_engine_matches_reference_through_the_latent_cache(f32_run):
    errors = _errors(f32_run)
    assert len(errors) == sum(m for _, m in SIZES)   # every position
    assert errors.max() <= F32_TOL, errors.max()


def test_bfloat16_engine_matches_reference_with_bounded_flips(bf16_run):
    errors = _errors(bf16_run)
    flips = int((errors > 0.1).sum())
    assert np.median(errors) <= 0.04, np.median(errors)
    assert flips <= len(errors) // 10 and errors.max() <= 0.6, \
        (flips, errors.max())


def test_engine_spans_carry_the_expert_and_latent_counts(bf16_run):
    from apex_tpu.telemetry import spans

    rounds = [r for r in spans.snapshot() if r.name == "engine.round"
              and r.attrs and "latent_pages_live" in r.attrs]
    assert rounds
    a = rounds[-1].attrs
    assert a["experts_held"] == 2 * 4      # expert layers x held experts
    assert 0 < a["experts_touched"] <= a["experts_held"]
    assert a["expert_tokens_sum"] >= a["expert_tokens_max"] >= 1
    # the family's own name for its pool, and none of MiMo's
    assert a["latent_pages_live"] >= 1
    assert "global_pages_live" not in a and "window_pages" not in a
    assert bf16_run[2].decode_attn_impl == "jnp"   # the CPU


def test_prefill_fetch_span_carries_the_row_bound_account(monkeypatch):
    """The expert layers' row bound (``moe.held_row_bound``) engages in
    a 512-row trunk and not in a 64-row one; either way the float32
    program stays on the reference, and ``prefill.fetch`` says what the
    bound was, how near the held assignments came, and that no layer
    took the form over every row (the toy routes uniformly)."""
    monkeypatch.setattr(moe, "HELD_ROWS_SPARED_MIN", 8)   # toy sizes
    cfg = A.toy_config(cache_dtype="float32")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    A.toy_params(cfg))
    errors, (long, short) = T.long_prompt_run(
        cfg, params, lambda seq: ref.logits(cfg.to_dict(), params, seq))
    assert len(errors) == 2 * 3 and errors.max() <= F32_TOL, errors.max()
    assert long["expert_rows"] == moe.held_row_bound(512, 4, 4, 16) == 1024
    assert short["expert_rows"] == 64 * 4              # every row: no bound
    for attrs, tokens in ((long, 300), (short, 20)):
        assert 0 < attrs["held_rows_max"] <= tokens * 4
        assert attrs["expert_rows_full"] == 0
    assert long["held_rows_max"] < long["expert_rows"]


# ------------------------------------------------------ negative controls

CONTROLS = {
    "scale_without_m_squared": ({}, "no_mscale_in_scale"),
    "yarn_left_out": (dict(rope_scaling=None), None),
    "rotary_on_the_wrong_dims": ({}, "rope_on_nope_dims"),
    "kv_a_norm_skipped": ({}, "kv_norm_skipped"),
    "shared_expert_dropped": (dict(n_shared_experts=0), None),
    "scaling_1_for_2_5": (dict(routed_scaling_factor=1.0), None),
}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_negative_control_fails_the_comparison(f32_run, name):
    changes, fault = CONTROLS[name]
    errors = _errors(f32_run, {**f32_run[0].to_dict(), **changes},
                     fault=fault)
    assert errors.max() > F32_TOL


def _unnormalised_rows(inner, lp, cfg, positions):
    """``axk1.latent_rows`` with ``c_kv`` cached as ``wkv_a`` gives it."""
    rank = cfg.kv_lora_rank
    inv_freq, mult, _ = axk1.yarn(cfg)
    kv = axk1._mm(inner, lp["wkv_a"])
    return jnp.concatenate(
        [kv[:, :rank], axk1._rotary(kv[:, rank:], positions, inv_freq, mult)],
        axis=-1)


@pytest.mark.parametrize("name", ["c_kv_unnormalised_in_the_cache",
                                  "fp8_cache"])
def test_a_faulty_cache_fails_the_comparison(monkeypatch, name):
    """Two faults of the PROGRAM's side, float32 weights, the sound
    reference: rows cached before ``kv_a``'s norm, and a cache that
    rounds its rows to fp8 (e4m3, the nearest precision below)."""
    if name == "fp8_cache":
        cfg = A.toy_config(cache_dtype="float8_e4m3fn")
    else:
        cfg = A.toy_config(cache_dtype="float32")
        monkeypatch.setattr(axk1, "latent_rows", _unnormalised_rows)
    params = _f32(A.toy_params(cfg))
    run = (cfg, params) + _run(cfg, params, sizes=SIZES[:2])
    assert _errors(run).max() > F32_TOL


# --------------------------------------- two forms of the one attention

def test_absorbed_form_equals_expanded_form():
    """One sequence of 23 tokens, one layer's attention block: the
    prefill form (K and V expanded per head from the rows, the packed
    causal kernel's jnp form) and the decode form (every position its
    own lane, reading the rows back through pages of 4 in the absorbed
    form) give the same block output, and both the reference's."""
    cfg = A.toy_config(cache_dtype="float32")
    lp = _f32(A.toy_params(cfg))["layers"][1]
    n, ps = 23, 4
    inner = jax.random.normal(jax.random.PRNGKey(5), (n, cfg.hidden_size))
    pos = jnp.arange(n, dtype=jnp.int32)
    seg = jnp.ones((n,), jnp.int32)

    expanded = axk1.latent_attention(
        inner, lp, cfg, pos, lambda q_nope, q_pe, row: axk1.attend_expanded(
            q_nope, q_pe, row, lp, cfg, seg, attn_impl="jnp"))

    leaf = axk1.init_cache(cfg, 8, ps, jnp.float32)["latent"][0]
    leaf = kv_cache.write_latent_rows(leaf, 1 + pos // ps, pos % ps,
                                      axk1.latent_rows(inner, lp, cfg, pos))
    assert leaf.shape == (8, ps, 128) and not leaf[:, :, 40:].any()
    pages = jnp.broadcast_to(1 + jnp.arange(7, dtype=jnp.int32), (n, 7))
    table, base = kv_cache.pool_view(pages, pos, pos + 1, ps)
    absorbed = axk1.latent_attention(
        inner, lp, cfg, pos, lambda q_nope, q_pe, row: axk1.attend_absorbed(
            q_nope, q_pe, leaf, lp, cfg, pos + 1, table, base))

    with jax.default_matmul_precision("highest"):
        want = ref.attention(cfg.to_dict(), lp, inner)
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(expanded, want, atol=2e-6)
    np.testing.assert_allclose(absorbed, want, atol=2e-6)


def test_yarn_numbers_at_the_published_settings():
    cfg = axk1.AXK1Config(vocab_size=8, num_hidden_layers=1)
    inv_freq, mult, scale = axk1.yarn(cfg)
    m = 0.1 * np.log(32.0) + 1.0
    assert abs(m - 1.3466) < 1e-4 and mult == 1.0
    assert abs(scale - 192 ** -0.5 * m * m) < 1e-9 and abs(
        scale - 0.13087) < 1e-5
    want, want_mult, want_scale = ref.yarn(cfg.to_dict())
    np.testing.assert_array_equal(inv_freq, want)
    assert (mult, scale) == (want_mult, want_scale)
    # the fastest pairs keep their frequency, the slowest are divided by
    # the factor, the ramp lies between
    plain = 10000.0 ** (-np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv_freq[:10], plain[:10], rtol=1e-6)
    np.testing.assert_allclose(inv_freq[-6:], plain[-6:] / 32, rtol=1e-6)
    assert np.all(np.diff(inv_freq) < 0)
    plain_only = axk1.yarn(axk1.AXK1Config(vocab_size=8, num_hidden_layers=1,
                                           rope_scaling=None))
    np.testing.assert_allclose(plain_only[0], plain, rtol=1e-6)
    assert plain_only[1:] == (1.0, 192 ** -0.5)


# ----------------------------------------------------------- the share

def _moe_layer(cfg_dict, seed=0, tokens=24):
    keys = jax.random.split(jax.random.PRNGKey(seed), 9)
    H, F, E = (cfg_dict["hidden_size"], cfg_dict["moe_intermediate_size"],
               cfg_dict["n_routed_experts"])
    lp = {"router": jax.random.normal(keys[0], (E, H)) * 0.1,
          "w_gate": jax.random.normal(keys[2], (E, H, F)) * 0.05,
          "w_up": jax.random.normal(keys[3], (E, H, F)) * 0.05,
          "w_down": jax.random.normal(keys[4], (E, F, H)) * 0.05,
          "shared_gate": jax.random.normal(keys[5], (H, F)) * 0.05,
          "shared_up": jax.random.normal(keys[6], (H, F)) * 0.05,
          "shared_down": jax.random.normal(keys[7], (F, H)) * 0.05}
    return lp, jax.random.normal(keys[8], (tokens, H))


def _share(lp, first, count):
    return {**lp, **{n: lp[n][first:first + count]
                     for n in ("w_gate", "w_up", "w_down")}}


def test_the_four_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """What every chip computes alike counts ONCE: the four shares'
    routed parts (the program's ``mimo.moe_ffn`` path and the
    reference's ``routed``) plus one shared expert give the reference's
    uncut layer; four whole share layers overcount by three shared
    experts."""
    from apex_tpu.serving import mimo

    d = A.toy_config().to_dict()
    lp, x = _moe_layer(d)
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe({**d, "held_experts": (0, 16)}, lp, x)
        shared = ref.swiglu(x, lp["shared_gate"], lp["shared_up"],
                            lp["shared_down"])
        routed, routed_ref, whole, assigned = 0.0, 0.0, 0.0, 0
        for first in (0, 4, 8, 12):
            cfg = A.toy_config(held_experts=(first, 4))
            part = _share(lp, first, 4)
            y, counts = mimo.moe_ffn(x, part, cfg)
            routed, assigned = routed + y, assigned + int(counts.sum())
            routed_ref = routed_ref + ref.routed(cfg.to_dict(), part, x)
            whole = whole + axk1.moe_ffn(x, part, cfg)[0]
        once = moe.gated_mlp(x, lp["shared_gate"], lp["shared_up"],
                             lp["shared_down"])
    assert assigned == x.shape[0] * d["num_experts_per_tok"]   # dropless
    assert float(jnp.max(jnp.abs(shared))) > 0.01
    np.testing.assert_allclose(routed + once, uncut, atol=3e-6)
    np.testing.assert_allclose(routed_ref + shared, uncut, atol=3e-6)
    np.testing.assert_allclose(whole - 3 * once, uncut, atol=6e-6)


def test_routing_is_the_plain_top_k_scaled():
    d = A.toy_config().to_dict()
    lp, x = _moe_layer(d, seed=1)
    with jax.default_matmul_precision("highest"):
        chosen, w = ref.route(d, lp, x)
    experts, weights = moe.route_sigmoid_topk(
        x, lp["router"], None, d["num_experts_per_tok"], True, 2.5)
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(chosen, -1))
    np.testing.assert_allclose(np.sort(weights, -1), np.sort(w, -1),
                               atol=1e-6)
    np.testing.assert_allclose(weights.sum(-1), 2.5, atol=1e-6)
    zero_bias = moe.route_sigmoid_topk(x, lp["router"], jnp.zeros(16),
                                       d["num_experts_per_tok"], True, 2.5)
    np.testing.assert_array_equal(experts, zero_bias[0])


def test_padding_rows_reach_no_expert():
    cfg = A.toy_config()
    lp, x = _moe_layer(cfg.to_dict(), seed=3, tokens=16)
    part = _share(lp, 4, 4)
    valid = jnp.arange(16) < 10                     # six rows of padding
    y_all, n_all = axk1.moe_ffn(x, part, cfg)
    y, n = axk1.moe_ffn(x, part, cfg, valid)
    experts, _ = moe.route_sigmoid_topk(x, lp["router"], None, 4)
    held = (np.asarray(experts) >= 4) & (np.asarray(experts) < 8)
    assert int(n.sum()) == int(held[:10].sum()) < int(n_all.sum())
    np.testing.assert_allclose(y[:10], y_all[:10], atol=1e-6)
    # a padding row gets the shared expert's output alone (nobody reads
    # it), never a routed expert's
    shared = moe.gated_mlp(x, lp["shared_gate"], lp["shared_up"],
                           lp["shared_down"])
    np.testing.assert_allclose(y[10:], shared[10:], atol=1e-6)


# ------------------------------------------------------------ the kernel

@pytest.mark.parametrize("hq,width,rank,live,ps", [
    (8, 128, 32, 40, 4),        # the toy: 40 live columns of a 128 row
    (16, 640, 512, 576, 16),    # published widths: 576 of 640
], ids=["toy", "wide"])
def test_latent_decode_kernel_matches_jnp_in_interpret_mode(
        hq, width, rank, live, ps):
    """Ragged lengths (an empty lane, one token, a whole page, a page
    and a row, every page of the table) over shuffled pages, the table
    past a slot's last page repeating it as the decode program's does."""
    b, n, pages = 5, 6, 24
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    q = jax.random.normal(keys[0], (b, hq, width))
    latent = jax.random.normal(keys[1], (pages, ps, width)) \
        * (jnp.arange(width) < live)
    lengths = jnp.asarray([0, n * ps, 1, ps, 2 * ps + 1], jnp.int32)
    table = jnp.asarray(np.random.RandomState(1).randint(
        1, pages, (b, n)), jnp.int32)
    kw = dict(rank=rank, sm_scale=0.13)
    plain = dap.latent_decode_attention(q, latent, table, lengths,
                                        impl="jnp", **kw)
    kernel = dap.latent_decode_attention(q, latent, table, lengths,
                                         impl="pallas", interpret=True, **kw)
    assert plain.shape == (b, hq, rank)
    assert float(jnp.abs(plain[1]).max()) > 0 and not plain[0].any()
    # scores sum up to 576 products of unit normals: float32 orderings
    # differ by a few 1e-6 at the wide size
    np.testing.assert_allclose(kernel, plain, atol=1e-5)
    view, base = kv_cache.pool_view(table, jnp.maximum(lengths - 1, 0),
                                    lengths, ps)
    walked = dap.latent_decode_attention(
        q, latent, view, lengths, page_base=base, impl="pallas",
        interpret=True, **kw)
    np.testing.assert_allclose(walked, plain, atol=1e-5)
    # the value is the row's first ``rank`` columns and nothing else
    rows = latent[table[2, 0], :1, :rank]
    np.testing.assert_allclose(plain[2], jnp.broadcast_to(rows, (hq, rank)),
                               atol=1e-6)


def test_latent_kernel_is_for_whole_tiles_and_float_pages():
    assert dap.latent_supported(64, 640, 512, 128, jnp.bfloat16)
    assert dap.latent_supported(64, 640, 512, 128, jnp.float32)
    assert not dap.latent_supported(64, 640, 512, 128, jnp.float8_e4m3fn)
    assert not dap.latent_supported(64, 640, 500, 128, jnp.bfloat16)
    assert not dap.latent_supported(60, 640, 512, 128, jnp.bfloat16)
    assert not dap.latent_supported(64, 576, 512, 128, jnp.bfloat16)
    assert dap.latent_supported(8, 128, 64, 16, jnp.bfloat16)   # the twin
    assert dap.latent_resolved(64, 640, 512, 128, jnp.bfloat16) == "jnp"
    with pytest.raises(ValueError, match="unknown decode-attention impl"):
        dap.latent_resolved(64, 640, 512, 128, jnp.bfloat16, "mosaic")
    with pytest.raises(ValueError, match="unsupported geometry"):
        dap.latent_decode_attention_pallas(
            jnp.zeros((1, 60, 640)), jnp.zeros((2, 128, 640)),
            jnp.zeros((1, 1), jnp.int32), jnp.ones((1,), jnp.int32), 1.0,
            rank=512)


def test_latent_cache_rows_are_padded_to_lane_tiles():
    assert kv_cache.latent_row_width(576) == 640
    assert kv_cache.latent_row_width(640) == 640
    cache = kv_cache.init_latent_cache(2, 5, 8, 576)
    assert list(cache) == ["latent"] and len(cache["latent"]) == 2
    assert cache["latent"][0].shape == (5, 8, 640)
    leaf = kv_cache.write_latent_rows(
        cache["latent"][0], jnp.asarray([2, 3]), jnp.asarray([1, 7]),
        jnp.ones((2, 576)))
    assert float(leaf.sum()) == 2 * 576 and not leaf[:, :, 576:].any()
    assert float(leaf[2, 1].sum()) == float(leaf[3, 7].sum()) == 576


# ------------------------------------------- the prefill's row counts

@pytest.mark.parametrize("tokens,rows", [(5, 8), (9, 16), (27, 32)])
def test_prefill_does_not_depend_on_where_its_trunk_stops(
        monkeypatch, tokens, rows):
    """Two prompts packed into 32 rows: the program that stops at the
    smallest row count holding them gives the logits and the cache (the
    null page apart, which takes the padding's rows) of the program that
    runs all 32 rows, in float32 to summation order."""
    cfg = A.toy_config(cache_dtype="float32")
    params = _f32(A.toy_params(cfg))
    S, ps, slots = 32, 4, 2
    assert next(r for r in axk1.prefill_rows(S) if tokens <= r) == rows
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
        cache = axk1.init_cache(cfg, 1 + slots * 8, ps, jnp.float32)
        return jax.jit(lambda c: axk1.prefill(
            params, c, ids, positions, seg, token_rows, table, last,
            cfg=cfg))(cache)

    got = run()
    monkeypatch.setattr(axk1, "prefill_rows", lambda S: (S,))
    want = run()
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)
    for a, b in zip(got[0]["latent"], want[0]["latent"]):
        np.testing.assert_allclose(a[1:], b[1:], atol=1e-5)
        assert float(jnp.abs(b[1:]).max()) > 0


# --------------------------------------------------------------- the seam

@pytest.mark.parametrize("option,value", [
    ("tp", 2), ("weight_quant", True), ("kv_quant", True),
    ("kv_swap", True), ("prefix_cache", True), ("spec_decode", 2),
    ("decode_k", 2), ("overlap", True)])
def test_axk1_family_refuses_by_name_what_it_cannot_honour(option, value):
    cfg = A.toy_config()
    with pytest.raises(ValueError, match=f"axk1 .*{option}="):
        ServingEngine(cfg, params={}, num_slots=2, page_size=4,
                      num_pages=8, max_seq=16, prefill_len=8,
                      **{option: value})


def test_axk1_family_drops_environment_preferences(monkeypatch):
    for name in ("APEX_SERVE_PREFIX_CACHE", "APEX_SERVE_KV_QUANT",
                 "APEX_SERVE_OVERLAP", "APEX_SERVE_WEIGHT_QUANT"):
        monkeypatch.setenv(name, "1")
    monkeypatch.setenv("APEX_SPEC_DECODE", "2")
    monkeypatch.setenv("APEX_SERVE_DECODE_K", "4")
    cfg = A.toy_config()
    engine = ServingEngine(cfg, params=A.toy_params(cfg), num_slots=2,
                           page_size=4, num_pages=8, max_seq=16,
                           prefill_len=8)
    assert engine.prefix is None and not engine.kv_quant
    assert not engine.overlap and not engine.weight_quant
    assert engine.spec_k == 0 and engine.decode_k == 1 and engine.tp == 1
    assert list(engine.cache) == ["latent"]
    assert engine.cache["latent"][0].shape == (8, 4, 128)


def test_the_family_and_the_config_class_follow_the_model_type():
    fam = family_mod.family_of(A.toy_config())
    assert fam.name == "axk1" and fam.one_prefill_a_round
    assert set(fam.refused) == set(family_mod.OPTIONS_OFF)
    assert fam.prefill_rows(4096) == (512, 1024, 2048, 4096)
    published = {k: (list(v) if isinstance(v, tuple) else v)
                 for k, v in A.TOY.items()
                 if k not in ("held_experts", "rope_scaling")}
    cfg = family_mod.config_from_dict({
        **published, "rope_scaling": dict(A.YARN), "n_routed_experts": 4,
        "published_n_routed_experts": 16, "model_type": "axk1",
        "n_group": 8, "topk_group": 4, "ep_size": 1})
    assert isinstance(cfg, axk1.AXK1Config)
    assert cfg.n_routed_experts == 16 and cfg.held_experts == (0, 4)
    assert cfg.to_dict()["rope_scaling"] == A.YARN and hash(cfg)
    axk1.check_config(cfg)
    assert [cfg.is_expert_layer(i) for i in range(3)] == [False, True, True]
    mimo_cfg = family_mod.config_from_dict({
        **{k: (list(v) if isinstance(v, tuple) else v)
           for k, v in T.TOY.items()}, "model_type": "mimo_v2"})
    assert family_mod.family_of(mimo_cfg).name == "mimo"
    with pytest.raises(ValueError, match="no serving family reads"):
        family_mod.config_from_dict({"model_type": "gpt2"})


@pytest.mark.parametrize("changes,said", [
    (dict(held_experts=(14, 4)), "held_experts"),
    (dict(topk_method="noaux_tc"), "topk_method"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(rope_scaling=(("factor", 4), ("type", "linear"))), "rope_scaling"),
    (dict(qk_rope_head_dim=7), "odd number of rotary dims"),
])
def test_check_config_names_what_the_programs_do_not_model(changes, said):
    with pytest.raises(ValueError, match=said):
        axk1.check_config(A.toy_config(**changes))


def test_axk1_engine_prefills_one_dispatch_a_round():
    cfg = A.toy_config()
    engine = ServingEngine(cfg, params=A.toy_params(cfg), num_slots=8,
                           page_size=4, num_pages=96, max_seq=40,
                           prefill_len=32)
    requests = [Request(rid=i, prompt=[7 + i] * 20, max_new_tokens=8)
                for i in range(4)]
    info = whole_round(engine, arrivals=requests)
    assert len(info["prefilled"]) == 1 and engine.scheduler.queue_depth() == 3
    for _ in range(3):      # a round: its prefill half, its decode half
        assert len(whole_round(engine)["prefilled"]) == 1
    assert engine.prefill_batches == 4 and engine.scheduler.queue_depth() == 0
    assert len(requests[0].out_tokens) == 5
