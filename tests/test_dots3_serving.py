"""The dots3-note serving family against its plain reference
(``perf/references/dots3_note.py``: the EXPANDED form of latent attention,
``lax.top_k`` on float32 index scores, nothing of ``apex_tpu``), through
``ServingEngine.step``: the scheduler, the page allocator, the three
kinds of state (a latent leaf and an index-key leaf a full layer on the
pool's pages, a latent ring a sliding layer) and both programs, whose
attention takes two forms (prefill expands K and V per head and masks by
the selection or the window; decode absorbs ``wkv_b``, scores the slot's
index pages, gathers its ``index_topk`` best rows and reads them, or the
ring, in the latent), at a toy size with every mechanism of the real one
(``dots3_toy.TOY``: ``index_topk`` 8 and a window of 5, both SHORTER than
the sequences, so the selection cuts and the ring wraps).

Two runs, five requests each (prompts of 3-20 tokens: shorter and longer
than a page of 4, than the window and than ``index_topk``; 24-30 decode
steps each, contexts to 50):

* float32 weights and cache: the program and the reference differ by
  summation order and by the absorbed form's other association of the
  same products (measured 2.0e-6 at logits of size ~2.7), so the
  comparison is held to 1e-4, below the smallest thing it has to catch
  (the controls below move it by 1e-3 to 1). Every negative control
  runs through THIS comparison and must fail it. In float32 the two
  sides select the SAME rows: the selected sets are compared as sets.
* bfloat16 as deployed: measured median 0.032, largest 1.46 and 49 of
  133 positions over 0.1 on this seed: a flip at the selection's edge
  swaps one row of EIGHT here (one of 2,048 at the published size), in
  three layers, and a top-4 expert choice that flips swaps one expert's
  output for another's; positions over 0.1 are counted as flips, and
  bounded at half.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import dots3_toy as D
import mimo_toy as T
from round_halves import whole_round
from apex_tpu.ops import attention as attn_ops
from apex_tpu.ops import decode_attention_pallas as dap
from apex_tpu.serving import ServingEngine, dots3
from apex_tpu.serving import family as family_mod
from apex_tpu.serving import kv_cache
from apex_tpu.serving.scheduler import Request
from apex_tpu.transformer import moe

ref = D.reference
SIZES = [(3, 26), (11, 24), (20, 30), (5, 28), (9, 25)]   # prompt, answer
F32_TOL = 1e-4


def _f32(params):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)


def _run(cfg, params, sizes=SIZES, seed=3, **engine_kw):
    engine = ServingEngine(cfg, params=params, num_slots=4, page_size=4,
                           num_pages=64, max_seq=64, prefill_len=32,
                           **engine_kw)
    tap = T.LogitsTap(engine)
    rs = np.random.RandomState(seed)
    requests = [Request(rid=i, prompt=rs.randint(0, 512, n).tolist(),
                        max_new_tokens=m) for i, (n, m) in enumerate(sizes)]
    T.serve(engine, requests)
    assert (tap._prefill._cache_size(), tap._decode._cache_size()) == (1, 1)
    return engine, tap, requests


@pytest.fixture(scope="module")
def f32_run():
    cfg = D.toy_config(cache_dtype="float32")
    params = _f32(D.toy_params(cfg))
    return (cfg, params) + _run(cfg, params)


@pytest.fixture(scope="module")
def bf16_run():
    cfg = D.toy_config()
    params = D.toy_params(cfg)
    return (cfg, params) + _run(cfg, params)


def _errors(run, config=None, fault=None):
    cfg, params, _, tap, requests = run
    config = cfg.to_dict() if config is None else config
    return T.compare(tap, requests, lambda seq: ref.logits(
        config, params, seq, _fault=fault))[0]


def test_float32_engine_matches_reference_through_three_kinds_of_state(
        f32_run):
    errors = _errors(f32_run)
    assert len(errors) == sum(m for _, m in SIZES)   # every position
    assert errors.max() <= F32_TOL, errors.max()


def test_float32_engine_with_interpreted_kernels_matches_reference():
    """The decode kernels (index scores, the latent kernel over gathered
    rows and over the ring with its ``starts``) in Pallas interpret mode,
    through the engine."""
    cfg = D.toy_config(cache_dtype="float32")
    params = _f32(D.toy_params(cfg))
    run = (cfg, params) + _run(cfg, params, sizes=SIZES[:3],
                               decode_impl="pallas")
    assert run[2].decode_attn_impl == "pallas"
    assert _errors(run).max() <= F32_TOL


def test_bfloat16_engine_matches_reference_with_bounded_flips(bf16_run):
    errors = _errors(bf16_run)
    flips = int((errors > 0.1).sum())
    assert np.median(errors) <= 0.06, np.median(errors)
    assert flips <= len(errors) // 2 and errors.max() <= 2.5, \
        (flips, errors.max())


def test_engine_spans_carry_the_selection_and_expert_counts():
    import time

    from apex_tpu.telemetry import spans

    cfg = D.toy_config()
    t0 = time.perf_counter()
    engine, _, _ = _run(cfg, D.toy_params(cfg), sizes=[(20, 12)])
    seen = spans.snapshot(t0)
    rounds = [r for r in seen if r.name == "engine.round"
              and r.attrs and "index_rows_scored" in r.attrs]
    a = rounds[-1].attrs       # one slot, at a context past index_topk
    assert a["experts_held"] == 5 * 4      # expert layers x held experts
    assert 0 < a["experts_touched"] <= a["experts_held"]
    assert a["expert_tokens_sum"] >= a["expert_tokens_max"] >= 1
    # the family's own name for its pool, and none of MiMo's
    assert a["latent_pages_live"] >= 1
    assert "global_pages_live" not in a and "window_pages" not in a
    assert a["index_rows_scored"] == 20 + 11 > cfg.index_topk
    assert a["sparse_rows_selected"] == cfg.index_topk
    assert a["window_rows"] == cfg.sliding_window_size
    fetch, = [r.attrs for r in seen if r.name == "prefill.fetch"]
    assert {"held_rows_max", "expert_rows", "expert_rows_full"} <= set(fetch)
    # contexts 1..20, of which 8 at most are attended
    assert fetch["index_pairs"] == 210
    assert fetch["sparse_pairs"] == 36 + 12 * 8
    assert engine.decode_attn_impl == "jnp"   # the CPU


# ------------------------------------------------------ negative controls

CONTROLS = {
    "gate_left_out": ({}, "gate_left_out"),
    "rescale_left_out": ({}, "rescale_left_out"),
    "selection_is_the_last_rows": ({}, "selection_is_the_last_rows"),
    "window_one_row_longer": ({}, "window_one_row_longer"),
    "index_rope_left_out": ({}, "index_rope_left_out"),
    "index_topk_twice": (dict(index_topk=16), None),
    "shared_expert_dropped": (dict(n_shared_experts=0), None),
    "rotary_bases_swapped": (dict(rope_theta=5e4, swa_rope_theta=8e7), None),
}


@pytest.mark.parametrize("name", list(CONTROLS))
def test_negative_control_fails_the_comparison(f32_run, name):
    changes, fault = CONTROLS[name]
    errors = _errors(f32_run, {**f32_run[0].to_dict(), **changes},
                     fault=fault)
    assert errors.max() > F32_TOL


@pytest.mark.parametrize("name", ["fp8_cache", "selection_by_position"])
def test_a_faulty_program_fails_the_comparison(monkeypatch, name):
    """Two faults of the PROGRAM's side, float32 weights, the sound
    reference: the three kinds of state rounded to fp8 (e4m3, the nearest
    precision below), and a decode that takes each slot's LAST
    ``index_topk`` rows for the indexer's choice."""
    if name == "fp8_cache":
        cfg = D.toy_config(cache_dtype="float8_e4m3fn")
    else:
        cfg = D.toy_config(cache_dtype="float32")

        def last_rows(scores, page_table, k, page_size):
            live = jnp.sum(scores > -1e29, axis=1, keepdims=True)
            pos = jnp.maximum(live - 1 - jnp.arange(k)[None, :], 0)
            return (jnp.take_along_axis(page_table, pos // page_size, axis=1),
                    pos % page_size, pos)

        monkeypatch.setattr(dots3, "select_rows", last_rows)
    params = _f32(D.toy_params(cfg))
    run = (cfg, params) + _run(cfg, params, sizes=SIZES[:2])
    assert _errors(run).max() > F32_TOL


# ---------------------------------- the selection, and the two forms of it

def _full_layer(n=23, layer=1):
    cfg = D.toy_config(cache_dtype="float32")
    lp = _f32(D.toy_params(cfg))["layers"][layer]
    inner = jax.random.normal(jax.random.PRNGKey(5), (n, cfg.hidden_size))
    return cfg, lp, inner, jnp.arange(n, dtype=jnp.int32)


def _decode_view(n, ps, pages_a_slot):
    """Every position of one sequence its own lane, all on the same
    pages ``1..``."""
    pos = jnp.arange(n, dtype=jnp.int32)
    pages = jnp.broadcast_to(
        1 + jnp.arange(pages_a_slot, dtype=jnp.int32), (n, pages_a_slot))
    return (pages,) + kv_cache.pool_view(pages, pos, pos + 1, ps)


def test_selected_sets_and_both_forms_equal_the_reference_in_float32():
    """One sequence of 23 tokens, one full layer: the prefill form (index
    scores, the 8th largest a query, the packed kernel's jnp form under
    the mask) and the decode form (every position its own lane: scores
    over the index pages, ``lax.top_k``, the chosen rows gathered and
    read in the absorbed form) select the reference's rows, as sets, and
    give its block output."""
    cfg, lp, inner, pos = _full_layer()
    kd = dots3.kind(cfg, False)
    n, ps = inner.shape[0], 4
    seg = jnp.ones((n,), jnp.int32)
    seen = {}

    def expanded(q_nope, q_pe, row, c_q):
        k_idx = dots3.index_keys(inner, lp, cfg, pos)
        seen["prefill"] = dots3.select_packed(c_q, inner, k_idx, lp, cfg,
                                              pos, seg, attn_impl="jnp")
        return dots3.attend_expanded(q_nope, q_pe, row, lp, kd, seg,
                                     seen["prefill"], attn_impl="jnp")

    got_prefill = dots3.latent_attention(inner, lp, cfg, kd, pos, expanded)

    cache = dots3.init_cache(cfg, 1, 8, ps, jnp.float32)
    leaf = kv_cache.write_latent_rows(
        cache["latent"][0], 1 + pos // ps, pos % ps,
        dots3.latent_rows(inner, lp, cfg, kd, pos))
    index_leaf = kv_cache.write_latent_rows(
        cache["index"][0], 1 + pos // ps, pos % ps,
        dots3.index_keys(inner, lp, cfg, pos))
    assert leaf.shape == (8, ps, 128) and index_leaf.shape == (8, ps, 128)
    assert not leaf[:, :, 40:].any() and not index_leaf[:, :, 16:].any()
    pages, table, base = _decode_view(n, ps, 7)

    def absorbed(q_nope, q_pe, row, c_q):
        seen["c_q"] = c_q
        return dots3.attend_sparse(
            q_nope, q_pe, c_q, inner, leaf, index_leaf, lp, cfg, pos,
            pos + 1, pages, table, base)

    got_decode = dots3.latent_attention(inner, lp, cfg, kd, pos, absorbed)

    scores = dots3.index_scores_paged(seen["c_q"], inner, index_leaf, lp,
                                      cfg, pos, pos + 1, table, base)
    seen["decode"] = dots3.select_rows(scores, pages, cfg.index_topk, ps)[2]

    taps = {}
    with jax.default_matmul_precision("highest"):
        want = ref.attention(
            cfg.to_dict(), lp, inner, False,
            index_tap=lambda i, inner, scores, chosen: taps.update(
                scores=scores, chosen=chosen))
    chosen = np.asarray(taps["chosen"])
    assert chosen.sum(axis=1).tolist() == [min(t + 1, 8) for t in range(n)]
    np.testing.assert_array_equal(np.asarray(seen["prefill"]) != 0, chosen)
    for t in range(n):
        mine = set(np.asarray(seen["decode"][t, :min(t + 1, 8)]).tolist())
        assert mine == set(np.nonzero(chosen[t])[0].tolist()), t
    assert float(jnp.abs(want).max()) > 0.05
    np.testing.assert_allclose(got_prefill, want, atol=3e-6)
    np.testing.assert_allclose(got_decode, want, atol=3e-6)


def test_up_to_index_topk_the_full_layer_is_dense_latent_attention():
    """A context of ``index_topk`` tokens or fewer selects every row: the
    decode form takes the dense walk of the pool's pages (the cond's
    other branch scores nothing) and the prefill form under the mask
    equals the packed kernel with no mask; both equal the reference with
    ``index_topk`` above every context."""
    cfg, lp, inner, pos = _full_layer(n=8)
    kd = dots3.kind(cfg, False)
    n, ps = 8, 4
    seg = jnp.ones((n,), jnp.int32)
    dense = dots3.latent_attention(
        inner, lp, cfg, kd, pos, lambda q_nope, q_pe, row, c_q:
        dots3.attend_expanded(q_nope, q_pe, row, lp, kd, seg,
                              attn_impl="jnp"))
    leaf = kv_cache.write_latent_rows(
        dots3.init_cache(cfg, 1, 4, ps, jnp.float32)["latent"][0],
        1 + pos // ps, pos % ps, dots3.latent_rows(inner, lp, cfg, kd, pos))
    pages, table, base = _decode_view(n, ps, 2)

    def absorbed(q_nope, q_pe, row, c_q):
        return dots3.attend_sparse(q_nope, q_pe, c_q, inner, leaf, leaf, lp,
                                   cfg, pos, pos + 1, pages, table, base)

    text = str(jax.make_jaxpr(lambda: dots3.latent_attention(
        inner, lp, cfg, kd, pos, absorbed))())
    assert text.count("cond[") == 1 and "top_k" in text
    walked = dots3.latent_attention(inner, lp, cfg, kd, pos, absorbed)
    with jax.default_matmul_precision("highest"):
        want = ref.attention({**cfg.to_dict(), "index_topk": 64}, lp, inner,
                             False)
    np.testing.assert_allclose(dense, want, atol=3e-6)
    np.testing.assert_allclose(walked, want, atol=3e-6)


def test_index_keys_written_by_prefill_and_read_by_decode_are_a_recompute(
        f32_run):
    """After the run the engine's index leaves hold, at the pages its
    scheduler gave the last request still resident, the keys a recompute
    of the reference's ``LayerNorm(u idx_wk)`` gives: prefill wrote the
    prompt's, decode each answer token's."""
    cfg, params = f32_run[:2]
    engine = ServingEngine(cfg, params=params, num_slots=2, page_size=4,
                           num_pages=32, max_seq=48, prefill_len=32)
    seq = np.random.RandomState(9).randint(0, 512, 13).tolist()
    req = Request(rid=0, prompt=seq, max_new_tokens=9)
    engine.step(arrivals=[req])
    while len(req.out_tokens) < 8:
        engine.step()
    slot = next(s for s in engine.scheduler.slots if s is not None)
    tokens = seq + list(req.out_tokens)
    n = slot.pos                                   # rows written so far
    held = np.concatenate([np.asarray(engine.cache["index"][0][p])
                           for p in slot.pages])[:n, :cfg.index_head_dim]
    lp = params["layers"][0]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(tokens[:n]), axis=0)
        u = ref.rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
        k = ref.layer_norm(u @ lp["idx_wk"], lp["idx_k_gain"],
                           lp["idx_k_bias"], cfg.rms_norm_eps)
        rope = cfg.qk_rope_head_dim
        k = jnp.concatenate([ref.rotary(
            k[:, :rope], jnp.arange(n), ref.inv_freq(rope, cfg.rope_theta)),
            k[:, rope:]], axis=-1)
    assert n > len(seq) and float(jnp.abs(k).max()) > 0.5
    np.testing.assert_allclose(held, k, atol=2e-5)


# ------------------------------------------- each kernel and its jnp form

def test_index_decode_kernel_matches_jnp_in_interpret_mode():
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    b, n, ps, hi, di = 3, 4, 128, 8, 128
    pages = jax.random.normal(keys[0], (1 + b * n, ps, di))
    table = 1 + jnp.arange(b * n, dtype=jnp.int32).reshape(b, n)
    lengths = jnp.asarray([0, 130, 512], jnp.int32)
    q = jax.random.normal(keys[1], (b, hi, di))
    w = jax.random.normal(keys[2], (b, hi))
    plain = dap.index_decode_scores(q, w, pages, table, lengths, impl="jnp")
    kernel = dap.index_decode_scores(q, w, pages, table, lengths,
                                     impl="pallas", interpret=True)
    assert plain.shape == (b, n * ps)
    live = np.arange(n * ps)[None, :] < np.asarray(lengths)[:, None]
    assert np.all(np.asarray(plain)[~live] <= -1e29)
    assert np.all(np.asarray(kernel)[~live] <= -1e29)
    np.testing.assert_allclose(np.asarray(kernel)[live],
                               np.asarray(plain)[live], atol=2e-5)
    # by hand: one row of one slot
    s = jnp.maximum(q[1] @ pages[table[1, 1], 1], 0.0) @ w[1]
    np.testing.assert_allclose(plain[1, ps + 1], s, rtol=1e-5)
    assert dap.index_supported(64, 128, 128, jnp.bfloat16)
    assert not dap.index_supported(64, 128, 128, jnp.float8_e4m3fn)
    assert not dap.index_supported(64, 16, 4, jnp.float32)
    assert dap.index_resolved(64, 128, 128, jnp.bfloat16) == "jnp"   # CPU
    with pytest.raises(ValueError, match="unknown decode-attention impl"):
        dap.index_resolved(64, 128, 128, jnp.bfloat16, "mosaic")


def test_latent_kernel_over_a_ring_matches_jnp_in_interpret_mode():
    """The latent kernel with ``starts`` over a ring of 3 pages of 128 a
    slot, at contexts before and after the ring has wrapped."""
    keys = jax.random.split(jax.random.PRNGKey(1), 3)
    b, ps, window, width, rank, hq = 4, 128, 200, 256, 128, 8
    ring = kv_cache.ring_pages(window, ps)
    assert ring == 3
    leaf = jax.random.normal(keys[0], (1 + b * ring, ps, width))
    q = jax.random.normal(keys[1], (b, hq, width))
    lengths = jnp.asarray([0, 90, 300, 1000], jnp.int32)
    table = kv_cache.ring_table(b, ring)
    base, starts = kv_cache.ring_view(lengths, ring, ps, window)
    kw = dict(rank=rank, sm_scale=0.1, page_base=base, starts=starts)
    plain = dap.latent_decode_attention(q, leaf, table, lengths, impl="jnp",
                                        **kw)
    kernel = dap.latent_decode_attention(q, leaf, table, lengths,
                                         impl="pallas", interpret=True, **kw)
    assert not np.asarray(plain[0]).any()
    np.testing.assert_allclose(kernel, plain, atol=3e-6)
    # without ``starts`` the rows behind the window would be read
    more = dap.latent_decode_attention(q, leaf, table, lengths, impl="jnp",
                                       rank=rank, sm_scale=0.1,
                                       page_base=base)
    assert float(jnp.abs(more[2] - plain[2]).max()) > 1e-3
    np.testing.assert_allclose(more[1], plain[1], atol=3e-6)   # 90 < window


def test_prefill_index_and_selected_kernels_match_jnp_in_interpret_mode():
    keys = jax.random.split(jax.random.PRNGKey(2), 6)
    hi, S, di, K = 8, 256, 128, 32
    q = jax.random.normal(keys[0], (hi, S, di))
    w = jax.random.normal(keys[1], (S, hi))
    k = jax.random.normal(keys[2], (S, di))
    seg = jnp.concatenate([jnp.ones(100), 2 * jnp.ones(120),
                           jnp.zeros(36)]).astype(jnp.int32)
    plain = attn_ops.packed_index_scores(q, w, k, seg, impl="jnp")
    kernel = attn_ops.packed_index_scores(q, w, k, seg, impl="pallas",
                                          interpret=True)
    masked = np.asarray(plain) <= -1e29
    np.testing.assert_array_equal(np.asarray(kernel) <= -1e29, masked)
    np.testing.assert_allclose(np.asarray(kernel)[~masked],
                               np.asarray(plain)[~masked], atol=3e-5)
    selected = attn_ops.select_keys(plain, K)
    scores = np.asarray(plain)
    for t in (0, 5, 50, 99, 100, 150, 219):
        live = int((~masked[t]).sum())
        want = set(np.argsort(-scores[t])[:min(K, live)].tolist())
        assert set(np.nonzero(np.asarray(selected[t]))[0].tolist()) == want
    hq, dk, dv = 4, 64, 128
    Q, Kk = (jax.random.normal(keys[i], (hq, S, dk)) for i in (3, 4))
    V = jax.random.normal(keys[5], (hq, S, dv))
    a = attn_ops.selected_attention(Q, Kk, V, seg, selected, impl="jnp")
    b = attn_ops.selected_attention(Q, Kk, V, seg, selected, impl="pallas",
                                    interpret=True)
    np.testing.assert_allclose(a, b, atol=3e-6)
    dense = attn_ops.packed_gqa_attention(Q, Kk, V, seg, impl="jnp")
    assert float(jnp.abs(a - dense)[150].max()) > 1e-3      # 51 keys cut to 32
    np.testing.assert_allclose(a[:, :32], dense[:, :32], atol=3e-6)


def test_kth_largest_is_exact_with_ties_negatives_and_short_rows():
    x = jnp.asarray([[3.0, -1.0, 3.0, 0.0, -0.0, 7.5, -2.5, 1e-30],
                     [-5.0, -5.0, -5.0, -5.0, -6.0, -7.0, -8.0, -9.0]])
    bits = attn_ops._ordered_bits(x)
    for k in range(1, 9):
        kth = attn_ops.kth_largest_bits(bits, k)
        want = np.sort(np.asarray(x), axis=1)[:, ::-1][:, k - 1]
        got = np.asarray(jnp.take_along_axis(
            x, jnp.argmax(bits == kth[:, None], axis=1)[:, None], axis=1))
        np.testing.assert_array_equal(got[:, 0], want)
    masked = jnp.where(jnp.arange(8)[None, :] < 3, x, -1e30)
    assert np.asarray(attn_ops.select_keys(masked, 5)).tolist() == \
        [[1, 1, 1, 0, 0, 0, 0, 0]] * 2
    # of the keys that tie with the k-th largest, the first by index:
    # what ``lax.top_k`` keeps; -0.0 ties with 0.0
    for k in range(1, 9):
        want = np.zeros((2, 8), np.int8)
        np.put_along_axis(want, np.asarray(jax.lax.top_k(x, k)[1]), 1, axis=1)
        np.testing.assert_array_equal(attn_ops.select_keys(x, k), want)


def test_three_kinds_of_state_under_one_page_table():
    cfg = D.toy_config()
    cache = dots3.init_cache(cfg, 3, 9, 4)
    assert sorted(cache) == ["index", "latent", "ring"]
    assert [len(cache[k]) for k in ("latent", "index", "ring")] == [3, 3, 3]
    ring = kv_cache.ring_pages(5, 4)
    assert ring == 3
    assert cache["latent"][0].shape == (9, 4, 128)     # 32 + 8 -> a lane tile
    assert cache["index"][0].shape == (9, 4, 128)      # 16 -> a lane tile
    assert cache["ring"][0].shape == (1 + 3 * ring, 4, 128)   # 64 + 8
    assert kv_cache.latent_row_width(1088) == 1152
    assert kv_cache.ring_pages(513, 128) == 6
    real = jax.eval_shape(lambda: dots3.init_cache(dots3.Dots3Config(
        vocab_size=8, layer_types=(dots3.FULL, dots3.SLIDING)), 16, 1280,
        128))
    assert real["latent"][0].shape == (1280, 128, 640)
    assert real["index"][0].shape == (1280, 128, 128)
    assert real["ring"][0].shape == (1 + 16 * 6, 128, 1152)
    assert list(kv_cache.init_latent_cache(2, 5, 8, 576)) == ["latent"]


# ----------------------------------------------------------- the share

def _moe_layer(cfg_dict, seed=0, tokens=24):
    keys = jax.random.split(jax.random.PRNGKey(seed), 10)
    H, F, E = cfg_dict["hidden_size"], cfg_dict["moe_intermediate_size"], \
        cfg_dict["n_routed_experts"]
    lp = {"router": jax.random.normal(keys[0], (E, H)),
          "router_bias": jax.random.normal(keys[8], (E,)) * 0.3,
          "w_gate": jax.random.normal(keys[1], (E, H, F)) * 0.05,
          "w_up": jax.random.normal(keys[2], (E, H, F)) * 0.05,
          "w_down": jax.random.normal(keys[3], (E, F, H)) * 0.05,
          "shared_gate": jax.random.normal(keys[5], (H, F)) * 0.05,
          "shared_up": jax.random.normal(keys[6], (H, F)) * 0.05,
          "shared_down": jax.random.normal(keys[7], (F, H)) * 0.05}
    return lp, jax.random.normal(keys[4], (tokens, H))


def test_the_four_shares_and_one_shared_expert_add_up_to_the_uncut_layer():
    """What every chip computes alike counts ONCE: the four shares'
    routed partial sums (the program's held-experts layer, bias-selected,
    checked against the reference's ``routed``) plus one shared expert
    give the reference's uncut layer."""
    cfg = D.toy_config()
    d = cfg.to_dict()
    lp, x = _moe_layer(d)
    with jax.default_matmul_precision("highest"):
        uncut = ref.moe({**d, "held_experts": (0, 16)}, lp, x)
        total, assigned = jnp.zeros_like(x), 0
        for first in (0, 4, 8, 12):
            share = {**lp, **{k: lp[k][first:first + 4]
                              for k in ("w_gate", "w_up", "w_down")}}
            share_cfg = dataclasses.replace(cfg, held_experts=(first, 4),
                                          n_shared_experts=0)
            part, counts = dots3.moe_ffn(x, share, share_cfg)
            np.testing.assert_allclose(part, ref.routed(
                {**d, "held_experts": (first, 4)}, share, x), atol=3e-6)
            total, assigned = total + part, assigned + int(counts.sum())
        once = moe.gated_mlp(x, lp["shared_gate"], lp["shared_up"],
                             lp["shared_down"])
    assert assigned == x.shape[0] * d["num_experts_per_tok"]   # dropless
    # the bias moved the choice: a plain top-k picks other experts
    chosen, _ = ref.route(d, lp, x)
    plain = jax.lax.top_k(jax.nn.sigmoid(x @ lp["router"].T), 4)[1]
    assert (np.sort(chosen, -1) != np.sort(plain, -1)).any()
    np.testing.assert_allclose(total + once, uncut, atol=5e-6)


# ------------------------------------------- the prefill's row counts

@pytest.mark.parametrize("tokens,rows", [(5, 8), (9, 16), (27, 32)])
def test_prefill_does_not_depend_on_where_its_trunk_stops(
        monkeypatch, tokens, rows):
    """Two prompts packed into 32 rows: the program that stops at the
    smallest row count holding them (8: no selection work at all; 16 and
    32: the indexer, the threshold, the mask) gives the logits, the
    counters and the three kinds of state (the null page apart) of the
    program that runs all 32 rows."""
    cfg = D.toy_config(cache_dtype="float32")
    params = _f32(D.toy_params(cfg))
    S, ps, slots = 32, 4, 2
    assert next(r for r in dots3.prefill_rows(S) if tokens <= r) == rows
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
        cache = dots3.init_cache(cfg, slots, 1 + slots * 8, ps, jnp.float32)
        return jax.jit(lambda c: dots3.prefill(
            params, c, ids, positions, seg, token_rows, table, last,
            cfg=cfg))(cache)

    got = run()
    monkeypatch.setattr(dots3, "prefill_rows", lambda S: (S,))
    want = run()
    np.testing.assert_allclose(got[1], want[1], atol=1e-5)
    lens = [first, tokens - first]
    assert int(got[2]["index_pairs"]) == sum(n * (n + 1) // 2 for n in lens)
    assert int(got[2]["sparse_pairs"]) == sum(
        sum(min(t + 1, 8) for t in range(n)) for n in lens)
    for name in ("latent", "index", "ring"):
        for a, b in zip(got[0][name], want[0][name]):
            np.testing.assert_allclose(a[1:], b[1:], atol=1e-5)
            assert float(jnp.abs(b[1:]).max()) > 0


# --------------------------------------------------------------- the seam

@pytest.mark.parametrize("option,value", [
    ("tp", 2), ("weight_quant", True), ("kv_quant", True),
    ("kv_swap", True), ("prefix_cache", True), ("spec_decode", 2),
    ("decode_k", 2), ("overlap", True)])
def test_dots3_family_refuses_by_name_what_it_cannot_honour(option, value):
    cfg = D.toy_config()
    with pytest.raises(ValueError, match=f"dots3 .*{option}="):
        ServingEngine(cfg, params={}, num_slots=2, page_size=4,
                      num_pages=8, max_seq=16, prefill_len=8,
                      **{option: value})


def test_the_family_and_the_config_class_follow_the_model_type():
    fam = family_mod.family_of(D.toy_config())
    assert fam.name == "dots3" and fam.one_prefill_a_round
    assert set(fam.refused) == set(family_mod.OPTIONS_OFF)
    assert fam.prefill_rows(8192) == (1024, 2048, 4096, 8192)
    published = {k: (list(v) if isinstance(v, tuple) else v)
                 for k, v in D.TOY.items() if k != "held_experts"}
    cfg = family_mod.config_from_dict({
        **published, "num_hidden_layers": 6, "n_routed_experts": 4,
        "published_n_routed_experts": 16, "model_type": "dots3_note",
        "rope_scaling": None, "hidden_act": "silu"})
    assert isinstance(cfg, dots3.Dots3Config) and hash(cfg)
    assert cfg.n_routed_experts == 16 and cfg.held_experts == (0, 4)
    assert cfg.num_layers == cfg.to_dict()["num_hidden_layers"] == 6
    dots3.check_config(cfg)
    assert [cfg.is_expert_layer(i) for i in range(3)] == [False, True, True]
    full, sliding = dots3.kind(cfg, False), dots3.kind(cfg, True)
    assert (full.heads, full.rank, full.width, full.window) == (8, 32, 40,
                                                                None)
    assert (sliding.heads, sliding.rank, sliding.width, sliding.window) \
        == (4, 64, 72, 5)
    assert abs(full.r_kv - 2.0) < 1e-12 and abs(full.r_q - 2 ** 0.5) < 1e-12
    real = dots3.Dots3Config(vocab_size=8, layer_types=(dots3.FULL,))
    assert abs(dots3.kind(real, False).r_kv ** 2 - 10) < 1e-9
    assert abs(dots3.kind(real, True).r_kv ** 2 - 5) < 1e-9
    assert dots3.kind(real, True).width == 1088
    with pytest.raises(ValueError, match="layer_types must name"):
        family_mod.config_from_dict({
            **published, "num_hidden_layers": 9, "n_routed_experts": 4,
            "model_type": "dots3_note"})


@pytest.mark.parametrize("changes,said", [
    (dict(held_experts=(14, 4)), "held_experts"),
    (dict(topk_method="none"), "topk_method"),
    (dict(scoring_func="softmax"), "scoring_func"),
    (dict(rope_scaling=(("factor", 4), ("type", "linear"))), "rope_scaling"),
    (dict(swa_qk_rope_head_dim=7), "odd number of rotary dims"),
    (dict(attention_gate_type="elementwise"), "attention_gate_type"),
    (dict(layer_types=("full_attention", "linear_attention")), "layer_types"),
])
def test_check_config_names_what_the_programs_do_not_model(changes, said):
    with pytest.raises(ValueError, match=said):
        dots3.check_config(D.toy_config(**changes))


def test_dots3_engine_prefills_one_dispatch_a_round():
    cfg = D.toy_config()
    engine = ServingEngine(cfg, params=D.toy_params(cfg), num_slots=8,
                           page_size=4, num_pages=96, max_seq=40,
                           prefill_len=32)
    assert sorted(engine.cache) == ["index", "latent", "ring"]
    requests = [Request(rid=i, prompt=[7 + i] * 20, max_new_tokens=8)
                for i in range(4)]
    info = whole_round(engine, arrivals=requests)
    assert len(info["prefilled"]) == 1 and engine.scheduler.queue_depth() == 3
    for _ in range(3):      # a round: its prefill half, its decode half
        assert len(whole_round(engine)["prefilled"]) == 1
    assert engine.prefill_batches == 4 and engine.scheduler.queue_depth() == 0
    assert len(requests[0].out_tokens) == 5
