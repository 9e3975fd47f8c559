"""Serving stack (apex_tpu.serving, ISSUE 10): decode/prefill logits
parity per dtype, paged-allocator invariants, scheduler no-starvation,
int8 weight-quant parity band, jaxpr stability across admit/evict, and
the serving ledger block's validation + check-8 teeth."""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.serving import (
    ContinuousBatchingScheduler,
    PageAllocator,
    Request,
    ServingEngine,
    init_cache,
    synthetic_trace,
)
from apex_tpu.serving import model as smodel
from apex_tpu.serving import quant as quant_mod
from apex_tpu.serving.kv_cache import pages_needed
from apex_tpu.telemetry import ledger as ledger_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(bf16=False):
    from apex_tpu.transformer.testing import TransformerConfig

    return TransformerConfig(
        hidden_size=64, num_layers=2, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=64,
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=bf16)


@pytest.fixture(scope="module")
def f32_setup():
    cfg = _cfg(False)
    return cfg, smodel.init_gpt_params(cfg)


@pytest.fixture(scope="module")
def bf16_setup():
    cfg = _cfg(True)
    return cfg, smodel.init_gpt_params(cfg)


def _oneshot_logits(cfg, params, tokens):
    """GPTModel.apply over the full sequence — the training stack's
    own numbers, the parity oracle for the serving forward."""
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.transformer.parallel_state import TENSOR_AXIS
    from apex_tpu.transformer.testing import GPTModel

    model = GPTModel(cfg)
    mesh = Mesh(np.asarray(jax.devices()[:1]), (TENSOR_AXIS,))
    ids = jnp.asarray(tokens, jnp.int32)[None, :]
    pos = jnp.arange(len(tokens), dtype=jnp.int32)[None, :]
    return jax.jit(jax.shard_map(
        lambda p, i, po: model.apply({"params": p}, i, po, None),
        mesh=mesh, in_specs=(P(),) * 3, out_specs=P(),
        check_vma=False))(params, ids, pos)[0]


def _decode_rollout(cfg, params, prompt, n_new, ps=8, qparams=None):
    """Model-level prefill + n_new greedy decode steps over one
    request's paged cache; returns (tokens, per-step logits)."""
    max_pages = pages_needed(len(prompt) + n_new, ps)
    n_pages = max_pages + 2
    cache = init_cache(cfg.num_layers, cfg.num_attention_heads,
                       n_pages, ps, cfg.head_dim,
                       smodel.compute_dtype(cfg))
    pt = np.zeros((2, max_pages), np.int32)
    pt[0] = np.arange(1, max_pages + 1)       # row 1 = null spare
    S = len(prompt)
    ids = jnp.asarray(prompt, jnp.int32)
    positions = jnp.arange(S, dtype=jnp.int32)
    seg = jnp.ones((S,), jnp.int32)
    token_rows = jnp.zeros((S,), jnp.int32)
    cache, logits0 = smodel.prefill(
        params, cache, ids, positions, seg, token_rows,
        jnp.asarray(pt), jnp.asarray([S - 1], jnp.int32), cfg=cfg)
    tok = int(jnp.argmax(logits0[0].astype(jnp.float32)))
    toks, steps = [tok], []
    pt1 = jnp.asarray(pt[:1])
    for i in range(n_new - 1):
        cache, nxt, lg = smodel.decode_step(
            params, cache, jnp.asarray([tok], jnp.int32),
            jnp.asarray([S + 1 + i], jnp.int32), pt1, cfg=cfg,
            qparams=qparams)
        steps.append(np.asarray(lg[0].astype(jnp.float32)))
        tok = int(nxt[0])
        toks.append(tok)
    return toks, logits0, steps


@pytest.mark.parametrize("setup,atol,name", [
    ("f32_setup", 2e-4, "f32"), ("bf16_setup", 0.35, "bf16")],
    ids=["f32", "bf16"])
def test_decode_matches_prefill_per_dtype(setup, atol, name, request):
    """Token-by-token decode over the paged cache equals the one-shot
    forward of the SAME weights over >= 32 generated tokens: greedy
    tokens identical, per-step logits within dtype tolerance (the
    ISSUE 10 acceptance parity)."""
    cfg, params = request.getfixturevalue(setup)
    rs = np.random.RandomState(0)
    prompt = [int(t) for t in rs.randint(0, cfg.vocab_size, 6)]
    n_new = 33
    toks, logits0, steps = _decode_rollout(cfg, params, prompt, n_new)
    full = prompt + toks
    oneshot = np.asarray(
        _oneshot_logits(cfg, params, full).astype(jnp.float32))
    greedy = np.argmax(oneshot, axis=-1)
    p = len(prompt)
    assert toks == [int(t) for t in greedy[p - 1:p - 1 + n_new]], (
        f"{name}: greedy decode diverged from the one-shot forward")
    # prefill's next-token logits == one-shot logits at the last
    # prompt position
    np.testing.assert_allclose(
        np.asarray(logits0[0].astype(jnp.float32)), oneshot[p - 1],
        atol=atol)
    # every decode step's logits vs the one-shot row at its position
    for i, lg in enumerate(steps):
        np.testing.assert_allclose(lg, oneshot[p + i], atol=atol,
                                   err_msg=f"{name} step {i}")


# ------------------------- the cache layout and where its rows land


@pytest.mark.parametrize("kv_quant", [False, True], ids=["float", "int8"])
def test_cache_is_one_page_major_leaf_a_layer(kv_quant):
    """K and V: one ``[pages, page_size, h * d]`` array a layer, page
    axis first in every leaf (the int8 tier's ``[pages, h]`` scales
    too): the pool of the two-kind cache with every layer global."""
    from apex_tpu.serving import kv_cache

    cache = init_cache(3, 4, 10, 8, 16, jnp.bfloat16, kv_quant=kv_quant)
    assert set(cache) == ({"k", "v", "k_scale", "v_scale"} if kv_quant
                          else {"k", "v"})
    for name, leaves in cache.items():
        assert len(leaves) == 3
        want = (10, 4) if name.endswith("_scale") else (10, 8, 4 * 16)
        assert all(a.shape == want and not np.asarray(a, np.float32).any()
                   for a in leaves)
    assert cache["k"][0].dtype == (jnp.int8 if kv_quant else jnp.bfloat16)
    pool = kv_cache.init_hybrid_cache((0, 0, 0), 10, 0, 8, 0, (4, 16, 16),
                                      None, cache["k"][0].dtype)
    assert [a.shape for a in pool["global_k"]] \
        == [a.shape for a in cache["k"]]
    assert not pool["window_k"] and not pool["window_v"]


def test_decode_step_writes_one_row_a_lane_and_idle_lanes_page_zero(
        f32_setup):
    """A decode step scatters each active lane's K/V of every head as
    ONE row at ``(page, offset)`` of every layer's leaf; an inactive
    lane's write lands on null page 0 and nothing else moves."""
    cfg, params = f32_setup
    ps, pages = 8, 6
    cache = init_cache(cfg.num_layers, cfg.num_attention_heads, pages, ps,
                       cfg.head_dim, jnp.float32)
    before = jax.tree.map(np.asarray, cache)
    # lane 0: position 10 -> table entry 1 (page 4), row 2; lane 1 idle
    pt = jnp.asarray([[2, 4, 0], [5, 3, 0]], jnp.int32)
    out, toks, _ = smodel.decode_step(
        params, cache, jnp.asarray([7, 9], jnp.int32),
        jnp.asarray([11, 0], jnp.int32), pt, cfg=cfg)
    assert int(toks[1]) == 0
    for part in ("k", "v"):
        assert isinstance(out[part], list) and cache[part] is not out[part]
        for layer in range(cfg.num_layers):
            got = np.asarray(out[part][layer])
            assert got.shape == (pages, ps, cfg.hidden_size)
            assert np.abs(got[4, 2]).min() > 0        # every head's columns
            changed = np.argwhere(
                (got != before[part][layer]).any(axis=-1))
            assert {tuple(c) for c in changed} <= {(4, 2), (0, 0)}
            assert (4, 2) in {tuple(c) for c in changed}


def test_prefill_rows_land_at_page_and_offset_padding_on_page_zero(
        f32_setup):
    """The packed prefill scatters token ``t`` of a request as row
    ``t % page_size`` of page ``table[t // page_size]``; padding rows go
    to page 0; each row is the decode step's row for the same token
    (both are ``k.reshape(rows, h * d)`` of one shared trunk)."""
    cfg, params = f32_setup
    ps, pages, S, n = 8, 6, 16, 11
    cache = init_cache(cfg.num_layers, cfg.num_attention_heads, pages, ps,
                       cfg.head_dim, jnp.float32)
    prompt = [int(t) for t in np.random.RandomState(0).randint(0, 128, n)]
    real = (np.arange(S) < n).astype(np.int32)
    ids = np.zeros(S, np.int32)
    ids[:n] = prompt
    pt = np.asarray([[3, 1, 0], [0, 0, 0]], np.int32)   # row 1: null spare
    filled, _ = smodel.prefill(
        params, cache, jnp.asarray(ids),
        jnp.asarray(np.arange(S, dtype=np.int32) * real), jnp.asarray(real),
        jnp.asarray(1 - real), jnp.asarray(pt),
        jnp.asarray([n - 1], jnp.int32), cfg=cfg)
    for part in ("k", "v"):
        for layer in range(cfg.num_layers):
            got = np.asarray(filled[part][layer])
            live = {(3, r) for r in range(8)} | {(1, r) for r in range(3)}
            written = {tuple(c) for c in np.argwhere(got.any(axis=-1))}
            assert live <= written <= live | {(0, 0)}, (part, layer)
    # the same 11th token through a decode step over the first 10's pages
    short, _ = smodel.prefill(
        params, cache, jnp.asarray(ids * (np.arange(S) < n - 1)),
        jnp.asarray(np.arange(S, dtype=np.int32) * (np.arange(S) < n - 1)),
        jnp.asarray((np.arange(S) < n - 1).astype(np.int32)),
        jnp.asarray((np.arange(S) >= n - 1).astype(np.int32)),
        jnp.asarray(pt), jnp.asarray([n - 2], jnp.int32), cfg=cfg)
    stepped, _, _ = smodel.decode_step(
        params, short, jnp.asarray([prompt[-1]], jnp.int32),
        jnp.asarray([n], jnp.int32), jnp.asarray(pt[:1]), cfg=cfg)
    for part in ("k", "v"):
        for layer in range(cfg.num_layers):
            np.testing.assert_allclose(
                np.asarray(stepped[part][layer])[1, 2],
                np.asarray(filled[part][layer])[1, 2], atol=2e-5)


def test_allocator_invariants_under_churn():
    alloc = PageAllocator(32)
    rs = np.random.RandomState(1)
    live = set()
    for step in range(200):
        if live and rs.rand() < 0.4:
            victim = rs.choice(sorted(live))
            alloc.free(("req", int(victim)))
            live.discard(int(victim))
        else:
            rid = step
            got = alloc.alloc(("req", rid), int(rs.randint(1, 5)))
            if got is not None:
                live.add(rid)
        alloc.check_invariants()
    for rid in list(live):
        alloc.free(("req", rid))
    alloc.check_invariants()
    assert alloc.free_count == 31  # free-list round trip (page 0 held)
    # exhaustion is all-or-nothing: state unchanged on refusal
    assert alloc.alloc(("req", "big"), 99) is None
    alloc.check_invariants()
    assert alloc.free_count == 31


def test_scheduler_no_starvation_fifo():
    """More requests than slots/pages: strict FIFO admission with
    head-of-line blocking — admission order equals arrival order and
    every request completes (no starvation under churn)."""
    alloc = PageAllocator(16)
    sch = ContinuousBatchingScheduler(2, 8, 8, alloc)
    reqs = [Request(rid=i, prompt=[1] * 4, max_new_tokens=4,
                    arrival=0) for i in range(8)]
    for r in reqs:
        sch.submit(r)
    tick = 0
    while len(sch.completed) < len(reqs):
        assert tick < 100
        sch.evict_done(tick)
        sch.admit(tick)
        for i in sch.active_indices():
            slot = sch.slots[i]
            slot.pos += 1
            slot.request.out_tokens.append(0)
        alloc.check_invariants()
        tick += 1
    order = [r.rid for r in sorted(reqs,
                                   key=lambda r: (r.admitted_tick,
                                                  r.rid))]
    assert order == list(range(8)), "admission violated FIFO arrival"
    assert all(r.done() for r in reqs)


def test_scheduler_refuses_impossible_request_at_submit():
    """An over-max_seq request raises at submit(), before anything is
    enqueued — one malformed submission can never crash a later
    scheduler round and take the serving loop down."""
    sch = ContinuousBatchingScheduler(2, 4, 8, PageAllocator(16))
    with pytest.raises(ValueError, match="exceed the per-slot table"):
        sch.submit(Request(rid=0, prompt=[1] * 30, max_new_tokens=10))
    assert not sch.queue
    sch.submit(Request(rid=1, prompt=[1] * 20, max_new_tokens=10))
    assert len(sch.queue) == 1


def test_int8_quant_parity_band(f32_setup):
    """Quantized decode logits track the full-precision ones within
    the int8 tolerance band, and the greedy tokens stay mostly
    aligned over the rollout."""
    cfg, params = f32_setup
    rs = np.random.RandomState(2)
    prompt = [int(t) for t in rs.randint(0, cfg.vocab_size, 6)]
    qp = smodel.quantize_decode_params(params, cfg)
    toks, lg0, steps = _decode_rollout(cfg, params, prompt, 12)
    qtoks, qlg0, qsteps = _decode_rollout(cfg, params, prompt, 12,
                                          qparams=qp)
    # same trajectory => positionwise comparable logits; compare while
    # the token streams agree (a flip decorrelates everything after)
    agree = 0
    for i, (a, b) in enumerate(zip(toks, qtoks)):
        if a != b:
            break
        agree += 1
        if i > 0:
            scale = max(1.0, float(np.max(np.abs(steps[i - 1]))))
            assert float(np.max(np.abs(
                steps[i - 1] - qsteps[i - 1]))) < 0.25 * scale, (
                f"int8 logits drifted outside the band at step {i}")
    assert agree >= 8, (
        f"int8 greedy stream diverged after {agree} tokens (band too "
        f"loose to be real quantization, not a broken matmul)")


def test_quant_knob_asymmetry(monkeypatch):
    with pytest.raises(ValueError):
        quant_mod.quantize_weight(jnp.zeros((4, 4), jnp.int32))
    with pytest.raises(ValueError):
        quant_mod.set_weight_quant("yes")
    monkeypatch.setenv("APEX_SERVE_WEIGHT_QUANT", "1")
    assert quant_mod.resolve() is True
    monkeypatch.setenv("APEX_SERVE_WEIGHT_QUANT", "0")
    assert quant_mod.resolve() is False
    from apex_tpu.dispatch import tiles

    tiles._warned_env.clear()
    monkeypatch.setenv("APEX_SERVE_WEIGHT_QUANT", "maybe")
    with pytest.warns(UserWarning, match="maybe"):
        assert quant_mod.resolve() is False  # default OFF
    monkeypatch.delenv("APEX_SERVE_WEIGHT_QUANT")
    quant_mod.set_weight_quant(True)
    try:
        assert quant_mod.resolve() is True
        assert quant_mod.resolve(per_call=False) is False  # call wins
    finally:
        quant_mod.set_weight_quant(None)


def test_quant_roundtrip_accuracy():
    rs = np.random.RandomState(3)
    w = jnp.asarray(rs.randn(16, 32), jnp.float32)
    wq, scale = quant_mod.quantize_weight(w)
    deq = np.asarray(wq, np.float32) * np.asarray(scale)[:, None]
    err = np.max(np.abs(deq - np.asarray(w)))
    assert err <= np.max(np.abs(np.asarray(w))) / 127.0 + 1e-6
    zero_row = jnp.zeros((1, 8), jnp.float32)
    wq0, s0 = quant_mod.quantize_weight(zero_row)
    assert float(s0[0]) == 0.0 and np.all(np.asarray(wq0) == 0)


def test_decode_jaxpr_stable_across_admit_evict(f32_setup):
    """The acceptance contract: admitting/evicting requests changes
    array VALUES only — the decode program compiles exactly once."""
    cfg, params = f32_setup
    eng = ServingEngine(cfg, params=params, num_slots=2, page_size=8,
                        num_pages=24, max_seq=64, prefill_len=32)
    a = Request(rid=0, prompt=[3, 5, 7, 9], max_new_tokens=10)
    b = Request(rid=1, prompt=[2, 4], max_new_tokens=3)
    eng.submit(a)
    eng.step()                    # the round's prefill half,
    eng.step()                    # and its decode half
    size_before = eng.decode_cache_size()
    eng.step(arrivals=[b])        # admit mid-stream
    while not (a.done() and b.done()):
        eng.step()
    eng.step()                    # final evict round
    assert size_before == eng.decode_cache_size() == 1, (
        "decode step recompiled across scheduler events")
    assert eng.allocator.free_count == 23
    eng.allocator.check_invariants()


def test_serving_config_refusals():
    """Unsupported TransformerConfig options are explicit refusals at
    engine build, never silent numeric drift."""
    import dataclasses

    for field, val in (("hidden_dropout", 0.1),
                       ("apply_query_key_layer_scaling", True),
                       ("num_moe_experts", 2),
                       ("sequence_parallel", True)):
        bad = dataclasses.replace(_cfg(False), **{field: val})
        with pytest.raises(ValueError, match="serving does not"):
            smodel.check_serving_config(bad)


def test_serving_block_validation():
    good = {"tokens_per_s": 100.0, "p50_ms": 5.0, "p99_ms": 9.0,
            "trace_id": "tr-0123456789", "kv_pages": 64}
    rec = ledger_mod.make_record("profile_serving", "cpu", 0.1, 2,
                                 extra={"serving": dict(good)})
    assert ledger_mod.validate_record(rec) == []
    for field, bad in (("tokens_per_s", -1), ("p99_ms", True),
                       ("trace_id", "lg-x"), ("kv_pages", 0)):
        r = ledger_mod.make_record(
            "profile_serving", "cpu", 0.1, 2,
            extra={"serving": dict(good, **{field: bad})})
        assert any(field in p for p in ledger_mod.validate_record(r)), \
            field
    r = ledger_mod.make_record(
        "profile_serving", "cpu", 0.1, 2,
        extra={"serving": dict(good, p50_ms=10.0)})
    assert any("exceeds" in p for p in ledger_mod.validate_record(r))


def _check8_env(tmp_path, knobs):
    block = {"tokens_per_s": 10.0, "p50_ms": 1.0, "p99_ms": 2.0,
             "trace_id": "tr-0123456789", "kv_pages": 8}
    rec = ledger_mod.make_record("profile_serving", "cpu", 0.1, 2,
                                 knobs=knobs,
                                 extra={"serving": block})
    ledger = tmp_path / "ledger.jsonl"
    ledger.write_text(json.dumps(rec) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text(f"serving row cites ledger:{rec['id']}\n")
    table = tmp_path / "table.jsonl"
    table.write_text("")
    return ["--perf", str(perf), "--ledger", str(ledger),
            "--table", str(table)]


def test_check8_unpinned_serving_row_fails(tmp_path):
    from tests.conftest import run_check_bench_labels

    out = run_check_bench_labels(*_check8_env(tmp_path, {}))
    assert out.returncode == 1
    assert "APEX_SERVE_WEIGHT_QUANT" in out.stdout
    # multi-token decode blocks (ISSUE 17): the block size is a third
    # compiled-program axis the citation must pin
    assert "APEX_SERVE_DECODE_K" in out.stdout
    # KV tier (ISSUE 20): int8 cache and swap restore are different
    # cache tiers the citation must pin too
    assert "APEX_SERVE_KV_QUANT" in out.stdout
    assert "APEX_SERVE_KV_SWAP" in out.stdout


def test_check8_pinned_serving_row_clean(tmp_path):
    from tests.conftest import run_check_bench_labels

    out = run_check_bench_labels(*_check8_env(
        tmp_path, {"APEX_SERVE_WEIGHT_QUANT": "0",
                   "APEX_SERVE_DECODE_K": "1",
                   "APEX_SERVE_KV_QUANT": "0",
                   "APEX_SERVE_KV_SWAP": "0"}))
    assert out.returncode == 0, out.stdout


def test_dryrun_serving_contract():
    """The always-working driver contract (same as dryrun_multichip):
    prefill -> decode -> detokenized continuation with a mid-stream
    admission, in-process."""
    sys.path.insert(0, REPO)
    import __graft_entry__ as graft

    graft.dryrun_serving()


def test_profile_serving_smoke_emits_validated_row(tmp_path):
    """CPU end-to-end proof (ISSUE 10 + ISSUE 11 acceptance): one
    subprocess ``profile_serving.py --smoke`` run emits a ledger
    record whose serving AND slo blocks validate, whose knobs pin the
    dispatch choices (check 8) and the SLO thresholds / arrival
    process / scheduler policy (check 9 clean by construction, run
    against the produced ledger), and whose record renders the
    window_report serving-economics section."""
    ledger = tmp_path / "ledger.jsonl"
    env = dict(os.environ, APEX_TELEMETRY_LEDGER=str(ledger))
    env.pop("APEX_FAULT_PLAN", None)
    proc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "benchmarks", "profile_serving.py"),
         "--smoke"],
        env=env, cwd=REPO, text=True, capture_output=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-2000:]
    recs = ledger_mod.read_ledger(str(ledger))
    rec = recs[-1]
    assert ledger_mod.validate_record(rec) == []
    sv = rec["serving"]
    assert sv["tokens_per_s"] > 0 and sv["p50_ms"] <= sv["p99_ms"]
    assert sv["trace_id"].startswith("tr-") and sv["kv_pages"] > 0
    assert rec["knobs"].get("APEX_SERVE_WEIGHT_QUANT") in ("0", "1")
    # ISSUE 11: the slo block, its pins, and the overlap stamp
    slo = rec["slo"]
    assert slo["arrival_process"] == rec["knobs"]["APEX_SERVE_ARRIVALS"]
    assert slo["goodput_tok_s"] is not None \
        and 0 <= slo["slo_attainment"] <= 1
    assert slo["max_queue_depth"] is not None \
        and slo["kv_page_high_water"] is not None
    assert float(rec["knobs"]["APEX_SERVE_SLO_TTFT_MS"]) \
        == slo["slo_ttft_ms"]
    assert float(rec["knobs"]["APEX_SERVE_SLO_TPOT_MS"]) \
        == slo["slo_tpot_ms"]
    assert rec["knobs"]["APEX_SERVE_SCHED"] == "fifo"
    ob = rec["cost"]["overlap_bound"]
    assert ob["host_ms"] is not None and ob["host_ms"] >= 0
    # check 9 passes on the produced row (cited from a scratch PERF)
    from tests.conftest import run_check_bench_labels

    perf = tmp_path / "PERF.md"
    perf.write_text(f"serving slo row cites ledger:{rec['id']}\n")
    table = tmp_path / "table.jsonl"
    table.write_text("")
    out = run_check_bench_labels(
        "--perf", str(perf), "--ledger", str(ledger),
        "--table", str(table))
    assert out.returncode == 0, out.stdout
    # window_report renders the serving economics from the same ledger
    import io
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "window_report", os.path.join(REPO, "tools",
                                      "window_report.py"))
    wr = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(wr)
    report = wr.build_report(ledger_path=str(ledger))
    buf = io.StringIO()
    wr.print_report(report, out=buf)
    text = buf.getvalue()
    assert "serving economics:" in text
    assert sv["trace_id"] in text and "attainment=" in text
    assert "overlap" in text
