"""A serial round that ran a prefill returns at its first tokens, and
the next ``step`` call runs the same round's decode half
(``ServingEngine.step``): what each half does and returns, that the
schedule, the ticks and every token are the one-return round's, and
that no abandoned round leaves a half owed. One rule for the four
families, so every case runs on each family's toy engine."""

import json
import os

import jax
import jax.numpy as jnp
import pytest

import axk1_toy
import dots3_toy
import mimo_toy
from round_halves import round_open, whole_round
from apex_tpu.resilience import faults
from apex_tpu.serving import ServingEngine, model as smodel
from apex_tpu.serving.scheduler import Request
from apex_tpu.telemetry import spans
from apex_tpu.transformer.testing import TransformerConfig

FAMILIES = ("gpt2", "mimo", "axk1", "dots3")
TOYS = {"mimo": mimo_toy, "axk1": axk1_toy, "dots3": dots3_toy}


def _build():
    """``{family: (cfg, float32 params)}``."""
    cfg = TransformerConfig(
        hidden_size=64, num_layers=2, num_attention_heads=4,
        vocab_size=512, max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, apply_query_key_layer_scaling=False,
        bf16=False)
    out = {"gpt2": (cfg, smodel.init_gpt_params(cfg, seed=3))}
    for name, toy in TOYS.items():
        cfg = toy.toy_config(cache_dtype="float32")
        params = jax.tree.map(
            lambda x: x.astype(jnp.float32)
            if jnp.issubdtype(x.dtype, jnp.floating) else x,
            toy.toy_params(cfg))
        out[name] = (cfg, params)
    return out


@pytest.fixture(scope="module")
def built():
    return _build()


def _engine(built, family, **kw):
    cfg, params = built[family]
    return ServingEngine(cfg, params=params, num_slots=3, page_size=4,
                         num_pages=64, max_seq=40, prefill_len=32, **kw)


@pytest.fixture(autouse=True)
def _clean():
    faults._cache["fired"] = {}
    spans.clear()
    spans.set_enabled(True)
    yield
    faults._cache["fired"] = {}
    spans.clear()


def _request(rid, prompt=6, answer=5, arrival=0.0):
    return Request(rid=rid, prompt=[3 + (7 * rid + j) % 200
                                    for j in range(prompt)],
                   max_new_tokens=answer, arrival=arrival)


def _children(root):
    return [r.name for r in spans.snapshot() if r.parent == root.id
            and not r.name.startswith("request.")]


# ------------------------------------------------- (a) the prefill half

@pytest.mark.parametrize("family", FAMILIES)
def test_a_round_that_prefills_returns_at_its_first_tokens(built, family):
    engine = _engine(built, family)
    old = _request(0, answer=6)
    whole_round(engine, arrivals=[old])          # a lane that decodes
    assert len(old.out_tokens) == 2 and engine.tick == 1
    spans.clear()
    new = [_request(1, prompt=5), _request(2, prompt=7)]
    info = engine.step(arrivals=new)
    # (12 tokens: one dispatch in every family)
    assert len(info["admitted"]) == len(info["prefilled"]) == 2
    assert info["decoded_slots"] == 0 and info["verified"] == []
    assert info["tick"] == 1 == engine.tick and round_open(engine, info)
    assert [len(r.out_tokens) for r in new] == [1, 1]
    assert len(old.out_tokens) == 2              # its decode is still owed
    root, = [r for r in spans.snapshot() if r.name == "engine.round"]
    names = _children(root)
    assert "prefill.dispatch" in names and names[-1] == "prefill.commit"
    assert not [n for n in names if n.startswith("decode.")]
    assert root.attrs["returned"] == "prefill"
    assert root.attrs["prefilled"] == 2 and root.attrs["decoded"] == 0
    for r in new:
        assert root.t0 <= r.first_token_wall <= root.t1
    # nothing is in flight between the halves
    engine.flush()
    assert [len(r.out_tokens) for r in new] == [1, 1]


# -------------------------------------------------- (b) the decode half

@pytest.mark.parametrize("family", FAMILIES)
def test_the_next_call_is_the_same_rounds_decode_half(built, family):
    engine = _engine(built, family)
    old = _request(0, answer=6)
    whole_round(engine, arrivals=[old])
    new = _request(1)
    opened = engine.step(arrivals=[new])
    assert opened["prefilled"] and engine.tick == 1
    waiting = _request(2)
    assert engine.submit(waiting) is None        # queued; a slot is free
    assert engine.scheduler.queue_depth() == 1
    assert sum(s is None for s in engine.scheduler.slots) == 1
    spans.clear()
    info = engine.step()
    assert info == {"tick": 1, "evicted": [], "admitted": [],
                    "prefilled": [], "shed": [], "verified": [],
                    "decoded_slots": 2}
    assert engine.tick == 2                      # once over the pair
    assert engine.scheduler.queue_depth() == 1 and not waiting.out_tokens
    assert len(old.out_tokens) == 3 and len(new.out_tokens) == 2
    root, = [r for r in spans.snapshot() if r.name == "engine.round"]
    assert _children(root) == ["decode.stage", "decode.dispatch",
                               "decode.fetch", "decode.commit"]
    assert "returned" not in root.attrs and root.attrs["decoded"] == 2
    # the round after it is a round like any other: it admits
    info = engine.step()
    assert len(info["admitted"]) == 1 and info["tick"] == 2
    assert len(waiting.out_tokens) == 1


@pytest.mark.parametrize("family", FAMILIES)
def test_a_round_whose_lanes_are_all_done_still_decodes_them(built, family):
    """The harness's warm-up: every request done at its prefill. The
    lanes ride the decode step as ballast, as in the one-return round,
    and the next batch's round evicts them."""
    engine = _engine(built, family)
    batch = [_request(i, answer=1) for i in range(2)]
    info = engine.step(arrivals=batch)
    assert all(r.done() for r in batch) and round_open(engine, info)
    info = engine.step()
    assert info["decoded_slots"] == 2 and engine.tick == 1
    info = engine.step(arrivals=[_request(5, answer=1)])
    assert sorted(info["evicted"]) == [0, 1] and info["tick"] == 1


@pytest.mark.parametrize("family", FAMILIES)
def test_requests_handed_to_a_decode_half_get_their_round(built, family):
    """A caller that hands ``step`` requests reads their slots when it
    returns (the benchmark's judge seats one prompt a call): the call
    runs the owed half, then the round that schedules them."""
    engine = _engine(built, family)
    first, second = _request(0, answer=2), _request(1, answer=2)
    info = engine.step(arrivals=[first])
    assert round_open(engine, info) and len(first.out_tokens) == 1
    info = engine.step(arrivals=[second])
    assert first.done() and info["decoded_slots"] == 1
    assert info["tick"] == 1 and info["prefilled"] and engine.tick == 1
    assert any(s is not None and s.request is second
               for s in engine.scheduler.slots)
    assert len(second.out_tokens) == 1 and round_open(engine, info)


# ----------------------------------- (c) the schedule and every token

def _trace():
    """Arrivals over seven ticks into three slots: requests queue behind
    full slots (and, in the families that prefill one dispatch a round,
    behind a spent token budget), one is done at its prefill, rounds
    prefill with lanes decoding."""
    shape = [(0, 9, 6), (0, 14, 3), (0, 12, 8), (1, 5, 1), (2, 20, 4),
             (2, 7, 7), (4, 16, 2), (5, 6, 5), (6, 11, 6), (6, 4, 3)]
    return [_request(rid, prompt=p, answer=a, arrival=float(at))
            for rid, (at, p, a) in enumerate(shape)]


def _account(done):
    return {r.rid: (list(r.out_tokens), r.admitted_tick, r.finished_tick)
            for r in done}


def test_gpt2_trace_matches_the_one_return_round(built):
    """``_step_overlap`` keeps the one-return round: the same tokens,
    admitted and finished at the same ticks."""
    halves = _account(_engine(built, "gpt2").run_trace(_trace()))
    whole = _account(_engine(built, "gpt2", overlap=True)
                     .run_trace(_trace()))
    assert halves == whole and len(halves) == 10


# what the parent commit (78d5e26: one return a round) gave for
# ``_trace()`` through ``run_trace`` on these engines, as ``_account``
# has it: {family: {rid: [tokens, admitted_tick, finished_tick]}}
PARENT = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "round_halves_parent.json")


@pytest.mark.parametrize("family", ["mimo", "axk1", "dots3"])
def test_trace_matches_the_parent_commit(built, family):
    engine = _engine(built, family)
    got = _account(engine.run_trace(_trace()))
    with open(PARENT) as fh:
        want = {int(rid): (toks, admitted, finished) for rid,
                (toks, admitted, finished) in json.load(fh)[family].items()}
    assert {rid: ticks[1:] for rid, ticks in got.items()} \
        == {rid: ticks[1:] for rid, ticks in want.items()}
    assert got == want
    assert engine.prefill_cache_size() == engine.decode_cache_size() == 1


# ------------------------------------------- (d) abandoned rounds

def _tokens_undisturbed(built, family, requests):
    engine = _engine(built, family)
    engine.run_trace(requests)
    return {r.rid: list(r.out_tokens) for r in requests}


@pytest.mark.parametrize("half", ["prefill", "decode"])
@pytest.mark.parametrize("family", FAMILIES)
def test_a_failed_half_leaves_no_half_owed(built, family, half,
                                           monkeypatch):
    want = _tokens_undisturbed(
        built, family, [_request(0, answer=5), _request(1, answer=4)])
    monkeypatch.setenv("APEX_FAULT_PLAN", json.dumps([
        {"site": f"serve_{half}", "kind": "raise", "message": "gone",
         "match_ctx": {"tick": 1}}]))
    engine = _engine(built, family, recover=True, dispatch_timeout_s=60,
                     round_retry_wait_s=0)
    a, b = _request(0, answer=5), _request(1, answer=4)
    whole_round(engine, arrivals=[a])
    info = engine.step(arrivals=[b])             # tick 1 opens
    if half == "decode":
        assert round_open(engine, info) and len(b.out_tokens) == 1
        info = engine.step()                     # its decode half fails
    assert info["degraded"]["phase"] == half and engine.tick == 2
    assert sorted(info["degraded"]["requeued"]) == [0, 1]
    assert not engine._decode_owed
    # the next call opens a round: it admits and replays
    info = engine.step()
    assert info["tick"] == 2 and info["admitted"] and info["prefilled"]
    for _ in range(40):
        if a.done() and b.done():
            break
        engine.step()
    assert {0: a.out_tokens, 1: b.out_tokens} == want
    assert engine.resilience.degraded_rounds == 1


@pytest.mark.parametrize("family", FAMILIES)
def test_a_drain_between_the_halves_leaves_no_half_owed(built, family):
    engine = _engine(built, family)
    a, b = _request(0), _request(1)
    whole_round(engine, arrivals=[a])
    assert round_open(engine, engine.step(arrivals=[b]))
    drained = engine.drain_for_failover(engine.tick)
    assert sorted(r.rid for r in drained) == [0, 1]
    assert not engine._decode_owed
    # the emptied engine's next call is a round of its own
    c = _request(2, answer=1)
    info = engine.step(arrivals=[c])
    assert info["admitted"] and info["prefilled"] and c.done()
