"""The span recorder (apex_tpu.telemetry.spans) and its use inside the
engine round, the request lifecycle and the trainer loop: the span
names, their nesting and what they add up to are what the benchmark's
``program_span`` metrics and docs/API.md's operating notes rely on."""

import functools
import glob
import os
import re
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving import ServingEngine, model as smodel
from apex_tpu.serving.kv_cache import init_cache
from apex_tpu.serving.scheduler import Request
from apex_tpu.telemetry import spans
from apex_tpu.transformer.testing import TransformerConfig

SERVE_SCOPES = ("embed", "weights_cast", "layer/attn/qkv",
                "layer/attn/kv_write", "layer/attn/attend",
                "layer/attn/out", "layer/mlp", "final_norm", "lm_head",
                "sample")
TRAIN_SCOPES = ("fwd_bwd", "grad_pmean", "unscale", "optimizer",
                "apply_update")
ROUND_CHILDREN = ["engine.schedule", "prefill.pack", "prefill.stage",
                  "prefill.dispatch", "prefill.fetch", "prefill.commit",
                  "decode.stage", "decode.dispatch", "decode.fetch",
                  "decode.commit"]


@pytest.fixture(autouse=True)
def _fresh_ring():
    spans.clear()
    spans.set_enabled(True)
    yield
    spans.clear()
    spans.set_enabled(True)


def _cfg():
    return TransformerConfig(
        hidden_size=64, num_layers=2, num_attention_heads=4,
        vocab_size=256, max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, apply_query_key_layer_scaling=False,
        bf16=True)


@pytest.fixture(scope="module")
def params():
    return smodel.init_gpt_params(_cfg(), seed=3)


def _engine(params, **kw):
    return ServingEngine(_cfg(), params=params, seed=3, num_slots=4,
                         page_size=8, num_pages=24, max_seq=64,
                         prefill_len=64, **kw)


def _requests(n=6):
    return [Request(rid=i, prompt=[1 + (i + j) % 200 for j in range(5 + i)],
                    max_new_tokens=3 + i % 3, arrival=float(i // 2))
            for i in range(n)]


def _named(records, name):
    return [r for r in records if r.name == name]


# ------------------------------------------------------------ the recorder

def test_nesting_gives_parent_ids_and_stamps_in_order():
    with spans.span("outer", tick=7) as outer:
        with spans.span("inner", rid=5):
            pass
        with spans.span("inner"):
            pass
        outer.set(done=2)
    first, second, root = spans.snapshot()
    assert (root.name, root.parent) == ("outer", None)
    assert root.attrs == {"tick": 7, "done": 2}
    assert first.parent == second.parent == root.id
    assert (first.rid, first.attrs, second.rid) == (5, None, None)
    assert root.t0 <= first.t0 <= first.t1 <= second.t0 <= second.t1 \
        <= root.t1
    assert len({first.id, second.id, root.id}) == 3


def test_each_thread_has_its_own_stack():
    def worker():
        with spans.span("on.worker"):
            pass

    with spans.span("on.main") as main:
        thread = threading.Thread(target=worker)
        thread.start()
        thread.join()
        with spans.span("child"):
            pass
    worker_span, = _named(spans.snapshot(), "on.worker")
    child, = _named(spans.snapshot(), "child")
    assert worker_span.parent is None      # a root on its own thread
    assert child.parent == main.id


def test_record_stamps_a_span_after_the_fact():
    with spans.span("cause") as cause:
        spans.record("request.queue", 1.0, 2.5, rid=9, prompt=12)
    rec, _ = spans.snapshot()
    assert (rec.name, rec.t0, rec.t1, rec.rid) == \
        ("request.queue", 1.0, 2.5, 9)
    assert rec.attrs == {"prompt": 12} and rec.parent == cause.id


def test_the_ring_is_bounded_and_says_what_it_still_covers():
    spans.clear(capacity=8)
    assert spans.covers(0.0) and spans.dropped() == 0
    for k in range(8):
        spans.record("old", float(k), k + 0.5)
    assert spans.covers(0.0) and spans.dropped() == 0
    for k in range(8, 12):
        spans.record("new", float(k), k + 0.5)
    assert len(spans.snapshot()) == 8 and spans.dropped() == 4
    # records 0..3 fell off: the last of them ended at 3.5
    assert not spans.covers(0.0) and not spans.covers(3.5)
    assert spans.covers(3.6) and spans.covers(10.0)
    assert spans.CAPACITY >= 2 ** 17


def test_snapshot_returns_what_overlaps_the_stretch():
    for k in range(5):
        spans.record("r", float(k), k + 1.0)
    assert [r.t0 for r in spans.snapshot(1.5, 3.5)] == [1.0, 2.0, 3.0]
    assert [r.t0 for r in spans.snapshot(t0=3.5)] == [3.0, 4.0]
    assert len(spans.snapshot()) == 5


def test_disabled_appends_nothing_and_shares_one_null_context():
    assert spans.set_enabled(False) is True
    a, b = spans.span("x", tick=1), spans.span("y")
    assert a is b
    with a as inside:
        inside.set(k=1)
        spans.record("z", 0.0, 1.0)
    assert spans.snapshot() == []
    assert spans.set_enabled(True) is False
    with spans.span("x"):
        pass
    assert len(spans.snapshot()) == 1


# --------------------------------------------------------- the engine round

def test_serving_is_identical_with_spans_on_and_off(params):
    outs, waited = {}, {}
    for on in (True, False):
        spans.set_enabled(on)
        spans.clear()
        engine = _engine(params)
        done = engine.run_trace(_requests())
        outs[on] = {r.rid: list(r.out_tokens) for r in done}
        # the queue's account lives on the requests, recorder on or off
        waited[on] = {r.rid: (r.queued_rounds, r.blocked) for r in done}
        assert engine.decode_cache_size() == 1
        assert engine.prefill_cache_size() == 1
        assert bool(spans.snapshot()) is on
    assert outs[True] == outs[False] and len(outs[True]) == 6
    assert waited[True] == waited[False]
    assert any(rounds for rounds, _ in waited[True].values())


def test_one_round_yields_the_documented_span_tree(params):
    engine = _engine(params)
    engine.step(arrivals=_requests(3))     # admits and prefills
    engine.step()                          # the same round: decodes
    records = spans.snapshot()
    first, second = _named(records, "engine.round")
    halves = [[r for r in records if r.parent == root.id
               and not r.name.startswith("request.")]
              for root in (first, second)]
    # the round's tree, cut where the first call returned: behind the
    # prefill's commit
    assert [r.name for r in halves[0]] == ROUND_CHILDREN[:6]
    assert [r.name for r in halves[1]] == ROUND_CHILDREN[6:]
    for root, children in zip((first, second), halves):
        assert root.t0 <= children[0].t0 and children[-1].t1 <= root.t1
        for before, after in zip(children, children[1:]):
            assert before.t1 <= after.t0  # inside the parent, no overlap
    assert first.t1 <= second.t0
    assert first.attrs["tick"] == 0 and first.attrs["prefilled"] == 3
    assert first.attrs["decoded"] == 0
    assert first.attrs["returned"] == "prefill"
    assert second.attrs["tick"] == 0 and second.attrs["prefilled"] == 0
    assert second.attrs["decoded"] == 3 and "returned" not in second.attrs
    schedule, pack = halves[0][0], halves[0][1]
    assert schedule.attrs == {"admitted": 3, "queue_depth": 0,
                              "stopped": None}
    assert pack.attrs == {"rows": 3, "tokens": 5 + 6 + 7, "trunk_rows": 32}
    # a round that only decodes has no prefill span
    spans.clear()
    engine.step()
    names = [r.name for r in spans.snapshot()
             if not r.name.startswith("request.")]
    assert names == ["engine.schedule", "decode.stage", "decode.dispatch",
                     "decode.fetch", "decode.commit", "engine.round"]


def _queue_account(engine, requests, reason):
    """``({rid: (rounds, blocked)}, [stopped of each engine.schedule])``
    of three requests sent at once and served to their end; after the
    first round the second is the candidate, the third behind it."""
    engine.step(arrivals=requests)
    assert [(r.queued_rounds, r.blocked) for r in requests] == [
        (0, None), (1, reason), (1, "behind")]
    while not all(r.done() for r in requests):
        engine.step()
    records = spans.snapshot()
    waits = {r.rid: (r.attrs["rounds"], r.attrs["blocked"])
             for r in _named(records, "request.queue")}
    return waits, [r.attrs["stopped"]
                   for r in _named(records, "engine.schedule")]


def _three(prompt, answer=2):
    return [Request(rid=i, prompt=[1 + i + j for j in range(prompt)],
                    max_new_tokens=answer) for i in range(3)]


@pytest.mark.parametrize("reason", ["slots", "budget", "pages"])
def test_the_queue_says_why_admission_stopped(params, reason):
    """Three requests at once into an engine that takes one a round:
    the first enters, the second is the candidate admission stops at,
    the third is behind it; a round later the third is the candidate."""
    if reason == "slots":       # one slot
        engine = ServingEngine(_cfg(), params=params, seed=3, num_slots=1,
                               page_size=8, num_pages=24, max_seq=64,
                               prefill_len=64)
        requests = _three(prompt=5)
    elif reason == "budget":    # two prompts pass one dispatch's tokens
        engine = _engine(params)
        engine._admit_tokens = 40
        requests = _three(prompt=24)
    else:                       # a pool of 5 pages, 4 a request
        engine = ServingEngine(_cfg(), params=params, seed=3, num_slots=4,
                               page_size=8, num_pages=6, max_seq=64,
                               prefill_len=64)
        requests = _three(prompt=24, answer=8)
    waits, stopped = _queue_account(engine, requests, reason)
    assert waits[0] == (0, None)
    assert waits[1][1] == reason and waits[1][0] >= 1
    # the third waited behind the second, then was the candidate itself
    assert waits[2][1] == reason and waits[2][0] > waits[1][0]
    assert stopped[0] == reason and stopped[-1] is None
    assert set(stopped) == {reason, None}
    assert engine.scheduler.stopped is None


def test_the_request_behind_the_candidate_is_marked_behind(params):
    """The bare scheduler, one admit call: the candidate gets the
    reason, the one behind it "behind", each one more round queued; a
    call that empties the queue writes nothing and resets the reason."""
    engine = _engine(params)
    sch = engine.scheduler
    first, second, third = requests = _three(prompt=24)
    for req in requests:
        sch.submit(req, tick=0)
    assert len(sch.admit(0, token_budget=40)) == 1
    assert sch.stopped == "budget"
    assert (first.queued_rounds, first.blocked) == (0, None)
    assert (second.queued_rounds, second.blocked) == (1, "budget")
    assert (third.queued_rounds, third.blocked) == (1, "behind")
    assert len(sch.admit(1)) == 2 and sch.stopped is None
    assert (second.queued_rounds, third.queued_rounds) == (1, 1)
    assert third.blocked == "behind"       # its last reason stays


def test_the_round_carries_its_threads_cpu_seconds(params):
    engine = _engine(params)
    engine.run_trace(_requests())
    rounds = _named(spans.snapshot(), "engine.round")
    assert rounds
    for r in rounds:
        assert 0.0 <= r.attrs["cpu_s"] <= (r.t1 - r.t0) + 1e-3
    # a thread that computes all through a round reads about its wall
    # (the CPU backend runs the programs on other threads: no equality)
    assert sum(r.attrs["cpu_s"] for r in rounds) > 0.0


def test_dispatch_and_fetch_spans_add_up_to_device_dispatch_s(params):
    engine = _engine(params)
    engine.run_trace(_requests())          # warm: compiles stay out
    spans.clear()
    before = engine.device_dispatch_s
    done = engine.run_trace([Request(rid=100 + r.rid, prompt=r.prompt,
                                     max_new_tokens=r.max_new_tokens)
                             for r in _requests()])
    assert len(done) == 12
    seam = ("prefill.stage", "prefill.dispatch", "prefill.fetch",
            "decode.dispatch", "decode.fetch")
    total = sum(r.t1 - r.t0 for r in spans.snapshot() if r.name in seam)
    assert total == pytest.approx(engine.device_dispatch_s - before,
                                  rel=0.01)


def test_every_token_is_recoverable_and_request_spans_tile(params):
    engine = _engine(params)
    done = engine.run_trace(_requests())
    records = spans.snapshot()
    got = {}
    for rnd in _named(records, "engine.round"):
        fetch_ends = [r.t1 for r in records if r.parent == rnd.id
                      and r.name.endswith(".fetch")]
        for rid, n, wall in rnd.attrs["emitted"]:
            got.setdefault(rid, []).extend([wall] * n)
            # the wall is the stamp the fetch took just before it closed
            assert any(0 <= end - wall < 1e-3 for end in fetch_ends)
    for req in done:
        walls = got[req.rid]
        assert len(walls) == len(req.out_tokens) == req.max_new_tokens
        assert walls == sorted(walls)
        assert walls[0] == req.first_token_wall
        assert walls[-1] == req.finish_wall
        tiles = {r.name: r for r in records if r.rid == req.rid}
        assert set(tiles) == {"request.queue", "request.prefill",
                              "request.decode"}
        assert tiles["request.queue"].t0 == req.enqueue_wall
        assert tiles["request.queue"].t1 == tiles["request.prefill"].t0 \
            == req.admitted_wall
        assert tiles["request.prefill"].t1 == tiles["request.decode"].t0 \
            == req.first_token_wall
        assert tiles["request.decode"].t1 == req.finish_wall
        queued = tiles["request.queue"].attrs
        assert queued["prompt"] == len(req.prompt)
        assert (queued["rounds"], queued["blocked"]) == (
            req.queued_rounds, req.blocked)
        assert (queued["rounds"] >= 1) == (queued["blocked"] is not None)
        assert tiles["request.decode"].attrs == {
            "tokens": req.max_new_tokens}


def test_the_overlapped_round_keeps_the_root_and_shared_spans(params):
    serial = {r.rid: list(r.out_tokens)
              for r in _engine(params).run_trace(_requests())}
    spans.clear()
    engine = _engine(params, overlap=True)
    done = engine.run_trace(_requests())
    assert {r.rid: list(r.out_tokens) for r in done} == serial
    names = {r.name for r in spans.snapshot()}
    assert {"engine.round", "prefill.pack", "prefill.dispatch",
            "decode.stage", "decode.dispatch", "request.decode"} <= names
    assert "decode.fetch" not in names and "engine.schedule" not in names
    emitted = sum(n for r in _named(spans.snapshot(), "engine.round")
                  for _, n, _ in r.attrs["emitted"])
    # the last round's tokens land at flush(), outside any round
    assert 0 < emitted <= sum(len(v) for v in serial.values())


# ----------------------------------------------------------- the trainer

_TOY_ARGV = ["--model", "gpt", "--num-layers", "2", "--hidden-size", "64",
             "--num-attention-heads", "4", "--max-position-embeddings",
             "32", "--vocab-size", "256", "--seq-length", "32",
             "--micro-batch-size", "2", "--tensor-model-parallel-size", "1",
             "--bf16", "--optimizer", "adam", "--lr", "1e-4",
             "--lr-decay-style", "constant", "--log-interval", "2",
             "--train-iters", "6"]


class _StepSpy:
    """Stands in for ``jax.jit(step)`` in ``pretrain.main``: keeps the
    HLO text of the step as it is lowered for its first arguments."""

    def __init__(self, jitted, seen):
        self.jitted, self.seen = jitted, seen

    def __call__(self, *args):
        if "text" not in self.seen:
            self.seen["text"] = self.jitted.lower(*args).as_text(
                dialect="hlo", debug_info=True)
        return self.jitted(*args)

    def _cache_size(self):
        return self.jitted._cache_size()


@pytest.fixture(scope="module")
def toy_training():
    from examples.transformer import pretrain

    seen, real = {}, jax.jit

    def spy(fun, **kw):
        jitted = real(fun, **kw)
        return _StepSpy(jitted, seen) \
            if getattr(fun, "__name__", "") == "step" else jitted

    spans.clear()
    spans.set_enabled(True)
    jax.jit = spy
    try:
        t0 = time.perf_counter()
        out = pretrain.main(_TOY_ARGV)
    finally:
        jax.jit = real
    return out, spans.snapshot(t0), seen["text"]


def test_pretrain_main_yields_chunk_spans_and_keeps_its_record(
        toy_training):
    out, records, _ = toy_training
    chunks = _named(records, "trainer.chunk")
    assert len(chunks) == len(out["chunks"]) == 3
    assert [c.attrs for c in chunks] == [
        {"steps": 2, "iter": 2 * (k + 1), "programs": 1} for k in range(3)]
    for chunk, rec in zip(chunks, out["chunks"]):
        kids = [r for r in records if r.parent == chunk.id]
        assert [r.name for r in kids] == ["chunk.dispatch", "chunk.fetch",
                                          "chunk.host"]
        assert chunk.t0 <= kids[0].t0 and kids[-1].t1 <= chunk.t1
        assert kids[0].t1 <= kids[1].t0 and kids[1].t1 <= kids[2].t0
        # the record's stamp is taken inside chunk.host
        assert kids[2].t0 <= rec["t_end"] <= kids[2].t1
    # ``seconds``: from where the chunk before was fetched (and saved)
    # to where this one was; the first from just before the loop
    recs = out["chunks"]
    for before, chunk, rec in zip(chunks, chunks[1:], recs[1:]):
        fetched = [r for r in records if r.parent == chunk.id][1].t1
        earlier = [r for r in records if r.parent == before.id][1].t1
        assert rec["seconds"] == pytest.approx(fetched - earlier, abs=2e-3)
    assert recs[0]["seconds"] >= chunks[0].t1 - chunks[0].t0 - 2e-3
    assert recs[0]["seconds"] > recs[1]["seconds"]   # it holds the compile
    setup = [r.name for r in records if r.name.startswith("trainer.setup")]
    assert setup == ["trainer.setup.init", "trainer.setup.opt_init"]
    assert all(r.t1 <= chunks[0].t0 for r in records
               if r.name.startswith("trainer.setup"))


# ------------------------------------------------ the profiler and the HLO

def test_spans_reach_the_profilers_host_plane(params, tmp_path):
    from jax.profiler import ProfileData

    engine = _engine(params)
    engine.run_trace(_requests(2))         # compile outside the trace
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        engine.run_trace([Request(rid=50, prompt=[3, 4, 5, 6],
                                  max_new_tokens=3)])
    finally:
        jax.profiler.stop_trace()
    xplane, = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    host = [p for p in ProfileData.from_file(xplane).planes
            if p.name == "/host:CPU"]
    names = {e.name for p in host for line in p.lines for e in line.events}
    assert set(ROUND_CHILDREN) | {"engine.round"} <= names
    # outside a session a span opens no annotation, only the ring entry
    assert {"engine.round", "decode.fetch"} <= {
        r.name for r in spans.snapshot()}


def _scoped_share(text, scopes):
    """(scope names found, share of the instructions under one of them).
    Counted: every HLO instruction that came from a traced op (it
    carries an ``op_name`` with a path), bar the plumbing of a loop's or
    a call's tuple, which is no op of the program."""
    rows = re.findall(
        r'^\s*(?:ROOT )?\S+ = \S+ ([a-z\-]+)\(.*op_name="([^"]+)"', text,
        re.M)
    ops = ["/" + name for opcode, name in rows if "/" in name
           and opcode not in ("get-tuple-element", "tuple", "parameter",
                              "call", "while")]
    found = {s for s in scopes if any(f"/{s}/" in op for op in ops)}
    under = sum(1 for op in ops if any(f"/{s}/" in op for s in scopes))
    return found, under / len(ops)


@pytest.mark.parametrize("program", ["decode", "prefill", "train",
                                     "decode_served", "prefill_served"])
def test_lowered_programs_carry_the_scope_names(program, params,
                                                toy_training):
    """``*_served``: the programs on the tree the engine hands them
    (``model.serving_params``), whose matmul weights need no cast."""
    cfg = _cfg()
    if program == "train":
        text, scopes = toy_training[2], TRAIN_SCOPES
    else:
        tree = smodel.serving_params(params, cfg) \
            if program.endswith("_served") else params
        cache = init_cache(cfg.num_layers, cfg.num_attention_heads, 24, 8,
                           cfg.head_dim, jnp.bfloat16)
        table = jnp.zeros((4, 8), jnp.int32)
        if program.startswith("decode"):
            fn = functools.partial(smodel.decode_step, cfg=cfg)
            args = (tree, cache, jnp.ones((4,), jnp.int32),
                    jnp.full((4,), 3, jnp.int32), table)
            scopes = SERVE_SCOPES
        else:
            fn = functools.partial(smodel.prefill, cfg=cfg)
            flat = jnp.zeros((64,), jnp.int32)
            args = (tree, cache, flat, flat, flat, flat,
                    jnp.zeros((5, 8), jnp.int32), jnp.zeros((4,), jnp.int32))
            scopes = SERVE_SCOPES[:-1]      # first tokens are picked eagerly
        text = jax.jit(fn).lower(*args).as_text(dialect="hlo",
                                                debug_info=True)
        if tree is not params:
            assert _scoped_share(text, ("weights_cast",))[0] == set()
            scopes = tuple(s for s in scopes if s != "weights_cast")
    found, share = _scoped_share(text, scopes)
    assert found == set(scopes)
    assert share >= 0.95, share


def test_request_spans_skip_a_stream_without_its_four_walls(params):
    engine = _engine(params)
    req = Request(rid=1, prompt=[1, 2, 3], max_new_tokens=1)
    engine.scheduler.submit(req, tick=0)   # past engine.submit: no wall
    engine.step()
    assert req.done() and req.enqueue_wall is None
    assert not [r for r in spans.snapshot()
                if r.name.startswith("request.")]
    assert np.isfinite(req.finish_wall)
