"""The tree the GPT-2 serving programs read (``model.serving_params``):
the matmul weights and biases in the compute dtype, cast once at engine
build, and a compute-dtype copy of the tied table for the logits. The
MXU's operands are those the programs cast from the fp32 tree in every
step, so logits and tokens are the same bit for bit; the caller's tree,
its int8 records and its tensor-parallel placement stay as they were."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.serving import Request, SamplingParams, ServingEngine
from apex_tpu.serving import model as smodel
from apex_tpu.telemetry import spans
from apex_tpu.transformer.testing import TransformerConfig

# widths no activation of the programs below takes: a weight's shape
# names it in a jaxpr
HIDDEN, VOCAB = 64, 256


def _cfg(dtype):
    return TransformerConfig(
        hidden_size=HIDDEN, num_layers=2, num_attention_heads=4,
        vocab_size=VOCAB, max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, apply_query_key_layer_scaling=False,
        bf16=dtype == "bf16", fp16=dtype == "fp16")


@pytest.fixture(scope="module")
def params():
    return smodel.init_gpt_params(_cfg("bf16"), seed=5)


def _engine(cfg, params, **kw):
    return ServingEngine(cfg, params=params, seed=5, num_slots=2,
                         page_size=8, num_pages=40, max_seq=64,
                         prefill_len=48, **kw)


def _requests(sampled=False):
    rs = np.random.RandomState(7)
    return [Request(rid=i, prompt=[int(t) for t in rs.randint(0, VOCAB,
                                                              3 + 4 * i)],
                    max_new_tokens=6,
                    sampling=SamplingParams(temperature=0.9, top_k=32,
                                            seed=i) if sampled else None)
            for i in range(4)]


class _Logits:
    """Stands in for one of an engine's programs and keeps the logits
    of every call (the prefill program's second output, the decode
    program's third)."""

    def __init__(self, program, at, seen):
        self.program, self.at, self.seen = program, at, seen

    def __call__(self, *args):
        out = self.program(*args)
        self.seen.append(np.asarray(out[self.at]))
        return out


def _serve(engine, sampled):
    seen = []
    engine._prefill_fn = _Logits(engine._prefill_fn, 1, seen)
    engine._decode_fn = _Logits(engine._decode_fn, 2, seen)
    reqs = _requests(sampled)
    engine.run_trace(reqs)
    return seen, {r.rid: list(r.out_tokens) for r in reqs}


@pytest.mark.parametrize("mode", ["greedy", "decode_k", "sampling"])
@pytest.mark.parametrize("dtype", ["bf16", "fp16"])
def test_served_tree_gives_the_cast_in_step_numbers_bit_for_bit(
        params, dtype, mode):
    cfg = _cfg(dtype)
    kw = {"decode_k": {"decode_k": 2}, "sampling": {"sampling": True},
          "greedy": {}}[mode]
    served = _engine(cfg, params, **kw)
    cast_in_step = _engine(cfg, params, **kw)
    cast_in_step.served_params = cast_in_step.params   # the fp32 tree
    assert served.served_params["word_logits"].dtype == \
        smodel.compute_dtype(cfg)
    logits, tokens = _serve(served, mode == "sampling")
    want_logits, want_tokens = _serve(cast_in_step, mode == "sampling")
    assert tokens == want_tokens
    assert all(len(t) == 6 for t in tokens.values())
    assert len(logits) == len(want_logits) > 4
    for got, want in zip(logits, want_logits):
        assert got.dtype == want.dtype == smodel.compute_dtype(cfg)
        np.testing.assert_array_equal(got, want)


def test_an_fp32_config_serves_the_callers_arrays(params):
    cfg = _cfg("fp32")
    fp32 = jax.tree.map(lambda leaf: leaf.astype(jnp.float32), params)
    engine = _engine(cfg, fp32)
    assert engine.served_params is fp32
    for got, leaf in zip(jax.tree_util.tree_leaves(engine.served_params),
                         jax.tree_util.tree_leaves(fp32)):
        assert got is leaf


def test_a_leaf_in_the_compute_dtype_is_served_as_handed(params):
    cfg = _cfg("bf16")
    mixed = dict(params, word_embeddings=params["word_embeddings"]
                 .astype(jnp.bfloat16))
    served = smodel.serving_params(mixed, cfg)
    assert "word_logits" not in served
    assert served["word_embeddings"] is mixed["word_embeddings"]
    bf16 = jax.tree.map(lambda leaf: leaf.astype(jnp.bfloat16), params)
    assert smodel.serving_params(bf16, cfg) is bf16


def _weight_shapes(cfg):
    h = cfg.hidden_size
    return {(3 * h, h), (h, h), (4 * h, h), (h, 4 * h), (VOCAB, h)}


def _eqns(jaxpr):
    """Every equation of ``jaxpr``, those of the jaxprs its equations
    hold (an inner ``jit``, a ``cond``'s branches, a ``scan``'s body)
    included."""
    for eqn in jaxpr.eqns:
        yield eqn
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def _weight_casts(jaxpr, shapes):
    return sum(1 for e in _eqns(jaxpr)
               if e.primitive.name == "convert_element_type"
               and e.invars[0].aval.dtype == jnp.float32
               and tuple(e.invars[0].aval.shape) in shapes)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_the_served_programs_cast_no_weight(params, program):
    cfg = _cfg("bf16")
    engine = _engine(cfg, params)
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)   # noqa: E731
    table = i32(engine.num_slots + 1, engine.max_pages)
    if program == "prefill":
        fn = engine._prefill_fn
        args = lambda tree: (tree, engine.cache, *[i32(48)] * 4,  # noqa: E731
                             table, i32(engine.num_slots))
    else:
        fn = engine._decode_fn
        args = lambda tree: (tree, None, engine.cache,  # noqa: E731
                             i32(engine.num_slots), i32(engine.num_slots),
                             table[:engine.num_slots])
    shapes = _weight_shapes(cfg)
    # the fp32 tree casts every matmul weight in the step, the tied table
    # for the logits too: two layers of four, and one
    raw = jax.make_jaxpr(fn)(*args(engine.params)).jaxpr
    assert _weight_casts(raw, shapes) >= 4 + 1
    served = jax.make_jaxpr(fn)(*args(engine.served_params)).jaxpr
    assert _weight_casts(served, shapes) == 0


def test_the_callers_tree_and_its_int8_records_stay(params):
    cfg = _cfg("bf16")
    engine = _engine(cfg, params, weight_quant=True)
    assert engine.params is params
    want = smodel.quantize_decode_params(params, cfg)
    got = jax.tree_util.tree_leaves(engine.qparams)
    assert len(got) == len(jax.tree_util.tree_leaves(want))
    for a, b in zip(got, jax.tree_util.tree_leaves(want)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # the int8 decode still runs beside the served prefill
    reqs = _requests()
    engine.run_trace(reqs)
    assert all(len(r.out_tokens) == 6 for r in reqs)


def test_tp2_serves_tp1s_tokens_and_keeps_each_leafs_sharding(params):
    cfg = _cfg("bf16")
    one = _engine(cfg, params)
    two = _engine(cfg, params, tp=2)
    assert two.params is not params and one.params is params
    placed = dict(jax.tree_util.tree_flatten_with_path(two.params)[0])
    for path, leaf in jax.tree_util.tree_flatten_with_path(
            two.served_params)[0]:
        # the logits' table is a copy of the word table
        source = placed.get(path, two.params["word_embeddings"])
        assert leaf.sharding.is_equivalent_to(source.sharding, leaf.ndim), \
            path
    reqs_one, reqs_two = _requests(), _requests()
    one.run_trace(reqs_one)
    two.run_trace(reqs_two)
    assert [r.out_tokens for r in reqs_two] == \
        [r.out_tokens for r in reqs_one]


def test_engine_weights_counts_the_served_tree(params):
    spans.clear()
    was = spans.set_enabled(True)
    try:
        engine = _engine(_cfg("bf16"), params)
        record, = [r for r in spans.snapshot() if r.name == "engine.weights"]
    finally:
        spans.set_enabled(was)
        spans.clear()
    served = jax.tree_util.tree_leaves(engine.served_params)
    bf16 = sum(leaf.nbytes for leaf in served if leaf.dtype == jnp.bfloat16)
    fp32 = sum(leaf.nbytes for leaf in served if leaf.dtype == jnp.float32)
    # four weights and four biases a layer, and the logits' table
    assert record.attrs == {"bytes_bf16": bf16, "bytes_fp32": fp32,
                            "leaves_cast": 2 * 8 + 1}
    h = HIDDEN
    assert bf16 == 2 * (2 * (12 * h * h + 9 * h) + VOCAB * h)
