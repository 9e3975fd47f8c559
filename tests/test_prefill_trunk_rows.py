"""The prefill program's trunk follows the batch (``family.prefill_rows``,
a ``lax.switch`` on the batch's token count inside the ONE program of
either family): the row counts, the GPT-2 program's results and cache
independent of where its trunk stops, the one-program contract across
admission, verify and replay, the program's structure, and MiMo's
prefill jaxpr held to the text it had before ``prefill_rows`` moved."""

import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.serving import ServingEngine, init_cache, model as smodel
from apex_tpu.serving.family import family_of, prefill_rows
from apex_tpu.serving.scheduler import Request
from apex_tpu.telemetry import spans
from apex_tpu.transformer.testing import TransformerConfig

S = 64                        # prefill_rows(64) == (8, 16, 32, 64)
BUCKETS = [(7, 8), (13, 16), (29, 32), (50, 64)]   # (tokens, trunk rows)


def _cfg():
    return TransformerConfig(
        hidden_size=64, num_layers=2, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=64, hidden_dropout=0.0,
        attention_dropout=0.0, apply_query_key_layer_scaling=False)


@pytest.fixture(scope="module")
def params():
    return smodel.init_gpt_params(_cfg())


# --------------------------------------------------------- the row counts

def test_row_counts_exist_for_every_length_and_end_in_it():
    """Ascending, never empty, the last count ``S`` itself; where the
    rule before this one gave a usable tuple (``S`` a whole number of
    sublane tiles) it is that tuple. (The old rule gave nothing for 20
    and ``(8,)``, which cannot hold 17 tokens, for 17.)"""
    for n in range(1, 2049):
        rows = prefill_rows(n)
        assert rows and rows[-1] == n and list(rows) == sorted(set(rows))
        assert all(n % r == 0 and r * 8 >= n for r in rows)
        if n % 8 == 0:
            assert rows == tuple(n >> j for j in (3, 2, 1, 0)
                                 if n >> j and (n >> j) % 8 == 0)
    assert prefill_rows(2048) == (256, 512, 1024, 2048)
    assert prefill_rows(1024) == (128, 256, 512, 1024)
    assert prefill_rows(20) == (20,) and prefill_rows(17) == (17,)


def test_gpt2_row_counts_keep_the_attention_path_of_the_full_length(
        monkeypatch):
    """Where ``fused_attention`` takes its flash kernel at ``S`` (the
    chip; whole 128-row blocks) every count takes it too; on the CPU,
    where none does, every halving stays."""
    from apex_tpu.ops import attention

    assert smodel.trunk_rows(512) == (64, 128, 256, 512)
    monkeypatch.setattr(attention, "_tpu_available", lambda: True)
    assert smodel.trunk_rows(1024) == (128, 256, 512, 1024)
    assert smodel.trunk_rows(512) == (128, 256, 512)
    assert smodel.trunk_rows(384) == (384,)    # 192 rows: the dense path
    assert smodel.trunk_rows(64) == (8, 16, 32, 64)    # dense at 64 too
    assert all(attention.flash_supported(r, r)
               for r in smodel.trunk_rows(1024))
    assert family_of(_cfg()).prefill_rows is smodel.trunk_rows


# ------------------------------ results do not depend on the trunk's rows

def _packed(tokens, write_from):
    """Two requests packed into ``S`` rows: slot 0's first ``write_from``
    positions route their K/V to the spare row (a prefix-cache hit or a
    verify's context: live ``seg``, no write), slot 1 is a plain
    prompt."""
    first = tokens // 2
    ps, slots = 4, 2
    ids = np.zeros(S, np.int32)
    ids[:tokens] = np.random.RandomState(tokens).randint(0, 128, tokens)
    positions, seg = np.zeros(S, np.int32), np.zeros(S, np.int32)
    token_rows = np.full(S, slots, np.int32)
    for slot, (a, b) in enumerate(((0, first), (first, tokens))):
        positions[a:b] = np.arange(b - a)
        seg[a:b], token_rows[a:b] = slot + 1, slot
    token_rows[:write_from] = slots
    table = np.zeros((slots + 1, S // ps), np.int32)
    table[:slots] = 1 + np.arange(slots * (S // ps)).reshape(slots, -1)
    last = np.asarray([first - 1, tokens - 1], np.int32)
    return ps, slots, (ids, positions, seg, token_rows, table, last)


@pytest.mark.parametrize("kv_quant", [False, True], ids=["float", "int8"])
@pytest.mark.parametrize("tokens,rows", BUCKETS)
def test_prefill_does_not_depend_on_where_its_trunk_stops(
        monkeypatch, params, tokens, rows, kv_quant):
    """One packed batch sized into each row count: the logits and every
    cache leaf (null page 0 apart, which takes the padding's rows) are
    those of the program forced onto all ``S`` rows, in float32 to
    summation order; on the int8 tier to one code."""
    cfg = _cfg()
    assert next(r for r in smodel.trunk_rows(S) if tokens <= r) == rows
    ps, slots, args = _packed(tokens, write_from=2)
    pages = 1 + slots * (S // ps)
    keep = np.ones(pages, np.float32) if kv_quant else None

    def run():
        cache = init_cache(cfg.num_layers, cfg.num_attention_heads, pages,
                           ps, cfg.head_dim, jnp.float32, kv_quant=kv_quant)
        return jax.jit(lambda c: smodel.prefill(
            params, c, *args, keep, cfg=cfg))(cache)

    got = run()
    monkeypatch.setattr(smodel, "trunk_rows", lambda S: (S,))
    want = run()
    np.testing.assert_allclose(got[1], want[1], atol=2e-5)
    assert np.abs(np.asarray(want[1])).max() > 1e-2
    for name in want[0]:
        for a, b in zip(got[0][name], want[0][name]):
            a, b = (np.asarray(x, np.float32)[1:] for x in (a, b))
            if kv_quant:
                np.testing.assert_allclose(
                    a, b, atol=0 if name.endswith("_scale") else 1,
                    rtol=1e-2)
            else:
                np.testing.assert_allclose(a, b, atol=2e-5)
    # slot 1 wrote its rows, slot 0 none below ``write_from``
    k0 = np.asarray(want[0]["k"][0], np.float32)
    assert k0[1 + S // ps].any() and not k0[1, :2].any() and k0[1, 2].any()


# ------------------------------------------ one program, whoever calls it

def _engine(params, **kw):
    return ServingEngine(_cfg(), params=params, seed=3, num_slots=4,
                         page_size=4, max_seq=64, prefill_len=S, **kw)


def _prompts():
    return [Request(rid=i, prompt=[1 + (3 * i + j) % 100 for j in range(n)],
                    max_new_tokens=10, arrival=float(i))
            for i, (n, _) in enumerate(BUCKETS)]


@pytest.mark.parametrize("caller,options", [
    ("verify", dict(num_pages=80, spec_decode=3)),
    ("replay", dict(num_pages=32, preempt=True)),
], ids=["verify", "replay"])
def test_every_row_count_verify_and_replay_share_one_program(
        monkeypatch, params, caller, options):
    """An engine that prefills a batch of every row count, then verifies
    drafts (``_run_verify``) or replays a preempted stream
    (``_replay_prefill``) through the same program, compiled it once;
    ``prefill.pack`` names the count each dispatch takes; the tokens are
    those of an engine whose trunk always runs all ``S`` rows."""
    spans.clear()
    spans.set_enabled(True)
    engine = _engine(params, **options)
    done = engine.run_trace(_prompts())
    packs = [r.attrs for r in spans.snapshot() if r.name == "prefill.pack"]
    spans.clear()
    assert engine.prefill_cache_size() == 1
    assert engine.decode_cache_size() == 1
    assert {p["trunk_rows"] for p in packs} >= {r for _, r in BUCKETS}
    assert all(p["trunk_rows"] == next(
        r for r in prefill_rows(S) if p["tokens"] <= r) for p in packs)
    if caller == "verify":
        assert engine.verify_calls > 0
    else:
        assert engine.resilience.preempted > 0
    monkeypatch.setattr(smodel, "trunk_rows", lambda S: (S,))
    whole = _engine(params, **options).run_trace(_prompts())
    assert {r.rid: r.out_tokens for r in done} \
        == {r.rid: r.out_tokens for r in whole}


# ------------------------------------------------ the program's structure

def _eqns(jaxpr):
    """Every equation of ``jaxpr`` in order, those of the jaxprs its
    equations hold (``pjit``, ``custom_vjp``, ...) included, a ``cond``'s
    branches apart."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name == "cond":
            continue
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _eqns(inner)


def test_the_program_holds_one_switch_whose_branches_run_fewer_rows(params):
    """One ``cond`` of ``len(trunk_rows(S))`` branches, and branch ``j``'s
    first matmul has ``trunk_rows(S)[j]`` rows: a trunk run on ``S``
    rows again fails here and not only on the chip. Nothing outside the
    switch multiplies more than the gathered rows."""
    cfg = _cfg()
    ps, slots, args = _packed(20, write_from=0)
    cache = init_cache(cfg.num_layers, cfg.num_attention_heads,
                       1 + slots * (S // ps), ps, cfg.head_dim, jnp.float32)
    jaxpr = jax.make_jaxpr(functools.partial(smodel.prefill, cfg=cfg))(
        params, cache, *args).jaxpr
    top = list(_eqns(jaxpr))
    conds = [e for e in top if e.primitive.name == "cond"]
    rows = smodel.trunk_rows(S)
    assert len(conds) == 1 and len(conds[0].params["branches"]) == len(rows)
    for want, branch in zip(rows, conds[0].params["branches"]):
        first = next(e for e in _eqns(branch.jaxpr)
                     if e.primitive.name == "dot_general")
        assert first.invars[0].aval.shape[0] == want
        matmuls = [e for e in _eqns(branch.jaxpr)
                   if e.primitive.name == "dot_general"]
        assert len(matmuls) >= 4 * cfg.num_layers
    outside = [e.invars[0].aval.shape[0] for e in top
               if e.primitive.name == "dot_general"]
    assert outside == [len(args[-1])]          # the lm_head, on [G] rows


# ----------------------------------------- MiMo's prefill program, pinned

@pytest.mark.parametrize("rows,kernels,digest", [
    (2048, dict(attn_impl="pallas", moe_impl="pallas", interpret=False),
     "d4f405886cbdf8d5fc2a0ff76c71fbb8542b08e7d6bff44074188b62d1094530"),
    (128, {},
     "2fbd87d8ed8be3320938020b699d0a5829f09536f7272d3ed33241d70ad15a17"),
], ids=["cell-2048", "rehearsal-128"])
def test_mimo_prefill_program_is_the_one_pr32_measured(
        rows, kernels, digest):
    """The MiMo prefill's jaxpr at published widths (one layer of each
    kind, 16 held experts), at the cell's 2,048 packed rows with the
    kernels the chip takes and at the rehearsal's 128 with the forms the
    CPU takes, is the text PR 32 measured: PR 29's (which ``prefill_rows``
    moving to the family seam in PR 30 left alone) plus the expert
    counts it now returns and, in the 2,048-row trunk, the expert
    layer's ONE cond on the held assignments (``moe.held_row_bound``;
    the shorter trunks and the 128-row program hold none); since PR 34
    the 2,048-row text holds the packed kernel's new ``pallas_call`` (a
    grid of live block pairs, a group of heads a step, inside a ``jit``
    of its own), the 128-row ``jnp`` text is as it was. Source
    positions and object addresses are cut out, as in
    ``test_decode_attention_mosaic``'s digest of the decode program. A
    PR that changes MiMo's prefill on purpose records the new digests."""
    from apex_tpu.serving import mimo

    slots, ps, pages = 64, 128, 192
    cfg = mimo.MiMoConfig(
        vocab_size=2048, max_position_embeddings=1048576,
        hybrid_layer_pattern=(0, 1), moe_layer_freq=(0, 1),
        held_experts=(0, 16))
    params = jax.eval_shape(
        lambda: mimo.init_params(cfg, jax.random.PRNGKey(0)))
    cache = jax.eval_shape(lambda: mimo.init_cache(cfg, slots, pages, ps))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = str(jax.make_jaxpr(
        functools.partial(mimo.prefill, cfg=cfg, **kernels))(
            params, cache, i32(rows), i32(rows), i32(rows), i32(rows),
            i32(slots + 1, 3072 // ps), i32(slots)))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    text = re.sub(r"/[\w/\.\-]+\.py:\d+", "<src>", text)
    assert text.count("cond[") >= 1
    assert hashlib.sha256(text.encode()).hexdigest() == digest


# ----------------------------------- GPT-2's programs are the parent's text

@pytest.mark.parametrize("program,digest", [
    ("decode",
     "7659bf9e24a7d074d40d072816331943ceed1cc4e5c172864cada41a527a5228"),
    ("prefill",
     "28cff0de0006b221c77a33e8e6ade14298f0968455d27e29885d0e31d401b3de"),
])
def test_gpt2_programs_are_the_ones_before_the_latent_cache(program, digest):
    """PR 31 gave the cache a third kind of state (a latent row in a
    lane-padded leaf, written through ``kv_cache.write_latent_rows``)
    and the seam a third family; ``write_rows`` is the parent's, so both
    of GPT-2's programs' jaxprs at
    GPT-2 large's widths (two layers, the serve cell's geometry) are the
    text they were at PR 30's commit. Source positions and addresses cut
    out, as above. A PR that changes them on purpose records the new
    digests."""
    from apex_tpu.serving import kv_cache

    h, b, pages, ps, d, rows, layers = 20, 16, 96, 128, 64, 1024, 2
    cfg = TransformerConfig(
        hidden_size=h * d, num_layers=layers, num_attention_heads=h,
        vocab_size=50304, max_position_embeddings=1024, hidden_dropout=0.0,
        attention_dropout=0.0, apply_query_key_layer_scaling=False,
        bf16=True)
    params = jax.eval_shape(lambda: smodel.init_gpt_params(cfg, 0))
    cache = jax.eval_shape(
        lambda: kv_cache.init_cache(layers, h, pages, ps, d))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    if program == "decode":
        fn = functools.partial(smodel.decode_step, cfg=cfg,
                               decode_impl="pallas", interpret=False)
        args = (i32(b), i32(b), i32(b, 1024 // ps))
    else:
        fn = functools.partial(smodel.prefill, cfg=cfg)
        args = (i32(rows), i32(rows), i32(rows), i32(rows),
                i32(b + 1, 1024 // ps), i32(b))
    text = str(jax.make_jaxpr(fn)(params, cache, *args))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    text = re.sub(r"/[\w/\.\-]+\.py:\d+", "<src>", text)
    assert hashlib.sha256(text.encode()).hexdigest() == digest
