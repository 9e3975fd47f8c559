"""The Falcon-H1 family (serving/falcon_h1.py) against the plain reference
(perf/references/falcon_h1.py), and the reference against
``transformers``' own ``FalconH1ForCausalLM``: logits of a packed
prefill and then of decode steps through both kinds of state (the KV
pool and the state a slot owns), packed prompts against the same
prompts alone, a replayed (preempted) stream against the one never
preempted, the state's reach across 300 tokens, the seam's refusals.
Toy widths on seeded weights with the Mamba-2 initialisation of the
mixer's scalars (tests/falcon_h1_toy.py)."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.serving import ServingEngine
from apex_tpu.serving import falcon_h1 as fh
from apex_tpu.serving import family as family_mod
from apex_tpu.serving.scheduler import Request

import falcon_h1_toy as T

PS, SLOTS, ROWS = 8, 3, 64


def _f32_model(**changes):
    cfg = T.toy_config(cache_dtype="float32", **changes)
    params = T.toy_params(cfg)
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), params)
    return cfg, params


def _reference(cfg, params, ids, **kw):
    return T.reference.forward(cfg.to_dict(), params, ids, **kw)


def _pack(prompts, rows=ROWS, slots=SLOTS, pages_a_slot=16):
    """One packed dispatch of ``prompts`` (slot ``r`` the ``r``-th),
    the page table giving slot ``s`` pages ``1 + s * pages_a_slot ..``."""
    ids, positions, seg = (np.zeros(rows, np.int32) for _ in range(3))
    token_rows = np.full(rows, slots, np.int32)
    last = np.zeros(slots, np.int32)
    cursor = 0
    for r, p in enumerate(prompts):
        n = len(p)
        ids[cursor:cursor + n] = p
        positions[cursor:cursor + n] = np.arange(n)
        seg[cursor:cursor + n] = r + 1
        token_rows[cursor:cursor + n] = r
        last[r] = cursor + n - 1
        cursor += n
    table = np.zeros((slots + 1, pages_a_slot), np.int32)
    table[:slots] = 1 + np.arange(slots)[:, None] * pages_a_slot \
        + np.arange(pages_a_slot)[None, :]
    return tuple(map(jnp.asarray, (ids, positions, seg, token_rows, table,
                                   last)))


def _programs(cfg, **kw):
    pre = jax.jit(functools.partial(fh.prefill, cfg=cfg, **kw),
                  donate_argnums=(1,))
    dec = jax.jit(functools.partial(fh.decode_step, cfg=cfg, **kw),
                  donate_argnums=(1,))
    return pre, dec


def _cache(cfg, pages=1 + SLOTS * 16):
    return fh.init_cache(cfg, SLOTS, pages, PS, jnp.dtype(cfg.cache_dtype))


@pytest.fixture(scope="module")
def f32():
    return _f32_model()


# ------------------------------------------------- the published equations

def _hf_model(cfg, params):
    torch = pytest.importorskip("torch")
    pytest.importorskip("transformers.models.falcon_h1")
    from transformers.models.falcon_h1 import modeling_falcon_h1 as mf
    from transformers.models.falcon_h1.configuration_falcon_h1 import (
        FalconH1Config)

    hc = FalconH1Config(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        intermediate_size=cfg.intermediate_size,
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps,
        max_position_embeddings=cfg.max_position_embeddings,
        mamba_d_ssm=cfg.mamba_d_ssm, mamba_n_heads=cfg.mamba_n_heads,
        mamba_d_head=cfg.mamba_d_head, mamba_n_groups=cfg.mamba_n_groups,
        mamba_d_state=cfg.mamba_d_state, mamba_d_conv=cfg.mamba_d_conv,
        mamba_chunk_size=cfg.mamba_chunk_size, mamba_conv_bias=True,
        mamba_proj_bias=False, mamba_norm_before_gate=False,
        mamba_rms_norm=True, projectors_bias=False,
        rope_theta=cfg.rope_theta,
        lm_head_multiplier=cfg.lm_head_multiplier,
        embedding_multiplier=cfg.embedding_multiplier,
        mlp_multipliers=list(cfg.mlp_multipliers),
        key_multiplier=cfg.key_multiplier,
        attention_out_multiplier=cfg.attention_out_multiplier,
        attention_in_multiplier=cfg.attention_in_multiplier,
        ssm_multipliers=list(cfg.ssm_multipliers),
        ssm_in_multiplier=cfg.ssm_in_multiplier,
        ssm_out_multiplier=cfg.ssm_out_multiplier,
        attn_implementation="eager")
    torch.manual_seed(0)
    model = mf.FalconH1ForCausalLM(hc).float().eval()

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    sd = {"model.embed_tokens.weight": t(params["embed"]),
          "lm_head.weight": t(params["head"]),
          "model.final_layernorm.weight": t(params["final_norm"])}
    for i, lp in enumerate(params["layers"]):
        p = f"model.layers.{i}."
        sd.update({
            p + "input_layernorm.weight": t(lp["input_norm"]),
            p + "pre_ff_layernorm.weight": t(lp["ff_norm"]),
            p + "self_attn.q_proj.weight": t(lp["wq"]).T,
            p + "self_attn.k_proj.weight": t(lp["wk"]).T,
            p + "self_attn.v_proj.weight": t(lp["wv"]).T,
            p + "self_attn.o_proj.weight": t(lp["wo"]).T,
            p + "mamba.in_proj.weight": t(lp["w_in"]).T,
            p + "mamba.out_proj.weight": t(lp["w_out"]).T,
            p + "mamba.conv1d.weight": t(lp["conv_w"]).T[:, None, :],
            p + "mamba.conv1d.bias": t(lp["conv_b"]),
            p + "mamba.A_log": t(lp["A_log"]),
            p + "mamba.dt_bias": t(lp["dt_bias"]),
            p + "mamba.D": t(lp["D"]),
            p + "mamba.norm.weight": t(lp["gate_norm"]),
            p + "feed_forward.gate_proj.weight": t(lp["w_gate"]).T,
            p + "feed_forward.up_proj.weight": t(lp["w_up"]).T,
            p + "feed_forward.down_proj.weight": t(lp["w_down"]).T})
    missing, unexpected = model.load_state_dict(sd, strict=False)
    assert not missing and not unexpected, (missing, unexpected)
    return torch, model


def test_reference_matches_transformers_falcon_h1(f32):
    """The plain reference against ``FalconH1ForCausalLM``'s
    ``torch_forward`` (its chunked scan, chunks of 8) in float32 over 40
    tokens: the same logits to float32 rounding (2e-5 of their
    spread)."""
    cfg, params = f32
    torch, model = _hf_model(cfg, params)
    ids = np.random.RandomState(1).randint(0, cfg.vocab_size, 40)
    with torch.no_grad():
        want = model(input_ids=torch.tensor(ids)[None],
                     use_cache=False).logits[0].numpy()
    got, _ = _reference(cfg, params, ids)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0,
                               atol=2e-5 * np.abs(want).max())


# ------------------------------------------- the programs, through the cache

def test_prefill_then_decode_through_the_cache_matches_the_reference(f32):
    """Three prompts packed in one dispatch (boundaries inside chunks,
    one of 2 tokens: shorter than the conv), then 14 decode steps each
    fed the sequence's next token: every logit of the prefill's last
    rows and of each step equals the reference's full forward of the
    sequence, float32 both (1e-4 of the logits' spread)."""
    cfg, params = f32
    rng = np.random.RandomState(0)
    seqs = [list(rng.randint(0, cfg.vocab_size, n)) for n in (28, 30, 24)]
    prompts = [s[:n] for s, n in zip(seqs, (13, 2, 9))]
    pre, dec = _programs(cfg)
    ids, positions, seg, token_rows, table, last = _pack(prompts)
    cache, logits = pre(params, _cache(cfg), ids, positions, seg,
                        token_rows, table, last)
    got = {r: [np.asarray(logits[r])] for r in range(3)}
    lengths = np.asarray([len(p) for p in prompts])
    for _ in range(14):
        lengths = lengths + 1
        tokens = jnp.asarray([s[n - 1] for s, n in zip(seqs, lengths)],
                             jnp.int32)
        cache, _, logits = dec(params, cache, tokens,
                               jnp.asarray(lengths, jnp.int32), table[:SLOTS])
        for r in range(3):
            got[r].append(np.asarray(logits[r]))
    for r, (seq, p) in enumerate(zip(seqs, prompts)):
        want, _ = _reference(cfg, params, seq)
        want = np.asarray(want)[len(p) - 1:len(p) + 14]
        np.testing.assert_allclose(np.stack(got[r]), want, rtol=0,
                                   atol=1e-4 * np.abs(want).max())


def test_the_state_rows_are_the_references_state(f32):
    """What the prefill wrote into each slot's rows, every layer, is
    the reference's state and conv inputs after the prompt's last
    token; the spare row and the rows of slots without a prompt stay
    zero."""
    cfg, params = f32
    rng = np.random.RandomState(4)
    prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in (19, 3)]
    pre, _ = _programs(cfg)
    cache, _ = pre(params, _cache(cfg), *_pack(prompts))
    for r, p in enumerate(prompts):
        _, states = _reference(cfg, params, p, at=[len(p) - 1])
        for i, (S, conv) in enumerate(states):
            np.testing.assert_allclose(cache["ssm"][i][r], S[0], rtol=1e-4,
                                       atol=1e-5)
            np.testing.assert_allclose(cache["conv"][i][r],
                                       np.asarray(conv[0]).reshape(-1),
                                       rtol=1e-5,
                                       atol=1e-6)
    for i in range(cfg.num_layers):
        assert not np.asarray(cache["ssm"][i][2:]).any()
        assert not np.asarray(cache["conv"][i][2:]).any()


def test_decoded_state_rows_are_the_references_as_the_published_code_serves():
    """A bfloat16 state (the published cache's dtype), float32 weights:
    after a prefill and 40 decode steps the rows the program stored are
    the reference's as the published code serves (``cache_from``: the
    state stored in the cache's dtype from the prompt's end on) to
    bfloat16 rounding, every layer."""
    cfg, params = _f32_model()
    cfg = T.toy_config(cache_dtype="bfloat16")
    rng = np.random.RandomState(8)
    seq = list(rng.randint(0, cfg.vocab_size, 60))
    n_p = 20
    pre, dec = _programs(cfg)
    ids, positions, seg, token_rows, table, last = _pack([seq[:n_p]])
    cache, _ = pre(params, _cache(cfg), ids, positions, seg, token_rows,
                   table, last)
    for n in range(n_p + 1, n_p + 41):
        cache, _, _ = dec(params, cache,
                          jnp.asarray([seq[n - 1], 0, 0], jnp.int32),
                          jnp.asarray([n, 0, 0], jnp.int32), table[:SLOTS])
    _, states = _reference(cfg, params, seq[:n_p + 40], at=[n_p + 39],
                           cache_from=n_p)
    for i, (S, conv) in enumerate(states):
        got = np.asarray(cache["ssm"][i][0], np.float32)
        assert np.linalg.norm(got - S[0]) <= 1e-2 * np.linalg.norm(S[0]), i
        np.testing.assert_allclose(
            np.asarray(cache["conv"][i][0], np.float32),
            np.asarray(conv[0]).reshape(-1), rtol=1e-2, atol=1e-3)


def test_packed_prompts_equal_the_same_prompts_alone(f32):
    """Logits and state rows of prompts packed together (boundaries
    inside chunks) equal those of each prompt prefilled alone: no conv
    input, decay, carry or final state crosses a segment."""
    cfg, params = f32
    rng = np.random.RandomState(5)
    prompts = [list(rng.randint(0, cfg.vocab_size, n)) for n in (11, 1, 21)]
    pre, _ = _programs(cfg)
    cache, logits = pre(params, _cache(cfg), *_pack(prompts))
    for r, p in enumerate(prompts):
        ids, positions, seg, token_rows, table, last = _pack([p])
        token_rows = jnp.where(seg > 0, r, SLOTS)
        alone, alone_logits = pre(params, _cache(cfg), ids, positions, seg,
                                  token_rows, table, last)
        np.testing.assert_allclose(logits[r], alone_logits[0], rtol=1e-5,
                                   atol=1e-5)
        for i in range(cfg.num_layers):
            np.testing.assert_allclose(cache["ssm"][i][r], alone["ssm"][i][r],
                                       rtol=1e-5, atol=1e-6)
            np.testing.assert_allclose(cache["conv"][i][r],
                                       alone["conv"][i][r], rtol=1e-5,
                                       atol=1e-6)


def test_the_state_carries_token_zero_300_tokens_on(f32):
    """With the attention heads' output multiplied by 0, the only way
    from token 0 to position 300 is the state (the conv reaches 3
    tokens): changing token 0 moves the logits there by more than five
    times the tolerance the engine is held to the reference with (1e-4
    of their spread), through the prefill's scan and 40 decode steps
    after it."""
    cfg, params = _f32_model(attention_out_multiplier=0.0)
    # every mixer head at the slow corner of the Mamba-2
    # initialisation's range (A = 1, softplus(dt_bias) = 0.001): it
    # keeps exp(-0.3) of a token after 300; no skip (D = 0) and no conv
    # bias, so that y is the state's alone and its inputs vary by token,
    # and those inputs (the xBC columns of w_in) 30 times larger, so that
    # y clears the gated norm's epsilon
    d = cfg.d_ssm
    for lp in params["layers"]:
        lp["w_in"] = lp["w_in"].at[:, d:d + cfg.conv_dim].multiply(30.0)
        lp["A_log"] = jnp.zeros_like(lp["A_log"])
        lp["dt_bias"] = jnp.full_like(lp["dt_bias"], np.log(np.expm1(1e-3)))
        lp["D"] = jnp.zeros_like(lp["D"])
        lp["conv_b"] = jnp.zeros_like(lp["conv_b"])
    rng = np.random.RandomState(6)
    seq = list(rng.randint(1, cfg.vocab_size, 301))
    pre, dec = _programs(cfg)
    rows = 320

    def last_logits(first):
        s = [first] + seq[1:]
        cache, logits = pre(params, _cache(cfg, pages=1 + SLOTS * 48),
                            *_pack([s[:261]], rows=rows, pages_a_slot=48))
        table = _pack([s], rows=rows, pages_a_slot=48)[4]
        for n in range(262, 302):
            cache, _, logits = dec(
                params, cache, jnp.asarray([s[n - 1], 0, 0], jnp.int32),
                jnp.asarray([n, 0, 0], jnp.int32), table[:SLOTS])
        return np.asarray(logits[0])

    a, b = last_logits(seq[0]), last_logits((seq[0] + 7) % cfg.vocab_size)
    assert np.abs(a - b).max() > 5 * 1e-4 * np.abs(a).max()


def test_the_engine_serves_the_reference_and_replays_a_preempted_stream():
    """Through ``ServingEngine`` (bf16 weights, float32 state): every
    emitted token is the reference's best at its position or within 1e-3
    of the logits' spread of it, and with a page pool too small for both
    streams' peaks (preemption on) the preempted stream, replayed
    through the prefill program, emits the never-preempted tokens."""
    cfg = T.toy_config(cache_dtype="float32")
    params = T.toy_params(cfg)
    rng = np.random.RandomState(7)
    spec = [(list(rng.randint(0, cfg.vocab_size, 13)), 18),
            (list(rng.randint(0, cfg.vocab_size, 6)), 21)]

    def serve(**kw):
        eng = ServingEngine(cfg, params=params, num_slots=2, page_size=PS,
                            max_seq=40, prefill_len=32, **kw)
        reqs = [Request(rid=i, prompt=p, max_new_tokens=m)
                for i, (p, m) in enumerate(spec)]
        eng.step(arrivals=reqs)
        while not all(r.done() for r in reqs):
            eng.step()
        return eng, [list(r.out_tokens) for r in reqs]

    _, roomy = serve(num_pages=12)
    eng, tight = serve(num_pages=6, preempt=True)
    assert eng.resilience.preempted >= 1, eng.resilience
    assert tight == roomy
    for (prompt, _), out in zip(spec, roomy):
        seq = prompt + out
        logits, _ = _reference(cfg, params, seq)
        logits = np.asarray(logits)[len(prompt) - 1:len(seq) - 1]
        chosen = np.take_along_axis(logits, np.asarray(out)[:, None], 1)[:, 0]
        assert (logits.max(1) - chosen).max() <= 1e-3 * np.abs(logits).max()


def test_the_engine_counts_what_the_state_layer_does():
    """``engine.round`` carries ``ssm_slots_live``, ``prefill.fetch``
    ``ssm_tokens`` and ``ssm_segments``, all from what the engine holds
    on the host."""
    from apex_tpu.telemetry import spans

    cfg = T.toy_config()
    eng = ServingEngine(cfg, params=T.toy_params(cfg), num_slots=2,
                        page_size=PS, num_pages=12, max_seq=40,
                        prefill_len=32)
    spans.clear()
    reqs = [Request(rid=0, prompt=[3, 4, 5], max_new_tokens=4),
            Request(rid=1, prompt=[6] * 9, max_new_tokens=3)]
    eng.step(arrivals=reqs)
    while not all(r.done() for r in reqs):
        eng.step()
    records = spans.snapshot()
    fetch = [r.attrs for r in records if r.name == "prefill.fetch"]
    assert fetch[0]["ssm_tokens"] == 12 and fetch[0]["ssm_segments"] == 2
    live = [r.attrs["ssm_slots_live"] for r in records
            if r.name == "engine.round"]
    assert live and max(live) == 2


# ---------------------------------------------------------------- the seam

@pytest.mark.parametrize("option,value", [
    ("tp", 2), ("weight_quant", True), ("kv_quant", True),
    ("kv_swap", True), ("prefix_cache", True), ("spec_decode", 2),
    ("decode_k", 2), ("overlap", True)])
def test_falcon_h1_family_refuses_by_name_what_it_cannot_honour(option,
                                                                 value):
    cfg = T.toy_config()
    with pytest.raises(ValueError, match=f"falcon_h1 .*{option}="):
        ServingEngine(cfg, params={}, num_slots=2, page_size=4,
                      num_pages=8, max_seq=16, prefill_len=8,
                      **{option: value})


def test_the_family_follows_the_model_type_and_reads_the_cell_config():
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), os.pardir, "perf",
                        "configs", "falcon-h1-34b-pp12.json")
    with open(path) as fh_:
        d = json.load(fh_)
    d.pop("rehearse")
    cfg = family_mod.config_from_dict(d)
    assert isinstance(cfg, fh.FalconH1Config)
    assert family_mod.family_of(cfg).name == "falcon_h1"
    fh.check_config(cfg)
    assert (cfg.d_ssm, cfg.conv_dim, cfg.num_layers) == (4096, 5120, 6)
    assert fh.mup_vector(cfg).shape == (9248,)
    fam = family_mod.family_of(cfg)
    assert set(fam.refused) == set(family_mod.OPTIONS_OFF)


@pytest.mark.parametrize("changes,said", [
    (dict(attn_layer_indices=(0,)), "attn_layer_indices"),
    (dict(mamba_norm_before_gate=True), "mixer norm"),
    (dict(mamba_d_ssm=24), "mamba_d_ssm"),
    (dict(mamba_n_groups=3), "mamba_n_groups"),
    (dict(rope_scaling={"type": "linear"}), "rope_scaling")])
def test_check_config_names_what_the_programs_do_not_model(changes, said):
    with pytest.raises(ValueError, match=said):
        fh.check_config(T.toy_config(**changes))


def test_the_decode_program_aliases_the_state_leaves(f32):
    """Donated to the decode program, every state and conv leaf comes
    back as the same buffer (the compiled module aliases it)."""
    cfg, params = f32
    cache = _cache(cfg)
    compiled = jax.jit(functools.partial(fh.decode_step, cfg=cfg),
                       donate_argnums=(1,)).lower(
        params, cache, jnp.zeros(SLOTS, jnp.int32),
        jnp.zeros(SLOTS, jnp.int32),
        jnp.zeros((SLOTS, 16), jnp.int32)).compile()
    leaves = len(jax.tree_util.tree_leaves(params))
    aliased = compiled.as_text().split("input_output_alias={", 1)[1] \
        .split("}, entry", 1)[0]
    n_cache = len(jax.tree_util.tree_leaves(cache))
    for j in range(n_cache):
        assert f"({leaves + j}, {{}}" in aliased, (j, aliased)
