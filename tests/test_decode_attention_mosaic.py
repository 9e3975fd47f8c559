"""The TPU compiler's verdict on the paged decode-attention kernel at the
shapes the chip runs, with no chip: libtpu compiles for a described v5e
(``jax.experimental.topologies``). Interpret-mode parity is not a
compile verdict (two kernels passed it for ten PRs and never compiled),
and what XLA does AROUND the custom call decides the round as much as
the kernel: no serving program may copy a KV cache (four whole-cache
copies were 24 of a 36 ms decode round until PR 28).

One file, one process loads libtpu: the topology is described inside a
fixture, never at import."""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from apex_tpu.ops import decode_attention_pallas as dap


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    os.environ.setdefault("TPU_ACCELERATOR_TYPE", "v5litepod-4")
    os.environ.setdefault("TPU_WORKER_HOSTNAMES", "localhost")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize("h,b,pages", [
    (20, 16, 96),   # serve-large-batch (gpt2-large)
    (12, 8, 72),    # chip_smoke / GPT-2 small
], ids=["gpt2-large", "gpt2-small"])
def test_kernel_compiles_for_the_v5e_at_gpt2_widths(one_chip, h, b, pages):
    """``n_kv = h``, K and V 64 wide: the whole-page form (every head's
    banded query against a ``[128, h * 64]`` page, rows padded to a
    sublane tile)."""
    ps, d, max_pages = 128, 64, 8
    assert dap.grouped_supported(h, h, d, d, ps, jnp.bfloat16)

    def f(q, k, v, pt, ln):
        return dap.grouped_decode_attention(q, k, v, pt, ln, n_kv=h,
                                            impl="pallas", interpret=False)

    text = jax.jit(f).lower(
        _sds(one_chip, (b, h, d), jnp.bfloat16),
        _sds(one_chip, (pages, ps, h * d), jnp.bfloat16),
        _sds(one_chip, (pages, ps, h * d), jnp.bfloat16),
        _sds(one_chip, (b, max_pages), jnp.int32),
        _sds(one_chip, (b,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and dap.GROUPED_KERNEL_NAME in text


@pytest.mark.parametrize("program,layers", [("decode", 4), ("prefill", 36)])
def test_serve_cell_program_copies_no_cache_leaf(one_chip, monkeypatch,
                                                 program, layers):
    """GPT-2 large's widths, 16 slots, 96 pages of 128 tokens: no
    operation makes an array of a cache leaf's shape by ``copy``, every
    leaf is aliased input to output, the decode program (four layers of
    the 36) reaches the kernel in every layer and its temporaries stay
    far under a leaf's size (8.2 GB of them at full depth before PR
    28). The prefill program at FULL depth and with the attention kernel
    the chip takes: a ``cond`` that carries the cache copies no leaf at
    four layers and every leaf five times at 36 (PR 30), so its four
    trunks carry none, the write behind them is a loop that holds the
    leaves in place, no leaf goes through VMEM and back (``copy-done``),
    and the temporaries stay near the K/V rows that leave the switch."""
    from apex_tpu.ops import attention
    from apex_tpu.serving import kv_cache
    from apex_tpu.serving import model as smodel
    from apex_tpu.transformer.testing import TransformerConfig

    h, b, pages, ps, d, S = 20, 16, 96, 128, 64, 1024
    cfg = TransformerConfig(
        hidden_size=h * d, num_layers=layers, num_attention_heads=h,
        vocab_size=50304, max_position_embeddings=1024,
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=True)

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = sds(jax.eval_shape(lambda: smodel.init_gpt_params(cfg, 0)))
    cache = sds(jax.eval_shape(
        lambda: kv_cache.init_cache(layers, h, pages, ps, d)))
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)

    if program == "decode":
        def fn(params, cache, tokens, lengths, page_table):
            return smodel.decode_step(params, cache, tokens, lengths,
                                      page_table, cfg=cfg,
                                      decode_impl="pallas", interpret=False)
        args = (i32(b), i32(b), i32(b, 1024 // ps))
    else:
        # the host platform is the CPU: steer ``fused_attention`` onto
        # the flash kernel the chip takes at these lengths
        monkeypatch.setattr(attention, "_tpu_available", lambda: True)
        assert smodel.trunk_rows(S) == (128, 256, 512, 1024)

        def fn(params, cache, ids, positions, seg, rows, page_table, last):
            return smodel.prefill(params, cache, ids, positions, seg, rows,
                                  page_table, last, cfg=cfg)
        args = (i32(S), i32(S), i32(S), i32(S), i32(b + 1, 1024 // ps),
                i32(b))

    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    text = compiled.as_text()
    leaf = rf"bf16\[(?:{pages},{ps}|{pages * ps}),{h * d}\]"
    made = re.findall(rf"= {leaf}\S* ([\w-]+)\(", text)
    assert made and "copy" not in made, sorted(set(made))
    mem = compiled.memory_analysis()
    leaf_bytes = pages * ps * h * d * 2
    assert mem.alias_size_in_bytes >= 2 * layers * leaf_bytes
    if program == "decode":
        assert text.count("tpu_custom_call") >= layers
        assert mem.temp_size_in_bytes < leaf_bytes
        # full depth is 36 layers: under 1 GB of temporaries there
        assert mem.temp_size_in_bytes * 36 / layers < 1e9
    else:
        assert len(re.findall(r" conditional\(", text)) == 1
        assert text.count("tpu_custom_call") >= 4 * layers
        assert "copy-done" not in made, sorted(set(made))
        # 2 * 36 * [1024, 1280] bf16 of K/V rows out of the switch are
        # 0.19 GB; a second set of leaves would be 2.27
        assert mem.temp_size_in_bytes < 0.6e9


# ---- the MiMo family's kernels at the widths serve-mimo-decode runs ----

@pytest.mark.parametrize("n_kv,n,pages,sink", [
    (4, 24, 1536, False),   # a global layer: 4 KV heads, the paged pool
    (8, 2, 129, True),      # a window layer: 8 KV heads, rings of 2, sinks
], ids=["mimo-global", "mimo-window"])
def test_grouped_decode_kernel_compiles_for_the_v5e(one_chip, n_kv, n,
                                                    pages, sink):
    hq, dk, dv, ps, b = 64, 192, 128, 128, 64
    assert dap.grouped_supported(hq, n_kv, dk, dv, ps, jnp.bfloat16)

    def f(q, k, v, table, base, starts, lengths, *s):
        return dap.grouped_decode_attention(
            q, k, v, table, lengths, n_kv=n_kv, page_base=base,
            starts=starts, sink=s[0] if s else None, impl="pallas",
            interpret=False)

    args = [_sds(one_chip, (b, hq, dk), jnp.bfloat16),
            _sds(one_chip, (pages, ps, n_kv * dk), jnp.bfloat16),
            _sds(one_chip, (pages, ps, n_kv * dv), jnp.bfloat16),
            _sds(one_chip, (b, n), jnp.int32),
            _sds(one_chip, (b, n), jnp.int32),
            _sds(one_chip, (b,), jnp.int32), _sds(one_chip, (b,), jnp.int32)]
    if sink:
        args.append(_sds(one_chip, (hq,), jnp.float32))
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and dap.GROUPED_KERNEL_NAME in text


@pytest.mark.parametrize("n_kv,window,sink", [(4, None, False),
                                              (8, 128, True)],
                         ids=["mimo-global", "mimo-window"])
def test_packed_prefill_kernel_compiles_for_the_v5e(one_chip, n_kv, window,
                                                    sink):
    from apex_tpu.ops import attention_pallas as ap
    from apex_tpu.ops.attention import packed_gqa_attention

    hq, dk, dv, S = 64, 192, 128, 2048
    assert ap.packed_supported(S, dk, dv)

    def f(q, k, v, seg, *s):
        return packed_gqa_attention(q, k, v, seg, window=window,
                                    sink=s[0] if s else None,
                                    impl="pallas", interpret=False)

    args = [_sds(one_chip, (hq, S, dk), jnp.bfloat16),
            _sds(one_chip, (n_kv, S, dk), jnp.bfloat16),
            _sds(one_chip, (n_kv, S, dv), jnp.bfloat16),
            _sds(one_chip, (S,), jnp.int32)]
    if sink:
        args.append(_sds(one_chip, (hq,), jnp.float32))
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and ap.PACKED_KERNEL_NAME in text


def test_mimo_decode_program_compiles_with_no_copy_of_a_cache(one_chip):
    """Published widths, 64 slots, pages of 128, one layer of each kind
    with 16 held experts: the decode program reaches both attention
    kernels and the three grouped matmuls of an expert layer, and no
    array of a cache's shape is copied."""
    import functools

    from apex_tpu.serving import mimo

    slots, ps, pages = 64, 128, 192
    cfg = mimo.MiMoConfig(
        vocab_size=2048, max_position_embeddings=1048576,
        hybrid_layer_pattern=(0, 1), moe_layer_freq=(0, 1),
        held_experts=(0, 16))

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = sds(jax.eval_shape(
        lambda: mimo.init_params(cfg, jax.random.PRNGKey(0))))
    cache = sds(jax.eval_shape(
        lambda: mimo.init_cache(cfg, slots, pages, ps)))
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)
    decode = functools.partial(mimo.decode_step, cfg=cfg,
                               decode_impl="pallas", moe_impl="pallas",
                               interpret=False)
    text = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, i32(slots), i32(slots), i32(slots, 3072 // ps)
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 2 + 3
    cache_shapes = r"bf16\[(?:192|129),128,(?:768|512|1536|1024)\]"
    assert not re.findall(rf"= {cache_shapes}\S* copy\(", text)


def test_mimo_decode_program_is_the_one_pr27_traced():
    """The kernel's whole-page form (GPT-2's shapes, PR 28) is a branch
    on the static ``(group, dk, dv)``; for MiMo's geometry every branch
    taken is the one taken before it, so ``serve-mimo-decode`` cannot
    move: the decode program's jaxpr, kernels' bodies included, at
    published widths (one layer of each kind, 16 held experts) is the
    text it was at PR 27's commit. Source positions and object addresses
    are cut out: the lowered module's Mosaic payloads carry line numbers,
    which move with any edit of the file. A PR that changes MiMo's
    decode program on purpose records the new digest here."""
    import functools
    import hashlib

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
    decode = functools.partial(mimo.decode_step, cfg=cfg,
                               decode_impl="pallas", moe_impl="pallas",
                               interpret=False)
    text = str(jax.make_jaxpr(decode)(
        params, cache, i32(slots), i32(slots), i32(slots, 3072 // ps)))
    text = re.sub(r" at 0x[0-9a-f]+", "", text)
    text = re.sub(r"/[\w/\.\-]+\.py:\d+", "<src>", text)
    assert dap.GROUPED_KERNEL_NAME in text
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "1aa4a6e0d71a3002c5be3234d73b9c8be4de6b8e6b16a80f40e64bd0baa447ae")


# ---- the A.X-K1 family's kernels at the widths serve-axk1-longprompt runs ----

LATENT = dict(hq=64, rank=512, live=576, ps=128, slots=32, pages=1408,
              table=5120 // 128)


@pytest.mark.parametrize("width,copied", [(640, False), (576, True)],
                         ids=["padded-640", "unpadded-576"])
def test_latent_decode_kernel_compiles_and_only_a_padded_row_lies_still(
        one_chip, monkeypatch, width, copied):
    """A row scatter then the latent kernel over 1,408 pages of 128
    rows. With the row padded to 640 columns (whole lane tiles) the leaf
    is aliased and nothing of its shape is copied. With the 576 live
    columns alone the TPU keeps the array position-minor (576 is 4.5
    lane tiles, 128 is one) and copies the whole leaf to row-major for
    the scatter and the kernel and back: why ``kv_cache.
    init_latent_cache`` pads."""
    g = LATENT
    assert dap.latent_supported(g["hq"], width, g["rank"], g["ps"],
                                jnp.bfloat16) == (width % 128 == 0)
    # Mosaic itself takes a 576-column page; the rule refuses it for
    # what XLA does around the call
    monkeypatch.setattr(dap, "latent_supported", lambda *a, **k: True)

    def f(leaf, rows, page, off, q, table, lengths):
        leaf = leaf.at[page, off, :].set(rows)
        return leaf, dap.latent_decode_attention(
            q, leaf, table, lengths, rank=g["rank"], sm_scale=0.13,
            impl="pallas", interpret=False)

    b = g["slots"]
    compiled = jax.jit(f, donate_argnums=(0,)).lower(
        _sds(one_chip, (g["pages"], g["ps"], width), jnp.bfloat16),
        _sds(one_chip, (b, width), jnp.bfloat16),
        _sds(one_chip, (b,), jnp.int32), _sds(one_chip, (b,), jnp.int32),
        _sds(one_chip, (b, g["hq"], width), jnp.bfloat16),
        _sds(one_chip, (b, g["table"]), jnp.int32),
        _sds(one_chip, (b,), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and dap.LATENT_KERNEL_NAME in text
    made = re.findall(rf"= bf16\[{g['pages']},{g['ps']},{width}\]\S* "
                      rf"([\w-]+)\(", text)
    assert ("copy" in made) == copied, sorted(set(made))
    leaf_bytes = g["pages"] * g["ps"] * width * 2
    if copied:
        assert compiled.memory_analysis().temp_size_in_bytes >= leaf_bytes
    else:
        assert compiled.memory_analysis().temp_size_in_bytes < leaf_bytes / 8


def test_packed_prefill_kernel_compiles_with_a_kv_head_a_query_head(one_chip):
    """The expanded form of latent attention: as many KV heads as query
    heads, K 192 and V 128 wide, a 4,096-row pack."""
    from apex_tpu.ops import attention_pallas as ap
    from apex_tpu.ops.attention import packed_gqa_attention

    hq, dk, dv, S = 64, 192, 128, 4096
    assert ap.packed_supported(S, dk, dv)

    def f(q, k, v, seg):
        return packed_gqa_attention(q, k, v, seg, sm_scale=0.13087,
                                    impl="pallas", interpret=False)

    text = jax.jit(f).lower(
        _sds(one_chip, (hq, S, dk), jnp.bfloat16),
        _sds(one_chip, (hq, S, dk), jnp.bfloat16),
        _sds(one_chip, (hq, S, dv), jnp.bfloat16),
        _sds(one_chip, (S,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in text and ap.PACKED_KERNEL_NAME in text


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_axk1_program_compiles_with_no_copy_of_a_latent_leaf(one_chip,
                                                             program):
    """Published widths, the cell's 32 slots and 1,408 pages of 128,
    layer 0 (dense) and one expert layer with 12 held experts and the
    shared one: the decode program reaches the latent kernel in both
    layers and the three grouped matmuls (and holds no conditional: its
    expert layer takes every row), the prefill program (4,096 packed
    rows: four trunks behind one switch) the packed kernel; every
    leaf is aliased input to output and no array of a leaf's shape is
    copied."""
    import functools

    from apex_tpu.serving import axk1

    g = LATENT
    cfg = axk1.AXK1Config(vocab_size=2048, num_hidden_layers=2,
                          held_experts=(0, 12))

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = sds(jax.eval_shape(
        lambda: axk1.init_params(cfg, jax.random.PRNGKey(0))))
    cache = sds(jax.eval_shape(
        lambda: axk1.init_cache(cfg, g["pages"], g["ps"])))
    assert cache["latent"][0].shape == (g["pages"], g["ps"], 640)
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)
    b, S = g["slots"], 4096      # the cell's packed rows
    if program == "decode":
        fn = functools.partial(axk1.decode_step, cfg=cfg,
                               decode_impl="pallas", moe_impl="pallas",
                               interpret=False)
        args = (i32(b), i32(b), i32(b, g["table"]))
    else:
        fn = functools.partial(axk1.prefill, cfg=cfg, attn_impl="pallas",
                               moe_impl="pallas", interpret=False)
        args = (i32(S), i32(S), i32(S), i32(S), i32(b + 1, g["table"]),
                i32(b))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    text = compiled.as_text()
    made = re.findall(rf"= bf16\[{g['pages']},{g['ps']},640\]\S* "
                      rf"([\w-]+)\(", text)
    assert made and not {"copy", "copy-done"} & set(made), sorted(set(made))
    mem = compiled.memory_analysis()
    leaf_bytes = g["pages"] * g["ps"] * 640 * 2
    assert mem.alias_size_in_bytes >= 2 * leaf_bytes
    if program == "decode":
        assert text.count(dap.LATENT_KERNEL_NAME) >= 1
        assert text.count("tpu_custom_call") >= 2 + 3
        assert mem.temp_size_in_bytes < leaf_bytes / 4
        assert " conditional(" not in text
    else:
        # the trunk's switch, and in each trunk whose expert layer's row
        # bound engages (2,048 and 4,096 rows) that layer's ONE cond
        from apex_tpu.serving.family import prefill_rows
        from apex_tpu.transformer.moe import held_row_bound

        bounded = sum(held_row_bound(R, 8, 12, 192) < R * 8
                      for R in prefill_rows(S))
        assert bounded == 2
        assert len(re.findall(r" conditional\(", text)) == 1 + bounded
        assert text.count("tpu_custom_call") >= 4 * (2 + 3)


# ---- the dots3-note family's kernels at the widths serve-dots3-longcontext runs ----

SPARSE = dict(slots=16, pages=1280, ps=128, table=8704 // 128, rows=8192)


def test_selection_kernels_compile_for_the_v5e(one_chip):
    """The decode index kernel over 68 table entries of 128-wide keys,
    the latent kernel with ``starts`` over a ring of 6 pages of 1,152
    columns (rank 1,024, 64 heads) and over 2,048 gathered rows of 640
    (rank 512, 128 heads); the prefill index kernel (64 heads in a step)
    and the packed kernel under an int8 selection at 128 heads of 192 /
    128, 2,048 packed rows."""
    from apex_tpu.ops import attention as attn
    from apex_tpu.ops import attention_pallas as ap

    g, bf, f32, i32 = SPARSE, jnp.bfloat16, jnp.float32, jnp.int32
    b, n = g["slots"], g["table"]
    assert dap.index_supported(64, 128, g["ps"], bf)
    assert dap.latent_supported(64, 1152, 1024, g["ps"], bf)
    assert ap.index_scores_supported(g["rows"], 64, 128)

    def compiled(f, *args):
        return jax.jit(f).lower(*(_sds(one_chip, *a) for a in args)) \
            .compile().as_text()

    text = compiled(
        lambda q, w, p, t, ln: dap.index_decode_scores(
            q, w, p, t, ln, impl="pallas", interpret=False),
        ((b, 64, 128), bf), ((b, 64), f32), ((g["pages"], g["ps"], 128), bf),
        ((b, n), i32), ((b,), i32))
    assert dap.INDEX_KERNEL_NAME in text
    text = compiled(
        lambda q, p, t, ln, base, st: dap.latent_decode_attention(
            q, p, t, ln, rank=1024, sm_scale=0.06, page_base=base, starts=st,
            impl="pallas", interpret=False),
        ((b, 64, 1152), bf), ((1 + b * 6, g["ps"], 1152), bf), ((b, 6), i32),
        ((b,), i32), ((b, 6), i32), ((b,), i32))
    assert dap.LATENT_KERNEL_NAME in text
    text = compiled(
        lambda q, p, t, ln: dap.latent_decode_attention(
            q, p, t, ln, rank=512, sm_scale=0.07, impl="pallas",
            interpret=False),
        ((b, 128, 640), bf), ((b * 16, g["ps"], 640), bf), ((b, 16), i32),
        ((b,), i32))
    assert dap.LATENT_KERNEL_NAME in text
    S = 2048
    text = compiled(
        lambda q, w, k, s: attn.packed_index_scores(
            q, w, k, s, impl="pallas", interpret=False),
        ((64, S, 128), bf), ((S, 64), f32), ((S, 128), bf), ((S,), i32))
    assert ap.INDEX_SCORES_KERNEL_NAME in text
    text = compiled(
        lambda q, k, v, s, sel: attn.selected_attention(
            q, k, v, s, sel, sm_scale=0.07, impl="pallas", interpret=False),
        ((128, S, 192), bf), ((128, S, 192), bf), ((128, S, 128), bf),
        ((S,), i32), ((S, S), jnp.int8))
    assert ap.PACKED_KERNEL_NAME in text


@pytest.mark.parametrize("hq,dk,window,selected", [
    (128, 192, None, True), (64, 256, 513, False)],
    ids=["full-under-a-selection", "sliding-513"])
def test_packed_prefill_kernel_compiles_at_dots3s_8192_rows(
        one_chip, hq, dk, window, selected):
    """The cell's longest trunk, a KV head a query head, V 128 wide: the
    full layers' 128 heads of 192 under an int8 ``[S, S]`` selection (528
    live block pairs a group of heads) and the sliding layers' 64 heads
    of 256 under a window of 513 (93), at the heads a step the rule
    gives these widths."""
    from apex_tpu.ops import attention as attn
    from apex_tpu.ops import attention_pallas as ap

    S, dv, bf = SPARSE["rows"], 128, jnp.bfloat16
    heads = ap.packed_heads_a_step(hq, hq, 256, dk, dv, 2, selected)
    assert heads > 1 and ap.packed_grid_steps(
        S, hq, hq, dk, dv, window, selected) == (
            hq // heads * (528 if window is None else 93), hq * 32 * 32)

    def f(q, k, v, seg, *sel):
        if sel:
            return attn.selected_attention(q, k, v, seg, sel[0],
                                           sm_scale=0.07, impl="pallas",
                                           interpret=False)
        return attn.packed_gqa_attention(q, k, v, seg, sm_scale=0.06,
                                         window=window, impl="pallas",
                                         interpret=False)

    args = [_sds(one_chip, (hq, S, dk), bf), _sds(one_chip, (hq, S, dk), bf),
            _sds(one_chip, (hq, S, dv), bf), _sds(one_chip, (S,), jnp.int32)]
    if selected:
        args.append(_sds(one_chip, (S, S), jnp.int8))
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and ap.PACKED_KERNEL_NAME in text


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_dots3_program_compiles_with_no_copy_of_a_pool_leaf(
        one_chip, monkeypatch, program):
    """Published widths, the cell's 16 slots, 1,280 pages of 128 and 68
    table entries, one full layer (dense MLP) and one sliding layer (16
    held experts + the shared one): the decode program reaches the index
    kernel, the latent kernel twice (the gathered rows or the pool's
    pages under ONE cond; the ring) and the grouped matmuls; the prefill
    program (4,096 packed rows: four trunks behind one switch, of which
    the 4,096-row one alone is past ``index_topk`` and runs the indexer)
    the packed kernel in every trunk. The latent leaf (0.21 GB) and the index
    leaf (0.04 GB) of the pool are aliased input to output and no array
    of their shapes is copied; a ring leaf (0.03 GB) XLA may stage in
    its faster memory by itself (an async copy pair, seen at nine
    layers), which is not asserted on."""
    import functools

    from apex_tpu.ops import attention as attn
    from apex_tpu.serving import dots3

    monkeypatch.setattr(attn, "_tpu_available", lambda: True)
    g = SPARSE
    cfg = dots3.Dots3Config(vocab_size=2048,
                            layer_types=(dots3.FULL, dots3.SLIDING),
                            held_experts=(0, 16))

    def sds(tree):
        return jax.tree_util.tree_map(
            lambda a: _sds(one_chip, a.shape, a.dtype), tree)

    params = sds(jax.eval_shape(
        lambda: dots3.init_params(cfg, jax.random.PRNGKey(0))))
    cache = sds(jax.eval_shape(
        lambda: dots3.init_cache(cfg, g["slots"], g["pages"], g["ps"])))
    i32 = lambda *shape: _sds(one_chip, shape, jnp.int32)
    b, S = g["slots"], 4096
    if program == "decode":
        fn = functools.partial(dots3.decode_step, cfg=cfg,
                               decode_impl="pallas", moe_impl="pallas",
                               interpret=False)
        args = (i32(b), i32(b), i32(b, g["table"]))
    else:
        fn = functools.partial(dots3.prefill, cfg=cfg, attn_impl="pallas",
                               moe_impl="pallas", interpret=False)
        args = (i32(S), i32(S), i32(S), i32(S), i32(b + 1, g["table"]),
                i32(b))
    compiled = jax.jit(fn, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    text = compiled.as_text()
    for width in (640, 128):
        made = re.findall(rf"= bf16\[{g['pages']},{g['ps']},{width}\]\S* "
                          rf"([\w-]+)\(", text)
        assert made and not {"copy", "copy-done"} & set(made), \
            (width, sorted(set(made)))
    mem = compiled.memory_analysis()
    pool_bytes = g["pages"] * g["ps"] * (640 + 128) * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    if program == "decode":
        assert dap.INDEX_KERNEL_NAME in text
        assert text.count(dap.LATENT_KERNEL_NAME) >= 2
        assert len(re.findall(r" conditional\(", text)) == 1
        assert mem.temp_size_in_bytes < pool_bytes / 2
    else:
        from apex_tpu.ops import attention_pallas as ap

        # the 4,096-row trunk alone is past index_topk: one index kernel
        assert text.count(ap.INDEX_SCORES_KERNEL_NAME) >= 1
        assert text.count(ap.PACKED_KERNEL_NAME) >= 4 * 2
