"""The TPU compiler's verdict on the paged decode-attention kernel at the
shapes the chip runs, with no chip: libtpu compiles for a described v5e
(``jax.experimental.topologies``). Interpret-mode parity is not a
compile verdict (two kernels passed it for ten PRs and never compiled),
and what XLA does AROUND the custom call decides the round as much as
the kernel: the serve cell's decode program has to reach the kernel with
no copy of the cache beyond the four at entry and exit.

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


@pytest.mark.parametrize("h,b,pages,dtype", [
    (20, 16, 96, jnp.bfloat16),   # serve-large-batch (gpt2-large)
    (12, 8, 72, jnp.bfloat16),    # chip_smoke / GPT-2 small
    (12, 8, 72, jnp.int8),        # the int8 KV tier, one layer's arrays
], ids=["gpt2-large-bf16", "gpt2-small-bf16", "gpt2-small-int8"])
def test_kernel_compiles_for_the_v5e(one_chip, h, b, pages, dtype):
    ps, d, max_pages = 128, 64, 8
    quant = dtype == jnp.int8
    assert dap.supported(h, pages, ps, d, dtype)

    def f(q, k, v, pt, ln, *scales):
        kw = dict(k_scale=scales[0], v_scale=scales[1]) if quant else {}
        return dap.decode_attention(q, k, v, pt, ln, impl="pallas",
                                    interpret=False, **kw)

    args = [_sds(one_chip, (b, h, d), jnp.bfloat16),
            _sds(one_chip, (h, pages, ps, d), dtype),
            _sds(one_chip, (h, pages, ps, d), dtype),
            _sds(one_chip, (b, max_pages), jnp.int32),
            _sds(one_chip, (b,), jnp.int32)]
    if quant:
        args += [_sds(one_chip, (h, pages), jnp.bfloat16)] * 2
    text = jax.jit(f).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text and dap.KERNEL_NAME in text


def test_serve_cell_decode_program_feeds_the_kernel_without_a_copy(one_chip):
    """GPT-2 large's widths, 16 slots, 96 pages of 128 tokens, four
    layers of the 36: every layer's kernel takes a bitcast of the
    scattered cache, and the only whole-cache copies are the two
    arguments' at entry and the two results' at exit."""
    from apex_tpu.serving import kv_cache
    from apex_tpu.serving import model as smodel
    from apex_tpu.transformer.testing import TransformerConfig

    layers, h, b, pages, ps, d = 4, 20, 16, 96, 128, 64
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

    def decode(params, cache, tokens, lengths, page_table):
        return smodel.decode_step(params, cache, tokens, lengths,
                                  page_table, cfg=cfg, decode_impl="pallas",
                                  interpret=False)

    text = jax.jit(decode, donate_argnums=(1,)).lower(
        params, cache, i32(b), i32(b), i32(b, 1024 // ps)
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= layers
    whole = rf"bf16\[{layers},(?:{h},{pages},{ps}|{pages},{ps},{h}),{d}\]"
    made = re.findall(rf"= {whole}\S* ([\w-]+)\(", text)
    assert made.count("copy") == 4, sorted(set(made))
    assert made.count("bitcast") >= 2 * layers, sorted(set(made))
    # no per-layer slice of the cache is materialised for the kernel
    one_layer = rf"= bf16\[(?:{h},{pages},{ps}|{pages},{ps},{h}),{d}\]"
    assert not re.findall(one_layer, text)


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
