"""Pure prefill / decode-step functions over the GPTModel param tree:
the GPT-2 family of the serving engine (``serving/family.py`` is the
seam; the MiMo family, with grouped-query attention, window layers and
held experts, is ``serving/mimo.py``).

The serving forward consumes the EXACT parameter tree
``GPTModel.init`` produces (standalone_transformer_lm.py — flagship
model; weights move from training to serving with no conversion), and
mirrors its numerics op-for-op: fp32 layer-norm statistics
(normalization/fused_layer_norm.py jnp path), ``x @ W^T`` matmuls with
fp32 accumulation cast back to the compute dtype
(tensor_parallel/layers.py ``_mm``), the per-head ``[q|k|v]``
interleaving of the fused qkv projection, approximate-gelu MLP, and
tied logits against the word table (``parallel_lm_logits``). Parity
with ``GPTModel.apply`` is asserted in tests/test_serving.py — the
serving stack's numbers are the training stack's numbers. The engine
hands the programs :func:`serving_params` of that tree (the matmul
weights in the compute dtype, cast once at build); they take the tree
itself too and then cast in every step, to the same operands.

Two jitted programs (built once per engine — the ISSUE 10
jaxpr-stability contract):

* :func:`prefill` — one packed varlen prompt batch ``[S_pack]`` with
  segment ids (exactly the fmha-style packed shape the CP satellite
  opens up): causal + segment-masked attention via ``fused_attention``,
  every token's K/V scattered into its request's cache pages (pure
  index arithmetic — page/offset computed from the page table), and
  the next-token logits gathered at each request's last prompt token.
* :func:`decode_step` — one token per active slot over the paged
  cache: append K/V at ``length-1``, attend through the dispatched
  decode-attention family (ops/decode_attention_pallas.py), greedy
  next token. Decode matmuls optionally run int8-quantized weights
  (``apex_tpu.serving.quant`` — knob-gated, default OFF).

Constraints of THIS family (validated by :func:`check_serving_config`):
no dropout, no query-key layer scaling (its coeff is a training-range
trick; minimal.py disables it for the same uniformity reason), single
chip (tp=1 param shapes), no sequence/context parallelism, and the
dense GPT-2 MLP only: a ``TransformerConfig`` with ``num_moe_experts``
is the trainer's Switch block, which has no serving program (the engine
as a whole serves experts through the MiMo family).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.dispatch import tiles as _tiles
from apex_tpu.serving import kv_cache as kv_cache_mod
from apex_tpu.serving import kv_tier as kv_tier_mod
from apex_tpu.serving import quant as quant_mod
from apex_tpu.serving import sampling as sampling_mod
from apex_tpu.serving.family import prefill_rows


def check_serving_config(cfg):
    """Raise on TransformerConfig options the serving forward does not
    model (explicit refusal beats silent numeric drift)."""
    problems = []
    if cfg.hidden_dropout or cfg.attention_dropout:
        problems.append("dropout > 0 (serving is deterministic)")
    if cfg.apply_query_key_layer_scaling:
        problems.append("apply_query_key_layer_scaling (training-range "
                        "trick; set False like minimal.py)")
    if cfg.num_moe_experts:
        problems.append("num_moe_experts (the trainer's Switch block has "
                        "no serving program in the gpt2 family; held "
                        "experts are served by serving/mimo.py)")
    if cfg.sequence_parallel or cfg.context_parallel_axis:
        problems.append("sequence/context parallelism (single-chip "
                        "serving engine)")
    if problems:
        raise ValueError("serving does not support: "
                         + "; ".join(problems))


def compute_dtype(cfg):
    return jnp.bfloat16 if cfg.bf16 else (
        jnp.float16 if cfg.fp16 else jnp.float32)


def init_gpt_params(cfg, seed=0):
    """GPTModel.init on a 1-device TENSOR_AXIS mesh (the lax.axis_size
    calls inside the model need the axis bound) — the serving param
    source when no trained checkpoint is supplied."""
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.transformer.parallel_state import TENSOR_AXIS
    from apex_tpu.transformer.testing import GPTModel

    model = GPTModel(cfg)
    mesh = Mesh(np.asarray(jax.devices()[:1]), (TENSOR_AXIS,))
    b, s = 1, min(8, cfg.max_position_embeddings)
    ids = jnp.zeros((b, s), jnp.int32)
    pos = jnp.zeros((b, s), jnp.int32)

    def init(ids, pos):
        return model.init(jax.random.PRNGKey(seed), ids, pos,
                          None)["params"]

    return jax.jit(jax.shard_map(
        init, mesh=mesh, in_specs=(P(), P()), out_specs=P(),
        check_vma=False))(ids, pos)


# the four projections of ``_trunk_layer``: their weights and biases are
# what :func:`serving_params` casts
_MATMULS = frozenset(
    ("query_key_value", "dense", "dense_h_to_4h", "dense_4h_to_h"))


@functools.partial(jax.jit, static_argnums=1)
def _cast(leaves, dtype):
    return [leaf.astype(dtype) for leaf in leaves]


def serving_params(params, cfg):
    """The tree the prefill and decode programs read, built once: the
    caller's ``params`` with the layers' matmul weights and biases in
    the compute dtype, and a compute-dtype copy of the tied word table
    under ``"word_logits"`` for the logits matmul. ``_mm`` feeds the MXU
    the compute-dtype rounding of an fp32 weight either way; cast here,
    a step streams half the bytes and the numbers are the same, bit for
    bit. The embedding gather, the position table and the norms keep
    reading the caller's arrays, as before.

    A leaf already in the compute dtype is served as handed (a bf16
    checkpoint; an fp32 config serves ``params`` itself). The casts are
    ONE jitted program over the leaves, each copy keeping its leaf's
    sharding (``tp > 1`` places ``params`` first)."""
    dtype = jnp.dtype(compute_dtype(cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    picked = [i for i, (path, leaf) in enumerate(flat)
              if leaf.dtype != dtype
              and _MATMULS & {getattr(k, "key", None) for k in path}]
    word = params["word_embeddings"]
    cast_word = word.dtype != dtype
    if not picked and not cast_word:
        return params
    cast = _cast([flat[i][1] for i in picked]
                 + ([word] if cast_word else []), dtype)
    leaves = [leaf for _, leaf in flat]
    for i, leaf in zip(picked, cast):
        leaves[i] = leaf
    served = dict(jax.tree_util.tree_unflatten(treedef, leaves))
    if cast_word:
        served["word_logits"] = cast[-1]
    return served


def _mm(x, w, dtype):
    """x @ w^T, fp32 accumulation (the layers.py `_mm` idiom)."""
    x = x.astype(dtype)
    with jax.named_scope("weights_cast"):
        # a no-op on the served tree (:func:`serving_params`); the
        # caller's fp32 tree is cast here in every step
        w = w.astype(dtype)
    return lax.dot_general(
        x, w,
        (((x.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(dtype)


def _layer_norm(x, p, eps):
    """fp32-stats LN (fused_layer_norm's jnp path, op-for-op)."""
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    y = (xf - mean) * lax.rsqrt(var + eps)
    y = y * p["weight"].astype(jnp.float32) \
        + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def _split_qkv(qkv, n_heads, hd):
    """[rows, 3*proj] -> (q, k, v) each [rows, n_heads, hd] with the
    per-head [q|k|v] interleaving of ParallelAttention's fused
    projection (reshape to [rows, np, 3*hd], split on the last axis)."""
    rows = qkv.shape[0]
    qkv = qkv.reshape(rows, n_heads, 3 * hd)
    return (qkv[..., :hd], qkv[..., hd:2 * hd], qkv[..., 2 * hd:])


def quantize_decode_params(params, cfg):
    """The decode-side weight records: each matmul weight becomes
    ``{"wq", "scale"}`` (int8 + per-channel fp32); biases and norms
    stay full precision, and the word table keeps its float copy for
    the embedding GATHER (only the logits MATMUL runs the int8 copy —
    the gather reads one row per token, the matmul reads them all)."""
    qp = {"layers": [], "word_logits": None}
    for i in range(cfg.num_layers):
        lp = params["transformer"][f"layer_{i}"]
        rec = {}
        for name, sub in (("qkv", lp["self_attention"]["query_key_value"]),
                          ("dense", lp["self_attention"]["dense"]),
                          ("h4", lp["mlp"]["dense_h_to_4h"]),
                          ("4h", lp["mlp"]["dense_4h_to_h"])):
            wq, scale = quant_mod.quantize_weight(sub["weight"])
            rec[name] = {"wq": wq, "scale": scale}
        qp["layers"].append(rec)
    wq, scale = quant_mod.quantize_weight(params["word_embeddings"])
    qp["word_logits"] = {"wq": wq, "scale": scale}
    return qp


def _logits_table(params):
    """The tied table the logits matmul reads: the served tree's
    compute-dtype copy (:func:`serving_params`), else the word table."""
    return params.get("word_logits", params["word_embeddings"])


def _wmat(x, full_w, qrec, dtype):
    """One decode matmul: the int8 record when quantization resolved
    ON (qrec non-None), else the full-precision weight."""
    if qrec is not None:
        return quant_mod.qmatmul(x, qrec["wq"], qrec["scale"], dtype)
    return _mm(x, full_w, dtype)


def _trunk_layer(x, lp, qr, cfg, attn):
    """ONE transformer layer of the serving trunk — shared verbatim by
    prefill and decode so the two paths cannot drift numerically (the
    decode-vs-prefill parity the acceptance pins is a property of this
    function, applied twice). ``qr`` is the layer's int8 record dict
    ({} = full precision — ``_wmat`` with qrec None IS ``_mm``);
    ``attn(q, k, v)`` owns everything path-specific: the cache scatter
    for this layer's k/v and the attention itself, returning the
    ``[rows, n_heads*head_dim]`` context."""
    dtype = x.dtype
    # the scopes name each op's stretch of the layer in the device trace
    # (``tf_op``) and the compiled HLO; ``attn`` adds ``kv_write`` and
    # ``attend`` under ``layer/attn``
    with jax.named_scope("layer"):
        with jax.named_scope("attn"):
            sa = lp["self_attention"]
            with jax.named_scope("qkv"):
                ln1 = _layer_norm(x, lp["input_layernorm"],
                                  cfg.layernorm_epsilon)
                qkv = _wmat(ln1, sa["query_key_value"]["weight"],
                            qr.get("qkv"), dtype) \
                    + sa["query_key_value"]["bias"].astype(dtype)
                q, k, v = _split_qkv(qkv, cfg.num_attention_heads,
                                     cfg.head_dim)
            ctx = attn(q, k, v)
            with jax.named_scope("out"):
                attn_out = _wmat(ctx, sa["dense"]["weight"],
                                 qr.get("dense"), dtype) \
                    + sa["dense"]["bias"].astype(dtype)
                x = x + attn_out
        with jax.named_scope("mlp"):
            ln2 = _layer_norm(x, lp["post_attention_layernorm"],
                              cfg.layernorm_epsilon)
            mlp = lp["mlp"]
            inter = _wmat(ln2, mlp["dense_h_to_4h"]["weight"],
                          qr.get("h4"), dtype) \
                + mlp["dense_h_to_4h"]["bias"].astype(dtype)
            inter = jax.nn.gelu(inter, approximate=True)
            out = _wmat(inter, mlp["dense_4h_to_h"]["weight"],
                        qr.get("4h"), dtype) \
                + mlp["dense_4h_to_h"]["bias"].astype(dtype)
            return x + out


# --------------------------------------------------------------- prefill

def trunk_rows(S):
    """The row counts the prefill program's trunk can run on for ``S``
    packed rows (``family.prefill_rows``): where ``fused_attention``
    takes its flash kernel at ``S`` every count is a length it takes it
    at too (whole 128-row blocks), so no count falls to the dense
    path."""
    from apex_tpu.ops.attention import flash_supported

    return prefill_rows(S, 128 if flash_supported(S, S) else 8)


def prefill(params, cache, ids, positions, seg, token_rows, page_table,
            last_idx, keep_scale=None, *, cfg):
    """One packed prompt batch through the trunk, filling the cache.

    ids/positions/seg/token_rows: ``[S_pack]`` — token values, their
    within-request positions, segment ids (0 = padding, 1..R real),
    and each token's row into ``page_table`` (padding rows point at
    the all-null spare row). A batch's tokens lie FIRST, the padding
    behind them. page_table: ``[R_rows, max_pages]``.
    last_idx: ``[G]`` flat pack indices to gather logits at (inactive
    entries 0 — callers mask; every entry lies below the batch's
    token count). Plain prefill gathers one index per
    request (its last prompt token); the SPECULATIVE VERIFY dispatch
    of this same program (ISSUE 13) gathers K+1 indices per request —
    the pending-token + draft positions whose greedy chain decides
    acceptance. Returns ``(cache, logits [G, vocab])``.

    keep_scale: ``[num_pages]`` float (1 = the page already holds live
    rows whose scale must survive, 0 = fresh or null) — required by
    and only consumed on the int8 KV tier (``kv_tier.is_quantized``),
    where the scatter routes through the quantize-at-write codec.

    The trunk (embedding, every layer, final norm) runs on the first
    ``R`` of the ``S`` packed rows, ``R`` the smallest of
    :func:`trunk_rows` that holds the batch's tokens: a ``lax.switch``
    on the count of ``seg > 0`` inside the ONE program, so a short
    batch does not pay for a full dispatch. The rows behind the tokens
    are padding: no token attends to them (segment 0), no ``last_idx``
    gathers them and their K/V lands on the null page, so the logits
    and every live page do not depend on ``R``. Each layer's K and V
    rows leave the branch padded back to ``S`` and are written behind
    the switch (:func:`_write_behind`), so no branch carries a cache: a
    ``cond`` that holds the 72 leaves copies each (PERF.md §5, PR 30)."""
    dtype = compute_dtype(cfg)
    hd, n_heads = cfg.head_dim, cfg.num_attention_heads
    ps = cache["k"][0].shape[1]
    S = ids.shape[0]

    if kv_tier_mod.is_quantized(cache) and keep_scale is None:
        raise ValueError(
            "prefill on a quantized cache needs the keep_scale row — "
            "requantizing without it would zero surviving pages")

    from apex_tpu.ops import fused_attention

    word = params["word_embeddings"]

    def trunk_on(R):
        # one jitted function a branch: its 36 calls are ONE trace and
        # one lowering (of the flash kernel too), not 36 of each
        @jax.jit
        def layer(x, lp, seg2):
            kv = []   # this layer's k and v rows, padded to S

            def attn(q, k, v):
                # packed causal+segment attention over the branch's
                # rows; the K/V rows go out to be written
                kv.extend(jnp.pad(a.reshape(R, n_heads * hd),
                                  ((0, S - R), (0, 0))) for a in (k, v))
                with jax.named_scope("attend"):
                    ctx = fused_attention(
                        q.transpose(1, 0, 2)[None],
                        k.transpose(1, 0, 2)[None],
                        v.transpose(1, 0, 2)[None], causal=True,
                        sm_scale=1.0 / math.sqrt(hd),
                        segment_ids=(seg2, seg2))
                    return ctx[0].transpose(1, 0, 2).reshape(
                        R, n_heads * hd)

            return _trunk_layer(x, lp, {}, cfg, attn), tuple(kv)

        def branch(ids, positions, seg):
            ids, positions = ids[:R], positions[:R]
            seg2 = seg[:R].astype(jnp.int32)[None, :]
            with jax.named_scope("embed"):
                x = jnp.take(word, ids, axis=0) \
                    + jnp.take(params["embedding"]["position_embeddings"],
                               positions, axis=0)
                x = x.astype(dtype)
            written = []   # every layer's (k, v), in layer order
            for i in range(cfg.num_layers):
                x, kv = layer(x, params["transformer"][f"layer_{i}"], seg2)
                written.append(kv)
            with jax.named_scope("final_norm"):
                x = _layer_norm(x, params["transformer"]["final_layernorm"],
                                cfg.layernorm_epsilon)
            with jax.named_scope("lm_head"):
                return jnp.take(x, last_idx, axis=0), written

        return branch

    rows = trunk_rows(S)
    tokens = jnp.sum((seg > 0).astype(jnp.int32))
    x_last, written = lax.switch(
        sum((tokens > R).astype(jnp.int32) for R in rows[:-1]),
        [trunk_on(R) for R in rows], ids, positions, seg)

    with jax.named_scope("embed"):
        dest_page = jnp.take_along_axis(
            token_rows_to_pages(page_table, token_rows),
            (positions // ps)[:, None], axis=1)[:, 0]
        dest_off = positions % ps
    # the scope the decode program's write has inside ``_trunk_layer``
    with jax.named_scope("layer"), jax.named_scope("attn"), \
            jax.named_scope("kv_write"):
        cache = _write_behind(cache, written, dest_page, dest_off, tokens,
                              keep_scale, rows[0])
    with jax.named_scope("lm_head"):
        logits = _mm(x_last, _logits_table(params), dtype)
    return cache, logits


def _write_behind(cache, written, page, off, tokens, keep_scale, chunk):
    """Every layer's K/V rows (``written``: a ``(k, v)`` of ``[S, H * d]``
    a layer) scattered into the paged cache at ``(page[t], off[t])`` —
    index arithmetic only. A scatter costs by its rows (GPT-2 large on a
    v5e: 1.4 ms for 128 rows into each of the 72 leaves, whatever the
    chunk; 1,024 rows at once were ~5 ms of every dispatch, PERF.md §5),
    so the rows are written ``chunk`` at a time and only the chunks that
    hold tokens: a loop of ``ceil(tokens / chunk)`` trips that carries
    the leaves in place. The int8 tier's quantize-at-write codec
    re-scales whole pages by what a dispatch writes to them, so it
    takes the ``S`` rows in one call."""
    cache = {name: list(leaves) for name, leaves in cache.items()}
    if kv_tier_mod.is_quantized(cache):
        n_heads = cache["k_scale"][0].shape[1]
        for i, kv in enumerate(written):
            for part, val in zip("kv", kv):
                cache = kv_tier_mod.prefill_scatter_quant(
                    cache, i, part, val.reshape(val.shape[0], n_heads, -1),
                    page, off, keep_scale)
        return cache

    def write_chunk(c, cache):
        cache = {name: list(leaves) for name, leaves in cache.items()}
        cut = lambda a: lax.dynamic_slice_in_dim(      # noqa: E731
            a, c * chunk, chunk)
        for i, (k, v) in enumerate(written):
            _write_rows(cache, i, cut(page), cut(off), cut(k), cut(v))
        return cache

    return lax.fori_loop(0, (tokens + chunk - 1) // chunk, write_chunk,
                         cache)


def _write_rows(cache, layer, page, off, k, v):
    """This layer's ``k``/``v`` (``[rows, H, d]`` or ``[rows, H * d]``)
    into its two leaves at ``(page[r], off[r])``, one row a token."""
    for part, val in (("k", k), ("v", v)):
        cache[part][layer] = kv_cache_mod.write_rows(
            cache[part][layer], page, off, val)


def token_rows_to_pages(page_table, token_rows):
    """[S, max_pages] per-token page-table rows (a gather; split out
    so the scatter line above stays readable)."""
    return jnp.take(page_table, token_rows, axis=0)


# ---------------------------------------------------------------- decode

def decode_step(params, cache, tokens, lengths, page_table, *, cfg,
                qparams=None, decode_impl=None, interpret=None):
    """One greedy decode step for every slot (q_len = 1).

    tokens/lengths: ``[B]`` — the token to process and the context
    length INCLUDING it (0 = inactive slot: its writes land on the
    null page, its logits/next token are zeros). page_table:
    ``[B, max_pages]``. Returns ``(cache, next_tokens [B],
    logits [B, vocab])``.

    ``qparams`` (from :func:`quantize_decode_params`) switches the
    decode matmuls to the int8 records; ``decode_impl`` rides per-call
    into the decode-attention family (None = the family's own rule: the
    Pallas kernel on a TPU where it supports the geometry, the jnp
    reference otherwise).
    """
    from apex_tpu.ops import decode_attention_pallas as dap

    dtype = compute_dtype(cfg)
    hd, n_heads = cfg.head_dim, cfg.num_attention_heads
    cache = {name: list(leaves) for name, leaves in cache.items()}
    ps = cache["k"][0].shape[1]
    B = tokens.shape[0]

    word = params["word_embeddings"]
    with jax.named_scope("embed"):
        active = lengths > 0
        positions = jnp.maximum(lengths - 1, 0)
        write_page = jnp.where(
            active,
            jnp.take_along_axis(page_table, (positions // ps)[:, None],
                                axis=1)[:, 0],
            0)
        write_off = jnp.where(active, positions % ps, 0)

        x = jnp.take(word, tokens, axis=0) \
            + jnp.take(params["embedding"]["position_embeddings"],
                       positions, axis=0)
        x = x.astype(dtype)

    ql = qparams["layers"] if qparams is not None else None
    quant = kv_tier_mod.is_quantized(cache)

    # one jitted function for every layer: its 36 calls are ONE trace and
    # one lowering (of the decode kernel too), not 36 of each; XLA
    # inlines the calls, so the compiled program is what it was
    @jax.jit
    def layer(x, lp, qr, leaves):
        one = {name: [leaf] for name, leaf in leaves.items()}

        def attn(q, k, v):
            # append this step's k/v rows at (page, offset) — the int8
            # tier rewrites the touched pages through the per-page RMW
            # codec — then paged decode attention over the layer's
            # leaves where they lie (quantized pages ride with their
            # per-(page, head) scales and take the jnp form)
            nonlocal one
            with jax.named_scope("kv_write"):
                if quant:
                    for part, val in (("k", k), ("v", v)):
                        one = kv_tier_mod.decode_scatter_quant(
                            one, 0, part, val, write_page, write_off)
                else:
                    _write_rows(one, 0, write_page, write_off, k, v)
            with jax.named_scope("attend"):
                scales = dict(k_scale=one["k_scale"][0],
                              v_scale=one["v_scale"][0]) if quant else {}
                ctx = dap.grouped_decode_attention(
                    q.astype(dtype), one["k"][0], one["v"][0],
                    page_table, lengths, n_kv=n_heads,
                    sm_scale=1.0 / math.sqrt(hd), impl=decode_impl,
                    interpret=interpret, **scales)
                return ctx.reshape(B, n_heads * hd).astype(dtype)

        x = _trunk_layer(x, lp, qr, cfg, attn)
        return x, {name: leaf for name, (leaf,) in one.items()}

    for i in range(cfg.num_layers):
        x, leaves = layer(x, params["transformer"][f"layer_{i}"],
                          ql[i] if ql is not None else {},
                          {name: cache[name][i] for name in cache})
        for name, leaf in leaves.items():
            cache[name][i] = leaf

    with jax.named_scope("final_norm"):
        x = _layer_norm(x, params["transformer"]["final_layernorm"],
                        cfg.layernorm_epsilon)
    with jax.named_scope("lm_head"):
        logits = _wmat(x, _logits_table(params),
                       qparams["word_logits"] if qparams is not None
                       else None, dtype)
    with jax.named_scope("sample"):
        next_tokens = jnp.where(
            active, jnp.argmax(logits.astype(jnp.float32), axis=-1)
            .astype(jnp.int32), 0)
    return cache, next_tokens, logits


# ---------------------------------------------- multi-token decode block


def resolve_decode_k(per_call=None):
    """Knob resolution for the multi-token decode block (ISSUE 17),
    per the CLAUDE.md asymmetry: the per-call ``decode_k=`` argument
    is a DEMAND — a bool, non-int or K < 1 raises; the
    ``APEX_SERVE_DECODE_K`` env value is a PREFERENCE through the
    one-home positive-int parser (garbage warns once and falls back).
    Default K=1 per the measured-dispatch rule — the single-step
    program stays the dispatched one until the ``serving_multitok``
    device A/B (PERF.md §2) lands."""
    if per_call is not None:
        if isinstance(per_call, bool) or not isinstance(per_call, int) \
                or per_call < 1:
            raise ValueError(
                f"decode_k= wants an int >= 1, got {per_call!r}")
        return per_call
    return _tiles.env_int("APEX_SERVE_DECODE_K") or 1


def decode_block(params, cache, tokens, lengths, page_table,
                 steps_budget, warm_tokens, warm_steps, lanes=None, *,
                 k, cfg, qparams=None, decode_impl=None, interpret=None):
    """K decode steps in ONE dispatch (ISSUE 17): a ``lax.scan`` over
    :func:`decode_step` with in-program per-slot stop detection, so a
    single device round trip amortizes the per-dispatch host cost
    across up to K tokens per slot.

    ``k`` is a STATIC program constant — at most a second
    compile-cache key next to the K=1 single-step program; every
    per-round quantity below is an array VALUE, so scheduler events
    (admit/evict/shed/preempt between blocks) never recompile. Per
    scanned step ``j`` (0-based):

    * a lane is LIVE while ``j < steps_budget[i]`` (its host-computed
      budget: warmup steps left + remaining token budget, capped at
      K) and its staged length is non-zero. A finished/empty lane's
      length is masked to 0 for the step, which routes its K/V write
      to the null page 0 and emits the pad token 0 — exactly
      :func:`decode_step`'s inactive-slot contract — and its length
      does not advance.
    * warmup steps (``j < warm_steps[i]`` — a prefix-hit prompt or a
      resumed stream's replay overflow) feed the next KNOWN token
      (``warm_tokens[j, i]``) as the following step's input instead
      of the model's emission; the emitted token is discarded
      host-side, mirroring the K=1 warmup loop.
    * sampling lanes (``lanes`` = the engine's staged ``(temps,
      top_ks, top_ps, keys, counters)`` arrays) fold the generation
      index INSIDE the scan: the draw for generation index g always
      uses ``fold_in(key, g)`` whatever K or the batch composition —
      per-step counters are ``counters + max(0, j - warm_steps)``, so
      a seeded request's stream is pinned identical to the K=1
      engine's (the per-slot-RNG determinism test, now under K).

    tokens/lengths: ``[B]`` staged exactly as for :func:`decode_step`;
    steps_budget/warm_steps: ``[B]`` int32; warm_tokens: ``[K, B]``
    int32. Returns ``(cache, toks [K, B], logits [K, B, vocab])`` —
    row j holds step j's emissions (warmup/dead rows are discarded or
    pad by construction).
    """
    def body(carry, xs):
        cache, tok, lens = carry
        j, warm_j = xs
        live = (j < steps_budget) & (lens > 0)
        step_lens = jnp.where(live, lens, 0)
        cache, emitted, logits = decode_step(
            params, cache, tok, step_lens, page_table, cfg=cfg,
            qparams=qparams, decode_impl=decode_impl, interpret=interpret)
        if lanes is not None:
            temps, top_ks, top_ps, keys, counters = lanes
            ctr = counters + jnp.maximum(j - warm_steps, 0)
            emitted = sampling_mod.sample_tokens(
                logits, temps, top_ks, top_ps, keys, ctr, live)
        emitted = emitted.astype(jnp.int32)
        nxt = jnp.where(j < warm_steps, warm_j, emitted)
        lens = jnp.where(live, lens + 1, lens)
        return (cache, nxt, lens), (emitted, logits)

    xs = (jnp.arange(k, dtype=jnp.int32), warm_tokens)
    (cache, _, _), (toks, logits) = lax.scan(
        body, (cache, tokens, lengths), xs)
    return cache, toks, logits
