"""Standalone tensor/sequence-parallel GPT and BERT.

Capability port of apex/transformer/testing/standalone_transformer_lm.py
(1,574 LoC: embeddings, ParallelAttention :401, ParallelMLP :304,
ParallelTransformerLayer :709, ParallelTransformer :849, post-LM heads),
standalone_gpt.py:111 and standalone_bert.py. These are the reference's
test/benchmark models; here they are also the framework's flagship models.

TPU-first design notes:

  * hidden states keep Megatron's [s, b, h] layout so the sequence-parallel
    first-dim scatter/gather mappings apply unchanged;
  * attention is batched onto the MXU as [b*np, s, s] GEMMs in the amp
    compute dtype with fp32 accumulation (the reference's cublas strided
    batch GEMM + fused softmax kernel become two dot_generals + the ported
    FusedScaleMaskSoftmax, which XLA fuses);
  * weight tying (GPT logits against the word-embedding shard) is explicit
    dataflow — ``parallel_lm_logits(hidden, word_embedding_weight)`` — the
    functional form of Megatron's ``word_embeddings_weight()`` plumbing;
  * dropout uses flax's "dropout" rng collection; pass
    ``deterministic=True`` (default) for the reference's eval semantics and
    the analytic pipeline tests.

Run inside ``shard_map`` over the "tp" mesh axis (all parallel layers hold
local shards), optionally nested under "pp"/"dp" axes via the pipeline
schedules and DDP wrapper.
"""

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax import lax

from apex_tpu.normalization.fused_layer_norm import FusedLayerNorm
from apex_tpu.transformer.enums import AttnMaskType, AttnType, LayerType
from apex_tpu.transformer.functional import FusedScaleMaskSoftmax
from apex_tpu.transformer.parallel_state import TENSOR_AXIS
from apex_tpu.transformer.tensor_parallel import mappings
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    _sharded_init,
    vocab_parallel_embed,
)
from apex_tpu.transformer.utils import divide
from apex_tpu.utils import train_dropout


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    """One config dataclass replacing the reference's megatron argparse
    bundle (testing/arguments.py:23-337) for model-shape options."""

    hidden_size: int = 256
    num_layers: int = 2
    num_attention_heads: int = 8
    ffn_hidden_size: Optional[int] = None  # default 4*h
    vocab_size: int = 512
    max_position_embeddings: int = 512
    kv_channels: Optional[int] = None  # default h / heads
    layernorm_epsilon: float = 1e-5
    hidden_dropout: float = 0.1
    attention_dropout: float = 0.1
    apply_query_key_layer_scaling: bool = True
    attention_softmax_in_fp32: bool = False
    masked_softmax_fusion: bool = True
    # route the fused scale-mask-softmax (non-flash scores path) through
    # the Pallas kernel (ops/softmax_pallas.py) instead of the jnp path.
    # True/False pins; None (default) = unpinned — FusedScaleMaskSoftmax
    # consults the per-shape dispatch table (apex_tpu.dispatch), a miss
    # meaning the measured jnp default (PERF.md §4b)
    softmax_use_pallas: Optional[bool] = None
    # fuse the GPT LM head (logits matmul + vocab-parallel CE) into the
    # Pallas linear-cross-entropy kernel (ops/xent_pallas.py): the [n, V]
    # logits never reach HBM — at tp > 1 via the vocab-parallel variant
    # (per-shard online stats, pmax/psum combine; shard logits never
    # materialize either). Engages where the kernel applies (supported
    # shard shapes, no label smoothing, not tp>1+sequence_parallel);
    # falls back to the materialized path otherwise. _interpret is for
    # CPU tests. True/False pins; None (default) = unpinned — the head
    # consults the dispatch table (op "lm_head") at trace time, a miss
    # meaning the materialized path (the §10b measured default: fused
    # holds 63% of materialized throughput; its win is peak memory)
    fused_lm_head: Optional[bool] = None
    fused_lm_head_interpret: bool = False
    # training with attention_dropout > 0 (causal, no explicit mask):
    # route through the VMEM-rows kernel's in-kernel hash dropout instead
    # of the materialized-scores path. Default follows the committed
    # measurement (PERF.md §3: rows fwd+d(q,k,v) 1.82 ms vs XLA dense
    # 4.34 ms at GPT shape — the scores path additionally writes the
    # [b·h, s, s] probs to HBM); the in-kernel dropout delta rides the
    # queued device row (PERF.md §9). False restores the scores path.
    fused_attention_dropout: bool = True
    sequence_parallel: bool = False
    # context parallelism: mesh axis the SEQUENCE dim is sharded over for
    # the whole model (hidden states are [s/cp, b, h]); attention runs the
    # ring (ops.context_parallel.ring_attention) so every rank still sees
    # the full causal context. Orthogonal to tensor parallel.
    context_parallel_axis: Optional[str] = None
    # mixture of experts (reference surface: arguments.py --num-experts):
    # when set, every layer's MLP becomes an ExpertParallelMLP with this
    # many experts, optionally sharded over ``expert_parallel_axis``
    num_moe_experts: Optional[int] = None
    expert_parallel_axis: Optional[str] = None
    moe_capacity_factor: float = 1.25
    moe_top_k: int = 1
    # Switch aux-loss coefficient: trainers collect the sown
    # load_balancing_loss via mutable=["intermediates"] +
    # moe.collect_moe_aux and add coeff * aux to the objective
    moe_aux_loss_coeff: float = 1e-2
    # activation recompute (reference: --recompute-granularity full →
    # tensor_parallel.random.checkpoint per layer; here jax.checkpoint
    # around each transformer layer). "selective"/"full" pin remat on,
    # "none" pins it OFF; None (default) = unpinned — the trunk consults
    # the dispatch table (op "remat") at trace time, a miss meaning no
    # recompute (the built-in default)
    recompute_granularity: Optional[str] = None
    params_dtype: Any = jnp.float32
    fp16: bool = False
    bf16: bool = False
    init_method_std: float = 0.02
    # BERT extras
    bert_binary_head: bool = True

    @property
    def ffn_size(self):
        return self.ffn_hidden_size or 4 * self.hidden_size

    @property
    def head_dim(self):
        return self.kv_channels or divide(self.hidden_size,
                                          self.num_attention_heads)

    @property
    def compute_in_float16(self):
        return self.fp16 or self.bf16


def init_normal(std):
    return nn.initializers.normal(stddev=std)


def scaled_init_method_normal(sigma, num_layers):
    """Output-layer init scaled by 1/sqrt(2*num_layers) (reference:
    standalone_transformer_lm.py init helpers)."""
    return nn.initializers.normal(stddev=sigma / math.sqrt(2.0 * num_layers))


def init_method_normal(sigma):
    """N(0, sigma) initializer (reference parity name,
    standalone_transformer_lm.py:146; same object as ``init_normal``)."""
    return init_normal(sigma)


def get_linear_layer(rows, columns, init_method):
    """A plain Dense(rows→columns) with the given kernel init and zero
    bias (reference: standalone_transformer_lm.py:130-136)."""
    del rows  # flax infers the input width at first call
    return nn.Dense(columns, kernel_init=init_method,
                    bias_init=nn.initializers.zeros)


def get_num_layers(args, is_encoder_and_decoder_model,
                   pipeline_rank=0, before_split=True):
    """Transformer layers resident on one pipeline stage (reference:
    standalone_transformer_lm.py:1038-1096). The reference reads the
    stage index from the process's rank; in SPMD the caller passes the
    static ``pipeline_rank`` (and, for encoder-decoder models, whether
    that stage sits before the split) when building the per-stage
    program."""
    pp = args.pipeline_model_parallel_size
    if pp <= 1:
        return args.num_layers
    if is_encoder_and_decoder_model:
        assert args.pipeline_model_parallel_split_rank is not None
        # with a standalone embedding stage, the encoder loses one rank
        # to the embedding so the split rank keeps its meaning
        num_ranks_in_encoder = (
            args.pipeline_model_parallel_split_rank - 1
            if args.standalone_embedding_stage
            else args.pipeline_model_parallel_split_rank)
        num_ranks_in_decoder = (
            args.transformer_pipeline_model_parallel_size
            - num_ranks_in_encoder)
        assert args.num_layers % num_ranks_in_encoder == 0, (
            f"num_layers ({args.num_layers}) must be divisible by number "
            f"of ranks given to encoder ({num_ranks_in_encoder})")
        assert args.num_layers % num_ranks_in_decoder == 0, (
            f"num_layers ({args.num_layers}) must be divisible by number "
            f"of ranks given to decoder ({num_ranks_in_decoder})")
        if before_split:
            return (0 if args.standalone_embedding_stage
                    and pipeline_rank == 0
                    else args.num_layers // num_ranks_in_encoder)
        return args.num_layers // num_ranks_in_decoder
    assert (args.num_layers
            % args.transformer_pipeline_model_parallel_size == 0), (
        "num_layers must be divisible by "
        "transformer_pipeline_model_parallel_size")
    return (0 if args.standalone_embedding_stage and pipeline_rank == 0
            else args.num_layers
            // args.transformer_pipeline_model_parallel_size)


# ---------------------------------------------------------------------------
# functional logits (explicit weight tying; embedding core lives in
# tensor_parallel.layers.vocab_parallel_embed)
# ---------------------------------------------------------------------------

def parallel_lm_logits(hidden, word_embeddings_weight, parallel_output=True,
                       bias=None, sequence_parallel=False,
                       axis_name=TENSOR_AXIS):
    """LM logits against the (vocab-sharded) embedding weight (reference:
    standalone_transformer_lm.py post_language_model_processing /
    megatron parallel_lm_logits). Column-parallel over vocab: each rank
    computes its vocab slice; ``parallel_output=False`` gathers."""
    if sequence_parallel:
        hidden = mappings.gather_from_sequence_parallel_region(
            hidden, axis_name, True)
    else:
        hidden = mappings.copy_to_tensor_model_parallel_region(
            hidden, axis_name)
    w = word_embeddings_weight.astype(hidden.dtype)
    logits = lax.dot_general(
        hidden, w, (((hidden.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32).astype(hidden.dtype)
    if bias is not None:
        logits = logits + bias.astype(logits.dtype)
    if not parallel_output:
        logits = mappings.gather_from_tensor_model_parallel_region(
            logits, axis_name)
    return logits


# ---------------------------------------------------------------------------
# transformer blocks
# ---------------------------------------------------------------------------

class MoEMLP(nn.Module):
    """MoE drop-in for ParallelMLP: flattens [s, b, h] to tokens, routes
    through transformer.moe.ExpertParallelMLP (expert ffn dims tp-sharded
    over ``axis_name``), returns (out, zero-bias) so the layer's
    bias_dropout_add is unchanged. The sown load_balancing_loss propagates
    up the module tree — collect with mutable=["intermediates"]."""

    cfg: TransformerConfig
    axis_name: str = TENSOR_AXIS

    @nn.compact
    def __call__(self, hidden):
        from apex_tpu.transformer.moe import ExpertParallelMLP, MoEConfig

        cfg = self.cfg
        if cfg.sequence_parallel:
            raise NotImplementedError(
                "num_moe_experts with sequence_parallel: the MLP input is "
                "sequence-sharded over tp, so routing would operate on "
                "different token sets per rank while the expert tp-psum "
                "assumes identical tokens — gather/scatter plumbing for "
                "this combination is not implemented")
        s, b, h = hidden.shape
        moe = ExpertParallelMLP(MoEConfig(
            hidden_size=h, ffn_hidden_size=cfg.ffn_size,
            num_experts=cfg.num_moe_experts,
            capacity_factor=cfg.moe_capacity_factor,
            num_selected=cfg.moe_top_k,
            expert_parallel_axis=cfg.expert_parallel_axis,
            tensor_parallel_axis=self.axis_name,
            params_dtype=cfg.params_dtype,
            init_method_std=cfg.init_method_std), name="moe")
        out = moe(hidden.reshape(s * b, h)).reshape(s, b, h)
        return out, jnp.zeros((h,), out.dtype)


class ParallelMLP(nn.Module):
    """h → 4h (column) → gelu → h (row) (reference:
    standalone_transformer_lm.py:304-399)."""

    cfg: TransformerConfig
    axis_name: str = TENSOR_AXIS

    @nn.compact
    def __call__(self, hidden):
        cfg = self.cfg
        dense_h_to_4h = ColumnParallelLinear(
            cfg.hidden_size, cfg.ffn_size, gather_output=False,
            skip_bias_add=True,
            init_method=init_normal(cfg.init_method_std),
            sequence_parallel_enabled=cfg.sequence_parallel,
            params_dtype=cfg.params_dtype, axis_name=self.axis_name,
            name="dense_h_to_4h")
        dense_4h_to_h = RowParallelLinear(
            cfg.ffn_size, cfg.hidden_size, input_is_parallel=True,
            skip_bias_add=True,
            init_method=scaled_init_method_normal(cfg.init_method_std,
                                                  cfg.num_layers),
            sequence_parallel_enabled=cfg.sequence_parallel,
            params_dtype=cfg.params_dtype, axis_name=self.axis_name,
            name="dense_4h_to_h")

        inter, bias = dense_h_to_4h(hidden)
        # bias_gelu fusion (reference fuses via jit; XLA fuses here)
        inter = nn.gelu(inter + bias.astype(inter.dtype), approximate=True)
        out, out_bias = dense_4h_to_h(inter)
        return out, out_bias


class ParallelAttention(nn.Module):
    """Self/cross attention over TP-sharded heads (reference:
    standalone_transformer_lm.py:401-707)."""

    cfg: TransformerConfig
    layer_number: int = 1
    attention_type: Any = AttnType.self_attn
    attn_mask_type: Any = AttnMaskType.padding
    axis_name: str = TENSOR_AXIS

    @nn.compact
    def __call__(self, hidden, attention_mask, encoder_output=None,
                 deterministic=True, padding_validity=None):
        cfg = self.cfg
        tp = lax.axis_size(self.axis_name)
        np_local = divide(cfg.num_attention_heads, tp)
        hd = cfg.head_dim
        proj_size = cfg.num_attention_heads * hd
        layer_number = max(1, self.layer_number)

        norm_factor = math.sqrt(hd)
        coeff = None
        # query-key layer scaling forces fp32 softmax (Megatron rule,
        # reference arguments.py consistency checks)
        softmax_in_fp32 = cfg.attention_softmax_in_fp32
        if cfg.apply_query_key_layer_scaling:
            coeff = float(layer_number)
            norm_factor *= coeff
            softmax_in_fp32 = True

        if self.attention_type == AttnType.self_attn:
            qkv_proj = ColumnParallelLinear(
                cfg.hidden_size, 3 * proj_size, gather_output=False,
                init_method=init_normal(cfg.init_method_std),
                sequence_parallel_enabled=cfg.sequence_parallel,
                params_dtype=cfg.params_dtype, axis_name=self.axis_name,
                name="query_key_value")
            qkv = qkv_proj(hidden)  # [s, b, 3*proj/tp]
            s, b = qkv.shape[0], qkv.shape[1]
            qkv = qkv.reshape(s, b, np_local, 3 * hd)
            q, k, v = jnp.split(qkv, 3, axis=-1)
        else:
            q_proj = ColumnParallelLinear(
                cfg.hidden_size, proj_size, gather_output=False,
                init_method=init_normal(cfg.init_method_std),
                params_dtype=cfg.params_dtype, axis_name=self.axis_name,
                name="query")
            kv_proj = ColumnParallelLinear(
                cfg.hidden_size, 2 * proj_size, gather_output=False,
                init_method=init_normal(cfg.init_method_std),
                params_dtype=cfg.params_dtype, axis_name=self.axis_name,
                name="key_value")
            q = q_proj(hidden)
            kv = kv_proj(encoder_output)
            s, b = q.shape[0], q.shape[1]
            sk = kv.shape[0]
            q = q.reshape(s, b, np_local, hd)
            kv = kv.reshape(sk, b, np_local, 2 * hd)
            k, v = jnp.split(kv, 2, axis=-1)

        # the output projection is shared by every dispatch branch below —
        # constructed once so the paths cannot drift apart (the flax
        # param path stays "dense" whichever branch traces)
        dense = RowParallelLinear(
            proj_size, cfg.hidden_size, input_is_parallel=True,
            skip_bias_add=True,
            init_method=scaled_init_method_normal(cfg.init_method_std,
                                                  cfg.num_layers),
            sequence_parallel_enabled=cfg.sequence_parallel,
            params_dtype=cfg.params_dtype, axis_name=self.axis_name,
            name="dense")

        def _via_bhsd(attn_fn):
            # [s, b, np, hd] -> [b, np, s, hd], run the kernel, restore
            # [s, b, np*hd] and project — the one layout adapter every
            # fused branch shares
            ctx = attn_fn(q.transpose(1, 2, 0, 3),
                          k.transpose(1, 2, 0, 3),
                          v.transpose(1, 2, 0, 3))
            ctx = ctx.transpose(2, 0, 1, 3).reshape(
                q.shape[0], q.shape[1], np_local * hd)
            return dense(ctx)

        # flash path: causal self-attention with no explicit mask and no
        # attention dropout lowers to the Pallas flash kernel on TPU (the
        # fmhalib / fused-softmax replacement); other configs take the
        # explicit scores→FusedScaleMaskSoftmax→ctx path below
        use_flash = (
            self.attn_mask_type == AttnMaskType.causal
            and attention_mask is None
            and (deterministic or cfg.attention_dropout == 0.0)
        )
        # training WITH attention dropout: the VMEM-rows kernel applies
        # inverted dropout inside the kernel (counter-hash, replayed in
        # backward) so the [b·h, s, s] probs never reach HBM — without
        # this the dropout>0 config silently falls off every fused path
        # (cfg.fused_attention_dropout documents the measured default).
        # Two eligible mask forms:
        #   * causal self-attention, no explicit mask (GPT);
        #   * padding-type self-attention whose [b, s] key validity was
        #     threaded down (BERT) — expressed as segment ids (valid=0,
        #     pad=1): valid queries exclude exactly the pad keys (the
        #     extended mask's semantics for them); pad ROWS attend pad
        #     keys — finite garbage the caller's loss mask drops, the
        #     same contract as fmhalib's packed path (reference
        #     contrib/fmha/fmha.py:33-61, where pad rows don't exist)
        drop_causal = (self.attn_mask_type == AttnMaskType.causal
                       and attention_mask is None)
        drop_padding = (self.attn_mask_type == AttnMaskType.padding
                        and padding_validity is not None
                        and self.attention_type == AttnType.self_attn
                        and q.shape[0] == k.shape[0]
                        and fused_padding_dropout_eligible(
                            cfg, deterministic, q.shape[0], hd))
        if (not use_flash
                and (drop_causal or drop_padding)
                and not deterministic and cfg.attention_dropout > 0.0
                and cfg.fused_attention_dropout):
            from apex_tpu.ops import attention_pallas

            def _drop_seed():
                # derived lazily so a fall-through (unsupported shape)
                # doesn't advance the flax rng stream for nn.Dropout
                return derive_attention_dropout_seed(
                    self.make_rng("dropout"), self.axis_name)

            if drop_causal and cfg.context_parallel_axis is not None:
                # context-parallel training with dropout: the ring
                # regenerates its slice of the global hash mask per
                # block (previously this combination raised)
                from apex_tpu.ops import ring_attention

                seed = _drop_seed()
                return _via_bhsd(lambda qf, kf, vf: ring_attention(
                    qf, kf, vf, cfg.context_parallel_axis, causal=True,
                    sm_scale=1.0 / math.sqrt(hd),
                    dropout_p=float(cfg.attention_dropout),
                    dropout_seed=seed[0, 0]))
            s_len, kv_len = q.shape[0], k.shape[0]
            # (drop_padding already implies supported() via the shared
            # eligibility predicate — the check is the single gate)
            if (cfg.context_parallel_axis is None
                    and attention_pallas.supported(s_len, kv_len, hd,
                                                   dropout=True)):
                seed = _drop_seed()
                segs = None
                if drop_padding:
                    pad_ids = (padding_validity.astype(jnp.int32)
                               == 0).astype(jnp.int32)
                    segs = (pad_ids, pad_ids)
                interpret = jax.devices()[0].platform == "cpu"
                return _via_bhsd(
                    lambda qf, kf, vf: attention_pallas.fused_attention_rows(
                        qf, kf, vf, drop_causal, 1.0 / math.sqrt(hd), segs,
                        interpret, None, None,
                        float(cfg.attention_dropout), seed))
        if use_flash:
            from apex_tpu.ops import fused_attention, ring_attention

            # q/norm_factor then softmax×coeff == plain 1/sqrt(hd) scaling
            # (qk-layer-scaling is an fp16-range trick; flash accumulates
            # in fp32 so the composed scale is exact)
            if cfg.context_parallel_axis is not None:
                return _via_bhsd(lambda qf, kf, vf: ring_attention(
                    qf, kf, vf, cfg.context_parallel_axis, causal=True,
                    sm_scale=1.0 / math.sqrt(hd)))
            return _via_bhsd(lambda qf, kf, vf: fused_attention(
                qf, kf, vf, causal=True, sm_scale=1.0 / math.sqrt(hd)))

        if cfg.context_parallel_axis is not None:
            raise NotImplementedError(
                "context_parallel_axis requires the ring-attention path "
                "(causal self-attention, no explicit mask, no attention "
                "dropout); the local scores path would silently compute "
                "block-diagonal attention over sequence shards")

        # [s, b, np, hd] → [b*np, s, hd] for MXU-batched GEMMs
        def to_bns(x):
            return x.transpose(1, 2, 0, 3).reshape(-1, x.shape[0], hd)

        qb, kb, vb = to_bns(q), to_bns(k), to_bns(v)

        # raw scores [b*np, sq, sk], fp32 accumulation
        scores = lax.dot_general(
            qb / jnp.asarray(norm_factor, qb.dtype), kb,
            (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)

        sq, sk = scores.shape[1], scores.shape[2]
        scores = scores.reshape(-1, np_local, sq, sk).astype(hidden.dtype)

        scale_mask_softmax = FusedScaleMaskSoftmax(
            cfg.fp16, cfg.bf16, self.attn_mask_type,
            cfg.masked_softmax_fusion, attention_mask_func,
            softmax_in_fp32, coeff, use_pallas=cfg.softmax_use_pallas)
        probs = scale_mask_softmax(scores, attention_mask)

        probs = nn.Dropout(rate=cfg.attention_dropout)(
            probs, deterministic=deterministic)

        ctx = lax.dot_general(
            probs.reshape(-1, sq, sk).astype(vb.dtype), vb,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32).astype(hidden.dtype)
        # [b*np, sq, hd] → [sq, b, np*hd]
        ctx = ctx.reshape(-1, np_local, sq, hd).transpose(2, 0, 1, 3)
        ctx = ctx.reshape(sq, ctx.shape[1], np_local * hd)

        out, bias = dense(ctx)
        return out, bias


def attention_mask_func(attention_scores, attention_mask):
    """Reference: testing/standalone_transformer_lm.py attention_mask_func —
    masked positions → large negative."""
    fill = jnp.asarray(-10000.0, attention_scores.dtype)
    return jnp.where(attention_mask, fill, attention_scores)


class ParallelTransformerLayer(nn.Module):
    """pre-LN block: LN → attn → residual → LN → MLP → residual
    (reference: standalone_transformer_lm.py:709-847)."""

    cfg: TransformerConfig
    layer_number: int = 1
    layer_type: Any = LayerType.encoder
    self_attn_mask_type: Any = AttnMaskType.padding
    axis_name: str = TENSOR_AXIS

    @nn.compact
    def __call__(self, hidden, attention_mask, encoder_output=None,
                 enc_dec_attn_mask=None, deterministic=True,
                 padding_validity=None):
        cfg = self.cfg
        ln = FusedLayerNorm(normalized_shape=cfg.hidden_size,
                            eps=cfg.layernorm_epsilon,
                            name="input_layernorm")
        attn_cls = ParallelAttention
        if cfg.recompute_granularity == "selective":
            # reference selective recompute: only the attention core is
            # recomputed in backward (arguments.py --recompute-activations)
            attn_cls = nn.remat(ParallelAttention, static_argnums=(4,))
        attn = attn_cls(cfg, self.layer_number,
                        AttnType.self_attn,
                        self.self_attn_mask_type,
                        axis_name=self.axis_name,
                        name="self_attention")
        post_ln = FusedLayerNorm(normalized_shape=cfg.hidden_size,
                                 eps=cfg.layernorm_epsilon,
                                 name="post_attention_layernorm")
        if cfg.num_moe_experts:
            mlp = MoEMLP(cfg, axis_name=self.axis_name, name="mlp")
        else:
            mlp = ParallelMLP(cfg, axis_name=self.axis_name, name="mlp")

        def _layer_bias_dropout_add(x, bias, residual):
            # reference: bias_dropout_add fusion (XLA fuses this chain).
            # Distinct from the module-level parity helper
            # ``bias_dropout_add`` (explicit-rng form): this closure uses
            # flax's "dropout" rng collection via nn.Dropout, the
            # convention every layer in this file follows.
            x = x + bias.astype(x.dtype)
            x = nn.Dropout(rate=cfg.hidden_dropout)(
                x, deterministic=deterministic)
            return residual + x

        # positional call: nn.remat's static_argnums counts self at 0, so
        # deterministic must arrive as positional arg 4
        attn_out, attn_bias = attn(ln(hidden), attention_mask, None,
                                   deterministic, padding_validity)
        hidden = _layer_bias_dropout_add(attn_out, attn_bias, hidden)

        if self.layer_type == LayerType.decoder:
            cross_ln = FusedLayerNorm(normalized_shape=cfg.hidden_size,
                                      eps=cfg.layernorm_epsilon,
                                      name="post_inter_attention_layernorm")
            cross = ParallelAttention(cfg, self.layer_number,
                                      AttnType.cross_attn,
                                      AttnMaskType.padding,
                                      axis_name=self.axis_name,
                                      name="inter_attention")
            c_out, c_bias = cross(post_ln(hidden), enc_dec_attn_mask,
                                  encoder_output=encoder_output,
                                  deterministic=deterministic)
            hidden = _layer_bias_dropout_add(c_out, c_bias, hidden)
            mlp_in = cross_ln(hidden)
        else:
            mlp_in = post_ln(hidden)

        mlp_out, mlp_bias = mlp(mlp_in)
        hidden = _layer_bias_dropout_add(mlp_out, mlp_bias, hidden)
        return hidden


class ParallelTransformer(nn.Module):
    """Layer stack with optional final LN + activation recompute
    (reference: standalone_transformer_lm.py:849-1020)."""

    cfg: TransformerConfig
    self_attn_mask_type: Any = AttnMaskType.padding
    post_layer_norm: bool = True
    pre_process: bool = True
    post_process: bool = True
    recompute_activations: bool = False
    axis_name: str = TENSOR_AXIS

    @nn.compact
    def __call__(self, hidden, attention_mask, deterministic=True,
                 padding_validity=None):
        cfg = self.cfg
        layer_cls = ParallelTransformerLayer
        if self.recompute_activations:
            # reference: tensor_parallel.random.checkpoint per layer;
            # static_argnums: (5,) = deterministic ((0,) is self)
            layer_cls = nn.remat(ParallelTransformerLayer,
                                 static_argnums=(5,))
        for i in range(cfg.num_layers):
            layer = layer_cls(
                cfg, layer_number=i + 1,
                self_attn_mask_type=self.self_attn_mask_type,
                axis_name=self.axis_name, name=f"layer_{i}")
            hidden = layer(hidden, attention_mask, None, None,
                           deterministic, padding_validity)
        if self.post_process and self.post_layer_norm:
            hidden = FusedLayerNorm(normalized_shape=cfg.hidden_size,
                                    eps=cfg.layernorm_epsilon,
                                    name="final_layernorm")(hidden)
        return hidden


# ---------------------------------------------------------------------------
# GPT
# ---------------------------------------------------------------------------

def _word_embeddings_param(module, cfg, axis_name):
    """The vocab-sharded tied word table every LM head reuses (one
    definition: GPTModel, BertModel and TransformerLanguageModel all
    carry it at model top level so pipeline stages without pre_process
    still reach it)."""
    tp_world = lax.axis_size(axis_name)
    return module.param(
        "word_embeddings",
        _sharded_init(init_normal(cfg.init_method_std),
                      (cfg.vocab_size, cfg.hidden_size), 0, axis_name),
        (divide(cfg.vocab_size, tp_world), cfg.hidden_size),
        cfg.params_dtype)


class Embedding(nn.Module):
    """Word + position (+ optional tokentype) embeddings with the
    [s, b, h] transpose, compute-dtype cast, the sequence-parallel
    scatter when ``cfg.sequence_parallel``, and embedding dropout
    (reference:
    standalone_transformer_lm.py Embedding :150-280). The word table is
    passed IN (and owned by the caller) because pipeline stages without
    ``pre_process`` still need it for tied logits — weight tying as
    explicit dataflow, per the module docstring."""

    cfg: TransformerConfig
    num_tokentypes: int = 0
    axis_name: str = TENSOR_AXIS

    @nn.compact
    def __call__(self, word_embeddings, input_ids, position_ids,
                 tokentype_ids=None, deterministic=True):
        cfg = self.cfg
        position_embeddings = self.param(
            "position_embeddings", init_normal(cfg.init_method_std),
            (cfg.max_position_embeddings, cfg.hidden_size),
            cfg.params_dtype)
        emb = (vocab_parallel_embed(word_embeddings, input_ids,
                                    self.axis_name)
               + jnp.take(position_embeddings, position_ids, axis=0))
        if self.num_tokentypes > 0:
            # table exists whenever the module declares tokentypes (the
            # reference's rule) — init without tokentype_ids must still
            # create it, or a later apply WITH them can't find the param
            tokentype_embeddings = self.param(
                "tokentype_embeddings", init_normal(cfg.init_method_std),
                (self.num_tokentypes, cfg.hidden_size), cfg.params_dtype)
            if tokentype_ids is not None:
                emb = emb + jnp.take(tokentype_embeddings, tokentype_ids,
                                     axis=0)
        else:
            assert tokentype_ids is None, (
                "tokentype_ids passed to an Embedding built with "
                "num_tokentypes=0")
        # [b, s, h] → [s, b, h]
        emb = emb.transpose(1, 0, 2)
        if cfg.compute_in_float16:
            emb = emb.astype(jnp.bfloat16 if cfg.bf16 else jnp.float16)
        if cfg.sequence_parallel:
            emb = mappings.scatter_to_sequence_parallel_region(
                emb, self.axis_name)
        return nn.Dropout(rate=cfg.hidden_dropout)(
            emb, deterministic=deterministic)


def resolve_recompute_granularity(cfg, hidden_shape):
    """Trace-time remat-policy resolution — the dispatch-table consumer
    for op "remat" (apex_tpu.dispatch). An explicit config value pins:
    "selective"/"full" turn recompute on, "none" pins it OFF; None
    (unpinned) consults the per-shape table keyed on (b, s, hidden,
    layers), a miss meaning no recompute (the built-in default).
    ``hidden_shape`` is the trunk input's [s, b, h]. Returns the
    effective granularity (None = no recompute) — the model composites
    bake it back into the cfg they hand the trunk, so the layer-level
    ``== "selective"`` / ``== "full"`` checks stay table-aware."""
    g = cfg.recompute_granularity
    if g == "none":
        return None
    if g is not None:
        return g
    from apex_tpu import dispatch

    s, b = int(hidden_shape[0]), int(hidden_shape[1])
    choice = dispatch.lookup(
        "remat", dtype="bfloat16" if cfg.bf16 else "float32",
        b=b, s=s, h=cfg.hidden_size, layers=cfg.num_layers)
    return None if choice in (None, "none") else choice


def _remat_resolved_cfg(cfg, hidden_shape):
    """cfg with ``recompute_granularity`` resolved for this trace."""
    return dataclasses.replace(
        cfg, recompute_granularity=resolve_recompute_granularity(
            cfg, hidden_shape))


class GPTModel(nn.Module):
    """GPT language model (reference: standalone_gpt.py:111 +
    standalone_transformer_lm.py TransformerLanguageModel/Embedding).

    ``__call__(input_ids, position_ids, attention_mask, labels=None)``:
    input_ids/position_ids [b, s]; returns vocab-parallel per-token loss
    [b, s] when labels given, else logits. Hidden layout [s, b, h].
    """

    cfg: TransformerConfig
    parallel_output: bool = True
    pre_process: bool = True
    post_process: bool = True
    axis_name: str = TENSOR_AXIS

    # NB: GPTModel composes Embedding + ParallelTransformer itself
    # rather than delegating to TransformerLanguageModel: its param tree
    # ("transformer", flat word table) is the layout every checkpoint,
    # sharding rule, and test in this repo addresses — delegating would
    # rename the trunk to "language_model/encoder". Keep shared fixes in
    # the pieces (Embedding, ParallelTransformer, Pooler), which both
    # composites build on.

    def _fused_head_applies(self, hidden):
        """``(applies, interpret, row_block_pref)``: whether the Pallas
        fused LM head replaces logits+CE for this call, and whether it
        runs in interpret mode. ``cfg.fused_lm_head`` True/False pins;
        None consults the dispatch table (op "lm_head", keyed on the
        GLOBAL (n, vocab, h) shape) — a backend-keyed table "fused"
        measured on CPU runs in interpret mode, same as it was
        measured. A pinned True still requires a real TPU (or the
        explicit ``fused_lm_head_interpret`` test knob), and supported
        SHARD shapes either way. tp > 1 runs the vocab-parallel kernel
        (``linear_cross_entropy_sharded`` — per-shard online stats +
        pmax/psum combine); under sequence parallelism the standard
        pre-matmul seq gather runs first (with split-bwd, since the
        sharded head's dX is already cross-rank reduced). All static —
        the choice is baked at trace time. ``row_block_pref`` is the
        entry's tile payload, handed to the kernel as a preference
        (below its per-call ``row_block`` and ``set_row_block``)."""
        cfg = self.cfg
        tp = lax.axis_size(self.axis_name)
        s, b, h = hidden.shape
        if cfg.sequence_parallel:
            s = s * tp  # hidden arrives seq-sharded; the head gathers
        fused = cfg.fused_lm_head
        interpret = cfg.fused_lm_head_interpret
        from_table = False
        row_block_pref = None
        if fused is None:
            from apex_tpu import dispatch

            choice, params = dispatch.lookup_params(
                "lm_head", dtype=hidden.dtype, n=b * s,
                v=cfg.vocab_size, h=h)
            fused = choice == "fused"
            from_table = fused
            if params:
                row_block_pref = params.get("row_block")
        if not fused:
            return False, interpret, None
        from apex_tpu.ops import xent_pallas
        from apex_tpu.ops.attention import _on_cpu, _tpu_available

        if from_table and not interpret:
            interpret = _on_cpu()
        if not (interpret or _tpu_available()):
            return False, interpret, None
        return (xent_pallas.supported(b * s, cfg.vocab_size // tp, h),
                interpret, row_block_pref)

    @nn.compact
    def __call__(self, input_ids, position_ids, attention_mask, labels=None,
                 deterministic=True, hidden_state=None):
        """``hidden_state``: the upstream stage's [s, b, h] activation when
        ``pre_process=False`` — the functional form of the reference's
        ``set_input_tensor`` plumbing (schedules/common.py:30-80)."""
        cfg = self.cfg
        word_embeddings = _word_embeddings_param(self, cfg,
                                                 self.axis_name)

        hidden = hidden_state
        if self.pre_process:
            hidden = Embedding(
                cfg, axis_name=self.axis_name, name="embedding")(
                word_embeddings, input_ids, position_ids,
                deterministic=deterministic)
        assert hidden is not None, (
            "pre_process=False requires hidden_state (the upstream "
            "pipeline stage's activation)")

        cfg = _remat_resolved_cfg(cfg, hidden.shape)
        hidden = ParallelTransformer(
            cfg, self_attn_mask_type=AttnMaskType.causal,
            pre_process=self.pre_process, post_process=self.post_process,
            recompute_activations=(cfg.recompute_granularity == "full"),
            axis_name=self.axis_name, name="transformer")(
            hidden, attention_mask, deterministic=deterministic)

        if not self.post_process:
            return hidden

        fused_head, head_interpret, head_row_block = \
            self._fused_head_applies(hidden)
        if labels is not None and fused_head:
            from apex_tpu.ops import xent_pallas

            # the fused kernel instead of materializing [n, V] logits;
            # at tp > 1 the vocab-parallel variant combines per-shard
            # online stats across ranks (no shard logits in HBM either)
            head_in = hidden
            sp_gathered = (cfg.sequence_parallel
                           and lax.axis_size(self.axis_name) > 1)
            if sp_gathered:
                # the same pre-matmul gather parallel_lm_logits
                # performs; its reduce-scatter backward does the
                # cross-rank dX sum, so the head runs reduce_dx=False
                # (partial dX out — half the collective traffic of
                # psum-then-split on the model's hottest bwd tensor)
                head_in = mappings.gather_from_sequence_parallel_region(
                    hidden, self.axis_name, True)
            s, b, h = head_in.shape
            x2d = head_in.transpose(1, 0, 2).reshape(b * s, h)
            if lax.axis_size(self.axis_name) == 1:
                loss = xent_pallas.linear_cross_entropy(
                    x2d, word_embeddings.astype(x2d.dtype),
                    labels.reshape(-1),
                    head_interpret, 0.0,
                    row_block_pref=head_row_block)
            else:
                loss = xent_pallas.linear_cross_entropy_sharded(
                    x2d, word_embeddings.astype(x2d.dtype),
                    labels.reshape(-1), self.axis_name,
                    head_interpret, 0.0,
                    not sp_gathered,
                    row_block_pref=head_row_block)
            return loss.reshape(b, s)

        logits = parallel_lm_logits(
            hidden, word_embeddings, parallel_output=self.parallel_output,
            sequence_parallel=cfg.sequence_parallel,
            axis_name=self.axis_name)
        # [s, b, v'] → [b, s, v']
        logits = logits.transpose(1, 0, 2)

        if labels is None:
            return logits
        # post_language_model_processing: vocab-parallel CE in fp32
        return vocab_parallel_cross_entropy(
            logits, labels, axis_name=self.axis_name)


class TransformerLanguageModel(nn.Module):
    """Embedding + transformer trunk (+ optional pooler): the composite
    the reference's heads build on (reference:
    standalone_transformer_lm.py TransformerLanguageModel :1260-1420,
    get_language_model :1240-1257). Returns ``(encoder_output,
    word_embeddings)`` — or ``(encoder_output, pooled_output,
    word_embeddings)`` with ``add_pooler`` — so heads can tie logits to
    the word table explicitly."""

    cfg: TransformerConfig
    num_tokentypes: int = 0
    add_pooler: bool = False
    encoder_attn_mask_type: Any = AttnMaskType.padding
    pre_process: bool = True
    post_process: bool = True
    axis_name: str = TENSOR_AXIS

    @nn.compact
    def __call__(self, enc_input_ids, enc_position_ids, enc_attn_mask,
                 tokentype_ids=None, pooling_sequence_index=0,
                 deterministic=True, hidden_state=None):
        cfg = self.cfg
        word_embeddings = _word_embeddings_param(self, cfg,
                                                 self.axis_name)

        hidden = hidden_state
        if self.pre_process:
            hidden = Embedding(
                cfg, num_tokentypes=self.num_tokentypes,
                axis_name=self.axis_name, name="embedding")(
                word_embeddings, enc_input_ids, enc_position_ids,
                tokentype_ids=tokentype_ids, deterministic=deterministic)
        assert hidden is not None, (
            "pre_process=False requires hidden_state")

        cfg = _remat_resolved_cfg(cfg, hidden.shape)
        encoder_output = ParallelTransformer(
            cfg, self_attn_mask_type=self.encoder_attn_mask_type,
            pre_process=self.pre_process, post_process=self.post_process,
            recompute_activations=(cfg.recompute_granularity == "full"),
            axis_name=self.axis_name, name="encoder")(
            hidden, enc_attn_mask, deterministic=deterministic)

        if self.post_process and self.add_pooler:
            pooled = Pooler(cfg.hidden_size,
                            init_normal(cfg.init_method_std),
                            params_dtype=cfg.params_dtype,
                            sequence_parallel=cfg.sequence_parallel,
                            axis_name=self.axis_name, name="pooler")(
                encoder_output, pooling_sequence_index)
            return encoder_output, pooled, word_embeddings
        return encoder_output, word_embeddings


def get_language_model(cfg, num_tokentypes=0, add_pooler=False,
                       encoder_attn_mask_type=AttnMaskType.padding,
                       pre_process=True, post_process=True,
                       axis_name=TENSOR_AXIS, **unused):
    """Reference: standalone_transformer_lm.py:1240-1257 — returns
    ``(language_model, language_model_key)``. The init-method arguments
    the reference threads through are fixed by ``cfg.init_method_std``
    here (the same defaulting its callers use)."""
    model = TransformerLanguageModel(
        cfg, num_tokentypes=num_tokentypes, add_pooler=add_pooler,
        encoder_attn_mask_type=encoder_attn_mask_type,
        pre_process=pre_process, post_process=post_process,
        axis_name=axis_name)
    return model, "language_model"


def gpt_model_provider(cfg, pre_process=True, post_process=True, **kwargs):
    """Reference: run_gpt_minimal_test.py gpt_model_provider."""
    return GPTModel(cfg, pre_process=pre_process, post_process=post_process,
                    **kwargs)


def bias_dropout_add(x, bias, residual, prob, training, rng=None):
    """residual + dropout(x + bias) (reference:
    standalone_transformer_lm.py:585-588)."""
    out = x + bias
    if training and prob > 0.0:
        if rng is None:
            raise ValueError("bias_dropout_add: rng required in training")
        out = train_dropout(rng, out, prob)
    return residual + out


def get_bias_dropout_add(training):
    """Reference: standalone_transformer_lm.py:591-595."""
    def _bias_dropout_add(x, bias, residual, prob, rng=None):
        return bias_dropout_add(x, bias, residual, prob, training, rng)
    return _bias_dropout_add


class NoopTransformerLayer(nn.Module):
    """Identity stage filler for uneven pipeline splits (reference:
    standalone_transformer_lm.py:1099-1124 — used when a stage carries
    zero real layers, e.g. the standalone embedding stage)."""

    layer_number: int = 1

    @nn.compact
    def __call__(self, hidden_states, *args, **kwargs):
        return hidden_states


class Pooler(nn.Module):
    """First-token (or ``sequence_index``) tanh pooler (reference:
    standalone_transformer_lm.py:1208-1236). Input [s, b, h]; with
    ``sequence_parallel`` the input is the trunk's sequence-sharded
    [s/tp, b, h] and is gathered first — ``sequence_index`` is a GLOBAL
    position (the reference Pooler does the same gather). The gather's
    backward uses the replicated-output-grad convention (plain split,
    not reduce-scatter): the pooled path is replicated across tp."""

    hidden_size: int
    init_method: Any = None
    params_dtype: Any = jnp.float32
    sequence_parallel: bool = False
    axis_name: str = TENSOR_AXIS

    @nn.compact
    def __call__(self, hidden_states, sequence_index=0):
        if self.sequence_parallel:
            hidden_states = mappings.gather_from_sequence_parallel_region(
                hidden_states, self.axis_name,
                tensor_parallel_output_grad=False)
        dense = nn.Dense(
            self.hidden_size,
            kernel_init=self.init_method or init_normal(0.02),
            param_dtype=self.params_dtype, name="dense")
        return jnp.tanh(dense(hidden_states[sequence_index]))


# ---------------------------------------------------------------------------
# BERT
# ---------------------------------------------------------------------------


def derive_attention_dropout_seed(key, axis_name):
    """Per-rank int32 seed for the in-kernel/in-ring dropout hash.

    The flax "dropout" rng is replicated across the mesh, and the hash
    keys on LOCAL (head, row, col) coordinates — without folding the
    tensor-parallel rank in, TP head shards would regenerate
    bit-identical masks for corresponding local heads (silently
    correlated dropout noise). fold_in(tp_rank) decorrelates the shards
    while staying uniform along any OTHER axis (the cp ring requires
    the same seed on every cp rank)."""
    key = jax.random.fold_in(key, lax.axis_index(axis_name))
    return jax.random.randint(key, (1, 1), -2**31, 2**31 - 1, jnp.int32)


def fused_padding_dropout_eligible(cfg, deterministic, s_len, hd):
    """Static predicate shared by BertModel and ParallelAttention: does
    padding-type training-with-dropout route through the rows kernel?
    Both sides must agree — BertModel skips building the [b, 1, s, s]
    extended mask exactly when the attention will not read it."""
    from apex_tpu.ops import attention_pallas

    return (cfg.fused_attention_dropout
            and not deterministic
            and cfg.attention_dropout > 0.0
            and cfg.context_parallel_axis is None
            and attention_pallas.supported(s_len, s_len, hd, dropout=True))


def bert_extended_attention_mask(attention_mask):
    """[b, s] (1 = attend) → [b, 1, s, s] boolean, True = masked out
    (reference: standalone_bert.py bert_extended_attention_mask — builds
    the same pairwise mask then inverts to the <0.5 convention)."""
    m = attention_mask.astype(bool)
    return ~(m[:, None, None, :] & m[:, None, :, None])


def bert_position_ids(token_ids):
    """[b, s] position ids (reference: standalone_bert.py
    bert_position_ids)."""
    b, s = token_ids.shape
    return jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None, :], (b, s))


class BertLMHead(nn.Module):
    """Masked-LM head: dense + gelu + layernorm, then logits against the
    tied word embeddings (reference: standalone_bert.py BertLMHead —
    dense/LN/gelu with the output weight shared with the embedding).
    Input [s, b, h]; returns [s, b, vocab/tp]."""

    cfg: TransformerConfig
    parallel_output: bool = True
    axis_name: str = TENSOR_AXIS

    @nn.compact
    def __call__(self, hidden, word_embeddings):
        cfg = self.cfg
        dense = nn.Dense(cfg.hidden_size, name="dense",
                         param_dtype=cfg.params_dtype)
        ln = FusedLayerNorm(normalized_shape=cfg.hidden_size,
                            eps=cfg.layernorm_epsilon, name="layernorm")
        h = ln(nn.gelu(dense(hidden), approximate=True))
        # reference: a zero-init learnable bias over this rank's vocab
        # shard, applied with the tied-embedding logits
        bias = self.param("bias", nn.initializers.zeros,
                          (word_embeddings.shape[0],), cfg.params_dtype)
        return parallel_lm_logits(
            h, word_embeddings, parallel_output=self.parallel_output,
            bias=bias, sequence_parallel=cfg.sequence_parallel,
            axis_name=self.axis_name)


class BertModel(nn.Module):
    """Bidirectional encoder with MLM head + optional binary (NSP) head
    (reference: standalone_bert.py, 255 LoC).

    ``__call__(input_ids, attention_mask, tokentype_ids=None,
    lm_labels=None)``; attention_mask [b, s] with 1 = attend.
    """

    cfg: TransformerConfig
    parallel_output: bool = True
    pre_process: bool = True
    post_process: bool = True
    axis_name: str = TENSOR_AXIS

    @nn.compact
    def __call__(self, input_ids, attention_mask, tokentype_ids=None,
                 lm_labels=None, deterministic=True, hidden_state=None):
        cfg = self.cfg
        position_ids = bert_position_ids(input_ids)
        # when every layer's self-attention will take the fused
        # segment-id dropout route, the [b, 1, s, s] extended mask is
        # never read — don't build it (it would be the very [s, s]
        # materialization the route exists to avoid)
        if fused_padding_dropout_eligible(
                cfg, deterministic, input_ids.shape[1], cfg.head_dim):
            ext_mask = None
        else:
            ext_mask = bert_extended_attention_mask(attention_mask)

        word_embeddings = _word_embeddings_param(self, cfg,
                                                 self.axis_name)
        hidden = hidden_state
        if self.pre_process:
            hidden = Embedding(
                cfg, num_tokentypes=2,
                axis_name=self.axis_name, name="embedding")(
                word_embeddings, input_ids, position_ids,
                tokentype_ids=tokentype_ids, deterministic=deterministic)
        assert hidden is not None, (
            "pre_process=False requires hidden_state")

        cfg = _remat_resolved_cfg(cfg, hidden.shape)
        hidden = ParallelTransformer(
            cfg, self_attn_mask_type=AttnMaskType.padding,
            pre_process=self.pre_process, post_process=self.post_process,
            recompute_activations=(cfg.recompute_granularity == "full"),
            axis_name=self.axis_name, name="transformer")(
            hidden, ext_mask, deterministic=deterministic,
            padding_validity=attention_mask)

        if not self.post_process:
            return hidden

        lm_logits = BertLMHead(
            cfg, parallel_output=self.parallel_output,
            axis_name=self.axis_name, name="lm_head")(
            hidden, word_embeddings).transpose(1, 0, 2)

        binary_logits = None
        if cfg.bert_binary_head:
            pooled = Pooler(cfg.hidden_size,
                            init_normal(cfg.init_method_std),
                            params_dtype=cfg.params_dtype,
                            sequence_parallel=cfg.sequence_parallel,
                            axis_name=self.axis_name,
                            name="pooler")(hidden)
            binary_logits = nn.Dense(2, name="binary_head",
                                     param_dtype=cfg.params_dtype)(pooled)

        if lm_labels is None:
            return lm_logits, binary_logits
        lm_loss = vocab_parallel_cross_entropy(
            lm_logits, lm_labels,
            axis_name=self.axis_name)
        return lm_loss, binary_logits


def bert_model_provider(cfg, pre_process=True, post_process=True, **kwargs):
    """Reference: run_bert_minimal_test.py bert_model_provider."""
    return BertModel(cfg, pre_process=pre_process, post_process=post_process,
                     **kwargs)
