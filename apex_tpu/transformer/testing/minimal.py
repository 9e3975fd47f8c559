"""Minimal end-to-end 3D-parallel (pp x dp x tp) GPT pretrain step.

Capability port of the reference's minimal-test launchers
(tests/L0/run_transformer/run_gpt_minimal_test.py, gpt_scaling_test.py):
build the parallel topology, construct a pipelined GPT, run real training
steps with mixed precision + fused optimizer.

TPU-first shape: the ENTIRE training step — pipeline 1F1B scan, TP
collectives, DP gradient psum, dynamic loss scaling, fused Adam update — is
ONE jitted SPMD program inside ``shard_map`` over the (pp, dp, tp) mesh.
There is no per-rank Python; XLA's latency-hiding scheduler overlaps the
pp ppermutes / tp psums with compute (the reference hand-builds this
overlap with NCCL streams, apex/parallel/distributed.py:425-556).
"""

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.amp.scaler import LossScaler
from apex_tpu.normalization.fused_layer_norm import FusedLayerNorm
from apex_tpu.optimizers.fused_adam import fused_adam
from apex_tpu.transformer.enums import AttnMaskType
from apex_tpu.transformer.parallel_state import (
    DATA_AXIS,
    PIPELINE_AXIS,
    TENSOR_AXIS,
)
from apex_tpu.transformer.pipeline_parallel.schedules import (
    forward_backward_no_pipelining,
    forward_backward_pipelining_without_interleaving,
)
from apex_tpu.transformer.tensor_parallel.cross_entropy import (
    vocab_parallel_cross_entropy,
)
from apex_tpu.transformer.tensor_parallel.layers import ColumnParallelLinear
from apex_tpu.transformer.testing.standalone_transformer_lm import (
    ParallelTransformerLayer,
    TransformerConfig,
    init_normal,
    vocab_parallel_embed,
)
from apex_tpu.transformer.tensor_parallel.layers import _sharded_init
from apex_tpu.transformer.utils import divide


class GPTEmbed(nn.Module):
    """First pipeline stage: word + position embeddings → [s, b, h]."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, input_ids):
        cfg = self.cfg
        tp = lax.axis_size(TENSOR_AXIS)
        word = self.param(
            "word_embeddings",
            _sharded_init(init_normal(cfg.init_method_std),
                          (cfg.vocab_size, cfg.hidden_size), 0, TENSOR_AXIS),
            (divide(cfg.vocab_size, tp), cfg.hidden_size), cfg.params_dtype)
        pos = self.param(
            "position_embeddings", init_normal(cfg.init_method_std),
            (cfg.max_position_embeddings, cfg.hidden_size), cfg.params_dtype)
        s = input_ids.shape[1]
        emb = (vocab_parallel_embed(word, input_ids)
               + jnp.take(pos, jnp.arange(s), axis=0)[None])
        emb = emb.transpose(1, 0, 2)  # [s, b, h]
        if cfg.compute_in_float16:
            emb = emb.astype(jnp.bfloat16 if cfg.bf16 else jnp.float16)
        return emb


class GPTStage(nn.Module):
    """One pipeline stage's chunk of the layer stack (causal)."""

    cfg: TransformerConfig
    layers_per_stage: int

    @nn.compact
    def __call__(self, hidden):
        for i in range(self.layers_per_stage):
            hidden = ParallelTransformerLayer(
                self.cfg, layer_number=i + 1,
                self_attn_mask_type=AttnMaskType.causal,
                name=f"layer_{i}")(hidden, None, None, None, True)
        return hidden


class GPTHead(nn.Module):
    """Last pipeline stage: final LN → vocab-parallel logits → mean CE.

    The LM head is untied here (its own [v/tp, h] weight): the pipeline
    schedule's embed params live on stage 0 and head params on stage pp-1,
    so tying would need a cross-stage weight broadcast; the reference's
    tied path does exactly such an embedding-grad all-reduce
    (schedules/common.py:320). The single-slab GPTModel keeps the tie.
    """

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, hidden, labels):
        cfg = self.cfg
        hidden = FusedLayerNorm(normalized_shape=cfg.hidden_size,
                                eps=cfg.layernorm_epsilon,
                                name="final_layernorm")(hidden)
        logits = ColumnParallelLinear(
            cfg.hidden_size, cfg.vocab_size, bias=False, gather_output=False,
            init_method=init_normal(cfg.init_method_std),
            params_dtype=cfg.params_dtype, name="lm_head")(hidden)
        logits = logits.transpose(1, 0, 2)  # [b, s, v/tp]
        loss = vocab_parallel_cross_entropy(logits, labels)
        return jnp.mean(loss)


def make_gpt_fns(cfg, pp):
    """(stage_fn, embed_fn, loss_fn) + init for the pipeline schedule."""
    assert cfg.num_layers % pp == 0
    embed_mod = GPTEmbed(cfg)
    stage_mod = GPTStage(cfg, layers_per_stage=cfg.num_layers // pp)
    head_mod = GPTHead(cfg)

    def embed_fn(ep, mb):
        return embed_mod.apply({"params": ep}, mb["ids"])

    def stage_fn(sp, hidden, chunk_idx):
        return stage_mod.apply({"params": sp}, hidden)

    def loss_fn(hp, hidden, mb):
        return head_mod.apply({"params": hp}, hidden, mb["labels"])

    def init_params(rng, mb):
        """Call inside shard_map. Stage params get a per-pp-stage RNG fork
        (the reference seeds each rank's model-parallel RNG differently,
        tensor_parallel/random.py:204)."""
        k_e, k_s, k_h = jax.random.split(rng, 3)
        ep = embed_mod.init(k_e, mb["ids"])["params"]
        hidden = embed_mod.apply({"params": ep}, mb["ids"])
        k_s = jax.random.fold_in(k_s, lax.axis_index(PIPELINE_AXIS))
        sp = stage_mod.init(k_s, hidden)["params"]
        hp = head_mod.init(k_h, hidden, mb["labels"])["params"]
        return sp, ep, hp

    return (stage_fn, embed_fn, loss_fn), init_params


_TP_SHARDED_MARKERS = ("query_key_value", "dense_h_to_4h",
                       "word_embeddings", "lm_head")
_TP_ROW_WEIGHT_MARKERS = ("dense_4h_to_h", "self_attention")


def _is_tp_sharded(path):
    """Whether the minimal-GPT param at *path* (a tree_util key path) is
    tensor-parallel-sharded (distinct shard per tp rank) as opposed to
    replicated. Column-parallel layers shard weight AND bias; row-parallel
    layers ('self_attention.dense', 'dense_4h_to_h') shard the weight but
    replicate the bias (added after the psum); layernorms and position
    embeddings are replicated. Structural, not value-based: zero-init
    biases defeat any cross-rank equality test."""
    names = [str(getattr(k, "key", k)) for k in path]
    if any(m in n for n in names for m in _TP_SHARDED_MARKERS):
        return True
    if any(m in n for n in names for m in _TP_ROW_WEIGHT_MARKERS):
        return names[-1] == "weight"
    return False


def global_grad_norm(grads):
    """Global L2 norm of the (stage, embed, head) *grads* trees over the
    (pp, tp) mesh axes, counting every logical parameter exactly once —
    call INSIDE shard_map, after the dp pmean (grads are dp-replicated
    there).

    tp-sharded leaves (see `_is_tp_sharded`) contribute the tp-psum of
    their shard sq-norms; tp-replicated leaves carry the full identical
    grad on every rank (the copy-region psums their cotangents in
    backward, mappings.py), so their local sq-norm IS the contribution.
    Stage grads are distinct per pp rank (psum over pp); embed/head grads
    come out of the schedule already reduced and replicated across pp
    (schedules.py `_pipelined_fwd_bwd`), so they count once, locally.
    Used for the n-device vs 1-device trajectory parity check (the
    reference's L0 run_transformer tests compare 1-rank-vs-n-rank grads
    the same way)."""
    gs, ge, gh = grads

    def leaf(path, g):
        sq = jnp.sum(jnp.square(g.astype(jnp.float32)))
        if _is_tp_sharded(path):
            sq = lax.psum(sq, TENSOR_AXIS)
        return sq

    def tree_sq(tree):
        sq_tree = jax.tree_util.tree_map_with_path(leaf, tree)
        return functools.reduce(
            jnp.add, jax.tree_util.tree_leaves(sq_tree), jnp.float32(0.0))

    total = lax.psum(tree_sq(gs), PIPELINE_AXIS) + tree_sq(ge) + tree_sq(gh)
    return jnp.sqrt(total)


def _resolve_zero_overlap(zero_stage, overlap_grad, pp):
    """The ONE paired resolution of the ``zero_stage`` × ``overlap_grad``
    knobs (shared by :func:`gpt_train_step_fn` and the callers that must
    know whether to cut params into shards — two copies of the pairing
    could disagree about which program runs). Returns ``(zero_mode,
    overlap_mode)``. Pairing per the engine precedent: two per-call
    demands raise; a demand drops the other side's env/setter
    preference; env-vs-env falls back with ZeRO-3 (the newer layer)
    yielding. The pp > 1 bucketed-overlap demand keeps its historical
    raise."""
    from apex_tpu import overlap as overlap_mod
    from apex_tpu.parallel import zero3 as zero3_mod

    zero_mode = zero3_mod.resolve_zero_stage(zero_stage)
    overlap_mode = overlap_mod.resolve_grad_overlap(overlap_grad)
    if overlap_mode == "bucketed" and pp > 1:
        if overlap_grad == "bucketed":
            raise ValueError(
                f"overlap_grad='bucketed' cannot be honored at pp={pp}: "
                f"the pipeline schedule owns the backward (the stage "
                f"grads complete inside the 1F1B scan) — use the env "
                f"preference for a silent fallback, or pp=1")
        overlap_mode = "off"  # preference semantics: fall back
    if zero_mode == 3 and overlap_mode == "bucketed":
        if zero_stage == 3 and overlap_grad == "bucketed":
            raise ValueError(
                "zero_stage=3 cannot be honored with "
                "overlap_grad='bucketed': the bucketed backward emits "
                "full dp-averaged grads inside each microbatch, but "
                "ZeRO-3 reduce-scatters the terminal grads straight "
                "into the shard (no full-grad materialization) — drop "
                "one of the two demands")
        if zero_stage == 3:
            overlap_mode = "off"  # demand drops the overlap preference
        else:
            # overlap demand, or env-vs-env: the zero3 preference yields
            zero_mode = 0
    return zero_mode, overlap_mode


def gpt_train_step_fn(cfg, pp, num_microbatches, lr=1e-4,
                      checkpoint_stages=True, with_grad_norm=False,
                      dp_axes=DATA_AXIS, compress=None, hierarchical=None,
                      overlap_grad=None, overlap_buckets=None,
                      zero_stage=None):
    """Returns ``(step, tx, scaler)`` where ``step(params, opt_state,
    scaler_state, batch) -> (params, opt_state, scaler_state, loss)`` — to
    be called INSIDE shard_map over the (pp, dp, tp) mesh; ``tx``/``scaler``
    are the exact transform objects ``step`` uses (for state init).
    ``batch``: {"ids","labels"} of [M, mb, s] (already dp-local).
    ``with_grad_norm``: append the unscaled `global_grad_norm` as a 5th
    output (trajectory-parity diagnostics).

    ``dp_axes``: the data-parallel axis — a name, or the declared
    ``(inner, outer)`` pair of a hierarchically factored dp mesh.
    ``compress``/``hierarchical`` ride to
    ``parallel.distributed.allreduce_gradients`` as per-call knob forms
    (None = the process-wide APEX_GRAD_COMPRESS / APEX_HIER_ALLREDUCE
    preferences); with everything off the emitted jaxpr is
    byte-identical to the historical per-leaf pmean. The compressed
    grad sync here is stateless (no error-feedback residual is
    threaded — the step signature stays fixed); EF-carried compression
    lives in the ZeRO optimizers, whose state holds the residual.

    ``zero_stage`` (ISSUE 18, knob home
    :func:`apex_tpu.parallel.zero3.resolve_zero_stage`): per-call 3 is
    a demand for gather-on-use parameter sharding — ``params`` must
    then be the :class:`~apex_tpu.parallel.zero3.Zero3Params` resident
    shards (cut by ``zero3.shard_params`` after init), the step
    all-gathers full weights per layer/bucket at their first use,
    reduce-scatters the grads straight into the shard and runs the
    ZeRO-2 flat-Adam update on the shard — no terminal update gather
    (the master shard IS the parameter). ``compress``/``hierarchical``
    ride both ZeRO-3 hops exactly as they ride the dp allreduce; the
    quantized gather is error-feedback-free by construction (params
    re-gathered fresh from fp32 master each step — ``zero3`` module
    docstring). None consults the ``APEX_ZERO_STAGE`` preference;
    default OFF (the measured-dispatch rule — A/B queued in PERF.md
    §2). Pairing with ``overlap_grad='bucketed'`` per
    :func:`_resolve_zero_overlap`.

    ``overlap_grad``/``overlap_buckets`` (ISSUE 14, knob home
    :mod:`apex_tpu.overlap`): per-call ``"bucketed"`` restructures the
    dp grad sync into layer-group buckets reduced INSIDE each
    microbatch backward (``overlap.bucketed.tag_tree`` — the reference
    DDP's hook-per-backward schedule, apex delay_allreduce=False; one
    collective set per microbatch, interleaved with the remaining
    backward per ``costs.collective_schedule``). Honored for pp == 1
    only — over a pp > 1 pipeline the 1F1B scan owns the backward, so
    a per-call demand RAISES while the env/setter preference falls
    back to the terminal reduction. Resolved off, the step is the
    historical program byte-for-byte.

    The full apex training semantics: forward/backward through the 1F1B
    schedule with loss scaling, DP gradient allreduce (the DDP
    reduction), found_inf-gated fused-Adam update (the skip-step of
    apex/amp/handle.py:128-154), dynamic scale update.
    """
    from apex_tpu import overlap as overlap_mod
    from apex_tpu.overlap.bucketed import tag_tree
    from apex_tpu.parallel import zero3 as zero3_mod
    from apex_tpu.parallel.distributed import allreduce_gradients

    fns, _ = make_gpt_fns(cfg, pp)
    stage_fn, embed_fn, loss_fn = fns
    scaler = LossScaler()  # dynamic, 2^16
    fwd_bwd = (forward_backward_pipelining_without_interleaving if pp > 1
               else forward_backward_no_pipelining)

    zero_mode, overlap_mode = _resolve_zero_overlap(zero_stage,
                                                    overlap_grad, pp)
    tx = (zero3_mod.zero3_adam(learning_rate=lr) if zero_mode == 3
          else fused_adam(learning_rate=lr))
    if overlap_buckets is not None:
        overlap_mod.resolve_buckets(overlap_buckets)  # demand check

    def scaled_loss_fns(scale):
        def scaled(hp, hidden, mb):
            return loss_fn(hp, hidden, mb) * scale
        return (stage_fn, embed_fn, scaled)

    def bucketed_fwd_bwd(params, scaler_state, batch):
        """The bucketed route (pp == 1): the SAME microbatch
        accumulation as the tuple form of
        ``forward_backward_no_pipelining``, with the params routed
        through their bucket reduction tags INSIDE the per-microbatch
        loss — each bucket's collective is emitted in the backward as
        its cotangents complete, so grads come back already
        dp-averaged and the terminal allreduce below is skipped."""
        scale = scaler.scale(jnp.float32(1.0), scaler_state)
        nelems = sum(
            int(np.prod(leaf.shape)) for leaf in
            jax.tree_util.tree_leaves(params))
        nb = overlap_mod.resolve_buckets(overlap_buckets, nelems=nelems)

        def composed(params3, mb):
            sp, ep, hp = tag_tree(params3, dp_axes, nb,
                                  compress=compress,
                                  hierarchical=hierarchical)
            h = embed_fn(ep, mb)
            h = stage_fn(sp, h, 0)
            return loss_fn(hp, h, mb) * scale

        losses, grads = forward_backward_no_pipelining(
            composed, batch, params)
        return jnp.mean(losses), grads

    def zero3_grad_norm(g_shards, grads_full):
        """`global_grad_norm` semantics off the flat SHARDS: per-bucket
        per-tensor sq-norms psum'd over dp re-assemble each tensor's
        full sq-norm; the tp/pp weighting then mirrors the per-leaf
        walk (tp-sharded tensors psum over tp, stage buckets psum over
        pp), with the tp flags read structurally off the full-grads
        tree paths (`_is_tp_sharded`)."""
        gs, ge, gh = grads_full
        spec = g_shards.spec
        sqs = zero3_mod.shard_sq_norms(g_shards, dp_axes)
        total = jnp.float32(0.0)
        stage_total = jnp.float32(0.0)
        for key, kind, sq in zip(spec.keys, spec.kinds, sqs):
            sub = (gs[key[len("stage:"):]] if kind == "stage"
                   else ge if kind == "embed" else gh)
            flat, _ = jax.tree_util.tree_flatten_with_path(sub)
            flags = jnp.asarray(
                [1.0 if _is_tp_sharded(p) else 0.0 for p, _ in flat],
                jnp.float32)
            sq_dp = lax.psum(sq, dp_axes)
            combined = (flags * lax.psum(sq_dp, TENSOR_AXIS)
                        + (1.0 - flags) * sq_dp)
            if kind == "stage":
                stage_total = stage_total + jnp.sum(combined)
            else:
                total = total + jnp.sum(combined)
        return jnp.sqrt(total + lax.psum(stage_total, PIPELINE_AXIS))

    def step(params, opt_state, scaler_state, batch):
        grads_full = None
        if zero_mode == 3:
            # gather-on-use: each bucket's full weights re-assemble
            # from the resident fp32 shards at their first consumer
            # (XLA dataflow placement), grads reduce-scatter straight
            # back into shard form — no full flat grad, no update
            # gather (zero3 module docstring)
            full_params = zero3_mod.gather_params(
                params, dp_axes, compress=compress,
                hierarchical=hierarchical)
            loss, grads_full = fwd_bwd(
                scaled_loss_fns(scaler.scale(jnp.float32(1.0),
                                             scaler_state)),
                batch, full_params, num_microbatches=num_microbatches,
                checkpoint_stages=checkpoint_stages)
            grads = zero3_mod.grad_shards(
                grads_full, params.spec, dp_axes, compress=compress,
                hierarchical=hierarchical)
            dp_size = _collectives_axes_size(dp_axes)
            grads = jax.tree_util.tree_map(lambda g: g / dp_size, grads)
        elif overlap_mode == "bucketed":
            loss, grads = bucketed_fwd_bwd(params, scaler_state, batch)
        else:
            loss, grads = fwd_bwd(
                scaled_loss_fns(scaler.scale(jnp.float32(1.0),
                                             scaler_state)),
                batch, params, num_microbatches=num_microbatches,
                checkpoint_stages=checkpoint_stages)
            # DDP: data-parallel gradient averaging (reference
            # apex/parallel/distributed.py:425-475) through the ONE
            # collectives layer — psum+mean when the knobs are off
            grads = allreduce_gradients(
                grads, dp_axes, compress=compress,
                hierarchical=hierarchical)
        # unscale + overflow detect; found_inf is synced over pp/tp like
        # transformer.amp.GradScaler (grad_scaler.py:38-49)
        grads, found_inf = scaler.unscale(grads, scaler_state)
        found_inf = lax.pmax(lax.pmax(found_inf, PIPELINE_AXIS), TENSOR_AXIS)
        if zero_mode == 3:
            # shard-local infs are NOT dp-replicated (the unsharded
            # path's post-pmean grads are) — sync the skip decision
            found_inf = lax.pmax(found_inf, dp_axes)
        new_scaler_state = scaler.update(scaler_state, found_inf)
        updates, new_opt_state = tx.update(grads, opt_state, params)
        # skip-step on overflow (select, not branch: SPMD-uniform)
        new_params = jax.tree_util.tree_map(
            lambda p, u: jnp.where(found_inf, p, p + u.astype(p.dtype)),
            params, updates)
        new_opt_state = jax.tree_util.tree_map(
            lambda new, old: jnp.where(found_inf, old, new),
            new_opt_state, opt_state)
        loss = loss / scaler.scale(jnp.float32(1.0), scaler_state)
        if with_grad_norm:
            gnorm = (zero3_grad_norm(grads, grads_full)
                     if zero_mode == 3 else global_grad_norm(grads))
            return (new_params, new_opt_state, new_scaler_state, loss,
                    gnorm)
        return new_params, new_opt_state, new_scaler_state, loss

    return step, tx, scaler


def _collectives_axes_size(dp_axes):
    from apex_tpu.parallel import collectives

    return collectives.axes_size(dp_axes)


def dp_axes_of(dp):
    """Normalize a topology's dp entry: an int declares the flat
    ``DATA_AXIS``; an ``(inner, outer)`` pair declares the
    hierarchically factored axes ``(dp_in, dp_out)`` (intra-slice,
    inter-slice — the two-stage collectives of
    ``apex_tpu.parallel.collectives``). Returns ``(dp_size,
    axis_names_tuple, mesh_axis_sizes_tuple)``."""
    if isinstance(dp, (tuple, list)):
        inner, outer = dp
        return inner * outer, (DATA_AXIS + "_in", DATA_AXIS + "_out"), \
            (inner, outer)
    return dp, (DATA_AXIS,), (dp,)


def dp_axis_arg(dp_names):
    """The ONE collapse of a dp-names tuple to the form consumers
    pass around: the bare name for a flat dp, the (inner, outer)
    tuple for a factored declaration. Used both as the collective
    axis argument (``allreduce_gradients``/``lax.pmean``) and as the
    PartitionSpec entry sharding the batch."""
    return dp_names[0] if len(dp_names) == 1 else tuple(dp_names)


_dp_spec = dp_axis_arg  # the spec entry is the same collapse


def factorize_mesh(n_devices):
    """Pick (pp, dp, tp) for n devices: prefer tp (ICI-adjacent), then pp
    — each capped at 2, with dp absorbing the remainder — so all three
    axes stay active on 8 devices (2, 2, 2). Deeper tp/pp factorizations
    (tp=4, pp=4) are driven through the explicit ``topology`` argument of
    ``run_minimal_gpt_training``."""
    def largest_pow2_factor(n, cap):
        f = 1
        while f * 2 <= cap and n % (f * 2) == 0:
            f *= 2
        return f

    tp = largest_pow2_factor(n_devices, min(n_devices, 2))
    rem = n_devices // tp
    pp = largest_pow2_factor(rem, min(rem, 2))
    dp = rem // pp
    return pp, dp, tp


def toy_batch(vocab_size, num_microbatches, global_mb, seq_len):
    """The deterministic [M, global_mb, s] ids/labels batch every minimal
    run (and its parity reference) shares."""
    rs = np.random.RandomState(0)
    return {
        "ids": jnp.asarray(rs.randint(
            0, vocab_size,
            (num_microbatches, global_mb, seq_len)), jnp.int32),
        "labels": jnp.asarray(rs.randint(
            0, vocab_size,
            (num_microbatches, global_mb, seq_len)), jnp.int32),
    }


def reference_first_step_loss(cfg, pp, batch, device=None):
    """Single-device recomputation of the first-step loss of
    ``run_minimal_gpt_training(cfg, topology=(pp, dp, tp))``.

    Same modules, same per-stage init keys (``fold_in(k_s, stage)``
    mirrors init_params' pipeline-rank fork), but the microbatches run
    sequentially through the stage chunks on ONE device — no pipeline
    ring, no dp slicing, no tp sharding. Agreement with the n-device run
    certifies the 3D-parallel step computes the same function, not merely
    a finite one (the reference's L0 run_transformer tests make the same
    1-rank-vs-n-rank comparison).
    """
    # one step of the full replay: the loss scale multiplies then divides
    # out on step 0, so this equals the pre-round-5 direct recomputation
    return reference_training(cfg, pp, batch, num_steps=1,
                              device=device)[0][0]


def reference_training(cfg, pp, batch, num_steps, lr=1e-4, device=None):
    """Sequential single-device replay of ``num_steps`` of the EXACT
    training semantics of ``gpt_train_step_fn`` — same per-stage init keys
    as ``init_params`` (``fold_in(k_s, stage)``), same dynamic loss
    scaling / found_inf skip-step / fused-Adam update — with the
    microbatches run one after another on ONE device: no pipeline ring,
    no dp slicing, no tp sharding.

    Returns ``(losses, grad_norms)`` as per-step float lists; the grad
    norms are of the unscaled grads, directly comparable to the
    ``with_grad_norm=True`` output of the n-device run. Multi-step
    agreement certifies the whole 3D-parallel TRAJECTORY — optimizer
    update, scaler bookkeeping, gradient collectives — not just the first
    forward (the single-step analog of the reference's
    tests/L0/run_transformer 1-rank-vs-n-rank comparisons).
    """
    if device is None:
        device = jax.devices("cpu")[0]
    mesh = Mesh(np.asarray([device]).reshape(1, 1, 1),
                (PIPELINE_AXIS, DATA_AXIS, TENSOR_AXIS))
    embed_mod = GPTEmbed(cfg)
    stage_mod = GPTStage(cfg, layers_per_stage=cfg.num_layers // pp)
    head_mod = GPTHead(cfg)
    M = batch["ids"].shape[0]
    scaler = LossScaler()
    tx = fused_adam(learning_rate=lr)

    def f(batch):
        mb0 = {k: v[0] for k, v in batch.items()}
        k_e, k_s, k_h = jax.random.split(jax.random.PRNGKey(0), 3)
        ep = embed_mod.init(k_e, mb0["ids"])["params"]
        hidden0 = embed_mod.apply({"params": ep}, mb0["ids"])
        sps = tuple(
            stage_mod.init(jax.random.fold_in(k_s, s), hidden0)["params"]
            for s in range(pp))
        hp = head_mod.init(k_h, hidden0, mb0["labels"])["params"]
        params = (sps, ep, hp)
        opt_state = tx.init(params)
        scaler_state = scaler.init()

        def scaled_loss(params, scale):
            sps, ep, hp = params

            def mb_loss(i):
                mb = {k: v[i] for k, v in batch.items()}
                h = embed_mod.apply({"params": ep}, mb["ids"])
                for sp in sps:
                    h = stage_mod.apply({"params": sp}, h)
                return head_mod.apply({"params": hp}, h, mb["labels"])

            return jnp.mean(jnp.stack(
                [mb_loss(i) for i in range(M)])) * scale

        losses, gnorms = [], []
        for _ in range(num_steps):
            scale = scaler.scale(jnp.float32(1.0), scaler_state)
            loss, grads = jax.value_and_grad(scaled_loss)(params, scale)
            grads, found_inf = scaler.unscale(grads, scaler_state)
            new_scaler_state = scaler.update(scaler_state, found_inf)
            updates, new_opt_state = tx.update(grads, opt_state, params)
            params = jax.tree_util.tree_map(
                lambda p, u: jnp.where(found_inf, p, p + u.astype(p.dtype)),
                params, updates)
            opt_state = jax.tree_util.tree_map(
                lambda new, old: jnp.where(found_inf, old, new),
                new_opt_state, opt_state)
            losses.append(loss / scale)
            scaler_state = new_scaler_state
            sq = functools.reduce(
                jnp.add,
                [jnp.sum(jnp.square(g.astype(jnp.float32)))
                 for g in jax.tree_util.tree_leaves(grads)],
                jnp.float32(0.0))
            gnorms.append(jnp.sqrt(sq))
        return jnp.stack(losses), jnp.stack(gnorms)

    g = jax.jit(jax.shard_map(
        f, mesh=mesh, in_specs=({"ids": P(), "labels": P()},),
        out_specs=(P(), P()), check_vma=False))
    losses, gnorms = jax.block_until_ready(g(batch))
    return ([float(x) for x in np.asarray(losses)],
            [float(x) for x in np.asarray(gnorms)])


def _traced_training_jaxpr(devices, cfg, topology, num_microbatches=4,
                           micro_batch_size=2, seq_len=16, compress=None,
                           hierarchical=None, overlap_grad=None,
                           overlap_buckets=None, zero_stage=None):
    """``(jaxpr, axis_sizes)`` of ONE (pp, dp, tp) training step (init
    + 1 full step) — pure host tracing, nothing compiled or executed.
    The shared front end of :func:`training_comm_bytes` and
    :func:`training_collective_schedule`, so the payload count and the
    schedule verdict can never be taken from different programs."""
    pp, dp, tp = topology
    dp_size, dp_names, dp_sizes = dp_axes_of(dp)
    assert pp * dp_size * tp == len(devices), (topology, len(devices))
    mesh = Mesh(np.asarray(devices).reshape(pp, *dp_sizes, tp),
                (PIPELINE_AXIS, *dp_names, TENSOR_AXIS))
    dp_axes = dp_axis_arg(dp_names)
    _, init_params = make_gpt_fns(cfg, pp)
    zero_mode, _ = _resolve_zero_overlap(zero_stage, overlap_grad, pp)
    step, tx, scaler = gpt_train_step_fn(
        cfg, pp, num_microbatches, dp_axes=dp_axes, compress=compress,
        hierarchical=hierarchical, overlap_grad=overlap_grad,
        overlap_buckets=overlap_buckets, zero_stage=zero_stage)
    global_mb = micro_batch_size * dp_size
    batch = toy_batch(cfg.vocab_size, num_microbatches, global_mb,
                      seq_len)

    def one(batch):
        from apex_tpu.parallel import zero3 as zero3_mod

        params = init_params(jax.random.PRNGKey(0),
                             {k: v[0] for k, v in batch.items()})
        if zero_mode == 3:
            params = zero3_mod.shard_params(params, dp_axes)
        opt_state = tx.init(params)
        scaler_state = scaler.init()
        out = step(params, opt_state, scaler_state, batch)
        return lax.pmean(out[3], dp_axes)

    spec = _dp_spec(dp_names)
    f = jax.shard_map(
        one, mesh=mesh,
        in_specs=({"ids": P(None, spec), "labels": P(None, spec)},),
        out_specs=P(), check_vma=False)
    sizes = {PIPELINE_AXIS: pp, TENSOR_AXIS: tp}
    sizes.update(dict(zip(dp_names, dp_sizes)))
    return jax.make_jaxpr(f)(batch), sizes, f, batch


def training_comm_bytes(devices, cfg, topology, num_microbatches=4,
                        micro_batch_size=2, seq_len=16, compress=None,
                        hierarchical=None, overlap_grad=None,
                        overlap_buckets=None, zero_stage=None):
    """Per-mesh-axis collective payload bytes of ONE (pp, dp, tp)
    training step — init + 1 full step traced to a jaxpr and counted by
    ``apex_tpu.telemetry.costs.comm_from_jaxpr`` (psum/all_gather/
    ppermute/all_to_all operand bytes; microbatch scan bodies
    multiplied by their trip count). Pure host tracing: nothing is
    compiled or executed, so the dryrun can print the counts for every
    topology at jaxpr cost. Returns ``{axis: bytes}`` — the checkable
    claim surface for the quantized/hierarchical collectives (ROADMAP
    item 3): ``compress``/``hierarchical`` ride per-call into the dp
    grad sync (None = the APEX_GRAD_COMPRESS / APEX_HIER_ALLREDUCE
    preferences), and the topology's dp entry may be a declared
    ``(inner, outer)`` pair (axes ``dp_in``/``dp_out``).
    ``overlap_grad``/``overlap_buckets`` ride to ``gpt_train_step_fn``
    (ISSUE 14): the bucketed schedule's per-microbatch reduction is
    visible here as an M× dp payload — the honest cost side of the
    hook-per-backward semantics the A/B weighs."""
    jaxpr, sizes, _, _ = _traced_training_jaxpr(
        devices, cfg, topology, num_microbatches=num_microbatches,
        micro_batch_size=micro_batch_size, seq_len=seq_len,
        compress=compress, hierarchical=hierarchical,
        overlap_grad=overlap_grad, overlap_buckets=overlap_buckets,
        zero_stage=zero_stage)
    from apex_tpu.telemetry import costs

    # size-1 axes move nothing on the wire (costs.wire_bytes — the
    # one home of the filter every claim applies)
    return costs.wire_bytes(costs.comm_from_jaxpr(jaxpr), sizes)


def training_collective_schedule(devices, cfg, topology,
                                 num_microbatches=4, micro_batch_size=2,
                                 seq_len=16, compress=None,
                                 hierarchical=None, overlap_grad=None,
                                 overlap_buckets=None, zero_stage=None):
    """``costs.collective_schedule`` verdict of the SAME traced
    training step :func:`training_comm_bytes` counts, judged on the
    DP AXES ONLY (``collective_schedule(axes=...)`` — the forward tp
    psums and pp ppermutes interleave by construction and are not the
    claim) — the jaxpr-level proof surface of the bucket-interleaved
    grad sync (ISSUE 14): with ``overlap_grad="bucketed"`` the
    per-bucket dp collectives interleave with remaining-backward
    compute; with it off the grad sync reads terminal. The MULTICHIP
    dryrun prints both twins per topology."""
    pp, dp, tp = topology
    _, dp_names, _ = dp_axes_of(dp)
    jaxpr, _, _, _ = _traced_training_jaxpr(
        devices, cfg, topology, num_microbatches=num_microbatches,
        micro_batch_size=micro_batch_size, seq_len=seq_len,
        compress=compress, hierarchical=hierarchical,
        overlap_grad=overlap_grad, overlap_buckets=overlap_buckets,
        zero_stage=zero_stage)
    from apex_tpu.telemetry import costs

    return costs.collective_schedule(jaxpr, axes=dp_names)


def training_overlap_profile(devices, cfg, topology, num_microbatches=4,
                             micro_batch_size=2, seq_len=16,
                             compress=None, hierarchical=None,
                             overlap_grad=None, overlap_buckets=None,
                             include_floor=True, zero_stage=None):
    """The MULTICHIP tail's per-topology overlap account (ISSUE 14):
    the dp-axes collective-schedule verdict plus an ENVELOPE
    ``costs.overlap_bound`` of the traced (init + 1 step) program —
    XLA-counted flops over the v5e bf16 peak as the compute floor,
    per-axis collective payload over the ICI envelope as ``comm_ms``
    (size-1 axes filtered; both honestly envelopes, the virtual-CPU
    dryrun measures nothing). ``hideable_ms`` is the per-mesh-shape
    upper bound on what the overlap paths could hide. ONE trace feeds
    everything — ``comm`` rides in the result so the dryrun never
    re-traces the same program for the payload count, and the twin of
    an already-floored profile can pass ``include_floor=False`` to
    skip the jit-lowering (the flops are schedule-independent).
    Returns ``{"schedule": {...}, "overlap_bound": {...}|None,
    "comm": {axis: bytes}}``; the compute floor degrades to None
    where the backend reports no flops."""
    pp, dp, tp = topology
    _, dp_names, _ = dp_axes_of(dp)
    jaxpr, sizes, f, batch = _traced_training_jaxpr(
        devices, cfg, topology, num_microbatches=num_microbatches,
        micro_batch_size=micro_batch_size, seq_len=seq_len,
        compress=compress, hierarchical=hierarchical,
        overlap_grad=overlap_grad, overlap_buckets=overlap_buckets,
        zero_stage=zero_stage)
    from apex_tpu.telemetry import costs

    comm = costs.wire_bytes(costs.comm_from_jaxpr(jaxpr), sizes)
    # an analytic bound against the v5e envelope, whatever device traces
    comm_ms = costs.comm_ms_from_axis_bytes(comm, costs.V5E_KIND)
    floor_ms = None
    if include_floor:
        from apex_tpu import _compat

        ca = _compat.cost_analysis_dict(jax.jit(f).lower(batch))
        flops = ca.get("flops") if ca else None
        if flops:
            floor_ms = round(
                float(flops) / costs.V5E_PEAK_BF16_FLOPS * 1e3, 6)
    return {"schedule": costs.collective_schedule(jaxpr, axes=dp_names),
            "overlap_bound": costs.overlap_bound(floor_ms,
                                                 comm_ms=comm_ms),
            "comm": comm}


def run_minimal_gpt_training(n_devices=None, cfg=None, num_microbatches=4,
                             micro_batch_size=2, seq_len=16, num_steps=1,
                             devices=None, topology=None,
                             return_grad_norms=False, zero_stage=None,
                             compress=None, hierarchical=None):
    """Build an (pp, dp, tp) mesh over ``n_devices`` and run ``num_steps``
    full GPT training steps. Returns the per-step losses (floats).

    ``topology``: explicit (pp, dp, tp) overriding ``factorize_mesh`` —
    tests drive tp=4 / pp=4 programs through this (reference grid:
    parallel_state tests cover the full (pp, dp, tp) factor grid). The
    dp entry may be a declared ``(inner, outer)`` pair: the mesh then
    carries the factored ``dp_in``/``dp_out`` axes and the grad sync
    goes through the hierarchical-capable collectives layer.

    This is the dryrun/CI entry: init + steps execute in shard_map with
    real tp/pp/dp shardings; on CPU it runs under
    ``--xla_force_host_platform_device_count``.

    ``zero_stage=3`` (ISSUE 18) cuts the freshly initialized params
    into :class:`~apex_tpu.parallel.zero3.Zero3Params` resident shards
    over the dp axes before the first step — every dp rank initializes
    the same full tree, so the slice needs no broadcast — and the step
    runs the gather-on-use program; ``compress``/``hierarchical`` ride
    the ZeRO-3 gather/scatter hops (or the dp allreduce when
    unsharded). Both default to the env preferences; all OFF by
    default.
    """
    if devices is None:
        devices = jax.devices()[:n_devices] if n_devices else jax.devices()
    n = len(devices)
    pp, dp, tp = topology or factorize_mesh(n)
    dp_size, dp_names, dp_sizes = dp_axes_of(dp)
    assert pp * dp_size * tp == n, (
        f"topology {(pp, dp, tp)} does not factor {n} devices")
    # apply_query_key_layer_scaling off: its coeff is the GLOBAL layer
    # number, which is stage-dependent — a non-uniform static in the SPMD
    # stage program (every stage runs one compiled trunk here)
    cfg = cfg or TransformerConfig(
        hidden_size=64, num_layers=2 * pp, num_attention_heads=4,
        vocab_size=128, max_position_embeddings=seq_len,
        hidden_dropout=0.0, attention_dropout=0.0, bf16=True,
        apply_query_key_layer_scaling=False)
    mesh = Mesh(np.asarray(devices).reshape(pp, *dp_sizes, tp),
                (PIPELINE_AXIS, *dp_names, TENSOR_AXIS))
    dp_axes = dp_axis_arg(dp_names)

    _, init_params = make_gpt_fns(cfg, pp)
    zero_mode, _ = _resolve_zero_overlap(zero_stage, None, pp)
    step, tx, scaler = gpt_train_step_fn(cfg, pp, num_microbatches,
                                         with_grad_norm=return_grad_norms,
                                         dp_axes=dp_axes,
                                         zero_stage=zero_stage,
                                         compress=compress,
                                         hierarchical=hierarchical)

    global_mb = micro_batch_size * dp_size
    batch = toy_batch(cfg.vocab_size, num_microbatches, global_mb, seq_len)

    def whole_run(batch):
        from apex_tpu.parallel import zero3 as zero3_mod

        params = init_params(jax.random.PRNGKey(0),
                             {k: v[0] for k, v in batch.items()})
        if zero_mode == 3:
            params = zero3_mod.shard_params(params, dp_axes)
        opt_state = tx.init(params)
        scaler_state = scaler.init()
        losses, gnorms = [], []
        for _ in range(num_steps):
            out = step(params, opt_state, scaler_state, batch)
            params, opt_state, scaler_state, loss = out[:4]
            losses.append(lax.pmean(loss, dp_axes))
            if return_grad_norms:
                gnorms.append(out[4])
        if return_grad_norms:
            return jnp.stack(losses), jnp.stack(gnorms)
        return jnp.stack(losses)

    out_specs = (P(), P()) if return_grad_norms else P()
    spec = _dp_spec(dp_names)
    f = jax.jit(jax.shard_map(
        whole_run, mesh=mesh,
        in_specs=({"ids": P(None, spec), "labels": P(None, spec)},),
        out_specs=out_specs, check_vma=False))
    out = jax.block_until_ready(f(batch))
    if return_grad_norms:
        return ([float(x) for x in np.asarray(out[0])],
                [float(x) for x in np.asarray(out[1])])
    return [float(x) for x in np.asarray(out)]
