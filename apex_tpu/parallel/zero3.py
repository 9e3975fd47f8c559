"""ZeRO-3 parameter sharding: resident shards, all-gather on use.

Capability port of the parameter-sharding half of
apex/contrib/optimizers/distributed_fused_adam.py:76 (the reference's
``dwu`` flat buffer keeps each rank's parameter shard resident and
re-assembles full weights before forward; its ZeRO-2 sibling in
``apex_tpu.contrib.optimizers.distributed_fused_adam`` already ports the
gradient/optimizer-state half). The split here:

    my fp32 master shard ──all_gather──► full per-layer params  (on USE)
    full grads ──psum_scatter──► my grad shard                  (no full
                                                   grad materialization)
    my (m, v, master) shard ──adam──► master += update          (ZeRO-2
                                                   update path, as-is)

There is no terminal update all-gather: the master shard IS the resident
parameter, and the gather moves to the start of the next step's forward.
Params are bucketed per pipeline-stage layer (plus one embed and one
head bucket), so XLA's dataflow places each bucket's gather at its first
consumer instead of one monolithic prologue gather.

Every collective hop rides :mod:`apex_tpu.parallel.collectives` — plain,
int8-quantized (``compress``) and hierarchical (``hierarchical``) gathers
all compose. The quantized gather-on-use is deliberately
ERROR-FEEDBACK-FREE (``residual=None``): unlike the ZeRO-2 update
gather, whose quantization error would compound into the master copy
step after step without EF, the ZeRO-3 gather re-reads the exact fp32
master every step — the int8 error is a per-step forward perturbation
that never accumulates into state, so the parity band is flat in step
count (tests/test_zero3.py pins it).

Knob home: ``resolve_zero_stage`` — per-call ``zero_stage=`` is a demand
(raises on anything but 0/3), ``APEX_ZERO_STAGE`` is a preference
through the one-home ``tiles.env_choice`` parser. Default OFF
(dp-unsharded) per the measured-dispatch rule; the device A/B
(``zero3_gather`` plain-vs-int8-vs-hier) is queued in PERF.md §2.
"""

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.optimizers._fused import (
    get_meta,
    zero_grad_shard,
    zero_master_shard,
    zero_padded_total,
)


def _collectives():
    from apex_tpu.parallel import collectives
    return collectives


# ------------------------------------------------------------- knob home

def resolve_zero_stage(per_call=None):
    """The ONE resolution of the ZeRO stage the minimal training wiring
    runs at: 0 (dp-unsharded params — the committed default) or 3
    (gather-on-use parameter sharding, this module).

    Per-call values are demands: anything but 0/3 raises (stages 1/2
    live in the contrib optimizers, not in this knob — an explicit
    request for them here is un-honorable, not a fallback). ``None``
    consults the ``APEX_ZERO_STAGE`` env preference via the one-home
    ``tiles.env_choice`` parser (unknown values warn once and fall back
    to 0 — preference semantics)."""
    if per_call is not None:
        if isinstance(per_call, bool) or per_call not in (0, 3):
            raise ValueError(
                f"zero_stage must be 0 or 3 (stages 1/2 are the contrib "
                f"ZeRO optimizers, not a training-wiring knob), "
                f"got {per_call!r}")
        return per_call
    from apex_tpu.dispatch import tiles as _tiles

    v = _tiles.env_choice("APEX_ZERO_STAGE", ("0", "3"))
    return int(v) if v is not None else 0


# --------------------------------------------------------- the pytree

class Zero3Spec(NamedTuple):
    """Static bucket metadata (hashable: ``FlatMeta`` instances come out
    of the ``get_meta`` cache, so equal shapes compare identical).

    ``keys``/``kinds`` name the buckets — one per stage layer
    (kind ``"stage"``), plus the embed and head trees — ``treedefs`` /
    ``metas`` reassemble each bucket's leaves, ``num_shards`` is the dp
    world size the shards were cut for."""

    keys: tuple
    kinds: tuple
    treedefs: tuple
    metas: tuple
    num_shards: int


class Zero3Params:
    """The resident state: one fp32 flat shard per bucket. Registered
    pytree (children = shards, aux = spec), so the existing skip-step
    ``tree_map`` selects, ``scaler.unscale`` and optimizer-state plumbing
    in :mod:`apex_tpu.transformer.testing.minimal` apply unchanged."""

    def __init__(self, spec, shards):
        self.spec = spec
        self.shards = tuple(shards)

    def tree_flatten(self):
        return self.shards, self.spec

    @classmethod
    def tree_unflatten(cls, spec, shards):
        return cls(spec, shards)


jax.tree_util.register_pytree_node(
    Zero3Params,
    lambda z: z.tree_flatten(),
    Zero3Params.tree_unflatten)


def _stage_key_order(k):
    # "layer_10" after "layer_9", not after "layer_1"
    head, _, tail = k.rpartition("_")
    return (head, int(tail)) if tail.isdigit() else (k, -1)


def _buckets_of(params):
    """``(keys, kinds, subtrees)`` for a minimal-GPT ``(sp, ep, hp)``
    params tree: one bucket per stage layer + embed + head."""
    sp, ep, hp = params
    keys, kinds, subtrees = [], [], []
    for k in sorted(sp, key=_stage_key_order):
        keys.append("stage:" + k)
        kinds.append("stage")
        subtrees.append(sp[k])
    keys += ["embed", "head"]
    kinds += ["embed", "head"]
    subtrees += [ep, hp]
    return tuple(keys), tuple(kinds), tuple(subtrees)


def shard_params(params, axis_name):
    """Cut a freshly initialized ``(sp, ep, hp)`` tree into this rank's
    resident fp32 shards (call INSIDE shard_map, right after init —
    every dp rank initializes the same full params, so the slice is
    consistent without a broadcast). Shard index over a factored
    ``(inner, outer)`` dp axis is row-major (``collectives.axes_index``),
    matching the chunk order the staged hierarchical gather emits."""
    C = _collectives()
    num_shards = C.axes_size(axis_name)
    keys, kinds, subtrees = _buckets_of(params)
    treedefs, metas, shards = [], [], []
    for sub in subtrees:
        leaves, treedef = jax.tree_util.tree_flatten(sub)
        meta = get_meta(leaves)
        treedefs.append(treedef)
        metas.append(meta)
        shards.append(zero_master_shard(meta, leaves, num_shards,
                                        axis_name))
    spec = Zero3Spec(keys, kinds, tuple(treedefs), tuple(metas),
                     num_shards)
    return Zero3Params(spec, shards)


def gather_params(z3, axis_name, compress=None, hierarchical=None):
    """All-gather every bucket's full weights from the resident shards
    and reassemble the ``(sp, ep, hp)`` tree the model consumes — the
    gather-on-use hop. ``residual=None`` ALWAYS: params are re-gathered
    fresh from the fp32 master each step, so quantization error is a
    per-step perturbation, never accumulated state (module docstring).
    ``compress``/``hierarchical`` ride to
    ``collectives.all_gather_flat`` as per-call forms (None = the
    process-wide APEX_GRAD_COMPRESS / APEX_HIER_ALLREDUCE
    preferences); the quantized gather's result is bitwise replicated
    across ranks, so no dp divergence enters the forward."""
    spec = z3.spec
    sp = {}
    ep = hp = None
    for key, kind, treedef, meta, shard in zip(
            spec.keys, spec.kinds, spec.treedefs, spec.metas, z3.shards):
        full, _ = _collectives().all_gather_flat(
            shard, axis_name, compress=compress,
            hierarchical=hierarchical, residual=None)
        leaves = meta.unflatten(full.astype(jnp.float32)[:meta.total])
        sub = jax.tree_util.tree_unflatten(treedef, leaves)
        if kind == "stage":
            sp[key[len("stage:"):]] = sub
        elif kind == "embed":
            ep = sub
        else:
            hp = sub
    return sp, ep, hp


def grad_shards(grads, spec, axis_name, compress=None, hierarchical=None):
    """Reduce-scatter the full ``(gs, ge, gh)`` grads straight into
    per-bucket flat shards (each rank gets the dp SUM of its slice; the
    caller divides for averaging) — no full flat gradient is ever
    materialized: each bucket flattens and scatters independently.
    Stateless like the step-fn grad sync (no EF residual is threaded —
    the step signature stays fixed; EF-carried compression lives in the
    contrib ZeRO optimizers, whose state holds the residual). Returns a
    ``Zero3Params`` over the SAME spec, so downstream unscale/update/
    select plumbing treats grads and params uniformly."""
    _, _, subtrees = _buckets_of(grads)
    shards = []
    for meta, sub in zip(spec.metas, subtrees):
        leaves = jax.tree_util.tree_leaves(sub)
        shard, _ = zero_grad_shard(meta, leaves, spec.num_shards,
                                   axis_name, compress=compress,
                                   hierarchical=hierarchical,
                                   residual=None)
        shards.append(shard)
    return Zero3Params(spec, shards)


def shard_sq_norms(z3, axis_name):
    """Per-bucket per-tensor sum-of-squares of this rank's shards
    (``[num_tensors]`` each) — the grad-norm substrate: psum over dp
    re-assembles each tensor's full sq-norm, and the caller weights
    tp-sharded tensors per :func:`minimal._is_tp_sharded`. The padded
    tail lands in a sentinel segment and is dropped."""
    spec = z3.spec
    idx = _collectives().axes_index(axis_name)
    out = []
    for meta, shard_vals in zip(spec.metas, z3.shards):
        P = zero_padded_total(meta.total, spec.num_shards)
        shard = P // spec.num_shards
        seg_full = jnp.concatenate([
            jnp.asarray(meta._seg),
            jnp.full((P - meta.total,), meta.num_tensors, jnp.int32)])
        seg = lax.dynamic_slice_in_dim(seg_full, idx * shard, shard)
        sq = jax.ops.segment_sum(shard_vals * shard_vals, seg,
                                 num_segments=meta.num_tensors + 1)
        out.append(sq[:meta.num_tensors])
    return tuple(out)


# ------------------------------------------------- the shard optimizer

def zero3_adam(learning_rate=1e-3, betas=(0.9, 0.999), eps=1e-8,
               weight_decay=0.0, adam_w_mode=True, bias_correction=True):
    """optax-style Adam over the resident shards — the contrib ZeRO-2
    update path (``_adam_flat`` on this rank's (g, master, m, v) slice,
    ``master += update``) minus its terminal update all-gather: the
    updated master shard simply stays resident, and the next step's
    :func:`gather_params` is the re-assembly. ``_adam_flat`` is the
    exact elementwise math the per-leaf :func:`~apex_tpu.optimizers.
    fused_adam.fused_adam` runs, so the plain-gather trajectory matches
    the unsharded step bit-for-bit (tests/test_zero3.py).

    ``init``/``update`` take/return :class:`Zero3Params` (grads included
    — :func:`grad_shards` output), with m/v as ``Zero3Params`` too, so
    the skip-step where-selects in the minimal wiring tree_map through
    unchanged."""
    from apex_tpu.optimizers.fused_adam import FusedAdamState, _adam_flat
    beta1, beta2 = betas

    def init(z3):
        zeros = Zero3Params(z3.spec,
                            [jnp.zeros_like(s) for s in z3.shards])
        return FusedAdamState(
            count=jnp.zeros((), jnp.int32),
            m=zeros,
            v=Zero3Params(z3.spec,
                          [jnp.zeros_like(s) for s in z3.shards]))

    def update(grads, state, params=None):
        assert params is not None, "zero3_adam requires params"
        count = state.count + 1
        lr = learning_rate(count) if callable(learning_rate) \
            else learning_rate
        us, ms, vs = [], [], []
        for g, p, m, v in zip(grads.shards, params.shards,
                              state.m.shards, state.v.shards):
            u, nm, nv = _adam_flat(
                g.astype(jnp.float32), p.astype(jnp.float32), m, v,
                count, lr, beta1, beta2, eps, weight_decay, adam_w_mode,
                bias_correction)
            us.append(u.astype(g.dtype))
            ms.append(nm)
            vs.append(nv)
        spec = params.spec
        return Zero3Params(spec, us), FusedAdamState(
            count=count, m=Zero3Params(spec, ms),
            v=Zero3Params(spec, vs))

    import optax

    return optax.GradientTransformation(init, update)


# ---------------------------------------------- the capability rung

def capability_config():
    """The committed big-model rung (ISSUE 18): a GPT whose UNSHARDED
    serving weights alone cannot fit one v5e — ~22.0B params (48 layers
    × hidden 6144 × 48 heads, GPT-2 vocab), 88.1 GiB in the serving
    path's fp32 param tree vs the 16 GiB ``costs.
    V5E_HBM_CAPACITY_BYTES`` (bf16 weights alone would still be
    44 GiB, 2.8× over). :func:`capability_costs` commits that arithmetic
    as a validated costs block; the quantitative infeasibility argument
    + escape hatch + queued speed A/Bs live in PERF.md §2/§11 per the
    CLAUDE.md capability-default exception. Trainable under
    ``zero_stage=3`` (shard: 1/dp of the fp32 state) and serveable
    under ``ServingEngine(tp=...)``; the dp=8/tp∈{2,4} CPU-mesh tests
    drive a scaled-down twin through the SAME code paths."""
    from apex_tpu.transformer.testing import TransformerConfig

    return TransformerConfig(
        hidden_size=6144, num_layers=48, num_attention_heads=48,
        vocab_size=50304, max_position_embeddings=2048,
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=True)


def capability_costs(cfg=None, page_size=16, num_pages=64):
    """The infeasibility argument as a validated ``costs`` block —
    NOTHING is materialized: ``jax.eval_shape`` walks the serving param
    init and KV-cache shapes, and their byte total lands as the block's
    argument size, a strict LOWER bound on unsharded serving peak HBM
    (no activations, no workspace, no XLA temps). Returns ``(block,
    verdict)`` where ``verdict = costs.starvation(peak_hbm_bytes,
    costs.V5E_KIND)`` — ``"exceeds-hbm"`` for :func:`capability_config` is the
    committed proof that the unsharded path cannot run at this scale at
    all (the CLAUDE.md OOM-class capability exception)."""
    import functools

    import numpy as np

    from apex_tpu.serving import kv_cache as _kv
    from apex_tpu.serving import model as _smodel
    from apex_tpu.telemetry import costs as _costs

    cfg = cfg or capability_config()

    def nbytes(tree):
        return int(sum(int(np.prod(x.shape)) * jnp.dtype(x.dtype).itemsize
                       for x in jax.tree_util.tree_leaves(tree)))

    param_shapes = jax.eval_shape(
        functools.partial(_smodel.init_gpt_params, cfg))
    cache_shapes = jax.eval_shape(functools.partial(
        _kv.init_cache, cfg.num_layers, cfg.num_attention_heads,
        num_pages, page_size, cfg.head_dim,
        jnp.bfloat16 if cfg.bf16 else jnp.float32))
    arg_bytes = nbytes(param_shapes) + nbytes(cache_shapes)
    block = _costs.build(
        memory={"argument_size_in_bytes": arg_bytes,
                "output_size_in_bytes": 0, "temp_size_in_bytes": 0,
                "generated_code_size_in_bytes": 0,
                "alias_size_in_bytes": 0},
        device_kind=_costs.V5E_KIND, source="eval_shape")
    return block, _costs.starvation(block["peak_hbm_bytes"],
                                    _costs.V5E_KIND)
