"""Tensor-parallel serving shardings (ISSUE 18): the capability rung.

Reference surface: apex/transformer/tensor_parallel/layers.py:256
(ColumnParallelLinear) and apex/transformer/tensor_parallel/layers.py:452
(RowParallelLinear) — Megatron's column/row split, re-expressed as
GSPMD shardings instead of hand-written collectives. The serving
engine's two jitted programs are UNTOUCHED: the ONLY change at
``ServingEngine(tp=)`` > 1 is that the params and the paged KV cache
are ``device_put`` with :class:`~jax.sharding.NamedSharding` over a
``tp`` mesh, and GSPMD partitions the SAME prefill/decode jaxprs from
those committed input shardings. Host-side scheduling, page
accounting, sampling lanes and the one-compile contract
(``decode_cache_size()==1`` / ``prefill_cache_size()<=1``) are
mesh-invariant by construction — the mesh is a build-time constant
and every per-round input keeps its shape and sharding.

The split (Megatron pairing, whole heads per shard — demands a
``num_attention_heads % tp == 0`` config):

* ``query_key_value`` ``[3h, h]`` — COLUMN-parallel on the fused
  output dim. The per-head ``[q|k|v]`` interleaving
  (:func:`model._split_qkv` reshapes to ``[rows, np, 3*hd]``) makes a
  contiguous block of ``3h/tp`` rows exactly ``n_heads/tp`` whole
  heads, so attention stays head-local. Bias follows the output dim.
* ``self_attention.dense`` ``[h, h]`` — ROW-parallel on the input
  dim (the per-head context it consumes); the psum GSPMD inserts is
  Megatron's RowParallel all-reduce. Bias replicated (added once,
  after the reduction).
* ``mlp.dense_h_to_4h`` ``[4h, h]`` — column-parallel (+ bias);
  ``mlp.dense_4h_to_h`` ``[h, 4h]`` — row-parallel (bias replicated).
* Embeddings, layernorms, everything else — replicated. The logits
  matmul against the replicated word table is vocab-unsharded (the
  v5e HBM pressure is the 48-layer trunk, not the 50304-row table).
* KV cache, ``[pages, page_size, heads * head_dim]`` a layer (and the
  int8 tier's ``[pages, heads]`` scales) — sharded on its LAST axis:
  heads are contiguous blocks of it, so each chip holds its own
  heads' columns of every page and the decode gather never crosses
  chips.

Knob home (the CLAUDE.md asymmetry): per-call ``ServingEngine(tp=)``
is a DEMAND — un-honorable values (non-int, tp < 1, tp > visible
devices, ``n_heads % tp != 0``) raise here; the ``APEX_SERVE_TP`` env
preference rides the one-home :func:`tiles.env_int` parser and falls
back to tp=1 per shape. Default tp=1 (single-chip engine,
byte-identical to the pre-TP build) per the measured-dispatch rule —
the ``serving_tp`` A/B is queued in PERF.md §2; the capability
exception (the committed ~22B :func:`zero3.capability_config` whose
costs block PROVES peak_hbm > v5e HBM) is argued in PERF.md per the
CLAUDE.md capability-default rule.
"""

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from apex_tpu.dispatch import tiles as _tiles
from apex_tpu.transformer.parallel_state import TENSOR_AXIS


def resolve_serve_tp(tp=None, *, n_heads, n_devices=None):
    """Resolve the serving tensor-parallel width.

    Per-call ``tp=`` is a demand: raises on non-positive-int values,
    on ``tp`` exceeding the visible device count, and on a head count
    the whole-heads split cannot honor. ``None`` defers to the
    ``APEX_SERVE_TP`` env preference (one-home
    :func:`tiles.env_int`), which falls back to 1 when un-honorable
    — preference semantics, never a raise."""
    if n_devices is None:
        n_devices = len(jax.devices())
    if tp is not None:
        if isinstance(tp, bool) or not isinstance(tp, int) or tp < 1:
            raise ValueError(
                f"tp= wants a positive int, got {tp!r}")
        if tp > n_devices:
            raise ValueError(
                f"tp={tp} cannot be honored: only {n_devices} "
                f"device(s) visible")
        if n_heads % tp:
            raise ValueError(
                f"tp={tp} cannot be honored: num_attention_heads="
                f"{n_heads} does not split into whole heads per chip")
        return tp
    v = _tiles.env_int("APEX_SERVE_TP")
    if v is None or v == 1:
        return 1
    if v > n_devices or n_heads % v:
        return 1  # env preference: falls back per shape
    return v


def mesh_for(tp):
    """One-axis ``(TENSOR_AXIS,)`` mesh over the first ``tp`` visible
    devices — the build-time constant every sharding below names."""
    return Mesh(np.asarray(jax.devices()[:tp]), (TENSOR_AXIS,))


def _param_spec(path, leaf):
    """PartitionSpec for one serving-param leaf, by tree path (the
    module-docstring split table)."""
    keys = {getattr(k, "key", None) for k in path}
    col = ("query_key_value" in keys or "dense_h_to_4h" in keys)
    row = (("dense" in keys and "self_attention" in keys)
           or "dense_4h_to_h" in keys)
    if col:
        return P(TENSOR_AXIS, None) if leaf.ndim == 2 \
            else P(TENSOR_AXIS)
    if row and leaf.ndim == 2:
        return P(None, TENSOR_AXIS)
    return P()  # row-parallel bias, embeddings, norms: replicated


def param_shardings(params, mesh):
    """NamedSharding tree matching ``params`` (the serving GPT tree of
    :func:`model.init_gpt_params`) for ``device_put``."""
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(mesh, _param_spec(path, leaf)),
        params)


def cache_shardings(cache, mesh):
    """NamedSharding tree for the paged KV cache: every leaf sharded on
    its last axis, whose contiguous blocks are whole heads
    (``[pages, page_size, h * d]`` codes, ``[pages, h]`` scales)."""
    return jax.tree.map(
        lambda leaf: NamedSharding(
            mesh, P(*(None,) * (leaf.ndim - 1), TENSOR_AXIS)), cache)


def qparams_shardings(qparams, mesh):
    """NamedSharding tree for the int8 decode records
    (:func:`model.quantize_decode_params`) — the tp x weight_quant
    composition (ISSUE 20 satellite). Each record's ``wq`` is
    ``[out, in]`` and its ``scale`` is per-OUT-channel, so the specs
    follow the float split table exactly:

    * column-parallel records (``qkv``, ``h4``): ``wq``
      ``P(TENSOR_AXIS, None)`` — the out dim is the sharded fused
      output (whole heads per shard for qkv, 4h/tp rows for h4;
      both divide because ``n_heads % tp == 0`` forces ``h % tp ==
      0``) — and ``scale`` ``P(TENSOR_AXIS)`` rides the same dim.
    * row-parallel records (``dense``, ``4h``): ``wq``
      ``P(None, TENSOR_AXIS)`` on the in dim; ``scale`` replicated
      ``P()`` (it lands on the UNSHARDED output columns after the
      GSPMD psum, exactly like the row-parallel float bias).
    * ``word_logits``: replicated — the float word table is
      replicated and the logits matmul vocab-unsharded (module
      docstring), so its int8 copy keeps that layout.
    """
    col_wq = NamedSharding(mesh, P(TENSOR_AXIS, None))
    col_sc = NamedSharding(mesh, P(TENSOR_AXIS))
    row_wq = NamedSharding(mesh, P(None, TENSOR_AXIS))
    rep = NamedSharding(mesh, P())
    spec = {"layers": [
        {"qkv": {"wq": col_wq, "scale": col_sc},
         "h4": {"wq": col_wq, "scale": col_sc},
         "dense": {"wq": row_wq, "scale": rep},
         "4h": {"wq": row_wq, "scale": rep}}
        for _ in qparams["layers"]],
        "word_logits": {"wq": rep, "scale": rep}}
    return spec
