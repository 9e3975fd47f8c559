"""The seam between ``ServingEngine`` and a model family.

The engine owns the round (scheduler, allocator, staging, fetch, spans);
a family owns what the round dispatches: the check of its config, its
weights, its cache, its prefill and decode programs, and which engine
options it can honour. The family is chosen by the config object handed
to the engine (``cfg.serving_family``, ``"gpt2"`` where a config does
not say) and by nothing else: no knob, no environment variable.

A family is a :class:`Family` of plain functions:

* ``check_config(cfg)``: raise on what the programs do not model;
* ``init_params(cfg, seed)``: weights when the caller brings none;
* ``cache_dtype(cfg)``, ``init_cache(cfg, geometry)``: the KV state for
  the engine's :class:`Geometry` (slots, pages, page size, cache dtype,
  ``kv_quant``);
* ``prefill(params, cache, ids, positions, seg, token_rows, page_table,
  last_idx, keep_scale, *, cfg, kernels)`` -> ``(cache, logits[,
  extras])``; ``extras`` as ``decode_step``'s, turned into attributes of
  the ``prefill.fetch`` span by ``prefill_attrs(cfg, extras,
  trunk_rows)`` (``trunk_rows``: the row count the dispatch's trunk ran
  on);
* ``decode_step(params, cache, tokens, lengths, page_table, *, cfg,
  qparams, kernels)`` -> ``(cache, next_tokens, logits[, extras])``;
  ``extras`` is a dict of small device arrays the round fetches with
  its tokens and ``fetch_attrs(extras)`` turns into span attributes;
* ``prefill_rows(S)``: the row counts at which the family's prefill
  program can stop its trunk for ``S`` packed rows (ascending, ending
  in ``S``); the engine names the one a batch will take on the
  ``prefill.pack`` span;
* ``decode_block`` (K steps in one dispatch) and
  ``quantize_decode_params``, or None where the family has none;
* ``decode_attention(cfg, cache, kernels)`` -> the impl ("pallas" |
  "jnp") the decode program is built with
  (``engine.decode_attn_impl``);
* ``refused``: the engine options the family cannot honour, by name. A
  per-call demand of one raises at engine build, naming it; an
  environment preference for one is dropped (CLAUDE.md: explicit request
  != preference);
* ``round_attrs(cfg, scheduler)``: more attributes of ``engine.round``,
  or None;
* ``one_prefill_a_round``: the serial round admits at most one prefill
  dispatch's tokens (``prefill_len``); the rest of the queue waits a
  round, so the decode lanes are never held for a second dispatch;
* ``model_type``, ``config_from_dict``: the published ``model_type``
  whose ``config.json`` the family reads, and the constructor of its
  config object from such a dict (:func:`config_from_dict` finds the
  family by the first); None where a family has no published dict form.

A family is handed values, never the engine: ``kernels`` is the
:class:`Kernels` the caller asked for (``decode_impl``,
``interpret``). The ``*_attrs`` functions run only while the span
recorder is on.
"""

import dataclasses
import functools
from typing import Callable, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Geometry:
    """What a family's cache is sized by."""
    num_slots: int
    num_pages: int
    page_size: int
    cache_dtype: object
    kv_quant: bool = False


@dataclasses.dataclass(frozen=True)
class Kernels:
    """The caller's per-call demands on a family's attention kernels
    (None: the family's own rule)."""
    decode_impl: Optional[str] = None
    interpret: Optional[bool] = None


@dataclasses.dataclass(frozen=True)
class Family:
    name: str
    check_config: Callable
    init_params: Callable
    cache_dtype: Callable
    init_cache: Callable
    prefill: Callable
    prefill_rows: Callable
    decode_step: Callable
    decode_attention: Callable
    decode_block: Optional[Callable] = None
    quantize_decode_params: Optional[Callable] = None
    fetch_attrs: Optional[Callable] = None
    prefill_attrs: Optional[Callable] = None
    round_attrs: Optional[Callable] = None
    refused: Tuple[str, ...] = ()
    # admission stops at one prefill dispatch's tokens a round, the rest
    # of the queue waits for the next (scheduler.admit's token_budget)
    one_prefill_a_round: bool = False
    # the published model_type the family reads and the constructor of
    # its config object from a dict of the published keys
    model_type: Optional[str] = None
    config_from_dict: Optional[Callable] = None


def prefill_rows(S, tile=8):
    """The row counts at which a family's ONE prefill program can stop
    its trunk: the packed ``S`` and its exact halvings down to an eighth,
    as far as they stay whole multiples of ``tile`` (8: a sublane tile;
    a family whose attention kernel wants more says so), and always
    ``S`` itself, so every ``S`` has a count. A packed batch's tokens
    lie first, so a batch of ``n`` tokens runs the trunk on the smallest
    count that holds it (a ``lax.switch`` inside the program): one
    program, whose work follows what the round packed."""
    return tuple(S >> j for j in (3, 2, 1) if S % (tile << j) == 0) + (S,)


def switch_on_rows(rows, trunk_on, ids, positions, seg):
    """Run ``trunk_on(R)(ids, positions, seg)`` for the smallest ``R`` of
    ``rows`` (a family's :func:`prefill_rows`) that holds the packed
    batch's tokens (``seg > 0``; they lie first): ONE ``lax.switch`` on
    their count, every branch returning the same shapes. No branch
    carries a cache: a family writes its K/V (or latent) rows behind the
    switch."""
    import jax.numpy as jnp
    from jax import lax

    tokens = jnp.sum((seg > 0).astype(jnp.int32))
    return lax.switch(
        sum((tokens > R).astype(jnp.int32) for R in rows[:-1]),
        [trunk_on(R) for R in rows], ids, positions, seg)


# what each refusable option looks like when it is OFF, as the per-call
# argument that switches it off whatever the environment prefers
OPTIONS_OFF = {"tp": 1, "weight_quant": False, "kv_quant": False,
               "kv_swap": False, "prefix_cache": False, "spec_decode": 0,
               "decode_k": 1, "overlap": False}


def settle_options(family, options):
    """``options`` (the engine's per-call arguments, by name) with every
    option the family refuses switched off; raises ``ValueError`` naming
    each refused option the caller DEMANDED (a value other than None and
    other than off)."""
    demanded = [name for name in family.refused
                if options.get(name) not in (None, OPTIONS_OFF[name])]
    if demanded:
        raise ValueError(
            f"the {family.name} serving family cannot honour: "
            + ", ".join(f"{n}={options[n]!r}" for n in demanded))
    return {**options, **{n: OPTIONS_OFF[n] for n in family.refused}}


# ------------------------------------------------------------------ GPT-2

def _gpt2():
    from apex_tpu.ops import decode_attention_pallas as dap
    from apex_tpu.serving import kv_cache
    from apex_tpu.serving import model as smodel

    def init_cache(cfg, geometry):
        return kv_cache.init_cache(
            cfg.num_layers, cfg.num_attention_heads, geometry.num_pages,
            geometry.page_size, cfg.head_dim, geometry.cache_dtype,
            kv_quant=geometry.kv_quant)

    def prefill(params, cache, ids, positions, seg, token_rows, page_table,
                last_idx, keep_scale=None, *, cfg, kernels):
        return smodel.prefill(params, cache, ids, positions, seg,
                              token_rows, page_table, last_idx, keep_scale,
                              cfg=cfg)

    def decode_step(params, cache, tokens, lengths, page_table, *, cfg,
                    qparams, kernels):
        return smodel.decode_step(params, cache, tokens, lengths,
                                  page_table, cfg=cfg, qparams=qparams,
                                  **dataclasses.asdict(kernels))

    def decode_block(params, cache, tokens, lengths, page_table, steps,
                     warm_tokens, warm_steps, lanes, *, k, cfg, qparams,
                     kernels):
        return smodel.decode_block(
            params, cache, tokens, lengths, page_table, steps, warm_tokens,
            warm_steps, lanes=lanes, k=k, cfg=cfg, qparams=qparams,
            **dataclasses.asdict(kernels))

    def decode_attention(cfg, cache, kernels):
        leaf = cache["k"][0]
        return dap.grouped_resolved(
            cfg.num_attention_heads, cfg.num_attention_heads, cfg.head_dim,
            cfg.head_dim, leaf.shape[1], leaf.dtype, kernels.decode_impl)

    return Family(
        name="gpt2", check_config=smodel.check_serving_config,
        init_params=smodel.init_gpt_params,
        cache_dtype=smodel.compute_dtype, init_cache=init_cache,
        prefill=prefill, prefill_rows=smodel.trunk_rows,
        decode_step=decode_step,
        decode_block=decode_block, decode_attention=decode_attention,
        quantize_decode_params=smodel.quantize_decode_params)


# ------------------------------------------------------------------- MiMo

def _expert_attrs(extras):
    """``engine.round``'s expert counters, from a decode program's
    ``expert_tokens [moe layers, held]`` (MiMo's and A.X-K1's)."""
    counts = np.asarray(extras["expert_tokens"])
    return dict(experts_touched=int((counts > 0).sum()),
                experts_held=int(counts.size),
                expert_tokens_max=int(counts.max(initial=0)),
                expert_tokens_sum=int(counts.sum()))


def _held_row_attrs(cfg, extras, trunk_rows):
    """``prefill.fetch``'s account of the expert layers' row bound
    (``transformer.moe.held_experts_mlp``), from a prefill program's
    ``expert_tokens``: the largest count of held assignments a layer
    saw, the rows the bound gives the dispatch's trunk (by the function
    the program takes it from), and how many layers were over it and so
    worked on every row."""
    from apex_tpu.transformer.moe import held_row_bound

    counts = np.asarray(extras["expert_tokens"])
    held = counts.sum(axis=1)
    rows = held_row_bound(trunk_rows, cfg.num_experts_per_tok,
                          counts.shape[1], cfg.n_routed_experts)
    return dict(held_rows_max=int(held.max(initial=0)), expert_rows=rows,
                expert_rows_full=int((held > rows).sum()))


def _attend_step_attrs(trunk_rows, layers):
    """``prefill.fetch``'s account of the packed attention kernel's grid
    (``ops.attention.packed_attention_grid``): the steps the dispatch's
    layers took, a group of heads on a live block pair each, and what a
    grid of one step a (head, q block, k block) would have taken.
    ``layers``: ``(hq, n_kv, dk, dv, window, selected)`` a layer. Both 0
    where the jnp form runs."""
    from apex_tpu.ops.attention import packed_attention_grid

    steps = [packed_attention_grid(trunk_rows, hq, n_kv, dk, dv,
                                   window=window, selected=selected)
             for hq, n_kv, dk, dv, window, selected in layers]
    return dict(attend_steps=sum(s for s, _ in steps),
                attend_steps_dense=sum(d for _, d in steps))


def _mimo():
    import jax.numpy as jnp

    from apex_tpu.serving import mimo

    def init_cache(cfg, geometry):
        return mimo.init_cache(cfg, geometry.num_slots, geometry.num_pages,
                               geometry.page_size, geometry.cache_dtype)

    def prefill(params, cache, ids, positions, seg, token_rows, page_table,
                last_idx, keep_scale=None, *, cfg, kernels):
        return mimo.prefill(params, cache, ids, positions, seg, token_rows,
                            page_table, last_idx, cfg=cfg,
                            interpret=kernels.interpret)

    def decode_step(params, cache, tokens, lengths, page_table, *, cfg,
                    qparams, kernels):
        return mimo.decode_step(params, cache, tokens, lengths, page_table,
                                cfg=cfg, decode_impl=kernels.decode_impl,
                                interpret=kernels.interpret)

    def decode_attention(cfg, cache, kernels):
        return mimo.decode_attention_resolved(
            cfg, cache, kernels.decode_impl)

    def round_attrs(cfg, scheduler):
        # what the round's decode read of each kind of state: pages of
        # the pool that hold context (not the reserved ones), ring pages
        # that hold part of a window
        live, ring = scheduler.context_pages(cfg.sliding_window)
        return dict(global_pages_live=live, window_pages=ring)

    def prefill_attrs(cfg, extras, trunk_rows):
        layers = [(cfg.num_attention_heads, *mimo.layer_geometry(cfg, window),
                   cfg.sliding_window if window else None, False)
                  for window in map(bool, cfg.hybrid_layer_pattern)]
        return dict(_held_row_attrs(cfg, extras, trunk_rows),
                    **_attend_step_attrs(trunk_rows, layers))

    return Family(
        name="mimo", check_config=mimo.check_config,
        init_params=mimo.init_params,
        cache_dtype=lambda cfg: jnp.dtype(cfg.cache_dtype),
        init_cache=init_cache,
        prefill=prefill, prefill_rows=prefill_rows,
        decode_step=decode_step,
        decode_attention=decode_attention, fetch_attrs=_expert_attrs,
        prefill_attrs=prefill_attrs,
        round_attrs=round_attrs, refused=tuple(OPTIONS_OFF),
        one_prefill_a_round=True, model_type="mimo_v2",
        config_from_dict=mimo.MiMoConfig.from_dict)


# ------------------------------------------------------------------ A.X-K1

def _axk1():
    import jax.numpy as jnp

    from apex_tpu.serving import axk1

    def init_cache(cfg, geometry):
        return axk1.init_cache(cfg, geometry.num_pages, geometry.page_size,
                               geometry.cache_dtype)

    def prefill(params, cache, ids, positions, seg, token_rows, page_table,
                last_idx, keep_scale=None, *, cfg, kernels):
        return axk1.prefill(params, cache, ids, positions, seg, token_rows,
                            page_table, last_idx, cfg=cfg,
                            interpret=kernels.interpret)

    def decode_step(params, cache, tokens, lengths, page_table, *, cfg,
                    qparams, kernels):
        return axk1.decode_step(params, cache, tokens, lengths, page_table,
                                cfg=cfg, decode_impl=kernels.decode_impl,
                                interpret=kernels.interpret)

    def decode_attention(cfg, cache, kernels):
        return axk1.decode_attention_resolved(
            cfg, cache, kernels.decode_impl)

    def round_attrs(cfg, scheduler):
        # pages of the pool that hold context: each is one latent page a
        # layer, read once by the round's decode
        live, _ = scheduler.context_pages()
        return dict(latent_pages_live=live)

    def prefill_attrs(cfg, extras, trunk_rows):
        h = cfg.num_attention_heads     # expanded: a KV head a query head
        layers = [(h, h, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
                   cfg.v_head_dim, None, False)] * cfg.num_layers
        return dict(_held_row_attrs(cfg, extras, trunk_rows),
                    **_attend_step_attrs(trunk_rows, layers))

    return Family(
        name="axk1", check_config=axk1.check_config,
        init_params=axk1.init_params,
        cache_dtype=lambda cfg: jnp.dtype(cfg.cache_dtype),
        init_cache=init_cache,
        prefill=prefill, prefill_rows=prefill_rows,
        decode_step=decode_step,
        decode_attention=decode_attention, fetch_attrs=_expert_attrs,
        prefill_attrs=prefill_attrs,
        round_attrs=round_attrs, refused=tuple(OPTIONS_OFF),
        one_prefill_a_round=True, model_type="axk1",
        config_from_dict=axk1.AXK1Config.from_dict)


# ------------------------------------------------------------- dots3-note

def _dots3():
    import jax.numpy as jnp

    from apex_tpu.serving import dots3

    def init_cache(cfg, geometry):
        return dots3.init_cache(cfg, geometry.num_slots, geometry.num_pages,
                                geometry.page_size, geometry.cache_dtype)

    def prefill(params, cache, ids, positions, seg, token_rows, page_table,
                last_idx, keep_scale=None, *, cfg, kernels):
        return dots3.prefill(params, cache, ids, positions, seg, token_rows,
                             page_table, last_idx, cfg=cfg,
                             interpret=kernels.interpret)

    def decode_step(params, cache, tokens, lengths, page_table, *, cfg,
                    qparams, kernels):
        return dots3.decode_step(params, cache, tokens, lengths, page_table,
                                 cfg=cfg, decode_impl=kernels.decode_impl,
                                 interpret=kernels.interpret)

    def decode_attention(cfg, cache, kernels):
        return dots3.decode_attention_resolved(
            cfg, cache, kernels.decode_impl)

    def fetch_attrs(extras):
        # what the round's decode read: the context rows a full layer's
        # indexer scored, the rows its attention then gathered, the ring
        # rows inside a sliding layer's window
        return dict(_expert_attrs(extras), **{
            name: int(extras[name]) for name in (
                "index_rows_scored", "sparse_rows_selected", "window_rows")})

    def prefill_attrs(cfg, extras, trunk_rows):
        # the dispatch's (query, key) pairs: every pair the indexer has
        # to score, and those the selection leaves to attend to
        layers = [(kd.heads, kd.heads, kd.nope + kd.rope, kd.dv, kd.window,
                   kd.window is None and trunk_rows > cfg.index_topk)
                  for kd, _ in dots3.layer_kinds(cfg)]
        return dict(_held_row_attrs(cfg, extras, trunk_rows),
                    **_attend_step_attrs(trunk_rows, layers),
                    index_pairs=int(extras["index_pairs"]),
                    sparse_pairs=int(extras["sparse_pairs"]))

    def round_attrs(cfg, scheduler):
        # pool pages that hold context (a latent and an index-key page a
        # full layer each)
        live, _ = scheduler.context_pages()
        return dict(latent_pages_live=live)

    return Family(
        name="dots3", check_config=dots3.check_config,
        init_params=dots3.init_params,
        cache_dtype=lambda cfg: jnp.dtype(cfg.cache_dtype),
        init_cache=init_cache,
        prefill=prefill, prefill_rows=prefill_rows,
        decode_step=decode_step,
        decode_attention=decode_attention, fetch_attrs=fetch_attrs,
        prefill_attrs=prefill_attrs,
        round_attrs=round_attrs, refused=tuple(OPTIONS_OFF),
        one_prefill_a_round=True, model_type="dots3_note",
        config_from_dict=dots3.Dots3Config.from_dict)


_BUILDERS = {"gpt2": _gpt2, "mimo": _mimo, "axk1": _axk1, "dots3": _dots3}


@functools.lru_cache(maxsize=None)
def _built(name):
    return _BUILDERS[name]()   # imports the family's modules on first use


def config_from_dict(d):
    """The config object of a configuration dict with the published key
    names, from the family that reads its ``model_type``: what
    :func:`family_of` and ``ServingEngine`` take."""
    known = {f.model_type: f for f in map(_built, _BUILDERS) if f.model_type}
    if d.get("model_type") not in known:
        raise ValueError(f"no serving family reads model_type "
                         f"{d.get('model_type')!r} (known: {sorted(known)})")
    return known[d["model_type"]].config_from_dict(d)


def family_of(cfg):
    """The family that serves ``cfg``: its ``serving_family`` attribute,
    ``"gpt2"`` where it has none (``TransformerConfig``)."""
    name = getattr(cfg, "serving_family", "gpt2")
    if name not in _BUILDERS:
        raise ValueError(f"no serving family {name!r} "
                         f"(known: {sorted(_BUILDERS)})")
    return _built(name)
