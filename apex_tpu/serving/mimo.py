"""The MiMo-V2 block as a serving family: prefill and decode programs
over the two-kind KV cache (serving/kv_cache.py), for ``ServingEngine``'s
normal path (serving/family.py is the seam).

What differs from the GPT-2 family (serving/model.py), by mechanism:

* RMSNorm, bias-free projections, an untied head, SwiGLU;
* grouped-query attention with K 192 and V 128 wide, partial rotary
  (the first ``int(head_dim * partial_rotary_factor)`` dims, rotate-half)
  with one base per layer kind, ``attention_value_scale`` on V;
* layers alternate by ``hybrid_layer_pattern``: 0 = GLOBAL (every token
  kept, the paged pool), 1 = WINDOW (the last ``sliding_window`` tokens,
  a ring of pages a slot, a per-head sink logit in the softmax's
  denominator);
* the FFN is dense or, by ``moe_layer_freq``, a mixture of experts with
  sigmoid top-k routing over ALL ``n_routed_experts`` and a
  selection-only bias, of which this chip HOLDS ``held_experts = (first,
  count)``: the layer returns the partial sum over the chosen experts it
  holds (transformer/moe.py ``held_experts_mlp``; dropless), which is
  what goes on to the next layer. On one chip nothing stands in for the
  absent chips' sums;
* weights are bfloat16 as stored (norm gains, router, router bias and
  sinks float32), activations bfloat16, norms, rotary, routing and both
  softmaxes float32, logits float32.

The plain reference of these equations, with every assumed reading, is
``perf/references/mimo_v2.py``; ``tests/test_mimo_serving.py`` holds the
engine to it through both caches.

Device scopes: ``embed``, ``layer/attn_global/{qkv,rope,kv_write,attend,
out}``, ``layer/attn_window/...``, ``layer/mlp``, ``layer/moe/{route,
experts}``, ``final_norm``, ``lm_head``, ``sample``.
"""

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.serving import kv_cache
from apex_tpu.serving.family import prefill_rows, switch_on_rows
from apex_tpu.transformer import moe as moe_mod


@dataclasses.dataclass(frozen=True)
class MiMoConfig:
    """The published keys of ``config.json`` (``model_type`` ``mimo_v2``)
    that the serving programs read, plus the share this chip holds."""
    vocab_size: int
    max_position_embeddings: int
    hybrid_layer_pattern: Tuple[int, ...]   # per layer: 0 global, 1 window
    moe_layer_freq: Tuple[int, ...]         # per layer: 0 dense, 1 experts
    hidden_size: int = 4096
    num_attention_heads: int = 64
    num_key_value_heads: int = 4            # global layers
    swa_num_key_value_heads: int = 8        # window layers
    head_dim: int = 192
    v_head_dim: int = 128
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256             # the router's width
    num_experts_per_tok: int = 8
    held_experts: Tuple[int, int] = (0, 256)   # (first, count) held here
    sliding_window: int = 128
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    layernorm_epsilon: float = 1e-5
    norm_topk_prob: bool = True
    routed_scaling_factor: Optional[float] = None
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    # the KV cache's dtype; activations follow the weights' (both
    # bfloat16 as deployed; float32 weights and cache make the program a
    # float32 one, which the tests hold to the reference far tighter)
    cache_dtype: str = "bfloat16"

    serving_family = "mimo"   # serving/family.py picks the family by this

    @property
    def num_layers(self):
        return len(self.hybrid_layer_pattern)

    @classmethod
    def from_dict(cls, d):
        """From a configuration dict with the published key names. A cut
        configuration gives the held count as ``n_routed_experts`` and the
        router's width as ``published_n_routed_experts``."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in d.items() if k in names}
        kw["n_routed_experts"] = d.get("published_n_routed_experts",
                                       d["n_routed_experts"])
        kw.setdefault("held_experts", (0, d["n_routed_experts"]))
        return cls(**kw)

    def to_dict(self):
        """The dict the plain reference reads (published key names)."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}


def check_config(cfg):
    problems = []
    L = cfg.num_layers
    if L == 0 or len(cfg.moe_layer_freq) != L:
        problems.append("hybrid_layer_pattern and moe_layer_freq must "
                        "name every layer")
    for n_kv in (cfg.num_key_value_heads, cfg.swa_num_key_value_heads):
        if cfg.num_attention_heads % n_kv:
            problems.append(f"{cfg.num_attention_heads} query heads do "
                            f"not divide over {n_kv} KV heads")
    first, count = cfg.held_experts
    if not (0 <= first and count >= 1
            and first + count <= cfg.n_routed_experts):
        problems.append(f"held_experts {cfg.held_experts} outside the "
                        f"{cfg.n_routed_experts} routed experts")
    if cfg.add_full_attention_sink_bias:
        problems.append("add_full_attention_sink_bias (global layers "
                        "carry no sink here)")
    if int(cfg.head_dim * cfg.partial_rotary_factor) % 2:
        problems.append("an odd number of rotary dims")
    if problems:
        raise ValueError("serving does not support: " + "; ".join(problems))


def layer_geometry(cfg, window):
    """``(kv_heads, k_width, v_width)`` of one layer kind."""
    return ((cfg.swa_num_key_value_heads if window
             else cfg.num_key_value_heads), cfg.head_dim, cfg.v_head_dim)


# ---------------------------------------------------------------- weights

@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _normal(key, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_params(cfg, key, std=0.02, dtype=jnp.bfloat16):
    """Random weights from a PRNG key (an ARGUMENT of every program that
    makes them: a new seed compiles nothing). Matrices ``dtype``, N(0,
    ``std``); norm gains one; router float32; router bias and sinks
    float32 N(0, ``std``), so that both change results."""
    if isinstance(key, (int, np.integer)):
        key = jax.random.PRNGKey(int(key))
    H, hq, dk, dv = (cfg.hidden_size, cfg.num_attention_heads,
                     cfg.head_dim, cfg.v_head_dim)
    count = cfg.held_experts[1]
    keys = iter(jax.random.split(key, 16 * cfg.num_layers + 8))

    def mat(*shape, dtype=dtype):
        return _normal(next(keys), shape, dtype, std)

    ones = jnp.ones((H,), jnp.float32)
    params = {"embed": mat(cfg.vocab_size, H), "head": mat(cfg.vocab_size, H),
              "final_norm": ones, "layers": []}
    for window, is_moe in zip(cfg.hybrid_layer_pattern, cfg.moe_layer_freq):
        n_kv = layer_geometry(cfg, window)[0]
        lp = {"attn_norm": ones, "ffn_norm": ones,
              "wq": mat(H, hq * dk), "wk": mat(H, n_kv * dk),
              "wv": mat(H, n_kv * dv), "wo": mat(hq * dv, H)}
        if window and cfg.add_swa_attention_sink_bias:
            lp["sink"] = mat(hq, dtype=jnp.float32)
        if is_moe:
            F = cfg.moe_intermediate_size
            lp.update(router=mat(cfg.n_routed_experts, H, dtype=jnp.float32),
                      router_bias=mat(cfg.n_routed_experts,
                                      dtype=jnp.float32),
                      w_gate=mat(count, H, F), w_up=mat(count, H, F),
                      w_down=mat(count, F, H))
        else:
            F = cfg.intermediate_size
            lp.update(w_gate=mat(H, F), w_up=mat(H, F), w_down=mat(F, H))
        params["layers"].append(lp)
    return params


def init_cache(cfg, num_slots, num_pages, page_size, dtype=jnp.bfloat16):
    return kv_cache.init_hybrid_cache(
        cfg.hybrid_layer_pattern, num_pages, num_slots, page_size,
        cfg.sliding_window, layer_geometry(cfg, False),
        layer_geometry(cfg, True), dtype)


# ------------------------------------------------------------- the block

def _mm(x, w):
    """x @ w, float32 accumulation, back in x's dtype."""
    return lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)


def _rms_norm(x, gain, eps):
    xf = x.astype(jnp.float32)
    y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * gain.astype(jnp.float32)).astype(x.dtype)


def _rotary(x, positions, base, rot):
    """Rotate-half over the first ``rot`` dims of ``x [T, h, d]``."""
    half = rot // 2
    inv = jnp.float32(base) ** (
        -jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, xf[..., rot:]],
        axis=-1).astype(x.dtype)


def moe_ffn(inner, lp, cfg, valid=None, moe_impl=None, interpret=None):
    """The expert layer of one block as both programs run it (scopes
    ``route`` and ``experts`` under the caller's ``layer/moe``): route
    ``inner [T, hidden]`` over all experts (a layer with no
    ``router_bias`` takes the plain top-k of its scores), sum over the
    chosen experts that are held. Returns ``(y [T, hidden], tokens per
    held expert)``.
    Public because the benchmark's judge holds THIS function, on the
    engine's weights, to the plain reference's expert layer."""
    with jax.named_scope("route"):
        experts, weights = moe_mod.route_sigmoid_topk(
            inner, lp["router"], lp.get("router_bias"),
            cfg.num_experts_per_tok, cfg.norm_topk_prob,
            cfg.routed_scaling_factor or 1.0)
    with jax.named_scope("experts"):
        return moe_mod.held_experts_mlp(
            inner, experts, weights, lp["w_gate"], lp["w_up"],
            lp["w_down"], cfg.held_experts[0], valid=valid,
            impl=moe_impl, interpret=interpret,
            num_experts=lp["router"].shape[0])


def _layer(x, lp, cfg, window, is_moe, positions, valid, attn, moe_impl,
           interpret):
    """ONE layer, shared by prefill and decode. ``attn(q, k, v, sink)``
    owns what differs between them: the cache write of this layer's
    ``k [T, n_kv, dk]`` / ``v [T, n_kv, dv]`` and the attention itself,
    returning ``[T, heads * v_head_dim]``. ``valid [T]``: which rows are
    tokens (padding and empty lanes reach no expert). Returns ``(x,
    tokens per held expert or None)``."""
    T = x.shape[0]
    hq, dk, dv = cfg.num_attention_heads, cfg.head_dim, cfg.v_head_dim
    n_kv = layer_geometry(cfg, window)[0]
    rot = int(dk * cfg.partial_rotary_factor)
    base = cfg.swa_rope_theta if window else cfg.rope_theta
    counts = None
    with jax.named_scope("layer"):
        with jax.named_scope("attn_window" if window else "attn_global"):
            with jax.named_scope("qkv"):
                inner = _rms_norm(x, lp["attn_norm"], cfg.layernorm_epsilon)
                q = _mm(inner, lp["wq"]).reshape(T, hq, dk)
                k = _mm(inner, lp["wk"]).reshape(T, n_kv, dk)
                v = (_mm(inner, lp["wv"]).astype(jnp.float32)
                     * cfg.attention_value_scale).astype(x.dtype).reshape(
                         T, n_kv, dv)
            with jax.named_scope("rope"):
                q = _rotary(q, positions, base, rot)
                k = _rotary(k, positions, base, rot)
            ctx = attn(q, k, v, lp.get("sink"))
            with jax.named_scope("out"):
                x = x + _mm(ctx, lp["wo"])
        inner = _rms_norm(x, lp["ffn_norm"], cfg.layernorm_epsilon)
        if is_moe:
            with jax.named_scope("moe"):
                y, counts = moe_ffn(inner, lp, cfg, valid, moe_impl,
                                    interpret)
                x = x + y
        else:
            with jax.named_scope("mlp"):
                x = x + moe_mod.gated_mlp(inner, lp["w_gate"], lp["w_up"],
                                          lp["w_down"])
    return x, counts


def _trunk(params, cfg, x, positions, valid, attn_of, moe_impl, interpret):
    """Every layer; ``attn_of(i, window, index within its kind)`` gives
    the layer's ``attn``. Returns ``(x after the final norm, [moe
    layers, held] int32 tokens per held expert)``."""
    counts, seen = [], [0, 0]
    for i, lp in enumerate(params["layers"]):
        window = bool(cfg.hybrid_layer_pattern[i])
        x, c = _layer(x, lp, cfg, window, bool(cfg.moe_layer_freq[i]),
                      positions, valid, attn_of(window, seen[window]),
                      moe_impl, interpret)
        seen[window] += 1
        if c is not None:
            counts.append(c)
    with jax.named_scope("final_norm"):
        x = _rms_norm(x, params["final_norm"], cfg.layernorm_epsilon)
    held = cfg.held_experts[1]
    return x, (jnp.stack(counts) if counts
               else jnp.zeros((0, held), jnp.int32))


def _logits(x, head):
    return lax.dot_general(x, head, (((1,), (1,)), ((), ())),
                           preferred_element_type=jnp.float32)


def _write_kv(cache, kind, n, page, off, k, v):
    """This layer's ``k``/``v`` rows ``[T, n_kv, width]`` into layer ``n``
    of ``kind``'s arrays at ``(page, off)``, one row a token."""
    with jax.named_scope("kv_write"):
        for part, rows in (("_k", k), ("_v", v)):
            cache[kind + part][n] = kv_cache.write_rows(
                cache[kind + part][n], page, off, rows)


# --------------------------------------------------------------- prefill

def prefill(params, cache, ids, positions, seg, token_rows, page_table,
            last_idx, *, cfg, attn_impl=None, moe_impl=None, interpret=None):
    """One packed prompt batch through the trunk, filling both caches
    (arguments as ``serving.model.prefill`` less ``keep_scale``, the int8
    KV tier's; ``page_table`` is ``[slots + 1, max_pages]`` with the all-null spare row last, which is also what
    tells a padding token from a slot's). Global layers write every
    token at its page-table page; window layers write each segment's
    last ``sliding_window`` tokens into its slot's ring and send the rest
    to the null page. Returns ``(cache, logits [G, vocab] float32,
    {"expert_tokens": [moe layers, held] int32})``: how many of the
    batch's assignments each held expert received.

    The trunk runs on the first ``R`` of the ``S`` packed rows, ``R`` the
    smallest of ``family.prefill_rows`` that holds the batch's tokens (a
    ``lax.switch`` on their count: the rows behind them are padding,
    which no token attends to and no expert serves, so results do not
    depend on ``R``). Each layer's K and V rows come out of the branch
    padded back to ``S`` and are written here, behind the switch, so no
    branch carries a cache."""
    from apex_tpu.ops.attention import packed_gqa_attention

    cache = {name: list(arrays) for name, arrays in cache.items()}
    hq, dk, dv = cfg.num_attention_heads, cfg.head_dim, cfg.v_head_dim
    S = ids.shape[0]
    num_slots = page_table.shape[0] - 1
    ps = cache["global_k"][0].shape[1] if cache["global_k"] \
        else cache["window_k"][0].shape[1]
    ring = kv_cache.ring_pages(cfg.sliding_window, ps)
    seg = seg.astype(jnp.int32)

    def trunk_on(R):
        def branch(ids, positions, seg):
            ids, positions, seg = ids[:R], positions[:R], seg[:R]
            written = []   # every layer's (k, v) rows, in layer order

            def attn_of(window, n):
                def attn(q, k, v, sink):
                    written.append((k, v))
                    with jax.named_scope("attend"):
                        ctx = packed_gqa_attention(
                            q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                            v.transpose(1, 0, 2), seg,
                            sm_scale=1.0 / math.sqrt(dk),
                            window=cfg.sliding_window if window else None,
                            sink=sink, impl=attn_impl, interpret=interpret)
                        return ctx.transpose(1, 0, 2).reshape(R, hq * dv)

                return attn

            with jax.named_scope("embed"):
                x = jnp.take(params["embed"], ids, axis=0)
            x, counts = _trunk(params, cfg, x, positions, seg > 0, attn_of,
                               moe_impl, interpret)
            pad = lambda rows: jnp.pad(                      # noqa: E731
                rows.reshape(R, -1), ((0, S - R), (0, 0)))
            return (jnp.take(x, jnp.minimum(last_idx, R - 1), axis=0),
                    [(pad(k), pad(v)) for k, v in written], counts)

        return branch

    last, written, counts = switch_on_rows(prefill_rows(S), trunk_on, ids,
                                           positions, seg)

    with jax.named_scope("embed"):
        g_page = jnp.take_along_axis(
            jnp.take(page_table, token_rows, axis=0),
            (positions // ps)[:, None], axis=1)[:, 0]
        g_off = positions % ps
        # a segment's length, beside each of its tokens: only its last
        # window is ever read back by a window layer
        seg_len = jnp.zeros((S + 1,), jnp.int32).at[seg].add(1)[seg]
        keep = (seg > 0) & (token_rows < num_slots) \
            & (positions >= seg_len - cfg.sliding_window)
        w_page, w_off = kv_cache.ring_write(token_rows, positions, keep,
                                            ring, ps)
    seen = [0, 0]
    for window, (k, v) in zip(cfg.hybrid_layer_pattern, written):
        kind = "window" if window else "global"
        page, off = (w_page, w_off) if window else (g_page, g_off)
        with jax.named_scope("layer"), jax.named_scope("attn_" + kind):
            _write_kv(cache, kind, seen[window], page, off, k, v)
        seen[window] += 1
    with jax.named_scope("lm_head"):
        logits = _logits(last, params["head"])
    return cache, logits, {"expert_tokens": counts}


# ---------------------------------------------------------------- decode

def decode_step(params, cache, tokens, lengths, page_table, *, cfg,
                decode_impl=None, moe_impl=None, interpret=None):
    """One greedy decode step for every slot (arguments as
    ``serving.model.decode_step``; slot ``i`` is row ``i``). Returns
    ``(cache, next_tokens [B], logits [B, vocab] float32,
    {"expert_tokens": [moe layers, held] int32})``: how many of this
    round's assignments each held expert received."""
    from apex_tpu.ops import decode_attention_pallas as dap

    cache = {name: list(arrays) for name, arrays in cache.items()}
    hq, dk, dv = cfg.num_attention_heads, cfg.head_dim, cfg.v_head_dim
    B = tokens.shape[0]
    ps = cache["global_k"][0].shape[1] if cache["global_k"] \
        else cache["window_k"][0].shape[1]
    ring = kv_cache.ring_pages(cfg.sliding_window, ps)

    with jax.named_scope("embed"):
        active = lengths > 0
        positions = jnp.maximum(lengths - 1, 0)
        x = jnp.take(params["embed"], tokens, axis=0)
        g_page = jnp.where(active, jnp.take_along_axis(
            page_table, (positions // ps)[:, None], axis=1)[:, 0], 0)
        g_off = jnp.where(active, positions % ps, 0)
        w_page, w_off = kv_cache.ring_write(
            jnp.arange(B, dtype=jnp.int32), positions, active, ring, ps)
        g_table, g_base = kv_cache.pool_view(page_table, positions,
                                             lengths, ps)
        w_table = kv_cache.ring_table(B, ring)
        w_base, w_start = kv_cache.ring_view(lengths, ring, ps,
                                             cfg.sliding_window)

    def attn_of(window, n):
        kind = "window" if window else "global"
        page, off = (w_page, w_off) if window else (g_page, g_off)

        def attn(q, k, v, sink):
            _write_kv(cache, kind, n, page, off, k, v)
            with jax.named_scope("attend"):
                view = dict(page_table=w_table, page_base=w_base,
                            starts=w_start) if window else \
                    dict(page_table=g_table, page_base=g_base)
                ctx = dap.grouped_decode_attention(
                    q, cache[kind + "_k"][n], cache[kind + "_v"][n],
                    lengths=lengths, n_kv=k.shape[1],
                    sm_scale=1.0 / math.sqrt(dk), sink=sink,
                    impl=decode_impl, interpret=interpret, **view)
                return ctx.reshape(B, hq * dv)

        return attn

    x, counts = _trunk(params, cfg, x, positions, active, attn_of, moe_impl,
                       interpret)
    with jax.named_scope("lm_head"):
        logits = _logits(x, params["head"])
    with jax.named_scope("sample"):
        next_tokens = jnp.where(
            active, jnp.argmax(logits, axis=-1).astype(jnp.int32), 0)
    return cache, next_tokens, logits, {"expert_tokens": counts}


def decode_attention_resolved(cfg, cache, decode_impl):
    """The decode-attention impl of each layer kind
    (``engine.decode_attn_impl``): ``"pallas"``, ``"jnp"`` or a mix."""
    from apex_tpu.ops import decode_attention_pallas as dap

    impls = set()
    for window, name in ((False, "global_k"), (True, "window_k")):
        if cache[name]:
            n_kv, dk, dv = layer_geometry(cfg, window)
            arr = cache[name][0]
            impls.add(dap.grouped_resolved(
                cfg.num_attention_heads, n_kv, dk, dv, arr.shape[1],
                arr.dtype, decode_impl))
    return "+".join(sorted(impls))
