"""The A.X-K1 block (``model_type`` ``axk1``: the DeepSeek-V3 block) as a
serving family: prefill and decode programs over the latent KV cache
(serving/kv_cache.py ``init_latent_cache``), for ``ServingEngine``'s
normal path (serving/family.py is the seam).

What differs from the MiMo family (serving/mimo.py), by mechanism:

* LATENT attention. Queries go through a low rank (``wq_a``, a norm,
  ``wq_b``) to heads of ``qk_nope_head_dim + qk_rope_head_dim``; keys
  and values come from ONE row a token a layer: ``c_kv`` (``kv_lora_rank``
  wide, normed) ‖ ``rot(k_pe)`` (``qk_rope_head_dim`` wide, one for all
  heads). THAT row is the cache; it has no head axis and serves as K and
  as V. Two forms of the one attention:

  - prefill EXPANDS the row per head through ``wkv_b`` (``k = k_nope ‖
    rot(k_pe)``, ``v``) and runs the packed causal kernel with as many
    KV heads as query heads;
  - decode ABSORBS ``wkv_b``'s two halves into the query and the output
    and attends in the latent: ``q_lat = q_nope W_uk^T``, score ``(q_lat
    . c_kv + rot(q_pe) . rot(k_pe)) * s``, ``o_lat = sum p c_kv``, head
    output ``o_lat W_uv``: every head reads each cached row once
    (ops/decode_attention_pallas.py ``latent_decode_attention``).

* YaRN positions on the rotary dims (adjacent pairs), with the softmax
  scale ``(nope + rope)^-0.5 * m^2``;
* the expert layer is MiMo's (``mimo.moe_ffn``: sigmoid top-k over ALL
  ``n_routed_experts``, here with no selection bias and a
  ``routed_scaling_factor``; the partial sum of the experts this chip
  HOLDS, dropless) plus a SHARED expert every chip computes alike
  (transformer/moe.py ``gated_mlp``);
* weights are bfloat16 as stored (norm gains and router float32),
  activations bfloat16, norms, rotary, routing and softmaxes float32,
  logits float32.

The plain reference of these equations (the expanded form only), with
every assumed reading, is ``perf/references/axk1.py``;
``tests/test_axk1_serving.py`` holds the engine to it through the cache.

Device scopes: ``embed``, ``layer/attn_latent/{q_proj,kv_proj,rope,
kv_write,expand,absorb,attend,out}`` (``expand`` in prefill, ``absorb``
in decode), ``layer/mlp``, ``layer/moe/{route,experts,shared}``,
``final_norm``, ``lm_head``, ``sample``.
"""

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.serving import kv_cache, mimo
from apex_tpu.serving.family import prefill_rows, switch_on_rows
from apex_tpu.serving.mimo import _logits, _mm, _normal, _rms_norm
from apex_tpu.transformer import moe as moe_mod

_YARN = (("beta_fast", 32), ("beta_slow", 1), ("factor", 32),
         ("mscale", 1), ("mscale_all_dim", 1),
         ("original_max_position_embeddings", 4096), ("type", "yarn"))


@dataclasses.dataclass(frozen=True)
class AXK1Config:
    """The published keys of ``config.json`` (``model_type`` ``axk1``)
    that the serving programs read, plus the share this chip holds."""
    vocab_size: int
    num_hidden_layers: int
    max_position_embeddings: int = 131072
    hidden_size: int = 7168
    num_attention_heads: int = 64
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_routed_experts: int = 192             # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    held_experts: Tuple[int, int] = (0, 192)   # (first, count) held here
    norm_topk_prob: bool = True
    routed_scaling_factor: Optional[float] = 2.5
    scoring_func: str = "sigmoid"
    topk_method: str = "none"
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # the published ``rope_scaling`` dict as sorted (key, value) pairs
    # (hashable); None: plain rotary
    rope_scaling: Optional[Tuple[Tuple[str, object], ...]] = _YARN
    # the latent cache's dtype; activations follow the weights'
    cache_dtype: str = "bfloat16"

    serving_family = "axk1"   # serving/family.py picks the family by this

    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def latent_width(self):
        """Live columns of a cached row: ``c_kv ‖ rot(k_pe)``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def is_expert_layer(self, i):
        return i >= self.first_k_dense_replace \
            and i % self.moe_layer_freq == 0

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
        if isinstance(kw.get("rope_scaling"), dict):
            kw["rope_scaling"] = tuple(sorted(kw["rope_scaling"].items()))
        return cls(**kw)

    def to_dict(self):
        """The dict the plain reference reads (published key names)."""
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        d["rope_scaling"] = dict(self.rope_scaling) \
            if self.rope_scaling else None
        return d


def check_config(cfg):
    problems = []
    first, count = cfg.held_experts
    if not (0 <= first and count >= 1
            and first + count <= cfg.n_routed_experts):
        problems.append(f"held_experts {cfg.held_experts} outside the "
                        f"{cfg.n_routed_experts} routed experts")
    if cfg.scoring_func != "sigmoid":
        problems.append(f"scoring_func {cfg.scoring_func!r}")
    if cfg.topk_method != "none":
        problems.append(f"topk_method {cfg.topk_method!r} (group-limited "
                        f"routing and the correction bias are not built)")
    if cfg.qk_rope_head_dim % 2:
        problems.append("an odd number of rotary dims")
    if cfg.rope_scaling and dict(cfg.rope_scaling).get("type") != "yarn":
        problems.append(f"rope_scaling {dict(cfg.rope_scaling)}")
    if not 0 <= cfg.first_k_dense_replace <= cfg.num_hidden_layers:
        problems.append("first_k_dense_replace outside the layers")
    if problems:
        raise ValueError("serving does not support: " + "; ".join(problems))


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


@functools.lru_cache(maxsize=None)
def yarn(cfg):
    """``(inv_freq [rope / 2] float32, cos/sin multiplier, softmax
    scale)``, from the config alone (numpy: constants of the programs).
    The per-dimension blend of interpolated (``/ factor``) and
    extrapolated inverse frequencies by the linear ramp between the two
    correction dims; ``scale = (nope + rope)^-0.5 * m^2``."""
    d, base = cfg.qk_rope_head_dim, float(cfg.rope_theta)
    extra = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    scale = (cfg.qk_nope_head_dim + d) ** -0.5
    if not cfg.rope_scaling:
        return extra.astype(np.float32), 1.0, scale
    rs = dict(cfg.rope_scaling)
    factor, original = rs["factor"], rs["original_max_position_embeddings"]

    def correction_dim(rotations):
        return d * math.log(original / (rotations * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), d - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = extra / factor * ramp + extra * (1.0 - ramp)
    all_dim = rs.get("mscale_all_dim", 0)
    m = _mscale(factor, all_dim) if all_dim else 1.0
    return (inv.astype(np.float32),
            _mscale(factor, rs.get("mscale", 1)) / _mscale(factor, all_dim),
            scale * m * m)


# ---------------------------------------------------------------- weights

def init_params(cfg, key, std=0.02, dtype=jnp.bfloat16):
    """Random weights from a PRNG key (an ARGUMENT of every program that
    makes them: a new seed compiles nothing). Matrices ``dtype``, N(0,
    ``std``); norm gains one; router float32."""
    if isinstance(key, (int, np.integer)):
        key = jax.random.PRNGKey(int(key))
    H, hq = cfg.hidden_size, cfg.num_attention_heads
    nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
    q_rank, rank = cfg.q_lora_rank, cfg.kv_lora_rank
    count = cfg.held_experts[1]
    keys = iter(jax.random.split(key, 16 * cfg.num_layers + 8))

    def mat(*shape, dtype=dtype):
        return _normal(next(keys), shape, dtype, std)

    ones = lambda n: jnp.ones((n,), jnp.float32)            # noqa: E731
    params = {"embed": mat(cfg.vocab_size, H), "head": mat(cfg.vocab_size, H),
              "final_norm": ones(H), "layers": []}
    for i in range(cfg.num_layers):
        lp = {"attn_norm": ones(H), "ffn_norm": ones(H),
              "wq_a": mat(H, q_rank), "q_norm": ones(q_rank),
              "wq_b": mat(q_rank, hq * (nope + rope)),
              "wkv_a": mat(H, rank + rope), "kv_norm": ones(rank),
              "wkv_b": mat(rank, hq * (nope + dv)),
              "wo": mat(hq * dv, H)}
        if cfg.is_expert_layer(i):
            F = cfg.moe_intermediate_size
            lp.update(router=mat(cfg.n_routed_experts, H, dtype=jnp.float32),
                      w_gate=mat(count, H, F), w_up=mat(count, H, F),
                      w_down=mat(count, F, H))
            if cfg.n_shared_experts:
                Fs = cfg.n_shared_experts * F
                lp.update(shared_gate=mat(H, Fs), shared_up=mat(H, Fs),
                          shared_down=mat(Fs, H))
        else:
            F = cfg.intermediate_size
            lp.update(w_gate=mat(H, F), w_up=mat(H, F), w_down=mat(F, H))
        params["layers"].append(lp)
    return params


def init_cache(cfg, num_pages, page_size, dtype=jnp.bfloat16):
    return kv_cache.init_latent_cache(cfg.num_layers, num_pages, page_size,
                                      cfg.latent_width, dtype)


# ------------------------------------------------------------- the block

def _rotary(x, positions, inv_freq, mult):
    """Adjacent pairs ``(2i, 2i + 1)`` of the last axis of ``x [T, ...,
    d]`` turned by ``positions * inv_freq[i]``, in float32."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[1],)
    cos = (jnp.cos(ang) * mult).reshape(shape)
    sin = (jnp.sin(ang) * mult).reshape(shape)
    pairs = x.astype(jnp.float32).reshape(
        x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape).astype(x.dtype)


def latent_rows(inner, lp, cfg, positions):
    """The cache's row of each token of ``inner [T, hidden]`` (already
    normed): ``c_kv`` after its norm ‖ ``rot(k_pe)``, ``[T, rank +
    rope]``."""
    rank = cfg.kv_lora_rank
    inv_freq, mult, _ = yarn(cfg)
    with jax.named_scope("kv_proj"):
        kv = _mm(inner, lp["wkv_a"])
        c_kv = _rms_norm(kv[:, :rank], lp["kv_norm"], cfg.rms_norm_eps)
    with jax.named_scope("rope"):
        return jnp.concatenate(
            [c_kv, _rotary(kv[:, rank:], positions, inv_freq, mult)],
            axis=-1)


def latent_attention(inner, lp, cfg, positions, attend):
    """The attention block on ``inner [T, hidden]`` (already normed):
    queries through their low rank, the token's latent row, rotary on
    the shared dims, then ``attend(q_nope [T, h, nope], q_pe [T, h,
    rope], row [T, rank + rope])``, which owns what differs between the
    two forms (the cache write and the attention, returning ``[T, h *
    v_head_dim]``), then ``wo``. Returns the block's output, before the
    residual add. Public, with :func:`latent_rows` and
    :func:`attend_absorbed`, because the benchmark's judge holds THIS
    block, in its absorbed form through a cache, to the plain
    reference's expanded attention."""
    T = inner.shape[0]
    hq, nope = cfg.num_attention_heads, cfg.qk_nope_head_dim
    inv_freq, mult, _ = yarn(cfg)
    with jax.named_scope("q_proj"):
        c_q = _rms_norm(_mm(inner, lp["wq_a"]), lp["q_norm"],
                        cfg.rms_norm_eps)
        q = _mm(c_q, lp["wq_b"]).reshape(T, hq, -1)
    row = latent_rows(inner, lp, cfg, positions)
    with jax.named_scope("rope"):
        q_pe = _rotary(q[..., nope:], positions, inv_freq, mult)
    ctx = attend(q[..., :nope], q_pe, row)
    with jax.named_scope("out"):
        return _mm(ctx, lp["wo"])


def _split_kv_b(lp, cfg):
    """``wkv_b [rank, h * (nope + dv)]`` as ``(W_uk [rank, h, nope], W_uv
    [rank, h, dv])``."""
    w = lp["wkv_b"].reshape(cfg.kv_lora_rank, cfg.num_attention_heads, -1)
    return w[..., :cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim:]


def attend_expanded(q_nope, q_pe, row, lp, cfg, seg, attn_impl=None,
                    interpret=None):
    """The prefill form over one packed batch: K (``k_nope ‖ rot(k_pe)``)
    and V expanded per head from the rows, causal within a segment."""
    from apex_tpu.ops.attention import packed_gqa_attention

    T, hq = q_nope.shape[:2]
    rank, dv = cfg.kv_lora_rank, cfg.v_head_dim
    with jax.named_scope("expand"):
        w_uk, w_uv = _split_kv_b(lp, cfg)
        c_kv = row[:, :rank]
        k = jnp.concatenate([
            _mm(c_kv, w_uk.reshape(rank, -1)).reshape(T, hq, -1),
            jnp.broadcast_to(row[:, None, rank:],
                             (T, hq, row.shape[1] - rank))], axis=-1)
        v = _mm(c_kv, w_uv.reshape(rank, -1)).reshape(T, hq, dv)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
    with jax.named_scope("attend"):
        ctx = packed_gqa_attention(
            q.transpose(1, 0, 2), k.transpose(1, 0, 2), v.transpose(1, 0, 2),
            seg, sm_scale=yarn(cfg)[2], impl=attn_impl, interpret=interpret)
        return ctx.transpose(1, 0, 2).reshape(T, hq * dv)


def attend_absorbed(q_nope, q_pe, leaf, lp, cfg, lengths, table, base,
                    decode_impl=None, interpret=None):
    """The decode form: ``wkv_b`` absorbed into the query and the
    output, attention in the latent over the pages of ``leaf`` (this
    token's row already written)."""
    from apex_tpu.ops import decode_attention_pallas as dap

    B, hq = q_nope.shape[:2]
    rank = cfg.kv_lora_rank
    w_uk, w_uv = _split_kv_b(lp, cfg)
    with jax.named_scope("absorb"):
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_uk,
                           preferred_element_type=jnp.float32
                           ).astype(q_nope.dtype)
        q = jnp.concatenate([q_lat, q_pe], axis=-1)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, leaf.shape[2] - q.shape[2])))
    with jax.named_scope("attend"):
        o_lat = dap.latent_decode_attention(
            q, leaf, table, lengths, rank=rank, sm_scale=yarn(cfg)[2],
            page_base=base, impl=decode_impl, interpret=interpret)
    with jax.named_scope("absorb"):
        return jnp.einsum("bhr,rhd->bhd", o_lat, w_uv,
                          preferred_element_type=jnp.float32
                          ).astype(o_lat.dtype).reshape(B, -1)


def moe_ffn(inner, lp, cfg, valid=None, moe_impl=None, interpret=None):
    """The expert layer of one block as both programs run it (scopes
    ``route``, ``experts``, ``shared`` under the caller's ``layer/moe``):
    MiMo's routed part (the held experts' partial sum) plus the shared
    expert. Returns ``(y [T, hidden], tokens per held expert)``. Public
    for the benchmark's judge, as ``mimo.moe_ffn`` is."""
    y, counts = mimo.moe_ffn(inner, lp, cfg, valid, moe_impl, interpret)
    if cfg.n_shared_experts:
        with jax.named_scope("shared"):
            y = y + moe_mod.gated_mlp(inner, lp["shared_gate"],
                                      lp["shared_up"], lp["shared_down"])
    return y, counts


def _trunk(params, cfg, x, positions, valid, attend_of, moe_impl, interpret):
    """Every layer; ``attend_of(i, lp)`` gives layer ``i``'s ``attend``.
    ``valid [T]``: which rows are tokens (padding and empty lanes reach
    no routed expert). Returns ``(x after the final norm, [moe layers,
    held] int32 tokens per held expert)``."""
    counts = []
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope("layer"):
            with jax.named_scope("attn_latent"):
                x = x + latent_attention(
                    _rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps), lp, cfg,
                    positions, attend_of(i, lp))
            inner = _rms_norm(x, lp["ffn_norm"], cfg.rms_norm_eps)
            if cfg.is_expert_layer(i):
                with jax.named_scope("moe"):
                    y, c = moe_ffn(inner, lp, cfg, valid, moe_impl,
                                   interpret)
                    x = x + y
                counts.append(c)
            else:
                with jax.named_scope("mlp"):
                    x = x + moe_mod.gated_mlp(inner, lp["w_gate"],
                                              lp["w_up"], lp["w_down"])
    with jax.named_scope("final_norm"):
        x = _rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    held = cfg.held_experts[1]
    return x, (jnp.stack(counts) if counts
               else jnp.zeros((0, held), jnp.int32))


def _write_rows(cache, i, page, off, row):
    with jax.named_scope("kv_write"):
        cache["latent"][i] = kv_cache.write_latent_rows(
            cache["latent"][i], page, off, row)


# --------------------------------------------------------------- prefill

def prefill(params, cache, ids, positions, seg, token_rows, page_table,
            last_idx, *, cfg, attn_impl=None, moe_impl=None, interpret=None):
    """One packed prompt batch through the trunk, filling the latent
    cache (arguments and returns as ``mimo.prefill``).

    The trunk runs on the first ``R`` of the ``S`` packed rows, ``R`` the
    smallest of ``family.prefill_rows`` that holds the batch's tokens
    (``family.switch_on_rows``). Each layer's latent rows come out of
    the branch padded back to ``S`` and are written here, behind the
    switch, so no branch carries a cache."""
    cache = {"latent": list(cache["latent"])}
    S = ids.shape[0]
    ps = cache["latent"][0].shape[1]
    seg = seg.astype(jnp.int32)

    def trunk_on(R):
        def branch(ids, positions, seg):
            ids, positions, seg = ids[:R], positions[:R], seg[:R]
            written = []   # every layer's rows, in layer order

            def attend_of(i, lp):
                def attend(q_nope, q_pe, row):
                    written.append(row)
                    return attend_expanded(q_nope, q_pe, row, lp, cfg, seg,
                                           attn_impl, interpret)

                return attend

            with jax.named_scope("embed"):
                x = jnp.take(params["embed"], ids, axis=0)
            x, counts = _trunk(params, cfg, x, positions, seg > 0,
                               attend_of, moe_impl, interpret)
            return (jnp.take(x, jnp.minimum(last_idx, R - 1), axis=0),
                    [jnp.pad(row, ((0, S - R), (0, 0))) for row in written],
                    counts)

        return branch

    last, written, counts = switch_on_rows(prefill_rows(S), trunk_on, ids,
                                           positions, seg)
    with jax.named_scope("embed"):
        page = jnp.take_along_axis(
            jnp.take(page_table, token_rows, axis=0),
            (positions // ps)[:, None], axis=1)[:, 0]
        off = positions % ps
    for i, row in enumerate(written):
        with jax.named_scope("layer"), jax.named_scope("attn_latent"):
            _write_rows(cache, i, page, off, row)
    with jax.named_scope("lm_head"):
        logits = _logits(last, params["head"])
    return cache, logits, {"expert_tokens": counts}


# ---------------------------------------------------------------- decode

def decode_step(params, cache, tokens, lengths, page_table, *, cfg,
                decode_impl=None, moe_impl=None, interpret=None):
    """One greedy decode step for every slot (arguments and returns as
    ``mimo.decode_step``): each layer writes the token's latent row and
    attends in the absorbed form."""
    cache = {"latent": list(cache["latent"])}
    ps = cache["latent"][0].shape[1]

    with jax.named_scope("embed"):
        active = lengths > 0
        positions = jnp.maximum(lengths - 1, 0)
        x = jnp.take(params["embed"], tokens, axis=0)
        page = jnp.where(active, jnp.take_along_axis(
            page_table, (positions // ps)[:, None], axis=1)[:, 0], 0)
        off = jnp.where(active, positions % ps, 0)
        table, base = kv_cache.pool_view(page_table, positions, lengths, ps)

    def attend_of(i, lp):
        def attend(q_nope, q_pe, row):
            _write_rows(cache, i, page, off, row)
            return attend_absorbed(q_nope, q_pe, cache["latent"][i], lp,
                                   cfg, lengths, table, base, decode_impl,
                                   interpret)

        return attend

    x, counts = _trunk(params, cfg, x, positions, active, attend_of,
                       moe_impl, interpret)
    with jax.named_scope("lm_head"):
        logits = _logits(x, params["head"])
    with jax.named_scope("sample"):
        next_tokens = jnp.where(
            active, jnp.argmax(logits, axis=-1).astype(jnp.int32), 0)
    return cache, next_tokens, logits, {"expert_tokens": counts}


def decode_attention_resolved(cfg, cache, decode_impl):
    """The decode-attention impl (``engine.decode_attn_impl``)."""
    from apex_tpu.ops import decode_attention_pallas as dap

    leaf = cache["latent"][0]
    return dap.latent_resolved(cfg.num_attention_heads, leaf.shape[2],
                               cfg.kv_lora_rank, leaf.shape[1], leaf.dtype,
                               decode_impl)
