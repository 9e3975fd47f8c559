"""The dots3-note block (``model_type`` ``dots3_note``) as a serving
family: prefill and decode programs over three kinds of state under one
page table (serving/kv_cache.py ``init_latent_cache(index_width=)``,
``init_latent_ring``), for ``ServingEngine``'s normal path
(serving/family.py is the seam).

What differs from the A.X-K1 family (serving/axk1.py), by mechanism:

* layers alternate by ``layer_types``. FULL layers: latent attention
  (``q_lora_rank`` / ``kv_lora_rank``, one ``kv_lora_rank +
  qk_rope_head_dim`` row a token in the paged pool) with a LEARNED SPARSE
  SELECTION: an indexer (``index_n_heads`` queries from the query
  latent, ONE ``index_head_dim``-wide key a token, cached beside the
  latent row on the same page ids) scores every earlier token, ``I[t,
  j] = sum_h w[t, h] relu(q_idx[t, h] . k_idx[j])``, and the token
  attends over its ``index_topk`` best alone (over all while it has no
  more). SLIDING layers: latent attention of their own sizes (``swa_*``)
  over the last ``sliding_window_size`` tokens, the token itself
  included; their state is a latent RING a slot, no pool pages;
* both kinds: the normed latents times ``sqrt(hidden / rank)``
  (``apply_mla_qkv_lora_rescale``) and a head-wise output gate,
  ``sigmoid(u W_g)`` one scalar a head on the attention output;
* plain rotary (no scaling), one base a layer kind, adjacent pairs;
* two forms of each attention, as A.X-K1's: prefill EXPANDS K and V per
  head and masks (full layers: the indexer's scores in blocks, the
  ``index_topk``-th largest a query, the packed kernel under that mask;
  a trunk of ``index_topk`` rows or fewer selects nothing and runs no
  indexer); decode ABSORBS ``wkv_b`` and attends in the latent (full
  layers: scores over the slot's index pages, ``lax.top_k``, the chosen
  rows gathered as page x offset and read by ``latent_decode_attention``;
  a round whose longest context is ``index_topk`` or shorter walks the
  pool's pages with no selection work: ONE ``lax.cond`` a full layer);
* the expert layer is A.X-K1's (``axk1.moe_ffn``: MiMo's sigmoid top-k
  with its selection-only bias over the held experts, plus a shared
  expert).

The plain reference of these equations, with every assumed reading, is
``perf/references/dots3_note.py``; ``tests/test_dots3_serving.py`` holds
the engine to it through the three kinds of state.

Device scopes: ``embed``, ``layer/attn_sparse/{q_proj,kv_proj,rope,
kv_write,index,select,expand,absorb,attend,gate,out}``,
``layer/attn_window_latent/{q_proj,kv_proj,rope,kv_write,expand,absorb,
attend,gate,out}`` (``expand`` in prefill, ``absorb`` in decode),
``layer/mlp``, ``layer/moe/{route,experts,shared}``, ``final_norm``,
``lm_head``, ``sample``.
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
from apex_tpu.serving.axk1 import _rotary
# the expert layer is A.X-K1's; public here too, because the benchmark's
# judge calls ``moe_ffn`` of the config's own module
from apex_tpu.serving.axk1 import moe_ffn
from apex_tpu.serving.family import prefill_rows, switch_on_rows
from apex_tpu.serving.mimo import _logits, _mm, _normal, _rms_norm
from apex_tpu.transformer import moe as moe_mod

FULL, SLIDING = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class Dots3Config:
    """The published keys of ``config.json`` (``model_type``
    ``dots3_note``) that the serving programs read, plus the share this
    chip holds."""
    vocab_size: int
    layer_types: Tuple[str, ...]            # per layer: FULL | SLIDING
    max_position_embeddings: int = 524288
    hidden_size: int = 5120
    # full layers
    num_attention_heads: int = 128
    q_lora_rank: int = 1024
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 8e7
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    attention_gate_type: str = "headwise"
    # sliding layers
    swa_num_attention_heads: int = 64
    swa_q_lora_rank: int = 1024
    swa_kv_lora_rank: int = 1024
    swa_qk_nope_head_dim: int = 192
    swa_qk_rope_head_dim: int = 64
    swa_v_head_dim: int = 128
    swa_rope_theta: float = 5e4
    sliding_window_size: int = 513
    swa_attention_gate_type: str = "headwise"
    apply_mla_qkv_lora_rescale: bool = True
    rope_scaling: Optional[tuple] = None    # refused: plain rotary only
    # the feed-forward half
    intermediate_size: int = 13824
    moe_intermediate_size: int = 1536
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    n_routed_experts: int = 256             # the router's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    held_experts: Tuple[int, int] = (0, 256)   # (first, count) held here
    norm_topk_prob: bool = True
    routed_scaling_factor: Optional[float] = 1.0
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    rms_norm_eps: float = 1e-5
    # the three kinds of state share one dtype; activations follow the
    # weights'
    cache_dtype: str = "bfloat16"

    serving_family = "dots3"   # serving/family.py picks the family by this

    @property
    def num_layers(self):
        return len(self.layer_types)

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
        if d.get("num_hidden_layers", len(kw["layer_types"])) \
                != len(kw["layer_types"]):
            raise ValueError("layer_types must name every one of the "
                             "num_hidden_layers")
        kw["n_routed_experts"] = d.get("published_n_routed_experts",
                                       d["n_routed_experts"])
        kw.setdefault("held_experts", (0, d["n_routed_experts"]))
        if isinstance(kw.get("rope_scaling"), dict):   # hashable; refused
            kw["rope_scaling"] = tuple(sorted(kw["rope_scaling"].items()))
        return cls(**kw)

    def to_dict(self):
        """The dict the plain reference reads (published key names)."""
        d = {f.name: getattr(self, f.name) for f in dataclasses.fields(self)}
        d["num_hidden_layers"] = self.num_layers
        return d


def check_config(cfg):
    problems = []
    first, count = cfg.held_experts
    if not cfg.layer_types or set(cfg.layer_types) - {FULL, SLIDING}:
        problems.append(f"layer_types {sorted(set(cfg.layer_types))}")
    if not (0 <= first and count >= 1
            and first + count <= cfg.n_routed_experts):
        problems.append(f"held_experts {cfg.held_experts} outside the "
                        f"{cfg.n_routed_experts} routed experts")
    if cfg.scoring_func != "sigmoid":
        problems.append(f"scoring_func {cfg.scoring_func!r}")
    if cfg.topk_method != "noaux_tc":
        problems.append(f"topk_method {cfg.topk_method!r}")
    for name in ("attention_gate_type", "swa_attention_gate_type"):
        if getattr(cfg, name) != "headwise":
            problems.append(f"{name} {getattr(cfg, name)!r}")
    if cfg.rope_scaling:
        problems.append(f"rope_scaling {cfg.rope_scaling}")
    if cfg.qk_rope_head_dim % 2 or cfg.swa_qk_rope_head_dim % 2:
        problems.append("an odd number of rotary dims")
    if cfg.index_head_dim < cfg.qk_rope_head_dim:
        problems.append("an index head narrower than the rotary dims")
    if not 0 <= cfg.first_k_dense_replace <= cfg.num_layers:
        problems.append("first_k_dense_replace outside the layers")
    if problems:
        raise ValueError("serving does not support: " + "; ".join(problems))


@dataclasses.dataclass(frozen=True)
class Kind:
    """The sizes of one layer kind's latent attention."""
    scope: str
    heads: int
    q_rank: int
    rank: int
    nope: int
    rope: int
    dv: int
    theta: float
    r_q: float        # what the normed query latent is multiplied by
    r_kv: float       # and the normed KV latent
    window: Optional[int]

    @property
    def width(self):
        """Live columns of a cached row: ``c_kv ‖ rot(k_pe)``."""
        return self.rank + self.rope

    @property
    def scale(self):
        return (self.nope + self.rope) ** -0.5

    @property
    def inv_freq(self):
        return (float(self.theta) ** (
            -np.arange(0, self.rope, 2, dtype=np.float64) / self.rope)
        ).astype(np.float32)


@functools.lru_cache(maxsize=None)
def kind(cfg, sliding):
    """The :class:`Kind` of the full (``sliding`` false) or the sliding
    layers of ``cfg``."""
    p = "swa_" if sliding else ""
    q_rank, rank = (getattr(cfg, p + "q_lora_rank"),
                    getattr(cfg, p + "kv_lora_rank"))
    rescale = cfg.apply_mla_qkv_lora_rescale
    return Kind(
        scope="attn_window_latent" if sliding else "attn_sparse",
        heads=getattr(cfg, p + "num_attention_heads"), q_rank=q_rank,
        rank=rank, nope=getattr(cfg, p + "qk_nope_head_dim"),
        rope=getattr(cfg, p + "qk_rope_head_dim"),
        dv=getattr(cfg, p + "v_head_dim"),
        theta=getattr(cfg, p + "rope_theta"),
        r_q=math.sqrt(cfg.hidden_size / q_rank) if rescale else 1.0,
        r_kv=math.sqrt(cfg.hidden_size / rank) if rescale else 1.0,
        window=cfg.sliding_window_size if sliding else None)


def layer_kinds(cfg):
    """``[(Kind, index within its kind)]`` a layer."""
    seen, out = [0, 0], []
    for name in cfg.layer_types:
        sliding = name == SLIDING
        out.append((kind(cfg, sliding), seen[sliding]))
        seen[sliding] += 1
    return out


# ---------------------------------------------------------------- weights

def init_params(cfg, key, std=0.02, dtype=jnp.bfloat16):
    """Random weights from a PRNG key (an ARGUMENT of every program that
    makes them: a new seed compiles nothing). Matrices ``dtype``, N(0,
    ``std``); norm gains one, the index key's LayerNorm bias zero; router
    float32, its selection bias float32 N(0, ``std``), so that it changes
    results."""
    if isinstance(key, (int, np.integer)):
        key = jax.random.PRNGKey(int(key))
    H = cfg.hidden_size
    count = cfg.held_experts[1]
    hi, di = cfg.index_n_heads, cfg.index_head_dim
    keys = iter(jax.random.split(key, 24 * cfg.num_layers + 8))

    def mat(*shape, dtype=dtype):
        return _normal(next(keys), shape, dtype, std)

    ones = lambda n: jnp.ones((n,), jnp.float32)            # noqa: E731
    params = {"embed": mat(cfg.vocab_size, H), "head": mat(cfg.vocab_size, H),
              "final_norm": ones(H), "layers": []}
    for i, (kd, _) in enumerate(layer_kinds(cfg)):
        lp = {"attn_norm": ones(H), "ffn_norm": ones(H),
              "wq_a": mat(H, kd.q_rank), "q_norm": ones(kd.q_rank),
              "wq_b": mat(kd.q_rank, kd.heads * (kd.nope + kd.rope)),
              "wkv_a": mat(H, kd.rank + kd.rope), "kv_norm": ones(kd.rank),
              "wkv_b": mat(kd.rank, kd.heads * (kd.nope + kd.dv)),
              "attn_gate": mat(H, kd.heads),
              "wo": mat(kd.heads * kd.dv, H)}
        if kd.window is None:
            lp.update(idx_wq=mat(kd.q_rank, hi * di), idx_wk=mat(H, di),
                      idx_k_gain=ones(di),
                      idx_k_bias=jnp.zeros((di,), jnp.float32),
                      idx_ww=mat(H, hi))
        if cfg.is_expert_layer(i):
            F = cfg.moe_intermediate_size
            lp.update(router=mat(cfg.n_routed_experts, H, dtype=jnp.float32),
                      router_bias=mat(cfg.n_routed_experts,
                                      dtype=jnp.float32),
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


def init_cache(cfg, num_slots, num_pages, page_size, dtype=jnp.bfloat16):
    """``{"latent", "index", "ring"}``: a latent leaf and an index-key
    leaf a full layer (pool pages), a latent ring a sliding layer."""
    full, sliding = kind(cfg, False), kind(cfg, True)
    n_sliding = sum(t == SLIDING for t in cfg.layer_types)
    return {
        **kv_cache.init_latent_cache(
            cfg.num_layers - n_sliding, num_pages, page_size, full.width,
            dtype, index_width=cfg.index_head_dim),
        **kv_cache.init_latent_ring(
            n_sliding, num_slots, page_size, cfg.sliding_window_size,
            sliding.width, dtype)}


# ------------------------------------------------------------- the block

def latent_rows(inner, lp, cfg, kd, positions):
    """The cache's row of each token of ``inner [T, hidden]`` (already
    normed): the normed, rescaled ``c_kv`` ‖ ``rot(k_pe)``, ``[T, rank +
    rope]``."""
    with jax.named_scope("kv_proj"):
        kv = _mm(inner, lp["wkv_a"])
        c_kv = _rms_norm(kv[:, :kd.rank], lp["kv_norm"] * kd.r_kv,
                         cfg.rms_norm_eps)
    with jax.named_scope("rope"):
        return jnp.concatenate(
            [c_kv, _rotary(kv[:, kd.rank:], positions, kd.inv_freq, 1.0)],
            axis=-1)


def index_keys(inner, lp, cfg, positions):
    """The indexer's key of each token, the row the index leaf caches:
    ``LayerNorm(u W_k)`` with its first ``qk_rope_head_dim`` dims
    rotated, ``[T, index_head_dim]``."""
    rope = cfg.qk_rope_head_dim
    k = _mm(inner, lp["idx_wk"]).astype(jnp.float32)
    k = k - jnp.mean(k, axis=-1, keepdims=True)
    k = k * lax.rsqrt(jnp.mean(k * k, axis=-1, keepdims=True)
                      + cfg.rms_norm_eps)
    k = (k * lp["idx_k_gain"] + lp["idx_k_bias"]).astype(inner.dtype)
    return jnp.concatenate(
        [_rotary(k[:, :rope], positions, kind(cfg, False).inv_freq, 1.0),
         k[:, rope:]], axis=-1)


def index_queries(c_q, inner, lp, cfg, positions):
    """``(q_idx [T, hi, di], w [T, hi] float32)``: the indexer's queries
    from the query latent (first rotary dims rotated) and its head
    weights with both scales folded in."""
    T = c_q.shape[0]
    hi, di, rope = cfg.index_n_heads, cfg.index_head_dim, cfg.qk_rope_head_dim
    q = _mm(c_q, lp["idx_wq"]).reshape(T, hi, di)
    q = jnp.concatenate(
        [_rotary(q[..., :rope], positions, kind(cfg, False).inv_freq, 1.0),
         q[..., rope:]], axis=-1)
    w = _mm(inner, lp["idx_ww"]).astype(jnp.float32) \
        * (hi ** -0.5 * di ** -0.5)
    return q, w


def query_latent(inner, lp, cfg, kd):
    """``c_q [T, q_rank]``: the normed, rescaled query latent, what the
    heads' queries and the indexer's are both made from."""
    return _rms_norm(_mm(inner, lp["wq_a"]), lp["q_norm"] * kd.r_q,
                     cfg.rms_norm_eps)


def latent_attention(inner, lp, cfg, kd, positions, attend):
    """The attention block of one layer kind on ``inner [T, hidden]``
    (already normed): queries through their low rank, the token's latent
    row, rotary on the shared dims, then ``attend(q_nope [T, h, nope],
    q_pe [T, h, rope], row [T, rank + rope], c_q [T, q_rank])``, which
    owns what differs between the forms and the kinds (the cache writes,
    the indexer, the attention; returns ``[T, h, dv]``), then the
    head-wise gate and ``wo``. Returns the block's output, before the
    residual add. Public, with :func:`latent_rows`, :func:`index_keys`
    and the two decode attends, because the benchmark's judge holds THIS
    block, in its decode form through the engine's leaves, to the plain
    reference."""
    T = inner.shape[0]
    with jax.named_scope("q_proj"):
        c_q = query_latent(inner, lp, cfg, kd)
        q = _mm(c_q, lp["wq_b"]).reshape(T, kd.heads, -1)
    row = latent_rows(inner, lp, cfg, kd, positions)
    with jax.named_scope("rope"):
        q_pe = _rotary(q[..., kd.nope:], positions, kd.inv_freq, 1.0)
    ctx = attend(q[..., :kd.nope], q_pe, row, c_q)
    with jax.named_scope("gate"):
        g = jax.nn.sigmoid(_mm(inner, lp["attn_gate"]).astype(jnp.float32))
        ctx = (ctx.astype(jnp.float32) * g[:, :, None]).astype(ctx.dtype)
    with jax.named_scope("out"):
        return _mm(ctx.reshape(T, -1), lp["wo"])


def _split_kv_b(lp, kd):
    """``wkv_b [rank, h * (nope + dv)]`` as ``(W_uk [rank, h, nope], W_uv
    [rank, h, dv])``."""
    w = lp["wkv_b"].reshape(kd.rank, kd.heads, -1)
    return w[..., :kd.nope], w[..., kd.nope:]


def attend_expanded(q_nope, q_pe, row, lp, kd, seg, selected=None,
                    attn_impl=None, interpret=None):
    """The prefill form over one packed batch: K (``k_nope ‖ rot(k_pe)``)
    and V expanded per head from the rows; causal within a segment,
    inside the kind's window, over the ``selected [S, S]`` keys where
    given. Returns ``[T, h, dv]``."""
    from apex_tpu.ops import attention as attn

    T = q_nope.shape[0]
    with jax.named_scope("expand"):
        w_uk, w_uv = _split_kv_b(lp, kd)
        c_kv = row[:, :kd.rank]
        k = jnp.concatenate([
            _mm(c_kv, w_uk.reshape(kd.rank, -1)).reshape(T, kd.heads, -1),
            jnp.broadcast_to(row[:, None, kd.rank:],
                             (T, kd.heads, kd.rope))], axis=-1)
        v = _mm(c_kv, w_uv.reshape(kd.rank, -1)).reshape(T, kd.heads, kd.dv)
        q = jnp.concatenate([q_nope, q_pe], axis=-1)
    with jax.named_scope("attend"):
        q, k, v = (a.transpose(1, 0, 2) for a in (q, k, v))
        if selected is None:
            ctx = attn.packed_gqa_attention(
                q, k, v, seg, sm_scale=kd.scale, window=kd.window,
                impl=attn_impl, interpret=interpret)
        else:
            ctx = attn.selected_attention(
                q, k, v, seg, selected, sm_scale=kd.scale, impl=attn_impl,
                interpret=interpret)
        return ctx.transpose(1, 0, 2)


def select_packed(c_q, inner, k_idx, lp, cfg, positions, seg, attn_impl=None,
                  interpret=None):
    """The prefill selection of one full layer: ``[S, S]`` int8, 1 where
    a key is among its query's ``index_topk`` best."""
    from apex_tpu.ops import attention as attn

    with jax.named_scope("index"):
        q_idx, w = index_queries(c_q, inner, lp, cfg, positions)
        scores = attn.packed_index_scores(
            q_idx.transpose(1, 0, 2), w, k_idx, seg, impl=attn_impl,
            interpret=interpret)
    with jax.named_scope("select"):
        return attn.select_keys(scores, cfg.index_topk)


def _absorbed(q_nope, q_pe, lp, kd, width, attend):
    """``wkv_b`` absorbed into the query and the output around
    ``attend(q [B, h, width]) -> o_lat [B, h, rank]``."""
    w_uk, w_uv = _split_kv_b(lp, kd)
    with jax.named_scope("absorb"):
        q_lat = jnp.einsum("bhd,rhd->bhr", q_nope, w_uk,
                           preferred_element_type=jnp.float32
                           ).astype(q_nope.dtype)
        q = jnp.concatenate([q_lat, q_pe], axis=-1)
        q = jnp.pad(q, ((0, 0), (0, 0), (0, width - q.shape[2])))
    o_lat = attend(q)
    with jax.named_scope("absorb"):
        return jnp.einsum("bhr,rhd->bhd", o_lat, w_uv,
                          preferred_element_type=jnp.float32
                          ).astype(o_lat.dtype)


def select_rows(scores, page_table, k, page_size):
    """The decode selection: of ``scores [B, n * page_size]`` (the
    indexer's, ``NEG_INF`` past a slot's context) the ``k`` best rows of
    every slot as ``(page [B, k], offset [B, k], position [B, k])``,
    best first (so a slot with fewer than ``k`` rows has its own
    first)."""
    _, pos = lax.top_k(scores, k)
    page = jnp.take_along_axis(page_table, pos // page_size, axis=1)
    return page, pos % page_size, pos


def index_scores_paged(c_q, inner, index_leaf, lp, cfg, positions, lengths,
                       table, base, decode_impl=None, interpret=None):
    """The decode indexer: ``[B, n * page_size]`` scores of every row of
    each slot's index pages against its token's index queries
    (``NEG_INF`` past the context). Public for the benchmark's judge,
    which holds the selection made from THESE scores to the
    reference's."""
    from apex_tpu.ops import decode_attention_pallas as dap

    q_idx, w = index_queries(c_q, inner, lp, cfg, positions)
    # the leaf's row is the key padded to whole lane tiles
    q_idx = jnp.pad(q_idx, ((0, 0), (0, 0), (
        0, index_leaf.shape[2] - q_idx.shape[2])))
    return dap.index_decode_scores(
        q_idx, w, index_leaf, table, lengths, page_base=base,
        impl=decode_impl, interpret=interpret)


def attend_sparse(q_nope, q_pe, c_q, inner, leaf, index_leaf, lp, cfg,
                  positions, lengths, page_table, table, base,
                  decode_impl=None, interpret=None):
    """The decode form of a full layer (this token's latent row and
    index key already written): absorbed attention over the slot's
    ``index_topk`` best rows; over every row of the pool's pages, with no
    selection work, in a round whose longest context is ``index_topk``
    or shorter. Returns ``[B, h, dv]``."""
    from apex_tpu.ops import decode_attention_pallas as dap

    kd = kind(cfg, False)
    B, ps, width = q_nope.shape[0], leaf.shape[1], leaf.shape[2]
    K = cfg.index_topk
    attend = functools.partial(
        dap.latent_decode_attention, rank=kd.rank, sm_scale=kd.scale,
        impl=decode_impl, interpret=interpret)

    def walk(q):
        def dense():
            with jax.named_scope("attend"):
                return attend(q, leaf, table, lengths, page_base=base)

        def chosen():
            with jax.named_scope("index"):
                scores = index_scores_paged(
                    c_q, inner, index_leaf, lp, cfg, positions, lengths,
                    table, base, decode_impl, interpret)
            with jax.named_scope("select"):
                page, off, _ = select_rows(scores, page_table, K, ps)
                n = -(-K // ps)
                rows = jnp.pad(leaf[page, off],
                               ((0, 0), (0, n * ps - K), (0, 0)))
            with jax.named_scope("attend"):
                return attend(
                    q, rows.reshape(B * n, ps, width),
                    jnp.arange(B * n, dtype=jnp.int32).reshape(B, n),
                    jnp.minimum(lengths, K))

        return lax.cond(jnp.max(lengths) <= K, dense, chosen)

    return _absorbed(q_nope, q_pe, lp, kd, width, walk)


def attend_ring(q_nope, q_pe, leaf, lp, cfg, lengths, table, base, starts,
                decode_impl=None, interpret=None):
    """The decode form of a sliding layer: absorbed attention over the
    slot's ring (this token's row already written), positions before
    ``starts`` outside the window. Returns ``[B, h, dv]``."""
    from apex_tpu.ops import decode_attention_pallas as dap

    kd = kind(cfg, True)

    def walk(q):
        with jax.named_scope("attend"):
            return dap.latent_decode_attention(
                q, leaf, table, lengths, rank=kd.rank, sm_scale=kd.scale,
                page_base=base, starts=starts, impl=decode_impl,
                interpret=interpret)

    return _absorbed(q_nope, q_pe, lp, kd, leaf.shape[2], walk)


def _trunk(params, cfg, x, positions, valid, attend_of, moe_impl, interpret):
    """Every layer; ``attend_of(i, kind, index within its kind, lp,
    inner)`` gives layer ``i``'s ``attend``. ``valid [T]``: which rows
    are tokens (padding and empty lanes reach no routed expert). Returns
    ``(x after the final norm, [moe layers, held] int32 tokens per held
    expert)``."""
    counts = []
    for i, (lp, (kd, n)) in enumerate(zip(params["layers"],
                                          layer_kinds(cfg))):
        with jax.named_scope("layer"):
            with jax.named_scope(kd.scope):
                inner = _rms_norm(x, lp["attn_norm"], cfg.rms_norm_eps)
                x = x + latent_attention(inner, lp, cfg, kd, positions,
                                         attend_of(i, kd, n, lp, inner))
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


def _write(cache, name, n, page, off, rows):
    with jax.named_scope("kv_write"):
        cache[name][n] = kv_cache.write_latent_rows(cache[name][n], page,
                                                    off, rows)


def _pair_counts(context, cfg):
    """``(index pairs, attended pairs)`` of tokens whose contexts (their
    own position included; 0: no token) are ``context``."""
    return {"index_pairs": jnp.sum(context),
            "sparse_pairs": jnp.sum(jnp.minimum(context, cfg.index_topk))}


# --------------------------------------------------------------- prefill

def prefill(params, cache, ids, positions, seg, token_rows, page_table,
            last_idx, *, cfg, attn_impl=None, moe_impl=None, interpret=None):
    """One packed prompt batch through the trunk, filling the three
    kinds of state (arguments and returns as ``mimo.prefill``; the
    extras also carry ``index_pairs`` and ``sparse_pairs``: the sum over
    the batch's tokens of their context, and of its ``index_topk``
    largest part).

    The trunk runs on the first ``R`` of the ``S`` packed rows, ``R`` the
    smallest of ``family.prefill_rows`` that holds the batch's tokens
    (``family.switch_on_rows``). A trunk of ``index_topk`` rows or fewer
    holds no context the selection would cut: its full layers attend
    densely and run no indexer (their index KEYS are written all the
    same). Each layer's rows come out of the branch padded back to ``S``
    and are written here, behind the switch, so no branch carries a
    cache."""
    cache = {name: list(leaves) for name, leaves in cache.items()}
    S = ids.shape[0]
    num_slots = page_table.shape[0] - 1
    ps = (cache["latent"] or cache["ring"])[0].shape[1]
    ring = kv_cache.ring_pages(cfg.sliding_window_size, ps)
    seg = seg.astype(jnp.int32)

    def trunk_on(R):
        def branch(ids, positions, seg):
            ids, positions, seg = ids[:R], positions[:R], seg[:R]
            written = []   # every layer's rows, in layer order

            def attend_of(i, kd, n, lp, inner):
                def attend(q_nope, q_pe, row, c_q):
                    selected = None
                    if kd.window is None:
                        with jax.named_scope("index"):
                            k_idx = index_keys(inner, lp, cfg, positions)
                        written.append((row, k_idx))
                        if R > cfg.index_topk:
                            selected = select_packed(
                                c_q, inner, k_idx, lp, cfg, positions, seg,
                                attn_impl, interpret)
                    else:
                        written.append((row,))
                    return attend_expanded(q_nope, q_pe, row, lp, kd, seg,
                                           selected, attn_impl, interpret)

                return attend

            with jax.named_scope("embed"):
                x = jnp.take(params["embed"], ids, axis=0)
            x, counts = _trunk(params, cfg, x, positions, seg > 0,
                               attend_of, moe_impl, interpret)
            return (jnp.take(x, jnp.minimum(last_idx, R - 1), axis=0),
                    [tuple(jnp.pad(rows, ((0, S - R), (0, 0)))
                           for rows in layer) for layer in written],
                    counts)

        return branch

    last, written, counts = switch_on_rows(prefill_rows(S), trunk_on, ids,
                                           positions, seg)
    with jax.named_scope("embed"):
        page = jnp.take_along_axis(
            jnp.take(page_table, token_rows, axis=0),
            (positions // ps)[:, None], axis=1)[:, 0]
        off = positions % ps
        # a segment's length, beside each of its tokens: only its last
        # window is ever read back by a sliding layer
        seg_len = jnp.zeros((S + 1,), jnp.int32).at[seg].add(1)[seg]
        keep = (seg > 0) & (token_rows < num_slots) \
            & (positions >= seg_len - cfg.sliding_window_size)
        w_page, w_off = kv_cache.ring_write(token_rows, positions, keep,
                                            ring, ps)
        pairs = _pair_counts(jnp.where(seg > 0, positions + 1, 0), cfg)
    for (kd, n), rows in zip(layer_kinds(cfg), written):
        with jax.named_scope("layer"), jax.named_scope(kd.scope):
            if kd.window is None:
                _write(cache, "latent", n, page, off, rows[0])
                _write(cache, "index", n, page, off, rows[1])
            else:
                _write(cache, "ring", n, w_page, w_off, rows[0])
    with jax.named_scope("lm_head"):
        logits = _logits(last, params["head"])
    return cache, logits, {"expert_tokens": counts, **pairs}


# ---------------------------------------------------------------- decode

def decode_step(params, cache, tokens, lengths, page_table, *, cfg,
                decode_impl=None, moe_impl=None, interpret=None):
    """One greedy decode step for every slot (arguments and returns as
    ``mimo.decode_step``; the extras also carry ``index_rows_scored``,
    the context rows the round's indexer reads a full layer,
    ``sparse_rows_selected``, the rows its attention reads, and
    ``window_rows``, the ring rows inside a sliding layer's window).
    Each layer writes the token's rows and attends in the absorbed
    form."""
    cache = {name: list(leaves) for name, leaves in cache.items()}
    B = tokens.shape[0]
    ps = (cache["latent"] or cache["ring"])[0].shape[1]
    ring = kv_cache.ring_pages(cfg.sliding_window_size, ps)

    with jax.named_scope("embed"):
        active = lengths > 0
        positions = jnp.maximum(lengths - 1, 0)
        x = jnp.take(params["embed"], tokens, axis=0)
        page = jnp.where(active, jnp.take_along_axis(
            page_table, (positions // ps)[:, None], axis=1)[:, 0], 0)
        off = jnp.where(active, positions % ps, 0)
        w_page, w_off = kv_cache.ring_write(
            jnp.arange(B, dtype=jnp.int32), positions, active, ring, ps)
        table, base = kv_cache.pool_view(page_table, positions, lengths, ps)
        w_table = kv_cache.ring_table(B, ring)
        w_base, w_start = kv_cache.ring_view(lengths, ring, ps,
                                             cfg.sliding_window_size)
        counted = {"index_rows_scored": jnp.sum(lengths),
                   "sparse_rows_selected": jnp.sum(
                       jnp.minimum(lengths, cfg.index_topk)),
                   "window_rows": jnp.sum(
                       jnp.minimum(lengths, cfg.sliding_window_size))}

    def attend_of(i, kd, n, lp, inner):
        def attend(q_nope, q_pe, row, c_q):
            if kd.window is not None:
                _write(cache, "ring", n, w_page, w_off, row)
                return attend_ring(q_nope, q_pe, cache["ring"][n], lp, cfg,
                                   lengths, w_table, w_base, w_start,
                                   decode_impl, interpret)
            _write(cache, "latent", n, page, off, row)
            with jax.named_scope("index"):
                k_idx = index_keys(inner, lp, cfg, positions)
            _write(cache, "index", n, page, off, k_idx)
            return attend_sparse(
                q_nope, q_pe, c_q, inner, cache["latent"][n],
                cache["index"][n], lp, cfg, positions, lengths, page_table,
                table, base, decode_impl, interpret)

        return attend

    x, counts = _trunk(params, cfg, x, positions, active, attend_of,
                       moe_impl, interpret)
    with jax.named_scope("lm_head"):
        logits = _logits(x, params["head"])
    with jax.named_scope("sample"):
        next_tokens = jnp.where(
            active, jnp.argmax(logits, axis=-1).astype(jnp.int32), 0)
    return cache, next_tokens, logits, {"expert_tokens": counts, **counted}


def decode_attention_resolved(cfg, cache, decode_impl):
    """The decode-attention impl of each kind of state
    (``engine.decode_attn_impl``): ``"pallas"``, ``"jnp"`` or a mix."""
    from apex_tpu.ops import decode_attention_pallas as dap

    impls = set()
    for sliding, name in ((False, "latent"), (True, "ring")):
        if cache[name]:
            kd, leaf = kind(cfg, sliding), cache[name][0]
            impls.add(dap.latent_resolved(kd.heads, leaf.shape[2], kd.rank,
                                          leaf.shape[1], leaf.dtype,
                                          decode_impl))
    if cache["index"]:
        leaf = cache["index"][0]
        impls.add(dap.index_resolved(cfg.index_n_heads, leaf.shape[2],
                                     leaf.shape[1], leaf.dtype, decode_impl))
    return "+".join(sorted(impls))
