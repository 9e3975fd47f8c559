"""Plain reference of the dots3-note language model (``model_type``
``dots3_note``) as ``dots3-note-ep16`` runs it: float32 ``jax.numpy``
under matmul precision "highest", one sequence at a time, no cache, no
kernels, no batching, the EXPANDED form of latent attention only (the
program's decode attends in the latent over rows it gathers). It imports
nothing from ``apex_tpu``; it is handed the same parameter tree as the
engine (arrays only) and the configuration's published keys (a dict).

Equations (``x`` is ``[T, hidden]``; every projection is bias-free;
``rms(x, g) = x * rsqrt(mean(x^2) + rms_norm_eps) * g``; ``u`` is the
block's normed input):

* block: ``h = x + Attn(rms(x))``, ``y = h + FFN(rms(h))``; after the
  last layer a final norm and ``logits = x @ head^T`` (untied). Layer
  ``i`` is ``layer_types[i]``: ``full_attention`` or
  ``sliding_attention``; it has a dense SwiGLU of width
  ``intermediate_size`` where ``i < first_k_dense_replace``, else experts.
* latent attention, with the sizes of the layer's kind (full:
  ``q_lora_rank``, ``kv_lora_rank``, ``num_attention_heads``,
  ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
  ``rope_theta``; sliding: the same keys with ``swa_`` before them):
  ``c_q = rms(u wq_a, q_norm) * r_q``; ``q = c_q wq_b`` -> heads of
  ``q_nope ‖ q_pe``; ``u wkv_a`` -> ``c_kv = rms(first rank, kv_norm) *
  r_kv`` ‖ ``k_pe`` (one for all heads); rotary on ``q_pe`` and ``k_pe``
  only (plain: ``inv_freq = theta^(-2i/d)``); ``c_kv wkv_b`` -> heads of
  ``k_nope ‖ v``; ``k = k_nope ‖ rot(k_pe)``; softmax of ``q . k * (nope
  + rope)^-0.5`` over the keys the layer may see; **gate**: ``o_h <-
  sigmoid(u attn_gate)_h * o_h``; output ``[T, heads * v] wo``.
* the keys a query ``t`` may see. Sliding: ``t - sliding_window_size <
  j <= t``. Full: ``j <= t`` and ``j in S_t``, the **indexer's**
  choice: ``q_idx = c_q idx_wq`` (``index_n_heads`` x
  ``index_head_dim``); ``k_idx = LayerNorm(u idx_wk)`` (gain
  ``idx_k_gain``, bias ``idx_k_bias``, eps ``rms_norm_eps``), one a
  token; the first ``qk_rope_head_dim`` dims of both rotated with the
  layer's rotary; ``w = (u idx_ww) * index_n_heads^-0.5 *
  index_head_dim^-0.5``; ``I[t, j] = sum_h w[t, h] relu(q_idx[t, h] .
  k_idx[j])``; ``S_t`` = the ``index_topk`` largest ``I[t, j]`` over ``j
  <= t`` (``lax.top_k`` on the float32 scores: exact), all of them while
  ``t < index_topk``.
* experts: ``scores = sigmoid(x router^T)`` over all ``n_routed_experts``;
  the top ``num_experts_per_tok`` of ``scores + router_bias`` are chosen
  (``noaux_tc``: the bias is for the choice only); weights = the chosen
  experts' UNBIASED scores / (their sum + 1e-20) (``norm_topk_prob``),
  times ``routed_scaling_factor``; ``y = sum_e w_e E_e(x) +
  E_shared(x)``, every ``E`` a SwiGLU. **The share**: the parameter tree
  holds experts ``[first, first + count)`` only; the layer routes over
  all experts, sums over the chosen experts it holds and adds the shared
  expert. What the absent experts would add is left out, here as in the
  program, and the partial sum goes on to the next layer.

Assumed readings (the configuration file lists them under ``assumed``):
``apply_mla_qkv_lora_rescale`` is ``r_q = sqrt(hidden / q_rank)``, ``r_kv
= sqrt(hidden / kv_rank)`` on the normed latents; the head-wise gate is
a sigmoid of a linear map of the block's normed input, one scalar a
head, on the attention output before ``wo``; the indexer has the form
of DeepSeek-V3.2-Exp's public inference code; the window counts the
token itself; rotary pairs ADJACENT dims ``(2i, 2i + 1)``. Not built:
the vision and audio towers, the multi-token-prediction head.

The parameter tree (matrices bfloat16 as stored, the rest float32):
``embed [V, H]``, ``head [V, H]``, ``final_norm [H]``, and per layer
``attn_norm``, ``ffn_norm`` ``[H]``; ``wq_a [H, q_rank]``, ``q_norm
[q_rank]``, ``wq_b [q_rank, heads*(nope+rope)]``, ``wkv_a [H, kv_rank +
rope]``, ``kv_norm [kv_rank]``, ``wkv_b [kv_rank, heads*(nope+v)]``,
``attn_gate [H, heads]``, ``wo [heads*v, H]``; full layers also ``idx_wq
[q_rank, hi*di]``, ``idx_wk [H, di]``, ``idx_k_gain``, ``idx_k_bias``
``[di]``, ``idx_ww [H, hi]``; the feed-forward half as
``perf/references/axk1.py`` has it plus ``router_bias [E]``. Each layer
is upcast to float32 on its own (and of an expert layer's held experts
one at a time); attention and the indexer run in blocks of
``QUERY_BLOCK`` queries.

``_fault`` names ONE deliberate error, for the negative controls of
``tests/test_dots3_serving.py`` and of the benchmark's judge (each must
fail the comparison that the sound reference passes): ``gate_left_out``,
``rescale_left_out``, ``selection_is_the_last_rows`` (a window of
``index_topk`` passing for the indexer), ``window_one_row_longer``
(keys ``t - window <= j <= t``), ``index_rope_left_out``.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128
HEAD_GROUP = 16
FULL, SLIDING = "full_attention", "sliding_attention"
NEG = -jnp.inf


def held(config):
    """``(first, count)`` of the experts this share holds."""
    first, count = config.get("held_experts",
                              (0, config["n_routed_experts"]))
    return int(first), int(count)


def is_expert_layer(config, i):
    return i >= config["first_k_dense_replace"] \
        and i % config.get("moe_layer_freq", 1) == 0


_STACKS = ("w_gate", "w_up", "w_down")   # [count, ., .] in an expert layer


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def layer_norm(x, g, b, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                             + eps) * g + b


def sizes(config, sliding):
    """The latent attention's sizes of one layer kind, as a dict."""
    p = "swa_" if sliding else ""
    names = dict(heads="num_attention_heads", q_rank="q_lora_rank",
                 rank="kv_lora_rank", nope="qk_nope_head_dim",
                 rope="qk_rope_head_dim", dv="v_head_dim",
                 theta="rope_theta")
    return {k: config[p + v] for k, v in names.items()}


def inv_freq(d, theta):
    return (float(theta) ** (-np.arange(0, d, 2, dtype=np.float64) / d)
            ).astype(np.float32)


def rotary(x, positions, freq):
    """Adjacent pairs ``(2i, 2i + 1)`` of the last axis of ``x [T, ...,
    d]`` turned by ``positions * freq[i]``."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(freq)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[1],)
    cos, sin = jnp.cos(ang).reshape(shape), jnp.sin(ang).reshape(shape)
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def index_scores(config, lp, x, c_q, _fault=None):
    """``I [T, T]`` of one full layer (``-inf`` where ``j > t``), in
    blocks of queries."""
    T = x.shape[0]
    hi, di, rope = (config["index_n_heads"], config["index_head_dim"],
                    config["qk_rope_head_dim"])
    freq = inv_freq(rope, config["rope_theta"])
    pos = jnp.arange(T)
    q = (c_q @ lp["idx_wq"]).reshape(T, hi, di)
    k = layer_norm(x @ lp["idx_wk"], lp["idx_k_gain"], lp["idx_k_bias"],
                   config["rms_norm_eps"])
    if _fault != "index_rope_left_out":
        q = jnp.concatenate([rotary(q[..., :rope], pos, freq),
                             q[..., rope:]], axis=-1)
        k = jnp.concatenate([rotary(k[:, :rope], pos, freq), k[:, rope:]],
                            axis=-1)
    w = (x @ lp["idx_ww"]) * (hi ** -0.5 * di ** -0.5)
    out = []
    for q0 in range(0, T, QUERY_BLOCK):
        s = jnp.einsum("qhd,kd->qhk", q[q0:q0 + QUERY_BLOCK], k)
        s = jnp.sum(jax.nn.relu(s) * w[q0:q0 + QUERY_BLOCK, :, None], axis=1)
        out.append(jnp.where(
            pos[None, :] <= pos[q0:q0 + QUERY_BLOCK, None], s, NEG))
    return jnp.concatenate(out, axis=0)


def selection(config, scores, _fault=None):
    """``[T, T]`` bool: ``j in S_t``, from ``I`` (:func:`index_scores`)."""
    T, K = scores.shape[0], min(config["index_topk"], scores.shape[0])
    pos = jnp.arange(T)
    causal = pos[None, :] <= pos[:, None]
    if _fault == "selection_is_the_last_rows":
        return causal & (pos[:, None] - pos[None, :] < K)
    rows = []
    for q0 in range(0, T, QUERY_BLOCK):
        block = scores[q0:q0 + QUERY_BLOCK]
        _, chosen = jax.lax.top_k(block, K)
        rows.append(jnp.zeros(block.shape, bool).at[
            jnp.arange(block.shape[0])[:, None], chosen].set(True))
    return jnp.concatenate(rows, axis=0) & causal


def attention(config, lp, x, sliding, _fault=None, index_tap=None, i=None):
    """The attention block's output ``[T, hidden]`` for ``x`` already
    normed, of a full or a sliding layer. ``HEAD_GROUP`` heads at a time,
    in blocks of ``QUERY_BLOCK`` queries (the longest judged sequence's
    expanded K and V of every head at once would not fit beside the
    engine's weights)."""
    T = x.shape[0]
    z = sizes(config, sliding)
    heads, nope, rope, dv, rank = (z["heads"], z["nope"], z["rope"], z["dv"],
                                   z["rank"])
    eps, hidden = config["rms_norm_eps"], config["hidden_size"]
    rescale = config.get("apply_mla_qkv_lora_rescale", False) \
        and _fault != "rescale_left_out"
    r_q = math.sqrt(hidden / z["q_rank"]) if rescale else 1.0
    r_kv = math.sqrt(hidden / rank) if rescale else 1.0
    freq = inv_freq(rope, z["theta"])
    pos = jnp.arange(T)
    c_q = rms_norm(x @ lp["wq_a"], lp["q_norm"], eps) * r_q
    kv = x @ lp["wkv_a"]
    c_kv = rms_norm(kv[:, :rank], lp["kv_norm"], eps) * r_kv
    k_pe = rotary(kv[:, rank:], pos, freq)
    if sliding:
        window = config["sliding_window_size"] \
            + (_fault == "window_one_row_longer")
        seen = (pos[None, :] <= pos[:, None]) \
            & (pos[:, None] - pos[None, :] < window)
    else:
        scores = index_scores(config, lp, x, c_q, _fault)
        seen = selection(config, scores, _fault)
        if index_tap is not None:
            index_tap(i, x, scores, seen)
        del scores
    scale = (nope + rope) ** -0.5
    wq_b = lp["wq_b"].reshape(-1, heads, nope + rope)
    wkv_b = lp["wkv_b"].reshape(rank, heads, nope + dv)
    groups = []
    for h0 in range(0, heads, HEAD_GROUP):
        g = slice(h0, h0 + HEAD_GROUP)
        q = jnp.einsum("tr,rhd->thd", c_q, wq_b[:, g])
        q = jnp.concatenate(
            [q[..., :nope], rotary(q[..., nope:], pos, freq)], axis=-1)
        expanded = jnp.einsum("tr,rhd->thd", c_kv, wkv_b[:, g])
        k = jnp.concatenate([expanded[..., :nope], jnp.broadcast_to(
            k_pe[:, None, :], (T, q.shape[1], rope))], axis=-1)
        v = expanded[..., nope:]
        out = []
        for q0 in range(0, T, QUERY_BLOCK):   # [group, block, T] scores
            a = jnp.einsum("qhd,khd->hqk", q[q0:q0 + QUERY_BLOCK], k) * scale
            a = jnp.where(seen[None, q0:q0 + QUERY_BLOCK], a, NEG)
            out.append(jnp.einsum("hqk,khd->qhd",
                                  jax.nn.softmax(a, axis=-1), v))
        groups.append(jnp.concatenate(out, axis=0))
    out = jnp.concatenate(groups, axis=1)                 # [T, heads, dv]
    if _fault != "gate_left_out":
        out = out * jax.nn.sigmoid(x @ lp["attn_gate"])[:, :, None]
    return out.reshape(T, heads * dv) @ lp["wo"]


def route(config, lp, x):
    """``(experts [T, k], weights [T, k])`` over ALL routed experts."""
    s = jax.nn.sigmoid(x @ lp["router"].T)
    _, chosen = jax.lax.top_k(s + lp["router_bias"],
                              config["num_experts_per_tok"])
    w = jnp.take_along_axis(s, chosen, axis=-1)
    if config["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * (config.get("routed_scaling_factor") or 1.0)


def swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def routed(config, lp, x):
    """The share's partial sum: chosen experts that are held."""
    first, count = held(config)
    chosen, w = route(config, lp, x)
    y = jnp.zeros_like(x)
    for e in range(count):   # dense over the held experts, masked
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        y = y + w_e[:, None] * swiglu(x, *(
            jnp.asarray(lp[name][e], jnp.float32) for name in _STACKS))
    return y


def moe(config, lp, x):
    """The share's expert layer: held routed experts + the shared one."""
    y = routed(config, lp, x)
    if config.get("n_shared_experts"):
        y = y + swiglu(x, lp["shared_gate"], lp["shared_up"],
                       lp["shared_down"])
    return y


def hidden_states(config, params, ids, _fault=None, tap=None, attn_tap=None,
                  index_tap=None):
    """``[T, hidden]`` after the final norm, for one sequence ``ids``.
    ``tap(layer index, inner [T, hidden], y [T, hidden])`` is called at
    every expert layer with what went into it and the share's sum that
    came out (shared expert included); ``attn_tap`` likewise at every
    attention block (its normed input, its output after ``wo``);
    ``index_tap(layer index, inner, I [T, T], selected [T, T] bool)`` at
    every full layer, before its ``attn_tap``. All float32: a judge holds a program's layer to ``y`` on
    the same ``inner``."""
    eps = config["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(ids, jnp.int32),
                     axis=0).astype(jnp.float32)
        for i, lp in enumerate(params["layers"]):
            # this layer alone in float32, its held experts one at a time
            expert = is_expert_layer(config, i)
            lp = {**lp, **_f32({k: v for k, v in lp.items()
                                if not (expert and k in _STACKS)})}
            inner = rms_norm(x, lp["attn_norm"], eps)
            y = attention(config, lp, inner,
                          config["layer_types"][i] == SLIDING, _fault,
                          index_tap, i)
            if attn_tap is not None:
                attn_tap(i, inner, y)
            x = x + y
            inner = rms_norm(x, lp["ffn_norm"], eps)
            if expert:
                y = moe(config, lp, inner)
                if tap is not None:
                    tap(i, inner, y)
            else:
                y = swiglu(inner, lp["w_gate"], lp["w_up"], lp["w_down"])
            x = x + y
        return rms_norm(x, jnp.asarray(params["final_norm"], jnp.float32),
                        eps)


def logits(config, params, ids, _fault=None, tap=None, attn_tap=None,
           index_tap=None):
    """Float32 ``[T, vocab]`` logits of one sequence."""
    x = hidden_states(config, params, ids, _fault, tap, attn_tap, index_tap)
    with jax.default_matmul_precision("highest"):
        return x @ jnp.asarray(params["head"], jnp.float32).T


def best_and_chosen(config, params, ids, tap=None, attn_tap=None,
                    index_tap=None, _fault=None):
    """At every position but the last: the best next-token logit and the
    logit of the token that really follows. Two float32 ``[T - 1]``
    numpy arrays; the ``[T, vocab]`` logits stay on the device. The taps
    as in :func:`hidden_states`."""
    ids = jnp.asarray(ids, jnp.int32)
    out = logits(config, params, ids, _fault, tap=tap, attn_tap=attn_tap,
                 index_tap=index_tap)[:-1]
    chosen = jnp.take_along_axis(out, ids[1:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(out, axis=-1)), np.asarray(chosen)


def bf16_step(value):
    """The distance between neighbouring bfloat16 numbers at ``value``."""
    return 2.0 ** (math.floor(math.log2(abs(value))) - 7)
