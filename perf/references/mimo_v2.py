"""Plain reference of the MiMo-V2.5 language model as ``mimo-v2.5-ep16``
runs it: float32 ``jax.numpy`` under matmul precision "highest", one
sequence at a time, no cache, no kernels, no batching. It imports
nothing from ``apex_tpu``; it is handed the same parameter tree as the
engine (arrays only) and the configuration's published keys (a dict).

Equations (``x`` is ``[T, hidden]``; every projection is bias-free):

* block: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``;
  ``RMSNorm(x) = x * rsqrt(mean(x^2) + layernorm_epsilon) * g``; after
  the last layer a final RMSNorm and ``logits = x @ head^T`` (untied).
  ``hybrid_layer_pattern[i]``: 0 global, 1 window attention;
  ``moe_layer_freq[i]``: 0 dense SwiGLU, 1 mixture of experts.
* attention: ``q = x wq -> [T, heads, head_dim]``, ``k = x wk -> [T,
  n_kv, head_dim]``, ``v = x wv -> [T, n_kv, v_head_dim]`` with ``n_kv =
  num_key_value_heads`` (global) or ``swa_num_key_value_heads``
  (window); query head ``i`` reads KV head ``i // (heads / n_kv)``.
  Rotary on the first ``int(head_dim * partial_rotary_factor)`` dims of
  q and k, base ``rope_theta`` (global) or ``swa_rope_theta`` (window).
  ``v <- attention_value_scale * v``. Scores ``q.k / sqrt(head_dim)``,
  causal. Window layers: position ``i`` sees ``j`` with ``i -
  sliding_window < j <= i``, and a per-head sink logit ``s_h`` joins the
  softmax's denominator only (``add_swa_attention_sink_bias``). Output
  ``[T, heads * v_head_dim] wo``.
* mixture of experts: ``s = sigmoid(x router^T)`` over all
  ``n_routed_experts``; the top ``num_experts_per_tok`` of ``s +
  router_bias`` are chosen; weights ``s_e / (sum of the chosen s +
  1e-20)`` (``norm_topk_prob``); the bias selects and never weighs.
  ``y = sum_e w_e * w_down[e] (silu(w_gate[e] x) * w_up[e] x)``. **The
  share**: the parameter tree holds experts ``[first, first + count)``
  only; the layer routes over all experts with the published weights and
  sums over the chosen experts it holds. A token none of whose experts
  is held gets 0 from the layer. That partial sum goes on to the next
  layer: the other 15 chips' sums are absent here, as they are in the
  program.

Assumed readings (the published ``config.json`` does not spell them out;
the configuration file lists them under ``assumed``): the sink is a
denominator-only logit; ``attention_value_scale`` multiplies ``v``; the
window's edge is ``i - j < sliding_window``; rotary pairs dim ``d`` with
``d + rot/2`` (rotate-half) over the FIRST ``rot`` dims. Not built:
``attention_chunk_size``, the MTP layers, the vision and audio towers,
``attention_projection_layout`` (a storage layout; weights here are made
from the seed).

The parameter tree (matrices bfloat16 as stored, the rest float32):
``embed [V, H]``, ``head [V, H]``, ``final_norm [H]``, and per layer
``attn_norm``, ``ffn_norm`` ``[H]``; ``wq [H, heads*dk]``, ``wk [H,
n_kv*dk]``, ``wv [H, n_kv*dv]``, ``wo [heads*dv, H]``; window layers
``sink [heads]``; dense layers ``w_gate``, ``w_up`` ``[H, F]``,
``w_down [F, H]``; expert layers ``router [E, H]``, ``router_bias [E]``,
``w_gate``, ``w_up`` ``[count, H, Fm]``, ``w_down [count, Fm, H]``.
Each layer is upcast to float32 on its own, so the reference fits
beside the bfloat16 weights on one chip.

``_fault`` names ONE deliberate error, for the negative controls of
``tests/test_mimo_serving.py`` (each must fail the comparison that the
sound reference passes): ``bias_as_weight``, ``kv_map_off_by_one``.
The other controls are changes of the configuration dict.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np


def held(config):
    """``(first, count)`` of the experts this share holds."""
    first, count = config.get("held_experts",
                              (0, config["n_routed_experts"]))
    return int(first), int(count)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotary(x, positions, base, rot):
    """Rotate-half over the first ``rot`` dims of ``x [T, h, d]``."""
    half = rot // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rot)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def attention(config, lp, x, window, _fault=None):
    T = x.shape[0]
    heads, dk, dv = (config["num_attention_heads"], config["head_dim"],
                     config["v_head_dim"])
    n_kv = config["swa_num_key_value_heads" if window
                  else "num_key_value_heads"]
    base = config["swa_rope_theta" if window else "rope_theta"]
    rot = int(dk * config["partial_rotary_factor"])
    pos = jnp.arange(T)
    q = rotary((x @ lp["wq"]).reshape(T, heads, dk), pos, base, rot)
    k = rotary((x @ lp["wk"]).reshape(T, n_kv, dk), pos, base, rot)
    v = (x @ lp["wv"]).reshape(T, n_kv, dv) * config["attention_value_scale"]
    i, j = pos[:, None], pos[None, :]
    seen = j <= i
    if window:
        seen = seen & (i - j < config["sliding_window"])
    group = heads // n_kv
    out = []
    for h in range(heads):   # one head at a time: [T, T] scores
        g = h // group
        if _fault == "kv_map_off_by_one":
            g = (g + 1) % n_kv
        a = (q[:, h] @ k[:, g].T) / math.sqrt(dk)
        a = jnp.where(seen, a, -jnp.inf)
        m = jnp.max(a, axis=-1, keepdims=True)
        e = jnp.exp(a - m)
        denom = jnp.sum(e, axis=-1, keepdims=True)
        if window and config["add_swa_attention_sink_bias"]:
            m = jnp.maximum(m, lp["sink"][h])
            e = jnp.exp(a - m)
            denom = jnp.sum(e, axis=-1, keepdims=True) \
                + jnp.exp(lp["sink"][h] - m)
        out.append((e / denom) @ v[:, g])
    return jnp.stack(out, axis=1).reshape(T, heads * dv) @ lp["wo"]


def route(config, lp, x, _fault=None):
    """``(experts [T, k], weights [T, k])`` over ALL routed experts."""
    s = jax.nn.sigmoid(x @ lp["router"].T)
    k = config["num_experts_per_tok"]
    _, chosen = jax.lax.top_k(s + lp["router_bias"], k)
    picked = s + lp["router_bias"] if _fault == "bias_as_weight" else s
    w = jnp.take_along_axis(picked, chosen, axis=-1)
    if config["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return chosen, w * (config.get("routed_scaling_factor") or 1.0)


def moe(config, lp, x, _fault=None):
    """The share's partial sum: chosen experts that are held."""
    first, count = held(config)
    chosen, w = route(config, lp, x, _fault)
    y = jnp.zeros_like(x)
    for e in range(count):   # dense over the held experts, masked
        w_e = jnp.sum(jnp.where(chosen == first + e, w, 0.0), axis=-1)
        h = jax.nn.silu(x @ lp["w_gate"][e]) * (x @ lp["w_up"][e])
        y = y + w_e[:, None] * (h @ lp["w_down"][e])
    return y


def dense_mlp(lp, x):
    return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def hidden_states(config, params, ids, _fault=None, tap=None):
    """``[T, hidden]`` after the final norm, for one sequence ``ids``.
    ``tap(layer index, inner [T, hidden], y [T, hidden])`` is called at
    every expert layer with what went into it and the share's partial
    sum that came out, both float32: a judge holds a program's expert
    layer to ``y`` on the same ``inner``."""
    eps = config["layernorm_epsilon"]
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], jnp.asarray(ids, jnp.int32),
                     axis=0).astype(jnp.float32)
        for i, lp in enumerate(params["layers"]):
            lp = _f32(lp)   # this layer alone in float32
            window = bool(config["hybrid_layer_pattern"][i])
            x = x + attention(config, lp, rms_norm(x, lp["attn_norm"], eps),
                              window, _fault)
            inner = rms_norm(x, lp["ffn_norm"], eps)
            if config["moe_layer_freq"][i]:
                y = moe(config, lp, inner, _fault)
                if tap is not None:
                    tap(i, inner, y)
            else:
                y = dense_mlp(lp, inner)
            x = x + y
        return rms_norm(x, jnp.asarray(params["final_norm"], jnp.float32),
                        eps)


def logits(config, params, ids, _fault=None, tap=None):
    """Float32 ``[T, vocab]`` logits of one sequence."""
    x = hidden_states(config, params, ids, _fault, tap)
    with jax.default_matmul_precision("highest"):
        return x @ jnp.asarray(params["head"], jnp.float32).T


def best_and_chosen(config, params, ids, tap=None):
    """At every position but the last: the best next-token logit and the
    logit of the token that really follows. Two float32 ``[T - 1]``
    numpy arrays; the ``[T, vocab]`` logits stay on the device. ``tap``
    as in :func:`hidden_states`."""
    ids = jnp.asarray(ids, jnp.int32)
    out = logits(config, params, ids, tap=tap)[:-1]
    chosen = jnp.take_along_axis(out, ids[1:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(out, axis=-1)), np.asarray(chosen)


def bf16_step(value):
    """The distance between neighbouring bfloat16 numbers at ``value``."""
    return 2.0 ** (math.floor(math.log2(abs(value))) - 7)
