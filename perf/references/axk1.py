"""Plain reference of the A.X-K1 language model (``model_type`` ``axk1``:
the DeepSeek-V3 block) as ``axk1-ep16`` runs it: float32 ``jax.numpy``
under matmul precision "highest", one sequence at a time, no cache, no
kernels, no batching, the EXPANDED form of latent attention only (the
program's decode attends in the latent, with ``wkv_b`` absorbed into the
query and the output: other arithmetic, held to this one). It imports
nothing from ``apex_tpu``; it is handed the same parameter tree as the
engine (arrays only) and the configuration's published keys (a dict).

Equations (``x`` is ``[T, hidden]``; every projection is bias-free;
``rms(x, g) = x * rsqrt(mean(x^2) + rms_norm_eps) * g``):

* block: ``h = x + Attn(rms(x))``, ``y = h + FFN(rms(h))``; after the
  last layer a final norm and ``logits = x @ head^T`` (untied). Layer
  ``i`` has a dense SwiGLU of width ``intermediate_size`` where ``i <
  first_k_dense_replace``, else experts (``moe_layer_freq`` 1).
* queries: ``c_q = rms(x wq_a, q_norm)`` ``[q_lora_rank]``; ``q = c_q
  wq_b`` -> heads of ``qk_nope_head_dim + qk_rope_head_dim`` = ``q_nope
  ‖ q_pe``.
* latent: ``x wkv_a`` -> ``[kv_lora_rank + qk_rope_head_dim]`` = ``c_kv
  = rms(first kv_lora_rank, kv_norm)`` ‖ ``k_pe`` (one for all heads).
  Rotary on ``q_pe`` and ``k_pe`` only.
* expanded keys and values: ``c_kv wkv_b`` -> heads of ``qk_nope_head_dim
  + v_head_dim`` = ``k_nope ‖ v``; ``k = k_nope ‖ rot(k_pe)``; causal
  softmax of ``q . k * s``; output ``[T, heads * v_head_dim] wo``.
* scale: ``s = (qk_nope_head_dim + qk_rope_head_dim)^-0.5 * m^2``, ``m =
  0.1 * mscale_all_dim * ln(factor) + 1`` (1 without YaRN).
* YaRN: ``inv_freq = inter * (1 - mask) + extra * mask`` over the
  ``qk_rope_head_dim / 2`` pairs, ``extra = rope_theta^(-2i/d)``,
  ``inter = extra / factor``, ``mask = 1 - clip((i - low) / (high -
  low), 0, 1)``, ``low`` / ``high`` the floor / ceiling of the
  correction dims ``d ln(original / (beta 2 pi)) / (2 ln rope_theta)``
  at ``beta_fast`` / ``beta_slow``; cos and sin times ``mscale(factor,
  mscale) / mscale(factor, mscale_all_dim)``.
* experts: ``scores = sigmoid(x router^T)`` over all ``n_routed_experts``;
  the top ``num_experts_per_tok`` scores are chosen; weights = chosen
  scores / (their sum + 1e-20) (``norm_topk_prob``), times
  ``routed_scaling_factor``; ``y = sum_e w_e E_e(x) + E_shared(x)``,
  every ``E`` a SwiGLU (``w_down (silu(w_gate x) * w_up x)``), the shared
  one ``n_shared_experts * moe_intermediate_size`` wide. **The share**:
  the parameter tree holds experts ``[first, first + count)`` only; the
  layer routes over all experts, sums over the chosen experts it holds
  and adds the shared expert (every chip computes that alike). What the
  absent experts would add is left out, here as in the program, and the
  partial sum goes on to the next layer.

Assumed readings (the configuration file lists them under ``assumed``):
``topk_method: "none"`` is read as written: a plain top-k of the scores
over all experts, no correction bias, ``n_group`` / ``topk_group`` inert;
rotary pairs ADJACENT dims ``(2i, 2i + 1)`` of ``q_pe`` / ``k_pe``.
Not built: group-limited routing, the auxiliary loss (``seq_aux``).

The parameter tree (matrices bfloat16 as stored, the rest float32):
``embed [V, H]``, ``head [V, H]``, ``final_norm [H]``, and per layer
``attn_norm``, ``ffn_norm`` ``[H]``; ``wq_a [H, q_rank]``, ``q_norm
[q_rank]``, ``wq_b [q_rank, heads*(nope+rope)]``, ``wkv_a [H, kv_rank +
rope]``, ``kv_norm [kv_rank]``, ``wkv_b [kv_rank, heads*(nope+v)]``,
``wo [heads*v, H]``; dense layers ``w_gate``, ``w_up`` ``[H, F]``,
``w_down [F, H]``; expert layers ``router [E, H]``, ``w_gate``, ``w_up``
``[count, H, Fm]``, ``w_down [count, Fm, H]``, ``shared_gate``,
``shared_up`` ``[H, Fs]``, ``shared_down [Fs, H]``. Each layer is upcast
to float32 on its own (and of an expert layer's held experts one at a
time), so the reference fits beside the bfloat16 weights on one chip;
attention runs in blocks of ``QUERY_BLOCK`` queries, so the scores of
the longest judged sequence fit too.

``_fault`` names ONE deliberate error, for the negative controls of
``tests/test_axk1_serving.py`` (each must fail the comparison that the
sound reference passes): ``no_mscale_in_scale``, ``rope_on_nope_dims``,
``kv_norm_skipped``. The other controls are changes of the configuration
dict or of what the engine is given.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 256


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


def _mscale(factor, mscale):
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn(config):
    """``(inv_freq [rope/2] float32 numpy, cos/sin multiplier, softmax
    scale)`` of the configuration's ``rope_scaling`` (None: plain rotary)."""
    d, base = config["qk_rope_head_dim"], float(config["rope_theta"])
    extra = base ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    scale = (config["qk_nope_head_dim"] + d) ** -0.5
    rs = config.get("rope_scaling")
    if not rs:
        return extra.astype(np.float32), 1.0, scale
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
    mask = 1.0 - ramp                    # 1: extrapolated (unscaled)
    inv = extra / factor * (1.0 - mask) + extra * mask
    all_dim = rs.get("mscale_all_dim", 0)
    mult = _mscale(factor, rs.get("mscale", 1)) / _mscale(factor, all_dim)
    m = _mscale(factor, all_dim) if all_dim else 1.0
    return inv.astype(np.float32), mult, scale * m * m


def rotary(x, positions, inv_freq, mult):
    """Adjacent pairs ``(2i, 2i + 1)`` of the last axis of ``x [T, ...,
    d]`` turned by ``positions * inv_freq[i]``."""
    ang = positions.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (ang.shape[1],)
    cos, sin = (jnp.cos(ang) * mult).reshape(shape), \
        (jnp.sin(ang) * mult).reshape(shape)
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(config, lp, x, _fault=None):
    """The attention block's output ``[T, hidden]`` for ``x`` already
    normed: queries through their low rank, keys and values EXPANDED per
    head from the latent, causal softmax in blocks of queries."""
    T = x.shape[0]
    heads, nope, rope, dv, rank = (
        config["num_attention_heads"], config["qk_nope_head_dim"],
        config["qk_rope_head_dim"], config["v_head_dim"],
        config["kv_lora_rank"])
    eps = config["rms_norm_eps"]
    inv_freq, mult, scale = yarn(config)
    if _fault == "no_mscale_in_scale":
        scale = (nope + rope) ** -0.5
    pos = jnp.arange(T)
    q = (rms_norm(x @ lp["wq_a"], lp["q_norm"], eps)
         @ lp["wq_b"]).reshape(T, heads, nope + rope)
    kv = x @ lp["wkv_a"]
    c_kv, k_pe = kv[:, :rank], kv[:, rank:]
    if _fault != "kv_norm_skipped":
        c_kv = rms_norm(c_kv, lp["kv_norm"], eps)
    expanded = (c_kv @ lp["wkv_b"]).reshape(T, heads, nope + dv)
    k_nope, v = expanded[..., :nope], expanded[..., nope:]
    if _fault == "rope_on_nope_dims":   # the first `rope` dims turned
        q = jnp.concatenate([rotary(q[..., :rope], pos, inv_freq, mult),
                             q[..., rope:]], axis=-1)
        k = jnp.concatenate([
            rotary(k_nope[..., :rope], pos, inv_freq, mult),
            k_nope[..., rope:],
            jnp.broadcast_to(k_pe[:, None, :], (T, heads, rope))], axis=-1)
    else:
        q = jnp.concatenate([q[..., :nope],
                             rotary(q[..., nope:], pos, inv_freq, mult)],
                            axis=-1)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            rotary(k_pe, pos, inv_freq, mult)[:, None, :],
            (T, heads, rope))], axis=-1)
    out = []
    for q0 in range(0, T, QUERY_BLOCK):   # [heads, block, T] scores
        rows = pos[q0:q0 + QUERY_BLOCK]
        a = jnp.einsum("qhd,khd->hqk", q[q0:q0 + QUERY_BLOCK], k) * scale
        a = jnp.where(pos[None, None, :] <= rows[None, :, None], a, -jnp.inf)
        p = jax.nn.softmax(a, axis=-1)
        out.append(jnp.einsum("hqk,khd->qhd", p, v))
    return jnp.concatenate(out, axis=0).reshape(T, heads * dv) @ lp["wo"]


def route(config, lp, x):
    """``(experts [T, k], weights [T, k])`` over ALL routed experts."""
    s = jax.nn.sigmoid(x @ lp["router"].T)
    w, chosen = jax.lax.top_k(s, config["num_experts_per_tok"])
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


def hidden_states(config, params, ids, _fault=None, tap=None, attn_tap=None):
    """``[T, hidden]`` after the final norm, for one sequence ``ids``.
    ``tap(layer index, inner [T, hidden], y [T, hidden])`` is called at
    every expert layer with what went into it and the share's sum that
    came out (shared expert included); ``attn_tap`` likewise at every
    attention block (its normed input, its output after ``wo``). All
    float32: a judge holds a program's layer to ``y`` on the same
    ``inner``."""
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
            y = attention(config, lp, inner, _fault)
            if attn_tap is not None:
                attn_tap(i, inner, y)
            x = x + y
            inner = rms_norm(x, lp["ffn_norm"], eps)
            if is_expert_layer(config, i):
                y = moe(config, lp, inner)
                if tap is not None:
                    tap(i, inner, y)
            else:
                y = swiglu(inner, lp["w_gate"], lp["w_up"], lp["w_down"])
            x = x + y
        return rms_norm(x, jnp.asarray(params["final_norm"], jnp.float32),
                        eps)


def logits(config, params, ids, _fault=None, tap=None, attn_tap=None):
    """Float32 ``[T, vocab]`` logits of one sequence."""
    x = hidden_states(config, params, ids, _fault, tap, attn_tap)
    with jax.default_matmul_precision("highest"):
        return x @ jnp.asarray(params["head"], jnp.float32).T


def best_and_chosen(config, params, ids, tap=None, attn_tap=None):
    """At every position but the last: the best next-token logit and the
    logit of the token that really follows. Two float32 ``[T - 1]``
    numpy arrays; the ``[T, vocab]`` logits stay on the device. The taps
    as in :func:`hidden_states`."""
    ids = jnp.asarray(ids, jnp.int32)
    out = logits(config, params, ids, tap=tap, attn_tap=attn_tap)[:-1]
    chosen = jnp.take_along_axis(out, ids[1:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(out, axis=-1)), np.asarray(chosen)


def bf16_step(value):
    """The distance between neighbouring bfloat16 numbers at ``value``."""
    return 2.0 ** (math.floor(math.log2(abs(value))) - 7)
