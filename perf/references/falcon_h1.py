"""Plain reference of the Falcon-H1 language model as
``falcon-h1-34b-pp12`` runs it: float32 ``jax.numpy`` under matmul
precision "highest", one sequence at a time, no cache, no kernels, no
packing, no chunking: the recurrence walks the sequence TOKEN BY TOKEN
(``lax.scan``). It imports nothing from ``apex_tpu``; it is handed the
same parameter tree as the engine (arrays only) and the configuration's
published keys (a dict). Each layer is upcast to float32 on its own, so
the reference fits beside the engine's bfloat16 weights on one chip.

Equations (``x`` is ``[T, hidden]``; every projection bias-free;
``RMSNorm(x) = x * rsqrt(mean(x^2) + rms_norm_eps) * g``); every layer
is the same (``attn_layer_indices`` null, ``mamba_use_mlp`` true),
``transformers`` ``models/falcon_h1/modeling_falcon_h1.py:1066-1135``:

* ``h = RMSNorm_in(x)``; ``x' = x + Mixer(h * ssm_in_multiplier) *
  ssm_out_multiplier + Attn(h * attention_in_multiplier) *
  attention_out_multiplier``; ``out = x' + MLP(RMSNorm_ff(x'))``.
* ``Attn(u)``: ``q = u wq`` (heads x head_dim), ``k = (u wk) *
  key_multiplier``, ``v = u wv`` (KV heads; query head ``i`` reads KV
  head ``i // (heads / kv_heads)``); rotate-half rotary over the whole
  head at ``rope_theta``; causal softmax of ``q k / sqrt(head_dim)``;
  ``[T, heads * head_dim] wo``.
* ``Mixer(u)``: ``[z | xBC | dt] = (u w_in) * mup`` (``ssm_multipliers``
  over the z, x, B, C and dt columns: ``:1172-1206``); ``xBC =
  silu(sum_k conv_w[k] xBC[t - d_conv + 1 + k] + conv_b)`` (positions
  before 0 read 0); ``[xs | B | C] = xBC`` (``mamba_d_ssm | groups x
  d_state | groups x d_state``); ``dt = softplus(dt + dt_bias)``, ``A =
  -exp(A_log)``; per head ``h`` (group ``h // (heads / groups)``) and
  token ``t``: ``S = exp(dt A) S + dt xs B^T``, ``y = S C + D xs``
  (``:900-905``'s one-step form, from ``S = 0``); ``g = y * silu(z)``,
  RMS-normed over each of ``mamba_n_groups`` channel groups, times
  ``gate_norm`` (``FalconH1RMSNormGated``, ``norm_before_gate`` false:
  ``:391-425``); ``w_out``.
* ``MLP(u) = ((u w_up) * silu((u w_gate) * m_gate)) w_down * m_down``
  (``mlp_multipliers``: ``:1007-1022``).
* embedding ``E[id] * embedding_multiplier``; logits ``RMSNorm_final(x)
  head^T * lm_head_multiplier`` (untied).

Departures from the published code: none in the arithmetic. The
published prefill computes the recurrence in its chunked form
(``:915-976``), which equals this one; its ``time_step_limit`` is (0,
inf), a clamp that changes nothing.

The CACHE (``cache_from``): by default the state stays float32 from the
first token to the last (the model's math). Served, the published code
keeps the state and the conv's last inputs in its cache, in the model's
dtype (``:98-115``): the prefill's final state is stored there, and
every decode step reads the stored state and stores its update
(``:886-888``), its conv reading stored inputs (``:803-816``). Given
``cache_from`` (the first position a decode step serves), this reference
does the same in the configuration's ``cache_dtype``: the state is
rounded to it after the update of every position from ``cache_from -
1`` on, and the conv of every position from ``cache_from`` on reads its
inputs rounded to it. The rounding is ``lax.reduce_precision``, not a
conversion to ``cache_dtype`` and back: inside one compiled program XLA
on a TPU drops such a pair (it may keep excess precision), which leaves
the state at the float32 math there, 9% off the stored state after 500
steps. A decode step's ``y`` is taken from the update
before it is stored, as ``mamba_ssm``'s ``selective_state_update`` takes
it (the published torch path reads it back from the cache: a rounding
of the state apart).

The parameter tree (matrices bfloat16 as stored, the rest float32):
``embed``, ``head [V, H]``, ``final_norm [H]``, and per layer
``input_norm``, ``ff_norm [H]``; ``wq [H, heads*hd]``, ``wk``, ``wv [H,
kv*hd]``, ``wo [heads*hd, H]``; ``w_in [H, 2 d_ssm + 2 G N + nh]``,
``conv_w [d_conv, conv_dim]``, ``conv_b [conv_dim]``, ``A_log``,
``dt_bias``, ``D [nh]``, ``gate_norm [d_ssm]``, ``w_out [d_ssm, H]``;
``w_gate``, ``w_up [H, F]``, ``w_down [F, H]``.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: jnp.asarray(a, jnp.float32), tree)


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rotary(x, positions, base):
    """Rotate-half over the whole last axis of ``x [T, h, d]``."""
    half = x.shape[-1] // 2
    inv = jnp.float32(base) ** (-jnp.arange(half, dtype=jnp.float32)
                                * 2.0 / x.shape[-1])
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(config, lp, u):
    T = u.shape[0]
    heads, n_kv, hd = (config["num_attention_heads"],
                       config["num_key_value_heads"], config["head_dim"])
    pos = jnp.arange(T)
    q = rotary((u @ lp["wq"]).reshape(T, heads, hd), pos,
               config["rope_theta"])
    k = rotary((u @ lp["wk"] * config["key_multiplier"]).reshape(
        T, n_kv, hd), pos, config["rope_theta"])
    v = (u @ lp["wv"]).reshape(T, n_kv, hd)
    seen = pos[None, :] <= pos[:, None]
    group = heads // n_kv
    out = []
    for h in range(heads):   # one head at a time: [T, T] scores
        a = (q[:, h] @ k[:, h // group].T) / math.sqrt(hd)
        out.append(jax.nn.softmax(jnp.where(seen, a, -jnp.inf), axis=-1)
                   @ v[:, h // group])
    return jnp.stack(out, axis=1).reshape(T, heads * hd) @ lp["wo"]


def mup(config):
    d, G, N = (config["mamba_d_ssm"], config["mamba_n_groups"],
               config["mamba_d_state"])
    widths = (d, d, G * N, G * N, config["mamba_n_heads"])
    return jnp.concatenate([jnp.full((w,), m, jnp.float32) for w, m in
                            zip(widths, config["ssm_multipliers"])])


def mixer(config, lp, u, at, cache_from):
    """``(output [T, hidden], state)`` of the mixer on ``u`` (already
    times ``ssm_in_multiplier``); ``state`` is ``(S [len(at), heads,
    d_head, d_state], conv inputs [len(at), d_conv - 1, conv_dim])``
    after each of the positions ``at`` (an int32 vector; -1 names none).
    ``cache_from`` (an int32 scalar; past the last position: the float32
    math alone) is the first position a decode step serves: from the one
    before it on, the state and the conv's inputs are what the published
    cache holds, values of ``cache_dtype`` (module docstring)."""
    T = u.shape[0]
    d, G, N = (config["mamba_d_ssm"], config["mamba_n_groups"],
               config["mamba_d_state"])
    nh, P, K = (config["mamba_n_heads"], config["mamba_d_head"],
                config["mamba_d_conv"])
    rep = nh // G
    p = (u @ lp["w_in"]) * mup(config)
    z, xbc, dt = p[:, :d], p[:, d:2 * d + 2 * G * N], p[:, 2 * d + 2 * G * N:]
    padded = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xbc])
    conv = sum(lp["conv_w"][k] * padded[k:k + T] for k in range(K))
    t = jnp.arange(T)
    dtype = jnp.finfo(config["cache_dtype"])

    def store(v):   # a value of cache_dtype, held in float32
        return jax.lax.reduce_precision(v, exponent_bits=dtype.nexp,
                                        mantissa_bits=dtype.nmant)

    cached = sum(lp["conv_w"][k] * store(padded[k:k + T]) for k in range(K))
    conv = jnp.where((t >= cache_from)[:, None], cached, conv)
    act = jax.nn.silu(conv + lp["conv_b"])
    xs = act[:, :d].reshape(T, nh, P)
    B = jnp.repeat(act[:, d:d + G * N].reshape(T, G, N), rep, axis=1)
    C = jnp.repeat(act[:, d + G * N:].reshape(T, G, N), rep, axis=1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    A = -jnp.exp(lp["A_log"])
    stored = t >= cache_from - 1

    def step(carry, inp):
        S, kept = carry
        hit, keep_as_stored, x_t, dt_t, B_t, C_t = inp
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * B_t[:, None, :]
        y = jnp.einsum("hpn,hn->hp", S, C_t) + lp["D"][:, None] * x_t
        S = jnp.where(keep_as_stored, store(S), S)
        return (S, jnp.where(hit[:, None, None, None], S[None], kept)), y

    zero = jnp.zeros((nh, P, N), jnp.float32)
    (_, kept), y = jax.lax.scan(
        step, (zero, jnp.zeros((at.shape[0], nh, P, N), jnp.float32)),
        (t[:, None] == at[None, :], stored, xs, dt, B, C))
    g = (y.reshape(T, d) * jax.nn.silu(z)).reshape(T, G, d // G)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + config["rms_norm_eps"])
    out = (g.reshape(T, d) * lp["gate_norm"]) @ lp["w_out"]
    idx = at[:, None] - K + 2 + jnp.arange(K - 1)[None, :]
    tail = jnp.where((idx >= 0)[..., None],
                     jnp.take(xbc, jnp.maximum(idx, 0), axis=0), 0.0)
    return out, (kept, jnp.where(cache_from < T, store(tail), tail))


def mlp(config, lp, u):
    gm, dm = config["mlp_multipliers"]
    return ((u @ lp["w_up"]) * jax.nn.silu((u @ lp["w_gate"]) * gm)) \
        @ lp["w_down"] * dm


def _layer(config, lp, x, at, cache_from):
    """One layer on ``x [T, hidden]`` float32: ``(out, its normed input,
    the mixer's output times ssm_out_multiplier, the mixer's state)``."""
    eps = config["rms_norm_eps"]
    lp = _f32(lp)   # this layer alone in float32
    h = rms_norm(x, lp["input_norm"], eps)
    m, state = mixer(config, lp, h * config["ssm_in_multiplier"], at,
                     cache_from)
    m = m * config["ssm_out_multiplier"]
    a = attention(config, lp, h * config["attention_in_multiplier"])
    x = x + m + a * config["attention_out_multiplier"]
    return x + mlp(config, lp, rms_norm(x, lp["ff_norm"], eps)), h, m, state


@functools.lru_cache(maxsize=None)
def _compiled_layer(config_json):
    """:func:`_layer` compiled once for a configuration (a JSON text: the
    dict is not hashable): every layer and every call of the same
    length reuse it."""
    config = json.loads(config_json)
    return jax.jit(functools.partial(_layer, config))


def forward(config, params, ids, at=None, mixer_tap=None, cache_from=None):
    """``(logits [T, vocab] float32, states)`` of one sequence ``ids``:
    ``states`` a layer ``(S, conv inputs)`` after each position of the
    list ``at``, stacked (None where ``at`` is None); ``cache_from`` as in
    :func:`mixer` (None: the float32 math alone). ``mixer_tap(layer, u,
    m)``, where given, sees each layer's normed input ``u`` and the
    mixer's output ``m`` (times ``ssm_out_multiplier``), both float32
    ``[T, hidden]``."""
    eps = config["rms_norm_eps"]
    ids = jnp.asarray(ids, jnp.int32)
    T = ids.shape[0]
    at_ = jnp.asarray([-1] if at is None else at, jnp.int32).reshape(-1)
    since = jnp.int32(T + 1 if cache_from is None else cache_from)
    layer = _compiled_layer(json.dumps(config, sort_keys=True))
    states = []
    with jax.default_matmul_precision("highest"):
        x = jnp.take(params["embed"], ids, axis=0).astype(jnp.float32) \
            * config["embedding_multiplier"]
        for i, lp in enumerate(params["layers"]):
            x, h, m, state = layer(lp, x, at_, since)
            if mixer_tap is not None:
                mixer_tap(i, h, m)
            states.append(state)
        x = rms_norm(x, jnp.asarray(params["final_norm"], jnp.float32), eps)
        logits = x @ jnp.asarray(params["head"], jnp.float32).T \
            * config["lm_head_multiplier"]
    return logits, (states if at is not None else None)


def best_and_chosen(config, params, ids, at=None, mixer_tap=None,
                    cache_from=None):
    """At every position but the last: the best next-token logit and the
    logit of the token that really follows (two float32 ``[T - 1]``
    numpy arrays), and :func:`forward`'s ``states``."""
    ids = jnp.asarray(ids, jnp.int32)
    logits, states = forward(config, params, ids, at, mixer_tap, cache_from)
    out = logits[:-1]
    chosen = jnp.take_along_axis(out, ids[1:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(out, axis=-1)), np.asarray(chosen), states


def bf16_step(value):
    """The distance between neighbouring bfloat16 numbers at ``value``."""
    return 2.0 ** (math.floor(math.log2(abs(value))) - 7)
