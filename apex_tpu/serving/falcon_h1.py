"""The Falcon-H1 block (``model_type`` ``falcon_h1``) as a serving family:
prefill and decode programs over the paged KV pool AND a state that each
slot owns (serving/kv_cache.py ``init_slot_state``), for
``ServingEngine``'s normal path (serving/family.py is the seam).

Every layer runs Mamba-2 heads BESIDE attention heads on the same normed
input (``transformers`` ``models/falcon_h1/modeling_falcon_h1.py``: the
layer ``:1066-1135``, the mixer ``:497-985``, the gated norm
``:391-425``, the MLP ``:1007-1022``, the multipliers ``:1172-1206``)::

    h   = RMSNorm_in(x)
    x'  = x + Mixer(h * ssm_in_multiplier) * ssm_out_multiplier
            + Attn(h * attention_in_multiplier) * attention_out_multiplier
    out = x' + MLP(RMSNorm_ff(x'))

* the MIXER: ``[z | xBC | dt] = (u W_in) * mup`` (``ssm_multipliers``
  over z, x, B, C, dt); ``xBC = silu(causal depthwise conv(xBC) + b)``
  (width ``mamba_d_conv``); ``[xs | B | C] = xBC``; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the recurrence of ops/ssm.py per head
  (state ``[mamba_d_head, mamba_d_state]``, ``mamba_n_groups`` groups of
  B and C); ``y = GroupRMSNorm(y * silu(z))`` over ``mamba_n_groups``
  groups of channels (``mamba_norm_before_gate`` false); ``W_out``;
* ATTENTION: grouped-query, ``k`` scaled by ``key_multiplier``,
  rotate-half rotary over the whole head at ``rope_theta``; the pool's
  pages, the kernels every family shares (``grouped_decode_attention``,
  ``packed_gqa_attention``);
* the MLP: ``down(up(u) * silu(gate(u) * m_gate)) * m_down``
  (``mlp_multipliers``);
* the embedding times ``embedding_multiplier``; logits of the untied
  head times ``lm_head_multiplier``;
* weights bfloat16 as stored (norm gains, ``A_log``, ``dt_bias``, ``D``,
  the conv float32), activations bfloat16, norms, rotary, the
  recurrence and softmaxes float32, logits float32; the state and the
  conv's last inputs in ``cache_dtype`` (bfloat16: the published
  cache's).

THE STATE A SLOT OWNS: per layer a ``[slots + 1, heads, d_head,
d_state]`` leaf (``S``) and a ``[slots + 1, (d_conv - 1) * conv
channels]`` leaf (the conv's last inputs, oldest first: one flat row a
slot, which the TPU keeps row-major where a ``[.., 3, 5120]`` leaf would
be laid out 3-major and copied around every row write), the last row a
spare. The prefill writes
each packed segment's final ``S`` and conv inputs WHOLE into the row of
the slot that owns the segment's last token (``token_rows``), so an
admission needs no reset and a replayed (preempted) stream rebuilds its
row from its tokens alone; the decode program takes both leaves donated
and updates the live slots' rows in place (``ops.ssm.ssm_decode_update``).

The plain reference of these equations is ``perf/references/falcon_h1.py``;
``tests/test_falcon_h1_serving.py`` holds the engine to it through the
cache, and it to ``transformers``' own code.

Device scopes: ``embed``, ``layer/ssm/{in_proj,conv,scan,gate_norm,out}``,
``layer/attn/{qkv,rope,kv_write,attend,out}``, ``layer/mlp``,
``final_norm``, ``lm_head``, ``sample``.
"""

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.ops import ssm as ssm_ops
from apex_tpu.serving import kv_cache
from apex_tpu.serving.family import prefill_rows, switch_on_rows
from apex_tpu.serving.mimo import _logits, _mm, _normal, _rms_norm, _rotary


@dataclasses.dataclass(frozen=True)
class FalconH1Config:
    """The published keys of ``config.json`` (``model_type``
    ``falcon_h1``) that the serving programs read."""
    vocab_size: int
    num_hidden_layers: int
    hidden_size: int = 5120
    num_attention_heads: int = 20
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 21504
    max_position_embeddings: int = 262144
    rms_norm_eps: float = 1e-5
    rope_theta: float = 1e11
    rope_scaling: Optional[dict] = None
    hidden_act: str = "silu"
    attention_bias: bool = False
    mlp_bias: bool = False
    projectors_bias: bool = False
    mamba_proj_bias: bool = False
    mamba_conv_bias: bool = True
    mamba_d_conv: int = 4
    mamba_d_head: int = 128
    mamba_d_ssm: Optional[int] = 4096
    mamba_d_state: int = 256
    mamba_expand: int = 2
    mamba_n_groups: int = 2
    mamba_n_heads: int = 32
    mamba_chunk_size: int = 128
    mamba_norm_before_gate: bool = False
    mamba_rms_norm: bool = True
    mamba_use_mlp: bool = True
    attn_layer_indices: Optional[Tuple[int, ...]] = None
    tie_word_embeddings: bool = False
    embedding_multiplier: float = 5.656854249492381
    lm_head_multiplier: float = 0.0078125
    key_multiplier: float = 0.011048543456039804
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 0.0375
    ssm_in_multiplier: float = 0.25
    ssm_out_multiplier: float = 0.08838834764831845
    # over z, x, B, C, dt of the mixer's input projection
    ssm_multipliers: Tuple[float, ...] = (
        0.3535533905932738, 0.25, 0.1767766952966369, 0.5,
        0.3535533905932738)
    mlp_multipliers: Tuple[float, float] = (0.1767766952966369,
                                            0.011160714285714284)
    # the state's and the conv inputs' dtype (the published cache's: the
    # model's) and the KV pages'
    cache_dtype: str = "bfloat16"

    serving_family = "falcon_h1"   # serving/family.py picks the family by this

    @property
    def num_layers(self):
        return self.num_hidden_layers

    @property
    def d_ssm(self):
        return self.mamba_d_ssm if self.mamba_d_ssm is not None \
            else self.mamba_expand * self.hidden_size

    @property
    def conv_dim(self):
        """Channels of the convolution: ``xs ‖ B ‖ C``."""
        return self.d_ssm + 2 * self.mamba_n_groups * self.mamba_d_state

    @classmethod
    def from_dict(cls, d):
        """From a configuration dict with the published key names."""
        names = {f.name for f in dataclasses.fields(cls)}
        kw = {k: (tuple(v) if isinstance(v, list) else v)
              for k, v in d.items() if k in names}
        return cls(**kw)

    def to_dict(self):
        """The dict the plain reference reads (published key names)."""
        return {f.name: getattr(self, f.name)
                for f in dataclasses.fields(self)}


def check_config(cfg):
    problems = []
    if cfg.attn_layer_indices is not None:
        problems.append("attn_layer_indices (every layer here runs both "
                        "kinds of head)")
    if not cfg.mamba_use_mlp:
        problems.append("mamba_use_mlp false")
    if cfg.mamba_norm_before_gate or not cfg.mamba_rms_norm:
        problems.append("a mixer norm other than RMS after the gate")
    if any((cfg.attention_bias, cfg.mlp_bias, cfg.projectors_bias,
            cfg.mamba_proj_bias)):
        problems.append("projection biases")
    if not cfg.mamba_conv_bias:
        problems.append("a conv without its bias")
    if cfg.rope_scaling:
        problems.append(f"rope_scaling {cfg.rope_scaling}")
    if cfg.tie_word_embeddings:
        problems.append("a tied head")
    if cfg.hidden_act != "silu":
        problems.append(f"hidden_act {cfg.hidden_act!r}")
    if cfg.d_ssm != cfg.mamba_n_heads * cfg.mamba_d_head:
        problems.append(f"mamba_d_ssm {cfg.d_ssm} is not mamba_n_heads x "
                        f"mamba_d_head")
    if cfg.mamba_n_heads % cfg.mamba_n_groups \
            or cfg.d_ssm % cfg.mamba_n_groups:
        problems.append("mixer heads or channels do not split into "
                        "mamba_n_groups")
    if cfg.num_attention_heads % cfg.num_key_value_heads:
        problems.append(f"{cfg.num_attention_heads} query heads do not "
                        f"divide over {cfg.num_key_value_heads} KV heads")
    if cfg.head_dim % 2:
        problems.append("an odd head width (rotary pairs)")
    if len(cfg.ssm_multipliers) != 5 or len(cfg.mlp_multipliers) != 2:
        problems.append("ssm_multipliers / mlp_multipliers of the wrong "
                        "length")
    if problems:
        raise ValueError("serving does not support: " + "; ".join(problems))


def mup_vector(cfg):
    """The multiplier of each column of the mixer's input projection
    (``modeling_falcon_h1.py:1172-1206``): z, x, B, C, dt. numpy."""
    G, N = cfg.mamba_n_groups, cfg.mamba_d_state
    widths = (cfg.d_ssm, cfg.d_ssm, G * N, G * N, cfg.mamba_n_heads)
    return np.concatenate([np.full(w, m, np.float32)
                           for w, m in zip(widths, cfg.ssm_multipliers)])


# ---------------------------------------------------------------- weights

def init_params(cfg, key, std=0.02, dtype=jnp.bfloat16):
    """Random weights from a PRNG key (an ARGUMENT of every program that
    makes them: a new seed compiles nothing). Matrices ``dtype``, N(0,
    ``std``); norm gains one. The mixer's scalars as Mamba-2 initialises
    them (``mamba_ssm`` ``Mamba2``): ``A_log = log U[1, 16]``,
    ``softplus(dt_bias)`` log-uniform in [0.001, 0.1], ``D = 1``; the
    conv's weight and bias U(+-1/sqrt(d_conv)) (a depthwise
    ``nn.Conv1d``'s own), all float32."""
    if isinstance(key, (int, np.integer)):
        key = jax.random.PRNGKey(int(key))
    H, hq, n_kv, hd = (cfg.hidden_size, cfg.num_attention_heads,
                       cfg.num_key_value_heads, cfg.head_dim)
    nh, K, F = cfg.mamba_n_heads, cfg.mamba_d_conv, cfg.intermediate_size
    proj = 2 * cfg.d_ssm + 2 * cfg.mamba_n_groups * cfg.mamba_d_state + nh
    keys = iter(jax.random.split(key, 16 * cfg.num_layers + 4))

    def mat(*shape):
        return _normal(next(keys), shape, dtype, std)

    def uniform(shape, lo, hi):
        return jax.random.uniform(next(keys), shape, jnp.float32, lo, hi)

    ones = lambda n: jnp.ones((n,), jnp.float32)            # noqa: E731
    bound = 1.0 / math.sqrt(K)
    params = {"embed": mat(cfg.vocab_size, H), "head": mat(cfg.vocab_size, H),
              "final_norm": ones(H), "layers": []}
    for _ in range(cfg.num_layers):
        dt0 = jnp.exp(uniform((nh,), math.log(1e-3), math.log(1e-1)))
        params["layers"].append({
            "input_norm": ones(H), "ff_norm": ones(H),
            "wq": mat(H, hq * hd), "wk": mat(H, n_kv * hd),
            "wv": mat(H, n_kv * hd), "wo": mat(hq * hd, H),
            "w_in": mat(H, proj), "w_out": mat(cfg.d_ssm, H),
            "conv_w": uniform((K, cfg.conv_dim), -bound, bound),
            "conv_b": uniform((cfg.conv_dim,), -bound, bound),
            "A_log": jnp.log(uniform((nh,), 1.0, 16.0)),
            # the inverse of the softplus: softplus(dt_bias) = dt0
            "dt_bias": dt0 + jnp.log(-jnp.expm1(-dt0)),
            "D": ones(nh), "gate_norm": ones(cfg.d_ssm),
            "w_gate": mat(H, F), "w_up": mat(H, F), "w_down": mat(F, H)})
    return params


def init_cache(cfg, num_slots, num_pages, page_size, dtype=jnp.bfloat16):
    """The pool's K/V pages (``{"k", "v"}``) and the state each slot
    owns (``{"ssm", "conv"}``), one leaf of each a layer."""
    cache = kv_cache.init_cache(cfg.num_layers, cfg.num_key_value_heads,
                                num_pages, page_size, cfg.head_dim, dtype)
    cache.update(kv_cache.init_slot_state(
        cfg.num_layers, num_slots,
        (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_d_state),
        ((cfg.mamba_d_conv - 1) * cfg.conv_dim,),
        jnp.dtype(cfg.cache_dtype)))
    return cache


# ------------------------------------------------------------- the block

def _mmf(x, w):
    """x @ w with float32 accumulation, left in float32 (a multiplier
    applies before the cast)."""
    return lax.dot_general(x, w, (((x.ndim - 1,), (0,)), ((), ())),
                           preferred_element_type=jnp.float32)


def mixer_inputs(u, lp, cfg):
    """The input projection of the mixer on the layer's normed input
    ``u [T, hidden]``: ``(z, xBC before the conv, dt before the softplus)``
    (``z``, ``xBC`` in u's dtype, ``dt`` float32)."""
    p = _mmf(u * jnp.asarray(cfg.ssm_in_multiplier, u.dtype), lp["w_in"]) \
        * jnp.asarray(mup_vector(cfg))
    d, c = cfg.d_ssm, cfg.conv_dim
    return p[:, :d].astype(u.dtype), p[:, d:d + c].astype(u.dtype), \
        p[:, d + c:]


def split_conv_out(xbc, cfg):
    """``silu(conv out) [T, conv_dim]`` float32 -> ``(xs [T, heads,
    d_head], B, C [T, groups, d_state])``."""
    T, d = xbc.shape[0], cfg.d_ssm
    G, N = cfg.mamba_n_groups, cfg.mamba_d_state
    return (xbc[:, :d].reshape(T, cfg.mamba_n_heads, cfg.mamba_d_head),
            xbc[:, d:d + G * N].reshape(T, G, N),
            xbc[:, d + G * N:].reshape(T, G, N))


def mixer_output(y, z, lp, cfg):
    """``y [T, heads, d_head]`` float32 (``S C + D x``) gated by
    ``silu(z)``, RMS-normed over each of ``mamba_n_groups`` channel
    groups, projected: the mixer's output times ``ssm_out_multiplier``,
    float32."""
    T = y.shape[0]
    with jax.named_scope("gate_norm"):
        g = y.reshape(T, -1) * jax.nn.silu(z.astype(jnp.float32))
        g = g.reshape(T, cfg.mamba_n_groups, -1)
        g = g * lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
        g = (g.reshape(T, -1) * lp["gate_norm"]).astype(z.dtype)
    with jax.named_scope("out"):
        return _mmf(g, lp["w_out"]) * cfg.ssm_out_multiplier


def mixer(u, lp, cfg, recur):
    """The mixer on the normed input ``u``; ``recur(xbc, dt)`` owns what
    differs between the programs (the conv and the recurrence over the
    slot's state or over the packed batch, with the state writes) and
    returns ``y = S C + D x`` float32 ``[T, heads, d_head]``. Returns the
    mixer's output times ``ssm_out_multiplier``, float32. Public: the
    benchmark's judge holds it, in its decode form over the engine's
    state, to the plain reference."""
    with jax.named_scope("in_proj"):
        z, xbc, dt = mixer_inputs(u, lp, cfg)
        dt = jax.nn.softplus(dt + lp["dt_bias"])
    return mixer_output(recur(xbc, dt), z, lp, cfg)


def attention(u, lp, cfg, positions, attend):
    """The attention heads on the normed input ``u``; ``attend(q, k, v)``
    owns the cache write and the attention (``[T, heads * head_dim]``
    out). Returns the block's output times ``attention_out_multiplier``,
    float32."""
    T = u.shape[0]
    hq, n_kv, hd = (cfg.num_attention_heads, cfg.num_key_value_heads,
                    cfg.head_dim)
    with jax.named_scope("qkv"):
        a = u * jnp.asarray(cfg.attention_in_multiplier, u.dtype)
        q = _mm(a, lp["wq"]).reshape(T, hq, hd)
        k = (_mmf(a, lp["wk"]) * cfg.key_multiplier).astype(u.dtype) \
            .reshape(T, n_kv, hd)
        v = _mm(a, lp["wv"]).reshape(T, n_kv, hd)
    with jax.named_scope("rope"):
        q = _rotary(q, positions, cfg.rope_theta, hd)
        k = _rotary(k, positions, cfg.rope_theta, hd)
    ctx = attend(q, k, v)
    with jax.named_scope("out"):
        return _mmf(ctx, lp["wo"]) * cfg.attention_out_multiplier


def mlp(u, lp, cfg):
    gm, dm = cfg.mlp_multipliers
    g = _mmf(u, lp["w_gate"]) * gm
    h = (_mmf(u, lp["w_up"]) * jax.nn.silu(g)).astype(u.dtype)
    return _mmf(h, lp["w_down"]) * dm


def _trunk(params, cfg, x, positions, attend_of, recur_of):
    """Every layer; ``attend_of(i)`` / ``recur_of(i)`` give layer ``i``'s
    closures. Returns ``x`` after the final norm."""
    eps = cfg.rms_norm_eps
    for i, lp in enumerate(params["layers"]):
        with jax.named_scope("layer"):
            h = _rms_norm(x, lp["input_norm"], eps)
            with jax.named_scope("ssm"):
                m = mixer(h, lp, cfg, recur_of(i))
            with jax.named_scope("attn"):
                a = attention(h, lp, cfg, positions, attend_of(i))
            x = (x.astype(jnp.float32) + m + a).astype(x.dtype)
            with jax.named_scope("mlp"):
                y = mlp(_rms_norm(x, lp["ff_norm"], eps), lp, cfg)
                x = (x.astype(jnp.float32) + y).astype(x.dtype)
    with jax.named_scope("final_norm"):
        return _rms_norm(x, params["final_norm"], eps)


def _embed(params, ids, cfg):
    return (jnp.take(params["embed"], ids, axis=0).astype(jnp.float32)
            * cfg.embedding_multiplier).astype(params["embed"].dtype)


def _lm_head(x, params, cfg):
    return _logits(x, params["head"]) * cfg.lm_head_multiplier


def _write_kv(cache, i, page, off, k, v):
    with jax.named_scope("kv_write"):
        cache["k"][i] = kv_cache.write_rows(cache["k"][i], page, off, k)
        cache["v"][i] = kv_cache.write_rows(cache["v"][i], page, off, v)


# --------------------------------------------------------------- prefill

def prefill(params, cache, ids, positions, seg, token_rows, page_table,
            last_idx, *, cfg, attn_impl=None, interpret=None):
    """One packed prompt batch through the trunk (arguments as
    ``mimo.prefill``): every token's K/V into its page, each segment's
    final state and conv inputs into the row of the slot that owns its
    last token. Returns ``(cache, logits [G, vocab] float32)``.

    The trunk runs on the first ``R`` of the ``S`` packed rows, ``R`` the
    smallest of ``family.prefill_rows`` that holds the batch's tokens. Out
    of the branch come each layer's K/V rows and what the segments'
    final states are made of (the conv's inputs, ``dt x``, ``B``, ``dt
    A``, the state entering each chunk), padded back to ``S`` rows;
    both are written here, behind the switch, so no branch carries a
    cache leaf. The state rows are written in ONE loop over the batch's
    segments."""
    from apex_tpu.ops.attention import packed_gqa_attention

    cache = {name: list(leaves) for name, leaves in cache.items()}
    hq, hd = cfg.num_attention_heads, cfg.head_dim
    S = ids.shape[0]
    Q = cfg.mamba_chunk_size
    nc = -(-S // Q)
    ps = cache["k"][0].shape[1]
    seg = seg.astype(jnp.int32)

    def trunk_on(R):
        def branch(ids, positions, seg):
            ids, positions, seg = ids[:R], positions[:R], seg[:R]
            kv, states = [], []   # every layer's, in layer order

            def attend_of(i):
                def attend(q, k, v):
                    kv.append((k, v))
                    with jax.named_scope("attend"):
                        ctx = packed_gqa_attention(
                            q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                            v.transpose(1, 0, 2), seg,
                            sm_scale=1.0 / math.sqrt(hd), impl=attn_impl,
                            interpret=interpret)
                        return ctx.transpose(1, 0, 2).reshape(R, hq * hd)

                return attend

            def recur_of(i):
                lp = params["layers"][i]

                def recur(xbc, dt):
                    with jax.named_scope("conv"):
                        xs, B, C = split_conv_out(jax.nn.silu(
                            ssm_ops.segment_conv(xbc, seg, lp["conv_w"],
                                                 lp["conv_b"])), cfg)
                    with jax.named_scope("scan"):
                        y, carry = ssm_ops.chunked_scan(
                            xs, dt, -jnp.exp(lp["A_log"]), B, C, seg,
                            chunk=Q)
                        states.append((xbc, carry))
                        return y + lp["D"][None, :, None] * xs

                return recur

            with jax.named_scope("embed"):
                x = _embed(params, ids, cfg)
            x = _trunk(params, cfg, x, positions, attend_of, recur_of)

            def rows(v):
                return jnp.pad(v, ((0, S - v.shape[0]),)
                               + ((0, 0),) * (v.ndim - 1))

            kv = [(rows(k.reshape(R, -1)), rows(v.reshape(R, -1)))
                  for k, v in kv]
            states = [(rows(xbc), tuple(map(rows, carry[:3]))
                       + (jnp.pad(carry[3], ((0, nc - carry[3].shape[0]),)
                                  + ((0, 0),) * 3),))
                      for xbc, carry in states]
            return (jnp.take(x, jnp.minimum(last_idx, R - 1), axis=0), kv,
                    states)

        return branch

    last, kv, states = switch_on_rows(prefill_rows(S), trunk_on, ids,
                                      positions, seg)
    with jax.named_scope("embed"):
        page = jnp.take_along_axis(
            jnp.take(page_table, token_rows, axis=0),
            (positions // ps)[:, None], axis=1)[:, 0]
        off = positions % ps
    for i, (k, v) in enumerate(kv):
        with jax.named_scope("layer"), jax.named_scope("attn"):
            _write_kv(cache, i, page, off, k, v)
    with jax.named_scope("layer"), jax.named_scope("ssm"):
        cache["ssm"], cache["conv"] = write_segment_states(
            cache["ssm"], cache["conv"], states, seg, token_rows, chunk=Q)
    with jax.named_scope("lm_head"):
        logits = _lm_head(last, params, cfg)
    return cache, logits


def write_segment_states(ssm_leaves, conv_leaves, states, seg, token_rows,
                         *, chunk):
    """Each segment's final state (scope ``scan``) and last conv inputs
    (scope ``conv``) of every layer, whole, into the row of the slot that
    owns the segment's last token: ONE loop over the batch's segments
    (ids ``1..max(seg)``, contiguous and in order), the leaves carried in
    place."""
    width = conv_leaves[0].shape[1] // states[0][0].shape[1]

    def body(r, leaves):
        ssm_l, conv_l = leaves
        end = jnp.sum(((seg > 0) & (seg <= r)).astype(jnp.int32)) - 1
        slot = token_rows[end]
        ssm_l, conv_l = list(ssm_l), list(conv_l)
        for i, (xbc, carry) in enumerate(states):
            with jax.named_scope("scan"):
                st = ssm_ops.final_state(carry, seg, end, chunk=chunk)
                ssm_l[i] = lax.dynamic_update_slice(
                    ssm_l[i], st[None].astype(ssm_l[i].dtype), (slot, 0, 0, 0))
            with jax.named_scope("conv"):
                tail = ssm_ops.conv_tail(xbc, seg, end, width)
                conv_l[i] = lax.dynamic_update_slice(
                    conv_l[i], tail.reshape(1, -1).astype(conv_l[i].dtype),
                    (slot, 0))
        return ssm_l, conv_l

    return lax.fori_loop(1, jnp.max(seg) + 1, body,
                         (list(ssm_leaves), list(conv_leaves)))


# ---------------------------------------------------------------- decode

def conv_step(conv_leaf, xbc, lp, active):
    """The conv of one token a lane over the slot's last inputs (rows
    ``[0, b)`` of ``conv_leaf``), which it then shifts in place. Returns
    ``(conv_leaf, silu(conv out) [b, conv_dim] float32)``."""
    b, C = xbc.shape
    old = conv_leaf[:b]
    window = jnp.concatenate([old.reshape(b, -1, C),
                              xbc[:, None, :].astype(old.dtype)],
                             axis=1)                      # [b, K, C]
    out = jnp.einsum("bkc,kc->bc", window.astype(jnp.float32),
                     lp["conv_w"].astype(jnp.float32),
                     precision=lax.Precision.HIGHEST) + lp["conv_b"]
    new = jnp.where(active[:, None], window[:, 1:].reshape(b, -1), old)
    return lax.dynamic_update_slice(conv_leaf, new, (0, 0)), \
        jax.nn.silu(out)


def decode_recur(cache, i, lp, cfg, active, ssm_impl=None, interpret=None):
    """Layer ``i``'s ``recur`` in the decode form: the conv over the
    slot's last inputs, then one step of the recurrence on the slot's
    state, both leaves updated in place. Public: the benchmark's judge
    runs the mixer through it over the engine's own state."""
    def recur(xbc, dt):
        with jax.named_scope("conv"):
            cache["conv"][i], out = conv_step(cache["conv"][i], xbc, lp,
                                              active)
            xs, B, C = split_conv_out(out, cfg)
        with jax.named_scope("scan"):
            cache["ssm"][i], y = ssm_ops.ssm_decode_update(
                cache["ssm"][i], xs, dt, -jnp.exp(lp["A_log"]), B, C,
                lp["D"], active, impl=ssm_impl, interpret=interpret)
            return y

    return recur


def decode_step(params, cache, tokens, lengths, page_table, *, cfg,
                decode_impl=None, ssm_impl=None, interpret=None):
    """One greedy decode step for every slot (arguments as
    ``mimo.decode_step``; slot ``i`` is row ``i`` of every state leaf).
    Returns ``(cache, next_tokens [B], logits [B, vocab] float32)``."""
    from apex_tpu.ops import decode_attention_pallas as dap

    cache = {name: list(leaves) for name, leaves in cache.items()}
    hq, hd = cfg.num_attention_heads, cfg.head_dim
    B = tokens.shape[0]
    ps = cache["k"][0].shape[1]

    with jax.named_scope("embed"):
        active = lengths > 0
        positions = jnp.maximum(lengths - 1, 0)
        x = _embed(params, tokens, cfg)
        page = jnp.where(active, jnp.take_along_axis(
            page_table, (positions // ps)[:, None], axis=1)[:, 0], 0)
        off = jnp.where(active, positions % ps, 0)
        table, base = kv_cache.pool_view(page_table, positions, lengths, ps)

    def attend_of(i):
        def attend(q, k, v):
            _write_kv(cache, i, page, off, k, v)
            with jax.named_scope("attend"):
                ctx = dap.grouped_decode_attention(
                    q, cache["k"][i], cache["v"][i], page_table=table,
                    page_base=base, lengths=lengths, n_kv=k.shape[1],
                    sm_scale=1.0 / math.sqrt(hd), impl=decode_impl,
                    interpret=interpret)
                return ctx.reshape(B, hq * hd)

        return attend

    def recur_of(i):
        return decode_recur(cache, i, params["layers"][i], cfg, active,
                            ssm_impl, interpret)

    x = _trunk(params, cfg, x, positions, attend_of, recur_of)
    with jax.named_scope("lm_head"):
        logits = _lm_head(x, params, cfg)
    with jax.named_scope("sample"):
        next_tokens = jnp.where(
            active, jnp.argmax(logits, axis=-1).astype(jnp.int32), 0)
    return cache, next_tokens, logits


def decode_attention_resolved(cfg, cache, decode_impl):
    """The decode-attention impl (``engine.decode_attn_impl``)."""
    from apex_tpu.ops import decode_attention_pallas as dap

    leaf = cache["k"][0]
    return dap.grouped_resolved(
        cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim,
        cfg.head_dim, leaf.shape[1], leaf.dtype, decode_impl)
