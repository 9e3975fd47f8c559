"""Fused (flash) attention for TPU.

The TPU-native replacement for the reference's attention kernel zoo —
``fmhalib`` (contrib/csrc/fmha, 6,958 LoC), ``fast_multihead_attn``
(8,010 LoC) and the three megatron softmax kernels (SURVEY §2.6): ONE
blockwise-softmax attention with causal and segment-id (varlen) masking.

On TPU this lowers to the Pallas flash-attention kernel (memory-bound
optimal: no [s, s] score tensor ever touches HBM; fwd and bwd are tiled
VMEM-resident loops with fp32 online-softmax accumulators). On the CPU
test mesh it takes the numerically-equivalent dense form. The choice
reads the platform JAX reports; a backend that fails to initialise
raises here like anywhere else — it is never read as "no TPU".

Layout: [batch, heads, seq, head_dim] (the kernel's native layout).
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax


def _dense_attention(q, k, v, causal, sm_scale, segment_ids):
    """Reference semantics (the flash kernel's mha_reference): fp32
    softmax, masked positions excluded, fully-masked rows → 0."""
    b, h, sq, d = q.shape
    sk = k.shape[2]
    scores = lax.dot_general(
        q, k, (((3,), (3,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32) * sm_scale
    mask = None
    if causal:
        mask = jnp.arange(sk)[None, :] > jnp.arange(sq)[:, None]
        mask = jnp.broadcast_to(mask, scores.shape)
    if segment_ids is not None:
        seg_q, seg_kv = segment_ids
        diff = seg_q[:, None, :, None] != seg_kv[:, None, None, :]
        diff = jnp.broadcast_to(diff, scores.shape)
        mask = diff if mask is None else (mask | diff)
    if mask is not None:
        scores = jnp.where(mask, jnp.finfo(jnp.float32).min, scores)
    m = jnp.max(scores, axis=-1, keepdims=True)
    e = jnp.exp(scores - m)
    if mask is not None:
        e = jnp.where(mask, 0.0, e)
    s = jnp.sum(e, axis=-1, keepdims=True)
    probs = jnp.where(s > 0, e / jnp.where(s > 0, s, 1.0), 0.0)
    return lax.dot_general(
        probs.astype(v.dtype), v, (((3,), (2,)), ((0, 1), (0, 1))),
        preferred_element_type=jnp.float32).astype(q.dtype)


@functools.lru_cache(maxsize=1)
def _platform():
    return jax.devices()[0].platform


def _tpu_available():
    return _platform() == "tpu"


def _on_cpu():
    """The one condition under which a Pallas kernel may run in
    interpret mode without an explicit argument asking for it."""
    return _platform() == "cpu"


def _block(n, cap):
    """Largest power-of-two block ≤ cap dividing n (≥ MIN_BLOCK_SIZE)."""
    b = 1
    while b * 2 <= cap and n % (b * 2) == 0:
        b *= 2
    return b


def flash_supported(sq, sk):
    """Whether ``fused_attention`` will take the Pallas flash path for
    these sequence lengths on the current backend (else the XLA-fused
    dense path). Public so harnesses/labels stay truthful by
    construction."""
    return _tpu_available() and sq % 128 == 0 and sk % 128 == 0


# Which TPU kernel backs fused_attention when both can: "flash" (the
# bundled multi-pass kernel, tuned blocks) or "rows" (the self-authored
# VMEM-row kernel, ops/attention_pallas.py). The default is whichever won
# benchmarks/profile_attention.py's fwd+d(q,k,v) decision row on the
# round's hardware (PERF.md); set_default_impl flips it process-wide.
# When neither a per-call impl nor the setter pins the choice, the
# per-shape dispatch table (apex_tpu.dispatch, op "attention") is
# consulted at trace time; a table miss lands on _DEFAULT_IMPL.
_DEFAULT_IMPL = "flash"
_IMPL_PINNED = False  # True once set_default_impl was called


def set_default_impl(impl):
    """Select the TPU kernel behind ``fused_attention``: "flash" or
    "rows" (shapes the chosen kernel can't handle still fall through
    flash → dense). Pins the choice process-wide — the dispatch table
    is no longer consulted (precedence: per-call > this setter > table
    > built-in)."""
    global _DEFAULT_IMPL, _IMPL_PINNED
    if impl not in ("flash", "rows"):
        raise ValueError(f"unknown attention impl {impl!r}")
    _DEFAULT_IMPL = impl
    _IMPL_PINNED = True


def reset_default_impl():
    """Back to the unpinned built-in default (tests / knob teardown)."""
    global _DEFAULT_IMPL, _IMPL_PINNED
    _DEFAULT_IMPL = "flash"
    _IMPL_PINNED = False


def _effective_impl_params(impl, q, k):
    """``(impl, from_table, tile_params)`` for one call: per-call
    ``impl`` > ``set_default_impl`` > dispatch-table entry for this
    shape bucket > built-in. Table entries are preferences (measured on
    this backend, keyed by shape bucket); unsupported shapes still fall
    through rows → flash → dense downstream. ``from_table`` lets the
    rows branch run a CPU-measured table choice in interpret mode — the
    way it was measured. ``tile_params`` is the entry's tile payload
    (block_q/...), handed to the rows kernel as a PREFERENCE — illegal
    tiles for the real shape fall back to the kernel heuristic there."""
    if impl is not None:
        return impl, False, None
    if _IMPL_PINNED:
        return _DEFAULT_IMPL, False, None
    from apex_tpu import dispatch

    choice, params = dispatch.lookup_params(
        "attention", dtype=q.dtype, b=q.shape[0], h=q.shape[1],
        sq=q.shape[2], sk=k.shape[2], d=q.shape[3])
    if choice:
        return choice, True, params
    # a params-only entry (tile measured for the shipped default impl)
    # still feeds the kernel's tile preference
    return _DEFAULT_IMPL, False, params


def _effective_impl(impl, q, k):
    """``(impl, from_table)`` — the choice half of
    :func:`_effective_impl_params` (kept for its callers/tests)."""
    return _effective_impl_params(impl, q, k)[:2]


def fused_attention(q, k, v, *, causal=False, sm_scale=None,
                    segment_ids=None, force_dense=None, impl=None):
    """Flash attention.

    Args:
      q, k, v: [b, h, s, d].
      causal: apply the lower-triangular mask.
      sm_scale: softmax scale; default 1/sqrt(d).
      segment_ids: optional (seg_q [b, sq], seg_kv [b, sk]) int arrays —
        tokens attend only within equal ids (varlen/packed batches; the
        fmha cu_seqlens capability).
      force_dense: force the XLA-fused dense path (tests / tiny shapes).
      impl: override the kernel choice for this call ("flash" | "rows");
        default is the measured process-wide default (set_default_impl).

    The Pallas paths require seq divisible by 128; other shapes (and
    non-TPU backends) use the XLA dense path.
    """
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if impl is not None and impl not in ("flash", "rows"):
        raise ValueError(f"unknown attention impl {impl!r}")
    sq, sk = q.shape[2], k.shape[2]
    # force_dense never consults the table: a consult the caller ignores
    # would still land in the dispatch.snapshot() consult log and
    # mislabel what a dense-baseline row actually ran
    eff_impl, from_table, tile_params = (
        ("flash", False, None) if force_dense
        else _effective_impl_params(impl, q, k))
    if eff_impl == "rows" and not force_dense:
        from apex_tpu.dispatch import tiles
        from apex_tpu.ops import attention_pallas as ap

        # the *default* dispatch caps the rows kernel at the fmha-style
        # moderate-seq envelope (beyond ~2k keys the multi-pass flash
        # kernel's causal skip + bounded unroll win back what the
        # single-pass structure saves); an explicit per-call impl="rows"
        # is honored for every supported shape so A/B rows stay truthful
        seq_ok = impl == "rows" or sk <= 2048
        # on the CPU the kernel can still run in interpret mode when
        # the choice came from a (backend-keyed, CPU-measured) table
        # entry or the pinned-A/B CPU leg asks for it (autotune
        # --smoke) — never silently: a "rows" label over a dense run is
        # label drift
        interp = (_on_cpu()
                  and (from_table
                       or tiles.env_flag("APEX_PALLAS_INTERPRET")))
        if ((_tpu_available() or interp) and seq_ok
                and ap.supported(sq, sk, q.shape[-1])):
            # table tile params ride as a PREFERENCE tuple (hashable —
            # custom_vjp nondiff arg); the kernel validates per shape
            # and falls back to its heuristic on an illegal tile
            pref = tuple(sorted(tile_params.items())) if tile_params \
                else None
            return ap.fused_attention_rows(q, k, v, causal,
                                           float(sm_scale), segment_ids,
                                           interp, tile_pref=pref)
    use_flash = flash_supported(sq, sk) and not force_dense
    if not use_flash:
        return _dense_attention(q, k, v, causal, sm_scale, segment_ids)

    from jax.experimental.pallas.ops.tpu import flash_attention as fa

    # Tuned on v5e (benchmarks/profile_attention.py, PERF.md): large q
    # blocks (fewer grid steps per head) with 512-wide k blocks beat the
    # kernel defaults ~3x at GPT shapes; block_b>1 doesn't help and big
    # values fail to compile.
    bq = _block(sq, 1024)
    blk = _block(min(sq, sk), 512)
    bs = fa.BlockSizes(
        block_q=bq, block_k_major=blk, block_k=blk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=blk,
        block_k_dkv=blk, block_q_dkv=bq,
        block_k_major_dq=blk, block_k_dq=blk, block_q_dq=bq)
    seg = None
    if segment_ids is not None:
        seg = fa.SegmentIds(q=segment_ids[0].astype(jnp.int32),
                            kv=segment_ids[1].astype(jnp.int32))
    return fa.flash_attention(q, k, v, segment_ids=seg, causal=causal,
                              sm_scale=float(sm_scale), block_sizes=bs)


# --------------------------------- packed grouped-query prefill attention

def masked_softmax(s, masked, sink=None):
    """Exact float32 softmax over the last axis of ``s`` where ``masked``
    (broadcastable to ``s``) is False. ``sink`` (broadcastable, with a
    last axis of 1) is one more logit of every row's denominator and of
    nothing else. A fully masked row comes out 0. The one softmax of the
    ``jnp`` attention forms: packed prefill here, both decode references
    in ``decode_attention_pallas``."""
    s = jnp.where(masked, -1e30, s)
    m = jnp.max(s, axis=-1, keepdims=True)
    extra = 0.0
    if sink is not None:
        m = jnp.maximum(m, sink)
        extra = jnp.exp(sink - m)
    e = jnp.where(masked, 0.0, jnp.exp(s - m))
    tot = jnp.sum(e, axis=-1, keepdims=True) + extra
    return e / jnp.where(tot > 0, tot, 1.0)


def _packed_gqa_dense(q, k, v, seg, sm_scale, window, sink, selected=None):
    """The jnp form of the packed grouped-query prefill: float32
    softmax over causal, same-segment (and, with ``window``, the last
    ``window`` positions; with ``selected``, the chosen) keys, the sink
    logit in the denominator."""
    hq, S, dk = q.shape
    n_kv = k.shape[0]
    qg = q.reshape(n_kv, hq // n_kv, S, dk)
    s = jnp.einsum("kgqd,ksd->kgqs", qg, k,
                   preferred_element_type=jnp.float32) * sm_scale
    row, col = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    masked = (col > row) | (seg[:, None] != seg[None, :])
    if window is not None:
        masked = masked | (row - col >= window)
    if selected is not None:
        masked = masked | (selected == 0)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(n_kv, hq // n_kv, 1, 1)
    p = masked_softmax(s, masked, sink).astype(v.dtype)
    out = jnp.einsum("kgqs,ksv->kgqv", p, v,
                     preferred_element_type=jnp.float32)
    return out.reshape(hq, S, v.shape[2]).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 7, 8))
def _packed_gqa(q, k, v, seg, sm_scale, window, sink, impl, interpret):
    if impl == "pallas":
        from apex_tpu.ops import attention_pallas as ap

        return ap.packed_gqa_attention_pallas(
            q, k, v, seg, sm_scale, window=window, sink=sink,
            interpret=interpret)
    return _packed_gqa_dense(q, k, v, seg, sm_scale, window, sink)


def _packed_gqa_fwd(q, k, v, seg, sm_scale, window, sink, impl, interpret):
    return _packed_gqa(q, k, v, seg, sm_scale, window, sink, impl,
                       interpret), None


def _packed_gqa_bwd(sm_scale, window, impl, interpret, res, g):
    raise NotImplementedError(
        "packed_gqa_attention is the serving prefill's forward; its "
        "backward (the training path of this block) is not built")


_packed_gqa.defvjp(_packed_gqa_fwd, _packed_gqa_bwd)


def _packed_impl(S, dk, dv):
    """What an unset ``impl`` of the packed attention is: the kernel on a
    TPU where it takes the shape, the jnp form elsewhere."""
    from apex_tpu.ops import attention_pallas as ap

    return "pallas" if _tpu_available() and ap.packed_supported(S, dk, dv) \
        else "jnp"


def packed_gqa_attention(q, k, v, segment_ids, *, sm_scale=None,
                         window=None, sink=None, impl=None,
                         interpret=None):
    """Causal attention over ONE packed sequence with grouped query
    heads, K and V of different widths, an optional window and an
    optional per-head sink logit; forward only (differentiating raises).

    q: [hq, S, dk]; k: [n_kv, S, dk]; v: [n_kv, S, dv]; segment_ids:
    [S] (tokens attend within equal ids; packed order is position
    order). Query head i reads KV head ``i // (hq / n_kv)``. ``window``:
    a token sees the last ``window`` positions, itself included.
    ``sink``: [hq] logits that join the softmax denominator only.
    ``impl`` is a per-call demand ("jnp" | "pallas"; the kernel compiled
    on a shape it does not support raises); unset, the kernel runs on a
    TPU where ``attention_pallas.packed_supported`` holds, the jnp form
    elsewhere. ``interpret`` defaults to True on the CPU platform only."""
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if impl is not None and impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown packed-attention impl {impl!r}")
    if impl is None:
        impl = _packed_impl(q.shape[1], q.shape[2], v.shape[2])
    if interpret is None:
        interpret = _on_cpu()
    return _packed_gqa(q, k, v, segment_ids.astype(jnp.int32),
                       float(sm_scale), window, sink, impl, bool(interpret))


def packed_attention_grid(S, hq, n_kv, dk, dv, *, window=None,
                          selected=False):
    """``(steps, dense steps)`` of one packed-attention call on these
    shapes with ``impl`` unset: the grid steps the kernel takes (a group
    of heads on a live block pair each) and what a grid of one step a
    (head, q block, k block) would; ``(0, 0)`` where the jnp form runs."""
    from apex_tpu.ops import attention_pallas as ap

    if _packed_impl(S, dk, dv) != "pallas":
        return 0, 0
    return ap.packed_grid_steps(S, hq, n_kv, dk, dv, window, selected)


# ------------------------- attention over SELECTED keys (packed prefill)
#
# A layer with a learned sparse selection (serving/dots3.py) scores every
# (query, key) pair with a small indexer, keeps each query's ``k`` best
# keys, and attends over those alone. Three steps, each with a jnp form:
# the scores ``[S, S]`` (a kernel that never holds the per-head scores),
# the selection (the k-th largest score of every row, found exactly by
# bisection on the scores' bit patterns: 32 passes of compare-and-count,
# no sort; ties at it broken as a sort breaks them), and the packed kernel
# under the selection's mask.

def _index_scores_dense(q_idx, w, k_idx, seg):
    """The jnp form of ``attention_pallas.packed_index_scores_pallas``,
    a head at a time (``[S, S]`` float32 is all that is ever held)."""
    S = q_idx.shape[1]

    def head(acc, qw):
        q, wh = qw
        s = jnp.einsum("qd,kd->qk", q, k_idx,
                       preferred_element_type=jnp.float32)
        return acc + jnp.maximum(s, 0.0) * wh[:, None], None

    acc, _ = jax.lax.scan(head, jnp.zeros((S, S), jnp.float32),
                          (q_idx, w.astype(jnp.float32).T))
    row, col = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    masked = (col > row) | (seg[:, None] != seg[None, :])
    return jnp.where(masked, jnp.float32(-1e30), acc)


def packed_index_scores(q_idx, w, k_idx, segment_ids, *, impl=None,
                        interpret=None):
    """The indexer's scores over ONE packed sequence: ``q_idx [hi, S,
    di]``, ``w [S, hi]`` (float32 head weights, the scale folded in),
    ``k_idx [S, di]`` -> ``[S, S]`` float32 ``sum_h w[t, h] relu(q_idx[h,
    t] . k_idx[j])``, -1e30 where ``j > t`` or the segments differ.
    ``impl`` / ``interpret`` as :func:`packed_gqa_attention`."""
    from apex_tpu.ops import attention_pallas as ap

    if impl is not None and impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown packed-attention impl {impl!r}")
    hi, S, di = q_idx.shape
    if impl is None:
        impl = "pallas" if _tpu_available() \
            and ap.index_scores_supported(S, hi, di) else "jnp"
    seg = segment_ids.astype(jnp.int32)
    if impl == "pallas":
        if interpret is None:
            interpret = _on_cpu()
        return ap.packed_index_scores_pallas(q_idx, w, k_idx, seg,
                                             interpret=bool(interpret))
    return _index_scores_dense(q_idx, w, k_idx, seg)


def _ordered_bits(x):
    """float32 -> uint32 whose unsigned order is the floats' order."""
    bits = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def kth_largest_bits(bits, k):
    """The ``k``-th largest of every row of ``bits [..., n]`` uint32,
    exactly: its bits are settled from the top, each by one count of the
    row's entries at or over the candidate."""
    def settle(i, found):
        cand = found | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        count = jnp.sum((bits >= cand[..., None]).astype(jnp.int32), axis=-1)
        return jnp.where(count >= k, cand, found)

    return jax.lax.fori_loop(0, 32, settle,
                             jnp.zeros(bits.shape[:-1], jnp.uint32))


def select_keys(scores, k):
    """``[S, S]`` int8: 1 where a key is among its query's ``k`` largest
    ``scores`` (:func:`packed_index_scores`; every unmasked key of a
    query with ``k`` or fewer). Exact, and ``lax.top_k``'s choice: of the
    keys that TIE with the k-th largest the first by index are kept (a
    count along the row, taken only where some row holds such a tie;
    ``-0.0`` ties with ``0.0``: a query whose every index head scores a
    key under zero gives it exactly that)."""
    scores = jnp.where(scores == 0, 0.0, scores)
    bits = _ordered_bits(scores)
    kth = kth_largest_bits(bits, k)[:, None]
    live = scores > -1e29

    def by_threshold():
        return bits >= kth

    def first_of_the_tied():
        above, tied = bits > kth, bits == kth
        room = k - jnp.sum(above.astype(jnp.int32), axis=-1, keepdims=True)
        return above | (tied & (jnp.cumsum(tied.astype(jnp.int32), axis=-1)
                                <= room))

    over = jnp.sum((live & (bits >= kth)).astype(jnp.int32), axis=-1) > k
    return (lax.cond(jnp.any(over), first_of_the_tied, by_threshold)
            & live).astype(jnp.int8)


def selected_attention(q, k, v, segment_ids, selected, *, sm_scale=None,
                       impl=None, interpret=None):
    """:func:`packed_gqa_attention` (no window, no sink) restricted to
    the keys ``selected [S, S]`` (:func:`select_keys`) marks nonzero;
    forward only."""
    from apex_tpu.ops import attention_pallas as ap

    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if impl is not None and impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown packed-attention impl {impl!r}")
    if impl is None:
        impl = _packed_impl(q.shape[1], q.shape[2], v.shape[2])
    seg = segment_ids.astype(jnp.int32)
    if impl == "pallas":
        if interpret is None:
            interpret = _on_cpu()
        return ap.packed_gqa_attention_pallas(
            q, k, v, seg, float(sm_scale), selected=selected,
            interpret=bool(interpret))
    return _packed_gqa_dense(q, k, v, seg, float(sm_scale), None, None,
                             selected)
