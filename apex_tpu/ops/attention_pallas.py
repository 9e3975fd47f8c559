"""Pallas TPU fused attention, VMEM-resident rows (fwd + bwd).

Self-authored alternative to the bundled multi-pass flash kernel for the
sequence lengths the reference's fused attention actually targets
(contrib/csrc/fmha supports seq <= 512; fast_multihead_attn seq ~64-1024):
at those lengths a whole [block_q, sk] score row fits in VMEM, so each
(batch, head, q-block) grid step computes scores, the exact fp32 softmax
over the FULL key row, and the output matmul in one kernel — no online
max/sum rescaling passes, no [s, s] tensor in HBM.

Backward comes in two structures behind the measured ``BWD_IMPL`` knob
(monolithic is the device-measured training-protocol winner and the
default — see the knob's comment and PERF.md §10):

* ``"split"``: a q-major dq pass that recomputes S and P from
  (q, k, v), forms dP = dO V^T, uses D = rowsum(dO * O) = rowsum(P * dP)
  to avoid needing O, writes dQ = dS K — and emits the per-row softmax
  stats (m, l, D) as [b, h, sq, 1] fp32 byproducts (the trailing 1 keeps
  the block's last dim equal to the array dim, satisfying Mosaic's
  last-two-dims tiling rule); then a k-major dk/dv
  pass where each (b, h, k-block) grid step reconstructs P row-exactly
  from those stats and owns its [bk, d] dk/dv outputs outright (no
  accumulation across grid steps). Eligibility is VMEM-gated
  (``_split_ok``): the k-major pass keeps the full [sq, d] q and dO
  resident, so very long sq falls back to monolithic.
* ``"monolithic"``: one self-contained q-major kernel (no saved stats)
  that additionally accumulates dK += dS^T Q, dV += P^T dO across
  q-blocks — safe because the TPU grid executes sequentially and the
  dk/dv blocks stay VMEM-resident while the innermost (q) index varies.

dk/dv accumulate in fp32 regardless of the input dtype in both.

Masking matches ops.attention._dense_attention exactly: causal triangle
(generated from iota, no mask operand), optional segment ids (packed
varlen batches), masked positions excluded from the softmax, fully-masked
rows → 0.

Trade-off vs flash: with causal masking the kernel still computes the
full [block_q, sk] score block (the masked half is wasted MXU work), so
it targets moderate sequence lengths where the single-pass structure wins
more than the causal skip would save. benchmarks/profile_attention.py
measures the crossover; ops.attention routes to this kernel via its
``impl="rows"`` knob / ``set_default_impl`` (the measured winner is the
default there).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.dispatch import tiles

# budget/working-set constants live in the shared tile model
# (apex_tpu/dispatch/tiles.py) — the sweeper, the label checker and
# this lowering judge tiles with the same arithmetic
_VMEM_BUDGET = tiles.ATTN_VMEM_BUDGET
_BWD_ARRAYS = tiles.ATTN_BWD_ARRAYS
# dropout keeps two extra [bq, sk] fp32 arrays live in the backward (the
# keep-scale tile and the dropped probs), so its q block is sized for a
# 6-array working set
_DROP_BWD_ARRAYS = tiles.ATTN_DROP_BWD_ARRAYS


def _q_block(sq, sk, n_arrays=_BWD_ARRAYS):
    """Largest power-of-two q block dividing sq whose bwd working set
    ([bq, sk] fp32 x n_arrays) fits the budget (0 → unsupported)."""
    return tiles.attn_q_block(sq, sk, n_arrays, budget=_VMEM_BUDGET)


# Process-wide q-block preference (tri-state; falls back per shape —
# only per-call tile knobs raise on an illegal tile)
_BLOCK_Q = None


def set_block_q(value):
    """Pin the process-wide q-block preference (int), or un-pin with
    None (table params / the heuristic apply again). Shapes the pinned
    tile can't block fall back to the heuristic silently."""
    global _BLOCK_Q
    tiles.check_setter_value(value, "block_q")
    _BLOCK_Q = value


def _env_block_q():
    return tiles.env_int("APEX_ATTN_BLOCK_Q")


def _pref_get(tile_pref, name):
    """Read one key out of a ``tile_pref`` tuple (the hashable
    ``((name, value), ...)`` form table params travel in — custom_vjp
    nondiff args must hash)."""
    if not tile_pref:
        return None
    return dict(tile_pref).get(name)


def supported(sq, sk, d, dropout=False):
    """Whether the VMEM-row kernel handles [.., sq, d] x [.., sk, d].
    sk must be lane-aligned; d bounded so the [sk, d] K/V operands and
    fp32 dk/dv accumulators stay small next to the score rows. Pass
    ``dropout=True`` when a dropout_p > 0 call is intended — the dropout
    backward's larger working set shrinks the viable q block and can
    push a shape that fits the plain kernel out of budget."""
    n_arrays = _DROP_BWD_ARRAYS if dropout else _BWD_ARRAYS
    return sk % 128 == 0 and d <= 256 and _q_block(sq, sk, n_arrays) != 0


def _masks(iq, bq, rows, sk, causal, seg_q, seg_kv, col0=0,
           seg_rows=None):
    """Boolean masked-out matrix for one [rows, sk] score block (True =
    excluded), or None when unmasked. seg_* are refs or None. ``col0``
    offsets the absolute column index (k-major blocks); ``seg_rows``
    overrides the row-id slice taken from seg_q (q chunks)."""
    masked = None
    if causal:
        row = iq * bq + lax.broadcasted_iota(jnp.int32, (rows, sk), 0)
        col = col0 + lax.broadcasted_iota(jnp.int32, (rows, sk), 1)
        masked = col > row
    if seg_q is not None:
        # seg_q is [1, bq|sq, 1] (sublane-major), seg_kv [1, 1, sk|bk]
        # (lane-major) — block sizes depend on the call site (q-major
        # passes tile seg_q; the k-major pass tiles seg_kv instead and
        # overrides rows via seg_rows); each layout matches the axis it
        # broadcasts along below
        sq_row = seg_q[0, :, 0] if seg_rows is None else seg_rows
        skv_row = seg_kv[0, 0, :]
        diff = sq_row[:, None] != skv_row[None, :]
        masked = diff if masked is None else masked | diff
    return masked


def _softmax_stats(s, masked):
    """Exact fp32 softmax over the full key row with dense-reference
    semantics (masked excluded, fully-masked rows -> 0). Returns
    (p, rowmax m, rowsum l) — m/l let a k-major pass reconstruct p
    row-exactly without the full row."""
    if masked is not None:
        s = jnp.where(masked, jnp.finfo(jnp.float32).min, s)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    if masked is not None:
        e = jnp.where(masked, 0.0, e)
    tot = jnp.sum(e, axis=-1, keepdims=True)
    p = jnp.where(tot > 0, e / jnp.where(tot > 0, tot, 1.0), 0.0)
    return p, m, tot


def _softmax(s, masked):
    return _softmax_stats(s, masked)[0]


def _p_from_stats(s, m, tot, masked):
    """Row-exact P reconstruction from saved (rowmax m, rowsum tot)
    [rows, 1] stats — same exclusion and zero-row semantics as
    ``_softmax_stats`` (whose outputs m/tot must come from the same
    mask)."""
    # Fully-masked rows save m = finfo.min, so an unclamped s - m
    # overflows to +inf in the k-major pass before the where() discards
    # it. s - m <= 0 holds for every live row (m is that row's max), so
    # clamping at 0 is exact — and keeps e finite for any future
    # arithmetic inserted before the mask (e.g. a fused scale).
    e = jnp.exp(jnp.minimum(s - m, 0.0))
    if masked is not None:
        e = jnp.where(masked, 0.0, e)
    return jnp.where(tot > 0, e / jnp.where(tot > 0, tot, 1.0), 0.0)


# ---------------------------------------------------------------------------
# attention dropout: counter-based PRNG, replayed exactly in backward
# ---------------------------------------------------------------------------
#
# The mask is a pure chained hash of the GLOBAL element coordinate
# (b, h, row, col) and the step seed — one murmur3 fmix32 avalanche per
# level, never a flat multiplied counter (which would wrap uint32 at
# large b·h·sq·sk). Tile-layout independent by construction: the
# backward pass (any block size, any q-major/k-major order) regenerates
# bit-identical keep decisions without storing the [sq, sk] mask in HBM
# — the same replay-from-offsets design as fmhalib's Philox states
# (reference apex/contrib/fmha/fmha.py:33-61 saves rng_state instead).
# Plain jnp uint32 ops so it lowers on Mosaic AND in interpret mode
# (pltpu.prng_* has no CPU interpret rule), and tests can rebuild the
# dense mask with the very same function.

def _fmix32(x):
    """murmur3 32-bit finalizer: full avalanche on distinct inputs."""
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _dropout_mscale(seed, ib, ih, row0, rows, sk, p, n_heads, col0=0):
    """fp32 [rows, sk] inverted-dropout scale (keep/(1-p), drop→0) for
    the score block whose global rows start at ``row0`` and columns at
    ``col0`` (ring-attention blocks pass a nonzero col0 so every rank
    regenerates the same global mask). ``seed`` is a traced
    uint32/int32 scalar; ``ib``/``ih`` the batch/head indices.

    The hash is CHAINED, not a flat element counter: seed → per-(b, h)
    key → per-row key → per-element bits, one fmix32 avalanche per
    level. A flat ``((b·H + h)·sq + row)·sk + col`` counter silently
    wraps uint32 once b·h·sq·sk > 2^32 (shapes the supported() gate
    admits), correlating far-apart elements; the chain never multiplies
    coordinates, so no level can overflow.

    Every index input is coerced to uint32 BEFORE any arithmetic: a
    traced int32 (``pl.program_id``) in the chain silently demotes the
    whole hash to int32, and the ``bits >= thresh`` compare then wraps
    thresh negative — an always-keep mask that drops nothing.
    """
    u32 = lambda x: jnp.asarray(x).astype(jnp.uint32)
    row = u32(row0) + lax.broadcasted_iota(jnp.uint32, (rows, 1), 0)
    col = u32(col0) + lax.broadcasted_iota(jnp.uint32, (rows, sk), 1)
    s = _fmix32(jnp.uint32(0x9E3779B9) ^ u32(seed))
    s_bh = _fmix32(s ^ (u32(ib) * jnp.uint32(n_heads) + u32(ih)))
    rowkey = _fmix32(s_bh ^ row)            # [rows, 1]
    bits = _fmix32(rowkey ^ col)            # [rows, sk]
    assert bits.dtype == jnp.uint32, bits.dtype
    thresh = jnp.uint32(min(max(p, 0.0), 1.0) * 4294967296.0)
    keep = bits >= thresh
    return jnp.where(keep, jnp.float32(1.0 / (1.0 - p)), jnp.float32(0.0))


def _fwd_kernel(*refs, scale, causal, has_seg, bq, dropout_p=0.0,
                n_heads=1):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    sq_ref = skv_ref = seed_ref = None
    if has_seg:
        sq_ref, skv_ref = refs[i:i + 2]
        i += 2
    if dropout_p > 0.0:
        seed_ref = refs[i]
        i += 1
    o_ref = refs[i]
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s * jnp.float32(scale)
    masked = _masks(pl.program_id(2), bq, q.shape[0], k.shape[0],
                    causal, sq_ref, skv_ref)
    p = _softmax(s, masked)
    if dropout_p > 0.0:
        p = p * _dropout_mscale(
            seed_ref[0, 0], pl.program_id(0), pl.program_id(1),
            pl.program_id(2) * bq, q.shape[0], k.shape[0], dropout_p,
            n_heads)
    o = lax.dot_general(p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)
    o_ref[0, 0] = o.astype(o_ref.dtype)


def _fwd_kernel_chunked(*refs, scale, causal, has_seg, bq):
    """Causal-skip fwd: keys are processed in bq-sized chunks and a chunk
    whose columns are all beyond this q-block's causal reach is never
    computed (the guarded branch genuinely skips — the TPU grid is
    sequential scalar control flow). Skipped chunks leave garbage in the
    score scratch; the softmax's causal `where` overwrites exactly those
    positions, so the garbage is never observed."""
    if has_seg:
        q_ref, k_ref, v_ref, sq_ref, skv_ref, o_ref, s_scr, o_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, s_scr, o_scr = refs
        sq_ref = skv_ref = None
    q = q_ref[0, 0]
    rows = q.shape[0]
    sk = k_ref.shape[2]
    nk = sk // bq
    iq = pl.program_id(2)
    reach = iq * bq + rows - 1  # last (absolute) row of this q block

    for c in range(nk):
        @pl.when(c * bq <= reach)
        def _(c=c):
            kc = k_ref[0, 0, c * bq:(c + 1) * bq, :]
            s_scr[:, c * bq:(c + 1) * bq] = lax.dot_general(
                q, kc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * jnp.float32(scale)

    masked = _masks(iq, bq, rows, sk, causal, sq_ref, skv_ref)
    p = _softmax(s_scr[...], masked).astype(v_ref.dtype)

    o_scr[...] = jnp.zeros_like(o_scr)
    for c in range(nk):
        @pl.when(c * bq <= reach)
        def _(c=c):
            vc = v_ref[0, 0, c * bq:(c + 1) * bq, :]
            o_scr[...] += lax.dot_general(
                p[:, c * bq:(c + 1) * bq], vc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    o_ref[0, 0] = o_scr[...].astype(o_ref.dtype)


def _bwd_kernel(*refs, scale, causal, has_seg, bq, dropout_p=0.0,
                n_heads=1):
    refs = list(refs)
    q_ref, k_ref, v_ref = refs[:3]
    i = 3
    sq_ref = skv_ref = seed_ref = None
    if has_seg:
        sq_ref, skv_ref = refs[i:i + 2]
        i += 2
    if dropout_p > 0.0:
        seed_ref = refs[i]
        i += 1
    do_ref, dq_ref, dk_ref, dv_ref = refs[i:i + 4]
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]

    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s * jnp.float32(scale)
    masked = _masks(pl.program_id(2), bq, q.shape[0], k.shape[0],
                    causal, sq_ref, skv_ref)
    p = _softmax(s, masked)

    # dP in fp32; D = rowsum(P * dP) == rowsum(dO * O) so O is not needed
    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    if dropout_p > 0.0:
        # replay the fwd keep mask from the counter hash: out = (P∘m)V,
        # so dV uses the dropped probs, dL/dP = m∘(dO V^T), and the
        # softmax-bwd row term rowsum(P ∘ dL/dP) == rowsum(Pd ∘ dP_raw)
        mscale = _dropout_mscale(
            seed_ref[0, 0], pl.program_id(0), pl.program_id(1),
            pl.program_id(2) * bq, q.shape[0], k.shape[0], dropout_p,
            n_heads)
        pd = p * mscale
        p_lo = pd.astype(q.dtype)          # feeds dV
        dcol = jnp.sum(pd * dp, axis=-1, keepdims=True)
        dp = dp * mscale
    else:
        p_lo = p.astype(q.dtype)
        dcol = jnp.sum(p * dp, axis=-1, keepdims=True)
    ds = (p * (dp - dcol) * jnp.float32(scale)).astype(q.dtype)

    dq = lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)

    # dk/dv accumulate across the (innermost, sequential) q grid axis;
    # their block index is constant in iq so the block stays resident
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
        dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])

    dk_ref[0, 0] += lax.dot_general(
        ds, q, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dv_ref[0, 0] += lax.dot_general(
        p_lo, do, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_kernel_chunked(*refs, scale, causal, has_seg, bq):
    """Causal-skip bwd (see _fwd_kernel_chunked). The score scratch is
    reused for dP once P is materialized; skipped chunks hold garbage in
    dP, so P*dP is masked to 0 there before the D reduction (P alone is
    exactly 0 at masked positions, but 0 * garbage could be NaN)."""
    if has_seg:
        (q_ref, k_ref, v_ref, sq_ref, skv_ref, do_ref,
         dq_ref, dk_ref, dv_ref, s_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref,
         dq_ref, dk_ref, dv_ref, s_scr, acc_scr) = refs
        sq_ref = skv_ref = None
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    rows = q.shape[0]
    sk = k_ref.shape[2]
    nk = sk // bq
    iq = pl.program_id(2)
    reach = iq * bq + rows - 1

    for c in range(nk):
        @pl.when(c * bq <= reach)
        def _(c=c):
            kc = k_ref[0, 0, c * bq:(c + 1) * bq, :]
            s_scr[:, c * bq:(c + 1) * bq] = lax.dot_general(
                q, kc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * jnp.float32(scale)

    masked = _masks(iq, bq, rows, sk, causal, sq_ref, skv_ref)
    p = _softmax(s_scr[...], masked)
    p_lo = p.astype(q.dtype)

    for c in range(nk):
        @pl.when(c * bq <= reach)
        def _(c=c):
            vc = v_ref[0, 0, c * bq:(c + 1) * bq, :]
            s_scr[:, c * bq:(c + 1) * bq] = lax.dot_general(
                do, vc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
    dp = s_scr[...]
    pdp = jnp.where(masked, 0.0, p * dp) if masked is not None else p * dp
    dcol = jnp.sum(pdp, axis=-1, keepdims=True)
    ds = (pdp - p * dcol) * jnp.float32(scale)

    @pl.when(iq == 0)
    def _init():
        dk_ref[0, 0] = jnp.zeros_like(dk_ref[0, 0])
        dv_ref[0, 0] = jnp.zeros_like(dv_ref[0, 0])

    acc_scr[...] = jnp.zeros_like(acc_scr)
    for c in range(nk):
        @pl.when(c * bq <= reach)
        def _(c=c):
            sl = slice(c * bq, (c + 1) * bq)
            dsc = ds[:, sl].astype(q.dtype)
            kc = k_ref[0, 0, sl, :]
            acc_scr[...] += lax.dot_general(
                dsc, kc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dk_ref[0, 0, sl, :] += lax.dot_general(
                dsc, q, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dv_ref[0, 0, sl, :] += lax.dot_general(
                p_lo[:, sl], do, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)


def _bwd_dq_kernel(*refs, scale, causal, has_seg, bq):
    """Split backward, pass 1 (q-major): dq plus the per-row softmax
    stats (rowmax m, rowsum l) and D = rowsum(P*dP) the k-major pass
    needs to reconstruct P and dS row-exactly."""
    if has_seg:
        (q_ref, k_ref, v_ref, sq_ref, skv_ref, do_ref,
         dq_ref, m_ref, l_ref, dcol_ref) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref,
         dq_ref, m_ref, l_ref, dcol_ref) = refs
        sq_ref = skv_ref = None
    q = q_ref[0, 0]
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    do = do_ref[0, 0]

    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)
    s = s * jnp.float32(scale)
    masked = _masks(pl.program_id(2), bq, q.shape[0], k.shape[0],
                    causal, sq_ref, skv_ref)
    p, m, tot = _softmax_stats(s, masked)

    dp = lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                         preferred_element_type=jnp.float32)
    dcol = jnp.sum(p * dp, axis=-1, keepdims=True)
    ds = (p * (dp - dcol) * jnp.float32(scale)).astype(q.dtype)

    dq = lax.dot_general(ds, k, (((1,), (0,)), ((), ())),
                         preferred_element_type=jnp.float32)
    dq_ref[0, 0] = dq.astype(dq_ref.dtype)
    # stats refs are [bq, 1] (the stats arrays carry a trailing 1 so the
    # block's last dim equals the array dim — Mosaic requires the last
    # two block dims be (8, 128)-divisible or full; a 3-D (1, 1, bq)
    # block has a bare 1 against the h axis and fails to lower)
    m_ref[0, 0] = m
    l_ref[0, 0] = tot
    dcol_ref[0, 0] = dcol


def _bwd_dq_kernel_chunked(*refs, scale, causal, has_seg, bq):
    """Causal-skip variant of the split dq pass (see _bwd_kernel_chunked
    for the skip/garbage rules) — without it the split default would pay
    the full-score causal tax the monolithic chunked kernel avoids."""
    if has_seg:
        (q_ref, k_ref, v_ref, sq_ref, skv_ref, do_ref,
         dq_ref, m_ref, l_ref, dcol_ref, s_scr, acc_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref,
         dq_ref, m_ref, l_ref, dcol_ref, s_scr, acc_scr) = refs
        sq_ref = skv_ref = None
    q = q_ref[0, 0]
    do = do_ref[0, 0]
    rows = q.shape[0]
    sk = k_ref.shape[2]
    nk = sk // bq
    iq = pl.program_id(2)
    reach = iq * bq + rows - 1

    for c in range(nk):
        @pl.when(c * bq <= reach)
        def _(c=c):
            kc = k_ref[0, 0, c * bq:(c + 1) * bq, :]
            s_scr[:, c * bq:(c + 1) * bq] = lax.dot_general(
                q, kc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * jnp.float32(scale)

    masked = _masks(iq, bq, rows, sk, causal, sq_ref, skv_ref)
    p, m, tot = _softmax_stats(s_scr[...], masked)

    for c in range(nk):
        @pl.when(c * bq <= reach)
        def _(c=c):
            vc = v_ref[0, 0, c * bq:(c + 1) * bq, :]
            s_scr[:, c * bq:(c + 1) * bq] = lax.dot_general(
                do, vc, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
    dp = s_scr[...]
    pdp = jnp.where(masked, 0.0, p * dp) if masked is not None else p * dp
    dcol = jnp.sum(pdp, axis=-1, keepdims=True)
    ds = (pdp - p * dcol) * jnp.float32(scale)

    acc_scr[...] = jnp.zeros_like(acc_scr)
    for c in range(nk):
        @pl.when(c * bq <= reach)
        def _(c=c):
            sl = slice(c * bq, (c + 1) * bq)
            kc = k_ref[0, 0, sl, :]
            acc_scr[...] += lax.dot_general(
                ds[:, sl].astype(q.dtype), kc, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
    dq_ref[0, 0] = acc_scr[...].astype(dq_ref.dtype)
    m_ref[0, 0] = m          # [bq, 1] refs — see _bwd_dq_kernel
    l_ref[0, 0] = tot
    dcol_ref[0, 0] = dcol


def _bwd_dkv_kernel(*refs, scale, causal, has_seg, bq, sq):
    """Split backward, pass 2 (k-major): each (b, h, k-block) grid step
    owns its [bk, d] dk/dv blocks outright — no accumulation across grid
    steps, no block revisiting. P and dS are reconstructed from the saved
    (m, l, D) row stats; q is processed in bq-sized chunks so causal
    blocks skip the strictly-below-diagonal chunks entirely."""
    if has_seg:
        (q_ref, k_ref, v_ref, sq_ref, skv_ref, do_ref, m_ref, l_ref,
         dcol_ref, dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, m_ref, l_ref, dcol_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        sq_ref = skv_ref = None
    k = k_ref[0, 0]
    v = v_ref[0, 0]
    bk = k.shape[0]
    ik = pl.program_id(2)
    nq = sq // bq

    dk_scr[...] = jnp.zeros_like(dk_scr)
    dv_scr[...] = jnp.zeros_like(dv_scr)

    for c in range(nq):
        def _chunk(c=c):
            qc = q_ref[0, 0, c * bq:(c + 1) * bq, :]
            doc = do_ref[0, 0, c * bq:(c + 1) * bq, :]
            m = m_ref[0, 0, c * bq:(c + 1) * bq, :]       # [bq, 1]
            tot = l_ref[0, 0, c * bq:(c + 1) * bq, :]
            dcol = dcol_ref[0, 0, c * bq:(c + 1) * bq, :]

            s = lax.dot_general(qc, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = s * jnp.float32(scale)

            seg_rows = (None if sq_ref is None
                        else sq_ref[0, c * bq:(c + 1) * bq, 0])
            masked = _masks(c, bq, bq, bk, causal, sq_ref, skv_ref,
                            col0=ik * bk, seg_rows=seg_rows)
            p = _p_from_stats(s, m, tot, masked)

            dp = lax.dot_general(doc, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
            ds = (p * (dp - dcol) * jnp.float32(scale)).astype(
                qc.dtype)
            p_lo = p.astype(qc.dtype)

            dk_scr[...] += lax.dot_general(
                ds, qc, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dv_scr[...] += lax.dot_general(
                p_lo, doc, (((0,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        if causal:
            # q rows < this k-block's first column contribute nothing —
            # skip the chunk (the grid is sequential scalar control flow)
            pl.when((c + 1) * bq - 1 >= ik * bk)(_chunk)
        else:
            _chunk()

    dk_ref[0, 0] = dk_scr[...]
    dv_ref[0, 0] = dv_scr[...]


def _specs(b, h, bq, sq, sk, d, has_seg):
    """(in_specs for q,k,v[,seg_q,seg_kv], qblk-spec, kvblk-spec)."""
    qspec = pl.BlockSpec((1, 1, bq, d), lambda ib, ih, iq: (ib, ih, iq, 0))
    kvspec = pl.BlockSpec((1, 1, sk, d), lambda ib, ih, iq: (ib, ih, 0, 0))
    ins = [qspec, kvspec, kvspec]
    if has_seg:
        # Mosaic's last-two-dims rule: each block dim must be (8, 128)-
        # divisible or span the full array dim. A 2-D (1, s) block over
        # [b, s] puts a bare 1 against the batch axis and fails it, so
        # seg_q travels SUBLANE-major as [b, sq, 1] — its (1, bq, 1)
        # block needs only 8-divisibility on bq, legal for every block
        # size _pick_bq can produce — while seg_kv stays LANE-major as
        # [b, 1, sk] with the always-full (and always-legal) (1, 1, sk)
        # block. Each layout matches the axis _masks broadcasts it along.
        ins.append(pl.BlockSpec((1, bq, 1), lambda ib, ih, iq: (ib, iq, 0)))
        ins.append(pl.BlockSpec((1, 1, sk), lambda ib, ih, iq: (ib, 0, 0)))
    return ins, qspec, kvspec


def _seg_ops(segment_ids):
    if segment_ids is None:
        return []
    seg_q, seg_kv = segment_ids
    # seg_q [b, s] -> [b, s, 1] (sublane-major), seg_kv -> [b, 1, s]
    # (lane-major): see the seg BlockSpec note in _specs
    return [seg_q.astype(jnp.int32)[:, :, None],
            seg_kv.astype(jnp.int32)[:, None, :]]


def _chunked(causal, bq, sq, sk):
    """Causal-skip applies when chunk boundaries are lane-aligned and
    there are >= 2 q blocks (a single block has nothing to skip)."""
    return causal and bq % 128 == 0 and sk % bq == 0 and sq >= 2 * bq


def _pick_bq(sq, sk, block_q, n_arrays=_BWD_ARRAYS, tile_pref=None,
             pref_keys=("block_q",)):
    """The effective q block: per-call ``block_q`` (raises on an
    illegal tile — the shared model's verdict) > ``set_block_q`` /
    ``APEX_ATTN_BLOCK_Q`` (fall back per shape) > ``tile_pref`` (table
    params, first legal of ``pref_keys``) > the heuristic."""
    if block_q is not None:
        problems = tiles.attn_q_problems("block_q", block_q, sq, sk,
                                         n_arrays, budget=_VMEM_BUDGET)
        if problems:
            raise ValueError("attention_pallas: " + "; ".join(problems))
        return block_q
    prefs = [_BLOCK_Q, _env_block_q()]
    prefs += [_pref_get(tile_pref, k) for k in pref_keys]
    for p in prefs:
        if p is not None and not tiles.attn_q_problems(
                "block_q", p, sq, sk, n_arrays, budget=_VMEM_BUDGET):
            return p
    return _q_block(sq, sk, n_arrays)


# Backward structure: "monolithic" = one q-major kernel accumulating
# dk/dv across the sequential grid; "split" = a q-major dq pass (emitting
# the (m, l, D) row stats) + a k-major dk/dv pass where each k-block is
# computed exactly once. Measured knob — the device A/B landed (PERF.md
# §10): monolithic wins the fwd+d(q,k,v) training protocol (1.509 vs
# 2.071 ms at the GPT-2 shape) and keeps the default; split wins the
# dq-only protocol 1.5x and remains the choice for no-kv-grad paths.
# Unpinned calls also consult the per-shape dispatch table
# (apex_tpu.dispatch, op "attention_bwd") below set_bwd_impl.
BWD_IMPL = "monolithic"
_BWD_PINNED = False  # True once set_bwd_impl was called


def set_bwd_impl(impl):
    """Set the process-wide backward-structure *preference*. Shapes that
    fail ``_split_ok`` fall back to monolithic silently (a model may mix
    eligible and ineligible layers); a per-call ``bwd_impl=`` is a strict
    demand and raises instead — benchmark rows use the per-call form so
    their labels stay truthful. Pins the choice above the dispatch
    table."""
    global BWD_IMPL, _BWD_PINNED
    if impl not in ("monolithic", "split"):
        raise ValueError(f"unknown rows bwd impl {impl!r}")
    BWD_IMPL = impl
    _BWD_PINNED = True


def reset_bwd_impl():
    """Back to the unpinned built-in default (tests / knob teardown)."""
    global BWD_IMPL, _BWD_PINNED
    BWD_IMPL = "monolithic"
    _BWD_PINNED = False


def _bwd_table_consult(q, k):
    """``(choice_or_None, tile_pref_tuple_or_None)`` from the
    dispatch-table "attention_bwd" entry for this bucket — the params
    half feeds the backward's tile resolution even when the impl itself
    is pinned (the impl pin and the tile axis are independent knobs)."""
    from apex_tpu import dispatch

    choice, params = dispatch.lookup_params(
        "attention_bwd", dtype=q.dtype, b=q.shape[0], h=q.shape[1],
        sq=q.shape[2], sk=k.shape[2], d=q.shape[3])
    pref = tuple(sorted(params.items())) if params else None
    return choice, pref


def _effective_bwd_impl(q, k):
    """Table-aware resolution for an unpinned backward: set_bwd_impl >
    dispatch-table "attention_bwd" entry for this bucket > built-in.
    Like the setter, a table "split" is a preference — ineligible shapes
    fall back to monolithic in _bwd_rule."""
    if _BWD_PINNED:
        return BWD_IMPL
    return _bwd_table_consult(q, k)[0] or BWD_IMPL


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(3, 4, 6, 7, 8, 9, 11, 12, 13))
def fused_attention_rows(q, k, v, causal, sm_scale, segment_ids=None,
                         interpret=False, block_q=None, bwd_impl=None,
                         dropout_p=0.0, dropout_seed=None,
                         bwd_block_q=None, block_k=None, tile_pref=None):
    """VMEM-row fused attention. q: [b, h, sq, d]; k, v: [b, h, sk, d];
    segment_ids: None or (seg_q [b, sq], seg_kv [b, sk]). Check
    ``supported(sq, sk, d)`` first. ``interpret=True`` for CPU tests.
    ``block_q`` overrides the auto q-block (benchmark sweeps);
    ``bwd_impl`` overrides the module-level ``BWD_IMPL``.

    ``dropout_p`` > 0 applies inverted attention-probability dropout
    INSIDE the kernel (counter-hash mask, replayed in backward — no
    [sq, sk] mask in HBM); requires a traced int32 ``dropout_seed``
    of shape (1, 1). Dropout forces the monolithic backward (an
    explicit ``bwd_impl="split"`` request raises).

    Tile knobs (all judged by ``apex_tpu.dispatch.tiles``; per-call
    values raise on an illegal tile): ``block_q`` sizes the fwd AND
    (absent ``bwd_block_q``) backward q blocks; ``bwd_block_q``
    overrides the backward only; ``block_k`` sizes the split backward's
    k-major dk/dv block (requires the split structure to stay
    eligible). ``tile_pref`` is the preference form — a hashable
    ``((name, value), ...)`` tuple the dispatch-table consumer passes;
    illegal entries fall back per shape, and ``set_block_q`` /
    ``APEX_ATTN_BLOCK_Q`` resolve above it."""
    if bwd_impl is not None and bwd_impl not in ("monolithic", "split"):
        raise ValueError(f"unknown rows bwd impl {bwd_impl!r}")
    if not 0.0 <= dropout_p < 1.0:
        raise ValueError(f"dropout_p={dropout_p} outside [0, 1)")
    if dropout_p > 0.0 and bwd_impl == "split":
        raise ValueError("dropout requires the monolithic backward")
    if block_k is not None and bwd_impl == "monolithic":
        raise ValueError("block_k tiles the split backward; it cannot "
                         "be honored with bwd_impl='monolithic'")
    return _fwd(q, k, v, causal, sm_scale, segment_ids, interpret,
                block_q, dropout_p, dropout_seed, tile_pref)[0]


def _drop_ops(dropout_p, dropout_seed):
    if dropout_p <= 0.0:
        return []
    if dropout_seed is None:
        raise ValueError("dropout_p > 0 requires dropout_seed")
    seed = jnp.asarray(dropout_seed).reshape(1, 1)
    return [seed.astype(jnp.int32)]


def _drop_spec(dropout_p):
    if dropout_p <= 0.0:
        return []
    return [pl.BlockSpec((1, 1), lambda ib, ih, iq: (0, 0))]


def _fwd(q, k, v, causal, sm_scale, segment_ids, interpret, block_q=None,
         dropout_p=0.0, dropout_seed=None, tile_pref=None):
    b, h, sq, d = q.shape
    sk = k.shape[2]
    if not supported(sq, sk, d, dropout=dropout_p > 0.0):
        raise ValueError(f"attention_pallas: unsupported {q.shape}x{k.shape}"
                         + (" with dropout" if dropout_p > 0.0 else ""))
    n_arrays = _DROP_BWD_ARRAYS if dropout_p > 0.0 else _BWD_ARRAYS
    bq = _pick_bq(sq, sk, block_q, n_arrays, tile_pref)
    has_seg = segment_ids is not None
    ins, qspec, _ = _specs(b, h, bq, sq, sk, d, has_seg)
    kern = functools.partial(_fwd_kernel, dropout_p=dropout_p, n_heads=h)
    scratch = []
    if dropout_p <= 0.0 and _chunked(causal, bq, sq, sk):
        kern = _fwd_kernel_chunked
        scratch = [pltpu.VMEM((bq, sk), jnp.float32),
                   pltpu.VMEM((bq, d), jnp.float32)]
    o = pl.pallas_call(
        functools.partial(kern, scale=float(sm_scale), causal=causal,
                          has_seg=has_seg, bq=bq),
        grid=(b, h, sq // bq),
        in_specs=ins + _drop_spec(dropout_p),
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v, *_seg_ops(segment_ids), *_drop_ops(dropout_p, dropout_seed))
    return o, (q, k, v, segment_ids, dropout_seed)


def _fwd_rule(q, k, v, causal, sm_scale, segment_ids, interpret,
              block_q=None, bwd_impl=None, dropout_p=0.0,
              dropout_seed=None, bwd_block_q=None, block_k=None,
              tile_pref=None):
    return _fwd(q, k, v, causal, sm_scale, segment_ids, interpret, block_q,
                dropout_p, dropout_seed, tile_pref)


def _pick_bwd_bq(sq, sk, block_q, bwd_block_q, n_arrays=_BWD_ARRAYS,
                 tile_pref=None):
    """Backward q block: per-call ``bwd_block_q`` (raise) > per-call
    ``block_q`` (raise — shared with fwd) > setter/env > table
    ``bwd_block_q`` then ``block_q`` prefs > heuristic."""
    if bwd_block_q is not None:
        problems = tiles.attn_q_problems("bwd_block_q", bwd_block_q, sq,
                                         sk, n_arrays,
                                         budget=_VMEM_BUDGET)
        if problems:
            raise ValueError("attention_pallas: " + "; ".join(problems))
        return bwd_block_q
    return _pick_bq(sq, sk, block_q, n_arrays, tile_pref,
                    pref_keys=("bwd_block_q", "block_q"))


def _bwd_monolithic(causal, sm_scale, interpret, block_q, res, g,
                    dropout_p=0.0, bwd_block_q=None, tile_pref=None):
    q, k, v, segment_ids, dropout_seed = res
    b, h, sq, d = q.shape
    sk = k.shape[2]
    n_arrays = _DROP_BWD_ARRAYS if dropout_p > 0.0 else _BWD_ARRAYS
    bq = _pick_bwd_bq(sq, sk, block_q, bwd_block_q, n_arrays, tile_pref)
    has_seg = segment_ids is not None
    ins, qspec, kvspec = _specs(b, h, bq, sq, sk, d, has_seg)
    kern = functools.partial(_bwd_kernel, dropout_p=dropout_p, n_heads=h)
    scratch = []
    if dropout_p <= 0.0 and _chunked(causal, bq, sq, sk):
        kern = _bwd_kernel_chunked
        scratch = [pltpu.VMEM((bq, sk), jnp.float32),
                   pltpu.VMEM((bq, d), jnp.float32)]
    dq, dk, dv = pl.pallas_call(
        functools.partial(kern, scale=float(sm_scale), causal=causal,
                          has_seg=has_seg, bq=bq),
        grid=(b, h, sq // bq),
        in_specs=ins + _drop_spec(dropout_p) + [qspec],
        out_specs=(qspec, kvspec, kvspec),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)),
        scratch_shapes=scratch,
        interpret=interpret,
    )(q, k, v, *_seg_ops(segment_ids),
      *_drop_ops(dropout_p, dropout_seed), g)
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None)


def _bwd_split(causal, sm_scale, interpret, block_q, res, g,
               bwd_block_q=None, block_k=None, tile_pref=None):
    q, k, v, segment_ids, _ = res  # no dropout on the split path
    b, h, sq, d = q.shape
    sk = k.shape[2]
    bq = _pick_bwd_bq(sq, sk, block_q, bwd_block_q,
                      tile_pref=tile_pref)
    has_seg = segment_ids is not None
    ins, qspec, kvspec = _specs(b, h, bq, sq, sk, d, has_seg)
    # stats carry a trailing 1 (block last dim == array dim) so the
    # (m, l, D) outputs satisfy Mosaic's last-two-dims rule on device
    vecspec = pl.BlockSpec((1, 1, bq, 1), lambda ib, ih, iq: (ib, ih, iq, 0))
    vecshape = jax.ShapeDtypeStruct((b, h, sq, 1), jnp.float32)

    dq_kern, dq_scratch = _bwd_dq_kernel, []
    if _chunked(causal, bq, sq, sk):
        dq_kern = _bwd_dq_kernel_chunked
        dq_scratch = [pltpu.VMEM((bq, sk), jnp.float32),
                      pltpu.VMEM((bq, d), jnp.float32)]
    dq, m, l, dcol = pl.pallas_call(
        functools.partial(dq_kern, scale=float(sm_scale),
                          causal=causal, has_seg=has_seg, bq=bq),
        grid=(b, h, sq // bq),
        in_specs=ins + [qspec],
        out_specs=(qspec, vecspec, vecspec, vecspec),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype),
                   vecshape, vecshape, vecshape),
        scratch_shapes=dq_scratch,
        interpret=interpret,
    )(q, k, v, *_seg_ops(segment_ids), g)

    # k blocks default to the VMEM-validated row block; block_k decouples
    # them (per-call raises via _bwd_rule's eligibility gate, a table
    # pref falls back there)
    bk = block_k if block_k is not None else bq
    fullq = pl.BlockSpec((1, 1, sq, d), lambda ib, ih, ik: (ib, ih, 0, 0))
    kvblk = pl.BlockSpec((1, 1, bk, d), lambda ib, ih, ik: (ib, ih, ik, 0))
    fullvec = pl.BlockSpec((1, 1, sq, 1), lambda ib, ih, ik: (ib, ih, 0, 0))
    dkv_ins = [fullq, kvblk, kvblk]
    if has_seg:
        # seg_q full-length sublane-major (q is chunked in-kernel);
        # seg_kv's (1, 1, bk) lane-dim block relies on _split_ok's
        # bq % 128 gate (bk = bq) for alignment
        dkv_ins.append(
            pl.BlockSpec((1, sq, 1), lambda ib, ih, ik: (ib, 0, 0)))
        dkv_ins.append(
            pl.BlockSpec((1, 1, bk), lambda ib, ih, ik: (ib, 0, ik)))
    dkv_ins += [fullq, fullvec, fullvec, fullvec]

    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=float(sm_scale),
                          causal=causal, has_seg=has_seg, bq=bq, sq=sq),
        grid=(b, h, sk // bk),
        in_specs=dkv_ins,
        out_specs=(kvblk, kvblk),
        out_shape=(jax.ShapeDtypeStruct(k.shape, jnp.float32),
                   jax.ShapeDtypeStruct(v.shape, jnp.float32)),
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, *_seg_ops(segment_ids), g, m, l, dcol)
    return (dq, dk.astype(k.dtype), dv.astype(v.dtype), None, None)


def _split_ok(sq, sk, d, bq, itemsize, bk=None):
    """VMEM eligibility of the split k-major pass: it keeps the full
    [sq, d] q and dO resident per grid step (the monolithic backward
    streams q instead), holds 3 [bq, bk] fp32 chunk arrays + 2 [bk, d]
    accumulators + 3 [sq] stat vectors, and unrolls sq/bq chunks.
    The model lives in the shared tile module (``tiles.split_ok``);
    bq % 128: the k-major pass tiles seg_kv into (.., bk) LANE-dim
    blocks (bk = bq by default) and every in-kernel
    [:, c*bq:(c+1)*bq] chunk slice in the q-major dq pass cuts the
    lane axis — both need 128-alignment under Mosaic."""
    return tiles.split_ok(sq, sk, d, bq, itemsize, bk,
                          budget=_VMEM_BUDGET)


def _bwd_rule(causal, sm_scale, interpret, block_q, bwd_impl, dropout_p,
              bwd_block_q, block_k, tile_pref, res, g):
    if bwd_impl is not None and bwd_impl not in ("monolithic", "split"):
        raise ValueError(f"unknown rows bwd impl {bwd_impl!r}")
    q, k, v, _, _ = res
    if dropout_p > 0.0:
        # the split structure has no dropout replay wired through its two
        # passes; the per-call demand raises (fused_attention_rows already
        # pre-checks this), the process-wide preference falls back.
        # BEFORE any table consult: dropout forces monolithic, and a
        # consult whose choice could never be honored would still land
        # in dispatch.snapshot()'s consult log — mislabeling what the
        # measured backward actually ran
        if bwd_impl == "split":
            raise ValueError("dropout requires the monolithic backward")
        if block_k is not None:
            raise ValueError("block_k tiles the split backward; it "
                             "cannot be honored with dropout")
        return _bwd_monolithic(causal, sm_scale, interpret, block_q, res,
                               g, dropout_p, bwd_block_q, tile_pref)
    if not _BWD_PINNED and bwd_impl is None:
        # the attention_bwd table entry's params feed the backward tile
        # resolution (below per-call knobs and setter/env), merged over
        # any call-level pref: bwd-specific keys win
        table_choice, table_pref = _bwd_table_consult(q, k)
        if table_pref:
            merged = dict(tile_pref or ())
            merged.update(dict(table_pref))
            tile_pref = tuple(sorted(merged.items()))
    else:
        table_choice = None
    impl = bwd_impl or (BWD_IMPL if _BWD_PINNED
                        else table_choice or BWD_IMPL)
    sq, sk = q.shape[2], k.shape[2]
    bq = _pick_bwd_bq(sq, sk, block_q, bwd_block_q, tile_pref=tile_pref)
    if block_k is not None:
        # an explicit k block is a demand on the split structure
        problems = []
        if not isinstance(block_k, int) or block_k % 128 or block_k < 128:
            problems.append(f"block_k={block_k!r} must be a multiple "
                            f"of 128")
        elif sk % block_k:
            problems.append(f"block_k={block_k} does not divide sk={sk}")
        elif not _split_ok(sq, sk, q.shape[3], bq, q.dtype.itemsize,
                           block_k):
            problems.append(
                f"block_k={block_k}: split bwd ineligible for "
                f"{q.shape}x{k.shape} (bq={bq})")
        if problems:
            raise ValueError("attention_pallas: " + "; ".join(problems))
        if bwd_impl is None and impl != "split":
            impl = "split"  # an explicit block_k selects the structure
    eff_bk = block_k if block_k is not None \
        else _pref_get(tile_pref, "block_k")
    if eff_bk is not None and block_k is None and not _split_ok(
            sq, sk, q.shape[3], bq, q.dtype.itemsize, eff_bk):
        eff_bk = None  # table pref falls back per shape
    ok = _split_ok(sq, sk, q.shape[3], bq, q.dtype.itemsize, eff_bk)
    if bwd_impl == "split" and not ok:
        # an explicit request must be honored or error — silently running
        # monolithic would mislabel A/B benchmark rows
        raise ValueError(
            f"split bwd ineligible for {q.shape}x{k.shape} (bq={bq})")
    if impl == "split" and ok:
        return _bwd_split(causal, sm_scale, interpret, block_q, res, g,
                          bwd_block_q, eff_bk, tile_pref)
    return _bwd_monolithic(causal, sm_scale, interpret, block_q, res, g,
                           0.0, bwd_block_q, tile_pref)


fused_attention_rows.defvjp(_fwd_rule, _bwd_rule)


# ------------------------------------- packed grouped-query prefill (fwd)
#
# The serving prefill of a model whose query heads share KV heads, whose
# K and V differ in width, and whose layers may see a window and carry a
# sink logit (serving/mimo.py): ONE packed sequence with segment ids,
# forward only. The kernels above (GPT-2's prefill and the trainer's
# flash forward and backward) are untouched.
#
#   q [hq, S, dk], k [n_kv, S, dk], v [n_kv, S, dv], seg [S] -> [hq, S, dv]
#
# Packed index order is position order inside a segment, so "causal" and
# "within the window" are index differences, and which (q block, k block)
# pairs hold any pair a query may see follows from ``(S, block, window)``
# alone: :func:`packed_live_pairs` lists them while tracing and the
# kernel takes the list as scalar-prefetch operands. Grid (group of
# query heads, LIVE pair), q block major and k blocks ascending, online
# softmax across a q block's pairs: a k block wholly above the diagonal
# or wholly behind the window is no grid step at all. A step builds its
# pair's mask ONCE (positions only where the pair crosses the diagonal
# or the window's edge; segments and the selection on every pair) and
# scores every head of its group under it; heads that share a KV head
# share the step's K and V block.

PACKED_KERNEL_NAME = "packed_gqa_attention"
PAIR_FIRST, PAIR_LAST, PAIR_CROSSES = 1, 2, 4
_HEADS_A_STEP = (8, 4, 2, 1)
# what a step's blocks and scratch may take of the 16 MiB of VMEM a
# kernel is scoped to unless it asks for more
_PACKED_VMEM_BUDGET = 12 << 20


def packed_block(S):
    """The q and k block of the packed kernel: 256 rows, or all of a
    shorter pack; 0 where S does not divide."""
    blk = min(256, S)
    return blk if S % blk == 0 and blk % 8 == 0 else 0


def packed_supported(S, dk, dv):
    """Whether Mosaic takes the packed kernel: blocks that divide S and
    are whole lane tiles (or all of S), bounded widths."""
    blk = packed_block(S)
    return blk != 0 and (blk % 128 == 0) and dk <= 512 and dv <= 512


@functools.lru_cache(maxsize=None)
def packed_live_pairs(S, blk, window):
    """``[3, n_live]`` int32 (numpy): the q block, the k block and the
    flags of every block pair that holds a (query, key) a causal mask
    and ``window`` leave, q block major, k blocks ascending. Flags:
    ``PAIR_FIRST`` / ``PAIR_LAST`` of its q block, ``PAIR_CROSSES`` where
    the positional mask can hide a pair of it (the diagonal block, a
    block the window's edge runs through)."""
    n = S // blk
    iq, ik = np.divmod(np.arange(n * n), n)
    live = ik <= iq                        # not wholly above the diagonal
    if window is not None:                 # nor wholly behind the window
        live &= (ik + 1) * blk - 1 > iq * blk - window
    iq, ik = iq[live], ik[live]
    crosses = ik == iq
    if window is not None:
        crosses |= (iq - ik) * blk + blk - 1 >= window
    edge = np.flatnonzero(np.diff(iq, prepend=-1, append=n))
    flags = PAIR_CROSSES * crosses
    flags[edge[:-1]] |= PAIR_FIRST
    flags[edge[1:] - 1] |= PAIR_LAST
    return np.stack([iq, ik, flags]).astype(np.int32)


def _packed_vmem_bytes(g, kv, blk, dk, dv, itemsize, has_sel):
    """What a step of ``g`` query heads over ``kv`` KV heads holds in
    VMEM: the double-buffered blocks, the scratch, the pair's mask and
    one head's float32 scores and weights."""
    lanes = lambda n: -(-n // 128) * 128                     # noqa: E731
    blocks = 2 * itemsize * blk * (g + kv) * (lanes(dk) + lanes(dv))
    scratch = 4 * blk * g * (lanes(dv) + 2 * 128)
    pair = blk * lanes(blk) * (4 + 3 * 4 + (2 if has_sel else 0))
    return blocks + scratch + pair


def packed_heads_a_step(hq, n_kv, blk, dk, dv, itemsize=2, has_sel=False):
    """How many query heads one grid step scores: the largest of 8 / 4 /
    2 / 1 that lies inside one KV head's queries (or, a KV head a query
    head, divides the heads) and whose blocks fit the kernel's VMEM."""
    group = hq // n_kv
    for g in _HEADS_A_STEP:
        if (group if group > 1 else hq) % g == 0 and _packed_vmem_bytes(
                g, 1 if group > 1 else g, blk, dk, dv, itemsize,
                has_sel) <= _PACKED_VMEM_BUDGET:
            return g
    return 1


def packed_grid_steps(S, hq, n_kv, dk, dv, window=None, has_sel=False):
    """``(grid steps of the kernel, grid steps of a (head, q block, k
    block) grid)`` for one call on these shapes."""
    blk = packed_block(S)
    n_live = packed_live_pairs(S, blk, window).shape[1]
    g = packed_heads_a_step(hq, n_kv, blk, dk, dv, has_sel=has_sel)
    return hq // g * n_live, hq * (S // blk) ** 2


def _packed_kernel(iq_ref, ik_ref, flag_ref, q_ref, k_ref, v_ref, sq_ref,
                   skv_ref, *rest, scale, blk, heads, shared_kv, window,
                   has_sink, has_sel=False):
    if has_sel:
        sel_ref, *rest = rest
    if has_sink:
        sink_ref, *rest = rest
    o_ref, acc_scr, m_scr, l_scr, mask_scr = rest
    pair = pl.program_id(1)
    iq, ik, flags = iq_ref[pair], ik_ref[pair], flag_ref[pair]

    @pl.when(flags & PAIR_FIRST != 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if has_sink:
            m_scr[...] = jnp.broadcast_to(sink_ref[...], m_scr.shape)
            l_scr[...] = jnp.ones_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, jnp.float32(-1e30))
            l_scr[...] = jnp.zeros_like(l_scr)

    # the pair's mask, once for every head of the step
    hidden = sq_ref[...] != skv_ref[...]
    if has_sel:
        hidden = hidden | (sel_ref[...].astype(jnp.int32) == 0)
    crosses = flags & PAIR_CROSSES != 0

    @pl.when(crosses)
    def _on_an_edge():
        row = iq * blk + lax.broadcasted_iota(jnp.int32, (blk, blk), 0)
        col = ik * blk + lax.broadcasted_iota(jnp.int32, (blk, blk), 1)
        behind = col > row
        if window is not None:
            behind = behind | (row - col >= window)
        mask_scr[...] = (hidden | behind).astype(jnp.int32)

    @pl.when(jnp.logical_not(crosses))
    def _inside():
        mask_scr[...] = hidden.astype(jnp.int32)

    for h in range(heads):
        kv = ... if shared_kv else h    # one K / V block for all, or its own
        masked = mask_scr[...] != 0
        s = lax.dot_general(q_ref[h], k_ref[kv],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.where(masked, jnp.float32(-1e30), s * jnp.float32(scale))
        m_prev = m_scr[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(masked, 0.0, jnp.exp(s - m_new))
        l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + lax.dot_general(
            p.astype(v_ref.dtype), v_ref[kv], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    @pl.when(flags & PAIR_LAST != 0)
    def _finish():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def packed_gqa_attention_pallas(q, k, v, seg, sm_scale, *, window=None,
                                sink=None, selected=None, interpret=False):
    """The packed grouped-query prefill kernel (layouts above).
    ``selected [S, S]`` int8 (a layer that selects its keys:
    :func:`packed_index_scores_pallas`, ``attention.select_keys``): a
    query attends only where it is nonzero, besides the other masks."""
    hq, S, dk = q.shape
    n_kv, dv = k.shape[0], v.shape[2]
    blk = packed_block(S)
    if blk == 0 or hq % n_kv or (not interpret
                                 and not packed_supported(S, dk, dv)):
        raise ValueError(f"packed_gqa_attention_pallas: unsupported "
                         f"q {q.shape} k {k.shape} v {v.shape}")
    heads = packed_heads_a_step(hq, n_kv, blk, dk, dv, q.dtype.itemsize,
                                selected is not None)
    return _packed_call(q, k, v, seg, sink, selected, heads=heads,
                        sm_scale=float(sm_scale), window=window,
                        interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("heads", "sm_scale", "window",
                                             "interpret"))
def _packed_call(q, k, v, seg, sink, selected, *, heads, sm_scale, window,
                 interpret):
    """``heads`` query heads a grid step. A ``jit`` of its own so that a
    program traces and lowers the kernel once for all its layers of one
    shape: a step's unrolled heads cost every trace of it ~0.5 s."""
    hq, S, dk = q.shape
    n_kv, dv = k.shape[0], v.shape[2]
    blk = packed_block(S)
    group = hq // n_kv
    has_sink, has_sel = sink is not None, selected is not None
    pairs = packed_live_pairs(S, blk, window)
    if group > 1:     # a step's heads lie inside one KV head's queries
        kv_heads, kv_of = None, lambda g: g * heads // group  # noqa: E731
    else:
        kv_heads, kv_of = heads, lambda g: g                  # noqa: E731

    in_specs = [
        pl.BlockSpec((heads, blk, dk),
                     lambda g, p, iq, ik, fl: (g, iq[p], 0)),
        pl.BlockSpec((kv_heads, blk, dk),
                     lambda g, p, iq, ik, fl: (kv_of(g), ik[p], 0)),
        pl.BlockSpec((kv_heads, blk, dv),
                     lambda g, p, iq, ik, fl: (kv_of(g), ik[p], 0)),
        pl.BlockSpec((blk, 1), lambda g, p, iq, ik, fl: (iq[p], 0)),
        pl.BlockSpec((1, blk), lambda g, p, iq, ik, fl: (0, ik[p])),
    ]
    seg = seg.astype(jnp.int32)
    operands = [q, k, v, seg[:, None], seg[None, :]]
    if has_sel:
        in_specs.append(pl.BlockSpec(
            (blk, blk), lambda g, p, iq, ik, fl: (iq[p], ik[p])))
        operands.append(selected.astype(jnp.int8))
    if has_sink:
        in_specs.append(pl.BlockSpec(
            (heads, 1, 1), lambda g, p, iq, ik, fl: (g, 0, 0)))
        operands.append(sink.astype(jnp.float32).reshape(hq, 1, 1))
    kern = functools.partial(_packed_kernel, scale=sm_scale, blk=blk,
                             heads=heads, shared_kv=group > 1, window=window,
                             has_sink=has_sink, has_sel=has_sel)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(hq // heads, pairs.shape[1]),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((heads, blk, dv),
                                   lambda g, p, iq, ik, fl: (g, iq[p], 0)),
            scratch_shapes=[pltpu.VMEM((heads, blk, dv), jnp.float32),
                            pltpu.VMEM((heads, blk, 1), jnp.float32),
                            pltpu.VMEM((heads, blk, 1), jnp.float32),
                            pltpu.VMEM((blk, blk), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct((hq, S, dv), q.dtype),
        interpret=interpret,
        name=PACKED_KERNEL_NAME,
    )(*(jnp.asarray(row) for row in pairs), *operands)


# --------------------------------------- the indexer's scores (prefill)
#
# A layer that SELECTS its keys (serving/dots3.py) scores every (query,
# key) pair of a packed batch with a small indexer before it attends:
#
#   q_idx [hi, S, di], w [S, hi] float32, k_idx [S, di], seg [S]
#     -> [S, S] float32:  sum_h w[t, h] relu(q_idx[h, t] . k_idx[j]),
#        -1e30 where j > t or the segments differ
#
# Grid (q block, k block); a step holds every head's queries of its q
# block and sums the heads' rectified, weighted scores of one k block in
# registers, so no [S, S, hi] array exists. A k block wholly above the
# diagonal is neither fetched nor computed.

INDEX_SCORES_KERNEL_NAME = "packed_index_scores"
_INDEX_BQ, _INDEX_BK = 128, 512


def index_blocks(S):
    """``(q block, k block)`` of the index kernel; ``(0, 0)`` where S
    does not divide into whole lane tiles."""
    bq, bk = min(_INDEX_BQ, S), min(_INDEX_BK, S)
    return (bq, bk) if S % bq == 0 and S % bk == 0 and bq % 128 == 0 \
        else (0, 0)


def index_scores_supported(S, hi, di):
    return index_blocks(S)[0] != 0 and di % 128 == 0 and hi <= 128


def _index_scores_kernel(q_ref, w_ref, k_ref, sq_ref, skv_ref, o_ref, *,
                         bq, bk, hi):
    iq, ik = pl.program_id(0), pl.program_id(1)
    live = ik * bk <= iq * bq + bq - 1

    @pl.when(jnp.logical_not(live))
    def _above():
        o_ref[...] = jnp.full_like(o_ref, jnp.float32(-1e30))

    @pl.when(live)
    def _block():
        k = k_ref[...]
        w = w_ref[...]
        acc = jnp.zeros((bq, bk), jnp.float32)
        for h in range(hi):
            s = lax.dot_general(q_ref[h], k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            acc = acc + jnp.maximum(s, 0.0) * w[:, h:h + 1]
        row = iq * bq + lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        col = ik * bk + lax.broadcasted_iota(jnp.int32, acc.shape, 1)
        masked = (col > row) | (sq_ref[...] != skv_ref[...])
        o_ref[...] = jnp.where(masked, jnp.float32(-1e30), acc)


def packed_index_scores_pallas(q_idx, w, k_idx, seg, *, interpret=False):
    """The prefill index kernel (layouts above)."""
    hi, S, di = q_idx.shape
    bq, bk = index_blocks(S)
    if bq == 0 or (not interpret
                   and not index_scores_supported(S, hi, di)):
        raise ValueError(f"packed_index_scores_pallas: unsupported "
                         f"q {q_idx.shape} k {k_idx.shape}")

    def k_block(iq, ik):   # a block above the diagonal is not fetched
        return jnp.minimum(ik, (iq * bq + bq - 1) // bk)

    seg = seg.astype(jnp.int32)
    return pl.pallas_call(
        functools.partial(_index_scores_kernel, bq=bq, bk=bk, hi=hi),
        grid=(S // bq, S // bk),
        in_specs=[
            pl.BlockSpec((hi, bq, di), lambda iq, ik: (0, iq, 0)),
            pl.BlockSpec((bq, hi), lambda iq, ik: (iq, 0)),
            pl.BlockSpec((bk, di), lambda iq, ik: (k_block(iq, ik), 0)),
            pl.BlockSpec((bq, 1), lambda iq, ik: (iq, 0)),
            pl.BlockSpec((1, bk), lambda iq, ik: (0, k_block(iq, ik))),
        ],
        out_specs=pl.BlockSpec((bq, bk), lambda iq, ik: (iq, ik)),
        out_shape=jax.ShapeDtypeStruct((S, S), jnp.float32),
        interpret=interpret,
        name=INDEX_SCORES_KERNEL_NAME,
    )(q_idx, w.astype(jnp.float32), k_idx, seg[:, None], seg[None, :])
