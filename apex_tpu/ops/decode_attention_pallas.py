"""Pallas TPU decode attention over a paged KV cache (q_len = 1).

The FIFTH dispatch family (ISSUE 10): serving decode is a genuinely
different program shape from every training kernel in ops/ — one query
row per sequence, the whole cost is streaming the KV cache out of HBM,
and the cache is PAGED (block-granular allocation,
``apex_tpu.serving.kv_cache``) so the key/value rows of one sequence
are scattered across non-contiguous pages named by a page table.

Kernel structure: grid ``(b, h/block_h, pages)``; the page table and
per-sequence context lengths ride as SCALAR-PREFETCH operands
(``pltpu.PrefetchScalarGridSpec``) so the K/V BlockSpec index maps do
the gather — grid step ``(i, hb, j)`` DMAs page ``page_table[i, j]``
for ``block_h`` heads directly from the paged arrays; allocation is
pure index arithmetic, never a reshape. Online-softmax accumulators
(fp32 m/l/acc) live in VMEM scratch across the sequential page axis;
pages at or beyond the sequence's context length are skipped
(``pl.when`` — the padded page-table tail points at the reserved null
page 0, fetched but never read into the accumulators).

Scores and the context reduction are computed as broadcast-multiply +
lane reductions rather than 1-row MXU matmuls: with q_len = 1 the MXU
would idle on a [1, d] operand anyway, and decode is bandwidth-bound —
the VPU keeps pace with the DMA stream.

Dispatch (the same shape as the four existing families):

    per-call ``impl=`` (raises on un-honorable)
      > ``set_decode_impl`` / ``APEX_DECODE_ATTN_IMPL`` (fall back)
      > dispatch-table entry (op "decode_attention")
      > built-in ``jnp``

The built-in default is the XLA gather-attention reference
(:func:`decode_attention_reference`) per the measured-dispatch rule —
no device A/B has landed for this family yet (queued in PERF.md §2);
the Pallas kernel engages via knob or a measured table entry. Tile
axis: ``block_h`` (heads per grid step), judged by
``apex_tpu.dispatch.tiles`` (op "decode_attention") with the usual
asymmetry — per-call raises, setter/env/table fall back per shape.

Layouts:
  q                [b, h, d]          (one query row per sequence slot)
  k_pages/v_pages  [h, pages, page_size, d]
  page_table       [b, max_pages]     int32 (padding -> null page 0)
  lengths          [b]                int32 (0 = inactive slot -> 0 out)
  k_scale/v_scale  [h, pages]         per-(page, head) scales of the
                                      int8 KV tier (ISSUE 20), or None

int8 KV tier (serving.kv_tier): when the pages are int8 codes, the
per-(page, head) scales are gathered through the page table by XLA
(``b * h * max_pages`` elements) and ride as two more operands, one
resident fp32 ``[block_h, max_pages]`` row block per (slot, head
block). Both impls dequantize at read — the kernel scales the scores
and the context sum per head rather than the page, so no dequantized
page copy is ever materialized. The VMEM model budgets the scale
blocks at the int8 itemsize (tiles.decode_vmem_bytes).
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.dispatch import tiles

NEG_INF = -1e30  # python float: jnp scalars would be captured consts
                 # inside the pallas kernel (Mosaic requires operands)

# Process-wide impl preference (tri-state; falls back per shape — only
# per-call impl= raises on un-honorable requests, CLAUDE.md asymmetry)
_IMPL = None


def set_decode_impl(impl):
    """Pin the process-wide decode-attention impl preference ("jnp" |
    "pallas"), or un-pin with None (env/table/built-in apply again).
    Shapes the pinned kernel can't run fall back to the jnp reference
    silently; a setter CALL with an unknown impl still raises."""
    global _IMPL
    if impl not in (None, "jnp", "pallas"):
        raise ValueError(f"unknown decode-attention impl {impl!r}")
    _IMPL = impl


def _env_impl():
    """APEX_DECODE_ATTN_IMPL preference (tiles.env_choice: unknown
    values warn once and are ignored — an env knob is a preference,
    never a raise)."""
    return tiles.env_choice("APEX_DECODE_ATTN_IMPL", ("jnp", "pallas"))


# Process-wide head-block preference (same fall-back semantics as the
# other families' tile setters)
_BLOCK_H = None


def set_block_h(value):
    """Pin the process-wide head-block preference (positive int), or
    un-pin with None. Judged per shape by the shared tile model; an
    illegal pin falls back to the heuristic silently."""
    global _BLOCK_H
    tiles.check_setter_value(value, "block_h")
    _BLOCK_H = value


def supported(h, pages, page_size, d, dtype=None):
    """Whether the Pallas kernel handles this cache geometry: the page
    block's last two dims span full array axes (always Mosaic-legal),
    so the gate is the VMEM working set at the minimum one-head tile
    plus a bounded head_dim (the fp32 accumulators scale with d).
    ``dtype`` is the cache dtype — the SAME itemsize the tile model
    (and ``_pick_bh``) judges with, so this gate and the block picker
    cannot disagree at the VMEM boundary (fp32 assumed when absent)."""
    itembytes = tiles.itemsize(dtype) if dtype is not None else 4
    return (d <= 512 and page_size >= 1 and pages >= 1
            and tiles.decode_block_h(h, page_size, d, itembytes) != 0)


def _pick_bh(h, ps, d, dtype, block_h, tile_pref):
    """Effective head block: per-call (raises via the shared model) >
    setter/env (fall back) > table pref (falls back) > heuristic."""
    dims = {"b": 1, "h": h, "pages": 1, "ps": ps, "d": d}
    if block_h is not None:
        problems = tiles.legal("decode_attention", dims, dtype,
                               {"block_h": block_h})
        if problems:
            raise ValueError("decode_attention_pallas: "
                             + "; ".join(problems))
        return block_h
    prefs = [_BLOCK_H, tiles.env_int("APEX_DECODE_ATTN_BLOCK_H")]
    if tile_pref:
        prefs.append(dict(tile_pref).get("block_h"))
    for p in prefs:
        if p is not None and not tiles.legal(
                "decode_attention", dims, dtype, {"block_h": p}):
            return p
    return tiles.decode_block_h(h, ps, d, tiles.itemsize(dtype))


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
            scale, ps, n_pages, quant):
    """Every value keeps the layout it is loaded in — heads on the
    leading (untiled) axis, page positions on sublanes, head_dim on
    lanes — so the body is broadcasts and keepdims reductions only.
    Mosaic refuses the shape casts a 2-D ``[bh, d]`` formulation needs
    (``[bh, d] <-> [bh, 1, d]`` moves heads between the sublane and the
    leading axis: "infer-vector-layout: unsupported shape cast")."""
    if quant:
        ks_ref, vs_ref, o_ref, acc_scr, m_scr, l_scr = rest
    else:
        o_ref, acc_scr, m_scr, l_scr = rest
    i = pl.program_id(0)   # sequence slot
    j = pl.program_id(2)   # page index within the slot's table

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, jnp.float32(NEG_INF))
        l_scr[...] = jnp.zeros_like(l_scr)

    length = len_ref[i]

    @pl.when(j * ps < length)
    def _page():
        q = q_ref[0].astype(jnp.float32) * jnp.float32(scale)  # [bh, 1, d]
        k = k_ref[:, 0].astype(jnp.float32)                    # [bh, ps, d]
        v = v_ref[:, 0].astype(jnp.float32)
        # [bh, ps, 1] scores: sublane-broadcast multiply + lane
        # reduction (see module docstring — q_len=1 makes the MXU moot)
        s = jnp.sum(q * k, axis=-1, keepdims=True)
        if quant:
            # dequantize at read: this page's per-head scale is lane j
            # of the resident [bh, 1, n_pages] row block (an iota mask,
            # no dynamic lane index); one scale per head factors out of
            # both reductions, so it multiplies [bh, ps, 1] and
            # [bh, 1, d] instead of the [bh, ps, d] page
            here = lax.broadcasted_iota(
                jnp.int32, (k.shape[0], 1, n_pages), 2) == j
            ks = jnp.sum(jnp.where(here, ks_ref[0, 0], 0.0), axis=-1,
                         keepdims=True)                        # [bh, 1, 1]
            vs = jnp.sum(jnp.where(here, vs_ref[0, 0], 0.0), axis=-1,
                         keepdims=True)
            s = s * ks
        pos = j * ps + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        masked = pos >= length
        s = jnp.where(masked, jnp.float32(NEG_INF), s)
        m_new = jnp.maximum(m_scr[...], jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_scr[...] - m_new)
        p = jnp.exp(s - m_new)
        p = jnp.where(masked, 0.0, p)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        ctx = jnp.sum(p * v, axis=1, keepdims=True)            # [bh, 1, d]
        if quant:
            ctx = ctx * vs
        acc_scr[...] = acc_scr[...] * alpha + ctx
        m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        l = l_scr[...]
        o = acc_scr[...] / jnp.where(l > 0, l, 1.0)
        o_ref[0] = o.astype(o_ref.dtype)


def decode_attention_pallas(q, k_pages, v_pages, page_table, lengths,
                            sm_scale, *, k_scale=None, v_scale=None,
                            block_h=None, interpret=False,
                            tile_pref=None):
    """The Pallas paged-decode kernel (layouts in the module
    docstring). Call :func:`decode_attention` for the dispatched
    surface; this entry raises on unsupported geometry. With
    ``k_scale``/``v_scale`` (``[h, pages]`` — the int8 KV tier) the
    scales of each slot's pages ride as two extra operands and the
    kernel dequantizes at read."""
    b, h, d = q.shape
    n_pages_total, ps = k_pages.shape[1], k_pages.shape[2]
    max_pages = page_table.shape[1]
    quant = k_scale is not None
    if not supported(h, n_pages_total, ps, d, k_pages.dtype):
        raise ValueError(
            f"decode_attention_pallas: unsupported geometry h={h} "
            f"ps={ps} d={d} ({k_pages.dtype})")
    # judged at the CACHE dtype — the K/V pages are the streamed
    # working set the VMEM model budgets (same itemsize supported()
    # gates with; the int8 itemsize implies the scale operands, which
    # tiles.decode_vmem_bytes budgets too)
    bh = _pick_bh(h, ps, d, k_pages.dtype, block_h, tile_pref)
    q4 = q[:, :, None, :]                   # [b, h, 1, d]
    grid = (b, h // bh, max_pages)

    def q_map(i, hb, j, pt, ln):
        return (i, hb, 0, 0)

    def kv_map(i, hb, j, pt, ln):
        return (hb, pt[i, j], 0, 0)

    def sc_map(i, hb, j, pt, ln):
        return (i, hb, 0, 0, 0)

    in_specs = [
        pl.BlockSpec((1, bh, 1, d), q_map),
        pl.BlockSpec((bh, 1, ps, d), kv_map),
        pl.BlockSpec((bh, 1, ps, d), kv_map),
    ]
    operands = [q4, k_pages, v_pages]
    if quant:
        # [h, pages] -> [b, h/bh, bh, 1, max_pages]: XLA gathers each
        # slot's scales through the page table, so the block's last two
        # dims span their array axes whatever bh is (a (bh, 1, 1) block
        # over [h, pages, 1] is refused by the Mosaic lowering unless
        # bh == h), heads sit on the leading axis like the K/V blocks',
        # and the block index is constant along the page axis — one DMA
        # per (slot, head block), not one per grid step
        def slot_scales(scale):
            g = scale[:, page_table].astype(jnp.float32)  # [h, b, mp]
            return g.reshape(h // bh, bh, b, 1, max_pages).transpose(
                2, 0, 1, 3, 4)

        in_specs += [pl.BlockSpec((1, 1, bh, 1, max_pages), sc_map)] * 2
        operands += [slot_scales(k_scale), slot_scales(v_scale)]

    kern = functools.partial(_kernel, scale=float(sm_scale), ps=ps,
                             n_pages=max_pages, quant=quant)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, bh, 1, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((bh, 1, d), jnp.float32),
                pltpu.VMEM((bh, 1, 1), jnp.float32),
                pltpu.VMEM((bh, 1, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q4.shape, q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)
    return out[:, :, 0, :]


def decode_attention_reference(q, k_pages, v_pages, page_table,
                               lengths, sm_scale, k_scale=None,
                               v_scale=None):
    """The jnp gather-attention reference (and the family's built-in
    default impl): gather each slot's pages, mask past the context
    length, exact fp32 softmax. Inactive slots (length 0) return 0 —
    the same fully-masked-row semantics as every attention kernel in
    ops/. ``k_scale``/``v_scale`` (``[h, pages]``, the int8 KV tier)
    gather through the SAME page table and dequantize at read."""
    b, h, d = q.shape
    ps = k_pages.shape[2]
    # [h, b, max_pages, ps, d] -> [b, h, S, d]
    k = k_pages[:, page_table].transpose(1, 0, 2, 3, 4).reshape(
        b, h, -1, d).astype(jnp.float32)
    v = v_pages[:, page_table].transpose(1, 0, 2, 3, 4).reshape(
        b, h, -1, d).astype(jnp.float32)
    if k_scale is not None:
        # [h, b, max_pages] -> [b, h, S] (one scale per page, repeated
        # over the page's positions)
        ks = jnp.repeat(k_scale[:, page_table].transpose(1, 0, 2)
                        .astype(jnp.float32), ps, axis=-1)
        vs = jnp.repeat(v_scale[:, page_table].transpose(1, 0, 2)
                        .astype(jnp.float32), ps, axis=-1)
        k = k * ks[..., None]
        v = v * vs[..., None]
    s = jnp.sum(
        (q.astype(jnp.float32) * jnp.float32(sm_scale))[:, :, None, :]
        * k, axis=-1)                              # [b, h, S]
    col = jnp.arange(s.shape[-1], dtype=jnp.int32)[None, None, :]
    masked = col >= lengths.astype(jnp.int32)[:, None, None]
    s = jnp.where(masked, NEG_INF, s)
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    e = jnp.where(masked, 0.0, e)
    tot = jnp.sum(e, axis=-1, keepdims=True)
    p = jnp.where(tot > 0, e / jnp.where(tot > 0, tot, 1.0), 0.0)
    return jnp.sum(p[..., None] * v, axis=2).astype(q.dtype)


def _effective_impl(impl, q, k_pages, page_table):
    """``(impl, from_table, tile_pref)``: per-call > setter > env >
    dispatch-table entry for this cache-geometry bucket > built-in
    "jnp". A table "pallas" measured on CPU runs in interpret mode —
    the way it was measured (same contract as ops.attention)."""
    if impl is not None:
        return impl, False, None
    pref = _IMPL or _env_impl()
    if pref is not None:
        return pref, False, None
    from apex_tpu import dispatch

    b, h, d = q.shape
    choice, params = dispatch.lookup_params(
        "decode_attention", dtype=q.dtype, b=b, h=h,
        pages=page_table.shape[1], ps=k_pages.shape[2], d=d)
    pref_t = tuple(sorted(params.items())) if params else None
    if choice:
        return choice, True, pref_t
    return "jnp", False, pref_t


def decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                     sm_scale=None, k_scale=None, v_scale=None,
                     impl=None, block_h=None,
                     interpret=None, tile_pref=None):
    """Dispatched paged decode attention (q: [b, h, d]; pages:
    [h, P, ps, d]; page_table: [b, max_pages]; lengths: [b]).

    ``impl`` is a per-call DEMAND ("jnp" | "pallas"; "pallas" on an
    unsupported geometry raises); ``set_decode_impl`` /
    ``APEX_DECODE_ATTN_IMPL`` are preferences that fall back, and an
    unpinned call consults the dispatch table (op "decode_attention").
    ``block_h`` is the per-call tile demand (raises when illegal);
    ``interpret`` defaults to True on the CPU platform only (an
    explicit argument wins; any other platform compiles the kernel). ``k_scale``/``v_scale``
    (``[h, P]``) engage the int8 KV tier's dequantize-at-read on
    either impl; int8 pages WITHOUT scales raise — codes are
    meaningless without their scales, there is no honorable
    fallback."""
    if sm_scale is None:
        import math

        sm_scale = 1.0 / math.sqrt(q.shape[-1])
    if impl is not None and impl not in ("jnp", "pallas"):
        raise ValueError(f"unknown decode-attention impl {impl!r}")
    if (k_scale is None) != (v_scale is None):
        raise ValueError("decode_attention: k_scale and v_scale come "
                         "as a pair (one of them is missing)")
    if k_scale is None and k_pages.dtype == jnp.int8:
        raise ValueError(
            "decode_attention: int8 pages without k_scale/v_scale — "
            "quantized codes are meaningless without their scales")
    eff, from_table, pref_t = _effective_impl(impl, q, k_pages,
                                              page_table)
    if tile_pref:
        merged = dict(pref_t or ())
        merged.update(dict(tile_pref))
        pref_t = tuple(sorted(merged.items()))
    b, h, d = q.shape
    ok = supported(h, k_pages.shape[1], k_pages.shape[2], d,
                   k_pages.dtype)
    if eff == "pallas" and not ok and impl == "pallas":
        raise ValueError(
            f"decode_attention: impl='pallas' cannot be honored for "
            f"h={h} ps={k_pages.shape[2]} d={d}")
    if eff == "pallas" and ok:
        if interpret is None:
            interpret = jax.devices()[0].platform == "cpu"
        return decode_attention_pallas(
            q, k_pages, v_pages, page_table, lengths, sm_scale,
            k_scale=k_scale, v_scale=v_scale,
            block_h=block_h, interpret=interpret, tile_pref=pref_t)
    # the jnp path is what actually runs from here on: an explicit
    # per-call tile demand cannot be honored on it, whatever
    # preference resolved the impl (a "pallas" setter/table choice
    # that fell back on unsupported geometry included) — per-call
    # raises, preferences fall back
    if block_h is not None:
        raise ValueError("decode_attention: block_h tiles the pallas "
                         "kernel; it cannot be honored on the jnp path")
    return decode_attention_reference(q, k_pages, v_pages, page_table,
                                      lengths, sm_scale,
                                      k_scale=k_scale, v_scale=v_scale)
