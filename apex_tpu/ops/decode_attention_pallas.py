"""Pallas TPU decode attention over a paged KV cache (q_len = 1).

The FIFTH kernel family (ISSUE 10): serving decode is a genuinely
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
pure index arithmetic. Online-softmax accumulators (fp32 m/l/acc) live
in VMEM scratch across the sequential page axis; pages at or beyond
the sequence's context length are skipped (``pl.when``) and not even
fetched: past a slot's last page the block index stays where it was.

The kernel reads the cache where XLA keeps it. A decode step scatters
``[slots, h, d]`` rows into ``cache[layer, :, page, offset, :]``, and
for that scatter XLA holds the cache with (h, d) as the minor tile —
physically ``[layers, pages, page_size, h, d]``. The kernel takes that
view (a transpose that is a bitcast there) and the layer as a block
coordinate, so the engine's stacked float cache reaches the custom
call with no copy; a page is a ``[page_size, block_h, d]`` block,
heads on sublanes. Measured on the v5e at GPT-2 large, 16 slots
(PERF.md §6, PR 26): row-major ``[block_h, page_size, d]`` blocks cost
a relayout of every layer's slice every step (22.7 ms a round beside a
5.1 ms kernel), and the whole stacked array in that form makes the
compiler copy the whole cache once per layer.

Scores and the context reduction are broadcast-multiplies and
reductions on the VPU: with q_len = 1 a product is one weight load per
(head, page) on the MXU, not a stream. Measured there too: 6.2 ms a
round against a 3.6 ms byte floor at GPT-2 large, and head-batched
``dot_general`` products were within 5% of the VPU body.

Which program runs (no knob):

    per-call ``impl=`` (raises on un-honorable)
      > the kernel, where the default backend is a TPU and
        :func:`supported` holds for the cache geometry
      > the jnp reference (the CPU; an unsupported geometry)

Tile axis: ``block_h`` (heads per grid step), judged by
``apex_tpu.dispatch.tiles`` (op "decode_attention"): all of h, or a
divisor of h that is whole sublane tiles; the heuristic takes the
largest that fits VMEM (every grid step costs the same fixed overhead).
Per-call ``block_h=`` raises when illegal; ``set_block_h`` /
``APEX_DECODE_ATTN_BLOCK_H`` are preferences that fall back per shape.

Layouts:
  q                [b, h, d]          (one query row per sequence slot)
  k_pages/v_pages  [h, pages, page_size, d], or the engine's stacked
                   [layers, h, pages, page_size, d] with ``layer=``
  page_table       [b, max_pages]     int32 (padding -> null page 0)
  lengths          [b]                int32 (0 = inactive slot -> 0 out)
  k_scale/v_scale  [h, pages]         per-(page, head) scales of the
                                      int8 KV tier (ISSUE 20), or None
                                      (stacked like the pages)

int8 KV tier (serving.kv_tier): when the pages are int8 codes, the
per-(page, head) scales are gathered through the page table by XLA
(``b * h * max_pages`` elements) and ride as two more operands, one
resident fp32 ``[block_h, max_pages]`` row block per (slot, head
block). Both impls dequantize at read — the kernel scales the scores
and the context sum per head rather than the page, so no dequantized
page copy is ever materialized.
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.dispatch import tiles
from apex_tpu.ops.attention import masked_softmax

NEG_INF = -1e30  # python float: jnp scalars would be captured consts
                 # inside the pallas kernel (Mosaic requires operands)

KERNEL_NAME = "paged_decode_attention"  # the device op's name in a trace

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


def _pick_bh(h, ps, d, dtype, block_h):
    """Effective head block: per-call (raises via the shared model) >
    setter/env (fall back) > heuristic."""
    dims = {"b": 1, "h": h, "pages": 1, "ps": ps, "d": d}
    if block_h is not None:
        problems = tiles.legal("decode_attention", dims, dtype,
                               {"block_h": block_h})
        if problems:
            raise ValueError("decode_attention_pallas: "
                             + "; ".join(problems))
        return block_h
    for p in (_BLOCK_H, tiles.env_int("APEX_DECODE_ATTN_BLOCK_H")):
        if p is not None and not tiles.legal(
                "decode_attention", dims, dtype, {"block_h": p}):
            return p
    return tiles.decode_block_h(h, ps, d, tiles.itemsize(dtype))


def _kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, *rest,
            scale, ps, n_pages, quant):
    """One (slot, head block, page) step. A page arrives as
    ``[ps, bh, d]``: positions on the leading (untiled) axis, heads on
    sublanes, head_dim on lanes. The softmax runs over the leading
    axis, so its max and sums are elementwise across vregs and only
    the score's contraction over head_dim reduces lanes; nothing is
    reshaped (Mosaic refuses casts that move heads between the
    sublane and the leading axis)."""
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
        q = q_ref[...].astype(jnp.float32) * jnp.float32(scale)  # [bh, d]
        k = k_ref[...].astype(jnp.float32)                    # [ps, bh, d]
        s = jnp.sum(q[None] * k, axis=-1, keepdims=True)      # [ps, bh, 1]
        if quant:
            # dequantize at read: this page's per-head scale is lane j
            # of the resident [bh, n_pages] row block (an iota mask, no
            # dynamic lane index); one scale per head factors out of
            # both reductions, so it multiplies the [ps, bh, 1] scores
            # and the [bh, d] context instead of the [ps, bh, d] page
            here = lax.broadcasted_iota(
                jnp.int32, (q.shape[0], n_pages), 1) == j
            ks = jnp.sum(jnp.where(here, ks_ref[...], 0.0), axis=-1,
                         keepdims=True)                        # [bh, 1]
            vs = jnp.sum(jnp.where(here, vs_ref[...], 0.0), axis=-1,
                         keepdims=True)
            s = s * ks[None]
        pos = j * ps + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        masked = pos >= length
        s = jnp.where(masked, jnp.float32(NEG_INF), s)
        m_new = jnp.maximum(m_scr[...], jnp.max(s, axis=0))   # [bh, 1]
        alpha = jnp.exp(m_scr[...] - m_new)
        p = jnp.exp(s - m_new[None])
        p = jnp.where(masked, 0.0, p)
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=0)
        ctx = jnp.sum(p * v_ref[...].astype(jnp.float32), axis=0)  # [bh, d]
        if quant:
            ctx = ctx * vs
        acc_scr[...] = acc_scr[...] * alpha + ctx
        m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _finish():
        l = l_scr[...]
        o = acc_scr[...] / jnp.where(l > 0, l, 1.0)
        o_ref[...] = o.astype(o_ref.dtype)


def decode_attention_pallas(q, k_pages, v_pages, page_table, lengths,
                            sm_scale, *, k_scale=None, v_scale=None,
                            layer=None, block_h=None, interpret=False):
    """The Pallas paged-decode kernel (layouts in the module
    docstring). Call :func:`decode_attention` for the dispatched
    surface; this entry raises on unsupported geometry. With
    ``k_scale``/``v_scale`` (``[h, pages]`` — the int8 KV tier) the
    scales of each slot's pages ride as two extra operands and the
    kernel dequantizes at read. With ``layer`` (a static int) the
    pages and scales are the engine's stacked ``[layers, ...]`` arrays
    and the layer is one more coordinate of the K/V block index."""
    b, h, d = q.shape
    n_pages_total, ps = k_pages.shape[-3], k_pages.shape[-2]
    max_pages = page_table.shape[1]
    quant = k_scale is not None
    if not supported(h, n_pages_total, ps, d, k_pages.dtype):
        raise ValueError(
            f"decode_attention_pallas: unsupported geometry h={h} "
            f"ps={ps} d={d} ({k_pages.dtype})")
    # judged at the CACHE dtype — the K/V pages are the streamed
    # working set the VMEM model budgets (same itemsize supported()
    # gates with)
    bh = _pick_bh(h, ps, d, k_pages.dtype, block_h)
    if layer is None:
        k_pages, v_pages, layer = k_pages[None], v_pages[None], 0
        if quant:
            k_scale, v_scale = k_scale[None], v_scale[None]

    # [layers, h, pages, ps, d] seen as [layers, pages, ps, h, d]: the
    # layout XLA keeps a cache in that a step scatters [slots, h, d]
    # rows into (heads x head_dim as the minor tile), so for the
    # engine's stacked float cache this transpose is a bitcast and the
    # kernel reads the cache where it lies. Row-major [.., ps, d]
    # blocks cost a relayout of every layer's slice, every step.
    def paged(x):
        return jnp.transpose(x, (0, 2, 3, 1, 4))

    def q_map(i, hb, j, pt, ln):
        return (i, hb, 0)

    def kv_map(i, hb, j, pt, ln):
        # past the slot's last page the index stays on it: an
        # unchanged block index is not fetched again, so the padded
        # tail of a page table costs no DMA
        last = jnp.maximum(ln[i] - 1, 0) // ps
        return (layer, pt[i, jnp.minimum(j, last)], 0, hb, 0)

    kv_spec = pl.BlockSpec((None, None, ps, bh, d), kv_map)
    in_specs = [pl.BlockSpec((None, bh, d), q_map), kv_spec, kv_spec]
    operands = [q, paged(k_pages), paged(v_pages)]
    if quant:
        # [h, pages] -> [b, h, max_pages]: XLA gathers each slot's
        # scales through the page table (a few KB), heads on sublanes
        # like the K/V blocks', and the block index is constant along
        # the page axis — one DMA per (slot, head block)
        def slot_scales(scale):
            return scale[layer][:, page_table].astype(
                jnp.float32).transpose(1, 0, 2)

        in_specs += [pl.BlockSpec((None, bh, max_pages), q_map)] * 2
        operands += [slot_scales(k_scale), slot_scales(v_scale)]

    kern = functools.partial(_kernel, scale=float(sm_scale), ps=ps,
                             n_pages=max_pages, quant=quant)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, h // bh, max_pages),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, bh, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((bh, d), jnp.float32),
                pltpu.VMEM((bh, 1), jnp.float32),
                pltpu.VMEM((bh, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        name=KERNEL_NAME,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      *operands)


def _context_view(page_table, page_size, page_base=None, starts=None):
    """``(page_base [b, n], starts [b])`` int32 with their defaults: entry
    ``j`` of a slot's table holds positions from ``j * page_size``, and
    the context starts at position 0."""
    b, n = page_table.shape
    if page_base is None:
        page_base = jnp.broadcast_to(
            jnp.arange(n, dtype=jnp.int32)[None, :] * page_size, (b, n))
    if starts is None:
        starts = jnp.zeros((b,), jnp.int32)
    return page_base.astype(jnp.int32), starts.astype(jnp.int32)


def _outside_context(page_table, page_size, lengths, page_base=None,
                     starts=None):
    """``[b, n * page_size]`` bool over a slot's gathered rows: True
    where a row is NOT a position of ``[start, length)``. Shared by
    both jnp references."""
    page_base, starts = _context_view(page_table, page_size, page_base,
                                      starts)
    pos = (page_base[:, :, None] + jnp.arange(
        page_size, dtype=jnp.int32)[None, None, :]).reshape(
            page_table.shape[0], -1)
    return (pos >= lengths.astype(jnp.int32)[:, None]) \
        | (pos < starts[:, None])


def decode_attention_reference(q, k_pages, v_pages, page_table,
                               lengths, sm_scale, k_scale=None,
                               v_scale=None):
    """The jnp gather-attention reference (what runs where the kernel
    cannot: the CPU, an unsupported geometry, a GSPMD-partitioned
    cache): gather each slot's pages, mask past the context
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
    masked = _outside_context(page_table, ps, lengths)[:, None, :]
    p = masked_softmax(s, masked)
    return jnp.sum(p[..., None] * v, axis=2).astype(q.dtype)


def _effective_impl(impl, h, pages, page_size, d, dtype):
    """The rule, from what the code can observe: a per-call ``impl`` is
    a demand; otherwise the Pallas kernel where the default backend is
    a TPU and :func:`supported` holds for the cache geometry, the jnp
    reference everywhere else."""
    if impl is not None:
        return impl
    if jax.default_backend() == "tpu" and supported(h, pages, page_size,
                                                    d, dtype):
        return "pallas"
    return "jnp"


def resolved(h, pages, page_size, d, dtype, impl=None, block_h=None):
    """``(impl, block_h)`` a :func:`decode_attention` call with this
    cache geometry and these demands runs with (``block_h`` None on
    the jnp path) — for a caller that reports what it was built
    with."""
    eff = _effective_impl(impl, h, pages, page_size, d, dtype)
    if eff != "pallas":
        return eff, None
    return eff, _pick_bh(h, page_size, d, dtype, block_h)


def decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                     sm_scale=None, k_scale=None, v_scale=None,
                     layer=None, impl=None, block_h=None,
                     interpret=None):
    """Dispatched paged decode attention (q: [b, h, d]; pages:
    [h, P, ps, d]; page_table: [b, max_pages]; lengths: [b]). With
    ``layer`` (a static int) the pages and scales are stacked
    ``[layers, ...]`` arrays and the call attends that layer's.

    ``impl`` is a per-call DEMAND ("jnp" | "pallas"; "pallas" on an
    unsupported geometry raises). Unset, the kernel runs where the
    default backend is a TPU and the geometry is supported, the jnp
    reference otherwise. ``block_h`` is the per-call tile demand
    (raises when illegal, and on the jnp path); ``interpret`` defaults
    to True on the CPU platform only (an explicit argument wins; any
    other platform compiles the kernel). ``k_scale``/``v_scale``
    (``[h, P]``) engage the int8 KV tier's dequantize-at-read on
    either impl; int8 pages WITHOUT scales raise — codes are
    meaningless without their scales, there is no honorable
    fallback."""
    if sm_scale is None:
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
    h, d = q.shape[1:]
    pages, ps = k_pages.shape[-3:-1]
    eff = _effective_impl(impl, h, pages, ps, d, k_pages.dtype)
    if eff == "pallas":
        # (a demand on a geometry the kernel does not support raises
        # in decode_attention_pallas; the rule never picks one)
        if interpret is None:
            interpret = jax.devices()[0].platform == "cpu"
        return decode_attention_pallas(
            q, k_pages, v_pages, page_table, lengths, sm_scale,
            k_scale=k_scale, v_scale=v_scale, layer=layer,
            block_h=block_h, interpret=interpret)
    # a per-call tile demand cannot be honored on the jnp path,
    # whether the rule or the caller chose it
    if block_h is not None:
        raise ValueError("decode_attention: block_h tiles the pallas "
                         "kernel; it cannot be honored on the jnp path")
    if layer is not None:
        k_pages, v_pages = k_pages[layer], v_pages[layer]
        if k_scale is not None:
            k_scale, v_scale = k_scale[layer], v_scale[layer]
    return decode_attention_reference(q, k_pages, v_pages, page_table,
                                      lengths, sm_scale,
                                      k_scale=k_scale, v_scale=v_scale)


# ------------------------------------------------- grouped-query decode
#
# A second cache layout and kernel for models whose query heads share KV
# heads, whose K and V differ in width, and whose layers may see only a
# window of the context and carry a sink logit (serving/mimo.py). GPT-2's
# call above is untouched. The two paths share the masks and the softmax
# of their jnp forms and nothing else, and that is owed a deletion
# (ROADMAP S2): once GPT-2's cache is re-laid ``[pages, page_size, h*d]``
# this kernel has to take a query group below 8 rows (pad it to a sublane
# tile) and V 64 wide (chunked like K), and then ``paged_decode_attention``,
# its reference, ``supported``/``resolved`` and the old layout go.
#
# Layouts:
#   q            [b, hq, dk]            one query row per slot
#   k_pages      [pages, page_size, n_kv * dk]   one layer; (h, d) minor,
#   v_pages      [pages, page_size, n_kv * dv]   so 768 / 512 / 1536 /
#                                       1024 lanes are whole tiles: no pad
#   page_table   [b, n]      int32      the pages a slot reads, in any order
#   page_base    [b, n]      int32      the position of row 0 of each entry
#                                       (None: entry j holds j * page_size;
#                                       a ring names whatever it holds now)
#   starts       [b]         int32      first visible position (None: 0)
#   lengths      [b]         int32      context length, the query's own
#                                       position included (0 = inactive)
#   sink         [hq]        float32    a logit that joins each head's
#                                       softmax denominator only, or None
# Query head i reads KV head i // (hq / n_kv). Out: [b, hq, dv].

GROUPED_KERNEL_NAME = "grouped_decode_attention"


def _kv_chunk(n_kv, dk, dv):
    """KV heads a kernel step takes together: the fewest whose K columns
    fill whole lane tiles (2 at dk = 192, 1 at 128 or 256), so that every
    slice inside the kernel is tile-aligned; 0 where none does."""
    if dv % 128:
        return 0
    return next((c for c in range(1, n_kv + 1)
                 if n_kv % c == 0 and (c * dk) % 128 == 0), 0)


def grouped_supported(hq, n_kv, dk, dv, page_size, dtype=None):
    """Whether Mosaic takes the grouped kernel at this geometry: aligned
    lane slices (:func:`_kv_chunk`), query groups that are whole float32
    sublane tiles, and a K and V page (double-buffered) inside VMEM."""
    itembytes = tiles.itemsize(dtype) if dtype is not None else 4
    page_bytes = 2 * page_size * n_kv * (dk + dv) * itembytes
    return (hq % n_kv == 0 and (hq // n_kv) % 8 == 0
            and _kv_chunk(n_kv, dk, dv) != 0 and page_size % 8 == 0
            and page_bytes <= 8 * 2 ** 20)


def _band_queries(q, n_kv, c):
    """[b, hq, dk] -> [b, hq, c * dk]: each head's query in the columns
    of its KV head within its chunk of ``c``, zeros in the others', so
    that one product against the chunk's K columns gives every head its
    own KV head's scores with no unaligned slice."""
    b, hq, dk = q.shape
    if c == 1:
        return q
    within = (jnp.arange(hq) // (hq // n_kv)) % c             # [hq]
    onehot = (within[:, None] == jnp.arange(c)[None, :]).astype(q.dtype)
    return (q[:, :, None, :] * onehot[None, :, :, None]).reshape(
        b, hq, c * dk)


def _grouped_kernel(pt_ref, base_ref, start_ref, len_ref, q_ref, k_ref,
                    v_ref, *rest, scale, ps, n_iter, n_kv, c, group, dk,
                    dv, has_sink):
    """One (slot, table entry) step: a page arrives as ``[ps, n_kv*dk]``
    and ``[ps, n_kv*dv]``, positions on sublanes. Per chunk of ``c`` KV
    heads the banded queries meet the chunk's K columns on the MXU
    (scores ``[c*group, ps]``, positions on lanes), then each KV head's
    probabilities meet its V columns."""
    if has_sink:
        sink_ref, o_ref, acc_scr, m_scr, l_scr = rest
    else:
        o_ref, acc_scr, m_scr, l_scr = rest
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        if has_sink:
            # the sink is one more logit of the denominator: start the
            # running max at it and the running sum at exp(0)
            m_scr[...] = sink_ref[...]
            l_scr[...] = jnp.ones_like(l_scr)
        else:
            m_scr[...] = jnp.full_like(m_scr, jnp.float32(NEG_INF))
            l_scr[...] = jnp.zeros_like(l_scr)

    base, length, start = base_ref[i, j], len_ref[i], start_ref[i]

    @pl.when((base < length) & (base + ps > start))
    def _page():
        pos = base + lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        masked = (pos >= length) | (pos < start)                 # [1, ps]
        rows = c * group
        for ci in range(n_kv // c):
            r0 = ci * rows
            qc = q_ref[r0:r0 + rows, :]                      # [rows, c*dk]
            kc = k_ref[:, ci * c * dk:(ci + 1) * c * dk]     # [ps, c*dk]
            s = lax.dot_general(qc, kc, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.where(masked, jnp.float32(NEG_INF),
                          s * jnp.float32(scale))            # [rows, ps]
            m_prev = m_scr[r0:r0 + rows, :]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)                  # [rows, 1]
            p = jnp.where(masked, 0.0, jnp.exp(s - m_new))
            l_scr[r0:r0 + rows, :] = l_scr[r0:r0 + rows, :] * alpha \
                + jnp.sum(p, axis=1, keepdims=True)
            m_scr[r0:r0 + rows, :] = m_new
            for u in range(c):
                g = ci * c + u
                a0, p0 = r0 + u * group, u * group
                vu = v_ref[:, g * dv:(g + 1) * dv]           # [ps, dv]
                ctx = lax.dot_general(
                    p[p0:p0 + group, :].astype(vu.dtype), vu,
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)      # [group, dv]
                acc_scr[a0:a0 + group, :] = \
                    acc_scr[a0:a0 + group, :] * alpha[p0:p0 + group, :] \
                    + ctx

    @pl.when(j == n_iter - 1)
    def _finish():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def grouped_decode_attention_pallas(q, k_pages, v_pages, page_table,
                                    lengths, sm_scale, *, n_kv,
                                    page_base=None, starts=None, sink=None,
                                    interpret=False):
    """The grouped-query paged decode kernel (layouts above). Compiled,
    it wants :func:`grouped_supported`; interpreted it takes any widths
    (one chunk of all KV heads where none aligns)."""
    b, hq, dk = q.shape
    ps = k_pages.shape[1]
    dv = v_pages.shape[2] // n_kv
    n = page_table.shape[1]
    if k_pages.shape[2] != n_kv * dk or hq % n_kv:
        raise ValueError(
            f"grouped_decode_attention: q {q.shape} and K pages "
            f"{k_pages.shape} do not make {n_kv} KV heads of width {dk}")
    if not interpret and not grouped_supported(hq, n_kv, dk, dv, ps,
                                               k_pages.dtype):
        raise ValueError(
            f"grouped_decode_attention_pallas: unsupported geometry "
            f"hq={hq} n_kv={n_kv} dk={dk} dv={dv} ps={ps}")
    c = _kv_chunk(n_kv, dk, dv) or n_kv
    group = hq // n_kv
    page_base, starts = _context_view(page_table, ps, page_base, starts)
    has_sink = sink is not None

    def slot_map(i, j, pt, base, st, ln):
        return (i, 0, 0)

    def page_map(i, j, pt, base, st, ln):
        return (pt[i, j], 0, 0)

    in_specs = [pl.BlockSpec((None, hq, c * dk), slot_map),
                pl.BlockSpec((None, ps, n_kv * dk), page_map),
                pl.BlockSpec((None, ps, n_kv * dv), page_map)]
    operands = [_band_queries(q, n_kv, c), k_pages, v_pages]
    if has_sink:
        in_specs.append(pl.BlockSpec((hq, 1), lambda i, j, *_: (0, 0)))
        operands.append(sink.astype(jnp.float32).reshape(hq, 1))
    kern = functools.partial(
        _grouped_kernel, scale=float(sm_scale), ps=ps, n_iter=n, n_kv=n_kv,
        c=c, group=group, dk=dk, dv=dv, has_sink=has_sink)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, n),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, hq, dv), slot_map),
            scratch_shapes=[
                pltpu.VMEM((hq, dv), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, dv), q.dtype),
        interpret=interpret,
        name=GROUPED_KERNEL_NAME,
    )(page_table.astype(jnp.int32), page_base, starts,
      lengths.astype(jnp.int32), *operands)


def grouped_decode_attention_reference(q, k_pages, v_pages, page_table,
                                       lengths, sm_scale, *, n_kv,
                                       page_base=None, starts=None,
                                       sink=None):
    """The jnp form of the grouped kernel: gather each slot's pages,
    mask outside ``[start, length)``, exact float32 softmax with the sink
    in its denominator. Inactive slots return 0."""
    b, hq, dk = q.shape
    ps = k_pages.shape[1]
    dv = v_pages.shape[2] // n_kv
    n = page_table.shape[1]
    k = k_pages[page_table].reshape(b, n * ps, n_kv, dk).astype(jnp.float32)
    v = v_pages[page_table].reshape(b, n * ps, n_kv, dv).astype(jnp.float32)
    masked = _outside_context(page_table, ps, lengths, page_base,
                              starts)[:, None, None, :]      # [b, 1, 1, S]
    qg = q.astype(jnp.float32).reshape(b, n_kv, hq // n_kv, dk)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k,
                   precision=lax.Precision.HIGHEST) * jnp.float32(sm_scale)
    if sink is not None:
        sink = sink.astype(jnp.float32).reshape(1, n_kv, hq // n_kv, 1)
    out = jnp.einsum("bkgs,bskv->bkgv", masked_softmax(s, masked, sink), v,
                     precision=lax.Precision.HIGHEST)
    return out.reshape(b, hq, dv).astype(q.dtype)


def grouped_resolved(hq, n_kv, dk, dv, page_size, dtype, impl=None):
    """The impl a :func:`grouped_decode_attention` call runs with: a
    per-call demand, else the kernel on a TPU where the geometry is
    supported, else the jnp form."""
    if impl is not None:
        if impl not in ("jnp", "pallas"):
            raise ValueError(f"unknown decode-attention impl {impl!r}")
        return impl
    if jax.default_backend() == "tpu" and grouped_supported(
            hq, n_kv, dk, dv, page_size, dtype):
        return "pallas"
    return "jnp"


def grouped_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                             n_kv, sm_scale=None, page_base=None,
                             starts=None, sink=None, impl=None,
                             interpret=None):
    """Dispatched grouped-query paged decode attention (layouts above).
    ``impl`` is a per-call demand ("jnp" | "pallas"; "pallas" compiled on
    an unsupported geometry raises); ``interpret`` defaults to True on
    the CPU platform only."""
    hq, dk = q.shape[1:]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(dk)
    ps = k_pages.shape[1]
    dv = v_pages.shape[2] // n_kv
    kw = dict(n_kv=n_kv, page_base=page_base, starts=starts, sink=sink)
    if grouped_resolved(hq, n_kv, dk, dv, ps, k_pages.dtype,
                        impl) == "pallas":
        if interpret is None:
            interpret = jax.devices()[0].platform == "cpu"
        return grouped_decode_attention_pallas(
            q, k_pages, v_pages, page_table, lengths, sm_scale,
            interpret=interpret, **kw)
    return grouped_decode_attention_reference(
        q, k_pages, v_pages, page_table, lengths, sm_scale, **kw)
