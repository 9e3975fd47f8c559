"""Pallas TPU decode attention over a paged KV cache (q_len = 1).

Serving decode is a different program shape from every training kernel
in ops/: one query row per sequence, the whole cost is streaming the KV
cache out of HBM, and the cache is PAGED (``apex_tpu.serving.kv_cache``):
the rows of one sequence lie in non-contiguous pages named by a page
table. One kernel, one jnp reference and one family of layouts serve
every model family: multi-head (GPT-2: ``n_kv = hq``, K and V 64 wide),
grouped-query with K and V of different widths, window layers reading a
ring, sink logits (serving/mimo.py).

Layouts:
  q            [b, hq, dk]            one query row per slot
  k_pages      [pages, page_size, n_kv * dk]   one layer; (head, width)
  v_pages      [pages, page_size, n_kv * dv]   minor, so 1280 / 768 / 512
                                      lanes are whole tiles: nothing is
                                      padded, a step's rows scatter in
                                      place and the kernel reads a page
                                      where it lies (no copy of a cache
                                      in any program: PERF.md, PR 27/28)
  page_table   [b, n]      int32      the pages a slot reads, in any order
                                      (padding -> null page 0)
  page_base    [b, n]      int32      the position of row 0 of each entry
                                      (None: entry j holds j * page_size;
                                      a ring names whatever it holds now)
  starts       [b]         int32      first visible position (None: 0)
  lengths      [b]         int32      context length, the query's own
                                      position included (0 = inactive
                                      slot -> 0 out)
  sink         [hq]        float32    a logit that joins each head's
                                      softmax denominator only, or None
  k_scale/v_scale  [pages, n_kv]      per-(page, head) scales of the int8
                                      KV tier (serving.kv_tier), or None
Query head i reads KV head i // (hq / n_kv). Out: [b, hq, dv].

Kernel structure: grid ``(b, n)``; the page table, bases, starts and
lengths ride as SCALAR-PREFETCH operands so the K/V BlockSpec index maps
do the gather: step ``(i, j)`` DMAs page ``page_table[i, j]`` whole.
Online-softmax accumulators (fp32 m/l/acc) live in VMEM scratch across
the sequential page axis; entries outside ``[start, length)`` are
skipped (``pl.when``). How a step meets its page follows the static
``(group, dk, dv)`` alone (:func:`_whole_page`):

* query groups of whole sublane tiles and V of whole lane tiles (MiMo:
  groups of 16 / 8, 192 / 128 wide): per chunk of the fewest KV heads
  whose K columns fill lane tiles, the chunk's banded queries meet its K
  columns, then each KV head's probabilities meet its V columns;
* anything narrower (GPT-2: a group of 1 is below a sublane tile, 64
  columns are half a lane tile): no slice of a page would be aligned, so
  every head's banded query meets the WHOLE page in one product and its
  probabilities the whole V page in another; head i's context is
  columns ``[kv(i) * dv, (kv(i) + 1) * dv)`` of row i, taken outside the
  kernel. The products it does not need are a few MFLOP a page on an MXU
  that q_len = 1 leaves idle; the bytes are the cost either way.

Which program runs (no knob): a per-call ``impl=`` is a demand (raises
on un-honorable); else the kernel where the default backend is a TPU
and :func:`grouped_supported` holds, else the jnp reference (the CPU;
an unsupported geometry). The int8 tier's pages take the jnp form, which
dequantizes at read (no cell runs the tier; its scales inside the
kernel are a later PR's).
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


GROUPED_KERNEL_NAME = "grouped_decode_attention"


def _kv_chunk(n_kv, dk, dv):
    """KV heads a kernel step takes together: the fewest whose K columns
    fill whole lane tiles (2 at dk = 192, 1 at 128 or 256), so that every
    slice inside the kernel is tile-aligned; 0 where none does."""
    if dv % 128:
        return 0
    return next((c for c in range(1, n_kv + 1)
                 if n_kv % c == 0 and (c * dk) % 128 == 0), 0)


def _whole_page(group, dk, dv):
    """Whether a kernel step meets its page WHOLE, every head's banded
    query in one product, rather than by chunks of KV heads: a query
    group below a sublane tile of 8 rows, or V columns that are not
    whole lane tiles, leave no aligned slice to take (GPT-2: group 1,
    64 wide)."""
    return group % 8 != 0 or dv % 128 != 0


def _rows(hq):
    """Query rows of the whole-page form: ``hq`` padded to sublane
    tiles."""
    return -(-hq // 8) * 8


def grouped_supported(hq, n_kv, dk, dv, page_size, dtype=None):
    """Whether Mosaic takes the kernel at this geometry: a K and V page
    (double-buffered) with the accumulators inside VMEM, and either
    aligned lane slices (:func:`_kv_chunk`) under query groups that are
    whole float32 sublane tiles, or pages of whole lane tiles met whole
    (:func:`_whole_page`)."""
    itembytes = tiles.itemsize(dtype) if dtype is not None else 4
    page_bytes = 2 * page_size * n_kv * (dk + dv) * itembytes
    if hq % n_kv or page_size % 8:
        return False
    if _whole_page(hq // n_kv, dk, dv):
        # the float32 accumulator is as wide as a V page: itself, the
        # page's contribution and the output block twice
        return ((n_kv * dk) % 128 == 0 and (n_kv * dv) % 128 == 0
                and page_bytes + 16 * _rows(hq) * n_kv * dv <= 8 * 2 ** 20)
    return _kv_chunk(n_kv, dk, dv) != 0 and page_bytes <= 8 * 2 ** 20


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


def _unband(o, hq, n_kv):
    """[b, rows, n_kv * dv] -> [b, hq, dv]: of row ``i`` of the
    whole-page form, the columns of head ``i``'s own KV head (the others
    hold its probabilities against heads it does not read)."""
    b, dv = o.shape[0], o.shape[2] // n_kv
    own = (jnp.arange(hq) // (hq // n_kv))[:, None] \
        == jnp.arange(n_kv)[None, :]                           # [hq, n_kv]
    return jnp.sum(jnp.where(own[None, :, :, None],
                             o[:, :hq].reshape(b, hq, n_kv, dv), 0), axis=2)


def _softmax_step(s, masked, m_scr, l_scr, r0, rows):
    """One page's scores ``[rows, ps]`` into the running max and sum of
    rows ``r0..``: returns the page's probabilities and the factor
    ``alpha [rows, 1]`` that rescales what was accumulated before."""
    m_prev = m_scr[r0:r0 + rows, :]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)                          # [rows, 1]
    p = jnp.where(masked, 0.0, jnp.exp(s - m_new))
    l_scr[r0:r0 + rows, :] = l_scr[r0:r0 + rows, :] * alpha \
        + jnp.sum(p, axis=1, keepdims=True)
    m_scr[r0:r0 + rows, :] = m_new
    return p, alpha


def _grouped_kernel(pt_ref, base_ref, start_ref, len_ref, q_ref, k_ref,
                    v_ref, *rest, scale, ps, n_iter, n_kv, c, group, dk,
                    dv, has_sink, whole):
    """One (slot, table entry) step: a page arrives as ``[ps, n_kv*dk]``
    and ``[ps, n_kv*dv]``, positions on sublanes. Per chunk of ``c`` KV
    heads the banded queries meet the chunk's K columns on the MXU
    (scores ``[c*group, ps]``, positions on lanes), then each KV head's
    probabilities meet its V columns; ``whole``: one chunk of every KV
    head, and the probabilities meet the whole V page
    (:func:`_whole_page`)."""
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

    def scores(qc, kc):
        s = lax.dot_general(qc, kc, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        return s * jnp.float32(scale)

    def context(p, vu):
        return lax.dot_general(p.astype(vu.dtype), vu,
                               (((1,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)

    @pl.when((base < length) & (base + ps > start))
    def _page():
        pos = base + lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        masked = (pos >= length) | (pos < start)                 # [1, ps]
        if whole:
            rows = q_ref.shape[0]
            s = jnp.where(masked, jnp.float32(NEG_INF),
                          scores(q_ref[...], k_ref[...]))    # [rows, ps]
            p, alpha = _softmax_step(s, masked, m_scr, l_scr, 0, rows)
            acc_scr[...] = acc_scr[...] * alpha \
                + context(p, v_ref[...])               # [rows, n_kv * dv]
            return
        rows = c * group
        for ci in range(n_kv // c):
            r0 = ci * rows
            qc = q_ref[r0:r0 + rows, :]                      # [rows, c*dk]
            kc = k_ref[:, ci * c * dk:(ci + 1) * c * dk]     # [ps, c*dk]
            s = jnp.where(masked, jnp.float32(NEG_INF),
                          scores(qc, kc))                    # [rows, ps]
            p, alpha = _softmax_step(s, masked, m_scr, l_scr, r0, rows)
            for u in range(c):
                g = ci * c + u
                a0, p0 = r0 + u * group, u * group
                vu = v_ref[:, g * dv:(g + 1) * dv]           # [ps, dv]
                ctx = context(p[p0:p0 + group, :], vu)       # [group, dv]
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
    """The paged decode kernel (layouts in the module docstring).
    Compiled, it wants :func:`grouped_supported`; interpreted it takes
    any widths (one chunk of all KV heads where none aligns)."""
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
    group = hq // n_kv
    whole = _whole_page(group, dk, dv)
    c = n_kv if whole else _kv_chunk(n_kv, dk, dv) or n_kv
    # the whole-page form: rows padded to sublane tiles (zero queries,
    # dropped with the other heads' columns by _unband), a row as wide
    # as a V page
    rows, width = (_rows(hq), n_kv * dv) if whole else (hq, dv)
    page_base, starts = _context_view(page_table, ps, page_base, starts)
    has_sink = sink is not None

    def slot_map(i, j, pt, base, st, ln):
        return (i, 0, 0)

    def page_map(i, j, pt, base, st, ln):
        return (pt[i, j], 0, 0)

    def padded(x):
        return jnp.pad(x, ((0, 0),) * (x.ndim - 2)
                       + ((0, rows - hq), (0, 0))) if rows != hq else x

    in_specs = [pl.BlockSpec((None, rows, c * dk), slot_map),
                pl.BlockSpec((None, ps, n_kv * dk), page_map),
                pl.BlockSpec((None, ps, n_kv * dv), page_map)]
    operands = [padded(_band_queries(q, n_kv, c)), k_pages, v_pages]
    if has_sink:
        in_specs.append(pl.BlockSpec((rows, 1), lambda i, j, *_: (0, 0)))
        operands.append(padded(sink.astype(jnp.float32).reshape(hq, 1)))
    kern = functools.partial(
        _grouped_kernel, scale=float(sm_scale), ps=ps, n_iter=n, n_kv=n_kv,
        c=c, group=group, dk=dk, dv=dv, has_sink=has_sink, whole=whole)
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b, n),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((None, rows, width), slot_map),
            scratch_shapes=[
                pltpu.VMEM((rows, width), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
                pltpu.VMEM((rows, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, width), q.dtype),
        interpret=interpret,
        name=GROUPED_KERNEL_NAME,
    )(page_table.astype(jnp.int32), page_base, starts,
      lengths.astype(jnp.int32), *operands)
    return _unband(out, hq, n_kv) if whole else out


def grouped_decode_attention_reference(q, k_pages, v_pages, page_table,
                                       lengths, sm_scale, *, n_kv,
                                       page_base=None, starts=None,
                                       sink=None, k_scale=None,
                                       v_scale=None):
    """The jnp form of the kernel (what runs where the kernel cannot: the
    CPU, an unsupported geometry, a GSPMD-partitioned cache, the int8
    tier): gather each slot's pages, mask outside ``[start, length)``,
    exact float32 softmax with the sink in its denominator. Inactive
    slots return 0. ``k_scale``/``v_scale`` (``[pages, n_kv]``) gather
    through the same page table and dequantize at read."""
    b, hq, dk = q.shape
    ps = k_pages.shape[1]
    dv = v_pages.shape[2] // n_kv
    n = page_table.shape[1]
    k = k_pages[page_table].reshape(b, n * ps, n_kv, dk).astype(jnp.float32)
    v = v_pages[page_table].reshape(b, n * ps, n_kv, dv).astype(jnp.float32)
    if k_scale is not None:
        def of_rows(scale):
            # [pages, n_kv] -> [b, n * ps, n_kv, 1]: a page's scale for
            # each of its rows
            return jnp.repeat(scale[page_table].astype(jnp.float32), ps,
                              axis=1)[..., None]

        k, v = k * of_rows(k_scale), v * of_rows(v_scale)
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
    per-call demand (the kernel cannot be demanded of int8 pages), else
    the kernel on a TPU where the geometry is supported, else the jnp
    form."""
    quant = jnp.dtype(dtype) == jnp.int8
    if impl is not None:
        if impl not in ("jnp", "pallas"):
            raise ValueError(f"unknown decode-attention impl {impl!r}")
        if impl == "pallas" and quant:
            raise ValueError(
                "grouped_decode_attention: impl='pallas' cannot be "
                "honored on int8 pages (the int8 KV tier dequantizes at "
                "read in the jnp form)")
        return impl
    if not quant and jax.default_backend() == "tpu" and grouped_supported(
            hq, n_kv, dk, dv, page_size, dtype):
        return "pallas"
    return "jnp"


def grouped_decode_attention(q, k_pages, v_pages, page_table, lengths, *,
                             n_kv, sm_scale=None, page_base=None,
                             starts=None, sink=None, k_scale=None,
                             v_scale=None, impl=None, interpret=None):
    """Dispatched paged decode attention (layouts in the module
    docstring). ``impl`` is a per-call demand ("jnp" | "pallas"; "pallas"
    compiled on an unsupported geometry, or on int8 pages, raises);
    ``interpret`` defaults to True on the CPU platform only.
    ``k_scale``/``v_scale`` come as a pair with int8 pages: codes are
    meaningless without their scales, there is no honorable fallback."""
    hq, dk = q.shape[1:]
    if sm_scale is None:
        sm_scale = 1.0 / math.sqrt(dk)
    if (k_scale is None) != (v_scale is None):
        raise ValueError("grouped_decode_attention: k_scale and v_scale "
                         "come as a pair (one of them is missing)")
    if (k_scale is None) == (k_pages.dtype == jnp.int8):
        raise ValueError(
            "grouped_decode_attention: int8 pages and k_scale/v_scale "
            "come together (quantized codes are meaningless without "
            f"their scales): pages {k_pages.dtype}, scales "
            f"{'given' if k_scale is not None else 'missing'}")
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
        q, k_pages, v_pages, page_table, lengths, sm_scale,
        k_scale=k_scale, v_scale=v_scale, **kw)


# ------------------------------------------------ latent pages (one row a
# token, no head axis)
#
# Latent attention (serving/axk1.py) keeps ONE row a token a layer:
# ``rank`` columns of normalised compressed KV, then the rotated key
# dims that every head shares. In the absorbed decode form every query
# head meets that same row: its score is ``q . row`` over the whole
# width, and the value it sums is the row's first ``rank`` columns. So
# a page is ONE operand, fetched once, and serves as K and as V:
#
#   q            [b, hq, width]     ``q_nope W_uk^T`` ‖ rotated ``q_pe``
#   latent_pages [pages, page_size, width]   one layer (kv_cache.py)
#   out          [b, hq, rank]      ``sum p c_kv``, before ``W_uv``
#
# The page walk (scalar-prefetched table, bases and lengths; an entry at
# or past ``length`` skipped) and the online softmax step are the
# grouped kernel's own. A pool keeps every token and gives no ``starts``;
# a latent RING (kv_cache.init_latent_ring: window layers whose state is
# a latent row) gives the first visible position of each slot, and the
# kernel then carries it as a fourth scalar operand.

LATENT_KERNEL_NAME = "latent_decode_attention"


def latent_supported(hq, width, rank, page_size, dtype=None):
    """Whether Mosaic takes the latent kernel: query heads in whole
    sublane tiles, a row of whole lane tiles whose value columns start
    at column 0, a bfloat16 or float32 page (double-buffered) with the
    accumulators inside VMEM."""
    if dtype is not None and jnp.dtype(dtype) not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    itembytes = tiles.itemsize(dtype) if dtype is not None else 4
    return (hq % 8 == 0 and page_size % 8 == 0 and width % 128 == 0
            and rank % 8 == 0 and rank <= width
            and 2 * page_size * width * itembytes + 16 * hq * rank
            <= 8 * 2 ** 20)


def _latent_kernel(pt_ref, base_ref, *rest, scale, ps, n_iter, rank,
                   has_start=False):
    """One (slot, table entry) step: the page ``[ps, width]`` meets every
    head's query whole (scores ``[hq, ps]``), and its first ``rank``
    columns are what the probabilities sum."""
    if has_start:
        start_ref, len_ref, q_ref, kv_ref, o_ref, acc_scr, m_scr, l_scr = rest
    else:
        len_ref, q_ref, kv_ref, o_ref, acc_scr, m_scr, l_scr = rest
    i, j = pl.program_id(0), pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, jnp.float32(NEG_INF))
        l_scr[...] = jnp.zeros_like(l_scr)

    base, length = base_ref[i, j], len_ref[i]
    live = base < length
    if has_start:
        start = start_ref[i]
        live = live & (base + ps > start)

    @pl.when(live)
    def _page():
        pos = base + lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        masked = pos >= length                                   # [1, ps]
        if has_start:
            masked = masked | (pos < start)
        s = lax.dot_general(q_ref[...], kv_ref[...],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.where(masked, jnp.float32(NEG_INF), s * jnp.float32(scale))
        p, alpha = _softmax_step(s, masked, m_scr, l_scr, 0, s.shape[0])
        value = kv_ref[:, :rank]                              # [ps, rank]
        acc_scr[...] = acc_scr[...] * alpha + lax.dot_general(
            p.astype(value.dtype), value, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == n_iter - 1)
    def _finish():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / jnp.where(l > 0, l, 1.0)).astype(
            o_ref.dtype)


def latent_decode_attention_pallas(q, latent_pages, page_table, lengths,
                                   sm_scale, *, rank, page_base=None,
                                   starts=None, interpret=False):
    """The latent decode kernel (layouts above)."""
    b, hq, width = q.shape
    ps = latent_pages.shape[1]
    n = page_table.shape[1]
    if latent_pages.shape[2] != width or rank > width:
        raise ValueError(
            f"latent_decode_attention: q {q.shape} and latent pages "
            f"{latent_pages.shape} do not share a row of {rank} value "
            f"columns")
    if not interpret and not latent_supported(hq, width, rank, ps,
                                              latent_pages.dtype):
        raise ValueError(
            f"latent_decode_attention_pallas: unsupported geometry "
            f"hq={hq} width={width} rank={rank} ps={ps} "
            f"{latent_pages.dtype}")
    page_base, _ = _context_view(page_table, ps, page_base)
    scalars = [page_table.astype(jnp.int32), page_base,
               lengths.astype(jnp.int32)]
    kw = {}
    if starts is not None:
        scalars.insert(2, starts.astype(jnp.int32))
        kw["has_start"] = True
    kern = functools.partial(_latent_kernel, scale=float(sm_scale), ps=ps,
                             n_iter=n, rank=rank, **kw)
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(b, n),
            in_specs=[
                pl.BlockSpec((None, hq, width),
                             lambda i, j, *_: (i, 0, 0)),
                pl.BlockSpec((None, ps, width),
                             lambda i, j, pt, *_: (pt[i, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, hq, rank),
                                   lambda i, j, *_: (i, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((hq, rank), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
                pltpu.VMEM((hq, 1), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, hq, rank), q.dtype),
        interpret=interpret,
        name=LATENT_KERNEL_NAME,
    )(*scalars, q, latent_pages)


def latent_decode_attention_reference(q, latent_pages, page_table, lengths,
                                      sm_scale, *, rank, page_base=None,
                                      starts=None):
    """The jnp form of the latent kernel (the CPU, an unsupported
    geometry or page dtype): gather each slot's pages, mask at and past
    ``length`` (and before ``starts``), exact float32 softmax. Inactive
    slots return 0."""
    b, hq, width = q.shape
    ps = latent_pages.shape[1]
    rows = latent_pages[page_table].reshape(b, -1, width).astype(
        jnp.float32)                                   # [b, n * ps, width]
    masked = _outside_context(page_table, ps, lengths, page_base,
                              starts)[:, None, :]               # [b, 1, S]
    s = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows,
                   precision=lax.Precision.HIGHEST) * jnp.float32(sm_scale)
    out = jnp.einsum("bhs,bsr->bhr", masked_softmax(s, masked, None),
                     rows[..., :rank], precision=lax.Precision.HIGHEST)
    return out.astype(q.dtype)


def latent_resolved(hq, width, rank, page_size, dtype, impl=None):
    """The impl a :func:`latent_decode_attention` call runs with: a
    per-call demand, else the kernel on a TPU where the geometry and the
    pages' dtype are supported, else the jnp form."""
    if impl is not None:
        if impl not in ("jnp", "pallas"):
            raise ValueError(f"unknown decode-attention impl {impl!r}")
        return impl
    if jax.default_backend() == "tpu" and latent_supported(
            hq, width, rank, page_size, dtype):
        return "pallas"
    return "jnp"


def latent_decode_attention(q, latent_pages, page_table, lengths, *, rank,
                            sm_scale, page_base=None, starts=None,
                            impl=None, interpret=None):
    """Dispatched decode attention over latent pages (layouts above;
    ``starts [b]``: the first visible position of a slot, for a ring).
    ``impl`` is a per-call demand ("jnp" | "pallas"; "pallas" compiled on
    an unsupported geometry raises); ``interpret`` defaults to True on
    the CPU platform only."""
    hq, width = q.shape[1:]
    kw = dict(rank=rank, page_base=page_base)
    if starts is not None:
        kw["starts"] = starts
    if latent_resolved(hq, width, rank, latent_pages.shape[1],
                       latent_pages.dtype, impl) == "pallas":
        if interpret is None:
            interpret = jax.devices()[0].platform == "cpu"
        return latent_decode_attention_pallas(
            q, latent_pages, page_table, lengths, sm_scale,
            interpret=interpret, **kw)
    return latent_decode_attention_reference(
        q, latent_pages, page_table, lengths, sm_scale, **kw)


# -------------------------------------------- the indexer's scores (decode)
#
# A layer that SELECTS the rows it attends to (serving/dots3.py) keeps,
# beside each latent page, the indexer's key of every token: one
# ``index_width``-wide row. A decode step scores every live row of a slot
# against the token's index queries, one weighted sum of rectified head
# scores a row:
#
#   q_idx        [b, hi, di]        the token's index queries
#   w            [b, hi]   float32  its head weights (scale folded in)
#   index_pages  [pages, page_size, di]   one layer (kv_cache.py)
#   out          [b, n * page_size] float32:  ``sum_h w[h] relu(q_idx[h]
#                . key)`` of the row at each table entry, ``NEG_INF`` at
#                and past ``length``
#
# The walk is the latent kernel's: step ``(i, j)`` DMAs page
# ``page_table[i, j]`` and writes that entry's ``page_size`` scores.

INDEX_KERNEL_NAME = "index_decode_scores"


def index_supported(hi, di, page_size, dtype=None):
    """Whether Mosaic takes the index kernel: whole sublane tiles of
    heads, a key of whole lane tiles, pages of whole lane tiles of rows
    (a page's scores are one output row), a bfloat16 or float32 page."""
    if dtype is not None and jnp.dtype(dtype) not in (
            jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32)):
        return False
    return hi % 8 == 0 and di % 128 == 0 and page_size % 128 == 0


def _index_kernel(pt_ref, base_ref, len_ref, q_ref, w_ref, k_ref, o_ref, *,
                  ps):
    i, j = pl.program_id(0), pl.program_id(1)
    base, length = base_ref[i, j], len_ref[i]

    @pl.when(base >= length)
    def _skip():
        o_ref[...] = jnp.full_like(o_ref, jnp.float32(NEG_INF))

    @pl.when(base < length)
    def _page():
        s = lax.dot_general(q_ref[...], k_ref[...],
                            (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # [hi, ps]
        s = jnp.sum(jnp.maximum(s, 0.0) * w_ref[...], axis=0,
                    keepdims=True)                               # [1, ps]
        pos = base + lax.broadcasted_iota(jnp.int32, (1, ps), 1)
        o_ref[...] = jnp.where(pos >= length, jnp.float32(NEG_INF), s)


def index_decode_scores_pallas(q_idx, w, index_pages, page_table, lengths,
                               *, page_base=None, interpret=False):
    """The index kernel (layouts above)."""
    b, hi, di = q_idx.shape
    ps = index_pages.shape[1]
    n = page_table.shape[1]
    if not interpret and not index_supported(hi, di, ps, index_pages.dtype):
        raise ValueError(
            f"index_decode_scores_pallas: unsupported geometry hi={hi} "
            f"di={di} ps={ps} {index_pages.dtype}")
    if index_pages.shape[2] != di:
        raise ValueError(f"index_decode_scores: q {q_idx.shape} and index "
                         f"pages {index_pages.shape} differ in width")
    page_base, _ = _context_view(page_table, ps, page_base)
    out = pl.pallas_call(
        functools.partial(_index_kernel, ps=ps),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, n),
            in_specs=[
                pl.BlockSpec((None, hi, di), lambda i, j, *_: (i, 0, 0)),
                pl.BlockSpec((None, hi, 1), lambda i, j, *_: (i, 0, 0)),
                pl.BlockSpec((None, ps, di),
                             lambda i, j, pt, *_: (pt[i, j], 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, None, 1, ps),
                                   lambda i, j, *_: (i, j, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((b, n, 1, ps), jnp.float32),
        interpret=interpret,
        name=INDEX_KERNEL_NAME,
    )(page_table.astype(jnp.int32), page_base, lengths.astype(jnp.int32),
      q_idx, w.astype(jnp.float32)[:, :, None], index_pages)
    return out.reshape(b, n * ps)


def index_decode_scores_reference(q_idx, w, index_pages, page_table,
                                  lengths, *, page_base=None):
    """The jnp form of the index kernel: gather each slot's pages, score
    in float32, ``NEG_INF`` at and past ``length``."""
    b = q_idx.shape[0]
    ps = index_pages.shape[1]
    keys = index_pages[page_table].reshape(b, -1, index_pages.shape[2])
    s = jnp.einsum("bhd,bsd->bhs", q_idx, keys.astype(q_idx.dtype),
                   preferred_element_type=jnp.float32)
    s = jnp.sum(jnp.maximum(s, 0.0) * w.astype(jnp.float32)[:, :, None],
                axis=1)
    return jnp.where(_outside_context(page_table, ps, lengths, page_base),
                     jnp.float32(NEG_INF), s)


def index_resolved(hi, di, page_size, dtype, impl=None):
    """The impl an :func:`index_decode_scores` call runs with (the rule
    of :func:`latent_resolved`)."""
    if impl is not None:
        if impl not in ("jnp", "pallas"):
            raise ValueError(f"unknown decode-attention impl {impl!r}")
        return impl
    if jax.default_backend() == "tpu" and index_supported(
            hi, di, page_size, dtype):
        return "pallas"
    return "jnp"


def index_decode_scores(q_idx, w, index_pages, page_table, lengths, *,
                        page_base=None, impl=None, interpret=None):
    """Dispatched index scores over a slot's pages (layouts above)."""
    hi, di = q_idx.shape[1:]
    if index_resolved(hi, di, index_pages.shape[1], index_pages.dtype,
                      impl) == "pallas":
        if interpret is None:
            interpret = jax.devices()[0].platform == "cpu"
        return index_decode_scores_pallas(
            q_idx, w, index_pages, page_table, lengths,
            page_base=page_base, interpret=interpret)
    return index_decode_scores_reference(
        q_idx, w, index_pages, page_table, lengths, page_base=page_base)
