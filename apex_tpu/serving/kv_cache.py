"""Paged KV cache: block-granular allocation as index arithmetic.

Device side: per layer, K and V live as ``[num_pages, page_size,
kv_heads * width]`` arrays, one leaf a layer: the page axis is a plain
array axis, so "allocating" a page to a sequence is writing its index
into that sequence's page-table row and "freeing" it is forgetting the
index. No reshape, no growing array, no recompile: the decode step's
operand shapes are fixed for the life of the engine, whatever the
scheduler does between steps (the ISSUE 10 jaxpr-stability contract,
asserted by tests/test_serving.py).

(head, width) is the minor axis: a token's K or V of every head is one
row, 1280 lanes at GPT-2 large, 768 / 512 at MiMo's global layers, whole
lane tiles under ``page_size`` rows of whole sublane tiles. Nothing is
padded (a latent row apart: :func:`init_latent_cache`); a step scatters
``[tokens, kv_heads * width]`` rows in place;
the decode kernel (ops/decode_attention_pallas.py) DMAs a page where it
lies; no program copies a cache (PERF.md §6, PR 27 and PR 28: with
heads leading pages and 64 columns a row XLA re-laid the whole cache,
padded 2.4x, at the entry and exit of both programs: 24 of a 36 ms
decode round). Every leaf carries its page axis FIRST, the int8 tier's
``[num_pages, kv_heads]`` scales too, so the engine's page hops and the
host swap tier treat every leaf alike, and tensor-parallel serving
shards the LAST axis (heads are contiguous blocks of it).

Host side: :class:`PageAllocator` — an explicit free list over pages
``1..num_pages-1``. Page 0 is RESERVED as the null page: padded
page-table tails and padded prefill tokens point at it, so a garbage
index can never alias a live sequence's data (the kernel skips those
positions by context length; the null page absorbs the writes).
"""

import jax.numpy as jnp


def init_cache(num_layers, num_heads, num_pages, page_size, head_dim,
               dtype=jnp.bfloat16, kv_quant=False):
    """Zeroed cache dict ``{"k", "v"}`` of a model whose layers all keep
    every token under ``num_heads`` KV heads ``head_dim`` wide (the GPT-2
    family): each a list with one ``[num_pages, page_size, num_heads *
    head_dim]`` array a layer, the pool of :func:`init_hybrid_cache`.

    ``kv_quant=True`` (the int8 KV tier, ISSUE 20) stores the code
    arrays as int8 and adds per-(page, head) bf16 scale leaves
    ``{"k_scale", "v_scale"}``, ``[num_pages, num_heads]`` a layer. Zero
    scales make the all-zero init exact: a zero scale dequantizes (and
    quantizes) to exact zeros, which is also what pins null page 0 dead
    through the codec."""
    if kv_quant:
        from apex_tpu.serving import kv_tier

        dtype = kv_tier.CODE_DTYPE
    # every layer global: no slot owns a ring, no window kind
    pool = init_hybrid_cache(
        (0,) * num_layers, num_pages, 0, page_size, 0,
        (num_heads, head_dim, head_dim), None, dtype)
    cache = {"k": pool["global_k"], "v": pool["global_v"]}
    if kv_quant:
        cache.update(kv_tier.init_scales(num_layers, num_heads, num_pages))
    return cache


def latent_row_width(width):
    """Columns of a latent page's row: ``width`` padded to whole lane
    tiles of 128 (576 -> 640)."""
    return -(-int(width) // 128) * 128


def init_latent_cache(num_layers, num_pages, page_size, width,
                      dtype=jnp.bfloat16, index_width=None):
    """Zeroed cache ``{"latent"}`` of a model with latent attention
    (serving/axk1.py): a third kind of state, ONE row a token a layer
    with no head axis, ``width`` live columns (the normalised compressed
    KV, then the rotated key dims every head shares) that serve as K and
    as V. One ``[num_pages, page_size, latent_row_width(width)]`` leaf a
    layer, pages of the same pool, allocator and page table as
    :func:`init_cache`'s.

    The row is padded to whole lane tiles with zero columns that stay
    zero (:func:`write_latent_rows`). Unpadded, the TPU
    keeps a ``[pages, 128, 576]`` array position-minor (576 is 4.5 lane
    tiles, 128 is one) and every row scatter and every kernel call then
    copies the whole leaf to row-major and back (two 207 MB copies a
    layer a program at the cell's size: the compile-only verdict of
    tests/test_decode_attention_mosaic.py; PERF.md §6, PR 31).

    ``index_width`` (serving/dots3.py: layers that SELECT the rows they
    attend to) adds ``{"index"}``: beside each latent leaf the indexer's
    key of every token, ``[num_pages, page_size,
    latent_row_width(index_width)]``, on the same page ids."""
    def leaves(width):
        return [jnp.zeros((num_pages, page_size, latent_row_width(width)),
                          dtype) for _ in range(num_layers)]

    cache = {"latent": leaves(width)}
    if index_width is not None:
        cache["index"] = leaves(index_width)
    return cache


def init_latent_ring(num_layers, num_slots, page_size, window, width,
                     dtype=jnp.bfloat16):
    """Zeroed ``{"ring"}``: the state of WINDOW layers whose attention is
    latent (serving/dots3.py). A latent row a token a layer as
    :func:`init_latent_cache`'s, kept in a ring of :func:`ring_pages`
    pages a slot as :func:`init_hybrid_cache`'s window kind is: ``[1 +
    num_slots * ring, page_size, latent_row_width(width)]`` a layer,
    written at :func:`ring_write`, read through :func:`ring_table` /
    :func:`ring_view`."""
    pages = 1 + num_slots * ring_pages(window, page_size)
    return {"ring": [
        jnp.zeros((pages, page_size, latent_row_width(width)), dtype)
        for _ in range(num_layers)]}


def write_rows(leaf, page, off, rows):
    """``leaf [pages, page_size, heads * width]`` with ``rows [T, heads,
    width]`` scattered, one ``[heads * width]`` row a token, at
    ``(page[t], off[t])``: how every program of both families writes K
    and V (index arithmetic only; in place under donation)."""
    return leaf.at[page, off, :].set(
        rows.reshape(rows.shape[0], -1).astype(leaf.dtype))


def write_latent_rows(leaf, page, off, rows):
    """:func:`write_rows` for a latent leaf: ``rows [T, width]`` padded
    with zero columns to the leaf's whole lane tiles."""
    return write_rows(leaf, page, off, jnp.pad(
        rows, ((0, 0), (0, leaf.shape[2] - rows.shape[1]))))


def pool_view(page_table, positions, lengths, page_size):
    """What a decode kernel walks of the paged pool: ``(table [b, n],
    page_base [b, n])``. Past a slot's last page (the one holding
    ``positions[i]``) the table repeats it: an unchanged block index is
    not fetched again, and its base (``lengths[i]``) says "skip"."""
    last = positions // page_size
    j = jnp.arange(page_table.shape[1], dtype=jnp.int32)[None, :]
    table = jnp.take_along_axis(
        page_table, jnp.minimum(j, last[:, None]), axis=1)
    base = jnp.where(j <= last[:, None], j * page_size, lengths[:, None])
    return table, base


def pages_needed(tokens, page_size):
    """Pages to hold ``tokens`` positions at this page size."""
    return -(-int(tokens) // int(page_size))


class PageAllocator:
    """Explicit-free-list page allocator (host-side, stdlib-only).

    Pages ``1..num_pages-1`` are allocatable; page 0 is the reserved
    null page (module docstring). Allocation is all-or-nothing per
    request: :meth:`alloc` returns the page list or None when the free
    list is short — the scheduler then leaves the request queued
    (admission control, never a partial grant).
    """

    def __init__(self, num_pages):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is reserved)")
        self.num_pages = int(num_pages)
        # LIFO free list: recently freed pages are re-used first (their
        # cache lines are the warmest)
        self._free = list(range(1, self.num_pages))
        self._owned = {}  # owner id -> list of page indices

    @property
    def free_count(self):
        return len(self._free)

    def live_pages(self, owner=None):
        if owner is not None:
            return list(self._owned.get(owner, ()))
        return [p for pages in self._owned.values() for p in pages]

    def alloc(self, owner, n):
        """Allocate ``n`` pages to ``owner`` (appending to any it
        already holds); returns the new page list or None when the
        free list cannot cover the request (state unchanged)."""
        n = int(n)
        if n == 0:
            return []  # no phantom empty ownership entry either
        if len(self._free) < n:
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._owned.setdefault(owner, []).extend(pages)
        return pages

    def free(self, owner):
        """Return all of ``owner``'s pages to the free list."""
        for p in self._owned.pop(owner, ()):
            self._free.append(p)

    def transfer(self, owner_from, owner_to, pages):
        """Move specific ``pages`` between owners — the prefix-cache
        adoption hop (ISSUE 13: a registering request's prompt pages
        become cache-owned without round-tripping the free list, so
        their K/V content is never up for reallocation mid-transfer).
        Accounting only; the live set is unchanged. Raises when
        ``owner_from`` does not own every page (state unchanged)."""
        have = self._owned.get(owner_from, [])
        missing = [p for p in pages if p not in have]
        if missing:
            raise ValueError(
                f"pages {missing} are not owned by {owner_from!r}")
        for p in pages:
            have.remove(p)
            self._owned.setdefault(owner_to, []).append(p)
        if not have:
            self._owned.pop(owner_from, None)

    def check_invariants(self):
        """Raise AssertionError on aliasing or accounting drift — the
        test surface for the paged-allocator invariants (ISSUE 10):
        no page owned twice, no page both free and owned, page 0 never
        handed out, free + live == allocatable."""
        live = self.live_pages()
        assert len(live) == len(set(live)), (
            f"page aliasing across live owners: {sorted(live)}")
        assert 0 not in live and 0 not in self._free, (
            "null page 0 escaped the reservation")
        overlap = set(live) & set(self._free)
        assert not overlap, f"pages both free and owned: {overlap}"
        assert len(live) + len(self._free) == self.num_pages - 1, (
            f"accounting drift: {len(live)} live + "
            f"{len(self._free)} free != {self.num_pages - 1}")


# --------------------------------------------- two kinds of state, one cache
#
# A model whose layers alternate between GLOBAL attention (every token
# kept) and WINDOW attention (only the last ``window`` tokens ever read)
# holds two kinds of KV state side by side (serving/mimo.py):
#
# * global layers: the paged pool above, one K and one V array a layer of
#   ``[num_pages, page_size, kv_heads * width]`` ((head, width) minor:
#   whole lane tiles at 4 x 192, 4 x 128, so nothing is padded), pages
#   handed out by :class:`PageAllocator` through the scheduler's page
#   table; admission reserves THESE pages only.
# * window layers: a RING of :func:`ring_pages` pages a slot, owned by
#   the slot for the life of the engine: position ``t`` lives in ring page
#   ``(t // page_size) % ring`` at row ``t % page_size``. Never allocated,
#   never freed; a new request overwrites its slot's ring as it goes, and
#   what it has not yet overwritten lies outside ``[start, length)`` and
#   is masked. ``[1 + num_slots * ring, page_size, kv_heads * width]`` a
#   layer; page 0 stays the null page here too.

def ring_pages(window, page_size):
    """Pages of a slot's ring: the window may straddle one page edge
    more than its own length in pages."""
    return -(-int(window) // int(page_size)) + 1


def init_hybrid_cache(layer_kinds, num_pages, num_slots, page_size, window,
                      global_kv, window_kv, dtype=jnp.bfloat16):
    """Zeroed two-kind cache. ``layer_kinds``: one 0 (global) or 1
    (window) a layer; ``global_kv`` / ``window_kv``: ``(kv_heads, k_width,
    v_width)`` of each kind. Returns ``{"global_k", "global_v",
    "window_k", "window_v"}``, each a list with one array a layer of its
    kind, in layer order."""
    ring = ring_pages(window, page_size)
    cache = {"global_k": [], "global_v": [], "window_k": [], "window_v": []}
    for kind in layer_kinds:
        name, pages, (h, dk, dv) = (
            ("window", 1 + num_slots * ring, window_kv) if kind
            else ("global", num_pages, global_kv))
        cache[name + "_k"].append(
            jnp.zeros((pages, page_size, h * dk), dtype))
        cache[name + "_v"].append(
            jnp.zeros((pages, page_size, h * dv), dtype))
    return cache


def ring_table(num_slots, ring):
    """``[num_slots, ring]`` int32: slot ``s`` owns pages ``1 + s * ring
    .. 1 + s * ring + ring - 1`` of every window layer."""
    return 1 + (jnp.arange(num_slots, dtype=jnp.int32)[:, None] * ring
                + jnp.arange(ring, dtype=jnp.int32)[None, :])


def ring_write(slots, positions, keep, ring, page_size):
    """``(page, row)`` at which position ``positions[i]`` of slot
    ``slots[i]`` is written; where ``keep`` is false the write goes to
    the null page."""
    page = 1 + slots * ring + (positions // page_size) % ring
    return jnp.where(keep, page, 0), jnp.where(keep, positions % page_size, 0)


def ring_view(lengths, ring, page_size, window):
    """What a slot of context ``lengths[i]`` reads from its ring:
    ``(page_base [b, ring], starts [b])``. Ring page ``r`` holds the
    newest logical page ``L <= last`` with ``L % ring == r``, so its row
    0 is position ``L * page_size`` (negative before the ring has filled:
    wholly below ``start``, never read); ``starts`` is the first position
    inside the window."""
    last = jnp.maximum(lengths - 1, 0) // page_size
    r = jnp.arange(ring, dtype=jnp.int32)[None, :]
    logical = last[:, None] - (last[:, None] - r) % ring
    return (logical * page_size).astype(jnp.int32), \
        jnp.maximum(lengths - window, 0).astype(jnp.int32)
