"""KV-cache memory hierarchy: int8 KV quantization + host swap tier.

The paged KV cache is the serving batch ceiling — every "at scale"
lever (continuous batching, multi-token blocks, TP sharding) runs out
of road when paged KV fills HBM. This module is the two-layer answer
(ROADMAP item 5, ISSUE 20), both layers default OFF per the
measured-dispatch rule:

* **int8 KV quantization** (``APEX_SERVE_KV_QUANT`` /
  ``ServingEngine(kv_quant=)``): the paged cache stores int8 K/V with
  per-(page, head) bf16 scales — ≈2x effective pages per chip, which
  raises the preemption threshold and the batch ceiling directly.
  Prefill's in-program page scatter quantizes at write
  (:func:`prefill_scatter_quant`); the decode step re-quantizes the
  single written page read-modify-write (:func:`decode_scatter_quant`);
  decode attention dequantizes at read (the jnp form of
  ops/decode_attention_pallas.py, which the tier's pages take: their
  scales inside the kernel are not written yet). Null page 0 stays
  all-zero through the codec: its scale is pinned to 0, and quantizing
  under a zero scale emits int8 zeros (:func:`inv_scale`). Non-finite
  inputs are poisoned to 0 before the amax (the PR 8 block-quant
  NaN-flush precedent — one NaN must not zero a whole page's scale
  arithmetic).

* **host swap tier** (``APEX_SERVE_KV_SWAP`` / ``engine(kv_swap=)``):
  on KV-pressure preemption the victim's live pages copy
  device→host between dispatches (the DurableCheckpointer staging
  precedent; quantized pages swap in their int8+scale wire format, so
  the quant layer halves swap bytes too) into a :class:`SwappedPages`
  handle stashed next to ``resume_tokens``; re-admission copies the
  pages back into freshly granted device pages and resumes decode
  directly, skipping replay prefill. Whether a resumed stream
  restores by swap-in or by recompute is a per-prompt-length
  dispatch decision (:func:`resolve_kv_restore`, op ``kv_restore``):
  the crossover against the ~65 ms relay dispatch floor is
  shape-dependent, never a constant.

Knob asymmetry (CLAUDE.md): the per-call engine knobs are demands
(``kv_swap=True`` with preemption resolved off raises in the engine
ctor; ``kv_restore="swap"`` with the host tier off raises here); the
env knobs are preferences that fall back per shape. This module is
jax-backed (the codec runs inside the jitted prefill/decode
programs) — the stdlib-only scheduler only ever holds the opaque
:class:`SwappedPages` handle it is handed.
"""

import dataclasses
import hashlib
from typing import Any, Dict, Optional

import jax.numpy as jnp
import numpy as np

from apex_tpu import dispatch as _dispatch
from apex_tpu.dispatch import tiles as _tiles

# wire format of the quantized tier: int8 codes + per-(page, head)
# bf16 scales. bf16 is enough for a scale (it is an amax/127, consumed
# in fp32), and it halves the scale arrays' HBM + swap bytes.
CODE_DTYPE = jnp.int8
SCALE_DTYPE = jnp.bfloat16
QMAX = 127.0

SCALE_KEYS = ("k_scale", "v_scale")
RESTORE_CHOICES = ("recompute", "swap")


# ---------------------------------------------------------------------------
# knob resolution (engine per-call args are validated by the ENGINE —
# these resolvers own the env-preference legs)
# ---------------------------------------------------------------------------


def resolve_kv_quant(per_call=None):
    """The effective int8-KV decision: per-call (the engine's
    ``kv_quant=`` demand) > ``APEX_SERVE_KV_QUANT`` env preference
    (tiles.env_choice: unknown values warn once and are ignored) >
    built-in OFF (measured-dispatch rule — the 2x-pages argument is an
    expectation until the PERF.md §2 serving_kv_quant A/B commits)."""
    if per_call is not None:
        return bool(per_call)
    v = _tiles.env_choice("APEX_SERVE_KV_QUANT", ("1", "0"))
    if v is not None:
        return v == "1"
    return False


def resolve_kv_swap(per_call=None):
    """The effective host-swap-tier decision: per-call demand >
    ``APEX_SERVE_KV_SWAP`` env preference > built-in OFF. The
    preemption pairing (swap without preemption is dead weight) is the
    ENGINE ctor's job — it sees whether each side was a demand."""
    if per_call is not None:
        return bool(per_call)
    v = _tiles.env_choice("APEX_SERVE_KV_SWAP", ("1", "0"))
    if v is not None:
        return v == "1"
    return False


def resolve_kv_restore(per_call=None, *, swap_enabled, tokens, dtype,
                       backend=None):
    """The restore path for ONE resumed stream of ``tokens`` known
    tokens: per-call demand (raises when un-honorable — ``"swap"``
    demanded with the host tier off has no honorable answer) >
    ``APEX_SERVE_KV_RESTORE`` env preference > ``kv_restore``
    dispatch-table entry at bucket ``s=tokens`` (the committed
    per-prompt-length crossover) > built-in ``"swap"`` (with the tier
    ON, using the banked pages is the capability the knob bought;
    the table refines the shape-dependent crossover). With the tier
    off every preference falls back to ``"recompute"`` — the
    replay-prefill path preemption always had."""
    if per_call is not None:
        if per_call not in RESTORE_CHOICES:
            raise ValueError(
                f"unknown kv_restore {per_call!r} "
                f"(vocabulary: {RESTORE_CHOICES})")
        if per_call == "swap" and not swap_enabled:
            raise ValueError(
                "kv_restore='swap' demanded but the host swap tier is "
                "off (enable kv_swap=/APEX_SERVE_KV_SWAP=1) — no "
                "honorable way to restore from pages that were never "
                "banked")
        return per_call
    if not swap_enabled:
        return "recompute"
    v = _tiles.env_choice("APEX_SERVE_KV_RESTORE", RESTORE_CHOICES)
    if v is not None:
        return v
    choice = _dispatch.lookup("kv_restore", dtype, backend=backend,
                              s=max(1, int(tokens)))
    if choice is not None:
        return choice
    return "swap"


# ---------------------------------------------------------------------------
# the int8 codec (pure jnp — runs inside the jitted programs)
# ---------------------------------------------------------------------------


def is_quantized(cache):
    """Whether a cache dict carries the int8 tier's scale leaves."""
    return "k_scale" in cache


def finite(x):
    """Non-finite poisoning (the PR 8 NaN-flush precedent): NaN/Inf
    inputs become 0 BEFORE any amax, so one poisoned activation can
    neither NaN a page scale nor saturate it to Inf."""
    return jnp.where(jnp.isfinite(x), x, jnp.zeros_like(x))


def inv_scale(scale):
    """Guarded fp32 reciprocal of a scale array: 0 where the scale is
    0 (the null page / an all-zero page), so quantizing under a dead
    scale emits exact int8 zeros instead of NaN codes."""
    s = scale.astype(jnp.float32)
    return jnp.where(s > 0, 1.0 / jnp.where(s > 0, s, 1.0),
                     jnp.zeros_like(s))


def _by_head(x, heads):
    """``[..., rows, heads * width]`` seen as ``[..., rows, heads,
    width]``: the cache row with its head axis apart, so that a
    per-(page, head) scale ``[..., heads]`` broadcasts as
    ``scale[..., None, :, None]``."""
    return x.reshape(*x.shape[:-1], heads, x.shape[-1] // heads)


def quantize(x, scale):
    """int8 codes of ``x [..., page_size, heads * width]`` under
    per-(leading dims, head) ``scale [..., heads]``."""
    inv = inv_scale(scale)[..., None, :, None]
    q = jnp.round(_by_head(finite(x).astype(jnp.float32),
                           scale.shape[-1]) * inv)
    return jnp.clip(q, -QMAX, QMAX).astype(CODE_DTYPE).reshape(x.shape)


def dequantize(q, scale, dtype=jnp.float32):
    """Inverse of :func:`quantize` (same shapes)."""
    return (_by_head(q.astype(jnp.float32), scale.shape[-1])
            * scale.astype(jnp.float32)[..., None, :, None]
            ).astype(dtype).reshape(q.shape)


def init_scales(num_layers, num_heads, num_pages):
    """Zeroed per-(page, head) scale leaves ``{"k_scale", "v_scale"}``,
    each a list of one ``[num_pages, num_heads]`` array a layer: the
    page axis first like the code arrays', so the engine's
    page-copy/gather/scatter helpers treat every cache leaf alike, and
    the head axis last, where the TP ``cache_shardings`` split covers
    the scales too."""
    return {k: [jnp.zeros((num_pages, num_heads), SCALE_DTYPE)
                for _ in range(num_layers)] for k in SCALE_KEYS}


def _with_layer(cache, layer, part, codes, scales):
    """``cache`` with one layer's codes and scales of ``part`` replaced
    (a new dict and new lists: the caller's are left as they were)."""
    out = dict(cache)
    for name, leaf in ((part, codes), (part + "_scale", scales)):
        out[name] = list(cache[name])
        out[name][layer] = leaf
    return out


def prefill_scatter_quant(cache, layer, part, val, dest_page, dest_off,
                          keep_scale):
    """Quantize-at-write page scatter for the packed prefill program
    (the quant-tier replacement of the plain
    ``cache[part][layer].at[dest_page, dest_off, :].set(...)``).

    ``val`` is the layer's fresh K or V rows ``[s, h, d]``;
    ``dest_page``/``dest_off`` the packed rows' page/offset ``[s]``;
    ``keep_scale`` ``[num_pages]`` is 1 for pages whose existing
    content (and scale) is still live — a verify pass re-covering a
    partially filled page — and 0 for pages freshly granted to this
    prefill, whose stale codes and scale are dead. Functional
    recipe (no data-dependent shapes, so the one-compile contract
    holds): scatter-max the fresh rows' amax into a per-(page, head)
    scale floor, grow each destination page's surviving scale to
    cover it, re-quantize the whole layer under the grown scales
    (ratio 1 for untouched pages — bit-identical codes; ratio 0 for
    fresh pages and the null page — stale garbage zeroed), then
    quantize and scatter the fresh rows. Page 0's scale is pinned to
    0, so padded rows (which the packer routes to page 0) quantize to
    exact zeros — the null page stays all-zero through the codec."""
    q = cache[part][layer]               # [P, ps, h*d] int8
    sc = cache[part + "_scale"][layer]   # [P, h] bf16
    num_pages, h = sc.shape
    vf = finite(val.astype(jnp.float32))                 # [s, h, d]
    row_amax = jnp.max(jnp.abs(vf), axis=-1)             # [s, h]
    amax_pages = jnp.zeros((num_pages, h), jnp.float32)
    amax_pages = amax_pages.at[dest_page].max(row_amax)
    old = sc.astype(jnp.float32) * keep_scale[:, None]
    new_scale = jnp.maximum(old, amax_pages / QMAX)
    new_scale = new_scale.at[0].set(0.0)                 # null page pin
    ratio = jnp.where(new_scale > 0,
                      old / jnp.where(new_scale > 0, new_scale, 1.0),
                      jnp.zeros_like(new_scale))
    requant = jnp.clip(jnp.round(_by_head(q.astype(jnp.float32), h)
                                 * ratio[:, None, :, None]),
                       -QMAX, QMAX)                      # [P, ps, h, d]
    rows = jnp.round(vf * inv_scale(new_scale[dest_page])[:, :, None])
    rows = jnp.clip(rows, -QMAX, QMAX)                   # [s, h, d]
    updated = requant.at[dest_page, dest_off].set(rows)
    return _with_layer(cache, layer, part,
                       updated.astype(CODE_DTYPE).reshape(q.shape),
                       new_scale.astype(SCALE_DTYPE))


def decode_scatter_quant(cache, layer, part, val, write_page, write_off):
    """Quantize-at-write for the decode step's single-row scatter: a
    per-page read-modify-write (gather the B written pages — a
    ``[B, ps, h*d]`` transient, cheap — dequantize, zero the rows at
    and beyond the write offset (a freshly granted page arrives with
    ``write_off == 0``, so its stale garbage dies here without any
    alloc-time zeroing), insert the new row, re-derive the page scale
    from the page's live content, re-quantize, scatter back).
    ``val`` is ``[B, h, d]``; ``write_page``/``write_off`` ``[B]``
    with inactive lanes routed to page 0 — whose re-derived scale is
    forced to 0, so page 0 is re-written with exact zeros."""
    q = cache[part][layer]               # [P, ps, h*d] int8
    sc = cache[part + "_scale"][layer]   # [P, h] bf16
    ps, h = q.shape[1], sc.shape[1]
    pf = _by_head(dequantize(q[write_page], sc[write_page]), h)
    row_ids = jnp.arange(ps)[None, :, None, None]        # [B, ps, h, d]
    pf = jnp.where(row_ids < write_off[:, None, None, None], pf,
                   jnp.zeros_like(pf))
    vf = finite(val.astype(jnp.float32))                 # [B, h, d]
    pf = pf.at[jnp.arange(vf.shape[0]), write_off].set(vf)
    amax = jnp.max(jnp.abs(pf), axis=(1, 3))             # [B, h]
    new_scale = jnp.where(write_page[:, None] == 0,
                          jnp.zeros_like(amax), amax / QMAX)
    pq = quantize(pf.reshape(pf.shape[0], ps, -1), new_scale)
    return _with_layer(cache, layer, part, q.at[write_page].set(pq),
                       sc.at[write_page].set(
                           new_scale.astype(SCALE_DTYPE)))


# ---------------------------------------------------------------------------
# the host swap tier
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class SwappedPages:
    """Host-side copy of one preempted stream's live pages, in wire
    format (bf16 pages plain; int8 codes + bf16 scales under the quant
    tier — the quant layer halves swap bytes too). ``leaves`` maps
    each cache leaf name to a numpy array ``[layers, max_pages, ...]``:
    every layer's pages, the page axis (axis 1) padded to the engine's
    ``max_pages`` with null-page content, so
    the device gather/scatter programs compile exactly once. The
    sha1 seals the banked bytes: a corrupt handle (the ``serve_swap``
    chaos site's damage mode) is detected at swap-in and the stream
    falls back to recompute — degraded restore latency, never a
    corrupted token stream."""

    leaves: Dict[str, Any]
    page_count: int           # live pages banked (≤ the padded axis)
    tokens: int               # known-stream length the pages cover
    quant: bool
    checksum: Optional[str] = None

    def nbytes(self):
        return int(sum(a.nbytes for a in self.leaves.values()))

    def _digest(self):
        h = hashlib.sha1()
        h.update(repr((self.page_count, self.tokens,
                       self.quant)).encode())
        for name in sorted(self.leaves):
            arr = np.ascontiguousarray(self.leaves[name])
            h.update(name.encode())
            h.update(str(arr.dtype).encode())
            h.update(repr(arr.shape).encode())
            h.update(arr.tobytes())
        return h.hexdigest()

    def seal(self):
        self.checksum = self._digest()
        return self

    def intact(self):
        """Whether the banked bytes still match the seal."""
        return self.checksum is not None \
            and self.checksum == self._digest()


@dataclasses.dataclass
class KVTierStats:
    """Host-side counters of the swap tier's economics — the source of
    the serving ledger block's ``swap_rate`` /
    ``swapped_pages_high_water`` fields and window_report's
    KV-economics line. ``None``-when-disabled is the ENGINE's account
    (degradation, never omission); these counters just count."""

    swap_outs: int = 0
    swap_out_failures: int = 0
    swap_ins: int = 0
    swap_in_failures: int = 0
    restores_swap: int = 0
    restores_recompute: int = 0
    swapped_pages_live: int = 0
    swapped_pages_high_water: int = 0
    swapped_bytes_live: int = 0
    swapped_bytes_high_water: int = 0

    def banked(self, handle):
        self.swap_outs += 1
        self.swapped_pages_live += handle.page_count
        self.swapped_bytes_live += handle.nbytes()
        self.swapped_pages_high_water = max(
            self.swapped_pages_high_water, self.swapped_pages_live)
        self.swapped_bytes_high_water = max(
            self.swapped_bytes_high_water, self.swapped_bytes_live)

    def released(self, handle):
        self.swapped_pages_live -= handle.page_count
        self.swapped_bytes_live -= handle.nbytes()
