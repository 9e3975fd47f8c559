"""The state-space (Mamba-2) recurrence of a serving model: the one-step
update of a slot-owned state (decode), the chunked scan over one packed
batch of prompts (prefill), and the causal depthwise convolution before
both.

Per head ``h`` (``P`` = head width, ``N`` = state size; head ``h`` reads
group ``h // (H / G)`` of ``B`` and ``C``)::

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        S: [P, N]
    y_t = S_t C_t + D x_t

with ``S`` zero before a sequence's first token. This is
``selective_state_update`` (decode) and ``mamba_chunk_scan_combined``
with ``seq_idx`` (prefill) of ``mamba_ssm``; the published equations are
``transformers`` ``models/falcon_h1/modeling_falcon_h1.py:783-976``
(``torch_forward``: the one-step and the chunked forms).

Decode: :func:`ssm_decode_update` reads a state leaf ``[slots + 1, H, P,
N]`` (the last row a spare, as the page table's spare row) and updates
the rows of the live slots IN PLACE. On a TPU it is ONE Pallas kernel
(``ssm_decode_update``): a grid of one step a slot, the live slots first
by a scalar-prefetched list; a step loads the slot's whole state (every
head) in its dtype, computes the update and ``S C`` in float32 and
stores the state back in its dtype, through ``input_output_aliases``:
no copy of the leaf, ever. The steps past the live count name the last
live slot's block again, so nothing is fetched or written for them, and skip
their work. The jnp form (:func:`ssm_decode_update_reference`) is the
same arithmetic in XLA: the CPU's path and the kernel's parity.

Operands reach the kernel laid out for it: ``dt x`` as ``[b, P, H]``
(a head's column is a static lane slice), ``exp(dt A)`` as ``[b, 1,
H]``, ``B`` and ``C`` as ``[b, G, N]`` in float32; ``S C`` leaves as
``[b, P, H]``.

Prefill: :func:`chunked_scan` over the packed rows, in chunks of
``chunk`` rows (``mamba_chunk_size``), exact across segment boundaries
that fall inside a chunk: the decay inside a chunk, the chunk-to-chunk
carry and each segment's final state never cross a segment (the
packed batch's ``seg`` ids, 0 for padding). :func:`final_state` gives a
segment's state after its last token from what the scan returns. XLA,
float32 at ``Precision.HIGHEST``. :func:`segment_conv` is the causal
depthwise convolution restarted at every segment.
"""

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "ssm_decode_update"
_HIGHEST = lax.Precision.HIGHEST


def _heads_per_group(H, G):
    if H % G:
        raise ValueError(f"{H} heads do not split into {G} groups")
    return H // G


# ------------------------------------------------------------------ decode

def decode_supported(P, N):
    """Whether Mosaic takes the kernel: the head width and the state
    size whole sublane and lane tiles."""
    return P % 8 == 0 and N % 128 == 0


def decode_resolved(P, N, impl=None):
    """The impl a :func:`ssm_decode_update` call runs with: a per-call
    demand, else the kernel on a TPU where :func:`decode_supported`
    holds, else the jnp form."""
    if impl is not None:
        if impl not in ("jnp", "pallas"):
            raise ValueError(f"unknown ssm decode impl {impl!r}")
        if impl == "pallas" and not decode_supported(P, N):
            raise ValueError(
                f"ssm_decode_update: impl='pallas' cannot be honoured at "
                f"P={P}, N={N}")
        return impl
    if jax.default_backend() == "tpu" and decode_supported(P, N):
        return "pallas"
    return "jnp"


def _decode_kernel(live_ref, n_ref, s_ref, dtx_ref, da_ref, b_ref, c_ref,
                   s_out, y_ref, *, heads, per_group):
    """One live slot: every head's ``[P, N]`` state in float32, updated,
    stored back in the leaf's dtype; ``S C`` into the head's column."""
    del live_ref

    @pl.when(pl.program_id(0) < n_ref[0])
    def _slot():
        dtx = dtx_ref[0]                                   # [P, H]
        da = da_ref[0]                                     # [1, H]
        bm, cm = b_ref[0], c_ref[0]                        # [G, N]
        for h in range(heads):
            g = h // per_group
            s = s_ref[0, h].astype(jnp.float32)            # [P, N]
            s = da[:, h:h + 1] * s + dtx[:, h:h + 1] * bm[g:g + 1, :]
            s_out[0, h] = s.astype(s_out.dtype)
            y_ref[0, :, h:h + 1] = jnp.sum(s * cm[g:g + 1, :], axis=1,
                                           keepdims=True)


def _live_list(live):
    """``(slots [b], count [1])``: the live lanes first, in order, then
    the last live lane repeated (the spare row ``b`` where none is
    live), so the grid's dead steps name a block it already holds."""
    b = live.shape[0]
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    n = jnp.sum(live.astype(jnp.int32))
    last = jnp.where(n > 0, order[jnp.maximum(n - 1, 0)], b)
    slots = jnp.where(jnp.arange(b) < n, order, last).astype(jnp.int32)
    return slots, n.reshape(1)


def ssm_decode_update_pallas(state, dtx, da, B, C, live, *, interpret=False):
    """The kernel on the laid-out operands (module docstring): ``dtx [b +
    1, P, H]``, ``da [b + 1, 1, H]``, ``B``, ``C`` ``[b + 1, G, N]``
    float32 (row ``b`` the spare's), ``live [b]`` bool. Returns
    ``(state, S C as [b + 1, P, H] float32)``; rows of dead lanes are
    unwritten."""
    rows, H, P, N = state.shape
    G = B.shape[1]
    slots, n = _live_list(live)
    kernel = functools.partial(_decode_kernel, heads=H,
                               per_group=_heads_per_group(H, G))

    def at(*tail):
        return lambda i, slots, n: (slots[i],) + tail

    block_bytes = H * P * N * (state.dtype.itemsize * 4 + 4 * 3)
    return pl.pallas_call(
        kernel,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((rows, P, H), jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(live.shape[0],),
            in_specs=[pl.BlockSpec((1, H, P, N), at(0, 0, 0)),
                      pl.BlockSpec((1, P, H), at(0, 0)),
                      pl.BlockSpec((1, 1, H), at(0, 0)),
                      pl.BlockSpec((1, G, N), at(0, 0)),
                      pl.BlockSpec((1, G, N), at(0, 0))],
            out_specs=[pl.BlockSpec((1, H, P, N), at(0, 0, 0)),
                       pl.BlockSpec((1, P, H), at(0, 0))]),
        input_output_aliases={2: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=max(32 << 20, block_bytes + (8 << 20))),
        interpret=interpret, name=KERNEL_NAME,
    )(slots, n, state, dtx, da, B, C)


def ssm_decode_update_reference(state, x, dt, A, B, C, live):
    """The same arithmetic in XLA (float32, the state back in its
    dtype): rows ``[0, b)`` of ``state`` updated where ``live``, ``S
    C`` returned (0 for dead lanes)."""
    b, H = dt.shape
    rep = _heads_per_group(H, B.shape[1])
    s = state[:b].astype(jnp.float32)
    Bh = jnp.repeat(B.astype(jnp.float32), rep, axis=1)      # [b, H, N]
    Ch = jnp.repeat(C.astype(jnp.float32), rep, axis=1)
    da = jnp.exp(dt * A[None, :])
    dtx = dt[..., None] * x.astype(jnp.float32)               # [b, H, P]
    s = da[..., None, None] * s + dtx[..., None] * Bh[:, :, None, :]
    y = jnp.einsum("bhpn,bhn->bhp", s, Ch, precision=_HIGHEST)
    keep = live[:, None, None, None]
    state = lax.dynamic_update_slice(
        state, jnp.where(keep, s.astype(state.dtype), state[:b]),
        (0, 0, 0, 0))
    return state, jnp.where(live[:, None, None], y, 0.0)


def ssm_decode_update(state, x, dt, A, B, C, D, live, *, impl=None,
                      interpret=None):
    """One decode step of every live slot's recurrence, in place.

    ``state [b + 1, H, P, N]`` (row ``b`` spare), ``x [b, H, P]``, ``dt
    [b, H]`` float32 (after the softplus), ``A [H]`` (negative), ``B``,
    ``C [b, G, N]``, ``D [H]``, ``live [b]`` bool (lane ``i`` is state
    row ``i``). Returns ``(state, y [b, H, P] float32)``, ``y = S C + D
    x`` (0 for dead lanes). ``impl`` is a per-call demand ("jnp" |
    "pallas"); ``interpret`` defaults to True on the CPU platform only."""
    P, N = state.shape[-2:]
    b = x.shape[0]
    skip = D.astype(jnp.float32)[None, :, None] * x.astype(jnp.float32)
    if decode_resolved(P, N, impl) == "jnp":
        state, y = ssm_decode_update_reference(state, x, dt, A, B, C, live)
        return state, y + jnp.where(live[:, None, None], skip, 0.0)
    if interpret is None:
        interpret = jax.devices()[0].platform == "cpu"
    dt = dt.astype(jnp.float32)

    def spare(a):   # one more row: the spare's, named by no live step
        return jnp.pad(a, ((0, 1),) + ((0, 0),) * (a.ndim - 1))

    dtx = (dt[..., None] * x.astype(jnp.float32)).transpose(0, 2, 1)
    da = jnp.exp(dt * A[None, :].astype(jnp.float32))[:, None, :]
    state, yt = ssm_decode_update_pallas(
        state, spare(dtx), spare(da), spare(B.astype(jnp.float32)),
        spare(C.astype(jnp.float32)), live, interpret=interpret)
    y = yt[:b].transpose(0, 2, 1) + skip
    return state, jnp.where(live[:, None, None], y, 0.0)


# ----------------------------------------------------------------- prefill

def segment_conv(xbc, seg, w, bias):
    """Causal depthwise convolution of width ``K`` over each packed
    segment: ``out[t] = bias + sum_k w[k] xbc[t - K + 1 + k]``, an input
    of another segment (or before the batch) read as 0. ``xbc [T, C]``,
    ``w [K, C]``, ``bias [C]``; float32 out."""
    K, T = w.shape[0], xbc.shape[0]
    wf = w.astype(jnp.float32)
    out = xbc.astype(jnp.float32) * wf[K - 1]
    for j in range(1, K):
        prev = jnp.pad(xbc, ((j, 0), (0, 0)))[:T].astype(jnp.float32)
        same = jnp.pad(seg, (j, 0), constant_values=-1)[:T] == seg
        out = out + jnp.where(same[:, None], prev, 0.0) * wf[K - 1 - j]
    return out + bias.astype(jnp.float32)


def conv_tail(xbc, seg, end, width):
    """The last ``width`` inputs of the segment ending at row ``end``,
    oldest first, zero where they lie before the segment: the conv
    state a decode step continues from. ``[width, C]``."""
    at = end - width + 1 + jnp.arange(width)
    rows = jnp.take(xbc, jnp.maximum(at, 0), axis=0)
    same = (at >= 0) & (jnp.take(seg, jnp.maximum(at, 0)) == seg[end])
    return jnp.where(same[:, None], rows, jnp.zeros_like(rows))


def chunked_scan(x, dt, A, B, C, seg, *, chunk):
    """The recurrence over one packed batch of ``T`` rows, in chunks of
    ``chunk``: ``x [T, H, P]``, ``dt [T, H]`` float32 (after the
    softplus), ``A [H]``, ``B``, ``C [T, G, N]``, ``seg [T]`` (equal ids
    are one sequence, in position order; no state crosses a change of
    id). Returns ``(y [T, H, P] float32`` (``S C``, no ``D`` term),
    ``carry)``: what :func:`final_state` needs, ``(dt x [T, H, P], B [T,
    G, N], dt A [T, H]`` float32 ``, h_in [chunks, H, P, N])``, ``h_in[c]``
    the state entering chunk ``c`` of the segment that ends chunk ``c -
    1``."""
    T, H, P = x.shape
    G, N = B.shape[1:]
    rep = _heads_per_group(H, G)
    Q = chunk
    pad = (-T) % Q
    nc = (T + pad) // Q
    f32 = jnp.float32
    a = dt.astype(f32) * A.astype(f32)[None, :]
    xdt = x.astype(f32) * dt.astype(f32)[..., None]
    Bf, Cf = B.astype(f32), C.astype(f32)

    def chunks(v, fill=0):
        v = jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1),
                    constant_values=fill)
        return v.reshape((nc, Q) + v.shape[1:])

    ac, xc, bc, cc = chunks(a), chunks(xdt), chunks(Bf), chunks(Cf)
    sc = chunks(seg.astype(jnp.int32), -1)                  # [nc, Q]
    cs = jnp.cumsum(ac, axis=1)                              # [nc, Q, H]
    # inside a chunk: t sees s <= t of its own segment
    tri = jnp.tril(jnp.ones((Q, Q), bool))
    same = (sc[:, :, None] == sc[:, None, :]) & tri          # [nc, t, s]
    diff = cs[:, :, None, :] - cs[:, None, :, :]             # [nc, t, s, H]
    L = jnp.where(same[..., None], jnp.exp(jnp.minimum(diff, 0.0)), 0.0)
    cb = jnp.einsum("ctgn,csgn->cgts", cc, bc, precision=_HIGHEST)
    cb = jnp.repeat(cb, rep, axis=1)                         # [nc, H, t, s]
    y = jnp.einsum("chts,cshp->cthp", cb * L.transpose(0, 3, 1, 2), xc,
                   precision=_HIGHEST)
    # each chunk's state at its end, of the segment that ends it
    last = sc[:, -1]
    w_end = jnp.where((sc == last[:, None])[..., None],
                      jnp.exp(jnp.minimum(cs[:, -1:, :] - cs, 0.0)), 0.0)
    bh = jnp.repeat(bc, rep, axis=2)                         # [nc, Q, H, N]
    st = jnp.einsum("cshp,cshn->chpn", xc * w_end[..., None], bh,
                    precision=_HIGHEST)
    # chunk to chunk: a chunk wholly inside the segment that ends the one
    # before carries that state through, decayed by the whole chunk
    through = jnp.concatenate([jnp.zeros((1,), bool),
                               (sc[1:, 0] == sc[:-1, -1])
                               & (sc[1:, 0] == sc[1:, -1])])
    decay = jnp.exp(cs[:, -1, :])                            # [nc, H]

    def step(h, inp):
        st_c, through_c, decay_c = inp
        return st_c + jnp.where(through_c, decay_c[:, None, None] * h,
                                0.0), h

    _, h_in = lax.scan(step, jnp.zeros((H, P, N), f32),
                       (st, through, decay))
    # what enters a chunk reaches its rows of the segment that ended the
    # chunk before
    prev_last = jnp.concatenate([jnp.full((1,), -2, jnp.int32), last[:-1]])
    enters = (sc == prev_last[:, None])[..., None]           # [nc, Q, 1]
    ch = jnp.repeat(cc, rep, axis=2)                         # [nc, Q, H, N]
    y_in = jnp.einsum("cthn,chpn->cthp", ch, h_in, precision=_HIGHEST)
    y = y + jnp.where(enters[..., None], jnp.exp(cs)[..., None] * y_in, 0.0)
    y = y.reshape(nc * Q, H, P)[:T]
    return y, (xdt, Bf, a, h_in)


def final_state(carry, seg, end, *, chunk):
    """The state ``[H, P, N]`` float32 after row ``end`` of the segment
    it belongs to, from :func:`chunked_scan`'s ``carry``: the rows of its
    chunk up to ``end`` (its own segment's alone), plus the state that
    entered the chunk where the segment began before it."""
    xdt, Bf, a, h_in = carry
    Q = chunk
    H = xdt.shape[1]
    rep = _heads_per_group(H, Bf.shape[1])
    pad = (-xdt.shape[0]) % Q
    if pad:
        xdt, Bf, a = (jnp.pad(v, ((0, pad),) + ((0, 0),) * (v.ndim - 1))
                      for v in (xdt, Bf, a))
        seg = jnp.pad(seg, (0, pad), constant_values=-1)
    c, r = end // Q, end % Q
    start = c * Q
    xs, bs, as_ = (lax.dynamic_slice_in_dim(v, start, Q)
                   for v in (xdt, Bf, a))
    ss = lax.dynamic_slice_in_dim(seg, start, Q)
    cs = jnp.cumsum(as_, axis=0)                             # [Q, H]
    mine = seg[end]
    keep = (jnp.arange(Q) <= r) & (ss == mine)
    w = jnp.where(keep[:, None], jnp.exp(jnp.minimum(cs[r] - cs, 0.0)), 0.0)
    bh = jnp.repeat(bs, rep, axis=1)                         # [Q, H, N]
    own = jnp.einsum("shp,shn->hpn", xs * w[..., None], bh,
                     precision=_HIGHEST)
    carried = (c > 0) & (seg[jnp.maximum(start - 1, 0)] == mine) \
        & (ss[0] == mine)
    return own + jnp.where(carried, jnp.exp(cs[r])[:, None, None]
                           * h_in[c], 0.0)
