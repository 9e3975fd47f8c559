"""Quantized + hierarchical collectives — the ONE collectives layer.

Every scale-out path in the repo (DDP's ``allreduce_gradients``, the
ZeRO-2 flat-buffer reduce-scatter/all-gather in
``contrib.optimizers.distributed_fused_{adam,lamb}``, the minimal-GPT
dp grad sync) moves its gradient payload through this module, so the
two comm levers land in one place:

* **int8 block quantization with error feedback** (PAPERS.md EQuARX,
  arXiv:2506.17615): payloads ride the wire as int8 values + one
  bf16 scale per ``block`` elements (~4x narrower than fp32), and the
  per-rank quantization error is carried as an explicit fp32
  **residual** the caller threads across steps — compensation
  survives because the state is state, not a closure. Summation is
  always fp32 on the receiver (each contribution is quantized exactly
  once — no re-quantized partial sums to compound error through).
* **hierarchical two-stage reduction** (PAPERS.md MLPerf-on-TPU-pods,
  arXiv:1909.09756): for a dp axis *declared* as an ``(inner,
  outer)`` mesh-axis pair, allreduce = intra-slice reduce_scatter →
  inter-slice allreduce of the 1/inner-sized shard → intra-slice
  all_gather, so the scarce inter-slice links carry ``1/inner`` of
  the payload. Composition quantizes ONLY the inter-slice hop.

Byte accounting is the proof surface: ``telemetry.costs
.comm_from_jaxpr`` counts the per-axis collective payload of a traced
step, so "cuts dp comm ~4x" is asserted at trace time
(tests/test_collectives.py) — no device window required. Payload =
per-participant operand bytes, not wire bytes (costs.py docstring);
whether the narrower payload wins on the real interconnect is the
queued device A/B (PERF.md §2), and the defaults here stay OFF until
that row lands (measured dispatch, not asserted dispatch).

Knob asymmetry (CLAUDE.md): per-call ``compress=`` /
``hierarchical=`` arguments RAISE on un-honorable requests (unknown
scheme, hierarchical over an unfactored axis); the process-wide
setters / ``APEX_GRAD_COMPRESS`` / ``APEX_HIER_ALLREDUCE`` are
preferences that fall back silently. With both knobs off every entry
point emits the exact pre-existing jaxpr (one psum / psum_scatter /
all_gather per call — byte-identical, asserted by test).

Reference surfaces re-designed here: apex/parallel/distributed.py:
425-475 (allreduce_bucket — the fp32/bucketed DDP reduction this
module's quantized path replaces) and apex/contrib/optimizers/
distributed_fused_lamb.py:16 (``e5m2_allgather`` — the reference's
compressed param all-gather; the int8+scales gather with error
feedback is the TPU-native generalization).
"""

import contextlib
import os
import warnings

import jax
import jax.numpy as jnp
from jax import lax

SCHEMES = ("int8",)
DEFAULT_BLOCK = 128  # elements per scale: 2/128 bf16-scale overhead

# ---------------------------------------------------------------- knobs

_COMPRESS = None   # setter pin: None (consult env) | "off" | scheme
_HIER = None       # setter pin: None (consult env) | True | False
_FORCE_OFF = 0     # disabled() depth — baseline-trace escape hatch
_warned = set()


def _warn_once(msg):
    if msg not in _warned:
        _warned.add(msg)
        warnings.warn(msg)


def _env_compress():
    v = os.environ.get("APEX_GRAD_COMPRESS")
    if v in (None, "", "0", "off", "none"):
        return None
    if v in SCHEMES:
        return v
    # an env knob is a preference, never a raise
    _warn_once(f"APEX_GRAD_COMPRESS={v!r} is not a known scheme "
               f"{SCHEMES} — ignored (compression stays off)")
    return None


def _env_hier():
    v = os.environ.get("APEX_HIER_ALLREDUCE")
    if v == "1":
        return True
    if v in ("0", ""):  # present-but-empty = explicit off, like unset
        return False
    if v is not None:
        # same convention as _env_compress: an env knob is a
        # preference, never a raise — but "true"/"yes" silently
        # measuring the FLAT path under a hierarchical label is drift
        _warn_once(f"APEX_HIER_ALLREDUCE={v!r} is not '1'/'0' — "
                   f"ignored (hierarchical stays off)")
    return None


def set_grad_compress(scheme):
    """Pin the process-wide gradient-compression preference: a scheme
    name turns it on, ``"off"`` pins it off, None un-pins (env/default
    applies). A setter CALL is explicit, so an unknown scheme raises
    — but the pinned preference still falls back where it cannot
    apply (e.g. an unfactored hierarchical request elsewhere)."""
    global _COMPRESS
    if scheme is not None and scheme != "off" and scheme not in SCHEMES:
        raise ValueError(f"unknown compression scheme {scheme!r} "
                         f"(known: {SCHEMES} or 'off'/None)")
    _COMPRESS = scheme


def set_hier_allreduce(value):
    """Pin the process-wide hierarchical-allreduce preference
    (True/False), or un-pin with None. The preference engages only
    where the axis is declared as an (inner, outer) pair — it falls
    back to the flat collective elsewhere."""
    global _HIER
    if value is not None and not isinstance(value, bool):
        raise ValueError(f"hier preference must be True/False/None, "
                         f"got {value!r}")
    _HIER = value


def _table_choice(nelems):
    """The dispatch-table consult for op "grad_comm" (the tier strictly
    BELOW per-call knobs and the process-wide setters/env, per the PR-3
    precedence): keyed on the flat payload size, fed by the
    ``benchmarks/profile_comm.py`` A/B rows. None =
    miss (built-in default: off). Only call sites that know their flat
    payload consult (``allreduce_tree``/``ef_init`` pass ``nelems``);
    the ZeRO optimizers resolve WITHOUT a table consult — their
    error-feedback state layout is fixed at factory time, before any
    payload size exists, so a per-shape flip could desync init from
    update."""
    if nelems is None:
        return None
    from apex_tpu import dispatch
    return dispatch.lookup("grad_comm", "float32", n=int(nelems))


def resolve_compress(per_call=None, *, nelems=None):
    """Resolved scheme (or None=off): per-call (raise on unknown) >
    setter > env > dispatch table (only when ``nelems`` names the flat
    payload — see ``_table_choice``). ``disabled()`` overrides the
    preferences (never an explicit per-call demand)."""
    if per_call is not None:
        if per_call is False or per_call in ("off", "none"):
            return None
        if per_call not in SCHEMES:
            raise ValueError(f"unknown compression scheme {per_call!r} "
                             f"(known: {SCHEMES})")
        return per_call
    if _FORCE_OFF:
        return None
    if _COMPRESS is not None:
        return None if _COMPRESS == "off" else _COMPRESS
    env = _env_compress()
    if env is not None or "APEX_GRAD_COMPRESS" in os.environ:
        return env
    choice = _table_choice(nelems)
    if choice in ("int8", "int8_hier"):
        return "int8"
    return None


def resolve_hier(per_call, axes, *, nelems=None):
    """Whether the two-stage path runs over ``axes``. Per-call True
    over an unfactored axis raises (un-honorable demand); the
    setter/env preference — and below them a "hier"/"int8_hier"
    dispatch-table choice (see ``_table_choice``) — falls back to the
    flat collective."""
    axes = axes_tuple(axes)
    if per_call is not None:
        if per_call and len(axes) != 2:
            raise ValueError(
                "hierarchical allreduce needs the axis declared as an "
                f"(inner, outer) pair, got {axes!r}")
        return bool(per_call)
    if _FORCE_OFF:
        return False
    pref = _HIER if _HIER is not None else _env_hier()
    if pref is None and "APEX_HIER_ALLREDUCE" not in os.environ:
        pref = _table_choice(nelems) in ("hier", "int8_hier")
    return bool(pref) and len(axes) == 2


@contextlib.contextmanager
def disabled():
    """Trace-time escape hatch: inside the context every *preference*
    resolves off (explicit per-call demands still honor themselves).
    Used by harnesses to trace the uncompressed twin of a compressed
    program for the cost block's compressed-vs-uncompressed stamp."""
    global _FORCE_OFF
    _FORCE_OFF += 1
    try:
        yield
    finally:
        _FORCE_OFF -= 1


def snapshot(nelems=None, axes=None):
    """The resolved comm-compression config — the ``comm_compression``
    stamp harnesses put in their cost block. Pass ``nelems`` (the flat
    grad payload of the measured program) so the dispatch-table tier
    resolves here exactly as it does at the program's own trace time:
    a table-driven compressed run must stamp, or check 7 has nothing
    to pin-match (the unstamped-compressed-row drift class). Pass
    ``axes`` (the program's dp axis declaration) so ``hierarchical``
    reports whether the two-stage path actually ENGAGED — an
    APEX_HIER_ALLREDUCE=1 run over an unfactored axis runs the flat
    collective, and stamping hierarchical=true for it would be
    label drift. Without ``axes`` the field is the raw preference."""
    if axes is not None:
        hier = resolve_hier(None, axes, nelems=nelems)
    elif _FORCE_OFF:
        hier = False
    else:
        hier = _HIER if _HIER is not None else _env_hier()
        if hier is None and nelems is not None \
                and "APEX_HIER_ALLREDUCE" not in os.environ:
            hier = _table_choice(nelems) in ("hier", "int8_hier")
    return {"scheme": resolve_compress(None, nelems=nelems),
            "hierarchical": bool(hier),
            "block": DEFAULT_BLOCK}


def _reset_for_tests():
    global _COMPRESS, _HIER, _FORCE_OFF
    _COMPRESS = None
    _HIER = None
    _FORCE_OFF = 0
    _warned.clear()


# ----------------------------------------------------------- axis utils

def axes_tuple(axis_name):
    """Normalize an axis spec (name or (inner, outer) pair) to a
    tuple of names."""
    if isinstance(axis_name, (tuple, list)):
        return tuple(axis_name)
    return (axis_name,)


def axes_size(axis_name):
    """Product of the mesh-axis sizes (static under shard_map)."""
    size = 1
    for ax in axes_tuple(axis_name):
        size *= lax.axis_size(ax)
    return size


def axes_index(axis_name):
    """Row-major flat rank over the axis tuple — matches the chunk
    ordering of a tuple-axis ``psum_scatter``/``all_gather`` AND of
    the staged inner-then-outer decomposition, so hierarchical and
    flat collectives agree on shard ownership."""
    axes = axes_tuple(axis_name)
    idx = lax.axis_index(axes[0])
    for ax in axes[1:]:
        idx = idx * lax.axis_size(ax) + lax.axis_index(ax)
    return idx


# ------------------------------------------------- block quantization

def quantize_blocks(x, block=DEFAULT_BLOCK):
    """Block-quantize ``x`` ([..., n] float) to int8 with one bf16
    scale per ``block`` elements of the last dim.

    Returns ``(q, scales)``: ``q`` [..., nb, block] int8, ``scales``
    [..., nb] bf16. The last dim is zero-padded to a block multiple
    (padding quantizes to 0 — harmless on dequantize+slice). A block
    containing a non-finite value gets scale=inf, which poisons its
    dequantized block to non-finite — overflow semantics survive the
    quantized path (a scaled-grad inf still trips found_inf on the
    receiver instead of silently flushing to zero)."""
    n = x.shape[-1]
    nb = -(-n // block)
    pad = nb * block - n
    xf = x.astype(jnp.float32)
    if pad:
        xf = jnp.pad(xf, [(0, 0)] * (x.ndim - 1) + [(0, pad)])
    xb = xf.reshape(*x.shape[:-1], nb, block)
    amax = jnp.max(jnp.abs(xb), axis=-1)
    scales = jnp.where(amax > 0, amax / 127.0, 1.0)
    # a NaN amax fails the `> 0` test and would silently take scale=1
    # (int8-casting NaN yields 0 — the block would flush to FINITE
    # zero, found_inf never fires, and the EF residual turns NaN
    # forever); force every non-finite block to scale=inf so its
    # dequantized form is non-finite, like the inf case
    scales = jnp.where(jnp.isfinite(amax), scales,
                       jnp.inf).astype(jnp.bfloat16)
    # quantize against the SAME bf16-rounded scale the receivers
    # dequantize with, or the sender's residual would compensate a
    # different error than the one actually emitted
    s = scales.astype(jnp.float32)[..., None]
    q = jnp.clip(jnp.round(xb / s), -127, 127).astype(jnp.int8)
    return q, scales


def dequantize_blocks(q, scales, n):
    """Inverse of :func:`quantize_blocks`: [..., nb, block] int8 +
    [..., nb] bf16 → [..., n] fp32 (padding sliced off)."""
    xb = q.astype(jnp.float32) * scales.astype(jnp.float32)[..., None]
    return xb.reshape(*q.shape[:-2], -1)[..., :n]


def _compensate(x, residual):
    """(compensated input, emit-residual fn). Error feedback: the
    residual of what the previous steps failed to emit rides into
    this step's payload; the new residual is what THIS quantization
    failed to emit — sanitized to 0 where the dequantized value went
    non-finite (an overflow step is skipped by the caller's found_inf
    gate; carrying its nan would poison every later step)."""
    comp = x if residual is None else x + residual

    def new_residual(q, scales):
        if residual is None:
            return None
        dq = dequantize_blocks(q, scales, comp.shape[-1])
        return jnp.where(jnp.isfinite(dq), comp - dq, 0.0)

    return comp, new_residual


# --------------------------------------------------- flat-vector cores
# Everything below operates on ONE flat fp32 vector; the tree/pytree
# entry points flatten through these. All return (value, new_residual)
# where new_residual is None unless a residual was threaded in.

def quantized_allreduce_flat(x, axis_name, *, mean=False,
                             block=DEFAULT_BLOCK, residual=None):
    """One-shot gather-based quantized allreduce of a flat [n] vector:
    each rank quantizes its (residual-compensated) contribution ONCE,
    all-gathers the int8+scales payload, and sums the dequantized
    contributions in fp32 — requantization-free, so quantization
    error never compounds through partial sums (the property EQuARX
    buys with per-hop block rescaling). Payload: ~n int8 + 2n/block
    scale bytes vs 4n for the fp32 psum (~3.9x at block=128).

    Memory note: the gather materializes a W×n int8 working set per
    rank before the fp32 sum — O(W·n) receive-side peak vs the psum's
    O(n). At pod scale that cost belongs in the §6 small-HBM-first
    calculus (bench's warmed peak-HBM stamp will carry it); a
    reduce-scatter + all-gather decomposition caps it at O(n) and is
    the queued follow-up if the device A/B flags starvation."""
    axes = axes_tuple(axis_name)
    n = x.shape[-1]
    comp, emit = _compensate(x, residual)
    q, scales = quantize_blocks(comp, block)
    gq = lax.all_gather(q, axes, tiled=False)          # [W, nb, block]
    gs = lax.all_gather(scales, axes, tiled=False)     # [W, nb]
    total = jnp.sum(gq.astype(jnp.float32)
                    * gs.astype(jnp.float32)[..., None], axis=0)
    y = total.reshape(-1)[:n]
    if mean:
        y = y / axes_size(axes)
    return y, emit(q, scales)


def quantized_reduce_scatter_flat(x, axis_name, *, block=DEFAULT_BLOCK,
                                  residual=None):
    """Quantized reduce-scatter (sum) of a flat [P] vector over ONE
    axis, P divisible by its size: quantize the compensated vector
    per destination shard, all_to_all the int8+scales payload (each
    rank receives every rank's copy of ITS shard), dequantize and sum
    in fp32 → [P/W] shard. Payload ~P int8 vs 4P for psum_scatter."""
    (axis,) = axes_tuple(axis_name)
    world = lax.axis_size(axis)
    P = x.shape[-1]
    assert P % world == 0, (P, world)
    shard = P // world
    comp = x if residual is None else x + residual
    xb = comp.reshape(world, shard)
    q, scales = quantize_blocks(xb, block)
    new_res = None
    if residual is not None:
        dq = dequantize_blocks(q, scales, shard)        # [world, shard]
        new_res = jnp.where(jnp.isfinite(dq), xb - dq, 0.0).reshape(-1)
    qs = lax.all_to_all(q, axis, split_axis=0, concat_axis=0)
    ss = lax.all_to_all(scales, axis, split_axis=0, concat_axis=0)
    total = jnp.sum(qs.astype(jnp.float32)
                    * ss.astype(jnp.float32)[..., None], axis=0)
    y = total.reshape(-1)[:shard]
    return y, new_res


def quantized_all_gather_flat(shard, axis_name, *, block=DEFAULT_BLOCK,
                              residual=None):
    """Quantized all-gather of a flat [m] shard over ONE axis →
    [W*m]: the (compensated) shard rides as int8+scales; every rank
    dequantizes the same payload, so the gathered result stays
    bitwise replicated. Payload ~m int8 vs 4m fp32."""
    (axis,) = axes_tuple(axis_name)
    m = shard.shape[-1]
    comp, emit = _compensate(shard, residual)
    q, scales = quantize_blocks(comp, block)
    gq = lax.all_gather(q, axis, tiled=False)        # [W, nb, block]
    gs = lax.all_gather(scales, axis, tiled=False)   # [W, nb]
    full = dequantize_blocks(gq, gs, m).reshape(-1)
    return full, emit(q, scales)


def hierarchical_allreduce_flat(x, axis_name, *, mean=False,
                                compress=None, block=DEFAULT_BLOCK,
                                residual=None):
    """Two-stage allreduce of a flat [n] vector over a declared
    (inner, outer) axis pair: intra-slice reduce_scatter → inter-
    slice allreduce of the 1/inner shard (quantized when ``compress``
    — the ONLY quantized hop: intra-slice ICI is cheap, inter-slice
    is where bandwidth is scarcest) → intra-slice all_gather. The
    outer axis carries 1/inner of the flat payload (×~1/4 again
    under int8)."""
    inner, outer = axes_tuple(axis_name)
    isz = lax.axis_size(inner)
    n = x.shape[-1]
    P = -(-n // isz) * isz
    xp = jnp.pad(x.astype(jnp.float32), (0, P - n)) if P != n \
        else x.astype(jnp.float32)
    shard = lax.psum_scatter(xp, inner, scatter_dimension=0, tiled=True)
    if compress:
        shard, new_res = quantized_allreduce_flat(
            shard, (outer,), mean=False, block=block, residual=residual)
    else:
        shard = lax.psum(shard, outer)
        new_res = residual  # nothing quantized: state passes through
    full = lax.all_gather(shard, inner, tiled=True)
    y = full[:n]
    if mean:
        y = y / (isz * lax.axis_size(outer))
    return y, new_res


# ------------------------------------------------------ tree entry point

def _flat_size(leaves):
    total = 0
    for leaf in leaves:
        size = 1
        for d in leaf.shape:
            size *= d
        total += size
    return total


def _check_float(leaves, scheme):
    for leaf in leaves:
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            raise TypeError(
                f"compression scheme {scheme!r} needs floating-point "
                f"leaves, got {leaf.dtype}")


def ef_init(tree, axis_name, *, compress=None, hierarchical=None,
            block=DEFAULT_BLOCK):
    """The zero error-feedback residual :func:`allreduce_tree` carries
    for ``tree`` under the resolved knobs — None when the resolved
    config quantizes nothing (so threading the state is free when
    off). Call INSIDE shard_map (the hierarchical residual's shape
    depends on the inner axis size)."""
    del block
    axes = axes_tuple(axis_name)
    total = _flat_size(jax.tree_util.tree_leaves(tree))
    scheme = resolve_compress(compress, nelems=total)
    hier = resolve_hier(hierarchical, axes, nelems=total)
    if scheme is None:
        return None
    if hier:
        isz = lax.axis_size(axes[0])
        total = -(-total // isz)
    return jnp.zeros((total,), jnp.float32)


def allreduce_tree(tree, axis_name, *, mean=True, compress=None,
                   hierarchical=None, ef_state=None,
                   block=DEFAULT_BLOCK):
    """All-reduce a pytree over ``axis_name`` (a mesh-axis name or a
    declared (inner, outer) pair) under the resolved comm knobs.

    Returns ``(tree, new_ef_state)``. With everything resolved off
    this is one ``lax.psum`` per leaf (byte-identical to the
    pre-collectives jaxpr) and ``ef_state`` passes through untouched.
    Compressed/hierarchical paths flatten the tree to one fp32
    buffer (one collective pair instead of per-leaf traffic), reduce
    it, and unflatten back to the original dtypes."""
    axes = axes_tuple(axis_name)
    total = _flat_size(jax.tree_util.tree_leaves(tree))
    scheme = resolve_compress(compress, nelems=total)
    hier = resolve_hier(hierarchical, axes, nelems=total)
    if scheme is None and not hier:
        world = axes_size(axes)

        def reduce_one(g):
            g = lax.psum(g, axes if len(axes) > 1 else axes[0])
            return g / world if mean else g

        return jax.tree_util.tree_map(reduce_one, tree), ef_state

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if scheme is not None:
        _check_float(leaves, scheme)
    flat = jnp.concatenate(
        [jnp.ravel(leaf).astype(jnp.float32) for leaf in leaves])
    if hier:
        red, new_res = hierarchical_allreduce_flat(
            flat, axes, mean=mean, compress=scheme, block=block,
            residual=ef_state)
    else:
        red, new_res = quantized_allreduce_flat(
            flat, axes, mean=mean, block=block, residual=ef_state)
    out, offset = [], 0
    for leaf in leaves:
        size = 1
        for d in leaf.shape:
            size *= d
        out.append(lax.dynamic_slice_in_dim(red, offset, size)
                   .reshape(leaf.shape).astype(leaf.dtype))
        offset += size
    return jax.tree_util.tree_unflatten(treedef, out), new_res


# --------------------------------------- ZeRO flat-buffer entry points
# consumed by optimizers._fused.zero_grad_shard / zero_gather_updates:
# the staged (inner, outer) decompositions produce the SAME chunk
# ownership as the flat tuple-axis collectives (axes_index row-major),
# so the knobs flip the algorithm without moving any shard.

def reduce_scatter_flat(x, axis_name, *, compress=None,
                        hierarchical=None, block=DEFAULT_BLOCK,
                        residual=None):
    """Reduce-scatter (sum) a flat [P] vector over ``axis_name`` (name
    or (inner, outer) pair); P must divide by the total axis size.
    Returns ``([P/W] shard, new_residual)``. Hierarchical: intra-slice
    psum_scatter → inter-slice reduce-scatter of the 1/inner piece
    (the only hop quantized under ``compress``)."""
    axes = axes_tuple(axis_name)
    scheme = resolve_compress(compress)
    hier = resolve_hier(hierarchical, axes)
    if hier:
        inner, outer = axes
        piece = lax.psum_scatter(x, inner, scatter_dimension=0,
                                 tiled=True)
        if scheme is not None:
            return quantized_reduce_scatter_flat(
                piece, (outer,), block=block, residual=residual)
        return lax.psum_scatter(piece, outer, scatter_dimension=0,
                                tiled=True), residual
    if scheme is not None:
        if len(axes) > 1:
            # no factored declaration to stage over: quantize the one
            # flat hop (the whole tuple behaves as one big axis)
            return _quantized_rs_multi(x, axes, block, residual)
        return quantized_reduce_scatter_flat(
            x, axes, block=block, residual=residual)
    return lax.psum_scatter(x, axes if len(axes) > 1 else axes[0],
                            scatter_dimension=0, tiled=True), residual


def _quantized_rs_multi(x, axes, block, residual):
    """Quantized RS over a flat multi-axis tuple: all_to_all has no
    tuple form, so stage per axis with quantization on the FIRST hop
    (the full-width one) and full precision after."""
    first, rest = axes[0], axes[1:]
    # chunk ordering: tuple-axis RS is row-major, so the first axis is
    # the outermost chunk index — scatter over it first
    y, new_res = quantized_reduce_scatter_flat(
        x, (first,), block=block, residual=residual)
    y = lax.psum_scatter(y, rest if len(rest) > 1 else rest[0],
                         scatter_dimension=0, tiled=True)
    return y, new_res


def all_gather_flat(shard, axis_name, *, compress=None,
                    hierarchical=None, block=DEFAULT_BLOCK,
                    residual=None, gather_dtype=jnp.float32):
    """All-gather a flat [P/W] shard over ``axis_name`` → [P].
    Returns ``(full, new_residual)``. Hierarchical: inter-slice
    gather first (chunk order: outer is the innermost index — the
    inverse of :func:`reduce_scatter_flat`), quantized under
    ``compress``; intra-slice gather full width. ``gather_dtype``
    applies to the uncompressed hops only (the bf16 gather knob of
    the reference's ``e5m2_allgather``)."""
    axes = axes_tuple(axis_name)
    scheme = resolve_compress(compress)
    hier = resolve_hier(hierarchical, axes)
    dtype = shard.dtype

    def _plain(v, ax):
        return lax.all_gather(v.astype(gather_dtype),
                              ax if not isinstance(ax, tuple) or len(ax) > 1
                              else ax[0], tiled=True).astype(dtype)

    if hier:
        inner, outer = axes
        if scheme is not None:
            piece, new_res = quantized_all_gather_flat(
                shard, (outer,), block=block, residual=residual)
            piece = piece.astype(dtype)
        else:
            piece, new_res = _plain(shard, outer), residual
        return _plain(piece, inner), new_res
    if scheme is not None:
        if len(axes) > 1:
            full, new_res = quantized_all_gather_flat(
                shard, (axes[-1],), block=block, residual=residual)
            return _plain(full.astype(dtype), axes[:-1]), new_res
        full, new_res = quantized_all_gather_flat(
            shard, axes, block=block, residual=residual)
        return full.astype(dtype), new_res
    return _plain(shard, axes if len(axes) > 1 else axes[0]), residual
