"""Static cost/memory/communication accounting — the attribution layer.

A measured MFU carries no attribution on its own: is the gap to the
roofline compute-bound or HBM-bound, and which slice owns it? This
module derives, for every AOT-lowered
bench/harness program, a validated **cost block** from XLA's own
analyses — no measurement, no device time, no change to the measured
program (the analyses read the lowered/compiled artifact; PR-1's
disabled-is-free invariant holds trivially: the traced jaxpr is
byte-identical whether or not anyone asks XLA to count its flops).

The block (:func:`build`; schema policed by :func:`validate`, wired
into ``ledger.validate_record``)::

    {"source": "compiled"|"lowered"|"eval_shape"|None,
                                            # what surface reported —
                                            # "eval_shape" marks a pure
                                            # shape-walk lower bound (the
                                            # ISSUE 18 capability rung:
                                            # nothing compiled, arg bytes
                                            # only)
     "steps": K,                            # scan length (metadata —
                                            # XLA counts the body ONCE)
     "xla_flops_per_step":   ...,  # XLA-counted flops (real HLO work)
     "model_flops_per_step": ...,  # the 6·N·tokens an MFU claim uses
     "hbm_bytes_per_step":   ...,  # bytes moved ("bytes accessed")
     "peak_hbm_bytes":       ...,  # arg+out+temp+code − alias
     "memory": {...},              # the raw memory_analysis fields
     "comm_bytes_per_axis": {...}, # collective payload per mesh axis
     "peak_flops": ..., "hbm_bytes_per_s": ...,   # roofline constants
     "compute_floor_ms": ..., "bandwidth_floor_ms": ...,
     "step_floor_ms": ...,         # max(compute, bandwidth) floor
     "mfu_bound": ...}             # model flops at the floor ÷ peak

plus two OPTIONAL stamps (present only where they say something —
legacy blocks stay valid without them, malformed is a finding):
``comm_compression`` (the quantized-collectives claim, PR 8) and
``overlap_bound`` (:func:`overlap_bound` — compute floor vs measured
comm+host time, the ROADMAP 4d gap ``window_report`` prints).

Every field is None where the backend reports nothing (``_compat``
reads the installed jax's two analysis surfaces) or where the device
kind has no roofline (the CPU) — a cost block is always stampable.

Comm accounting (:func:`comm_from_jaxpr`) counts collective payload
bytes per mesh axis from the jaxpr — psum/pmean/all_gather/
reduce_scatter/ppermute/all_to_all operand bytes, scan bodies
multiplied by their trip count. "Payload" = per-participant operand
bytes, NOT wire bytes (a ring all-reduce moves ~2(n−1)/n× payload);
the number is the telemetry prerequisite for quantized-collective
work (ROADMAP item 3), where payload shrinkage is exactly the claim.

Predicted peak HBM can refuse a program BEFORE it is dispatched:
:func:`starvation` flags one whose predicted peak exceeds the chip
(hard infeasible) or the operator-set ``APEX_STARVE_HBM_BYTES``
threshold.

Stdlib-only at import (like ``ledger``): jax and ``_compat`` load
lazily inside the capture functions, so the ledger's validators and
``tools/window_report.py`` never touch a backend.
"""

import os

# ------------------------------------------------- chip roofline envelope
# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind`` —
# the ONE home of the constants an MFU claim, a roofline share and a
# cost block divide by. A kind that is not here is an error
# (:func:`peaks_for`), never a default: a v5e figure stamped on another
# chip's run is wrong by construction.
#
# "TPU v5 lite" (v5e; Google Cloud documentation, "TPU v5e"): 197
# TFLOP/s bf16, 16 GB of HBM at 819 GB/s, 1,600 Gbit/s of chip-to-chip
# interconnect. The ICI figure is an ENVELOPE (payload ÷ peak, no ring
# factor, no launch latency): every comm_ms derived from it is a lower
# bound until a multi-chip run measures the real curve.
V5E_KIND = "TPU v5 lite"
V5E_PEAK_BF16_FLOPS = 197e12
V5E_HBM_BYTES_PER_S = 819e9
V5E_HBM_CAPACITY_BYTES = 16 * 2 ** 30
V5E_ICI_BYTES_PER_S_ENVELOPE = 200e9
PEAKS = {
    V5E_KIND: {"bf16_flops": V5E_PEAK_BF16_FLOPS,
               "hbm_bytes_per_s": V5E_HBM_BYTES_PER_S,
               "hbm_bytes": V5E_HBM_CAPACITY_BYTES,
               "ici_bytes_per_s": V5E_ICI_BYTES_PER_S_ENVELOPE},
}

_NUMERIC_FIELDS = (
    "xla_flops_per_step", "model_flops_per_step", "hbm_bytes_per_step",
    "peak_hbm_bytes", "peak_flops", "hbm_bytes_per_s",
    "compute_floor_ms", "bandwidth_floor_ms", "step_floor_ms",
    "mfu_bound",
)
FIELDS = ("source", "steps", "memory", "comm_bytes_per_axis") \
    + _NUMERIC_FIELDS

_MEMORY_KEYS = ("argument_size_in_bytes", "output_size_in_bytes",
                "temp_size_in_bytes", "alias_size_in_bytes",
                "generated_code_size_in_bytes")

# collective primitives counted by comm_from_jaxpr; pmean/pmax/pmin
# lower to (or are) reductions over the same axes as psum
_COLLECTIVES = ("psum", "pmean", "pmax", "pmin", "all_gather",
                "all_to_all", "ppermute", "reduce_scatter",
                "psum_scatter")


def peaks_for(device_kind):
    """The :data:`PEAKS` row for ``jax.devices()[0].device_kind``.

    None for the CPU (and for None — an analytic block with no device
    in mind): no roofline is claimed there and every derived field
    stays None. An accelerator kind absent from the table RAISES — add
    its published peaks with their source rather than borrowing
    another chip's."""
    if device_kind is None or device_kind == "cpu":
        return None
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r} — add "
            f"a row (with its source) to apex_tpu.telemetry.costs.PEAKS; "
            f"known kinds: {sorted(PEAKS)}") from None


def _peak(device_kind, field):
    row = peaks_for(device_kind)
    return None if row is None else row[field]


def peak_flops_for(device_kind):
    """The bf16 roofline peak an MFU on this device kind divides by
    (None on the CPU — CPU numbers carry no MFU)."""
    return _peak(device_kind, "bf16_flops")


def hbm_bw_for(device_kind):
    return _peak(device_kind, "hbm_bytes_per_s")


def hbm_capacity_for(device_kind):
    return _peak(device_kind, "hbm_bytes")


def ici_bw_for(device_kind):
    """The ICI bandwidth ENVELOPE an overlap_bound ``comm_ms`` divides
    by (None on the CPU — its collective bytes carry no interconnect
    claim, same rule as :func:`peak_flops_for`)."""
    return _peak(device_kind, "ici_bytes_per_s")


def wire_bytes(comm, axis_sizes):
    """The per-axis payload that actually MOVES: drop size-1 axes (a
    single-participant collective is traced but free on the wire —
    counting it would overstate every degenerate topology). Axes not
    named in ``axis_sizes`` are kept (unknown means "assume it
    moves"). The ONE home of the claim-shaping filter every harness
    applies before :func:`comm_ms_from_axis_bytes` — five private
    copies of the idiom could silently disagree about what counts as
    wire payload."""
    if not isinstance(comm, dict):
        return comm
    sizes = axis_sizes or {}
    return {ax: v for ax, v in comm.items() if sizes.get(ax, 2) > 1}


def comm_ms_from_axis_bytes(comm, device_kind):
    """Predicted per-step collective milliseconds from a
    :func:`comm_from_jaxpr` per-axis payload dict over the measured-
    interconnect envelope — the TRAINING ``comm_ms`` input of
    :func:`overlap_bound` (ROADMAP 4d: bench/profile_gpt records get
    the same gap attribution serving records already carry).

    Returns 0.0 for a traced-but-collective-free program (an empty
    dict is a real answer: nothing to hide), and None when ``comm``
    is None (untraced — no claim) or the device is the CPU (no
    envelope). Payload over peak-ICI is an ENVELOPE lower bound (see
    ``V5E_ICI_BYTES_PER_S_ENVELOPE``); the stamp is still honest —
    a gap it names can only be larger on the real wire."""
    if not isinstance(comm, dict):
        return None
    bw = ici_bw_for(device_kind)
    if bw is None:
        return None
    total = 0.0
    for v in comm.values():
        if isinstance(v, (int, float)) and not isinstance(v, bool):
            total += float(v)
    return round(total / bw * 1e3, 6)


def requested():
    """Tri-state ``APEX_COST_ANALYSIS``: True ("1"), False ("0"), or
    None (unset — the caller's default applies). A process-wide
    preference, never a raise (CLAUDE.md knob asymmetry; same parsing
    as ``compile_cache.requested``)."""
    v = os.environ.get("APEX_COST_ANALYSIS")
    if v == "1":
        return True
    if v == "0":
        return False
    return None


def enabled(default=True):
    """Whether to run the XLA captures. Real runs default ON; smoke
    callers pass ``default=False`` (a CPU sanity run should not pay
    extra host traces for numbers nobody cites — mirroring the
    ledger's and compile cache's smoke rule). Disabled still stamps
    the all-None block: degradation, never omission."""
    r = requested()
    return bool(default) if r is None else r


def null_block():
    """The all-None degradation: the backend (or the escape hatch)
    reported nothing, and the record says so explicitly instead of
    omitting the block."""
    block = {k: None for k in FIELDS}
    return block


def comm_compression_block(snapshot, uncompressed=None):
    """The comm-compression stamp for a cost block:
    ``{scheme, hierarchical, block, uncompressed_bytes_per_axis}``.
    ``snapshot`` is ``parallel.collectives.snapshot()`` (the resolved
    process-wide knobs the measured program traced under);
    ``uncompressed`` the per-axis byte counts of the program's
    uncompressed twin (traced under ``collectives.disabled()``), so a
    record claiming a payload cut carries BOTH sides of the claim.
    Returns None when nothing is compressed (the block is only stamped
    where it says something — old records stay valid without it)."""
    if not snapshot.get("scheme") and not snapshot.get("hierarchical"):
        return None
    out = {"scheme": snapshot.get("scheme"),
           "hierarchical": bool(snapshot.get("hierarchical")),
           "block": snapshot.get("block")}
    if isinstance(uncompressed, dict):
        out["uncompressed_bytes_per_axis"] = {
            str(k): float(v) for k, v in sorted(uncompressed.items())}
    return out


def overlap_bound(compute_floor_ms, host_ms=None, comm_ms=None):
    """The overlap upper bound (ROADMAP 4d seed): compute floor vs the
    comm+host time a perfectly overlapped schedule would hide behind
    it. ``host_ms`` is MEASURED non-device wall per step (e.g. the
    serving loop's scheduler/staging slice — run wall minus device
    dispatch time, per decode round); ``comm_ms`` a per-step
    collective-time estimate where a caller has one. Returns None
    when neither is known (the stamp only exists where it says
    something); fields null-degrade individually::

        {"compute_floor_ms": ...,  # the block's roofline floor
         "host_ms": ..., "comm_ms": ...,
         "comm_host_ms": ...,      # what overlap could hide
         "hideable_ms": ...,       # min(floor, comm+host) — the win
         "bound_step_ms": ...}     # max(floor, comm+host) — the best
                                   # fully-overlapped step

    ``bound_step_ms − compute_floor_ms`` is the gap every future
    overlap/scheduler PR is chasing; ``window_report`` prints it as a
    column so the gap has a name before anyone claims to have closed
    it."""
    if host_ms is None and comm_ms is None:
        return None
    comm_host = (host_ms or 0.0) + (comm_ms or 0.0)
    out = {
        "compute_floor_ms": None if compute_floor_ms is None
        else round(float(compute_floor_ms), 6),
        "host_ms": None if host_ms is None else round(float(host_ms), 6),
        "comm_ms": None if comm_ms is None else round(float(comm_ms), 6),
        "comm_host_ms": round(float(comm_host), 6),
        "hideable_ms": None, "bound_step_ms": None,
    }
    if compute_floor_ms is not None:
        out["hideable_ms"] = round(min(float(compute_floor_ms),
                                       comm_host), 6)
        out["bound_step_ms"] = round(max(float(compute_floor_ms),
                                         comm_host), 6)
    return out


def attach_overlap(block, host_ms=None, comm_ms=None):
    """Return ``block`` with an ``overlap_bound`` stamp derived from
    its own ``compute_floor_ms`` (None-degrading: a null-degraded
    block still carries the measured comm+host side). The sub-block
    is OPTIONAL in the schema — legacy cost blocks stay valid without
    it — but malformed is a finding (:func:`validate`)."""
    ob = overlap_bound(
        (block or {}).get("compute_floor_ms"), host_ms=host_ms,
        comm_ms=comm_ms)
    if ob is None:
        return block
    out = dict(block or null_block())
    out["overlap_bound"] = ob
    return out


_OVERLAP_FIELDS = ("compute_floor_ms", "host_ms", "comm_ms",
                   "comm_host_ms", "hideable_ms", "bound_step_ms")


def build(xla_flops=None, hbm_bytes=None, memory=None, comm=None,
          steps=None, model_flops_per_step=None, device_kind=None,
          source=None, comm_compression=None, host_ms=None,
          comm_ms=None):
    """Assemble a validated cost block from XLA's reported numbers.

    ``xla_flops`` / ``hbm_bytes`` are the analyses' reported counts,
    which are PER-STEP already for a K-step ``lax.scan`` program: XLA
    counts a loop body ONCE, not × trip count (calibrated on the
    installed jax, 0.9.0, Lowered and Compiled both — a 16-step scan
    of a 2·64³-flop matmul reports 524,290 flops, one body plus loop
    overhead; asserted by tests/test_costs.py so a jax that changes the
    counting fails loudly instead of silently re-breaking attribution).
    ``steps`` is metadata — the scan length of the analyzed program,
    NOT a divisor. ``memory`` is the normalized memory_analysis dict;
    ``comm`` the per-axis payload dict (per step — the caller divides
    its whole-program jaxpr walk by the scan length, since
    ``comm_from_jaxpr`` DOES multiply bodies by trip count). Floors and
    the MFU bound are derived where the inputs allow, None elsewhere."""
    block = null_block()
    block["source"] = source
    steps = int(steps) if steps else None
    block["steps"] = steps
    if xla_flops is not None:
        block["xla_flops_per_step"] = float(xla_flops)
    if hbm_bytes is not None:
        block["hbm_bytes_per_step"] = float(hbm_bytes)
    if model_flops_per_step is not None:
        block["model_flops_per_step"] = float(model_flops_per_step)
    if isinstance(memory, dict):
        block["memory"] = {k: memory.get(k) for k in _MEMORY_KEYS}
        block["peak_hbm_bytes"] = max(0, (
            (memory.get("argument_size_in_bytes") or 0)
            + (memory.get("output_size_in_bytes") or 0)
            + (memory.get("temp_size_in_bytes") or 0)
            + (memory.get("generated_code_size_in_bytes") or 0)
            - (memory.get("alias_size_in_bytes") or 0)))
    if isinstance(comm, dict):
        block["comm_bytes_per_axis"] = {str(k): float(v)
                                        for k, v in sorted(comm.items())}
    if isinstance(comm_compression, dict):
        # the quantized/hierarchical-collectives stamp
        # (comm_compression_block): which knobs shaped the traced
        # payload, and what the uncompressed twin would have moved
        block["comm_compression"] = comm_compression
    peak = peak_flops_for(device_kind)
    bw = hbm_bw_for(device_kind)
    block["peak_flops"] = peak
    block["hbm_bytes_per_s"] = bw
    if peak and block["xla_flops_per_step"] is not None:
        block["compute_floor_ms"] = round(
            block["xla_flops_per_step"] / peak * 1e3, 6)
    if bw and block["hbm_bytes_per_step"] is not None:
        block["bandwidth_floor_ms"] = round(
            block["hbm_bytes_per_step"] / bw * 1e3, 6)
    floors = [f for f in (block["compute_floor_ms"],
                          block["bandwidth_floor_ms"]) if f is not None]
    if floors:
        block["step_floor_ms"] = max(floors)
        mf = block["model_flops_per_step"] or block["xla_flops_per_step"]
        if mf and peak and block["step_floor_ms"] > 0:
            block["mfu_bound"] = round(
                mf / (block["step_floor_ms"] / 1e3) / peak, 4)
    ob = overlap_bound(block["compute_floor_ms"], host_ms=host_ms,
                       comm_ms=comm_ms)
    if ob is not None:
        # the overlap upper bound (ROADMAP 4d): stamped only when a
        # caller measured a comm/host side — optional, never omitted
        # silently once known
        block["overlap_bound"] = ob
    return block


def capture(lowered=None, compiled=None, steps=1, comm=None,
            model_flops_per_step=None, device_kind=None,
            comm_compression=None, host_ms=None, comm_ms=None):
    """The capture path: ``cost_analysis`` / ``memory_analysis`` off an
    AOT stage pair, folded into one block.

    ``compiled`` is preferred (its analyses see the optimized
    executable, and only it carries memory_analysis); ``lowered``
    degrades to flops/bytes only. With the escape hatch thrown (or no
    stage at all) returns the all-None block. An accelerator
    ``device_kind`` without published peaks raises
    (:func:`peaks_for`)."""
    if not enabled() or (lowered is None and compiled is None):
        return build(comm=comm, steps=steps,
                     model_flops_per_step=model_flops_per_step,
                     device_kind=device_kind, source=None,
                     comm_compression=comm_compression,
                     host_ms=host_ms, comm_ms=comm_ms)
    from apex_tpu import _compat

    ca = ma = None
    source = None
    if compiled is not None:
        ca = _compat.cost_analysis_dict(compiled)
        ma = _compat.memory_analysis_dict(compiled)
        if ca is not None or ma is not None:
            source = "compiled"
    if ca is None and lowered is not None:
        ca = _compat.cost_analysis_dict(lowered)
        if ca is not None and source is None:
            source = "lowered"
    return build(
        xla_flops=ca.get("flops") if ca else None,
        hbm_bytes=ca.get("bytes accessed") if ca else None,
        memory=ma, comm=comm, steps=steps,
        model_flops_per_step=model_flops_per_step,
        device_kind=device_kind,
        source=source, comm_compression=comm_compression,
        host_ms=host_ms, comm_ms=comm_ms)


# --------------------------------------------------------- comm accounting

def _aval_bytes(var):
    aval = getattr(var, "aval", None)
    size = getattr(aval, "size", None)
    dtype = getattr(aval, "dtype", None)
    if size is None or dtype is None:
        return 0
    return int(size) * int(getattr(dtype, "itemsize", 0) or 0)


def _eqn_axes(params):
    axes = params.get("axes", params.get("axis_name"))
    if axes is None:
        return ()
    if isinstance(axes, (tuple, list)):
        return tuple(a for a in axes if isinstance(a, (str, int)))
    return (axes,)


def comm_from_jaxpr(jaxpr):
    """Per-mesh-axis collective payload bytes in a (Closed)Jaxpr.

    Walks every equation, recursing into sub-jaxprs (pjit/shard_map
    bodies, cond branches) and multiplying scan/while bodies by their
    static trip count where known (a microbatch loop's collectives
    happen once per microbatch per step). Payload = summed operand
    array bytes, attributed to EACH named axis of the eqn (a
    two-axis psum moves the payload on both meshes). Returns
    ``{axis_name: bytes}`` — empty dict = traced, no collectives;
    never raises (a jaxpr shape this walker doesn't know contributes
    nothing rather than crashing a harness)."""
    totals = {}

    def visit(jxp, mult):
        eqns = getattr(jxp, "eqns", None)
        if eqns is None:  # ClosedJaxpr
            inner = getattr(jxp, "jaxpr", None)
            if inner is None:
                return
            return visit(inner, mult)
        for eqn in eqns:
            name = getattr(eqn.primitive, "name", "")
            if name in _COLLECTIVES:
                nbytes = sum(_aval_bytes(v) for v in eqn.invars) * mult
                for ax in _eqn_axes(eqn.params):
                    ax = str(ax)
                    totals[ax] = totals.get(ax, 0) + nbytes
            # trip-count multiplier for loop bodies
            inner_mult = mult
            if name == "scan":
                length = eqn.params.get("length")
                if isinstance(length, int) and length > 0:
                    inner_mult = mult * length
            for p in eqn.params.values():
                for sub in _sub_jaxprs(p):
                    visit(sub, inner_mult)

    def _sub_jaxprs(p):
        if hasattr(p, "eqns") or hasattr(p, "jaxpr"):
            yield p
        elif isinstance(p, (tuple, list)):
            for item in p:
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    yield item

    try:
        visit(jaxpr, 1)
    except Exception:
        return {}
    return {k: int(v) for k, v in totals.items()}


# -------------------------------------------- collective scheduling

# the backward-compute primitives a collective can hide behind: matmul
# and convolution carry the step's MXU work (elementwise tails are
# bandwidth noise a psum cannot meaningfully overlap)
_COMPUTE_PRIMS = ("dot_general", "conv_general_dilated")


def collective_schedule(jaxpr, axes=None):
    """The jaxpr-level overlap verdict (ROADMAP 4b, the ISSUE 14 proof
    surface): walk every equation IN ORDER (recursing into
    pjit/shard_map/custom-vjp/scan sub-jaxprs at their position, the
    same traversal as :func:`comm_from_jaxpr`) and judge whether the
    collectives interleave with remaining compute or form one terminal
    block::

        {"verdict": "interleaved" | "terminal" | "no-collectives",
         "collectives": n,            # counted collective eqns
         "compute": n,                # dot_general/conv eqn count
         "compute_after_first_collective": n}

    ``axes`` restricts WHICH collectives are judged (an iterable of
    mesh-axis names — e.g. the dp axes of a grad sync): a real
    training program carries forward collectives too (tp psums in the
    parallel CE, pp ppermutes — traced even over size-1 axes), and
    those interleave with backward compute by construction, which
    would drown the grad-sync schedule the claim is about. With
    ``axes=None`` every collective counts (the profile_comm dp-only
    shape needs no filter).

    ``interleaved`` iff at least one compute equation appears AFTER
    the first counted collective — the bucket-interleaved schedule
    (``overlap.bucketed``) emits each bucket's psum as its cotangents
    complete, so later-bucket collectives precede earlier-layer
    backward matmuls; the historical terminal reduction emits every
    collective after the last backward matmul. Equation order is the
    claim surface: XLA's latency-hiding scheduler may still recover
    overlap from a terminal block, but only the interleaved jaxpr
    GUARANTEES the operands are ready early — which is why the verdict
    (not a hope about the scheduler) is what tests pin. Never raises;
    an unwalkable jaxpr returns the no-collectives verdict with zero
    counts (same degradation rule as :func:`comm_from_jaxpr`)."""
    axes = None if axes is None else {str(a) for a in axes}
    order = []

    def visit(jxp):
        eqns = getattr(jxp, "eqns", None)
        if eqns is None:  # ClosedJaxpr
            inner = getattr(jxp, "jaxpr", None)
            if inner is None:
                return
            return visit(inner)
        for eqn in eqns:
            name = getattr(eqn.primitive, "name", "")
            if name in _COLLECTIVES:
                eqn_axes = {str(a) for a in _eqn_axes(eqn.params)}
                if axes is None or (eqn_axes & axes):
                    order.append("coll")
            elif name in _COMPUTE_PRIMS:
                order.append("comp")
            for p in eqn.params.values():
                if hasattr(p, "eqns") or hasattr(p, "jaxpr"):
                    visit(p)
                elif isinstance(p, (tuple, list)):
                    for item in p:
                        if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                            visit(item)

    try:
        visit(jaxpr)
    except Exception:
        order = []
    n_coll = order.count("coll")
    n_comp = order.count("comp")
    out = {"verdict": "no-collectives", "collectives": n_coll,
           "compute": n_comp, "compute_after_first_collective": 0}
    if not n_coll:
        return out
    first_coll = order.index("coll")
    after = order[first_coll + 1:].count("comp")
    out["compute_after_first_collective"] = after
    out["verdict"] = "interleaved" if after else "terminal"
    return out


# --------------------------------------------------- starvation economics

def starve_threshold():
    """Operator-set predicted-peak-HBM starvation threshold in bytes
    (``APEX_STARVE_HBM_BYTES``; None = no committed threshold yet —
    the §6 mode's boundary is unmeasured, so nothing is flagged by
    default: measured dispatch, not asserted dispatch)."""
    from apex_tpu.dispatch.tiles import env_int

    return env_int("APEX_STARVE_HBM_BYTES")


def starvation(peak_hbm_bytes, device_kind=None):
    """Pre-flight verdict for a program's predicted peak HBM:
    ``"exceeds-hbm"`` (hard infeasible on the chip),
    ``"starvation-risk"`` (above the operator-set §6 threshold), or
    None (no flag / nothing to judge)."""
    if not isinstance(peak_hbm_bytes, (int, float)) or peak_hbm_bytes <= 0:
        return None
    cap = hbm_capacity_for(device_kind)
    if cap and peak_hbm_bytes > cap:
        return "exceeds-hbm"
    thresh = starve_threshold()
    if thresh and peak_hbm_bytes > thresh:
        return "starvation-risk"
    return None


# -------------------------------------------------------------- validation

def validate(block):
    """Schema problems for one cost block (empty list = clean). Fed by
    ``ledger.validate_record`` for every record carrying ``cost`` —
    a malformed block could silently mis-attribute a headline gap."""
    problems = []
    if not isinstance(block, dict):
        return ["cost is not a dict"]
    for field in FIELDS:
        if field not in block:
            problems.append(f"missing field {field!r}")
    for field in _NUMERIC_FIELDS:
        v = block.get(field)
        if v is not None and (not isinstance(v, (int, float))
                              or isinstance(v, bool) or v < 0):
            problems.append(f"{field} is not a non-negative number")
    src = block.get("source")
    if src is not None and src not in ("compiled", "lowered",
                                       "eval_shape"):
        problems.append(f"source {src!r} not in "
                        f"('compiled', 'lowered', 'eval_shape')")
    steps = block.get("steps")
    if steps is not None and (not isinstance(steps, int)
                              or isinstance(steps, bool) or steps <= 0):
        problems.append("steps is not a positive int")
    mem = block.get("memory")
    if mem is not None:
        if not isinstance(mem, dict):
            problems.append("memory is not a dict")
        else:
            for k in _MEMORY_KEYS:
                v = mem.get(k)
                if v is not None and (not isinstance(v, int)
                                      or isinstance(v, bool) or v < 0):
                    problems.append(
                        f"memory.{k} is not a non-negative int")
    comm = block.get("comm_bytes_per_axis")
    if comm is not None:
        if not isinstance(comm, dict):
            problems.append("comm_bytes_per_axis is not a dict")
        else:
            for k, v in comm.items():
                if not isinstance(k, str) or not isinstance(
                        v, (int, float)) or isinstance(v, bool) or v < 0:
                    problems.append(
                        f"comm_bytes_per_axis[{k!r}] is not a "
                        f"non-negative number")
    ob = block.get("overlap_bound")
    if ob is not None:
        # the overlap-bound stamp (ROADMAP 4d) — OPTIONAL (legacy
        # blocks carry none), but malformed is a finding: a broken
        # stamp could name a fake overlap gap for the next PR to
        # "close"
        if not isinstance(ob, dict):
            problems.append("overlap_bound is not a dict")
        else:
            for field in _OVERLAP_FIELDS:
                if field not in ob:
                    problems.append(
                        f"overlap_bound missing field {field!r}")
                v = ob.get(field)
                if v is not None and (not isinstance(v, (int, float))
                                      or isinstance(v, bool) or v < 0):
                    problems.append(
                        f"overlap_bound.{field} is not a non-negative "
                        f"number")
    cc = block.get("comm_compression")
    if cc is not None:
        # the quantized/hierarchical-collectives stamp — OPTIONAL
        # (legacy blocks carry none), but malformed is a finding: a
        # broken stamp could pass off a compressed row as uncompressed
        if not isinstance(cc, dict):
            problems.append("comm_compression is not a dict")
        else:
            scheme = cc.get("scheme")
            if scheme is not None and not isinstance(scheme, str):
                problems.append("comm_compression.scheme is not a "
                                "string or null")
            if not isinstance(cc.get("hierarchical"), bool):
                problems.append("comm_compression.hierarchical is not "
                                "a bool")
            blk = cc.get("block")
            if blk is not None and (not isinstance(blk, int)
                                    or isinstance(blk, bool) or blk <= 0):
                problems.append("comm_compression.block is not a "
                                "positive int")
            unc = cc.get("uncompressed_bytes_per_axis")
            if unc is not None:
                if not isinstance(unc, dict):
                    problems.append("comm_compression."
                                    "uncompressed_bytes_per_axis is "
                                    "not a dict")
                else:
                    for k, v in unc.items():
                        if not isinstance(k, str) or not isinstance(
                                v, (int, float)) or isinstance(v, bool) \
                                or v < 0:
                            problems.append(
                                f"comm_compression."
                                f"uncompressed_bytes_per_axis[{k!r}] "
                                f"is not a non-negative number")
    return problems
