"""Shared tile-validity model: the one place a Pallas tile is judged.

Every hand-written kernel in ops/ picks its block geometry from a VMEM
working-set model plus Mosaic's (8, 128) last-two-dims divisibility
rule. Before this module each kernel carried its own private copy of
that arithmetic (layer_norm_pallas ``_row_block``, softmax_pallas
``_sq_block``, attention_pallas ``_q_block``/``_split_ok``, xent_pallas
``_row_block``/``_v_chunk``) and the block size itself was an
*asserted* heuristic — the one dispatch decision the measured-dispatch
rule didn't reach. This module is the single implementation the four
training families' Pallas kernels and the dispatch table's ``params``
payloads consult (the serving decode-attention kernel takes whole pages
and has no tile to choose):

* ``legal(op, dims, dtype, params)`` — the judge. Empty list = the
  tile lowers (divisibility + VMEM model); non-empty names every
  violation. Per-call tile knobs raise with exactly this list; table
  payloads and process-wide setters fall back through it silently.
* ``default_params(op, dims, dtype)`` — the heuristic each kernel
  ships today, exported so sweeps can label (and keep, under the flip
  margin) the incumbent. The heuristics themselves are UNCHANGED: the
  kernels now call these functions instead of private copies.
* ``candidates(op, dims, dtype)`` — the legal sweep set: every
  enumerated tile passes ``legal``, so a sweep never submits a
  program Mosaic rejects.
* ``parse_bucket`` / ``validate_payload`` — the checker surface
  (``tools/check_bench_labels.py`` check 4): a committed ``params``
  payload must be legal under this model at its entry's bucket dims.

Stdlib-only (like the dispatch package): the ops modules import THIS,
never the reverse, so the label checker can validate payloads without
touching a jax backend.

Vocabulary — the tile parameters each op family accepts:

=============  =====================================================
op             params
=============  =====================================================
attention      ``block_q`` (fwd + monolithic-bwd q block),
               ``bwd_block_q`` (backward-only override),
               ``block_k`` (split k-major dk/dv block)
attention_bwd  ``bwd_block_q``, ``block_k`` (same meaning; rides the
               backward-structure entry)
layer_norm     ``block_rows`` (row block, fwd + bwd)
softmax        ``block_rows`` (sq block, fwd + bwd)
lm_head        ``row_block`` (exact row block), ``vmem_budget``
               (bytes — the model cap the row block is sized under)
=============  =====================================================
"""

import os
import re

# ---------------------------------------------------------------------------
# budgets and working-set constants — mirrored FROM the kernels when this
# module was extracted; the kernels now import them from here, so the
# model and the lowering can no longer drift apart.
# ---------------------------------------------------------------------------

LANE = 128
SUBLANE = 8  # fp32 sublane granularity — the repo's kernels size to it

LN_VMEM_BUDGET = 12 * 1024 * 1024
LN_FWD_ARRAYS = 3   # x, xc, y resident per fwd block
LN_BWD_ARRAYS = 6   # x, dy, dx, xhat, wg + headroom (the binding pass)

SM_VMEM_BUDGET = 12 * 1024 * 1024
SM_FWD_ARRAYS = 3
SM_BWD_ARRAYS = 4

ATTN_VMEM_BUDGET = 10 * 1024 * 1024
ATTN_BWD_ARRAYS = 4       # S/P, dP, dS + headroom
ATTN_DROP_BWD_ARRAYS = 6  # + keep-scale and dropped-probs tiles
ATTN_SPLIT_MAX_CHUNKS = 32  # sq/bq unroll bound of the k-major pass

XENT_VMEM_BUDGET = 8 * 1024 * 1024
XENT_MAX_VCHUNK = 512
XENT_ROW_CAP = 512  # the shipped _ROW_BLOCK cap
XENT_MIN_VMEM = 1 * 1024 * 1024
XENT_MAX_VMEM = 16 * 1024 * 1024

PARAM_KEYS = {
    "attention": ("block_q", "bwd_block_q", "block_k"),
    "attention_bwd": ("bwd_block_q", "block_k"),
    "layer_norm": ("block_rows",),
    "softmax": ("block_rows",),
    "lm_head": ("row_block", "vmem_budget"),
}

# dims each op's model needs (the same names its dispatch bucket uses)
DIM_KEYS = {
    "attention": ("b", "h", "sq", "sk", "d"),
    "attention_bwd": ("b", "h", "sq", "sk", "d"),
    "layer_norm": ("rows", "hidden"),
    "softmax": ("b", "h", "sq", "sk"),
    "lm_head": ("n", "v", "h"),
}

_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "float64": 8,
                "int8": 1}


def itemsize(dtype):
    """Bytes per element for a dtype name/object (default 4)."""
    name = getattr(dtype, "name", None) or getattr(dtype, "__name__",
                                                   None) or str(dtype)
    return _DTYPE_BYTES.get(str(name), 4)


def env_int(name):
    """Positive-int env tile knob, read at TRACE time (None when unset
    or garbage — an env knob is a preference, never a raise; a
    set-but-unparseable value warns ONCE per (knob, value) like
    env_choice/env_float, so a mistyped pin on a scarce collection
    window is loud, not silently the default shape). The one parser
    behind APEX_ATTN_BLOCK_Q / APEX_LN_BLOCK_ROWS /
    APEX_SOFTMAX_BLOCK_ROWS / APEX_XENT_ROW_BLOCK / APEX_ATTN_SEQ, so
    the knob-parsing semantics cannot drift apart."""
    v = os.environ.get(name)
    if v in (None, ""):
        return None
    if v.isdigit() and int(v) > 0:
        return int(v)
    if (name, v) not in _warned_env:
        import warnings

        warnings.warn(f"{name}={v!r} is not a positive integer — "
                      f"ignored (preference semantics)")
        _warned_env.add((name, v))
    return None


_warned_env = set()


def env_nonneg_int(name):
    """Non-negative-int env preference: like :func:`env_int` but 0 is
    a LEGAL value — the explicit off-pin of count knobs
    (APEX_SPEC_DECODE: a measuring harness stamps the resolved draft
    length, and 0 means "speculation off", which the positive-only
    parser cannot express). None when unset/empty; garbage warns ONCE
    per (knob, value) and is ignored — the same preference semantics,
    one home."""
    v = os.environ.get(name)
    if v in (None, ""):
        return None
    if v.isdigit():
        return int(v)
    if (name, v) not in _warned_env:
        import warnings

        warnings.warn(f"{name}={v!r} is not a non-negative integer — "
                      f"ignored (preference semantics)")
        _warned_env.add((name, v))
    return None


def env_choice(name, allowed):
    """Enumerated env preference: the value when it is in ``allowed``,
    else None — an unknown value warns ONCE per (knob, value) and is
    ignored (env knobs are preferences, never raises; per-call
    arguments raise instead). The one implementation behind
    APEX_SERVE_WEIGHT_QUANT and APEX_SERVE_ARRIVALS, so the
    warn-once-and-ignore semantics cannot drift per module."""
    v = os.environ.get(name)
    if v in (None, ""):
        return None
    if v in allowed:
        return v
    if (name, v) not in _warned_env:
        import warnings

        warnings.warn(f"{name}={v!r} is not one of {sorted(allowed)} "
                      f"— ignored (preference semantics)")
        _warned_env.add((name, v))
    return None


def env_float(name, default):
    """Positive-float env preference: the parsed value when valid,
    else ``default`` — an unparseable or non-positive value warns
    ONCE per (knob, value) and is ignored (the same
    warn-once-and-ignore semantics as :func:`env_choice`, one home).
    Behind the serving SLO thresholds (APEX_SERVE_SLO_TTFT_MS /
    APEX_SERVE_SLO_TPOT_MS via ``serving.lifecycle.env_ms``)."""
    v = os.environ.get(name)
    if v in (None, ""):
        return float(default)
    try:
        f = float(v)
        if f > 0:
            return f
    except ValueError:
        pass
    if (name, v) not in _warned_env:
        import warnings

        warnings.warn(f"{name}={v!r} is not a positive number — "
                      f"ignored (preference semantics; default "
                      f"{float(default):g})")
        _warned_env.add((name, v))
    return float(default)


def env_flag(name):
    """Boolean env gate: True iff the var is exactly ``"1"`` — the
    parse every ``=1`` collection/arming knob in the repo uses
    (APEX_TELEMETRY, APEX_SERVE_EVENTS, APEX_BENCH_SMOKE, ...). One
    home next to env_int/env_choice/env_float so the gates cannot drift
    to ``bool(v)``-style parses per module (tools/apexlint APX002
    polices raw reads)."""
    return os.environ.get(name) == "1"


def check_setter_value(value, knob):
    """Shared validation for the kernels' process-wide tile setters:
    a positive int pins the preference, None un-pins; anything else
    raises (a setter CALL is explicit even though the pinned value
    later falls back per shape)."""
    if value is not None and (isinstance(value, bool)
                              or not isinstance(value, int)
                              or value <= 0):
        raise ValueError(f"{knob} must be a positive int or None, "
                         f"got {value!r}")


def chain_block(n, cap):
    """Largest power-of-two block ≤ cap dividing ``n`` by repeated
    doubling (the shared heuristic loop: stops at the first non-dividing
    double, exactly like the kernels' private copies did)."""
    b = 1
    while b * 2 <= cap and n % (b * 2) == 0:
        b *= 2
    return b


# ------------------------------------------------------------- layer norm

def ln_row_block(rows, hidden, n_arrays=LN_BWD_ARRAYS):
    """The layer_norm_pallas heuristic: largest power-of-two row block
    with ``n_arrays`` fp32 [block, hidden] arrays in budget, dividing
    ``rows``; 0 when even 8 rows don't fit."""
    cap = max(1, LN_VMEM_BUDGET // (4 * hidden * n_arrays))
    b = chain_block(rows, cap)
    return b if b >= SUBLANE else 0


def _ln_legal(dims, dtype, params):
    rows, hidden = dims["rows"], dims["hidden"]
    br = params.get("block_rows")
    problems = []
    if br is not None:
        if not isinstance(br, int) or br < SUBLANE or br % SUBLANE:
            problems.append(f"block_rows={br!r} must be a multiple of "
                            f"{SUBLANE} (>= {SUBLANE})")
        elif rows % br:
            problems.append(f"block_rows={br} does not divide rows={rows}")
        elif 4 * hidden * LN_BWD_ARRAYS * br > LN_VMEM_BUDGET:
            problems.append(
                f"block_rows={br}: bwd working set "
                f"{4 * hidden * LN_BWD_ARRAYS * br} B exceeds the "
                f"{LN_VMEM_BUDGET} B VMEM budget at hidden={hidden}")
    return problems


# ---------------------------------------------------------------- softmax

def sm_row_block(sq, sk, n_arrays=SM_BWD_ARRAYS):
    """softmax_pallas heuristic sq block (0 → unsupported)."""
    cap = max(1, SM_VMEM_BUDGET // (4 * sk * n_arrays))
    b = chain_block(sq, cap)
    return b if b >= SUBLANE else 0


def _sm_legal(dims, dtype, params):
    sq, sk = dims["sq"], dims["sk"]
    bsq = params.get("block_rows")
    problems = []
    if bsq is not None:
        if not isinstance(bsq, int) or bsq < SUBLANE or bsq % SUBLANE:
            problems.append(f"block_rows={bsq!r} must be a multiple of "
                            f"{SUBLANE} (>= {SUBLANE})")
        elif sq % bsq:
            problems.append(f"block_rows={bsq} does not divide sq={sq}")
        elif 4 * sk * SM_BWD_ARRAYS * bsq > SM_VMEM_BUDGET:
            problems.append(
                f"block_rows={bsq}: bwd working set "
                f"{4 * sk * SM_BWD_ARRAYS * bsq} B exceeds the "
                f"{SM_VMEM_BUDGET} B VMEM budget at sk={sk}")
    return problems


# -------------------------------------------------------------- attention

def attn_q_block(sq, sk, n_arrays=ATTN_BWD_ARRAYS, budget=None):
    """attention_pallas heuristic q block (0 → unsupported).
    ``budget`` overrides the model budget (the kernel passes its
    module-level escape hatch so tests can shrink it)."""
    cap = max(1, (budget or ATTN_VMEM_BUDGET) // (4 * sk * n_arrays))
    b = chain_block(sq, cap)
    return b if b >= SUBLANE else 0


def attn_q_problems(name, bq, sq, sk, n_arrays=ATTN_BWD_ARRAYS,
                    budget=None):
    if not isinstance(bq, int) or bq < SUBLANE or bq % SUBLANE:
        return [f"{name}={bq!r} must be a multiple of {SUBLANE} "
                f"(>= {SUBLANE})"]
    if sq % bq:
        return [f"{name}={bq} does not divide sq={sq}"]
    if 4 * sk * n_arrays * bq > (budget or ATTN_VMEM_BUDGET):
        return [f"{name}={bq}: [bq, sk] working set "
                f"{4 * sk * n_arrays * bq} B exceeds the "
                f"{budget or ATTN_VMEM_BUDGET} B VMEM budget at sk={sk}"]
    return []


def split_ok(sq, sk, d, bq, itembytes, bk=None, budget=None):
    """VMEM eligibility of the split k-major backward (the
    attention_pallas ``_split_ok`` model, with an optional decoupled
    k block ``bk``): full [sq, d] q and dO resident, 3 [bq, bk] fp32
    chunk arrays, 2 [bk, d] fp32 accumulators, 3 [sq] stat vectors,
    sq/bq chunks unrolled; bq (and bk) lane-aligned."""
    bk = bq if bk is None else bk
    if sk % bq or bq % LANE or sq // bq > ATTN_SPLIT_MAX_CHUNKS:
        return False
    if bk % LANE or sk % bk:
        return False
    resident = (2 * sq * d * itembytes
                + 3 * bq * bk * 4
                + 2 * bk * d * 4
                + 3 * sq * 4)
    return resident <= (budget or ATTN_VMEM_BUDGET)


def _attn_legal(dims, dtype, params):
    sq, sk, d = dims["sq"], dims["sk"], dims["d"]
    problems = []
    bq = params.get("block_q")
    if bq is not None:
        problems += attn_q_problems("block_q", bq, sq, sk)
    bwd_bq = params.get("bwd_block_q")
    if bwd_bq is not None:
        problems += attn_q_problems("bwd_block_q", bwd_bq, sq, sk)
    bk = params.get("block_k")
    if bk is not None:
        if not isinstance(bk, int) or bk < LANE or bk % LANE:
            problems.append(f"block_k={bk!r} must be a multiple of "
                            f"{LANE} (lane-dim split blocks)")
        elif sk % bk:
            problems.append(f"block_k={bk} does not divide sk={sk}")
        else:
            eff_bq = bwd_bq or bq or attn_q_block(sq, sk)
            if not eff_bq or not split_ok(sq, sk, d, eff_bq,
                                          itemsize(dtype), bk):
                problems.append(
                    f"block_k={bk}: split backward ineligible at "
                    f"sq={sq} sk={sk} d={d} bq={eff_bq} "
                    f"(lane alignment / chunk unroll / VMEM model)")
    return problems


# ------------------------------------------------------------ xent / head

def xent_v_chunk(V):
    """Largest multiple-of-128 divisor of V ≤ XENT_MAX_VCHUNK (0 →
    unsupported) — the xent_pallas vocab chunk."""
    for bv in range(XENT_MAX_VCHUNK, 0, -LANE):
        if V % bv == 0:
            return bv
    return 0


def xent_row_cap(h, bv, budget=XENT_VMEM_BUDGET):
    """The VMEM-model row cap for the xent backward kernels (the
    binding dE/dx working sets): rows r such that 6*bv*h + r *
    max(8h+8bv, 6h+10bv) fits ``budget``; 0 when the fixed [bv, h]
    tiles alone overflow."""
    fixed = 6 * bv * h
    if fixed >= budget:
        return 0
    per_row = max(8 * h + 8 * bv, 6 * h + 10 * bv)
    return (budget - fixed) // per_row


def xent_row_block(n, h, bv, cap=XENT_ROW_CAP, budget=XENT_VMEM_BUDGET):
    """The xent_pallas heuristic: largest power-of-two ≥ 8 dividing
    ``n`` under min(cap, VMEM-model cap); 0 → unsupported."""
    model = xent_row_cap(h, bv, budget)
    if model <= 0:
        return 0
    lim = min(cap, model)
    b, best = SUBLANE, 0
    while b <= lim:
        if n % b == 0:
            best = b
        b *= 2
    return best


def _xent_legal(dims, dtype, params):
    n, V, h = dims["n"], dims["v"], dims["h"]
    problems = []
    budget = params.get("vmem_budget")
    if budget is not None:
        if not isinstance(budget, int) \
                or not XENT_MIN_VMEM <= budget <= XENT_MAX_VMEM:
            problems.append(
                f"vmem_budget={budget!r} outside "
                f"[{XENT_MIN_VMEM}, {XENT_MAX_VMEM}] bytes")
            budget = None
    br = params.get("row_block")
    if br is not None:
        bv = xent_v_chunk(V)
        if bv == 0:
            problems.append(f"v={V} has no lane-aligned vocab chunk "
                            f"<= {XENT_MAX_VCHUNK}")
        elif not isinstance(br, int) or br < SUBLANE or br % SUBLANE:
            problems.append(f"row_block={br!r} must be a multiple of "
                            f"{SUBLANE} (>= {SUBLANE})")
        elif n % br:
            problems.append(f"row_block={br} does not divide n={n}")
        else:
            model = xent_row_cap(h, bv, budget or XENT_VMEM_BUDGET)
            if br > model:
                problems.append(
                    f"row_block={br} exceeds the VMEM-model cap {model} "
                    f"at h={h} bv={bv} (budget "
                    f"{budget or XENT_VMEM_BUDGET} B)")
    return problems


# ----------------------------------------------------------- the surface

_LEGAL = {
    "attention": _attn_legal,
    "attention_bwd": _attn_legal,
    "layer_norm": _ln_legal,
    "softmax": _sm_legal,
    "lm_head": _xent_legal,
}


def legal(op, dims, dtype, params):
    """Problems for one tile-params dict at these dims (empty = the
    tile lowers under the model). Unknown ops / unknown param names /
    missing dims are problems, never crashes — the checker feeds this
    arbitrary committed payloads."""
    if op not in _LEGAL:
        return [f"op {op!r} takes no tile params"]
    if not isinstance(params, dict) or not params:
        return [f"params must be a non-empty dict, got {params!r}"]
    problems = [f"unknown param {k!r} for op {op!r} "
                f"(vocabulary: {PARAM_KEYS[op]})"
                for k in sorted(params) if k not in PARAM_KEYS[op]]
    missing = [k for k in DIM_KEYS[op] if k not in dims]
    if missing:
        return problems + [f"missing dim(s) {missing} for op {op!r}"]
    known = {k: v for k, v in params.items() if k in PARAM_KEYS[op]}
    return problems + _LEGAL[op](dims, dtype, known)


def model_vmem_bytes(op, dims, dtype, params=None):
    """The model's predicted VMEM working set (bytes) for a tile — the
    same arithmetic :func:`legal` budgets against, exposed as a number
    so it can be VALIDATED against XLA's accounting instead of only
    asserted. ``params`` defaults to the heuristic tile. None when the
    op/shape is unsupported or the dims are incomplete."""
    params = params or default_params(op, dims, dtype)
    if params is None or any(k not in dims for k in DIM_KEYS.get(op, ("_",))):
        return None
    if op in ("attention", "attention_bwd"):
        sq, sk, d = dims["sq"], dims["sk"], dims["d"]
        bq = params.get("bwd_block_q") or params.get("block_q") \
            or attn_q_block(sq, sk)
        if not bq:
            return None
        bk = params.get("block_k")
        if bk:  # split k-major backward resident set (split_ok's model)
            return (2 * sq * d * itemsize(dtype) + 3 * bq * bk * 4
                    + 2 * bk * d * 4 + 3 * sq * 4)
        return 4 * sk * ATTN_BWD_ARRAYS * bq
    if op == "layer_norm":
        br = params.get("block_rows") \
            or ln_row_block(dims["rows"], dims["hidden"])
        return 4 * dims["hidden"] * LN_BWD_ARRAYS * br if br else None
    if op == "softmax":
        br = params.get("block_rows") or sm_row_block(dims["sq"],
                                                      dims["sk"])
        return 4 * dims["sk"] * SM_BWD_ARRAYS * br if br else None
    if op == "lm_head":
        bv = xent_v_chunk(dims["v"])
        budget = params.get("vmem_budget") or XENT_VMEM_BUDGET
        br = params.get("row_block") \
            or xent_row_block(dims["n"], dims["h"], bv, budget=budget)
        if not bv or not br:
            return None
        h = dims["h"]
        return 6 * bv * h + br * max(8 * h + 8 * bv, 6 * h + 10 * bv)
    return None


def compare_vmem(op, dims, dtype, params, xla_bytes):
    """Validation hook: the model's predicted working set vs XLA's
    measured number for the same kernel program (e.g. the ``cost``
    block's ``memory.temp_size_in_bytes`` captured by
    ``apex_tpu.telemetry.costs`` off an AOT-compiled kernel scan).

    Returns ``{"model_bytes", "xla_bytes", "ratio", "within"}`` or None
    when either side can't report. ``within`` is a coarse 4x band in
    either direction — XLA's temp accounting includes pipeline
    double-buffering, layout padding and fusion scratch the model
    deliberately ignores, so the hook catches ORDER-OF-MAGNITUDE model
    drift (the failure mode that would let a "legal" tile spill), not
    byte equality. A committed tighter band needs a device measurement
    first (measured dispatch, not asserted dispatch)."""
    model = model_vmem_bytes(op, dims, dtype, params)
    if model is None or not isinstance(xla_bytes, (int, float)) \
            or xla_bytes <= 0:
        return None
    ratio = float(xla_bytes) / float(model)
    return {"model_bytes": int(model), "xla_bytes": int(xla_bytes),
            "ratio": round(ratio, 3),
            "within": 0.25 <= ratio <= 4.0}


def default_params(op, dims, dtype):
    """The shipped heuristic's tile for these dims — what the kernel
    picks with no knob set (the sweep's incumbent). None when the
    shape is unsupported outright."""
    if op in ("attention", "attention_bwd"):
        bq = attn_q_block(dims["sq"], dims["sk"])
        return {"block_q": bq} if bq else None
    if op == "layer_norm":
        br = ln_row_block(dims["rows"], dims["hidden"])
        return {"block_rows": br} if br else None
    if op == "softmax":
        bsq = sm_row_block(dims["sq"], dims["sk"])
        return {"block_rows": bsq} if bsq else None
    if op == "lm_head":
        bv = xent_v_chunk(dims["v"])
        if not bv:
            return None
        br = xent_row_block(dims["n"], dims["h"], bv)
        return {"row_block": br} if br else None
    return None


def candidates(op, dims, dtype, max_candidates=8):
    """The legal sweep set: power-of-two tiles around the heuristic,
    incumbent FIRST (the hysteresis baseline), every one re-checked
    through :func:`legal` so a sweep can never submit a tile that
    fails to lower. Empty when the shape is unsupported."""
    base = default_params(op, dims, dtype)
    if base is None:
        return []
    key = next(iter(base))  # the primary (swept) tile parameter
    out, seen = [], set()

    def add(params):
        t = tuple(sorted(params.items()))
        if t in seen or legal(op, dims, dtype, params):
            return
        seen.add(t)
        out.append(dict(params))

    add(base)
    # pow2 neighborhood of the incumbent: /8 .. x4 (tiles far below the
    # VMEM cap re-read the streamed operands proportionally more — a
    # sweep minute is better spent near the cap; the per-call knob can
    # still request anything legal).
    b = max(SUBLANE, base[key] // 8)
    while b <= base[key] * 4:
        add({key: b})
        b *= 2
    if op in ("attention", "attention_bwd"):
        # the split k-major block rides the bwd entry: sweep block_k at
        # the heuristic q block where the split pass is eligible at all
        bq = base["block_q"]
        bk = LANE
        while bk <= dims["sk"]:
            add({"block_q": bq, "block_k": bk})
            bk *= 2
    return out[:max_candidates]


_BUCKET_DIM_RE = re.compile(r"([a-z_]+)([0-9]+)")


def parse_bucket(bucket):
    """Invert :func:`apex_tpu.dispatch.bucket`: ``"b8-sq1024"`` →
    ``{"b": 8, "sq": 1024}`` (None on malformed input). The parsed
    dims are the pow2-rounded bucket dims — the shape the committed
    legality guarantee is stated at; runtime re-checks against the
    real call dims and falls back silently when they disagree."""
    dims = {}
    for part in str(bucket).split("-"):
        m = _BUCKET_DIM_RE.fullmatch(part)
        if not m:
            return None
        dims[m.group(1)] = int(m.group(2))
    return dims or None


def validate_payload(op, bucket, dtype, payload):
    """Checker surface (check 4): structural + legality problems for
    one entry's ``params`` payload (citation/pin resolution is the
    caller's job — it needs the ledger). Payload format::

        {"value": {"block_rows": 64}, "ledger": "lg-...",
         "pins": {...}, "measured": {...}}
    """
    if not isinstance(payload, dict):
        return [f"params payload is not a dict: {payload!r}"]
    problems = []
    value = payload.get("value")
    if not isinstance(value, dict) or not value:
        return [f"params.value must be a non-empty dict, got {value!r}"]
    if not isinstance(payload.get("ledger"), str):
        problems.append("params.ledger missing (a tile payload must "
                        "cite the record that measured it)")
    if "pins" in payload and not isinstance(payload["pins"], dict):
        problems.append("params.pins is not a dict")
    dims = parse_bucket(bucket)
    if dims is None:
        return problems + [f"unparseable bucket {bucket!r}"]
    return problems + legal(op, dims, dtype, value)


def runtime_value(op, payload):
    """The tile dict a consult applies at trace time, or None when the
    payload is malformed (skip-and-fallback: a corrupt committed line
    must degrade to the heuristic, never take down a trace — the same
    line is a check-4 finding)."""
    if not isinstance(payload, dict):
        return None
    value = payload.get("value")
    if not isinstance(value, dict) or not value:
        return None
    if any(k not in PARAM_KEYS.get(op, ()) or not isinstance(v, int)
           or isinstance(v, bool) for k, v in value.items()):
        return None
    return dict(value)
