"""Measured dispatch as data: the per-shape kernel-selection table.

Before this module, "measured dispatch" (CLAUDE.md) was a manual
discipline — a human read a PERF.md row and hand-edited a hard-coded
default (`ops.attention._DEFAULT_IMPL`, `fused_layer_norm.USE_PALLAS`,
...). This module makes the measurement itself the dispatch artifact:
``apex_tpu/dispatch/table.jsonl`` holds one committed entry per
``(op, shape-bucket, dtype, backend)`` key, each carrying the winning
impl **and the ``ledger:<id>`` of the run that measured it**
(``benchmarks/ledger.jsonl``), so every table-driven default is
auditable back to a raw record — ``tools/check_bench_labels.py``
validates the citation and the knob pins mechanically, in tier-1.

Consulted at trace time by the five Pallas op families
(attention/rows, layer-norm, scale-mask softmax, fused LM head, and
the serving decode-attention kernel), the FusedLAMB ``impl``
structure, the trunk remat policy and the grad-comm scheme — strictly
BELOW any explicit signal. The precedence at every call site is:

    per-call knob  >  process-wide setter  >  table entry  >  built-in

and the CLAUDE.md asymmetry is preserved: a table entry is a measured
*preference* (shapes where the chosen impl is unsupported fall back
silently, like a process-wide setter), never a demand — only per-call
knobs raise on un-honorable requests.

Table entries are keyed by backend, so the committed CPU-measured
demonstration rows can never leak into TPU dispatch.
``benchmarks/sweep_kv_restore.py`` is the one writer of the table.

File format — one JSON object per line::

    {"op": "attention", "bucket": "b8-d64-h16-sk1024-sq1024",
     "dtype": "bfloat16", "backend": "tpu", "choice": "rows",
     "ledger": "lg-1da2bfbbb0", "pins": {"APEX_ATTN_IMPL": "rows"},
     "measured": {...}, "rung": "gpt_rows"}

Entries may additionally carry a ``params`` payload — the per-shape
TILE geometry measured for the chosen kernel, its own citation riding
inside::

    "params": {"value": {"block_q": 256}, "ledger": "lg-...",
               "pins": {"APEX_ATTN_BLOCK_Q": "256"},
               "measured": {"256": {...}, "512": {...}}}

``lookup_params`` resolves it at trace time (strictly below per-call
tile knobs and the kernels' process-wide tile setters); legality under
the shared tile model (:mod:`apex_tpu.dispatch.tiles`) is re-checked by
the consuming kernel against the REAL call dims, so a payload measured
at the bucket shape degrades to the built-in heuristic — never a
Mosaic rejection — on a shape it can't tile. A malformed payload is
skip-and-fallback at runtime and a check-4 finding in
``tools/check_bench_labels.py``.

Shape bucketing: every dimension is rounded UP to the next power of
two (:func:`bucket`), so a measurement at b=8/s=1024 serves b=7/s=1000
but never a 2x-different working set. Dims are name-sorted in the key
so producers and consumers cannot disagree on ordering.

Env knobs: ``APEX_DISPATCH=off`` (or ``0``) disables every table
consult (the escape hatch — built-in defaults then apply unchanged);
``APEX_DISPATCH_TABLE=/path`` points at an alternative table.

Runtime reads are fault-tolerant: a corrupt line is skipped (dispatch
falls back to the built-in default for its key) — but the same line is
a tier-1 FINDING in ``check_bench_labels``, so corruption cannot
persist silently in the committed table.

This module is stdlib-only at import (``tools/check_bench_labels.py``
imports it without touching a jax backend); jax is imported lazily in
:func:`current_backend` only.
"""

import json
import os

from apex_tpu.dispatch import tiles

# allowed choices per op — the consuming call site's knob vocabulary.
# "attention" is ops.attention.fused_attention's impl; "attention_bwd"
# is attention_pallas' BWD_IMPL; "layer_norm"/"softmax" select the
# Pallas kernel vs the XLA-fused jnp path; "lm_head" is the fused
# linear-CE head vs materialized logits; "lamb" is FusedLAMB's compute
# structure; "remat" the trunk recompute granularity; "bench_batch"
# a train batch size (choice is the batch as a string; no code reads
# it, the committed row goes with the table);
# "grad_comm" the DDP gradient-sync algorithm
# (apex_tpu.parallel.collectives: int8 block quantization and/or the
# hierarchical two-stage reduction), keyed on the flat payload size.
OP_CHOICES = {
    "attention": ("flash", "rows"),
    "attention_bwd": ("monolithic", "split"),
    "layer_norm": ("jnp", "pallas"),
    "softmax": ("jnp", "pallas"),
    "lm_head": ("materialized", "fused"),
    "lamb": ("two_pass", "one_pass"),
    "remat": ("none", "selective", "full"),
    "bench_batch": None,  # any positive int (as str)
    "grad_comm": ("off", "int8", "hier", "int8_hier"),
    # bucket count of the bucket-interleaved gradient reduction
    # (apex_tpu.overlap, ISSUE 14), keyed on the flat grad payload
    # like "grad_comm" — choice is the count as a string, the
    # bench_batch convention for integer-valued ops
    "overlap_buckets": None,
    # restore path of a preempted stream with the host swap tier on
    # (serving.kv_tier, ISSUE 20): replay the known stream through the
    # packed prefill program ("recompute", vLLM's recompute
    # preemption) vs copy the swapped pages back host→device and
    # resume decode directly ("swap"). Keyed on the resumed stream's
    # token length ("s") — the crossover against a prefill dispatch
    # is shape-dependent, not a constant
    "kv_restore": ("recompute", "swap"),
}

REQUIRED_FIELDS = ("op", "bucket", "dtype", "backend", "choice", "ledger")

_cache = {}  # path -> (mtime_ns, size, entries, problems)
# trace-time consult log: (op, bucket, dtype, backend) -> choice (None =
# miss). The pin-the-label rule's answer to data-driven dispatch: a
# harness can't state its knob pins alone any more —
# Tracer.flush_ledger stamps snapshot() so every measurement records
# exactly which table entries resolved its unpinned choices.
_consults = {}


def default_path():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "table.jsonl")


def table_path():
    return os.environ.get("APEX_DISPATCH_TABLE") or default_path()


def dispatch_enabled():
    """False when ``APEX_DISPATCH`` is "off"/"0" — every lookup then
    misses and the built-in defaults apply."""
    return os.environ.get("APEX_DISPATCH", "").lower() not in ("off", "0")


def _pow2_up(n):
    n = int(n)
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


def bucket(**dims):
    """The shape-bucket key: each dim rounded UP to the next power of
    two, name-sorted — ``bucket(sq=1000, b=7)`` == ``"b8-sq1024"``."""
    return "-".join(f"{k}{_pow2_up(v)}" for k, v in sorted(dims.items()))


def normalize_dtype(dtype):
    """Canonical dtype string ("bfloat16", "float32", ...)."""
    name = getattr(dtype, "name", None)
    if name is None:
        name = getattr(dtype, "__name__", None) or str(dtype)
    return str(name)


def current_backend():
    """The active jax backend name ("tpu"/"cpu"/...). A backend that
    fails to initialise raises here like anywhere else: a consult that
    swallowed it would resolve every choice as if no table existed and
    hide the broken device behind the built-in defaults."""
    import jax

    return jax.default_backend()


def _key(entry):
    return (entry["op"], entry["bucket"], entry["dtype"], entry["backend"])


def load_table(path=None):
    """Parse the table into ``(entries, problems)`` where ``entries``
    maps ``(op, bucket, dtype, backend)`` to the LAST entry for that key
    (later lines supersede earlier — append-to-update) and ``problems``
    lists skipped lines. Runtime-tolerant: corrupt or incomplete lines
    land in ``problems`` and dispatch falls back to built-in defaults;
    the check tool turns the same list into tier-1 findings. A missing
    file is an empty table. Cached per (path, mtime, size)."""
    path = path or table_path()
    try:
        st = os.stat(path)
    except OSError:
        return {}, []
    cached = _cache.get(path)
    if cached is not None and cached[0] == (st.st_mtime_ns, st.st_size):
        return cached[1], cached[2]
    entries, problems = {}, []
    try:
        with open(path) as f:
            for lineno, line in enumerate(f, 1):
                line = line.strip()
                if not line or line.startswith("#"):
                    continue
                try:
                    e = json.loads(line)
                except ValueError as exc:
                    problems.append(f"{path}:{lineno}: unparseable ({exc})")
                    continue
                if not isinstance(e, dict) or any(
                        k not in e for k in REQUIRED_FIELDS):
                    problems.append(
                        f"{path}:{lineno}: missing required field(s) "
                        f"{[k for k in REQUIRED_FIELDS if k not in e]}")
                    continue
                entries[_key(e)] = e
    except OSError as exc:
        return {}, [f"{path}: unreadable ({exc})"]
    _cache[path] = ((st.st_mtime_ns, st.st_size), entries, problems)
    return entries, problems


def lookup_entry(op, dtype, backend=None, path=None, **dims):
    """The full table entry for this key, or None (disabled / miss)."""
    if not dispatch_enabled():
        return None
    backend = backend or current_backend()
    entries, _ = load_table(path)
    return entries.get((op, bucket(**dims), normalize_dtype(dtype),
                        backend))


def lookup(op, dtype, backend=None, path=None, **dims):
    """The measured ``choice`` for this key, or None. Invalid choices
    (not in the op's vocabulary) are treated as a miss — a bad entry
    must degrade to the built-in default, not crash a trace. Every
    lookup (hit or miss) lands in the process consult log
    (:func:`snapshot`)."""
    return lookup_params(op, dtype, backend=backend, path=path,
                         **dims)[0]


def lookup_params(op, dtype, backend=None, path=None, **dims):
    """``(choice, tile_params)`` for this key — the params form of
    :func:`lookup`. ``tile_params`` is the entry's ``params.value``
    dict when present and well-formed (``tiles.runtime_value``), else
    None: a malformed payload degrades to the heuristic tile
    (skip-and-fallback) while check 4 flags the committed line. The
    consult log records the resolved params next to the choice."""
    e = lookup_entry(op, dtype, backend=backend, path=path, **dims)
    choice, params = None, None
    if e is not None:
        choice = e.get("choice")
        allowed = OP_CHOICES.get(op)
        if allowed is not None and choice not in allowed:
            choice = None
        elif allowed is None and not str(choice).isdigit():
            # integer-valued ops (bench_batch, overlap_buckets): a
            # non-int choice is a miss, not a crash
            choice = None
        if "params" in e:
            params = tiles.runtime_value(op, e["params"])
    if dispatch_enabled():
        _consults[(op, bucket(**dims), normalize_dtype(dtype),
                   backend or current_backend())] = (choice, params)
    return choice, params


def consulted():
    """The consult log: one row per distinct key looked up in this
    process, with the choice that resolved (None = table miss, i.e. the
    built-in default applied) and, when a tile payload resolved too,
    the ``params`` the consult handed the kernel."""
    out = []
    for k, v in sorted(_consults.items(),
                       key=lambda kv: tuple(map(str, kv[0]))):
        choice, params = v
        row = {"op": k[0], "bucket": k[1], "dtype": k[2], "backend": k[3],
               "choice": choice}
        if params is not None:
            row["params"] = params
        out.append(row)
    return out


def snapshot():
    """The dispatch telemetry block stamped into every ledger record
    (Tracer.flush_ledger): ``{enabled, table,
    consulted}`` — the mechanical record of which table entries drove
    this run's unpinned choices."""
    return {"enabled": dispatch_enabled(), "table": table_path(),
            "consulted": consulted()}


def make_entry(op, dims, dtype, backend, choice, ledger_id, pins=None,
               measured=None, rung=None, params=None):
    """Build one table entry. ``pins`` are the APEX_* env knobs that
    produced the winning measurement — the checker asserts each one
    matches the cited ledger record's recorded knobs. ``params`` is the
    optional tile payload (``{"value": {...}, "ledger": ..., "pins":
    ..., "measured": ...}`` — see the module docstring), validated by
    check 4."""
    e = {"op": op, "bucket": bucket(**dims),
         "dtype": normalize_dtype(dtype), "backend": backend,
         "choice": choice, "ledger": ledger_id,
         "pins": dict(pins or {})}
    if measured:
        e["measured"] = measured
    if rung:
        e["rung"] = rung
    if params:
        e["params"] = params
    return e


def append_entry(entry, path=None):
    """Append one entry (later lines supersede earlier for their key)."""
    path = path or table_path()
    with open(path, "a") as f:
        f.write(json.dumps(entry, sort_keys=True) + "\n")


def validate_entry(entry, ledger_by_id):
    """Problems for one entry (empty = clean): vocabulary, citation
    resolution, and pin agreement — every pin in the entry must equal
    the cited ledger record's recorded value for that knob (an entry
    claiming APEX_ATTN_IMPL=rows over a record measured without the pin
    is exactly the label-drift class check_bench_labels exists for)."""
    problems = []
    for f in REQUIRED_FIELDS:
        if f not in entry:
            problems.append(f"missing field {f!r}")
    if problems:
        return problems
    op = entry["op"]
    if op not in OP_CHOICES:
        problems.append(f"unknown op {op!r}")
    else:
        allowed = OP_CHOICES[op]
        if allowed is not None and entry["choice"] not in allowed:
            problems.append(
                f"choice {entry['choice']!r} not in {allowed} for op {op!r}")
        if allowed is None and not str(entry["choice"]).isdigit():
            problems.append(f"choice {entry['choice']!r} is not an int "
                            f"string for op {op!r}")
    pins = entry.get("pins", {})
    if not isinstance(pins, dict):
        problems.append("pins is not a dict")
        pins = {}
    rid = entry["ledger"]
    rec = ledger_by_id.get(rid)
    if rec is None:
        problems.append(f"citation ledger:{rid} has no ledger record")
        return problems
    problems += _pin_problems(pins, rec.get("knobs") or {}, rid)
    return problems


def _pin_problems(pins, knobs, rid, prefix="pin"):
    """Pin-agreement findings: every pinned knob must equal the cited
    record's recorded value; a None pin asserts the knob was UNSET.
    Shared by the entry-level and params-payload validators so the two
    checks cannot drift."""
    problems = []
    for k, v in sorted(pins.items()):
        if v is None:
            if k in knobs:
                problems.append(
                    f"{prefix} {k}=unset but cited record {rid} pinned "
                    f"{k}={knobs[k]!r}")
        elif knobs.get(k) != v:
            problems.append(
                f"{prefix} {k}={v!r} does not match cited record {rid} "
                f"(measured with {k}={knobs.get(k)!r})")
    return problems


def validate_params(entry, ledger_by_id):
    """Problems for one entry's tile ``params`` payload (check 4 of
    ``tools/check_bench_labels.py``; empty when the entry has none).
    Three gates: legality under the shared tile model at the entry's
    bucket dims (a committed tile must lower), citation resolution
    (``params.ledger`` must name a real — and un-injected — record),
    and pin agreement (every ``params.pins`` knob must equal the cited
    record's recorded value). Runtime lookups skip a payload that
    fails ``tiles.runtime_value`` and fall back to the heuristic; here
    the same payload is a finding."""
    payload = entry.get("params")
    if payload is None:
        return []
    problems = tiles.validate_payload(
        entry.get("op"), entry.get("bucket"), entry.get("dtype"), payload)
    if not isinstance(payload, dict):
        return problems
    rid = payload.get("ledger")
    if isinstance(rid, str):
        rec = ledger_by_id.get(rid)
        if rec is None:
            problems.append(
                f"params citation ledger:{rid} has no ledger record")
        else:
            if rec.get("fault_plan"):
                problems.append(
                    f"params cites FAULT-INJECTED record {rid} "
                    f"(fault_plan={rec['fault_plan']})")
            pins = payload.get("pins")
            if isinstance(pins, dict):
                problems += _pin_problems(pins, rec.get("knobs") or {},
                                          rid, prefix="params pin")
    return problems


def _reset_for_tests():
    _cache.clear()
    _consults.clear()
