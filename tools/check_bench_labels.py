#!/usr/bin/env python
"""Cross-check PERF.md bench-table captions against benchmarks/ledger.jsonl.

The repo's measurement rule is "pin the label to what was measured"
(CLAUDE.md); rounds 1-2 shipped wrong headline numbers and the round-5
§10 caption said "dispatch overhead 68-75 ms" over a log that recorded
82.6 ms — label drift that only a prose audit caught. This tool makes
that class of drift mechanical. It runs in the tier-1 suite
(tests/test_bench_labels.py), like tools/check_api_parity.py.

Checks:

1. **Ledger schema** — every ledger line parses; every record carries
   the required fields (apex_tpu.telemetry.ledger.REQUIRED_FIELDS);
   ids are unique AND match their record's content hash (an id is a
   sha1 over the canonical record, so a record edited after the fact
   no longer matches its own id). Records carrying the warm-start
   telemetry block (``compile_cache: {enabled, dir, hits, misses,
   warm_age_s}`` — apex_tpu.compile_cache) must carry it well-formed:
   a malformed block could silently claim a number was compile-free.
2. **Caption cross-check** — every ``ledger:<id>`` citation in PERF.md
   must resolve to a ledger record, and any "dispatch overhead X ms"
   (or "X-Y ms" range) stated in the citing paragraph must agree with
   AT LEAST ONE cited record's ``dispatch_overhead_ms``: a single value
   within ±0.15 ms (captions round to 0.1), a range must bracket it.
   (At-least-one, not all: an A/B paragraph legitimately cites two
   records with two different overheads.)
3. **Dispatch table** (``apex_tpu/dispatch/table.jsonl``) — every
   entry parses and carries the required fields, its op/choice is in
   the vocabulary, its ``ledger`` id resolves to a record, and every
   knob in its ``pins`` matches the cited record's recorded ``knobs``
   (a table entry claiming APEX_ATTN_IMPL=rows over a record measured
   without the pin is the same label-drift class as a wrong caption —
   runtime lookups skip a corrupt line and fall back, but here it is a
   finding).
4. **Tile params payloads** — every entry carrying a ``params``
   payload (the per-shape tile geometry) must be LEGAL under the shared
   tile model (``apex_tpu.dispatch.tiles``: VMEM working set +
   (8, 128)-divisibility at the entry's bucket dims — a committed tile
   must lower), cite a resolving, un-injected ``params.ledger``
   record, and carry ``params.pins`` matching that record's knobs.
   Runtime consults skip a malformed payload and fall back to the
   kernel heuristic; here it is a finding, so corruption cannot
   persist in the committed table.
5. **Resume provenance** — a cited record carrying ``resumed_from``
   (profile_gpt under ``APEX_CKPT_RESUME``: the run restored a
   checkpointed TrainState and continued) must pin-match: the
   measurement pins saved in the checkpoint
   (``resumed_from.pins``, filtered by
   ``ledger.measurement_pins``) must equal the restored run's own
   recorded ``knobs`` — a resumed timing row whose pins drifted is
   mixing two configs under one label. And any paragraph making a
   COLD-start claim ("cold start", "cold compile", "cold cache")
   must not cite a resumed record at all: a run that restored state
   is not a cold start, whatever its compile-cache counters say.
   The same pin-match applies to dispatch-table entries citing
   resumed records.
6. **MFU/cost arithmetic** — a cited record that reports an ``mfu``
   AND carries a cost block with ``model_flops_per_step`` /
   ``peak_flops`` (plus ``value`` and ``config.batch``/``config.s``)
   must be arithmetically consistent:
   ``mfu == model_flops_per_step * value / (batch * s * peak_flops)``
   within rounding tolerance. A headline MFU that disagrees with its
   own record's flops accounting is the §10 label-drift class wearing
   an attribution costume. Records without the block (legacy, or
   null-degraded backends) are skipped — no block, no claim to check.
   Applies to PERF.md citations AND dispatch-table-cited records.
7. **Comm-compression pin-match** — a cited record whose cost block
   (run-level or any span's) carries a ``comm_compression`` stamp
   claiming the payload was compressed (``scheme`` non-null) or
   hierarchically staged must PIN the selecting knob in its recorded
   ``knobs`` (``APEX_GRAD_COMPRESS``/``APEX_HIER_ALLREDUCE`` — the
   quantized collectives of ``apex_tpu.parallel.collectives``): a row
   measured with compression engaged through a process-wide setter
   alone carries no pin the label can be checked against — the same
   drift class as an unpinned A/B. Applies to PERF.md citations AND
   dispatch-table-cited records.
8. **Serving pin-match** — a cited record carrying a ``serving``
   block (``benchmarks/profile_serving.py``: {tokens_per_s, p50_ms,
   p99_ms, trace_id, kv_pages}) must PIN the serving dispatch knob
   in its recorded ``knobs``: ``APEX_SERVE_WEIGHT_QUANT``. The decode
   step's program is shaped by it (int8 vs full-precision matmuls),
   and a serving row engaged through a process-wide
   setter alone carries no pin the label can be checked against —
   same teeth as checks 6-7. The harness stamps the RESOLVED values
   into its environment before the ledger write, so an unpinned run
   cannot produce a citable serving row. Generation fields (ISSUE
   13): a block with a non-None ``spec_acceptance_rate`` /
   ``prefix_hit_rate`` was measured with speculative decode / the
   prefix cache ENGAGED and must pin ``APEX_SPEC_DECODE`` /
   ``APEX_SERVE_PREFIX_CACHE`` at a non-off value — a rate under an
   off (or missing) pin names a program the label did not run.
9. **SLO pin-match** — a cited record carrying an ``slo`` block
   (``apex_tpu.serving.lifecycle.slo_block``: TTFT/per-token
   percentiles, goodput, SLO attainment under a named arrival
   process) must PIN the knobs that shaped the claim in its recorded
   ``knobs``: the SLO thresholds (``APEX_SERVE_SLO_TTFT_MS`` /
   ``APEX_SERVE_SLO_TPOT_MS`` — attainment and goodput are FUNCTIONS
   of the thresholds), the arrival process (``APEX_SERVE_ARRIVALS``
   — offered load means nothing without it), and the scheduler
   policy (``APEX_SERVE_SCHED`` — the dispatch choice every
   tail-latency number depends on). And the block's own
   ``arrival_process`` / ``slo_ttft_ms`` / ``slo_tpot_ms`` fields
   must AGREE with the pinned values — a block claiming a diurnal
   trace under a poisson pin (or a 1000 ms attainment under a
   500 ms pin) is the same label-drift class as a wrong caption.
   Resilience teeth (ISSUE 15, the check-8 generation pattern): a
   block whose ``shed_rate`` / ``preempt_rate`` / ``degraded_rounds``
   is non-None was measured with the deadline shedder / KV-pressure
   preemption / the dispatch watchdog ENGAGED and must pin
   ``APEX_SERVE_SHED`` / ``APEX_SERVE_PREEMPT`` /
   ``APEX_SERVE_RECOVER`` at a non-off value — a rate under an off
   (or missing) pin names an engine the label did not run.
10. **Overlap pin-match** — a cited record whose cost block (run-level
    or any span's) carries an ``overlap_bound`` with a non-null
    ``host_ms``/``comm_ms`` alongside an ``overlap`` claim block
    (``benchmarks/profile_overlap.py`` / ``profile_serving.py``:
    ``{grad, buckets, prefetch, serve}`` — which overlap schedules
    the measured program ran under, ISSUE 14) must PIN the claimed
    knobs in its recorded ``knobs`` at the claimed values
    (``APEX_OVERLAP_GRAD`` / ``APEX_OVERLAP_BUCKETS`` /
    ``APEX_PREFETCH`` / ``APEX_SERVE_OVERLAP``), and — the other
    direction — a non-off pin of any of those knobs on such a record
    must appear in the claim block: a host-slice number measured
    under the pipelined engine but labeled serial (or vice versa) is
    the same drift class as checks 7-9. Records with an
    overlap_bound but no claim block (the pre-ISSUE-14 serving rows)
    predate the knobs and are skipped. Applies to PERF.md citations
    AND dispatch-table-cited records.
11. **Parallel pin-match** — ZeRO-3 and tp-serving rows (ISSUE 18).
    A cited record carrying a ``parallel`` claim block
    (``benchmarks/profile_comm.py`` / ``profile_serving.py``:
    ``{zero_stage, tp}`` — whether the measured program ran with
    params dp-sharded behind the gather-on-use hop, and at what
    serving tensor-parallel width) must PIN the selecting knobs
    (``APEX_ZERO_STAGE`` / ``APEX_SERVE_TP``) in its recorded
    ``knobs`` at the claimed values, and — the other direction — an
    ENGAGED pin (``APEX_ZERO_STAGE`` past ``0``, ``APEX_SERVE_TP``
    past ``1``) must appear in the claim block even when the record
    carries no claim at all: a throughput number measured over the
    sharded program but labeled unsharded (or vice versa) is the
    same drift class as checks 7-10, and unlike check 10 there is no
    measurement gate — the pins reshape EVERY number in the record.
    Applies to PERF.md citations AND dispatch-table-cited records.
12. **Router pin-match** — fleet rows (ISSUE 19). A cited record
    carrying a ``router`` block (``benchmarks/profile_router.py`` /
    ``apex_tpu.serving.router.router_block``: fleet goodput,
    utilization spread, cross-replica tails, failover/replay counts,
    per-policy prefix hit rates) must PIN both fleet knobs in its
    recorded ``knobs`` (``APEX_ROUTE_POLICY`` /
    ``APEX_ROUTE_REPLICAS``), and the block's own ``route_policy`` /
    ``replicas`` fields must AGREE with the pinned values — a block
    claiming a prefix-affinity hit rate under a round-robin pin (or
    a 3-replica spread under a 2-replica pin) names a fleet the
    label did not run. The other direction: an engaged fleet pin on
    a record with NO router block is a finding — a routed fleet ran
    that the label does not name (the check-11 no-measurement-gate
    pattern: the pins reshape every number in the record). Applies
    to PERF.md citations AND dispatch-table-cited records.

New PERF.md table rows must cite their ledger record id in the caption
(``ledger:<id>``) — uncited legacy paragraphs are not flagged, but they
get no drift protection either.

Usage: python tools/check_bench_labels.py [--perf PATH] [--ledger PATH]
                                          [--table PATH] [--verbose]
Exit status: 0 when clean, 1 on any finding.
"""

import argparse
import os
import re
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu import dispatch as dispatch_mod  # noqa: E402
from apex_tpu.telemetry import ledger as ledger_mod  # noqa: E402

CITE_RE = re.compile(r"ledger:(lg-[0-9a-f]{10})")
# "dispatch overhead 82.6 ms" / "dispatch overhead 68-75 ms subtracted";
# both hyphen and en-dash spell the (drift-prone) range form
OVERHEAD_RE = re.compile(
    r"dispatch overhead\s+([0-9]+(?:\.[0-9]+)?)"
    r"(?:\s*[–-]\s*([0-9]+(?:\.[0-9]+)?))?\s*ms")
TOL_MS = 0.15  # captions round to 0.1 ms
# check 5: a paragraph claiming a cold start must not cite a record
# that restored checkpointed state (both hyphen and space spellings)
COLD_RE = re.compile(r"\bcold[- ](?:start|compile|cache)", re.IGNORECASE)


def resume_problems(rec, rid):
    """Check-5 pin-match for one cited record carrying resume
    provenance; [] when clean or not resumed. The comparison is the
    SAME filter the provenance was stamped with
    (``ledger.measurement_pins``), so infra knobs (paths, attempt
    counters) can never count as drift while measurement knobs always
    do."""
    rf = rec.get("resumed_from")
    if rf is None:
        return []
    if not isinstance(rf, dict) or not isinstance(rf.get("pins"), dict):
        return [f"record {rid} carries malformed resume provenance"]
    problems = []
    # the ONE drift comparison (ledger.pin_drift, shared with the
    # provenance producer) — both sides measurement-filtered
    drift = ledger_mod.pin_drift(rf["pins"], rec.get("knobs"))
    if drift:
        detail = ", ".join(f"{k}: ckpt={s!r} run={n!r}"
                           for k, (s, n) in sorted(drift.items()))
        problems.append(
            f"record {rid} resumed from checkpoint {rf.get('ckpt')} "
            f"under DIFFERENT measurement pins ({detail}) — the row "
            f"mixes two configs under one label")
    return problems


def mfu_problems(rec, rid):
    """Check-6 arithmetic for one cited record; [] when clean or when
    the record carries no checkable (mfu, cost) pair. The recomputation
    uses ONLY fields inside the content-hashed record — value, config
    batch/s, and the cost block's model flops + peak — so a drifted MFU
    cannot be repaired by editing any one of them without breaking the
    record's own id."""
    mfu = rec.get("mfu")
    cost = rec.get("cost")
    if mfu is None or not isinstance(cost, dict):
        return []
    model_flops = cost.get("model_flops_per_step")
    peak = cost.get("peak_flops")
    value = rec.get("value")
    cfg = rec.get("config") if isinstance(rec.get("config"), dict) else {}
    b, s = cfg.get("batch"), cfg.get("s")
    inputs = (model_flops, peak, value, b, s)
    if any(not isinstance(x, (int, float)) or isinstance(x, bool)
           or x <= 0 for x in inputs):
        return []  # null-degraded block / legacy record: nothing to check
    expect = model_flops * value / (b * s * peak)
    # mfu rounds to 4 decimals, value to 0.1 — tolerate both roundings
    tol = max(5e-4, 0.002 * expect)
    if abs(mfu - expect) > tol:
        return [f"record {rid} reports mfu={mfu} but its cost block's "
                f"flops imply {expect:.4f} "
                f"(model_flops_per_step={model_flops:g}, value={value:g} "
                f"tok/s, tokens={b * s}, peak={peak:g}) — MFU/cost "
                f"arithmetic drift"]
    return []


def comm_compress_problems(rec, rid):
    """Check-7 pin-match for one cited record; [] when clean or when no
    cost block carries a compression claim. The stamp's scheme /
    hierarchical flags come from ``collectives.snapshot()`` at capture
    time, so a setter-engaged compression that never pinned its env
    knob is caught here — the record claims a compressed payload its
    pins do not select."""
    blocks = [rec.get("cost")]
    for s in rec.get("spans") or []:
        if isinstance(s, dict):
            blocks.append(s.get("cost"))
    knobs = rec.get("knobs") if isinstance(rec.get("knobs"), dict) else {}
    problems = set()
    for b in blocks:
        cc = b.get("comm_compression") if isinstance(b, dict) else None
        if not isinstance(cc, dict):
            continue
        scheme = cc.get("scheme")
        if scheme and knobs.get("APEX_GRAD_COMPRESS") != scheme:
            problems.add(
                f"record {rid} was measured with compressed collectives "
                f"(comm_compression.scheme={scheme!r}) but does not pin "
                f"APEX_GRAD_COMPRESS={scheme!r} in its knobs "
                f"(recorded: {knobs.get('APEX_GRAD_COMPRESS')!r}) — an "
                f"unpinned compressed row cannot be cited")
        if cc.get("hierarchical") \
                and knobs.get("APEX_HIER_ALLREDUCE") != "1":
            problems.add(
                f"record {rid} was measured with hierarchical "
                f"collectives (comm_compression.hierarchical=true) but "
                f"does not pin APEX_HIER_ALLREDUCE=1 in its knobs "
                f"(recorded: {knobs.get('APEX_HIER_ALLREDUCE')!r})")
    return sorted(problems)


def serving_problems(rec, rid):
    """Check-8 pin-match for one cited record; [] when clean or when
    the record carries no serving block. Both serving dispatch knobs
    must be PRESENT in the record's knobs — the resolved value is what
    the label pins; absence means the choice came from a setter or a
    default the citation cannot be audited against. Generation teeth
    (ISSUE 13): a block whose ``spec_acceptance_rate`` is non-None was
    measured with speculative decode ENGAGED, so it must pin
    ``APEX_SPEC_DECODE`` (and its pin must not be the off value 0 —
    an acceptance rate under a spec-off pin names a program the label
    did not run); same for ``prefix_hit_rate`` and
    ``APEX_SERVE_PREFIX_CACHE``. Multi-token teeth (ISSUE 17): a
    serving row must pin ``APEX_SERVE_DECODE_K`` (the block size is a
    different compiled program — an unpinned K cannot be audited), and
    when the record's slo block carries ``decode_block_k`` the pin and
    the field must agree BOTH directions (a pin naming a K the engine
    did not run, or an engine K the label does not name, both fail).
    KV-tier teeth (ISSUE 20): the two cache knobs
    ``APEX_SERVE_KV_QUANT`` / ``APEX_SERVE_KV_SWAP`` must be pinned
    (the codec and the restore path are different programs), a
    non-None ``kv_quant``/``swap_rate`` field demands its knob pinned
    ON, and a knob pinned ON demands its field non-None — both
    directions, so neither the label nor the block can claim a tier
    the other did not run."""
    sv = rec.get("serving")
    if not isinstance(sv, dict):
        return []
    knobs = rec.get("knobs") if isinstance(rec.get("knobs"), dict) else {}
    problems = []
    for knob in ("APEX_SERVE_WEIGHT_QUANT", "APEX_SERVE_DECODE_K",
                 "APEX_SERVE_KV_QUANT", "APEX_SERVE_KV_SWAP"):
        if knob not in knobs:
            problems.append(
                f"record {rid} carries a serving block but does not pin "
                f"{knob} in its knobs — an unpinned serving row cannot "
                f"be cited")
    for field, knob in (("kv_quant", "APEX_SERVE_KV_QUANT"),
                        ("swap_rate", "APEX_SERVE_KV_SWAP")):
        pin = knobs.get(knob)
        if sv.get(field) is not None and str(pin) == "0":
            problems.append(
                f"record {rid} carries serving.{field}={sv[field]!r} "
                f"but pins {knob}={pin!r} (off) — the block and the "
                f"label name different cache tiers")
        if str(pin) == "1" and field in sv and sv.get(field) is None:
            problems.append(
                f"record {rid} pins {knob}=1 but its "
                f"serving.{field} is null — a tier the label claims "
                f"left no account in the block")
    for field, knob, off in (
            ("spec_acceptance_rate", "APEX_SPEC_DECODE", "0"),
            ("prefix_hit_rate", "APEX_SERVE_PREFIX_CACHE", "0")):
        if sv.get(field) is None:
            continue
        pin = knobs.get(knob)
        if pin is None:
            problems.append(
                f"record {rid} carries serving.{field}="
                f"{sv[field]!r} but does not pin {knob} in its knobs "
                f"— an unpinned speculative/prefix row cannot be cited")
        elif str(pin) == off:
            problems.append(
                f"record {rid} carries serving.{field}={sv[field]!r} "
                f"but pins {knob}={pin!r} (off) — the block and the "
                f"label name different programs")
    slo = rec.get("slo")
    dk = slo.get("decode_block_k") if isinstance(slo, dict) else None
    pin = knobs.get("APEX_SERVE_DECODE_K")
    if dk is not None and pin is not None:
        try:
            pinned = float(pin)
        except (TypeError, ValueError):
            problems.append(
                f"record {rid} pins APEX_SERVE_DECODE_K={pin!r}, which "
                f"is not a number")
            pinned = None
        if pinned is not None and isinstance(dk, (int, float)) \
                and not isinstance(dk, bool) \
                and abs(pinned - dk) > 1e-6:
            problems.append(
                f"record {rid} slo.decode_block_k={dk!r} disagrees "
                f"with its pinned APEX_SERVE_DECODE_K={pin!r} — the "
                f"block and the label name different decode programs")
    return problems


def slo_pin_problems(rec, rid):
    """Check-9 pin-match for one cited record; [] when clean or when
    the record carries no slo block. Presence teeth first (an
    unpinned slo row cannot be audited at all), then agreement teeth
    (the pinned value must be what the block claims — the knob and
    the block ride the same content-hashed record, so neither can be
    edited to fit the other without breaking the id)."""
    slo = rec.get("slo")
    if not isinstance(slo, dict):
        return []
    knobs = rec.get("knobs") if isinstance(rec.get("knobs"), dict) else {}
    problems = []
    for knob in ("APEX_SERVE_SLO_TTFT_MS", "APEX_SERVE_SLO_TPOT_MS",
                 "APEX_SERVE_ARRIVALS", "APEX_SERVE_SCHED"):
        if knob not in knobs:
            problems.append(
                f"record {rid} carries an slo block but does not pin "
                f"{knob} in its knobs — an unpinned slo row cannot be "
                f"cited")
    arr = knobs.get("APEX_SERVE_ARRIVALS")
    ap = slo.get("arrival_process")
    if arr is not None and ap is not None and ap != arr:
        problems.append(
            f"record {rid} slo.arrival_process={ap!r} disagrees with "
            f"its pinned APEX_SERVE_ARRIVALS={arr!r} — the block and "
            f"the label name different workloads")
    for knob, field in (("APEX_SERVE_SLO_TTFT_MS", "slo_ttft_ms"),
                        ("APEX_SERVE_SLO_TPOT_MS", "slo_tpot_ms")):
        pin, val = knobs.get(knob), slo.get(field)
        if pin is None or not isinstance(val, (int, float)) \
                or isinstance(val, bool):
            continue
        try:
            pinned = float(pin)
        except (TypeError, ValueError):
            # a corrupt knob value (list, dict, unparseable string) is
            # a FINDING, never a checker crash
            problems.append(
                f"record {rid} pins {knob}={pin!r}, which is not a "
                f"number")
            continue
        if abs(pinned - val) > 1e-6:
            problems.append(
                f"record {rid} slo.{field}={val:g} disagrees with its "
                f"pinned {knob}={pinned:g} — the attainment was judged "
                f"against a threshold the label does not name")
    # resilience teeth (ISSUE 15): a non-None rate/count names an
    # ENGAGED layer — its selecting knob must be pinned non-off (the
    # check-8 generation-field pattern)
    for field, knob in (("shed_rate", "APEX_SERVE_SHED"),
                        ("preempt_rate", "APEX_SERVE_PREEMPT"),
                        ("degraded_rounds", "APEX_SERVE_RECOVER")):
        if slo.get(field) is None:
            continue
        pin = knobs.get(knob)
        if pin is None:
            problems.append(
                f"record {rid} carries slo.{field}={slo[field]!r} but "
                f"does not pin {knob} in its knobs — an unpinned "
                f"resilience row cannot be cited")
        elif str(pin) == "0":
            problems.append(
                f"record {rid} carries slo.{field}={slo[field]!r} but "
                f"pins {knob}={pin!r} (off) — the block and the label "
                f"name different engines")
    return problems


# check 10: the overlap claim fields and the knobs that select them
# (the "off" value is what an engaged claim must not pin — and what an
# omitted claim field must not be pinned past; APEX_OVERLAP_BUCKETS
# has NO off value, so any pinned count at all is "engaged")
_OVERLAP_CLAIM_KNOBS = (
    ("grad", "APEX_OVERLAP_GRAD", "off"),
    ("buckets", "APEX_OVERLAP_BUCKETS", None),
    ("prefetch", "APEX_PREFETCH", "0"),
    ("serve", "APEX_SERVE_OVERLAP", "0"),
)


def overlap_problems(rec, rid):
    """Check-10 pin-match for one cited record; [] when clean, when no
    cost block carries a non-null overlap_bound host/comm side, or
    when the record carries no ``overlap`` claim block (the
    pre-ISSUE-14 rows predate the knobs — no claim, no teeth). Both
    directions: every non-None claim field must be pinned at the
    claimed value, and every non-off pin of an overlap knob must
    appear in the claim — a measured host/comm slice is a FUNCTION of
    the overlap schedules, so an unpinned or contradicted claim names
    a program the label did not run."""
    blocks = [rec.get("cost")]
    for s in rec.get("spans") or []:
        if isinstance(s, dict):
            blocks.append(s.get("cost"))
            extra = s.get("extra")
            if isinstance(extra, dict):
                blocks.append(extra.get("cost"))
    has_ob = False
    for b in blocks:
        ob = b.get("overlap_bound") if isinstance(b, dict) else None
        if isinstance(ob, dict) and (ob.get("host_ms") is not None
                                     or ob.get("comm_ms") is not None):
            has_ob = True
            break
    claim = rec.get("overlap")
    if not has_ob or not isinstance(claim, dict):
        return []
    knobs = rec.get("knobs") if isinstance(rec.get("knobs"), dict) else {}
    problems = []
    for field, knob, off in _OVERLAP_CLAIM_KNOBS:
        val = claim.get(field)
        pin = knobs.get(knob)
        if val is not None:
            if pin is None:
                problems.append(
                    f"record {rid} claims overlap.{field}={val!r} but "
                    f"does not pin {knob} in its knobs — an unpinned "
                    f"overlap row cannot be cited")
            elif str(pin) != str(val):
                problems.append(
                    f"record {rid} claims overlap.{field}={val!r} but "
                    f"pins {knob}={pin!r} — the claim and the label "
                    f"name different schedules")
        elif pin is not None and (off is None or str(pin) != off):
            problems.append(
                f"record {rid} pins {knob}={pin!r} (engaged) but its "
                f"overlap claim omits {field!r} — the measured "
                f"host/comm slice ran a schedule the claim does not "
                f"name")
    return problems


# check 11: the parallel claim fields (ISSUE 18 — ZeRO-3 parameter
# sharding and tp-serving) and the knobs that select them; the "off"
# value is the default program the claim-less rows ran
_PARALLEL_CLAIM_KNOBS = (
    ("zero_stage", "APEX_ZERO_STAGE", "0"),
    ("tp", "APEX_SERVE_TP", "1"),
)


def parallel_problems(rec, rid):
    """Check-11 pin-match for one cited record; [] when clean. Both
    directions, with NO measurement gate (unlike check 10): a
    non-None ``parallel`` claim field must be pinned at the claimed
    value, and an engaged pin (``APEX_ZERO_STAGE`` past 0,
    ``APEX_SERVE_TP`` past 1) must be claimed — even on a record
    with no ``parallel`` block at all, because the pins reshape
    every number in the record (a sharded program cited under an
    unsharded label is the checks-7-10 drift class)."""
    claim = rec.get("parallel")
    claim = claim if isinstance(claim, dict) else {}
    knobs = rec.get("knobs") if isinstance(rec.get("knobs"), dict) else {}
    problems = []
    for field, knob, off in _PARALLEL_CLAIM_KNOBS:
        val = claim.get(field)
        pin = knobs.get(knob)
        if val is not None:
            if pin is None:
                problems.append(
                    f"record {rid} claims parallel.{field}={val!r} "
                    f"but does not pin {knob} in its knobs — an "
                    f"unpinned zero3/tp row cannot be cited")
            elif str(pin) != str(val):
                problems.append(
                    f"record {rid} claims parallel.{field}={val!r} "
                    f"but pins {knob}={pin!r} — the claim and the "
                    f"label name different programs")
        elif pin is not None and str(pin) != off:
            problems.append(
                f"record {rid} pins {knob}={pin!r} (engaged) but its "
                f"parallel claim omits {field!r} — a sharded program "
                f"ran that the label does not name")
    return problems


# check 12: the router block fields and the fleet knobs that pin them
_ROUTER_CLAIM_KNOBS = (
    ("route_policy", "APEX_ROUTE_POLICY"),
    ("replicas", "APEX_ROUTE_REPLICAS"),
)


def router_problems(rec, rid):
    """Check-12 pin-match for one cited record; [] when clean. Both
    directions, with NO measurement gate (the check-11 pattern): a
    record carrying a ``router`` block must pin both fleet knobs and
    the block's ``route_policy``/``replicas`` must agree with them;
    an engaged fleet pin on a record WITHOUT a router block is a
    finding — a routed fleet ran that the label does not name."""
    rt = rec.get("router")
    knobs = rec.get("knobs") if isinstance(rec.get("knobs"), dict) else {}
    problems = []
    if isinstance(rt, dict):
        for field, knob in _ROUTER_CLAIM_KNOBS:
            val = rt.get(field)
            pin = knobs.get(knob)
            if pin is None:
                problems.append(
                    f"record {rid} carries a router block but does "
                    f"not pin {knob} in its knobs — an unpinned fleet "
                    f"row cannot be cited")
            elif val is not None and str(pin) != str(val):
                problems.append(
                    f"record {rid} router.{field}={val!r} disagrees "
                    f"with its pinned {knob}={pin!r} — the block and "
                    f"the label name different fleets")
    else:
        for field, knob in _ROUTER_CLAIM_KNOBS:
            if knobs.get(knob) is not None:
                problems.append(
                    f"record {rid} pins {knob}={knobs[knob]!r} "
                    f"(engaged) but carries no router block — a "
                    f"routed fleet ran that the label does not name")
    return problems


def _paragraphs(text):
    """(start_lineno, paragraph_text) blocks of consecutive non-blank
    lines — the unit a caption and its numbers share."""
    out, block, start = [], [], None
    for lineno, line in enumerate(text.splitlines(), 1):
        if line.strip():
            if not block:
                start = lineno
            block.append(line)
        elif block:
            out.append((start, "\n".join(block)))
            block = []
    if block:
        out.append((start, "\n".join(block)))
    return out


def check_ledger(records):
    problems = []
    seen = {}
    for i, rec in enumerate(records, 1):
        for p in ledger_mod.validate_record(rec):
            problems.append(f"ledger record {i} ({rec.get('id', '?')}): {p}")
        rid = rec.get("id")
        if rid is not None:
            if rid in seen:
                problems.append(
                    f"ledger record {i}: duplicate id {rid!r} "
                    f"(first at record {seen[rid]})")
            else:
                seen[rid] = i
    return problems


def check_captions(perf_text, perf_path, records):
    by_id = {r.get("id"): r for r in records}
    problems = []
    cited = 0
    for lineno, para in _paragraphs(perf_text):
        ids = CITE_RE.findall(para)
        if not ids:
            continue
        cited += len(ids)
        overheads = {}  # rid -> measured dispatch_overhead_ms
        for rid in ids:
            rec = by_id.get(rid)
            if rec is None:
                problems.append(
                    f"{perf_path}:{lineno}: citation ledger:{rid} has no "
                    f"ledger record")
                continue
            if rec.get("fault_plan"):
                # fault-injected records (APEX_FAULT_PLAN chaos runs —
                # apex_tpu.resilience.faults) are test artifacts: a
                # PERF.md caption must never cite one as a measurement
                problems.append(
                    f"{perf_path}:{lineno}: citation ledger:{rid} is a "
                    f"FAULT-INJECTED record (fault_plan="
                    f"{rec['fault_plan']}) — injected runs are not "
                    f"measurements")
            # check 5: resume provenance — pin-match + cold-start gate
            for p in resume_problems(rec, rid):
                problems.append(f"{perf_path}:{lineno}: {p}")
            # check 6: MFU/cost-block arithmetic consistency
            for p in mfu_problems(rec, rid):
                problems.append(f"{perf_path}:{lineno}: {p}")
            # check 7: comm-compression pin-match
            for p in comm_compress_problems(rec, rid):
                problems.append(f"{perf_path}:{lineno}: {p}")
            # check 8: serving-block pin-match
            for p in serving_problems(rec, rid):
                problems.append(f"{perf_path}:{lineno}: {p}")
            # check 9: slo-block pin-match + threshold/arrival agreement
            for p in slo_pin_problems(rec, rid):
                problems.append(f"{perf_path}:{lineno}: {p}")
            # check 10: overlap-schedule pin-match (both directions)
            for p in overlap_problems(rec, rid):
                problems.append(f"{perf_path}:{lineno}: {p}")
            # check 11: zero3/tp parallel pin-match (both directions)
            for p in parallel_problems(rec, rid):
                problems.append(f"{perf_path}:{lineno}: {p}")
            # check 12: fleet-router pin-match (both directions)
            for p in router_problems(rec, rid):
                problems.append(f"{perf_path}:{lineno}: {p}")
            if rec.get("resumed_from") is not None \
                    and COLD_RE.search(para):
                problems.append(
                    f"{perf_path}:{lineno}: paragraph makes a cold-"
                    f"start claim but cites ledger:{rid}, which "
                    f"RESUMED from checkpoint "
                    f"{rec['resumed_from'].get('ckpt') if isinstance(rec['resumed_from'], dict) else '?'}"
                    f" — a restored run is not a cold start")
            if rec.get("dispatch_overhead_ms") is not None:
                overheads[rid] = rec["dispatch_overhead_ms"]
        if not overheads:
            continue
        # a stated overhead must match AT LEAST ONE cited record — an
        # A/B paragraph cites two records with two different overheads,
        # and each stated number belongs to one of them
        for m in OVERHEAD_RE.finditer(para):
            lo = float(m.group(1))
            hi = float(m.group(2)) if m.group(2) else None
            if hi is None:
                ok = any(abs(lo - want) <= TOL_MS
                         for want in overheads.values())
                stated = f"{lo:g} ms"
            else:
                ok = any(lo - TOL_MS <= want <= hi + TOL_MS
                         for want in overheads.values())
                stated = f"{lo:g}-{hi:g} ms"
            if not ok:
                measured = ", ".join(f"{rid}: {want:g} ms"
                                     for rid, want in overheads.items())
                problems.append(
                    f"{perf_path}:{lineno}: caption states dispatch "
                    f"overhead {stated} but no cited record measured "
                    f"that ({measured}) — label drift")
    return problems, cited


def check_dispatch_table(path, records):
    """Validate every dispatch-table entry against the ledger (check 3).
    A missing table file is clean (the subsystem is additive); corrupt
    lines — which runtime lookups skip with a silent fallback — are
    findings here, so corruption can't persist in the committed table."""
    if not os.path.exists(path):
        return [], 0
    by_id = {r.get("id"): r for r in records}
    entries, problems = dispatch_mod.load_table(path)
    problems = [f"dispatch table {p}" for p in problems]
    for key, entry in sorted(entries.items(),
                             key=lambda kv: tuple(map(str, kv[0]))):
        tag = (f"{path}: entry {entry.get('op')}/{entry.get('bucket')}"
               f"/{entry.get('dtype')}/{entry.get('backend')}")
        for p in dispatch_mod.validate_entry(entry, by_id):
            problems.append(f"{tag}: {p}")
        # check 4: tile params payloads — legality under the shared
        # tile model + citation + pin agreement
        for p in dispatch_mod.validate_params(entry, by_id):
            problems.append(f"{tag}: {p}")
        # a dispatch default must never be decided by an injected run:
        # neither the entry itself nor any record it cites may carry
        # the APEX_FAULT_PLAN stamp
        if entry.get("fault_plan"):
            problems.append(f"{tag}: entry carries a fault_plan stamp "
                            f"({entry['fault_plan']}) — produced under "
                            f"injection")
        params_payload = entry.get("params") \
            if isinstance(entry.get("params"), dict) else {}
        cited = [entry.get("ledger")] + [
            m.get("ledger") for m in (entry.get("measured") or {}).values()
            if isinstance(m, dict)] + [
            m.get("ledger")
            for m in (params_payload.get("measured") or {}).values()
            if isinstance(m, dict)]
        for rid in cited:
            rec = by_id.get(rid)
            if rec is not None and rec.get("fault_plan"):
                problems.append(
                    f"{tag}: cites FAULT-INJECTED record {rid} "
                    f"(fault_plan={rec['fault_plan']})")
            if rec is not None:
                # check 5 on the table side: a dispatch default decided
                # by a resumed run must pin-match its checkpoint
                for p in resume_problems(rec, rid):
                    problems.append(f"{tag}: {p}")
                # check 6 on the table side: same arithmetic teeth
                for p in mfu_problems(rec, rid):
                    problems.append(f"{tag}: {p}")
                # check 7 on the table side: a grad_comm entry decided
                # by a compressed row must cite a knob-pinned record
                for p in comm_compress_problems(rec, rid):
                    problems.append(f"{tag}: {p}")
                # check 8 on the table side: a decode_attention entry
                # decided by a serving row must cite a knob-pinned one
                for p in serving_problems(rec, rid):
                    problems.append(f"{tag}: {p}")
                # check 9 on the table side: same slo teeth
                for p in slo_pin_problems(rec, rid):
                    problems.append(f"{tag}: {p}")
                # check 10 on the table side: an overlap_buckets (or
                # any) entry decided by an overlap-measured row must
                # cite a knob-pinned, claim-consistent record
                for p in overlap_problems(rec, rid):
                    problems.append(f"{tag}: {p}")
                # check 11 on the table side: a default decided by a
                # zero3/tp-sharded row must cite a knob-pinned,
                # claim-consistent record
                for p in parallel_problems(rec, rid):
                    problems.append(f"{tag}: {p}")
                # check 12 on the table side: a default decided by a
                # fleet-routed row must cite a knob-pinned,
                # claim-consistent record
                for p in router_problems(rec, rid):
                    problems.append(f"{tag}: {p}")
    return problems, len(entries)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--perf", default=os.path.join(REPO, "PERF.md"))
    ap.add_argument("--ledger",
                    default=os.path.join(REPO, "benchmarks", "ledger.jsonl"))
    ap.add_argument("--table", default=dispatch_mod.default_path())
    ap.add_argument("--verbose", action="store_true")
    args = ap.parse_args(argv)

    try:
        records = ledger_mod.read_ledger(args.ledger)
    except FileNotFoundError:
        print(f"FAIL: ledger {args.ledger} does not exist")
        return 1
    except ValueError as e:
        # read_ledger names the offending file:lineno for corrupt,
        # truncated and non-object lines — the finding, not a traceback
        print(f"FAIL: {e}")
        return 1
    problems = check_ledger(records)

    with open(args.perf) as f:
        perf_text = f.read()
    cap_problems, cited = check_captions(perf_text, args.perf, records)
    problems += cap_problems

    table_problems, n_entries = check_dispatch_table(args.table, records)
    problems += table_problems

    if args.verbose:
        print(f"{len(records)} ledger records; {cited} PERF.md citations "
              f"checked; {n_entries} dispatch-table entries validated")
    if problems:
        for p in problems:
            print(f"DRIFT: {p}")
        print(f"FAIL: {len(problems)} problem(s)")
        return 1
    print("OK: ledger schema valid, no caption drift, dispatch table "
          "resolves")
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SystemExit:
        raise
    except Exception as e:  # a checker that crashes is a checker that
        # silently stops gating: any unexpected error becomes a FAIL
        # finding (tier-1 sees exit 1 + a message, never a traceback)
        print(f"FAIL: checker error: {type(e).__name__}: {e}")
        sys.exit(1)
