"""Run ledger: one structured JSONL record per profile invocation.

Every measurement harness appends a record — git SHA, APEX_* knob pins,
measured dispatch overhead, scan length K, relay-degradation stamp,
platform, per-span rows — to ``benchmarks/ledger.jsonl``. PERF.md table
captions cite records as ``ledger:<id>`` and
``tools/check_bench_labels.py`` (run in the tier-1 suite, like
``check_api_parity.py``) cross-checks the captions against the records,
so label drift of the kind that shipped the §10 "68–75 ms" caption over
an 82.6 ms log is mechanically detectable instead of a prose audit.

Record ids are content hashes (``lg-`` + sha1 of the canonical record
sans ``id``), so a record edited after the fact no longer matches its
own id — the checker flags that too.

Writes are best-effort and NEVER raise: a harness must survive a
read-only checkout. Smoke-mode runs
(``APEX_BENCH_SMOKE=1``) skip the write unless ``APEX_TELEMETRY_LEDGER``
explicitly points somewhere — CPU sanity numbers do not belong in the
measurement ledger.
"""

import hashlib
import json
import os
import time

REQUIRED_FIELDS = ("id", "ts", "harness", "git_sha", "platform", "knobs",
                   "dispatch_overhead_ms", "k", "relay")


def repo_root():
    return os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def default_path():
    return os.path.join(repo_root(), "benchmarks", "ledger.jsonl")


def ledger_path():
    return os.environ.get("APEX_TELEMETRY_LEDGER") or default_path()


def knob_pins(env=None):
    """Every ``APEX_*`` env var, sorted — the process-wide knob pins.
    Per-call knobs ride in ``extra``."""
    env = os.environ if env is None else env
    return {k: env[k] for k in sorted(env) if k.startswith("APEX_")}


# Harness-infrastructure knobs that legitimately differ between the run
# that SAVED a checkpoint and the run that RESUMES it (paths, attempt
# counters, retry budgets) — everything else an APEX_* pin names shapes
# the measured program, and a resumed timing row whose pins drifted
# from the checkpoint's is mixing two configs under one label. Shared
# by checkpoint.resume_provenance and check_bench_labels check 5 so the
# two can never disagree about what counts as drift.
INFRA_KNOB_PREFIXES = (
    "APEX_CKPT_",
    "APEX_TELEMETRY_LEDGER", "APEX_TELEMETRY_PATH",
    "APEX_COMPILE_CACHE", "APEX_FAULT_PLAN",
    "APEX_COST_ANALYSIS",
)


def measurement_pins(knobs=None):
    """The subset of ``knobs`` (default: the live environment) that
    shapes the measured program — infra knobs stripped. This is what a
    checkpoint saves and what resume-provenance pin-matching compares."""
    knobs = knob_pins() if knobs is None else knobs
    return {k: v for k, v in knobs.items()
            if not any(k.startswith(p) for p in INFRA_KNOB_PREFIXES)}


def pin_drift(saved, now):
    """Measurement-pin drift between a checkpoint's saved pins and a
    run's knobs: ``{knob: [saved, now]}`` for every measurement knob
    that differs, BOTH sides filtered through
    :func:`measurement_pins`. The ONE implementation shared by the
    provenance producer (``checkpoint.resume_provenance``) and the
    citation checker (``check_bench_labels`` check 5) — two copies of
    this comparison could disagree about what counts as drift."""
    saved = measurement_pins(saved or {})
    now = measurement_pins(now or {})
    return {k: [saved.get(k), now.get(k)]
            for k in sorted(set(saved) | set(now))
            if saved.get(k) != now.get(k)}


def git_sha():
    """HEAD commit of the repo (None when git is unavailable)."""
    import subprocess

    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=repo_root(), timeout=10,
            capture_output=True, text=True)
        sha = out.stdout.strip()
        return sha if out.returncode == 0 and sha else None
    except (OSError, subprocess.SubprocessError):
        return None


def record_id(rec):
    """Deterministic short id: sha1 over the canonical record sans id."""
    body = json.dumps({k: v for k, v in rec.items() if k != "id"},
                      sort_keys=True)
    return "lg-" + hashlib.sha1(body.encode()).hexdigest()[:10]


def make_record(harness, platform, dispatch_overhead_ms, k, relay=None,
                knobs=None, git=None, ts=None, extra=None):
    """Build (but do not write) a ledger record with its content id.

    ``relay`` is the degradation stamp: ``{"degraded": bool|None,
    "kind": str|None}`` — None/None when the harness has no detector
    (every profile harness today)."""
    rec = {
        "ts": round(time.time(), 3) if ts is None else ts,
        "harness": harness,
        "git_sha": git_sha() if git is None else git,
        "platform": platform,
        "knobs": knob_pins() if knobs is None else dict(knobs),
        "dispatch_overhead_ms": dispatch_overhead_ms,
        "k": k,
        "relay": ({"degraded": None, "kind": None} if relay is None
                  else dict(relay)),
    }
    if extra:
        rec.update(extra)
    if os.environ.get("APEX_FAULT_PLAN"):
        # any record produced under fault injection (the test-only
        # APEX_FAULT_PLAN — apex_tpu.resilience.faults) is stamped with
        # the plan hash BEFORE the content id is computed, so the stamp
        # is tamper-evident: an injected run can never masquerade as a
        # measurement (tools/check_bench_labels.py refuses citations of
        # stamped records in tier-1). An ACTIVE-but-unresolvable plan
        # (bad path, malformed JSON) still stamps — a sentinel, never a
        # silent omission that would let the record pass as clean.
        try:
            from apex_tpu.resilience import faults as _faults

            fp = _faults.plan_hash() or "fp-unresolvable"
        except Exception:
            fp = "fp-unresolvable"
        rec["fault_plan"] = fp
    rec["id"] = record_id(rec)
    return rec


def append_record(harness, platform, dispatch_overhead_ms, k, relay=None,
                  knobs=None, extra=None, path=None):
    """Append one record; returns its id, or None when the write was
    skipped (smoke mode without an explicit path) or failed (never
    raises — see module docstring)."""
    try:
        if path is None:
            from apex_tpu.dispatch.tiles import env_flag

            if (env_flag("APEX_BENCH_SMOKE")
                    and not os.environ.get("APEX_TELEMETRY_LEDGER")):
                return None
            path = ledger_path()
        rec = make_record(harness, platform, dispatch_overhead_ms, k,
                          relay=relay, knobs=knobs, extra=extra)
        with open(path, "a") as f:
            f.write(json.dumps(rec, sort_keys=True) + "\n")
        return rec["id"]
    except Exception:
        return None


def read_ledger(path=None):
    """Parse a ledger file into a list of records. Raises ValueError
    (with the line number) on an unparseable OR non-object line — a
    corrupt/truncated ledger is a finding, not something to skip past
    silently, and a line truncated down to a bare JSON scalar (``42``,
    ``"harness"``) must fail here with its line number instead of
    crashing a consumer with an AttributeError later."""
    path = path or ledger_path()
    records = []
    with open(path) as f:
        for lineno, line in enumerate(f, 1):
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError as e:
                raise ValueError(f"{path}:{lineno}: unparseable ledger "
                                 f"line ({e})") from None
            if not isinstance(rec, dict):
                raise ValueError(
                    f"{path}:{lineno}: ledger line is not a JSON object "
                    f"(truncated line? parsed as {type(rec).__name__})")
            records.append(rec)
    return records


# the slo ledger block's schema (apex_tpu.serving.lifecycle builds it;
# this module owns the validation teeth, like the serving block above,
# so the stdlib-only validators never import the serving package)
SLO_FIELDS = ("ttft_p50_ms", "ttft_p99_ms", "per_token_p50_ms",
              "per_token_p99_ms", "goodput_tok_s", "slo_attainment",
              "slo_ttft_ms", "slo_tpot_ms", "arrival_process",
              "offered_load", "max_queue_depth", "kv_page_high_water",
              # resilience economics (ISSUE 15): None-when-disabled —
              # present always, so a disabled layer reads as explicit
              # degradation, never omission (check 9 refuses non-None
              # rates whose selecting knob is unpinned or off)
              "shed_rate", "preempt_rate", "degraded_rounds",
              # multi-token decode blocks (ISSUE 17): the K the row ran
              # at — a REQUIRED positive int (every engine has a block
              # size; K=1 is the single-step program, not an absence)
              "decode_block_k")
_SLO_NUMERIC = ("ttft_p50_ms", "ttft_p99_ms", "per_token_p50_ms",
                "per_token_p99_ms", "goodput_tok_s", "slo_ttft_ms",
                "slo_tpot_ms", "offered_load")
_SLO_COUNTS = ("max_queue_depth", "kv_page_high_water",
               "degraded_rounds")
_SLO_RATES = ("slo_attainment", "shed_rate", "preempt_rate")


def _validate_slo(slo):
    if not isinstance(slo, dict):
        return ["not a dict"]
    problems = []
    for field in SLO_FIELDS:
        if field not in slo:
            problems.append(f"missing field {field!r}")
    for field in _SLO_NUMERIC:
        v = slo.get(field)
        if v is not None and (not isinstance(v, (int, float))
                              or isinstance(v, bool) or v < 0):
            problems.append(f"{field} is not a non-negative number")
    for field in _SLO_COUNTS:
        v = slo.get(field)
        if v is not None and (not isinstance(v, int)
                              or isinstance(v, bool) or v < 0):
            problems.append(f"{field} is not a non-negative int")
    for field in _SLO_RATES:
        att = slo.get(field)
        if att is not None and (not isinstance(att, (int, float))
                                or isinstance(att, bool)
                                or not 0.0 <= att <= 1.0):
            problems.append(f"{field} is not in [0, 1]")
    for lo, hi in (("ttft_p50_ms", "ttft_p99_ms"),
                   ("per_token_p50_ms", "per_token_p99_ms")):
        a, b = slo.get(lo), slo.get(hi)
        if isinstance(a, (int, float)) and isinstance(b, (int, float)) \
                and not isinstance(a, bool) and not isinstance(b, bool) \
                and a > b:
            problems.append(f"{lo} exceeds {hi}")
    ap = slo.get("arrival_process")
    if "arrival_process" in slo and not (isinstance(ap, str) and ap):
        problems.append("arrival_process is not a non-empty string")
    dk = slo.get("decode_block_k")
    if "decode_block_k" in slo and (not isinstance(dk, int)
                                    or isinstance(dk, bool) or dk < 1):
        problems.append("decode_block_k is not a positive int")
    return problems


# the router ledger block's schema (apex_tpu.serving.router builds it;
# this module owns the validation teeth — same division as the slo
# block, so the stdlib-only validators never import the serving
# package). The policy vocabulary is duplicated from
# router.ROUTE_POLICIES on purpose (no serving import here);
# tests/test_router.py asserts the two tuples stay identical.
ROUTER_POLICY_VOCAB = ("round_robin", "least_loaded", "prefix_affinity")
ROUTER_FIELDS = ("route_policy", "replicas", "fleet_goodput_tok_s",
                 "util_spread", "ttft_p99_ms", "tpot_p99_ms",
                 "failovers", "replayed_requests", "requests",
                 "completed", "rejected_fleet", "rejected_replica",
                 "prefix_hit_rate_by_policy", "trace_id",
                 "arrival_process")
_ROUTER_NUMERIC = ("fleet_goodput_tok_s", "ttft_p99_ms", "tpot_p99_ms")
_ROUTER_COUNTS = ("failovers", "replayed_requests", "requests",
                  "completed", "rejected_fleet", "rejected_replica")


def _validate_router(rt):
    if not isinstance(rt, dict):
        return ["not a dict"]
    problems = []
    for field in ROUTER_FIELDS:
        if field not in rt:
            problems.append(f"missing field {field!r}")
    pol = rt.get("route_policy")
    if "route_policy" in rt and pol not in ROUTER_POLICY_VOCAB:
        problems.append(
            f"route_policy {pol!r} is not in {ROUTER_POLICY_VOCAB}")
    n = rt.get("replicas")
    if "replicas" in rt and (not isinstance(n, int)
                             or isinstance(n, bool) or n < 1):
        problems.append("replicas is not a positive int")
    for field in _ROUTER_NUMERIC:
        v = rt.get(field)
        if v is not None and (not isinstance(v, (int, float))
                              or isinstance(v, bool) or v < 0):
            problems.append(f"{field} is not a non-negative number")
    for field in _ROUTER_COUNTS:
        v = rt.get(field)
        if v is not None and (not isinstance(v, int)
                              or isinstance(v, bool) or v < 0):
            problems.append(f"{field} is not a non-negative int")
    sp = rt.get("util_spread")
    if sp is not None and (not isinstance(sp, (int, float))
                           or isinstance(sp, bool)
                           or not 0.0 <= sp <= 1.0):
        problems.append("util_spread is not in [0, 1]")
    hr = rt.get("prefix_hit_rate_by_policy")
    if hr is not None:
        # the policy sweep's proof surface: per-policy fleet hit rates
        # under the SAME trace — a malformed one could claim an
        # affinity win no sweep produced
        if not isinstance(hr, dict):
            problems.append("prefix_hit_rate_by_policy is not a dict")
        else:
            for k, v in hr.items():
                if k not in ROUTER_POLICY_VOCAB:
                    problems.append(
                        f"prefix_hit_rate_by_policy key {k!r} is not "
                        f"in {ROUTER_POLICY_VOCAB}")
                if not isinstance(v, (int, float)) \
                        or isinstance(v, bool) or not 0.0 <= v <= 1.0:
                    problems.append(
                        f"prefix_hit_rate_by_policy[{k!r}] is not in "
                        f"[0, 1]")
    for field in ("trace_id", "arrival_process"):
        v = rt.get(field)
        if field in rt and not (isinstance(v, str) and v):
            problems.append(f"{field} is not a non-empty string")
    return problems


def validate_record(rec):
    """Schema problems for one record (empty list = clean)."""
    problems = []
    for field in REQUIRED_FIELDS:
        if field not in rec:
            problems.append(f"missing field {field!r}")
    if not isinstance(rec.get("knobs", {}), dict):
        problems.append("knobs is not a dict")
    relay = rec.get("relay")
    if relay is not None and not isinstance(relay, dict):
        problems.append("relay is not a dict")
    oh = rec.get("dispatch_overhead_ms")
    if oh is not None and not isinstance(oh, (int, float)):
        problems.append("dispatch_overhead_ms is not numeric")
    if "k" in rec and rec["k"] is not None \
            and not isinstance(rec["k"], int):
        problems.append("k is not an int")
    cc = rec.get("compile_cache")
    if cc is not None:
        # the warm-start telemetry block (apex_tpu.compile_cache): a
        # malformed one could silently claim a number was compile-free
        if not isinstance(cc, dict):
            problems.append("compile_cache is not a dict")
        else:
            if not isinstance(cc.get("enabled"), bool):
                problems.append("compile_cache.enabled is not a bool")
            for field in ("hits", "misses"):
                v = cc.get(field)
                if not (isinstance(v, int) and not isinstance(v, bool)
                        and v >= 0):
                    problems.append(
                        f"compile_cache.{field} is not a non-negative int")
            if cc.get("dir") is not None \
                    and not isinstance(cc["dir"], str):
                problems.append("compile_cache.dir is not a string")
            age = cc.get("warm_age_s")
            if age is not None and not (isinstance(age, (int, float))
                                        and not isinstance(age, bool)
                                        and age >= 0):
                problems.append(
                    "compile_cache.warm_age_s is not a non-negative number")
    ck = rec.get("checkpoint")
    if ck is not None:
        # the durability telemetry block (apex_tpu.checkpoint
        # DurableCheckpointer.snapshot): a malformed one could silently
        # claim a window's state was banked when it was not
        if not isinstance(ck, dict):
            problems.append("checkpoint is not a dict")
        else:
            for field in ("saves", "queue_depth"):
                v = ck.get(field)
                if not (isinstance(v, int) and not isinstance(v, bool)
                        and v >= 0):
                    problems.append(
                        f"checkpoint.{field} is not a non-negative int")
            if ck.get("commit_ms") is not None and not isinstance(
                    ck["commit_ms"], (int, float)):
                problems.append("checkpoint.commit_ms is not numeric")
            if ck.get("last_step") is not None and not (
                    isinstance(ck["last_step"], int)
                    and not isinstance(ck["last_step"], bool)):
                problems.append("checkpoint.last_step is not an int")
    cost = rec.get("cost")
    if cost is not None:
        # the attribution block (apex_tpu.telemetry.costs): a malformed
        # one could silently mis-attribute a headline gap (wrong floor,
        # wrong MFU bound) — same teeth as the compile_cache block
        from apex_tpu.telemetry import costs as _costs

        problems += [f"cost: {p}" for p in _costs.validate(cost)]
    sv = rec.get("serving")
    if sv is not None:
        # the serving-bench block (benchmarks/profile_serving.py,
        # ISSUE 10): a malformed one could claim a tokens/s or latency
        # figure no trace produced — same teeth as the cost block
        if not isinstance(sv, dict):
            problems.append("serving is not a dict")
        else:
            for field in ("tokens_per_s", "p50_ms", "p99_ms"):
                v = sv.get(field)
                if v is not None and not (isinstance(v, (int, float))
                                          and not isinstance(v, bool)
                                          and v >= 0):
                    problems.append(
                        f"serving.{field} is not a non-negative number")
            p50, p99 = sv.get("p50_ms"), sv.get("p99_ms")
            if isinstance(p50, (int, float)) \
                    and isinstance(p99, (int, float)) and p50 > p99:
                problems.append("serving.p50_ms exceeds serving.p99_ms")
            if not (isinstance(sv.get("trace_id"), str)
                    and sv["trace_id"].startswith("tr-")):
                problems.append(
                    "serving.trace_id is not a trace hash (tr-...)")
            kp = sv.get("kv_pages")
            if not (isinstance(kp, int) and not isinstance(kp, bool)
                    and kp > 0):
                problems.append("serving.kv_pages is not a positive int")
            # generation fields (ISSUE 13): None-when-disabled is the
            # legal degradation; a present value must be a sane number
            # — a malformed rate could claim a speculation win no
            # verify chain produced. Absent fields are legacy rows.
            for field in ("spec_acceptance_rate", "prefix_hit_rate"):
                v = sv.get(field)
                if v is not None and (not isinstance(v, (int, float))
                                      or isinstance(v, bool)
                                      or not 0.0 <= v <= 1.0):
                    problems.append(
                        f"serving.{field} is not in [0, 1]")
            dl = sv.get("draft_len")
            if dl is not None and (not isinstance(dl, (int, float))
                                   or isinstance(dl, bool) or dl < 0):
                problems.append(
                    "serving.draft_len is not a non-negative number")
            # KV-tier fields (ISSUE 20): None-when-disabled like the
            # generation rates — a malformed swap_rate could claim a
            # restore economy no preemption churn produced
            kq = sv.get("kv_quant")
            if kq is not None and not isinstance(kq, bool):
                problems.append("serving.kv_quant is not a bool")
            sr = sv.get("swap_rate")
            if sr is not None and (not isinstance(sr, (int, float))
                                   or isinstance(sr, bool)
                                   or not 0.0 <= sr <= 1.0):
                problems.append("serving.swap_rate is not in [0, 1]")
            hw = sv.get("swapped_pages_high_water")
            if hw is not None and (not isinstance(hw, int)
                                   or isinstance(hw, bool) or hw < 0):
                problems.append(
                    "serving.swapped_pages_high_water is not a "
                    "non-negative int")
    slo = rec.get("slo")
    if slo is not None:
        # the SLO block (apex_tpu.serving.lifecycle.slo_block, ISSUE
        # 11): per-request tail latency + goodput under a named
        # arrival process. Malformed, it could claim an SLO attainment
        # no trace produced — same teeth as the serving block. Fields
        # may be null (a trace with no >=2-token request has no TPOT
        # percentile) but must be PRESENT: degradation, not omission.
        problems += [f"slo: {p}" for p in _validate_slo(slo)]
    rt = rec.get("router")
    if rt is not None:
        # the fleet block (apex_tpu.serving.router.router_block, ISSUE
        # 19): fleet goodput, utilization spread, cross-replica tails,
        # and the failover/replay account. Malformed, it could claim a
        # zero-loss failover or a prefix-affinity hit-rate delta no
        # fleet produced — same teeth as the slo block.
        problems += [f"router: {p}" for p in _validate_router(rt)]
    rf = rec.get("resumed_from")
    if rf is not None:
        # resume provenance (profile_gpt under APEX_CKPT_RESUME): rides
        # INSIDE the content-hashed id; check_bench_labels check 5
        # pin-matches citations of resumed records
        if not isinstance(rf, dict):
            problems.append("resumed_from is not a dict")
        else:
            if not (isinstance(rf.get("ckpt"), str)
                    and rf["ckpt"].startswith("ck-")):
                problems.append(
                    "resumed_from.ckpt is not a checkpoint id (ck-...)")
            if not (isinstance(rf.get("step"), int)
                    and not isinstance(rf.get("step"), bool)):
                problems.append("resumed_from.step is not an int")
            if not isinstance(rf.get("pins"), dict):
                problems.append("resumed_from.pins is not a dict")
    if "id" in rec and all(f in rec for f in REQUIRED_FIELDS):
        want = record_id(rec)
        if rec["id"] != want:
            problems.append(
                f"id {rec['id']!r} does not match record content "
                f"(expected {want!r}) — record edited after the fact?")
    return problems


# ------------------------------------------------------- inspection CLI
# ``python -m apex_tpu.telemetry.ledger status|tail|show <id>`` — until
# now the only ledger reader was the checker; a window operator (or the
# window-economics report) should not need a JSON one-liner to ask
# "what did this round record". Read-only; never writes the ledger.


def _summary_line(rec):
    """One human line per record: id, harness, platform, ts, verdict-ish
    marks (relay stamp / fault stamp / value / span count)."""
    import datetime

    ts = rec.get("ts")
    when = "?"
    if isinstance(ts, (int, float)):
        when = datetime.datetime.fromtimestamp(ts).strftime(
            "%Y-%m-%d %H:%M:%S")
    marks = []
    relay = rec.get("relay") or {}
    if isinstance(relay, dict) and relay.get("degraded"):
        marks.append(f"degraded:{relay.get('kind')}")
    if rec.get("fault_plan"):
        marks.append(f"INJECTED:{rec['fault_plan']}")
    if rec.get("value") is not None:
        marks.append(f"value={rec['value']}")
    if rec.get("mfu") is not None:
        marks.append(f"mfu={rec['mfu']}")
    spans = rec.get("spans")
    if isinstance(spans, list):
        marks.append(f"{len(spans)} span(s)")
    slo = rec.get("slo")
    if isinstance(slo, dict):
        att = slo.get("slo_attainment")
        # malformed attainment (a validator FINDING) must not crash
        # the summary that would surface it
        marks.append(f"slo={att:.0%}"
                     if isinstance(att, (int, float))
                     and not isinstance(att, bool) else "slo")
    cost = rec.get("cost")
    if isinstance(cost, dict) and cost.get("peak_hbm_bytes"):
        marks.append(f"peak_hbm={cost['peak_hbm_bytes'] / 2 ** 20:.0f}MiB")
    return (f"{rec.get('id', '?'):14s} {when}  "
            f"{str(rec.get('harness', '?')):22s} "
            f"{str(rec.get('platform', '?')):4s} "
            f"{' '.join(marks)}").rstrip()


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.telemetry.ledger",
        description="Inspect the run ledger (read-only).")
    ap.add_argument("--ledger", default=None,
                    help="ledger path (default: APEX_TELEMETRY_LEDGER "
                         "or benchmarks/ledger.jsonl)")
    sub = ap.add_subparsers(dest="cmd", required=True)
    sub.add_parser("status", help="record counts + schema findings")
    tail = sub.add_parser("tail", help="last N record summaries")
    tail.add_argument("n", nargs="?", type=int, default=10)
    show = sub.add_parser("show", help="pretty-print one record")
    show.add_argument("id", help="record id (lg-...)")
    args = ap.parse_args(argv)

    path = args.ledger or ledger_path()
    try:
        records = read_ledger(path)
    except FileNotFoundError:
        print(f"no ledger at {path}")
        return 1
    except ValueError as e:
        print(f"CORRUPT: {e}")
        return 1

    if args.cmd == "status":
        by_harness, problems, injected = {}, 0, 0
        for rec in records:
            h = rec.get("harness", "?")
            by_harness[h] = by_harness.get(h, 0) + 1
            if validate_record(rec):
                problems += 1
            if rec.get("fault_plan"):
                injected += 1
        print(f"{path}: {len(records)} record(s)")
        for h in sorted(by_harness):
            print(f"  {h:24s} {by_harness[h]}")
        print(f"  schema findings: {problems}; fault-injected: {injected}")
        # serving/slo account (ISSUE 11): a window operator asking
        # "what did serving bank" gets the tail-latency story, not
        # just a row count
        sv_rows = [r for r in records
                   if isinstance(r.get("serving"), dict)]
        slo_rows = [r for r in records if isinstance(r.get("slo"), dict)]
        if sv_rows or slo_rows:
            print(f"  serving: {len(sv_rows)} row(s), "
                  f"{len(slo_rows)} with slo block")
            for r in slo_rows:
                s = r["slo"]
                att = s.get("slo_attainment")
                # a malformed attainment is a schema FINDING above —
                # the status line that reports it must not crash on it
                att_s = (format(att, ".0%")
                         if isinstance(att, (int, float))
                         and not isinstance(att, bool) else "?")
                sv = r.get("serving")  # may be malformed: a finding,
                tid = (sv.get("trace_id", "?")  # never a crash here
                       if isinstance(sv, dict) else "?")
                print(f"    {r.get('id', '?')} "
                      f"{s.get('arrival_process', '?')} "
                      f"offered={s.get('offered_load')} req/tick "
                      f"attainment={att_s} "
                      f"goodput={s.get('goodput_tok_s')} tok/s "
                      f"ttft_p99={s.get('ttft_p99_ms')}ms [{tid}]")
        return 1 if problems else 0
    if args.cmd == "tail":
        # n<=0 prints nothing (records[-0:] would be the WHOLE ledger)
        for rec in records[-args.n:] if args.n > 0 else []:
            print(_summary_line(rec))
        return 0
    # show <id>
    for rec in records:
        if rec.get("id") == args.id:
            print(json.dumps(rec, indent=2, sort_keys=True))
            problems = validate_record(rec)
            for p in problems:
                print(f"FINDING: {p}")
            return 1 if problems else 0
    print(f"no record {args.id!r} in {path}")
    return 1


if __name__ == "__main__":
    import sys

    sys.exit(main())
