#!/usr/bin/env python
"""Ledger economics: one account of ``benchmarks/ledger.jsonl``.

Aggregates the run ledger into one report: per-record verdicts,
compile-cache hit/miss totals, cost-block coverage, the measured-MFU vs
MFU-bound attribution gap, the ``overlap_bound`` column (compute floor
vs comm+host — ROADMAP 4d), and the SERVING ECONOMICS section (ISSUE
11): per-trace SLO attainment, goodput vs the decode-scan throughput
line, and queue/KV-page occupancy from the ``serving``/``slo`` blocks.

    python tools/window_report.py [--ledger PATH] [--json]

Exit status 0 when the report was produced (an empty ledger is a
report, not an error); 1 only on unreadable inputs. ``--json`` appends
ONE machine-readable JSON line after the text.
"""

import argparse
import datetime
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu.telemetry import ledger as ledger_mod  # noqa: E402

def ledger_summary(records):
    """Aggregate the ledger's side of the account: per-harness counts,
    platform split, compile-cache totals, cost-block coverage, and the
    measured-vs-bound attribution rows."""
    by_harness = {}
    platforms = {}
    cc_hits = cc_misses = cc_records = 0
    cost_present = cost_reporting = 0
    injected = 0
    attribution = []
    comm_rows = []
    serving_rows = []
    overlap_rows = []
    router_rows = []
    for rec in records:
        by_harness[rec.get("harness", "?")] = \
            by_harness.get(rec.get("harness", "?"), 0) + 1
        platforms[rec.get("platform", "?")] = \
            platforms.get(rec.get("platform", "?"), 0) + 1
        if rec.get("fault_plan"):
            injected += 1
        cc = rec.get("compile_cache")
        if isinstance(cc, dict):
            cc_records += 1
            cc_hits += cc.get("hits") or 0
            cc_misses += cc.get("misses") or 0
        cost = rec.get("cost")
        if isinstance(cost, dict):
            cost_present += 1
            if cost.get("source"):
                cost_reporting += 1
            mfu = rec.get("mfu")
            bound = cost.get("mfu_bound")
            if mfu is not None and bound is not None:
                attribution.append({
                    "id": rec.get("id"), "harness": rec.get("harness"),
                    "mfu": mfu, "mfu_bound": bound,
                    "step_floor_ms": cost.get("step_floor_ms"),
                    "peak_hbm_bytes": cost.get("peak_hbm_bytes"),
                })
            # the comm column: per-axis collective payload from the
            # cost block, compressed-vs-uncompressed where the record
            # carries the collectives stamp — comm gets attributed the
            # same way flops do (ROADMAP item 3)
            comm = cost.get("comm_bytes_per_axis")
            if isinstance(comm, dict) and comm:
                stamp = cost.get("comm_compression") \
                    if isinstance(cost.get("comm_compression"), dict) \
                    else {}
                comm_rows.append({
                    "id": rec.get("id"), "harness": rec.get("harness"),
                    "bytes_per_axis": comm,
                    "scheme": stamp.get("scheme"),
                    "hierarchical": stamp.get("hierarchical"),
                    "uncompressed_bytes_per_axis":
                        stamp.get("uncompressed_bytes_per_axis"),
                })
            # the overlap column (ROADMAP 4d, costs.overlap_bound):
            # compute floor vs measured comm+host — the gap every
            # future overlap/scheduler PR is chasing, named per record
            ob = cost.get("overlap_bound")
            if isinstance(ob, dict):
                row = dict(ob, id=rec.get("id"),
                           harness=rec.get("harness"))
                # the ISSUE 14 columns: which overlap schedules the
                # record claims it measured under, and the jaxpr-level
                # collective-schedule verdict (interleaved/terminal)
                cs = rec.get("collective_schedule")
                if isinstance(cs, dict):
                    row["schedule_verdict"] = cs.get("verdict")
                claim = rec.get("overlap")
                if isinstance(claim, dict):
                    row["claim"] = claim
                overlap_rows.append(row)
        # serving economics (ISSUE 11): per-trace SLO attainment,
        # goodput vs decode-throughput gap, occupancy high-waters —
        # one row per record carrying a serving and/or slo block
        sv = rec.get("serving")
        slo = rec.get("slo")
        if isinstance(sv, dict) or isinstance(slo, dict):
            sv = sv if isinstance(sv, dict) else {}
            slo = slo if isinstance(slo, dict) else None
            serving_rows.append({
                "id": rec.get("id"), "harness": rec.get("harness"),
                "trace_id": sv.get("trace_id"),
                "tokens_per_s": sv.get("tokens_per_s"),
                "scan_tokens_per_s": sv.get("scan_tokens_per_s"),
                "kv_pages": sv.get("kv_pages"),
                # dispatch economics (ISSUE 17): decode_steps counts
                # DISPATCHES — tokens/dispatch is the K-block
                # amortization of the per-dispatch relay floor
                "decode_steps": sv.get("decode_steps"),
                "tokens_generated": sv.get("tokens_generated"),
                # generation economics (ISSUE 13): None-when-disabled
                "spec_acceptance_rate": sv.get("spec_acceptance_rate"),
                "draft_len": sv.get("draft_len"),
                "prefix_hit_rate": sv.get("prefix_hit_rate"),
                # KV-tier economics (ISSUE 20): None-when-disabled
                "kv_quant": sv.get("kv_quant"),
                "swap_rate": sv.get("swap_rate"),
                "swapped_pages_high_water":
                    sv.get("swapped_pages_high_water"),
                "slo": slo,
            })
        # fleet economics (ISSUE 19): the router block — utilization
        # spread, failover/replay account, per-policy prefix hit rates
        # — one row per record carrying it
        rt = rec.get("router")
        if isinstance(rt, dict):
            router_rows.append(dict(rt, id=rec.get("id"),
                                    harness=rec.get("harness")))
    ts = [r["ts"] for r in records
          if isinstance(r.get("ts"), (int, float))]
    return {
        "records": len(records),
        "by_harness": by_harness,
        "platforms": platforms,
        "span": ([_fmt_ts(min(ts)), _fmt_ts(max(ts))] if ts else None),
        "compile_cache": {"records": cc_records, "hits": cc_hits,
                          "misses": cc_misses},
        "cost_blocks": {"present": cost_present,
                        "reporting": cost_reporting},
        "injected": injected,
        "attribution": attribution,
        "comm": comm_rows,
        "overlap": overlap_rows,
        "serving": serving_rows,
        "router": router_rows,
    }


def _fmt_ts(ts):
    return datetime.datetime.fromtimestamp(ts).strftime(
        "%Y-%m-%d %H:%M:%S")


def build_report(ledger_path=None):
    report = {}
    if ledger_path and os.path.exists(ledger_path):
        report["ledger"] = ledger_summary(
            ledger_mod.read_ledger(ledger_path))
    return report


def print_report(report, out=None):
    out = out or sys.stdout  # resolved at call time, not import time
    p = lambda s="": print(s, file=out)  # noqa: E731
    led = report.get("ledger")
    if led:
        p(f"ledger: {led['records']} record(s)"
          + (f", {led['injected']} fault-injected" if led["injected"]
             else ""))
        if led["span"]:
            p(f"  span: {led['span'][0]} .. {led['span'][1]}")
        plat = ", ".join(f"{k}={v}" for k, v in
                         sorted(led["platforms"].items()))
        p(f"  platforms: {plat}")
        for h in sorted(led["by_harness"]):
            p(f"  {h:24s} {led['by_harness'][h]}")
        cc = led["compile_cache"]
        p(f"  compile cache: {cc['hits']} hit(s) / {cc['misses']} "
          f"miss(es) across {cc['records']} stamped record(s)")
        cb = led["cost_blocks"]
        p(f"  cost blocks: {cb['present']} present, {cb['reporting']} "
          f"with XLA numbers")
        for a in led["attribution"]:
            gap = (f", gap {a['mfu_bound'] - a['mfu']:.3f}"
                   if a["mfu_bound"] >= a["mfu"] else " (ABOVE bound — "
                   "check the model)")
            p(f"  attribution {a['id']} ({a['harness']}): measured MFU "
              f"{a['mfu']:.3f} vs bound {a['mfu_bound']:.3f}{gap}")
        for c in led.get("comm", []):
            axes = " ".join(f"{k}={int(v)}B" for k, v in
                            sorted(c["bytes_per_axis"].items()))
            line = (f"  comm {c['id']} ({c['harness']}): {axes}")
            if c.get("scheme") or c.get("hierarchical"):
                unc = c.get("uncompressed_bytes_per_axis") or {}
                unc_s = " ".join(f"{k}={int(v)}B" for k, v in
                                 sorted(unc.items()))
                line += (f" [scheme={c['scheme']}"
                         f" hier={bool(c['hierarchical'])}"
                         + (f" uncompressed: {unc_s}" if unc_s else "")
                         + "]")
            p(line)
        for o in led.get("overlap", []):
            def _ms(v):
                return "?" if v is None else f"{v:g} ms"
            line = (f"  overlap {o['id']} ({o['harness']}): compute "
                    f"floor {_ms(o.get('compute_floor_ms'))} vs "
                    f"comm+host {_ms(o.get('comm_host_ms'))}")
            if o.get("hideable_ms") is not None:
                line += (f" -> hideable {_ms(o['hideable_ms'])}, best "
                         f"overlapped step {_ms(o.get('bound_step_ms'))}")
            if o.get("schedule_verdict"):
                line += f" [schedule={o['schedule_verdict']}]"
            claim = o.get("claim")
            if isinstance(claim, dict):
                bits = " ".join(f"{k}={v}" for k, v in
                                sorted(claim.items()) if v is not None)
                if bits:
                    line += f" [{bits}]"
            p(line)
        if led.get("serving"):
            p("  serving economics:")
            for s in led["serving"]:
                tps = s.get("tokens_per_s")
                scan = s.get("scan_tokens_per_s")
                line = (f"    {s['id']} ({s['harness']}) "
                        f"[{s.get('trace_id') or '?'}]: "
                        f"{'?' if tps is None else format(tps, 'g')} "
                        f"tok/s replay")
                if scan:
                    line += f" vs {scan:g} tok/s decode-scan upper line"
                p(line)
                # dispatch economics (ISSUE 17): how many tokens each
                # ~65 ms relay dispatch bought — the K-block lever;
                # the slo block's decode_block_k names the program K
                # the trade was measured at
                toks = s.get("tokens_generated")
                steps = s.get("decode_steps")
                dk = (s.get("slo") or {}).get("decode_block_k") \
                    if isinstance(s.get("slo"), dict) else None
                if toks is not None and steps:
                    per = toks / steps
                    p(f"      dispatch economics: {per:.2f} "
                      f"tokens/dispatch ({toks} tok / {steps} "
                      f"decode dispatches"
                      + ("" if dk is None else
                         f", decode_block_k={dk}") + ")")
                # generation economics (ISSUE 13): the speculation and
                # prefix-sharing levers, printed only when measured —
                # None-when-disabled never renders a phantom rate
                gen = []
                if s.get("spec_acceptance_rate") is not None:
                    gen.append(
                        f"spec acceptance="
                        f"{s['spec_acceptance_rate']:.0%}"
                        + (f" (draft len {s['draft_len']:g})"
                           if s.get("draft_len") is not None else ""))
                if s.get("prefix_hit_rate") is not None:
                    gen.append(
                        f"prefix hit={s['prefix_hit_rate']:.0%}")
                if gen:
                    p(f"      generation: {', '.join(gen)}")
                # KV-tier economics (ISSUE 20): codec + swap/restore
                # levers, printed only when measured
                kv = []
                if s.get("kv_quant") is not None:
                    kv.append("kv=int8")
                if s.get("swap_rate") is not None:
                    kv.append(f"swap rate={s['swap_rate']:.0%}"
                              + (f" (pages hw "
                                 f"{s['swapped_pages_high_water']})"
                                 if s.get("swapped_pages_high_water")
                                 is not None else ""))
                if kv:
                    p(f"      kv tier: {', '.join(kv)}")
                slo = s.get("slo")
                if slo:
                    att = slo.get("slo_attainment")
                    good = slo.get("goodput_tok_s")
                    gap = None
                    if good is not None and scan:
                        gap = 1.0 - good / scan
                    # resilience economics (ISSUE 15): shed / preempt
                    # rates + degraded-round count next to attainment
                    # — None-when-disabled never renders a phantom
                    res = []
                    if slo.get("shed_rate") is not None:
                        res.append(f"shed={slo['shed_rate']:.0%}")
                    if slo.get("preempt_rate") is not None:
                        res.append(
                            f"preempt={slo['preempt_rate']:.0%}")
                    if slo.get("degraded_rounds") is not None:
                        res.append(
                            f"degraded_rounds="
                            f"{slo['degraded_rounds']}")
                    p(f"      slo: arrival={slo.get('arrival_process')} "
                      f"offered={slo.get('offered_load')} req/tick, "
                      f"attainment="
                      f"{'?' if att is None else format(att, '.0%')} "
                      f"(ttft<={slo.get('slo_ttft_ms')}ms "
                      f"tpot<={slo.get('slo_tpot_ms')}ms), goodput "
                      f"{'?' if good is None else format(good, 'g')} "
                      f"tok/s"
                      + ("" if gap is None else
                         f" ({gap:.0%} under the scan line)")
                      + (f" [{', '.join(res)}]" if res else ""))
                    p(f"      tails: ttft p50/p99 "
                      f"{slo.get('ttft_p50_ms')}/"
                      f"{slo.get('ttft_p99_ms')} ms, per-token p50/p99 "
                      f"{slo.get('per_token_p50_ms')}/"
                      f"{slo.get('per_token_p99_ms')} ms; max queue "
                      f"{slo.get('max_queue_depth')}, kv high-water "
                      f"{slo.get('kv_page_high_water')}"
                      + (f"/{s['kv_pages']} pages"
                         if s.get("kv_pages") else ""))
        if led.get("router"):
            # FLEET (ISSUE 19): the router block next to the per-engine
            # serving economics — fleet goodput, how evenly the
            # replicas shared the load, the failover/replay account,
            # and what each routing policy bought in prefix hits
            p("  fleet:")
            for rt in led["router"]:
                good = rt.get("fleet_goodput_tok_s")
                sp = rt.get("util_spread")
                p(f"    {rt['id']} ({rt['harness']}) "
                  f"[{rt.get('trace_id') or '?'}]: "
                  f"policy={rt.get('route_policy')} "
                  f"replicas={rt.get('replicas')}, fleet goodput "
                  f"{'?' if good is None else format(good, 'g')} tok/s, "
                  f"util spread "
                  f"{'?' if sp is None else format(sp, '.1%')}")
                p(f"      failover: {rt.get('failovers')} failed over, "
                  f"{rt.get('replayed_requests')} replayed "
                  f"({rt.get('requests')} routed, "
                  f"{rt.get('completed')} completed; rejected "
                  f"fleet={rt.get('rejected_fleet')} "
                  f"replica={rt.get('rejected_replica')})")
                p(f"      tails (cross-replica): ttft p99 "
                  f"{rt.get('ttft_p99_ms')} ms, tpot p99 "
                  f"{rt.get('tpot_p99_ms')} ms")
                hr = rt.get("prefix_hit_rate_by_policy")
                if isinstance(hr, dict) and hr:
                    bits = ", ".join(
                        f"{k}={v:.0%}" for k, v in sorted(hr.items()))
                    p(f"      prefix hit-rate by policy: {bits}")
    if not report:
        p("nothing to report (no readable inputs)")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--ledger",
                    default=os.path.join(REPO, "benchmarks",
                                         "ledger.jsonl"))
    ap.add_argument("--json", action="store_true",
                    help="append one machine-readable JSON line")
    args = ap.parse_args(argv)

    try:
        report = build_report(ledger_path=args.ledger)
    except (OSError, ValueError) as e:
        print(f"FAIL: {e}")
        return 1
    print_report(report)
    if args.json:
        print(json.dumps(report, sort_keys=True))
    return 0


if __name__ == "__main__":
    sys.exit(main())
