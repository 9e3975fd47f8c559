"""Verdict CLI over the resilience classifier — the thin interface
``benchmarks/probe_and_collect.sh`` consults so the shell driver holds
no health logic of its own.

Run held to the CPU (the classifier never needs the chip)::

    JAX_PLATFORMS=cpu python -m apex_tpu.resilience.probe <cmd> ...

Subcommands:

``log FILE [--smoke]``
    Classify the last JSON line of a driver log (bench.log /
    bench_first.log). Prints the verdict; exits 0 iff healthy — the
    probe loop's collection gate.

``stamp --rc RC [--detail STR] [--out FILE]``
    Classify one matmul-probe run from its exit status (0 = healthy,
    124/timeout = wedged, other = degraded when the probe printed a
    marginal-rate line, else wedged) and write the structured
    probe-state JSON ``{"ts", "verdict", "rc", "detail"}``. Prints the
    verdict; exits 0 iff healthy.

``status [--state FILE] [--bench LOG]``
    Report the classifier verdict of the LAST probe plus its age —
    ``probe_and_collect.sh --status`` calls this instead of dumping the
    raw state file. With ``--bench``, also classifies the window: a
    healthy probe next to a wedged/degraded bench log is the §6
    *selective large-HBM starvation* mode (small programs at device
    speed, the large training-step program starved). Exits 0 iff the
    last probe was healthy.
"""

import argparse
import json
import os
import sys
import time

from apex_tpu import resilience


def default_state():
    """Probe-state path (``APEX_PROBE_STATE``), read when the CLI
    builds its parser — not at import (the APX001 trace-time rule:
    probe_and_collect.sh exports the override per round, and a
    module-level read would freeze the first round's path into any
    long-lived process)."""
    return os.environ.get("APEX_PROBE_STATE", "/tmp/apex_tpu_probe_state")


def classify_probe(rc, detail=""):
    """Verdict for one marginal-rate matmul probe run (the shell's
    ``probe()`` heredoc): exit 0 = healthy band; a timeout killed it =
    wedged; a completed probe outside the band (it printed its marginal
    line) = degraded relay; anything else (no output, init hang killed
    early) = wedged."""
    if rc == 0:
        return resilience.HEALTHY
    if rc in resilience.TIMEOUT_RCS:
        return resilience.WEDGED
    return (resilience.DEGRADED_RELAY
            if "marginal" in (detail or "") else resilience.WEDGED)


def cmd_log(args):
    try:
        with open(args.file) as f:
            text = f.read()
    except OSError as e:
        print(f"{resilience.WEDGED}: no driver log ({e})")
        return 1
    _, rec = resilience.last_json(text)
    verdict = resilience.classify(rec, smoke=args.smoke)
    detail = ""
    if rec is not None:
        detail = (f" value={rec.get('value')} "
                  f"mfu={rec.get('mfu')}"
                  + (f" fault_plan={rec['fault_plan']}"
                     if rec.get("fault_plan") else ""))
    print(f"{verdict}:{detail or ' no JSON line in log'}")
    return 0 if verdict == resilience.HEALTHY else 1


def cmd_stamp(args):
    verdict = classify_probe(args.rc, args.detail)
    state = {"ts": round(time.time(), 3), "verdict": verdict,
             "rc": args.rc, "detail": (args.detail or "")[:500]}
    if args.out:
        resilience.atomic_write_json(args.out, state)
    print(verdict)
    return 0 if verdict == resilience.HEALTHY else 1


def read_state(path):
    """Parsed probe-state JSON, or a best-effort wrapper around a legacy
    plain-text state line (verdict unknown)."""
    with open(path) as f:
        text = f.read()
    try:
        state = json.loads(text)
        if isinstance(state, dict):
            return state
    except ValueError:
        pass
    return {"ts": os.path.getmtime(path), "verdict": None,
            "detail": text.strip()[:500]}


def cmd_status(args):
    try:
        state = read_state(args.state)
    except OSError:
        print("no probe has run yet (no state file)")
        return 1
    age = max(0, int(time.time() - (state.get("ts") or 0)))
    verdict = state.get("verdict") or "unknown (legacy state format)"
    print(f"last probe: {verdict} (age {age}s) — "
          f"{state.get('detail') or 'no detail'}")
    if args.bench and os.path.exists(args.bench):
        try:
            with open(args.bench) as f:
                _, rec = resilience.last_json(f.read())
        except OSError:
            rec = None
        bench_verdict = resilience.classify(
            rec, small_hbm_ok=(state.get("verdict") == resilience.HEALTHY))
        print(f"last bench: {bench_verdict}")
        if state.get("verdict") == resilience.HEALTHY \
                and bench_verdict in (resilience.WEDGED,
                                      resilience.DEGRADED_LARGE_HBM,
                                      resilience.DEGRADED_RELAY):
            print(f"window: {resilience.DEGRADED_LARGE_HBM} — probe "
                  "healthy but the large-HBM bench program starved "
                  "(PERF.md §6 selective starvation)")
    return 0 if state.get("verdict") == resilience.HEALTHY else 1


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.resilience.probe",
        description=__doc__.splitlines()[0])
    sub = ap.add_subparsers(dest="cmd", required=True)

    p = sub.add_parser("log", help="classify a driver log's last JSON line")
    p.add_argument("file")
    p.add_argument("--smoke", action="store_true",
                   help="CPU is the requested backend")
    p.set_defaults(fn=cmd_log)

    p = sub.add_parser("stamp", help="classify a probe run; write state")
    p.add_argument("--rc", type=int, required=True)
    p.add_argument("--detail", default="")
    p.add_argument("--out", default=default_state())
    p.set_defaults(fn=cmd_stamp)

    p = sub.add_parser("status", help="verdict + age of the last probe")
    p.add_argument("--state", default=default_state())
    p.add_argument("--bench", default=None,
                   help="bench log to cross-classify (large-HBM mode)")
    p.set_defaults(fn=cmd_status)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
