"""Heartbeat-driven rung supervisor (ISSUE 16) — replaces the bare
``timeout`` in ``run_all_tpu.sh``'s ``run()``.

``python -m apex_tpu.resilience.flight_watch --timeout T --row NAME
--flight-dir DIR -- <cmd...>`` runs the rung command with the flight
recorder armed (child env gains ``APEX_FLIGHT_DIR`` + the row label in
``APEX_FLIGHT_ROW``) and supervises its heartbeat stream
(apex_tpu.telemetry.flight):

* the FULL per-rung cap is kept while beats arrive — a slow-but-beating
  run (degraded relay, long compile) is never reaped early;
* a child whose stream goes heartbeat-silent for the silence threshold
  (``resilience.FLIGHT_SILENCE_S``; ``--silence``/``APEX_FLIGHT_SILENCE``
  override) is reaped at that threshold instead of burning the rest of
  its fixed slot — the round-5 gpt_rows wedge sat silent for 15.0 of
  71.4 window minutes that owed rows never got;
* a child that emitted NO beats keeps pre-PR semantics (full cap, reap
  only at timeout): only a stream that STOPPED proves instrumentation
  was there to go quiet — uninstrumented rows lose nothing.

A reap is SIGTERM -> grace (``FLIGHT_GRACE_S``, sized past bench's 15 s
inner-child emergency-flush wait so the PR 6 partial still banks) ->
SIGKILL, then a classified ``flight_reap`` ledger record (verdict from
``resilience.classify_inflight`` at the decision moment, reaped row
named; ``ledger.make_record`` stamps any active fault plan), and exit
143 — a ``resilience.TIMEOUT_RCS`` member, so the collection manifest
classifies the row WEDGED and keeps it owed, exactly as the bare
``timeout`` did.

Stdlib-only at module level; beats are read from files, never sockets.
"""

import os
import signal
import subprocess
import sys
import time

from apex_tpu import resilience
from apex_tpu.telemetry import flight
from apex_tpu.telemetry import ledger as _tledger


def _threshold(cli_value, raw_env, default):
    """--flag > APEX_FLIGHT_* env > the §6 constant. Raw float read:
    zero and fractional thresholds are legal (chaos tests pin seconds-
    scale silence), which the positive-int helpers cannot express."""
    if cli_value is not None:
        return float(cli_value)
    if raw_env:
        try:
            return float(raw_env)
        except ValueError:
            pass
    return float(default)


def _child_env(flight_dir, row):
    env = dict(os.environ)
    if flight_dir:
        env["APEX_FLIGHT_DIR"] = flight_dir
    if row:
        env["APEX_FLIGHT_ROW"] = row
    return env


def _reap(child, grace_s):
    """SIGTERM -> grace -> SIGKILL; returns the child's exit status if
    it surfaced one inside the grace (the emergency-flush path exits
    143 on its own), else None."""
    try:
        child.terminate()
    except OSError:
        pass
    try:
        return child.wait(timeout=grace_s)
    except (subprocess.TimeoutExpired, OSError):
        pass
    try:
        child.kill()
    except OSError:
        pass
    try:
        return child.wait(timeout=10)
    except (subprocess.TimeoutExpired, OSError):
        return None


def _reap_record(row, reason, verdict, beats, now, silence_s, timeout_s,
                 elapsed_s):
    stamps = [b["mono"] for b in beats
              if isinstance(b.get("mono"), (int, float))
              and not isinstance(b.get("mono"), bool)]
    block = {
        "row": row or "?",
        "verdict": verdict,
        "reason": reason,
        "silence_s": silence_s,
        "timeout_s": timeout_s,
        "elapsed_s": round(elapsed_s, 1),
        "beats": len(beats),
        "age_s": round(now - max(stamps), 1) if stamps else None,
        "last_phase": beats[-1].get("phase") if beats else None,
    }
    # never raises; smoke runs skip the write unless
    # APEX_TELEMETRY_LEDGER is set (the ledger's own rule)
    rec_id = _tledger.append_record(
        harness="flight_reap", platform="shell",
        dispatch_overhead_ms=None, k=None,
        extra={"flight_reap": block})
    print(f"# flight_watch: reaped row={block['row']} reason={reason} "
          f"verdict={verdict} after {block['elapsed_s']}s "
          f"(beats={block['beats']}, last_phase={block['last_phase']}, "
          f"age={block['age_s']}s, ledger={rec_id})",
          file=sys.stderr, flush=True)
    return block


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m apex_tpu.resilience.flight_watch",
        description="Run a rung command under heartbeat supervision: "
                    "full cap while beats arrive, early reap on "
                    "heartbeat silence.")
    ap.add_argument("--timeout", type=float, required=True,
                    help="full per-rung cap in seconds")
    ap.add_argument("--row", default=None,
                    help="collection-row label (stamped into beats and "
                         "the flight_reap record)")
    ap.add_argument("--flight-dir", default=None,
                    help="flight dir for the child (default: inherit "
                         "APEX_FLIGHT_DIR)")
    ap.add_argument("--silence", type=float, default=None,
                    help="heartbeat-silence reap threshold in seconds "
                         "(default: APEX_FLIGHT_SILENCE or the §6 "
                         "constant)")
    ap.add_argument("--grace", type=float, default=None,
                    help="SIGTERM->SIGKILL grace in seconds")
    ap.add_argument("cmd", nargs=argparse.REMAINDER,
                    help="-- <command...>")
    args = ap.parse_args(argv)

    cmd = list(args.cmd)
    if cmd and cmd[0] == "--":
        cmd = cmd[1:]
    if not cmd:
        ap.error("no command given after --")

    timeout_s = float(args.timeout)
    silence_s = _threshold(args.silence,
                           os.environ.get("APEX_FLIGHT_SILENCE"),
                           resilience.FLIGHT_SILENCE_S)
    grace_s = _threshold(args.grace, os.environ.get("APEX_FLIGHT_GRACE"),
                         resilience.FLIGHT_GRACE_S)
    fdir = args.flight_dir or os.environ.get("APEX_FLIGHT_DIR")
    if fdir:
        try:
            os.makedirs(fdir, exist_ok=True)
        except OSError:
            fdir = None

    start = time.monotonic()
    try:
        child = subprocess.Popen(cmd, env=_child_env(fdir, args.row))
    except OSError as e:
        print(f"# flight_watch: cannot start {cmd[0]!r}: {e}",
              file=sys.stderr, flush=True)
        return 127

    got = {"sig": None}

    def _forward(signum, frame):
        got["sig"] = signum

    signal.signal(signal.SIGTERM, _forward)
    signal.signal(signal.SIGINT, _forward)

    poll_s = min(2.0, max(0.2, silence_s / 4.0))
    while True:
        rc = child.poll()
        if rc is not None:
            # normal exit: propagate (negative = signal death; report
            # it the way a shell would, 128+sig)
            return rc if rc >= 0 else 128 - rc
        now = time.monotonic()
        beats = [b for b in flight.read_beats(fdir)
                 if isinstance(b.get("mono"), (int, float))
                 and not isinstance(b.get("mono"), bool)
                 and b["mono"] >= start] if fdir else []
        reason = None
        if got["sig"] is not None:
            reason = "signal"       # the outer backstop timeout fired
        elif now - start >= timeout_s:
            reason = "cap"          # full per-rung cap — pre-PR rule
        elif beats and resilience.classify_inflight(
                beats, now, silence_s=silence_s) == resilience.SILENT:
            # >=1 beat seen AND the stream stopped: the wedge
            # signature. A beat-free child never lands here — it keeps
            # its full cap (uninstrumented rows lose nothing).
            reason = "silence"
        if reason is not None:
            verdict = resilience.classify_inflight(
                beats, now, silence_s=silence_s)
            _reap(child, grace_s)
            _reap_record(args.row, reason, verdict, beats, now,
                         silence_s, timeout_s, now - start)
            # 143 regardless of what the emergency flush exited with:
            # a reaped rung is a TIMEOUT_RCS member so the manifest
            # keeps the row owed (the flush banks partials, it does
            # not cash the row)
            return 143
        time.sleep(poll_s)


if __name__ == "__main__":
    sys.exit(main())
