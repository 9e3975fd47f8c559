"""Serving resilience: admission control, load shedding, KV-pressure
preemption, and the per-round dispatch watchdog (ISSUE 15).

The collection pipeline got a full failure story in PR 4 (one
classifier + retry state machine + deterministic fault injection); the
``ServingEngine`` had none — a KV-page exhaustion, a wedged device
dispatch, or a sustained overload either
crashed the serving loop or deadlocked it. This module is the
host-side substrate of the four recovery layers the engine wires in
(production continuous-batching systems treat all four as first-class
— PAPERS.md arXiv:2605.25645's scheduler design; the vLLM
preemption/recompute map in docs/MIGRATING.md):

* **admission control** (``APEX_SERVE_ADMIT=N``): a bounded submit
  queue. ``ServingEngine.submit`` returns a structured
  :class:`Rejected` (reason + a retry-after estimate in scheduler
  ticks) instead of enqueueing when the queue is full — explicit
  reject at the front door, never an exception escaping the loop and
  never an unbounded queue OOMing the host under a burst.
* **deadline shedding** (``APEX_SERVE_SHED=1``): the engine drops
  queued requests whose SLO attainment is already IMPOSSIBLE — a
  request that has waited past the TTFT threshold cannot attain
  whatever happens next (TTFT >= waiting time), so serving it would
  burn decode rounds on a lost cause while attainable requests queue
  behind it. Conservative by construction: only provably-lost
  requests shed.
* **KV-pressure preemption** (``APEX_SERVE_PREEMPT=1``): admission
  reserves PROMPT pages only (overcommit — vLLM's model) and decode
  grows the page table as positions cross page boundaries; a refused
  mid-stream grant preempts the lowest-effective-priority running
  request instead of crashing or head-of-line-deadlocking — its pages
  are freed (prefix-cache refcounts respected), its prompt+generated
  tokens are requeued, and re-admission replays them through the
  EXISTING packed prefill program (token-for-token parity with the
  never-preempted stream — greedy decode is deterministic and the
  replayed K/V is the same computation the decode path wrote).
* **dispatch watchdog + round recovery** (``APEX_SERVE_RECOVER=1``):
  every device dispatch runs under :func:`guarded_dispatch` — a
  worker-thread timeout (default
  ``resilience.SERVE_DISPATCH_TIMEOUT_S``, the §6 envelope's serving
  entry) that converts a hung or crashing round into a
  :class:`DispatchFailure` carrying the resilience classifier's
  verdict (timeout = ``wedged``, exception = ``degraded_relay``). The
  engine then requeues every in-flight request, stamps
  ``degraded_round`` lifecycle events, rebuilds the device cache
  (the wedged dispatch may have consumed the donated buffer) and
  continues — bounded by ``SERVE_ROUND_ATTEMPTS`` consecutive
  failures with ``RetryPolicy`` pacing between them, so a dead
  device still kills the engine loudly instead of spinning.

Knob asymmetry (the CLAUDE.md rule): per-call engine arguments are
demands — garbage values raise, and ``preempt=True`` raises when the
page pool cannot guarantee a lone request's progress
(``num_pages - 1 < max_pages``: even with everything else preempted
the request could wedge) — while the env knobs are preferences that
fall back per shape. All four default OFF with disabled mode
token-for-token identical (tests/test_serving_chaos.py pins it), per
the measured-dispatch rule: the overload A/B (shed-vs-tail under the
diurnal trace) is queued in PERF.md §2 behind the
``serving_resilience`` rung.

Stdlib-only (like ``scheduler``/``lifecycle``): the watchdog is a
plain thread join; the jitted programs are untouched — the engine's
one-compile contract (``decode_cache_size()==1``,
``prefill_cache_size()<=1``) holds under every enabled combination.
"""

import dataclasses
import threading
from typing import Optional

from apex_tpu import resilience as _res
from apex_tpu.dispatch import tiles as _tiles


@dataclasses.dataclass(frozen=True)
class Rejected:
    """The structured admission refusal ``ServingEngine.submit``
    returns under admission control: never an exception (a full queue
    is load, not a programming error), never a silent drop (the
    caller holds the reason and a pacing hint). ``retry_after_ticks``
    is a crude drain estimate — queued-ahead over slot count — a
    client-side retry loop can multiply, not a promise."""
    reason: str
    retry_after_ticks: int


class DispatchFailure(Exception):
    """One failed serving dispatch under the watchdog: ``phase`` names
    the program (``prefill`` | ``decode`` | ``verify``), ``verdict``
    is the resilience classifier's word for it (``wedged`` for a
    timeout, ``degraded_relay`` for a crash), ``detail`` the
    underlying evidence."""

    def __init__(self, phase, verdict, detail):
        super().__init__(f"{phase} dispatch {verdict}: {detail}")
        self.phase = phase
        self.verdict = verdict
        self.detail = detail


def guarded_dispatch(fn, timeout_s, phase):
    """Run one device dispatch (call + fetch, no engine-state
    mutation) under the serving watchdog: *fn* executes on a worker
    thread and its result is adopted only on a clean in-budget return
    — a late result from a timed-out round can never overwrite the
    engine's recovered state. Raises :class:`DispatchFailure` with
    the classifier verdict on timeout or crash."""
    box = {}

    def _run():
        try:
            box["result"] = fn()
        except BaseException as e:  # classified, not swallowed
            box["error"] = e

    t = threading.Thread(target=_run, daemon=True,
                         name=f"serve-{phase}-dispatch")
    t.start()
    t.join(timeout_s)
    if t.is_alive():
        raise DispatchFailure(
            phase, _res.classify_subprocess(None, timed_out=True),
            f"no fetch within the {timeout_s}s round budget "
            f"(resilience.SERVE_DISPATCH_TIMEOUT_S envelope)")
    if "error" in box:
        err = box["error"]
        raise DispatchFailure(
            phase, _res.classify_subprocess(1),
            f"{type(err).__name__}: {err}") from err
    return box["result"]


# --------------------------------------------------------------------------
# knob resolution (per-call demands raise; env preferences fall back)


def resolve_admit(per_call=None):
    """The effective submit-queue bound: per-call int (>= 1 = bound,
    0/False = explicit off; anything else raises — a demand) >
    ``APEX_SERVE_ADMIT`` env preference (``tiles.env_nonneg_int``:
    garbage warns once and is ignored; 0 is the explicit off-pin) >
    built-in OFF (0: the unbounded queue serving always had)."""
    if per_call is not None:
        if per_call is False:
            return 0
        if not isinstance(per_call, int) or isinstance(per_call, bool) \
                or per_call < 0:
            raise ValueError(
                f"admit= wants a non-negative int (0 = off) or None, "
                f"got {per_call!r}")
        return per_call
    v = _tiles.env_nonneg_int("APEX_SERVE_ADMIT")
    return 0 if v is None else v


def _resolve_flag(per_call, env, name):
    if per_call is not None:
        if not isinstance(per_call, bool):
            raise ValueError(
                f"{name}= wants True/False/None, got {per_call!r}")
        return per_call
    v = _tiles.env_choice(env, ("1", "0"))
    if v is not None:
        return v == "1"
    return False


def resolve_shed(per_call=None):
    """Deadline shedding on/off: per-call bool (non-bool raises) >
    ``APEX_SERVE_SHED`` > built-in OFF."""
    return _resolve_flag(per_call, "APEX_SERVE_SHED", "shed")


def resolve_preempt(per_call=None):
    """KV-pressure preemption on/off: per-call bool (non-bool raises)
    > ``APEX_SERVE_PREEMPT`` > built-in OFF. The ENGINE additionally
    judges the progress guarantee (a lone request must be able to
    reach ``max_seq`` pages): a per-call True over a too-small pool
    raises there; the env preference falls back per shape."""
    return _resolve_flag(per_call, "APEX_SERVE_PREEMPT", "preempt")


def resolve_recover(per_call=None):
    """Dispatch watchdog + round recovery on/off: per-call bool
    (non-bool raises) > ``APEX_SERVE_RECOVER`` > built-in OFF."""
    return _resolve_flag(per_call, "APEX_SERVE_RECOVER", "recover")


@dataclasses.dataclass
class ResilienceStats:
    """Engine-lifetime counters of the four layers, and the rate
    surface the ``slo`` ledger block carries (None-when-disabled —
    degradation, never omission; check 9 refuses a non-None rate
    whose selecting knob is unpinned or off)."""
    rejected: int = 0
    shed: int = 0
    preempted: int = 0
    resubmitted: int = 0
    degraded_rounds: int = 0
    submit_attempts: int = 0
    admissions: int = 0
    # the last failed round's classifier verdict (round recovery)
    last_verdict: Optional[str] = None

    def rates(self, *, shed_on, preempt_on, recover_on):
        return {
            "shed_rate": (self.shed / self.submit_attempts
                          if self.submit_attempts else 0.0)
            if shed_on else None,
            "preempt_rate": (self.preempted / self.admissions
                             if self.admissions else 0.0)
            if preempt_on else None,
            "degraded_rounds": self.degraded_rounds
            if recover_on else None,
        }
