"""In-step training metrics: named-scalar registry + JSONL sink.

Collection contract (the zero-cost rule): the jitted train step gates
every metric computation on :func:`enabled` — a Python bool read at
TRACE time, never a traced value — and threads the scalars out as
auxiliary outputs of the step (stacked across iterations by the
training ``lax.scan``). Disabled, the gates short-circuit to ``None``
(an empty pytree) before any jnp op is built, so the step traces to a
byte-identical jaxpr and a pinned measurement is never perturbed;
tests/test_telemetry.py asserts this. Enabled, the host fetches the
stacked scalars AFTER the timed region with the same 1-element-sync-
then-fetch pattern as the measured value — zero host callbacks (a
callback is a host round trip in the middle of the program).

Providers stay pure and ungated: ``LossScaler.metrics(state)``
(amp/scaler.py) and ``optimizers.grad_norm_stats(grads)`` always return
their scalar dicts; the telemetry gate lives in the caller's
:func:`collect` / in-step ``if telemetry.enabled():`` branch. That
mirrors the repo's explicit-request-vs-preference asymmetry: the
providers honor the request verbatim, the process-wide switch is a
preference.
"""

import dataclasses
import json
import os

import numpy as np

from apex_tpu.telemetry import ledger as _ledger

# --------------------------------------------------------------------------
# enabled/disabled switch (trace-time; process-wide preference)

_FORCED = None  # programmatic override; None defers to the env knob


def enabled():
    """True when in-step metric collection is on (``APEX_TELEMETRY=1``,
    unless :func:`enable`/:func:`disable` overrode it). Read at trace
    time only — branch on it in Python, never inside traced code."""
    if _FORCED is not None:
        return _FORCED
    from apex_tpu.dispatch.tiles import env_flag

    return env_flag("APEX_TELEMETRY")


def enable():
    global _FORCED
    _FORCED = True


def disable():
    global _FORCED
    _FORCED = False


def reset_enabled():
    """Back to the env-var default (test hygiene)."""
    global _FORCED
    _FORCED = None


# --------------------------------------------------------------------------
# registry


@dataclasses.dataclass(frozen=True)
class MetricSpec:
    name: str
    unit: str = ""
    description: str = ""


_REGISTRY = {}


def register(name, unit="", description=""):
    """Register a named metric; idempotent for an identical spec,
    ValueError on a conflicting re-registration (two harnesses silently
    disagreeing about what a name means is exactly the label drift this
    subsystem exists to prevent)."""
    spec_ = MetricSpec(name, unit, description)
    prev = _REGISTRY.get(name)
    if prev is not None and prev != spec_:
        raise ValueError(
            f"metric {name!r} already registered as {prev}, conflicting "
            f"re-registration {spec_}")
    _REGISTRY[name] = spec_
    return spec_


def spec(name):
    return _REGISTRY.get(name)


def registered():
    return dict(_REGISTRY)


# The core training-step scalars every instrumented harness shares.
register("loss", unit="nats", description="unscaled mean per-token loss")
register("loss_scale", unit="", description="dynamic loss scale (amp)")
register("overflow", unit="bool",
         description="loss-scale skip event (non-finite grads this step)")
register("unskipped", unit="steps",
         description="steps since the last overflow (scaler window)")
register("grad_norm", unit="", description="global L2 norm of the grads")
register("grad_max", unit="", description="max |g| over the grad pytree")
register("tokens_per_sec", unit="tokens/s",
         description="host-derived throughput for the run")

# Serving-loop gauges (apex_tpu.serving.lifecycle.EventLog.sample_gauges
# — one sample per scheduler round, ISSUE 11): registered here so the
# registry stays the ONE schema and EventLog.gauge_rows() can sink
# through a strict MetricsWriter without auto-registration.
register("serve_slots_active", unit="slots",
         description="decode slots holding a live request this round")
register("serve_num_slots", unit="slots",
         description="decode slot capacity of the engine")
register("serve_queue_depth", unit="requests",
         description="requests waiting for admission this round")
register("serve_kv_pages_live", unit="pages",
         description="KV cache pages allocated to live requests")
register("serve_kv_pages_total", unit="pages",
         description="KV cache page capacity (incl. reserved null page)")
register("serve_hol_wait_ms", unit="ms",
         description="age of the head-of-line queued request")
register("serve_spec_drafted", unit="tokens",
         description="cumulative speculative draft tokens proposed "
                     "(ISSUE 13; 0 with spec decode off)")
register("serve_spec_accepted", unit="tokens",
         description="cumulative speculative draft tokens accepted "
                     "by the verify program")
register("serve_prefix_hit_tokens", unit="tokens",
         description="cumulative prompt tokens served from the "
                     "prefix cache (0 with the cache off)")
register("serve_rejected", unit="requests",
         description="cumulative submits refused by admission control "
                     "(ISSUE 15; 0 with APEX_SERVE_ADMIT off)")
register("serve_shed", unit="requests",
         description="cumulative queued requests dropped by the "
                     "deadline shedder (SLO attainment impossible)")
register("serve_preempted", unit="requests",
         description="cumulative KV-pressure preemptions (pages freed, "
                     "stream requeued for prefill replay)")
register("serve_resubmitted", unit="requests",
         description="cumulative requeues back into the admission "
                     "queue (preemption + degraded-round recovery)")
register("serve_degraded_rounds", unit="rounds",
         description="cumulative serving rounds lost to a timed-out "
                     "or crashed device dispatch (watchdog recovery)")

# Fleet-router gauges (apex_tpu.serving.router.Router.gauge_rows — one
# sample per router round, ISSUE 19): 0/absent without a router.
register("serve_routed", unit="requests",
         description="cumulative requests the fleet router assigned "
                     "to a replica (ISSUE 19; absent without a router)")
register("serve_failovers", unit="requests",
         description="cumulative requests pulled off a dead replica "
                     "(queued + in-flight) for requeue-and-replay")
register("serve_replayed", unit="requests",
         description="cumulative failed-over requests resubmitted "
                     "through a surviving replica (prefill replay)")


# --------------------------------------------------------------------------
# in-step collection


def collect(metrics, **scalars):
    """Merge named scalars into the step's metrics dict.

    Disabled (trace-time), the input passes through untouched — ``None``
    stays ``None``, so an uninstrumented and a disabled-instrumented
    step build identical jaxprs. Callers must gate any *computation* of
    a scalar on :func:`enabled` themselves; ``collect`` only gates the
    carry."""
    if not enabled():
        return metrics
    out = {} if metrics is None else dict(metrics)
    out.update(scalars)
    return out


# --------------------------------------------------------------------------
# JSONL sink


def metrics_path():
    """``APEX_TELEMETRY_PATH`` or ``benchmarks/telemetry_metrics.jsonl``."""
    return (os.environ.get("APEX_TELEMETRY_PATH")
            or os.path.join(_ledger.repo_root(), "benchmarks",
                            "telemetry_metrics.jsonl"))


class MetricsWriter:
    """Append-only JSONL sink for fetched (host-side numpy) metrics.

    One row per training step: ``{"run": <ledger id or None>, "step": i,
    "<name>": <float>, ...}``. ``strict=True`` refuses unregistered
    names (the registry is the schema); the default auto-registers them
    so an exploratory harness can't lose data to bookkeeping."""

    def __init__(self, path=None, strict=False):
        self.path = path or metrics_path()
        self.strict = strict

    def _check(self, names):
        for n in names:
            if spec(n) is None:
                if self.strict:
                    raise KeyError(f"metric {n!r} not registered")
                register(n)

    def append(self, record):
        """Append one pre-built row (a plain JSON-able dict)."""
        self._check(k for k in record if k not in ("run", "step"))
        with open(self.path, "a") as f:
            f.write(json.dumps(record, sort_keys=True) + "\n")

    def append_steps(self, stacked, run=None, start_step=0):
        """Write the scan-stacked in-step scalars: ``stacked`` maps
        metric name -> array of shape [k] (scalars and shape-[1] arrays
        broadcast to every row). Mismatched [k] lengths raise ValueError
        up front — a half-written run would read as a complete one.
        Returns the number of rows written."""
        if not stacked:
            return 0
        arrays = {k: np.asarray(v) for k, v in stacked.items()}
        lengths = {a.shape[0] for a in arrays.values()
                   if a.ndim and a.shape[0] != 1}
        if len(lengths) > 1:
            raise ValueError(
                f"mismatched metric lengths {sorted(lengths)}: "
                f"{ {n: a.shape for n, a in arrays.items()} }")
        k = lengths.pop() if lengths else 1
        self._check(arrays)
        rows = []
        for i in range(k):
            row = {"step": start_step + i}
            if run is not None:
                row["run"] = run
            for name, a in arrays.items():
                row[name] = float(a[i] if a.ndim and a.shape[0] == k
                                  else a[0] if a.ndim else a)
            rows.append(row)
        with open(self.path, "a") as f:
            for row in rows:
                f.write(json.dumps(row, sort_keys=True) + "\n")
        return len(rows)


def read_metrics(path=None):
    """Read a metrics JSONL file back as a list of row dicts."""
    path = path or metrics_path()
    rows = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    return rows
