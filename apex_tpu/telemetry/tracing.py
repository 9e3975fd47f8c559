"""Calibrated span/timer API — ONE implementation of the timing rule.

The rule (PERF.md "Timing rule"; the two calibration experiments were
re-run on the v5e in PR 21): time with the host clock around work whose
end the host observes, warm every shape first, and count no compile in
the window. On the local chip a dispatch costs the host about 0.2 ms to
enqueue and 1.7 ms until a fetched element is back, and
``block_until_ready`` observes completion, so either ending is valid.
What this module adds for kernel-sized rows:

  1. measured programs run K chained iterations inside ONE ``lax.scan``
     dispatch, so per-dispatch host latency is paid once and divides by
     K (a sub-millisecond kernel timed one dispatch at a time would be
     mostly that latency);
  2. the clock stops on a 1-element device fetch (:func:`sync`) — the
     result is on the host, whatever the backend;
  3. a literal-0 feedback chaining the scan carry is constant-folded,
     letting XLA hoist the loop-invariant body out of the scan — so the
     chain factor ``eps`` is a TRACED runtime scalar (0.0 to warm,
     1e-30 when timing).

:class:`Tracer` owns the scan length K and the measured per-dispatch
overhead for a run; every :class:`Span` it emits carries that
calibration metadata, and :meth:`Tracer.flush_ledger` writes the whole
run (spans + knob pins + git SHA + platform) as one
``benchmarks/ledger.jsonl`` record. ``benchmarks/_timing.py`` re-exports
the primitives.
"""

import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax


def sync(x):
    """Wait for device execution by fetching one element."""
    leaf = jax.tree_util.tree_leaves(x)[0]
    return np.asarray(jnp.ravel(leaf)[:1])


def _overhead_program(k):
    """The jitted calibration scan."""
    def run(c, eps):
        def body(c, _):
            return c + eps, ()
        c, _ = lax.scan(body, c, jnp.arange(k))
        return c

    return jax.jit(run)


def measure_dispatch_overhead(k):
    """Fixed per-dispatch latency (enqueue + fetch): best-of-3 trivial
    k-iter scans."""
    f = _overhead_program(k)
    sync(f(jnp.float32(0.0), jnp.float32(0.0)))
    best = float("inf")
    for i in range(3):
        t0 = time.perf_counter()
        sync(f(jnp.float32(0.0), jnp.float32(1e-30 * (i + 1))))
        best = min(best, time.perf_counter() - t0)
    return best


def device_peak_flops():
    """The published bf16 peak of this process's device kind
    (``costs.PEAKS``): None on the CPU — rows print no MFU — and an
    error on a chip without published peaks."""
    from apex_tpu.telemetry import costs

    return costs.peak_flops_for(jax.devices()[0].device_kind)


def bench_k(smoke, default=128):
    """Scan length for kernel-level microbenches (env ``APEX_BENCH_K``).

    The per-dispatch latency and its variance divide by K, so sub-ms
    kernel rows want a long scan; scan length does not grow the compiled
    program. Step-level harnesses (profile_gpt etc.) keep their own
    smaller fixed K — their rows are 10–100 ms.
    """
    from apex_tpu.dispatch.tiles import env_int

    return 2 if smoke else (env_int("APEX_BENCH_K") or default)


@dataclasses.dataclass
class Span:
    """One measured row and the calibration it was taken under.

    ``seconds`` is the per-iteration time with the dispatch overhead
    already subtracted (None when the row failed to run — ``error``
    holds the reason, so a window's failures reach the ledger too)."""

    name: str
    seconds: float  # per-iteration, overhead-subtracted; None on error
    total_s: float  # raw wall time of the timed dispatch
    k: int
    overhead_s: float
    method: str = "scan-chain"  # K chained steps in one dispatch
    flops_per_iter: float = None
    error: str = None
    extra: dict = dataclasses.field(default_factory=dict)

    @property
    def ms(self):
        return None if self.seconds is None else self.seconds * 1e3

    def tflops(self):
        if self.seconds is None or not self.flops_per_iter:
            return None
        return self.flops_per_iter / self.seconds / 1e12

    def mfu(self, peak_flops):
        if self.seconds is None or not self.flops_per_iter or not peak_flops:
            return None
        return self.flops_per_iter / self.seconds / peak_flops

    def format_row(self, peak_flops=None, width=28, ms_prec=2):
        """The harness table row (name, ms, optional TF/s + MFU)."""
        if self.seconds is None:
            return f"{self.name:{width}s} FAILED: {self.error}"
        extra = ""
        if self.flops_per_iter and peak_flops:
            extra = (f"  {self.tflops():6.1f} TF/s"
                     f"  MFU={self.mfu(peak_flops) * 100:5.1f}%")
        return f"{self.name:{width}s} {self.ms:8.{ms_prec}f} ms{extra}"

    def as_record(self):
        rec = {"name": self.name,
               "ms": None if self.ms is None else round(self.ms, 4),
               "k": self.k,
               "dispatch_overhead_ms": round(self.overhead_s * 1e3, 2),
               "method": self.method}
        if self.error is not None:
            rec["error"] = self.error
        rec.update(self.extra)
        return rec


class Tracer:
    """Calibrated timing context for one harness run.

    Calibrates the per-dispatch overhead once (``overhead=`` injects a
    pre-measured value), then
    times rows via :meth:`scan_time` / :meth:`time_call`; spans
    accumulate for :meth:`flush_ledger`.
    """

    def __init__(self, k, overhead=None, peak_flops=None):
        self.k = int(k)
        self.overhead = float(overhead) if overhead is not None \
            else measure_dispatch_overhead(self.k)
        self.peak_flops = device_peak_flops() if peak_flops is None \
            else peak_flops
        self.spans = []
        # the run-level attribution block (apex_tpu.telemetry.costs):
        # set by the first capture_cost=True row (or set_cost); flushed
        # with every ledger record — null-degraded when nothing captured
        self.cost = None

    @property
    def overhead_ms(self):
        return self.overhead * 1e3

    def _capture_cost(self, call, args, flops_per_iter, compiled=None,
                      comm=None, comm_compression=None, host_ms=None,
                      comm_ms=None):
        """Attribution block for one measured program (cost_analysis /
        memory_analysis via apex_tpu.telemetry.costs): ``compiled`` is
        an AOT object the caller already holds; otherwise one extra
        host-side ``call.lower`` trace, compiled only where that is a
        persistent-cache read — never a second cold compile. The first
        captured block becomes the run-level ``self.cost``."""
        from apex_tpu import compile_cache
        from apex_tpu.telemetry import costs

        lowered = None
        try:
            if compiled is None and hasattr(call, "lower"):
                lowered = call.lower(*args)
                if compile_cache.enabled():
                    compiled = lowered.compile()
        except Exception:
            pass
        block = costs.capture(lowered=lowered, compiled=compiled,
                              steps=self.k,
                              model_flops_per_step=flops_per_iter,
                              device_kind=jax.devices()[0].device_kind,
                              comm=comm,
                              comm_compression=comm_compression,
                              host_ms=host_ms, comm_ms=comm_ms)
        if self.cost is None:
            self.cost = block
        return block

    def time_call(self, name, call, warm_args, timed_args,
                  flops_per_iter=None, extra=None, on_fail="raise",
                  sync_out=sync, capture_cost=False, comm=None,
                  comm_compression=None, host_ms=None, comm_ms=None):
        """Warm (compile + drain) with ``warm_args``, then time one
        dispatch of ``call(*timed_args)``; per-iteration time = (wall -
        overhead) / K. The two argument tuples differ in a traced value
        (the eps chain).
        ``on_fail="span"`` records a failed row instead of raising (the
        sweep-harness pattern: one unlowered config must not kill the
        window's remaining rows)."""
        try:
            sync_out(call(*warm_args))
        except Exception as e:
            if on_fail != "span":
                raise
            span = Span(name, None, None, self.k, self.overhead,
                        flops_per_iter=flops_per_iter,
                        error=f"{type(e).__name__}: {str(e)[:100]}",
                        extra=dict(extra or {}))
            self.spans.append(span)
            return span
        t0 = time.perf_counter()
        sync_out(call(*timed_args))
        total = time.perf_counter() - t0
        span_extra = dict(extra or {})
        if capture_cost:
            # AFTER the timed region: the lower/compile are host work
            # that must never straddle t0 (the calibration-flap class)
            span_extra["cost"] = self._capture_cost(
                call, warm_args, flops_per_iter, comm=comm,
                comm_compression=comm_compression, host_ms=host_ms,
                comm_ms=comm_ms)
        span = Span(name, (total - self.overhead) / self.k, total, self.k,
                    self.overhead, flops_per_iter=flops_per_iter,
                    extra=span_extra)
        self.spans.append(span)
        return span

    def scan_time(self, name, make_body, carry0, ops, wrap=None,
                  flops_per_iter=None, extra=None, on_fail="raise",
                  capture_cost=False, comm=None, comm_compression=None,
                  host_ms=None, comm_ms=None):
        """The scan-chain protocol in one call. ``make_body(eps, *ops)``
        returns ``body(carry, t) -> (carry, metric)``; ``ops`` (big
        arrays) are jit ARGUMENTS — closure-captured constants would be
        inlined into the module as dense constants. ``wrap`` maps the
        run function before jit (e.g. a shard_map)."""
        k = self.k

        def run(carry0, eps, *ops):
            body = make_body(eps, *ops)
            return lax.scan(body, carry0, jnp.arange(k))

        f = jax.jit(run if wrap is None else wrap(run))
        return self.time_call(
            name, f, (carry0, jnp.float32(0.0)) + tuple(ops),
            (carry0, jnp.float32(1e-30)) + tuple(ops),
            flops_per_iter=flops_per_iter, extra=extra, on_fail=on_fail,
            capture_cost=capture_cost, comm=comm,
            comm_compression=comm_compression, host_ms=host_ms,
            comm_ms=comm_ms)

    def flush_ledger(self, harness, platform=None, relay=None, extra=None,
                     path=None):
        """Append this run (calibration + every span) as one ledger
        record; returns the record id (None when the write was skipped
        or failed — see ledger.append_record). Every
        written record is stamped with the compile-cache telemetry
        block, so a PERF.md row can prove whether its numbers were
        taken compile-free."""
        from apex_tpu import compile_cache, dispatch
        from apex_tpu.telemetry import ledger

        if platform is None:
            platform = jax.devices()[0].platform
        from apex_tpu.telemetry import costs

        payload = {"spans": [s.as_record() for s in self.spans],
                   "compile_cache": compile_cache.snapshot(),
                   "dispatch": dispatch.snapshot(),
                   # every Tracer record carries a validated cost block:
                   # the first capture_cost=True row's, or the explicit
                   # all-None degradation (never a silent omission)
                   "cost": self.cost if self.cost is not None
                   else costs.null_block()}
        payload.update(extra or {})
        return ledger.append_record(
            harness=harness, platform=platform,
            dispatch_overhead_ms=round(self.overhead_ms, 2), k=self.k,
            relay=relay, extra=payload, path=path)
