"""Timing primitives for the benchmark harnesses.

The implementation lives in ``apex_tpu.telemetry.tracing`` (the span/
timer layer every harness shares — its module docstring states the
timing rule). This module re-exports the primitives so existing call
sites keep resolving.
"""

from apex_tpu.telemetry.tracing import (  # noqa: F401
    Span,
    Tracer,
    bench_k,
    device_peak_flops,
    measure_dispatch_overhead,
    sync,
)
