"""The profiler as the benchmark uses it: device and host-annotation
tracing on, the Python call tracer off (it would slow the host loop it
is there to observe and swell the trace)."""

import contextlib

import jax

WINDOW = "perf.window"
# the harness's own host spans, by which idle gaps are labelled
LABELS = ("round.prefill", "round.decode", "client.refill", "chunk.fetch")


@contextlib.contextmanager
def window(trace_dir):
    """Trace what runs inside, and mark it as the traced window."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            yield
    finally:
        jax.profiler.stop_trace()


def span(label):
    return jax.profiler.TraceAnnotation(label)
