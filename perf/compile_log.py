"""Every XLA compile request of the process, persistent-cache hit or not:
when it came, on the clock the runners use (copied from
``chip_smoke.CompileLog``), and what the executable it gave needs on a
chip by XLA's own memory analysis. ``memory_stats()`` counts buffers and
not a program's temporaries on this backend (5.48 GB shown beside a
decode program of 13.55 GB, my chip run, PR 24), so the size of a cell
comes from here; on the chip it agrees to the byte with an AOT
``memory_analysis()`` of the same program, loaded from the cache or not."""

import time

import jax
from jax._src import compiler

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def footprint(executable):
    """Bytes one chip holds while ``executable`` runs: its arguments, its
    outputs less what they alias of the arguments, its temporaries and
    its code (``compiled.memory_analysis()``'s fields)."""
    m = executable.get_compiled_memory_stats()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes
            + m.generated_code_size_in_bytes)


class CompileLog:
    def __init__(self):
        self.times = []
        self.programs = []   # (time, name, footprint bytes), as loaded
        jax.monitoring.register_event_duration_secs_listener(self._on)
        # every jit, eager op and AOT compile of the process goes through
        # this one function, compiled anew or loaded from the cache
        inner = compiler.compile_or_get_cached

        def tapped(backend, computation, *args, **kwargs):
            executable = inner(backend, computation, *args, **kwargs)
            name = str(computation.operation.attributes["sym_name"])
            self.programs.append((time.perf_counter(), name.strip('"'),
                                  footprint(executable)))
            return executable

        compiler.compile_or_get_cached = tapped

    def _on(self, event, duration, **kw):
        if event == _COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0, t1):
        return sum(1 for t in self.times if t0 < t <= t1)

    def largest(self, before):
        """(name, bytes) of the largest program loaded up to ``before``."""
        loaded = [(size, name) for t, name, size in self.programs
                  if t <= before]
        size, name = max(loaded, default=(0, None))
        return name, size
