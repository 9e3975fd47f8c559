"""Device time by the program's named scopes (``jax.named_scope``), per
run of a program, from a profiler trace.

An ``XLA Ops`` event of the xplane carries its HLO instruction's name and
nothing of where in the program it came from; the instruction's
``op_name`` in the optimized HLO does (``.../layer/moe/experts/gmm/...``).
So :class:`HloNames` keeps, for the programs it is asked to watch, the
``instruction -> op_name`` table of every executable the process loads
(compiled anew or from the cache), and :func:`by_scope` joins a trace
with it: each op inside the traced window is given to the program whose
``XLA Modules`` run contains it and to the FIRST scope of ``scopes``
that its ``op_name`` lies under.

Returns nothing (None) where there is nothing to read: no trace, no
device plane, or none of the watched programs in it.
"""

import bisect
import collections
import re

from perf.reduce_trace import _CONTAINERS, _short
from perf.tracing import WINDOW

# a ``lax.switch`` is one ``cond.<n>`` on the ops line, around its branch's
# ops (the MiMo prefill's trunk): a container like the reducer's own
_ENCLOSING = _CONTAINERS + ("cond",)
_LINE = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*?op_name="([^"]*)"')


class HloNames:
    """Taps ``jax._src.compiler.compile_or_get_cached`` (as
    ``compile_log.CompileLog`` does) and keeps ``{program: {instruction:
    op_name}}`` for the programs named in ``watch``."""

    def __init__(self, watch):
        from jax._src import compiler

        self.tables = {}
        inner = compiler.compile_or_get_cached

        def tapped(backend, computation, *args, **kwargs):
            executable = inner(backend, computation, *args, **kwargs)
            name = str(computation.operation.attributes["sym_name"]).strip('"')
            if name in watch:
                try:
                    text = executable.hlo_modules()[0].to_string()
                except Exception:   # an executable that keeps no HLO
                    return executable
                table = {}
                for line in text.splitlines():
                    m = _LINE.match(line)
                    if m:
                        table.setdefault(m.group(1), m.group(2))
                self.tables[name] = table
            return executable

        compiler.compile_or_get_cached = tapped


def scope_of(op_name, scopes):
    path = "/" + (op_name or "") + "/"
    return next((s for s in scopes if f"/{s}/" in path), None)


def by_scope(xplane_path, tables, scopes):
    """``{program: {"runs": n, "seconds": {scope: s}, "total_s": s}}``
    for the first chip: ``seconds[scope]`` sums the device time of the
    ops under ``scope`` over the program's ``runs`` inside the traced
    window (a run cut by the window's edge is left out, ops and all)."""
    from jax.profiler import ProfileData

    if not xplane_path or not tables:
        return None
    space = ProfileData.from_file(xplane_path)
    ops, modules, window = [], [], None
    for plane in space.planes:
        if plane.name == "/device:TPU:0":
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(e.start_ns, e.duration_ns, _short(e.name))
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = sorted(
                        (e.start_ns, e.start_ns + e.duration_ns,
                         e.name.split("(")[0]) for e in line.events)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
    if not ops or not modules:
        return None
    if window is not None:
        modules = [m for m in modules
                   if m[0] >= window[0] and m[1] <= window[1]]
    starts = [m[0] for m in modules]
    out = {name: {"runs": 0, "seconds": collections.Counter(),
                  "total_s": 0.0} for name in tables}
    for _, _, name in modules:
        if name in out:
            out[name]["runs"] += 1
    for start, duration, op in ops:
        k = bisect.bisect_right(starts, start) - 1
        if k < 0 or start > modules[k][1] or op.startswith(_ENCLOSING):
            continue
        program = modules[k][2]
        if program not in out:
            continue
        scope = scope_of(tables[program].get(op), scopes)
        out[program]["total_s"] += duration / 1e9
        out[program]["seconds"][scope or "other"] += duration / 1e9
    out = {name: dict(acc, seconds=dict(acc["seconds"]))
           for name, acc in out.items() if acc["runs"]}
    return out or None
