"""Tier 1 walks the judged benchmark: every cell of ``BENCHMARK.json``
through ``perf/run.py --rehearse`` on the CPU, once plain and once
traced, and one case for every number the driver will look for on the
chip. A program change that renames a span, a counter or an attribute
that ``perf/`` reads turns the case of that metric red here, in the
session that can still fix it, and not as a ``null`` in the ledger.

Shared by ``test_benchmark_rehearsal_{train,serve}.py`` (two files so
that ``--dist loadfile`` gives each to a worker of its own). Every case
is read from the manifest; nothing here names a cell. Reads ``perf/``
and ``BENCHMARK.json``, edits neither.
"""

import ast
import functools
import glob
import json
import math
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
with open(os.path.join(REPO, "BENCHMARK.json")) as _fh:
    MANIFEST = json.load(_fh)

# what a CPU line can carry of the per-layer metrics: a device trace
# needs a device
CPU_SOURCES = ("program_span", "program_counter", "host_clock")
# the one such metric a CPU line leaves out, by name: it divides by the
# chip's published peak, and perf/peaks.json has no row for the CPU
NEEDS_THE_PEAK = "train_mfu"


def _cells_of(metric):
    return metric.get("workloads") or [w["name"]
                                       for w in MANIFEST["workloads"]]


TRAIN_CELLS = [w["name"] for w in MANIFEST["workloads"] if any(
    m["name"] == "train_tok_s" and w["name"] in _cells_of(m)
    for m in MANIFEST["end_to_end"])]
SERVE_CELLS = [w["name"] for w in MANIFEST["workloads"]
               if w["name"] not in TRAIN_CELLS]


def end_to_end_cases(cells):
    return [(c, m["name"]) for c in cells for m in MANIFEST["end_to_end"]
            if c in _cells_of(m)]


def per_layer_cases(cells):
    return [(c, m["name"]) for c in cells for m in MANIFEST["per_layer"]
            if c in _cells_of(m) and m["source"] in CPU_SOURCES
            and m["name"] != NEEDS_THE_PEAK]


def _rehearse(cell, chips, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={chips}"))
    env.pop("JAX_COMPILATION_CACHE_DIR", None)   # run.py places its own
    done = subprocess.run(
        [sys.executable, *MANIFEST["command"][1:], "--workload", cell,
         "--seed", "1", "--seconds", "3", "--rehearse", "--trace",
         str(trace)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600)
    line = None
    if done.returncode == 0:
        line = json.loads(done.stdout.strip().splitlines()[-1])
    return {"rc": done.returncode, "line": line,
            "said": (done.stderr or done.stdout)[-1500:]}


@functools.cache
def _both_rehearsals(cell):
    chips = next(w["chips"] for w in MANIFEST["workloads"]
                 if w["name"] == cell)
    return {"cell": cell, "plain": _rehearse(cell, chips, 0),
            "traced": _rehearse(cell, chips, 1)}


@pytest.fixture(scope="module")
def rehearsed(request):
    """Both rehearsals of the cell named by the (indirect) parameter,
    run once a process however the cases are ordered. A run that fails
    is handed on, not raised, so that every case can say which metric
    the chip would have lost."""
    return _both_rehearsals(request.param)


def _line(rehearsed, which, what):
    run, cell = rehearsed[which], rehearsed["cell"]
    assert run["rc"] == 0, (
        f"{cell}: {what} is lost: perf/run.py --rehearse exited "
        f"{run['rc']}:\n{run['said']}")
    return run["line"]


def check_metric(rehearsed, which, name):
    line, cell = _line(rehearsed, which, name), rehearsed["cell"]
    value = line["metrics"].get(name, {}).get("value")
    assert isinstance(value, (int, float)) and math.isfinite(value), (
        f"{cell}: the rehearsal's line has no finite {name} "
        f"(got {value!r}); metrics: {sorted(line['metrics'])}")


def check_correct(rehearsed, which):
    line = _line(rehearsed, which, "correct")
    assert line["correct"] is True and line["failed"] == 0, line
    assert line["rehearse"] is True
    assert line["device"]["platform"] == "cpu"


# ---- the span names perf/ reads by name ------------------------------
# A reader sums its root's children out of a defaultdict, so a child
# span the program has renamed reads 0.0, not None: the value stays
# finite and only the name can tell. Every dotted string constant of
# perf/span_ring.py, perf/span_account.py and perf/layer_metrics/ has to
# be a string literal of the program.

_SPAN_NAME = re.compile(r"^[a-z_]+\.[a-z_]+$")
_HELPERS = ("span_ring.py", "span_account.py")


def _string_constants(path):
    """``{string: {name of the enclosing top-level function or None}}``
    of a source file, docstrings left out."""
    with open(path) as fh:
        tree = ast.parse(fh.read())
    out = {}
    for top in tree.body:
        where = top.name if isinstance(top, ast.FunctionDef) else None
        docs = {id(n.value) for n in ast.walk(top)
                if isinstance(n, ast.Expr)
                and isinstance(n.value, ast.Constant)}
        for n in ast.walk(top):
            if isinstance(n, ast.Constant) and isinstance(n.value, str) \
                    and id(n) not in docs:
                out.setdefault(n.value, set()).add(where)
    return out


def _helper_reads(path):
    """``{top-level name of a helper: the span names whoever uses that
    name reads}``: a function's own dotted constants, and those of every
    top-level function or constant of the file it refers to, however far
    down (``first_token_parts`` -> ``requests``; ``rounds`` ->
    ``WAITS``)."""
    with open(path) as fh:
        tree = ast.parse(fh.read())
    own, refers = {}, {}
    for top in tree.body:
        if isinstance(top, ast.FunctionDef):
            defined = [top.name]
        elif isinstance(top, ast.Assign):
            defined = [t.id for t in top.targets if isinstance(t, ast.Name)]
        else:
            continue
        docs = {id(n.value) for n in ast.walk(top)
                if isinstance(n, ast.Expr)
                and isinstance(n.value, ast.Constant)}
        texts = {n.value for n in ast.walk(top)
                 if isinstance(n, ast.Constant) and isinstance(n.value, str)
                 and id(n) not in docs and _SPAN_NAME.match(n.value)}
        names = {n.id for n in ast.walk(top) if isinstance(n, ast.Name)}
        for name in defined:
            own[name], refers[name] = texts, names
    reads = {}
    for name in own:
        seen, todo = set(), [name]
        while todo:
            at = todo.pop()
            if at in own and at not in seen:
                seen.add(at)
                todo += refers[at]
        reads[name] = set().union(*(own[at] for at in seen))
    return reads


@functools.cache
def spans_perf_reads():
    """``{span name: sorted metric names that read it}``."""
    helpers = {"perf." + name[:-3]: _helper_reads(
        os.path.join(REPO, "perf", name)) for name in _HELPERS}
    reads = {}
    for path in glob.glob(os.path.join(REPO, "perf", "layer_metrics",
                                       "*.py")):
        metric = os.path.basename(path)[:-3]
        for text, _ in _string_constants(path).items():
            if _SPAN_NAME.match(text):
                reads.setdefault(text, set()).add(metric)
        with open(path) as fh:
            imports = [n for n in ast.walk(ast.parse(fh.read()))
                       if isinstance(n, ast.ImportFrom)
                       and n.module in helpers]
        for node in imports:
            for alias in node.names:
                for text in helpers[node.module].get(alias.name, ()):
                    reads.setdefault(text, set()).add(metric)
    return {span: sorted(metrics) for span, metrics in reads.items()}


def span_calls(path):
    """``(names, regexes)`` of the first argument of every
    ``spans.span(...)`` / ``spans.record(...)`` call of a source file:
    the constant names, and a regex for every f-string (a span opened
    as ``f"{program}.dispatch"`` is recorded under whatever that
    matches)."""
    with open(path) as fh:
        tree = ast.parse(fh.read())
    names, patterns = set(), []
    for node in ast.walk(tree):
        if not (isinstance(node, ast.Call) and node.args
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("span", "record")
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "spans"):
            continue
        first = node.args[0]
        if isinstance(first, ast.Constant):
            names.add(first.value)
        elif isinstance(first, ast.JoinedStr):
            patterns.append(re.compile("".join(
                re.escape(part.value) if isinstance(part, ast.Constant)
                else "[a-z_]+" for part in first.values)))
    return names, patterns


@functools.cache
def _program_strings(top):
    literals, patterns = set(), []
    for path in glob.glob(os.path.join(REPO, top, "**", "*.py"),
                          recursive=True):
        literals |= set(_string_constants(path))
        patterns += span_calls(path)[1]
    return literals, patterns


def recorded_under(span, top):
    """Whether a file under ``top`` holds ``span`` as a string literal,
    or an f-string that can spell it."""
    literals, patterns = _program_strings(top)
    return span in literals or any(p.fullmatch(span) for p in patterns)


def spans_read(under, recorded=True):
    """The spans perf/ reads that a file under ``under`` (a directory of
    the program) records; with ``recorded`` False, all the others, so
    that two callers share every span between them."""
    return [s for s in sorted(spans_perf_reads())
            if recorded_under(s, under) == recorded]


def check_span_is_recorded(span):
    assert recorded_under(span, "apex_tpu") \
        or recorded_under(span, "examples"), (
        f"perf/ reads the span {span!r} by name and no file under "
        f"apex_tpu/ or examples/ records it: {spans_perf_reads()[span]} "
        f"would read it as 0.0 on the chip")
