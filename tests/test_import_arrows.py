"""Which way the package's imports may point. An ``ast`` scan of every
module under ``apex_tpu/``: nothing is imported, and an import inside a
function counts like one at the top. Each rule is true of this tree; a
rule that is not yet (``parallel/zero3.py`` imports ``serving``) is in
ROADMAP.md, not here.
"""

import ast
import functools
import glob
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@functools.cache
def _imports(path):
    """The dotted names a module imports: ``import a.b`` as ``a.b``,
    ``from a import b`` as ``a.b`` (``b`` may be a submodule), relative
    imports made absolute."""
    package = os.path.relpath(path, REPO).split(os.sep)[:-1]
    with open(path) as fh:
        tree = ast.parse(fh.read())
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            found |= {a.name for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            base = node.module or ""
            if node.level:
                up = package[:len(package) - node.level + 1]
                base = ".".join(up + ([base] if base else []))
            found |= {f"{base}.{a.name}" for a in node.names}
    return found


def _modules(*under):
    """``{path relative to the repo: imports}`` of the ``.py`` files at
    or below each of ``under`` (paths relative to ``apex_tpu/``)."""
    out = {}
    for entry in under:
        full = os.path.join(REPO, "apex_tpu", entry)
        paths = [full] if full.endswith(".py") else glob.glob(
            os.path.join(full, "**", "*.py"), recursive=True)
        assert paths and all(os.path.exists(p) for p in paths), entry
        for path in paths:
            out[os.path.relpath(path, REPO)] = _imports(path)
    return out


def _under(name, prefix):
    return name == prefix or name.startswith(prefix + ".")


# (rule, the modules it binds, what they may not import, the exceptions
# as (module or None for any, import) pairs)
RULES = [
    ("resilience imports only the stdlib",
     ("resilience",), ("apex_tpu",), ()),
    ("telemetry/spans.py imports only the stdlib",
     ("telemetry/spans.py",), ("apex_tpu",), ()),
    ("telemetry imports of resilience only ledger.py -> faults",
     ("telemetry",), ("apex_tpu.resilience",),
     (("apex_tpu/telemetry/ledger.py", "apex_tpu.resilience.faults"),)),
    ("the package imports no harness, tool, benchmark or test",
     ("",), ("benchmarks", "tools", "perf", "tests", "bench"), ()),
    ("ops imports no model, server or telemetry",
     ("ops",), ("apex_tpu.serving", "apex_tpu.transformer",
                "apex_tpu.telemetry"), ()),
    ("dispatch imports nothing else of the package",
     ("dispatch",), ("apex_tpu",), ((None, "apex_tpu.dispatch"),)),
    ("the cache, the scheduler and the request log know no model family",
     ("serving/kv_cache.py", "serving/scheduler.py",
      "serving/lifecycle.py"),
     ("apex_tpu.serving.model", "apex_tpu.serving.mimo"), ()),
    ("a model family knows neither the engine nor the scheduler",
     ("serving/model.py", "serving/mimo.py"),
     ("apex_tpu.serving.engine", "apex_tpu.serving.scheduler"), ()),
]


@pytest.mark.parametrize("rule, under, forbidden, allowed", RULES,
                         ids=[r[0] for r in RULES])
def test_import_arrow(rule, under, forbidden, allowed):
    broken = {}
    for module, imports in _modules(*under).items():
        hits = sorted(
            name for name in imports
            if any(_under(name, f) for f in forbidden)
            and not any(m in (None, module) and _under(name, ok)
                        for m, ok in allowed))
        if hits:
            broken[module] = hits
    assert not broken, f"{rule}: {broken}"


def test_the_scan_sees_lazy_and_from_imports():
    """The scanner's own proof, on modules whose imports are known: the
    engine takes ``resilience`` as ``from apex_tpu import resilience``,
    the checkpointer takes ``faults`` inside a method."""
    engine = _imports(os.path.join(REPO, "apex_tpu", "serving",
                                   "engine.py"))
    assert {"apex_tpu.resilience", "apex_tpu.resilience.faults"} <= engine
    lazy = _imports(os.path.join(REPO, "apex_tpu", "checkpoint.py"))
    assert "apex_tpu.resilience.faults" in lazy
