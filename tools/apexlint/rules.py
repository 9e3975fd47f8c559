"""The six invariant rules. Each is a function
``(repo, config, report, reference_root=None) -> [Finding]`` walking
already-parsed ASTs; nothing here imports repo code (see core.py).
"""

import ast
import os
import re

from tools.apexlint.core import Finding

APEX_NAME_RE = re.compile(r"APEX_[A-Z0-9_]+")


# ---------------------------------------------------------------------------
# shared AST helpers
# ---------------------------------------------------------------------------

def _is_environ(node, ctx):
    """True for ``os.environ`` (any os alias, or direct import)."""
    if isinstance(node, ast.Attribute) and node.attr == "environ" \
            and isinstance(node.value, ast.Name) \
            and node.value.id in ctx.os_aliases:
        return True
    if isinstance(node, ast.Name):
        return any(alias == node.id and orig == "environ"
                   for alias, orig in ctx.direct_env_names)
    return False


def _is_getenv_call(node, ctx):
    f = node.func
    if isinstance(f, ast.Attribute) and f.attr == "getenv" \
            and isinstance(f.value, ast.Name) and f.value.id in ctx.os_aliases:
        return True
    if isinstance(f, ast.Name):
        return any(alias == f.id and orig == "getenv"
                   for alias, orig in ctx.direct_env_names)
    return False


def _literal_str(node, ctx):
    """Resolve a node to a string: literal constant, or a module-level
    ``NAME = "..."`` constant (the faults.py ``ENV`` pattern)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    if isinstance(node, ast.Name):
        return ctx.str_constants.get(node.id)
    return None


def iter_env_reads(ctx):
    """Yield ``(node, name_or_None)`` for every os.environ/os.getenv
    READ in the file: ``environ.get/getenv calls``, ``environ[k]``
    loads, ``k in environ`` tests, ``environ.setdefault``. Writes
    (``environ[k] = v``, ``pop``) are not reads."""
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            if _is_getenv_call(node, ctx):
                arg = node.args[0] if node.args else None
                yield node, _literal_str(arg, ctx)
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr in ("get", "setdefault") \
                    and _is_environ(node.func.value, ctx):
                arg = node.args[0] if node.args else None
                yield node, _literal_str(arg, ctx)
        elif isinstance(node, ast.Subscript) \
                and isinstance(getattr(node, "ctx", None), ast.Load) \
                and _is_environ(node.value, ctx):
            yield node, _literal_str(node.slice, ctx)
        elif isinstance(node, ast.Compare) \
                and any(isinstance(op, (ast.In, ast.NotIn))
                        for op in node.ops) \
                and any(_is_environ(c, ctx) for c in node.comparators):
            yield node, _literal_str(node.left, ctx)


def iter_env_writes(ctx):
    """Yield ``(node, name_or_None)`` for env WRITES: subscript
    stores, ``pop``, and the subprocess-env idiom
    ``dict(os.environ, APEX_X="1")``."""
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for t in targets:
                if isinstance(t, ast.Subscript) \
                        and _is_environ(t.value, ctx):
                    yield t, _literal_str(t.slice, ctx)
        elif isinstance(node, ast.Call):
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr == "pop" \
                    and _is_environ(f.value, ctx) and node.args:
                yield node, _literal_str(node.args[0], ctx)
            elif isinstance(f, ast.Name) and f.id == "dict" \
                    and node.args and _is_environ(node.args[0], ctx):
                for kw in node.keywords:
                    if kw.arg:
                        yield node, kw.arg


def iter_helper_reads(ctx, helper_names):
    """Yield ``(node, name)`` for ``env_int("APEX_X")``-style calls to
    the one-home parsers (any receiver: ``tiles.env_int`` or a direct
    import)."""
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        f = node.func
        fname = f.attr if isinstance(f, ast.Attribute) else (
            f.id if isinstance(f, ast.Name) else None)
        if fname in helper_names and node.args:
            name = _literal_str(node.args[0], ctx)
            if name:
                yield node, name


# ---------------------------------------------------------------------------
# APX001 — no import-time env reads in apex_tpu/
# ---------------------------------------------------------------------------

def apx001(repo, config, report, reference_root=None):
    findings = []
    for ctx in repo.ctxs(config.SCOPE_PKG):
        import_time = _import_time_nodes(ctx.tree)
        reads = list(iter_env_reads(ctx))
        # the one-home parsers count too: env_flag(...) at module level
        # is the same frozen-at-import knob, just better dressed
        reads += list(iter_helper_reads(ctx, config.ENV_HELPERS))
        for node, name in reads:
            if id(node) in import_time:
                what = name or "os.environ"
                findings.append(Finding(
                    "APX001", ctx.path, node.lineno,
                    f"import-time env read ({what}) — knobs are read at "
                    "TRACE time; move inside a function (PERF.md §0 / "
                    "ISSUE 5)"))
    return findings


def _import_time_nodes(tree):
    """ids of nodes evaluated at import: everything except function
    bodies (decorators and argument defaults DO run at import)."""
    ids = set()

    def mark(node):
        ids.add(id(node))
        for child in ast.iter_child_nodes(node):
            mark(child)

    def visit(node):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for d in node.decorator_list:
                mark(d)
            for default in (node.args.defaults + node.args.kw_defaults):
                if default is not None:
                    mark(default)
            return  # body is call-time
        if isinstance(node, ast.Lambda):
            return  # body is call-time
        ids.add(id(node))
        for child in ast.iter_child_nodes(node):
            visit(child)

    visit(tree)
    return ids


# ---------------------------------------------------------------------------
# APX002 — APEX_* raw reads outside the one-home parsers / allowlist
# ---------------------------------------------------------------------------

def _reader_entry(path, knob, config):
    """Index of the DESIGNATED_READERS entry covering this (file,
    knob) read, or None — the ONE matcher shared by allowlisting and
    stale-entry accounting (two copies could desynchronize)."""
    for i, (entry_path, spec, _reason) in enumerate(
            config.DESIGNATED_READERS):
        if path != entry_path:
            continue
        if spec.endswith("*"):
            if knob.startswith(spec[:-1]):
                return i
        elif knob == spec:
            return i
    return None


def apx002(repo, config, report, reference_root=None):
    findings = []
    hit_entries = set()
    for ctx in repo.ctxs(config.SCOPE_NONTEST):
        for node, name in iter_env_reads(ctx):
            if not name or not name.startswith("APEX_"):
                continue
            entry = _reader_entry(ctx.path, name, config)
            if entry is not None:
                hit_entries.add(entry)
                continue
            findings.append(Finding(
                "APX002", ctx.path, node.lineno,
                f"raw env read of {name} outside its designated reader "
                "— parse through dispatch.tiles.env_int/env_choice/"
                "env_float/env_flag, or add a DESIGNATED_READERS entry "
                "naming this file the knob's one home"))
    # allowlist hygiene: an entry no raw read matches is rot (the
    # check_api_parity stale-allowlist pattern). Only judged for files
    # present in the scanned tree — fixture trees carry a subset; a
    # DELETED file's entries are caught by the tier-1 test asserting
    # every configured path exists in the real repo.
    for i, (p, spec, _r) in enumerate(config.DESIGNATED_READERS):
        if i not in hit_entries and repo.exists(p):
            findings.append(Finding(
                "APX002", "tools/apexlint (config)", 0,
                f"stale DESIGNATED_READERS entry ({p}, {spec}) — no raw "
                "read matches it; prune"))
    return findings


# ---------------------------------------------------------------------------
# APX003 — knob registry: code uses == docs table + infra coverage
# ---------------------------------------------------------------------------

def _infra_prefixes(repo, config):
    """``ledger.INFRA_KNOB_PREFIXES`` read via AST, never import."""
    ctx = repo.ctx(config.LEDGER_PY)
    if ctx is None:
        return None
    for node in ctx.tree.body:
        if isinstance(node, ast.Assign) and any(
                isinstance(t, ast.Name)
                and t.id == "INFRA_KNOB_PREFIXES" for t in node.targets):
            if isinstance(node.value, (ast.Tuple, ast.List)):
                return tuple(
                    e.value for e in node.value.elts
                    if isinstance(e, ast.Constant)
                    and isinstance(e.value, str))
    return None


def _documented_knobs(repo, config):
    """Knob names from the docs/API.md table between the apexlint
    markers — the machine-checkable shape: every knob fully spelled
    inside backticks in each row's first cell."""
    if not repo.exists(config.API_MD):
        return None, 0
    text = repo.read_text(config.API_MD)
    begin = text.find(config.KNOB_TABLE_BEGIN)
    end = text.find(config.KNOB_TABLE_END)
    if begin < 0 or end < 0 or end < begin:
        return None, 0
    table = text[begin:end]
    line0 = text[:begin].count("\n") + 1
    knobs = {}
    for i, line in enumerate(table.splitlines()):
        if not line.lstrip().startswith("|"):
            continue
        cells = re.split(r"(?<!\\)\|", line)  # \| is a literal pipe
        first_cell = cells[1] if len(cells) >= 3 else ""
        for span in re.findall(r"`([^`]+)`", first_cell):
            for m in APEX_NAME_RE.finditer(span):
                knobs.setdefault(m.group(0), line0 + i)
    return knobs, line0


def apx003(repo, config, report, reference_root=None):
    findings = []
    prefixes = _infra_prefixes(repo, config)
    if prefixes is None:
        findings.append(Finding(
            "APX003", config.LEDGER_PY, 1,
            "could not extract INFRA_KNOB_PREFIXES (literal tuple "
            "expected)"))
        prefixes = ()
    documented, _ = _documented_knobs(repo, config)
    if documented is None:
        findings.append(Finding(
            "APX003", config.API_MD, 1,
            f"knob table markers missing ({config.KNOB_TABLE_BEGIN} … "
            f"{config.KNOB_TABLE_END}) — the table must be "
            "machine-checkable"))
        documented = {}

    used = {}  # name -> first (path, line)
    helper_names = config.ENV_HELPERS
    for ctx in repo.ctxs(config.SCOPE_NONTEST):
        for it in (iter_env_reads(ctx), iter_env_writes(ctx),
                   iter_helper_reads(ctx, helper_names)):
            for node, name in it:
                if name and name.startswith("APEX_"):
                    used.setdefault(name, (ctx.path,
                                           getattr(node, "lineno", 1)))

    for name in sorted(set(used) - set(documented)):
        if any(name.startswith(p) for p in prefixes):
            continue  # infra-covered (ledger.INFRA_KNOB_PREFIXES)
        path, line = used[name]
        findings.append(Finding(
            "APX003", path, line,
            f"knob {name} is read/set in code but absent from the "
            f"docs/API.md knob table (document it or drop the read)"))
    for name in sorted(set(documented) - set(used)):
        findings.append(Finding(
            "APX003", config.API_MD, documented[name],
            f"knob {name} is documented but never read or set anywhere "
            "in non-test code — a no-op knob row (the PR 4 audit class)"))
    for p in prefixes:
        if not any(u == p or u.startswith(p) for u in used):
            findings.append(Finding(
                "APX003", config.LEDGER_PY, 1,
                f"stale INFRA_KNOB_PREFIXES entry {p!r}: no used knob "
                "matches it"))
    return findings


# ---------------------------------------------------------------------------
# APX004 — timing hygiene in benchmarks/
# ---------------------------------------------------------------------------

_TIME_ATTRS = {"time", "perf_counter", "perf_counter_ns", "monotonic",
               "monotonic_ns"}


def apx004(repo, config, report, reference_root=None):
    findings = []
    for ctx in repo.ctxs(config.SCOPE_BENCH):
        time_aliases = {"time"}
        direct = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "time":
                        time_aliases.add(a.asname or "time")
            elif isinstance(node, ast.ImportFrom) and node.module == "time":
                for a in node.names:
                    if a.name in _TIME_ATTRS:
                        direct.add(a.asname or a.name)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            what = None
            if isinstance(f, ast.Attribute):
                if f.attr in _TIME_ATTRS and isinstance(f.value, ast.Name) \
                        and f.value.id in time_aliases:
                    what = f"time.{f.attr}()"
                elif f.attr == "block_until_ready":
                    what = "block_until_ready"
            elif isinstance(f, ast.Name) and f.id in direct:
                what = f"{f.id}()"
            if what:
                findings.append(Finding(
                    "APX004", ctx.path, node.lineno,
                    f"naked {what} in benchmarks/ — the PERF.md §0 "
                    "timing rules have ONE implementation "
                    "(apex_tpu.telemetry.tracing); use Tracer/Span, or "
                    "pragma with the reason this is not a measured row"))
    return findings


# ---------------------------------------------------------------------------
# APX005 — reference citations resolve (file exists, line in range)
# ---------------------------------------------------------------------------

_CITE_RE = re.compile(
    r"(?<![\w/])([A-Za-z0-9_][\w./-]*\.(?:py|cu|cpp|cuh|h|cc))"
    r":(\d+)(?:\s*[-–]\s*(\d+))?")


class _RefIndex:
    def __init__(self, ref_root):
        self.root = ref_root
        self.paths = []
        for dirpath, dirnames, filenames in os.walk(ref_root):
            dirnames[:] = [d for d in dirnames if d != ".git"]
            for f in filenames:
                self.paths.append(os.path.relpath(
                    os.path.join(dirpath, f), ref_root))
        self._nlines = {}

    def candidates(self, cited):
        cands = [p for p in self.paths
                 if p == cited or p.endswith("/" + cited)]
        if not cands and "/" not in cited:
            cands = [p for p in self.paths
                     if os.path.basename(p) == cited]
        return cands

    def nlines(self, rel):
        if rel not in self._nlines:
            try:
                with open(os.path.join(self.root, rel), "rb") as fh:
                    self._nlines[rel] = fh.read().count(b"\n") + 1
            except OSError:
                self._nlines[rel] = 0
        return self._nlines[rel]


def _docstrings(tree):
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            if (node.body and isinstance(node.body[0], ast.Expr)
                    and isinstance(node.body[0].value, ast.Constant)
                    and isinstance(node.body[0].value.value, str)):
                yield node.body[0].value


def apx005(repo, config, report, reference_root=None):
    ref_root = reference_root or config.REFERENCE_ROOT
    if not os.path.isdir(ref_root):
        report.notes.append(
            f"APX005 skipped: reference tree not found at {ref_root}")
        return []
    index = _RefIndex(ref_root)
    repo_suffixes = None  # lazily-built set for repo self-citations
    findings = []
    for ctx in repo.ctxs(config.SCOPE_CITED):
        for doc in _docstrings(ctx.tree):
            text = doc.value
            if "reference" not in text.lower():
                continue
            for m in _CITE_RE.finditer(text):
                cited, a, b = m.group(1), int(m.group(2)), m.group(3)
                line_in_doc = text.count("\n", 0, m.start())
                at = doc.lineno + line_in_doc
                cands = index.candidates(cited)
                if not cands:
                    if repo_suffixes is None:
                        repo_suffixes = repo.walk_py(
                            ("apex_tpu", "benchmarks", "tools", "tests"))
                    if any(p == cited or p.endswith("/" + cited)
                           or os.path.basename(p) == cited
                           for p in repo_suffixes):
                        continue  # repo self-citation, not a reference one
                    findings.append(Finding(
                        "APX005", ctx.path, at,
                        f"citation {m.group(0)!r} does not resolve under "
                        f"{ref_root}"))
                    continue
                end = int(b) if b else a
                if not any(index.nlines(c) >= end for c in cands):
                    best = max(index.nlines(c) for c in cands)
                    findings.append(Finding(
                        "APX005", ctx.path, at,
                        f"citation {m.group(0)!r}: line out of range "
                        f"(resolved file has {best} lines)"))
    return findings


# ---------------------------------------------------------------------------
# APX006 — stdlib-only claims hold, transitively over the import graph
# ---------------------------------------------------------------------------

def _module_rel(repo, dotted):
    """apex_tpu.x.y -> repo-relative file, resolving pkg __init__."""
    base = dotted.replace(".", "/")
    for cand in (base + ".py", base + "/__init__.py"):
        if repo.exists(cand):
            return cand
    return None


def _module_level_imports(ctx):
    """(dotted_module, lineno) for every import executed at import time
    (module body, incl. try/if blocks; ``if TYPE_CHECKING`` skipped).
    Relative imports are resolved against the module's own package so
    ``from .kv_cache import x`` cannot slip past the walk."""
    pkg_parts = ctx.path[:-3].replace("/", ".").split(".")
    if pkg_parts[-1] == "__init__":
        pkg_parts = pkg_parts[:-1]      # the package itself
    else:
        pkg_parts = pkg_parts[:-1]      # the containing package
    out = []

    def visit(body):
        for node in body:
            if isinstance(node, ast.Import):
                for a in node.names:
                    out.append((a.name, node.lineno))
            elif isinstance(node, ast.ImportFrom):
                if node.level == 0:
                    base = [node.module] if node.module else None
                else:
                    up = node.level - 1
                    anchor = pkg_parts[:len(pkg_parts) - up] if up else \
                        list(pkg_parts)
                    if not anchor:
                        continue  # escapes the tree — nothing to walk
                    base = anchor + ([node.module] if node.module else [])
                if base is None:
                    continue
                mod = ".".join(base)
                for a in node.names:
                    out.append((f"{mod}.{a.name}", node.lineno))
            elif isinstance(node, ast.If):
                test = ast.dump(node.test)
                if "TYPE_CHECKING" in test:
                    continue
                visit(node.body)
                visit(node.orelse)
            elif isinstance(node, ast.Try):
                visit(node.body)
                for h in node.handlers:
                    visit(h.body)
                visit(node.orelse)
                visit(node.finalbody)
    visit(ctx.tree.body)
    return out


def apx006(repo, config, report, reference_root=None):
    findings = []
    claimed = []
    for spec in config.STDLIB_ONLY_CLAIMED:
        # absent paths are skipped: fixture trees carry a subset, and
        # deletion rot is caught by the tier-1 config-paths-exist test
        if spec.endswith("/"):
            if repo.exists(spec.rstrip("/")):
                claimed.extend(repo.walk_py((spec.rstrip("/"),)))
        elif repo.exists(spec):
            claimed.append(spec)

    def offenders(rel, seen):
        """(dotted, via_chain) for every denylisted module-level import
        reachable from ``rel`` over explicit in-package imports. The
        documented parent-package exception applies: importing
        apex_tpu.x.y executes apex_tpu/__init__ (~3s, noted in the
        resilience docstring) but only explicitly-imported TARGET
        modules are walked."""
        if rel in seen:
            return []
        seen.add(rel)
        ctx = repo.ctx(rel)
        if ctx is None:
            return []
        out = []
        for dotted, lineno in _module_level_imports(ctx):
            top = dotted.split(".")[0]
            if top in config.STDLIB_DENYLIST:
                out.append((top, f"{rel}:{lineno}"))
            elif top == "apex_tpu":
                target = _module_rel(repo, dotted)
                if target is None and "." in dotted:
                    # "from apex_tpu.mod import name" where name is a
                    # def — resolve the module instead
                    target = _module_rel(repo, dotted.rsplit(".", 1)[0])
                if target and target != "apex_tpu/__init__.py":
                    for top2, via in offenders(target, seen):
                        out.append((top2, f"{rel}:{lineno} -> {via}"))
        return out

    for rel in claimed:
        ctx = repo.ctx(rel)
        if ctx is None:
            continue
        # one finding per offending import chain, anchored at the
        # claimed module's own import line so a fix has an address
        for top, via in offenders(rel, set()):
            line = int(via.split(" -> ")[0].rsplit(":", 1)[1])
            findings.append(Finding(
                "APX006", rel, line,
                f"stdlib-only module reaches a module-level import of "
                f"{top} (via {via})"))
    return findings


RULES = {
    "APX001": apx001,
    "APX002": apx002,
    "APX003": apx003,
    "APX004": apx004,
    "APX005": apx005,
    "APX006": apx006,
}
