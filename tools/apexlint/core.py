"""Framework: file model, pragma accounting, report rendering.

The linter is static all the way down: files are parsed with ``ast``,
facts about the repo (knob prefixes, the docs knob table) are extracted
from source text, and nothing under ``apex_tpu/`` is ever imported.
"""

import ast
import os
import re

PRAGMA_RE = re.compile(
    r"#\s*apexlint:\s*(disable|disable-file)\s*=\s*"
    r"(APX\d{3}(?:\s*,\s*APX\d{3})*)"          # rule list
    r"(?:\s*(?:—|–|--|-)\s*(.*?))?\s*$"  # — reason
)
# a line that tries to be a pragma but fails the strict shape above
PRAGMA_ATTEMPT_RE = re.compile(r"#\s*apexlint\s*:")


class Finding:
    """One violation: ``rule`` id, repo-relative ``path``, 1-based
    ``line``, human message. ``suppressed`` is set by pragma matching
    (a suppressed finding is counted, never fails the run)."""

    def __init__(self, rule, path, line, msg):
        self.rule = rule
        self.path = path
        self.line = line
        self.msg = msg
        self.suppressed = False

    def render(self):
        return f"{self.path}:{self.line}: {self.rule}: {self.msg}"

    def sort_key(self):
        return (self.path, self.line, self.rule, self.msg)


class Pragma:
    """One ``# apexlint: disable[-file]=`` comment. ``hits`` counts the
    findings it suppressed — a pragma that suppresses nothing is
    reported as unused (rot, like a stale allowlist entry)."""

    def __init__(self, path, line, rules, reason, file_level):
        self.path = path
        self.line = line
        self.rules = rules
        self.reason = reason
        self.file_level = file_level
        self.hits = 0


class FileCtx:
    """One parsed source file: AST, raw lines, pragmas, and the
    os-alias map rules need to recognize ``os.environ`` spelled as
    ``_os.environ`` or ``from os import environ``."""

    def __init__(self, relpath, source, known_rules):
        self.path = relpath
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=relpath)
        self.pragmas = []
        self.pragma_findings = []  # APX000
        self._scan_pragmas(known_rules)
        self.os_aliases, self.direct_env_names = self._scan_os_imports()
        # module-top-level NAME = "literal" str constants, for resolving
        # os.environ.get(ENV) where ENV is a module constant
        self.str_constants = {
            t.id: n.value.value
            for n in self.tree.body if isinstance(n, ast.Assign)
            and isinstance(n.value, ast.Constant)
            and isinstance(n.value.value, str)
            for t in n.targets if isinstance(t, ast.Name)
        }

    def _scan_pragmas(self, known_rules):
        for i, raw in enumerate(self.lines, start=1):
            if "apexlint" not in raw:
                continue
            m = PRAGMA_RE.search(raw)
            if not m:
                if PRAGMA_ATTEMPT_RE.search(raw):
                    self.pragma_findings.append(Finding(
                        "APX000", self.path, i,
                        "malformed apexlint pragma (want '# apexlint: "
                        "disable=APXnnn — <reason>')"))
                continue
            kind, rule_list, reason = m.groups()
            rules = tuple(r.strip() for r in rule_list.split(","))
            unknown = [r for r in rules if r not in known_rules]
            if unknown:
                self.pragma_findings.append(Finding(
                    "APX000", self.path, i,
                    f"pragma names unknown rule(s) {' '.join(unknown)}"))
                continue
            if not (reason or "").strip():
                self.pragma_findings.append(Finding(
                    "APX000", self.path, i,
                    "pragma without a reason — every suppression states "
                    "why (ISSUE 12 acceptance)"))
                continue
            self.pragmas.append(Pragma(
                self.path, i, rules, reason.strip(),
                file_level=(kind == "disable-file")))

    def _scan_os_imports(self):
        aliases, direct = set(), set()
        for node in ast.walk(self.tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    if a.name == "os":
                        aliases.add(a.asname or "os")
            elif isinstance(node, ast.ImportFrom) and node.module == "os":
                for a in node.names:
                    if a.name in ("environ", "getenv"):
                        direct.add((a.asname or a.name, a.name))
        return aliases, direct

    def suppress(self, finding):
        """Apply this file's pragmas to one finding; True if eaten."""
        for p in self.pragmas:
            if finding.rule not in p.rules:
                continue
            if p.file_level:
                p.hits += 1
                return True
            if p.line == finding.line:
                p.hits += 1
                return True
            # a standalone comment-line pragma covers the first
            # statement after its comment block (the pragma may open a
            # multi-line comment explaining the reason)
            if (p.line < finding.line
                    and self.lines[p.line - 1].lstrip().startswith("#")
                    and all(self.lines[i].lstrip().startswith("#")
                            or not self.lines[i].strip()
                            for i in range(p.line, finding.line - 1))):
                p.hits += 1
                return True
        return False


class Repo:
    """Lazily-parsed view of the tree rooted at ``root``. Rules pull
    files by scope; parse failures surface as findings, not crashes
    (a file the linter cannot read is a file the gate cannot vouch
    for)."""

    EXCLUDE_DIRS = {"__pycache__", ".git", ".compile_cache", "reference"}
    # the linter does not lint itself (its config spells every knob and
    # rule pattern as literals); fixtures are linted only by the tests
    EXCLUDE_PREFIXES = ("tools/apexlint/", "tests/fixtures/")

    def __init__(self, root, known_rules):
        self.root = os.path.abspath(root)
        self.known_rules = known_rules
        self._cache = {}
        self.parse_findings = []

    def abspath(self, rel):
        return os.path.join(self.root, rel)

    def exists(self, rel):
        return os.path.exists(self.abspath(rel))

    def read_text(self, rel):
        with open(self.abspath(rel), encoding="utf-8") as fh:
            return fh.read()

    def ctx(self, rel):
        if rel not in self._cache:
            try:
                self._cache[rel] = FileCtx(rel, self.read_text(rel),
                                           self.known_rules)
            except (SyntaxError, UnicodeDecodeError, OSError) as e:
                self.parse_findings.append(Finding(
                    "APX000", rel, getattr(e, "lineno", 1) or 1,
                    f"unparseable file: {type(e).__name__}: {e}"))
                self._cache[rel] = None
        return self._cache[rel]

    def walk_py(self, tops):
        """Yield repo-relative .py paths under the given top dirs/files,
        sorted, excluding the linter itself and test fixtures."""
        out = []
        for top in tops:
            top_abs = self.abspath(top)
            if os.path.isfile(top_abs):
                out.append(top)
                continue
            for dirpath, dirnames, filenames in os.walk(top_abs):
                dirnames[:] = sorted(d for d in dirnames
                                     if d not in self.EXCLUDE_DIRS)
                for f in sorted(filenames):
                    if not f.endswith(".py"):
                        continue
                    rel = os.path.relpath(os.path.join(dirpath, f),
                                          self.root)
                    if rel.startswith(self.EXCLUDE_PREFIXES):
                        continue
                    out.append(rel)
        return [p for p in out if self.exists(p)]

    def ctxs(self, tops):
        for rel in self.walk_py(tops):
            c = self.ctx(rel)
            if c is not None:
                yield c


class Report:
    """Outcome of one run: findings (split live/suppressed), pragma
    accounting, and the render/JSON surfaces the CLI prints."""

    def __init__(self, rule_ids):
        self.rule_ids = list(rule_ids)
        self.findings = []       # unsuppressed — these fail the gate
        self.suppressed = []
        self.pragmas = []
        self.notes = []

    @property
    def ok(self):
        return not self.findings

    def counts(self, items):
        c = {r: 0 for r in self.rule_ids}
        for f in items:
            c[f.rule] = c.get(f.rule, 0) + 1
        return {r: n for r, n in c.items() if n}

    def unused_pragmas(self):
        return [p for p in self.pragmas if p.hits == 0]

    def as_json(self):
        return {
            "ok": self.ok,
            "findings": self.counts(self.findings),
            "total": len(self.findings),
            "suppressed": self.counts(self.suppressed),
            "pragmas": len(self.pragmas),
            "unused_pragmas": len(self.unused_pragmas()),
            # skip notes ride the machine line too: an "ok" with
            # "APX005 skipped: no reference tree" must be
            # distinguishable from an ok that validated citations
            "notes": list(self.notes),
        }

    def render(self, verbose=False):
        lines = []
        for f in sorted(self.findings, key=Finding.sort_key):
            lines.append(f.render())
        if verbose or not self.findings:
            for n in self.notes:
                lines.append(f"note: {n}")
        # pragma account — suppressions are visible debt, never silent
        if self.pragmas:
            lines.append(
                f"pragmas: {len(self.pragmas)} "
                f"({len(self.suppressed)} finding(s) suppressed"
                + (f", {len(self.unused_pragmas())} UNUSED"
                   if self.unused_pragmas() else "") + ")")
            if verbose:
                for p in sorted(self.pragmas,
                                key=lambda p: (p.path, p.line)):
                    kind = "file" if p.file_level else "line"
                    lines.append(
                        f"  {p.path}:{p.line} [{kind}] "
                        f"{','.join(p.rules)} hits={p.hits} — {p.reason}")
        for p in self.unused_pragmas():
            lines.append(f"note: UNUSED pragma {p.path}:{p.line} "
                         f"({','.join(p.rules)}) — prune it")
        if self.findings:
            lines.append(f"FAIL: {len(self.findings)} finding(s)")
        else:
            lines.append("OK: apexlint clean")
        return "\n".join(lines)


def run(root, rules=None, reference_root=None):
    """Run the rule set over the tree at ``root``; returns a Report.

    ``rules`` filters by id (default: all). ``reference_root``
    overrides the APX005 resolution tree (default
    ``config.REFERENCE_ROOT``; absent tree = rule skipped with a
    note, like check_api_parity)."""
    from tools.apexlint import config
    from tools.apexlint.rules import RULES

    selected = {rid: fn for rid, fn in RULES.items()
                if rules is None or rid in rules}
    repo = Repo(root, known_rules=set(RULES))
    report = Report(sorted(set(RULES) | {"APX000"}))

    raw = []
    for rid, fn in sorted(selected.items()):
        raw.extend(fn(repo, config, report,
                      reference_root=reference_root))
    raw.extend(repo.parse_findings)

    # pragma application + accounting (APX000 findings are about the
    # pragmas themselves and cannot be suppressed by one)
    seen_files = set()
    for f in raw:
        ctx = repo._cache.get(f.path)
        if ctx is not None and ctx.suppress(f):
            report.suppressed.append(f)
        else:
            report.findings.append(f)
    for ctx in repo._cache.values():
        if ctx is None or ctx.path in seen_files:
            continue
        seen_files.add(ctx.path)
        report.pragmas.extend(ctx.pragmas)
        # pragma hygiene (APX000) rides along for every scanned file,
        # rule filter or not: a reasonless pragma must never pass just
        # because the run was narrowed
        report.findings.extend(ctx.pragma_findings)
    return report
