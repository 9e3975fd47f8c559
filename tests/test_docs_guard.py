"""The documents a newcomer reads first say what the tree is: every
path they cite exists, and the "Spans" table of ``docs/API.md`` names
exactly the spans the program records.
"""

import functools
import glob
import itertools
import os
import re

import pytest

from tests.benchmark_rehearsal import recorded_under, span_calls

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOCUMENTS = ("README.md", "CLAUDE.md", "docs/API.md")
PROGRAM = ("apex_tpu", "examples")

_PATH = re.compile(r"(?<![\w/.])(?:apex_tpu|benchmarks|tools)/[\w./{},*-]+")
_BRACES = re.compile(r"\{([^{}]*)\}")


def _read(relative):
    with open(os.path.join(REPO, relative)) as fh:
        return fh.read()


def _made_at_run_time():
    """What ``.gitignore`` lists: a document may cite it, a checkout
    does not hold it."""
    return {line.strip().rstrip("/") for line in
            _read(".gitignore").splitlines()
            if line.strip() and not line.startswith("#")}


def _expand(path):
    """``a/{b,c}.py`` as ``a/b.py`` and ``a/c.py``."""
    parts = _BRACES.split(path)
    choices = [[p] if i % 2 == 0 else p.split(",")
               for i, p in enumerate(parts)]
    return ["".join(c) for c in itertools.product(*choices)]


def cited_paths(text):
    found = set()
    for match in _PATH.finditer(text):
        for path in _expand(match.group(0).rstrip(".,")):
            found.add(path.rstrip("/"))
    return found


@pytest.mark.parametrize("document", DOCUMENTS)
def test_every_cited_path_exists(document):
    ignored = _made_at_run_time()
    missing = sorted(
        path for path in cited_paths(_read(document))
        if path not in ignored
        and not glob.glob(os.path.join(REPO, path)))
    assert not missing, f"{document} cites paths the tree lacks: {missing}"


def test_the_path_scan_reads_braces_globs_and_full_stops():
    assert cited_paths("see `apex_tpu/serving/{model,mimo}.py`, and "
                       "tools/apexlint/. Not docs/apex_tpu/x.py.") == {
        "apex_tpu/serving/model.py", "apex_tpu/serving/mimo.py",
        "tools/apexlint"}


# ---- the Spans table -------------------------------------------------

_SPAN = re.compile(r"`([a-z_]+(?:\.[a-z_]+)+)`")


@functools.cache
def documented_spans():
    """The names in the first column of the table under the "Spans"
    heading of ``docs/API.md``, before any parenthesis of attributes."""
    section = _read("docs/API.md").split("### Spans", 1)[1]
    rows = list(itertools.takewhile(
        lambda line: line.startswith("|"),
        itertools.dropwhile(lambda line: not line.startswith("|"),
                            section.splitlines())))
    names = []
    for row in rows[2:]:
        first = row.split("|")[1].split("(", 1)[0]
        names += _SPAN.findall(first)
    return names


@functools.cache
def recorded_spans():
    """``(constant names, f-string regexes)`` of every span the program
    opens or records."""
    names, patterns = set(), []
    for top in PROGRAM:
        for path in glob.glob(os.path.join(REPO, top, "**", "*.py"),
                              recursive=True):
            found = span_calls(path)
            names |= found[0]
            patterns += found[1]
    return names, patterns


@pytest.mark.parametrize("span", sorted(
    set(documented_spans()) | recorded_spans()[0]))
def test_span_is_documented_and_recorded(span):
    assert span in documented_spans(), (
        f"the program records {span!r} and docs/API.md's Spans table "
        f"leaves it out")
    assert any(recorded_under(span, top) for top in PROGRAM), (
        f"docs/API.md's Spans table names {span!r} and nothing under "
        f"{PROGRAM} records it")


def test_every_spelled_span_is_documented():
    """A span opened as ``f"{program}.dispatch"`` is in the table under
    a name that f-string can spell."""
    documented, (names, patterns) = documented_spans(), recorded_spans()
    assert len(documented) == len(set(documented))
    assert names and patterns
    for pattern in patterns:
        assert any(pattern.fullmatch(d) for d in documented), pattern
