"""Every fault site ``apex_tpu/resilience/faults.py`` documents still has
a caller. The module checks no site name (a plan may name anything), so
a site whose caller a later PR deletes would stay in the docstring, its
chaos test would script a fault that never fires, and the test would
pass for the wrong reason. An ``ast`` scan, nothing executed: a site
counts as called when a module under ``apex_tpu/`` that calls a hook of
``faults`` holds its name as a string constant, or spells it with an
f-string out of one (``engine.py``'s ``f"serve_{program}"`` with
``"prefill"`` and ``"decode"``).
"""

import ast
import functools
import glob
import json
import os
import re

import pytest

from apex_tpu.resilience import faults

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HOOKS = ("fire", "denied", "corrupt", "burst", "transform_json",
         "damage_file")


def _documented_sites():
    """The names in the docstring's ``"site": "a" | "b" | ...`` list."""
    block = faults.__doc__.split('{"site":', 1)[1].split('"kind":', 1)[0]
    return re.findall(r'"([a-z_]+)"', block)


def _scan(path):
    """``(constants, f-string regexes, first arguments of hook calls)``
    of one module; None when it never calls a hook of ``faults``."""
    with open(path) as fh:
        tree = ast.parse(fh.read())
    calls = [n for n in ast.walk(tree) if isinstance(n, ast.Call)
             and isinstance(n.func, ast.Attribute)
             and n.func.attr in HOOKS and isinstance(n.func.value, ast.Name)
             and "faults" in n.func.value.id]
    if not calls:
        return None
    constants = {n.value for n in ast.walk(tree)
                 if isinstance(n, ast.Constant) and isinstance(n.value, str)}
    spelled = [re.compile("".join(
        re.escape(p.value) if isinstance(p, ast.Constant) else "([a-z_]+)"
        for p in n.values))
        for n in ast.walk(tree) if isinstance(n, ast.JoinedStr)]
    named = {c.args[0].value for c in calls
             if c.args and isinstance(c.args[0], ast.Constant)}
    return constants, spelled, named


@functools.cache
def _callers():
    found = {}
    for path in glob.glob(os.path.join(REPO, "apex_tpu", "**", "*.py"),
                          recursive=True):
        if os.path.samefile(path, faults.__file__):
            continue
        scanned = _scan(path)
        if scanned is not None:
            found[os.path.relpath(path, REPO)] = scanned
    return found


def _is_called(site, callers):
    for constants, spelled, _ in callers.values():
        if site in constants:
            return True
        for pattern in spelled:
            match = pattern.fullmatch(site)
            if match and all(g in constants for g in match.groups()):
                return True
    return False


@pytest.mark.parametrize("site", _documented_sites())
def test_documented_site_parses_and_has_a_caller(site, monkeypatch):
    plan = [{"site": site, "kind": "hang", "seconds": 0}]
    monkeypatch.setenv(faults.ENV, json.dumps({"faults": plan}))
    assert faults.plan() == plan and faults.plan_hash().startswith("fp-")
    callers = _callers()
    assert _is_called(site, callers), (
        f"faults.py documents the site {site!r} and no module under "
        f"apex_tpu/ that calls a hook of faults names it "
        f"(callers: {sorted(callers)})")


def test_docstring_and_callers_agree_both_ways():
    documented = _documented_sites()
    assert len(documented) == len(set(documented))
    callers = _callers()
    named = set().union(*(n for _, _, n in callers.values()))
    assert named <= set(documented), (
        f"hook calls name sites the docstring leaves out: "
        f"{sorted(named - set(documented))}")
    # every site the failure-mode table scripts is in the list, and the
    # list holds nothing the table leaves out
    table = set(re.findall(r"\b([a-z]+_[a-z]+)/[a-z_]+",
                           faults.__doc__.split("Failure-mode map", 1)[1]))
    assert table == set(documented), (table ^ set(documented))
