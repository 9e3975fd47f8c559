"""tools/apexlint — the AST-level invariant gate (ISSUE 12).

Three surfaces under test:

1. **The committed tree is clean** — the tier-1 acceptance: zero
   findings over the real repo, every surviving pragma reasoned AND
   load-bearing (hits > 0), and the APX003 registry exactness holds.
2. **Each rule detects / passes / suppresses** — fixture twins per
   rule (``tests/fixtures/apexlint/``: violation, clean, pragma'd)
   run against a scaffolded mini-repo, plus pragma accounting
   (APX000: reasonless and unknown-rule pragmas are findings;
   unused pragmas are reported, never failures).
3. **The gate** — the CLI rc convention (0 clean / 1 findings /
   2 crash-as-finding) and the ``--json`` machine line.

No jax needed anywhere here: the linter is stdlib+AST by design.
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.apexlint import run  # noqa: E402
from tools.apexlint import config as lint_config  # noqa: E402
from tools.apexlint.cli import main as lint_main  # noqa: E402

FIXTURES = os.path.join(REPO, "tests", "fixtures", "apexlint")

# ---------------------------------------------------------------------------
# mini-repo scaffold: the smallest tree that is APX003-clean, so each
# rule test adds exactly its fixture and asserts exactly its findings
# ---------------------------------------------------------------------------

# the mini ledger carries the raw reads the real allowlist designates
# for this path (else those entries would read as stale over the
# fixture tree); both knobs are infra-prefix-covered for APX003
SCAFFOLD_LEDGER = (
    "import os\n\n"
    'INFRA_KNOB_PREFIXES = ("APEX_INFRA_", "APEX_TELEMETRY_LEDGER",\n'
    '                       "APEX_FAULT_PLAN")\n\n\n'
    "def ledger_path():\n"
    "    return os.environ.get(\"APEX_TELEMETRY_LEDGER\")\n\n\n"
    "def fault_stamp():\n"
    "    return os.environ.get(\"APEX_FAULT_PLAN\")\n")
SCAFFOLD_API = """# mini API
<!-- apexlint: knob-table begin -->
| Env | Effect |
|---|---|
| `APEX_DOCED=1` | documented fixture knob |
<!-- apexlint: knob-table end -->
"""
SCAFFOLD_READER = (
    "from apex_tpu.dispatch.tiles import env_flag, env_int\n\n\n"
    "def f():\n"
    "    return env_flag(\"APEX_DOCED\") or env_int(\"APEX_INFRA_X\")\n")


def make_tree(tmp_path, files=None, api_md=SCAFFOLD_API):
    """Build a scaffolded mini-repo; ``files`` maps repo-relative
    paths to content or to a fixture basename to copy."""
    base = {
        "apex_tpu/telemetry/ledger.py": SCAFFOLD_LEDGER,
        "apex_tpu/reader.py": SCAFFOLD_READER,
        "docs/API.md": api_md,
    }
    base.update(files or {})
    for rel, content in base.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        src = os.path.join(FIXTURES, content)
        if "\n" not in content and os.path.exists(src):
            shutil.copy(src, p)
        else:
            p.write_text(content)
    return str(tmp_path)


def rule_findings(report, rule):
    return [f for f in report.findings if f.rule == rule]


# ---------------------------------------------------------------------------
# 1. the committed tree
# ---------------------------------------------------------------------------

def test_repo_is_clean():
    """THE acceptance gate: zero findings over the committed tree —
    APX001-006 hold, the knob registry is exact, and no reasonless
    pragma survives (a reasonless pragma is an APX000 finding)."""
    report = run(REPO)
    assert report.ok, "\n" + report.render()


def test_repo_pragmas_are_reasoned_and_load_bearing():
    """Every surviving pragma carries a reason AND suppresses at least
    one live finding — a pragma that eats nothing is rot the report
    names (unused), and this tree must carry none."""
    report = run(REPO)
    assert report.pragmas, "the tree documents its suppressions inline"
    for p in report.pragmas:
        assert p.reason and len(p.reason) > 10, (p.path, p.line)
        assert p.hits > 0, f"unused pragma {p.path}:{p.line}"


def test_config_paths_exist_in_repo():
    """Deletion rot: every DESIGNATED_READERS / STDLIB_ONLY_CLAIMED
    path must exist (the rules skip absent paths so fixture trees can
    carry subsets — this test is where a stale path fails)."""
    for path, _spec, reason in lint_config.DESIGNATED_READERS:
        assert os.path.exists(os.path.join(REPO, path)), path
        assert reason.strip(), path
    for spec in lint_config.STDLIB_ONLY_CLAIMED:
        assert os.path.exists(os.path.join(REPO, spec.rstrip("/"))), spec


# ---------------------------------------------------------------------------
# 2. per-rule fixtures
# ---------------------------------------------------------------------------

def test_apx001_violation_clean_pragma(tmp_path):
    root = make_tree(tmp_path, {
        "apex_tpu/v.py": "apx001_violation.py",
        "apex_tpu/c.py": "apx001_clean.py",
        "apex_tpu/p.py": "apx001_pragma.py",
    })
    report = run(root, rules=["APX001"])
    found = rule_findings(report, "APX001")
    # module-level read, the default-argument read, and the
    # module-level env_flag helper call — never the clean twin's
    # function-body reads
    assert {f.path for f in found} == {"apex_tpu/v.py"}
    assert len(found) == 3
    assert any("APEX_FIX_HELPER" in f.msg for f in found)
    assert [f for f in report.suppressed if f.path == "apex_tpu/p.py"]


def test_apx002_violation_clean_pragma(tmp_path):
    root = make_tree(tmp_path, {
        "apex_tpu/v.py": "apx002_violation.py",
        "apex_tpu/c.py": "apx002_clean.py",
        "apex_tpu/p.py": "apx002_pragma.py",
    })
    report = run(root, rules=["APX002"])
    found = rule_findings(report, "APX002")
    assert {f.path for f in found} == {"apex_tpu/v.py"}
    # .get, the module-constant subscript, and the `in` presence test
    assert len(found) == 3
    assert any("APEX_FIX_CONST" in f.msg for f in found), \
        "NAME = 'APEX_FIX_CONST' must resolve through the constant map"
    assert [f for f in report.suppressed if f.path == "apex_tpu/p.py"]


def test_apx002_designated_reader_allows(tmp_path):
    # drop the violation at a path the real allowlist designates for
    # this knob: apex_tpu/telemetry/costs.py owns APEX_COST_ANALYSIS
    root = make_tree(tmp_path, {
        "apex_tpu/telemetry/costs.py":
            "import os\n\n\ndef f():\n"
            "    return os.environ.get(\"APEX_COST_ANALYSIS\")\n",
    })
    report = run(root, rules=["APX002"])
    assert not rule_findings(report, "APX002"), report.render()


def test_apx003_exactness_both_directions(tmp_path):
    api = SCAFFOLD_API.replace(
        "| `APEX_DOCED=1` | documented fixture knob |",
        "| `APEX_DOCED=1` | documented fixture knob |\n"
        "| `APEX_NEVER_READ` | a no-op row |")
    root = make_tree(tmp_path, {
        "apex_tpu/u.py":
            "from apex_tpu.dispatch.tiles import env_flag\n\n\n"
            "def f():\n"
            "    return env_flag(\"APEX_UNDOCUMENTED\")\n",
    }, api_md=api)
    report = run(root, rules=["APX003"])
    msgs = [f.msg for f in rule_findings(report, "APX003")]
    assert any("APEX_UNDOCUMENTED" in m and "absent from" in m
               for m in msgs), msgs
    assert any("APEX_NEVER_READ" in m and "never read" in m
               for m in msgs), msgs
    assert len(msgs) == 2


def test_apx003_infra_prefix_coverage_and_staleness(tmp_path):
    # APEX_INFRA_X is read but undocumented — covered by the prefix, no
    # finding; a prefix nothing matches is stale
    root = make_tree(tmp_path, files={
        "apex_tpu/telemetry/ledger.py":
            'INFRA_KNOB_PREFIXES = ("APEX_INFRA_", "APEX_GONE_")\n'})
    report = run(root, rules=["APX003"])
    msgs = [f.msg for f in rule_findings(report, "APX003")]
    assert len(msgs) == 1 and "APEX_GONE_" in msgs[0], msgs


def test_apx003_missing_markers_is_a_finding(tmp_path):
    root = make_tree(tmp_path, api_md="# no markers here\n")
    report = run(root, rules=["APX003"])
    assert any("markers missing" in f.msg
               for f in rule_findings(report, "APX003"))


def test_apx004_violation_clean_pragma(tmp_path):
    root = make_tree(tmp_path, {
        "benchmarks/v.py": "apx004_violation.py",
        "benchmarks/c.py": "apx004_clean.py",
        "benchmarks/p.py": "apx004_pragma.py",
        "benchmarks/pf.py": "apx004_pragma_file.py",
    })
    report = run(root, rules=["APX004"])
    found = rule_findings(report, "APX004")
    # time.time, the from-imported perf_counter, block_until_ready
    assert {f.path for f in found} == {"benchmarks/v.py"}
    assert len(found) == 3
    sup = {f.path for f in report.suppressed}
    assert {"benchmarks/p.py", "benchmarks/pf.py"} <= sup
    # the file-level pragma ate BOTH of pf.py's calls
    assert sum(f.path == "benchmarks/pf.py"
               for f in report.suppressed) == 2


def test_apx004_ignores_package_and_tools(tmp_path):
    root = make_tree(tmp_path, {
        "apex_tpu/t.py": "apx004_violation.py",
    })
    report = run(root, rules=["APX004"])
    assert not rule_findings(report, "APX004"), \
        "APX004 scopes benchmarks/ (tracing.py IS the implementation)"


@pytest.fixture()
def ref_tree(tmp_path_factory):
    ref = tmp_path_factory.mktemp("reference")
    (ref / "pkg").mkdir()
    (ref / "pkg" / "ok.py").write_text("\n".join(
        f"# line {i}" for i in range(1, 11)) + "\n")
    (ref / "pkg" / "sub").mkdir()
    (ref / "pkg" / "sub" / "deep.py").write_text("a = 1\nb = 2\nc = 3\nd = 4\n")
    return str(ref)


def test_apx005_violation_clean_pragma(tmp_path, ref_tree):
    root = make_tree(tmp_path, {
        "apex_tpu/v.py": "apx005_violation.py",
        "apex_tpu/c.py": "apx005_clean.py",
        "apex_tpu/p.py": "apx005_pragma.py",
    })
    report = run(root, rules=["APX005"], reference_root=ref_tree)
    found = rule_findings(report, "APX005")
    assert {f.path for f in found} == {"apex_tpu/v.py"}
    msgs = " ".join(f.msg for f in found)
    assert "does not resolve" in msgs and "out of range" in msgs
    assert len(found) == 2
    assert [f for f in report.suppressed if f.path == "apex_tpu/p.py"]


def test_apx005_skips_without_reference_tree(tmp_path):
    root = make_tree(tmp_path, {"apex_tpu/v.py": "apx005_violation.py"})
    report = run(root, rules=["APX005"],
                 reference_root=str(tmp_path / "nowhere"))
    assert not rule_findings(report, "APX005")
    assert any("APX005 skipped" in n for n in report.notes)


def test_apx006_direct_transitive_clean(tmp_path):
    # fixtures land AT claimed paths (config.STDLIB_ONLY_CLAIMED)
    root = make_tree(tmp_path, {
        "apex_tpu/serving/scheduler.py": "apx006_violation.py",
        "apex_tpu/serving/lifecycle.py": "apx006_transitive.py",
        "apex_tpu/helper_mod.py": "apx006_helper_jax.py",
        "apex_tpu/dispatch/tiles.py": "apx006_clean.py",
    })
    report = run(root, rules=["APX006"])
    found = rule_findings(report, "APX006")
    by_path = {f.path: f.msg for f in found}
    assert "apex_tpu/serving/scheduler.py" in by_path
    assert "numpy" in by_path["apex_tpu/serving/scheduler.py"]
    # the transitive chain is named end-to-end
    assert "apex_tpu/serving/lifecycle.py" in by_path
    assert "helper_mod" in by_path["apex_tpu/serving/lifecycle.py"]
    assert "apex_tpu/dispatch/tiles.py" not in by_path, \
        "function-level jax import is the sanctioned lazy pattern"
    assert len(found) == 2


def test_apx006_resolves_relative_imports(tmp_path):
    """`from .helper_rel import x` at module level must be walked like
    its absolute spelling — the silent false-negative a relative
    re-spelling of the scheduler's kv_cache import would open."""
    root = make_tree(tmp_path, {
        "apex_tpu/serving/scheduler.py": "apx006_relative.py",
        "apex_tpu/serving/helper_rel.py": "apx006_helper_jax.py",
    })
    report = run(root, rules=["APX006"])
    found = rule_findings(report, "APX006")
    assert len(found) == 1 and "helper_rel" in found[0].msg, \
        report.render()


# ---------------------------------------------------------------------------
# pragma machinery (APX000 + accounting)
# ---------------------------------------------------------------------------

def test_pragma_without_reason_is_a_finding(tmp_path):
    root = make_tree(tmp_path, {"benchmarks/n.py": "apx000_noreason.py"})
    report = run(root, rules=["APX004"])
    # the reasonless pragma does NOT suppress, and is itself flagged
    assert rule_findings(report, "APX004")
    assert any(f.rule == "APX000" and "without a reason" in f.msg
               for f in report.findings)


def test_pragma_with_unknown_rule_is_a_finding(tmp_path):
    root = make_tree(tmp_path, {"apex_tpu/u.py": "apx000_unknown.py"})
    report = run(root, rules=["APX001"])
    assert any(f.rule == "APX000" and "unknown rule" in f.msg
               for f in report.findings)


def test_unused_pragma_reported_not_failing(tmp_path):
    root = make_tree(tmp_path, {"benchmarks/u.py": "apx000_unused.py"})
    report = run(root, rules=["APX004"])
    assert report.ok
    assert len(report.unused_pragmas()) == 1
    assert "UNUSED" in report.render()


def test_pragma_accounting_in_json(tmp_path):
    root = make_tree(tmp_path, {
        "benchmarks/p.py": "apx004_pragma.py",
        "benchmarks/v.py": "apx004_violation.py",
    })
    report = run(root, rules=["APX004"])
    blob = report.as_json()
    assert blob["ok"] is False
    assert blob["findings"]["APX004"] == 3
    assert blob["suppressed"]["APX004"] == 1
    assert blob["pragmas"] == 1 and blob["unused_pragmas"] == 0


# ---------------------------------------------------------------------------
# 3. CLI
# ---------------------------------------------------------------------------

def test_cli_json_machine_line_on_repo():
    """ONE real subprocess for the script surface (`python -m
    tools.apexlint --json`): rc 0 on the committed tree and one
    parseable machine line — the window_report/CI trending hook."""
    env = dict(os.environ)
    out = subprocess.run(
        [sys.executable, "-m", "tools.apexlint", "--json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    blob = json.loads(out.stdout.strip().splitlines()[-1])
    assert blob["ok"] is True and blob["total"] == 0
    assert blob["pragmas"] >= 1 and blob["unused_pragmas"] == 0
    # rule skips are visible in the machine line: an "ok" that skipped
    # APX005 (no reference tree) must not read like a validated one
    assert isinstance(blob["notes"], list)
    if not os.path.isdir(lint_config.REFERENCE_ROOT):
        assert any("APX005 skipped" in n for n in blob["notes"])


def test_cli_rc1_on_findings(tmp_path):
    root = make_tree(tmp_path, {"apex_tpu/v.py": "apx001_violation.py"})
    rc = lint_main(["--root", root, "--rule", "APX001"])
    assert rc == 1


def test_cli_rc2_crash_as_finding(tmp_path):
    """A linter that dies must exit 2 with a message, never a silent
    pass (docs/API.md as a DIRECTORY makes the registry parse blow
    up past the per-file guards)."""
    root = make_tree(tmp_path)
    os.remove(tmp_path / "docs" / "API.md")
    (tmp_path / "docs" / "API.md").mkdir()
    env = dict(os.environ)
    out = subprocess.run(
        [sys.executable, "-m", "tools.apexlint", "--root", root],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stdout + out.stderr
    assert "CRASH: apexlint error" in out.stderr
    # under --json the stdout contract stays one parseable line
    out = subprocess.run(
        [sys.executable, "-m", "tools.apexlint", "--root", root, "--json"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2
    blob = json.loads(out.stdout.strip().splitlines()[-1])
    assert blob["ok"] is False and "CRASH" in blob["crash"]


def test_cli_rejects_unknown_rule_id():
    """A typo'd --rule must not select zero rules and report a green
    gate (explicit request ≠ preference — it raises)."""
    env = dict(os.environ)
    out = subprocess.run(
        [sys.executable, "-m", "tools.apexlint", "--rule", "APX04"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120)
    assert out.returncode == 2, out.stdout + out.stderr
    assert "unknown rule id" in out.stderr
