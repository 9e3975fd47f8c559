"""The serve cells of ``BENCHMARK.json``, rehearsed on the CPU: see
``tests/benchmark_rehearsal.py``."""

import pytest

from tests import benchmark_rehearsal as br

CELLS = br.SERVE_CELLS
rehearsed = br.rehearsed


@pytest.mark.parametrize("rehearsed, metric", br.end_to_end_cases(CELLS),
                         indirect=["rehearsed"])
def test_end_to_end_metric_is_reported(rehearsed, metric):
    br.check_metric(rehearsed, "plain", metric)


@pytest.mark.parametrize("rehearsed", CELLS, indirect=True)
def test_every_operation_is_correct(rehearsed):
    br.check_correct(rehearsed, "plain")
    br.check_correct(rehearsed, "traced")


@pytest.mark.parametrize("rehearsed, metric", br.per_layer_cases(CELLS),
                         indirect=["rehearsed"])
def test_per_layer_metric_is_reported(rehearsed, metric):
    br.check_metric(rehearsed, "traced", metric)


@pytest.mark.parametrize("span", br.spans_read("examples", recorded=False))
def test_span_the_benchmark_reads_is_recorded(span):
    br.check_span_is_recorded(span)


def test_a_control_of_the_sparse_cells_judge_reads_not_correct():
    """``serve-dots3-longcontext`` rehearsed with the reference taking each
    query's LAST ``index_topk`` rows for the indexer's choice (a window
    passing for the selection: ``perf/runners/serve_closed_sparse.py``
    ``CONTROL``): every request is served, and the line reads NOT
    correct."""
    import json
    import os
    import subprocess
    import sys

    code = (
        "import sys\n"
        "sys.argv = ['perf/run.py']\n"
        "sys.path.insert(0, 'perf')\n"
        "import run\n"
        "load = run._load_module\n"
        "def controlled(folder, name):\n"
        "    module = load(folder, name)\n"
        "    if name == 'serve_closed_sparse':\n"
        "        module.CONTROL = 'selection_is_the_last_rows'\n"
        "    return module\n"
        "run._load_module = controlled\n"
        "run.main(['--workload', 'serve-dots3-longcontext', '--seed', '1',\n"
        "          '--seconds', '2', '--rehearse', '--trace', '0'])\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run([sys.executable, "-c", code], cwd=br.REPO, env=env,
                          capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-1500:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearse"] is True
    said = next(text for text in done.stdout.splitlines()
                if text.startswith("serve checks"))
    assert '"control": "selection_is_the_last_rows"' in said
