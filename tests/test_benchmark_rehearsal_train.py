"""The train cells of ``BENCHMARK.json``, rehearsed on the CPU: see
``tests/benchmark_rehearsal.py``."""

import pytest

from tests import benchmark_rehearsal as br

CELLS = br.TRAIN_CELLS
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


@pytest.mark.parametrize("span", br.spans_read("examples"))
def test_span_the_benchmark_reads_is_recorded(span):
    br.check_span_is_recorded(span)
