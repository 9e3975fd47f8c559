"""The span metrics' readers on hand-built rings, and the command's
rehearsal with and without them. By hand, like the rest of this
directory:

    JAX_PLATFORMS=cpu python3 -m pytest perf/tests/test_span_metrics.py -q
"""

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from apex_tpu.telemetry import spans  # noqa: E402
from perf import check_manifest  # noqa: E402

SERVE = ("round_host_ms", "decode_fetch_ms", "queue_wait_p95_ms",
         "token_gap_p99_ms")
TRAIN = ("trainer_host_ms", "first_chunk_s")


def _read(name, record):
    path = check_manifest.reader_file("layer_metrics", name)
    spec = importlib.util.spec_from_file_location("reader_" + name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(record)


class _Clock:
    """Stands in for the recorder's ``time``: the test sets the time."""
    now = 0.0

    def perf_counter(self):
        return self.now


@pytest.fixture(autouse=True)
def clock(monkeypatch):
    spans.clear()
    spans.set_enabled(True)
    fake = _Clock()
    monkeypatch.setattr(spans, "time", fake)
    yield fake
    spans.clear()
    spans.set_enabled(True)


def _stretch(clock, name, seconds):
    with spans.span(name):
        clock.now += seconds


def _serve_ring(clock):
    """Five rounds from t=100: decode rounds of 10 ms host + 90 ms wait,
    one prefill round (60 + 90) third; two requests, a token a round."""
    clock.now, rounds = 100.0, []
    for k in range(5):
        t0 = clock.now
        with spans.span("engine.round", tick=k) as rnd:
            _stretch(clock, "engine.schedule", 0.06 if k == 2 else 0.01)
            _stretch(clock, "decode.dispatch", 0.02)
            _stretch(clock, "decode.fetch", 0.07)
            rnd.set(prefilled=int(k == 2), decoded=2,
                    emitted=[(7, 1, clock.now), (8, 1, clock.now)])
        rounds.append({"t0": t0, "t1": clock.now})
        clock.now += 1e-3
    spans.record("request.queue", 100.05, 100.06, rid=7, prompt=4)
    spans.record("request.queue", 100.20, 100.25, rid=8, prompt=4)
    spans.record("request.queue", 99.0, 99.9, rid=6, prompt=4)  # before
    return {"rounds": rounds}


def test_serve_readers_on_a_hand_built_ring(clock):
    record = _serve_ring(clock)
    assert _read("round_host_ms", record) == pytest.approx(10.0)
    assert _read("decode_fetch_ms", record) == pytest.approx(90.0)
    # waits of 10 and 50 ms: p95 by linear interpolation
    assert _read("queue_wait_p95_ms", record) == pytest.approx(48.0)
    # gaps per request: 101, 151, 101, 101 ms; p99 over the eight
    assert _read("token_gap_p99_ms", record) == pytest.approx(151.0)


def _train_ring(clock, chunks=3, first_s=20.0):
    clock.now = 1.0
    _stretch(clock, "trainer.chunk", 1.0)         # a calibration call
    clock.now = 50.0
    for k in range(chunks + 1):
        with spans.span("trainer.chunk", iter=k):
            _stretch(clock, "chunk.dispatch", first_s - 1.0 if k == 0
                     else 0.002)
            _stretch(clock, "chunk.fetch", 1.0 if k == 0 else 0.9)
            _stretch(clock, "chunk.host", 0.003)
    return {"chunks": [{"seconds": 0.905}] * chunks}


def test_train_readers_on_a_hand_built_ring(clock):
    record = _train_ring(clock)
    assert _read("trainer_host_ms", record) == pytest.approx(5.0)
    assert _read("first_chunk_s", record) == pytest.approx(20.0)


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_a_reader_returns_nothing_from_a_ring_that_lost_the_window(
        name, clock):
    spans.clear(capacity=12)
    record = _serve_ring(clock) if name in SERVE else _train_ring(clock)
    assert spans.dropped() > 0
    assert _read(name, record) is None


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_a_reader_returns_nothing_without_spans(name, clock):
    record = {"rounds": [{"t0": 1.0, "t1": 2.0}]} if name in SERVE \
        else {"chunks": [{"seconds": 1.0}]}
    assert _read(name, record) is None
    spans.set_enabled(False)
    record = _serve_ring(clock) if name in SERVE else _train_ring(clock)
    assert _read(name, record) is None


def _rehearse(cell, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    done = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", cell, "--seed",
         str(2 ** 31 + 11), "--seconds", "2", "--trace", str(trace),
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell, names", [("serve-large-batch", SERVE),
                                         ("train-medium", TRAIN)])
def test_a_traced_rehearsal_prints_the_span_metrics(cell, names):
    traced = _rehearse(cell, 1)["metrics"]
    assert set(names) <= set(traced)
    assert all(traced[n]["value"] >= 0 for n in names)
    assert not set(SERVE + TRAIN) & set(_rehearse(cell, 0)["metrics"])
    if cell == "serve-large-batch":
        whole = traced["round_host_ms"]["value"] \
            + traced["decode_fetch_ms"]["value"]
        assert whole == pytest.approx(traced["decode_round_ms"]["value"],
                                      rel=0.1)


def test_the_manifest_is_still_sound_and_names_the_six():
    with open(check_manifest.MANIFEST) as fh:
        manifest = json.load(fh)
    assert check_manifest.check(manifest) == []
    mine = [m for m in manifest["per_layer"]
            if m["source"] == "program_span"]
    assert [m["name"] for m in mine] == list(SERVE + TRAIN)
    assert manifest["per_layer"][-6:] == mine    # appended, nothing moved
