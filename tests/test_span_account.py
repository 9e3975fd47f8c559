"""The accounts the benchmark's newest readers draw from the span ring
(``perf/span_account.py`` and the six files of ``perf/layer_metrics/``
that use it): each on a ring built by hand, where every number is known,
and the identity they owe ``ttft_mean_ms`` on a rehearsed run."""

import json
import os
import subprocess
import sys

import pytest

from apex_tpu.telemetry import spans

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from perf import run, span_account  # noqa: E402

NEW = ("queue_wait_mean_ms", "queued_share", "prefill_wait_mean_ms",
       "token_held_mean_ms", "prefill_host_ms", "round_overrun_share",
       "round_overrun_share.ttft")


def read(metric, record):
    return run._load_module("layer_metrics", metric).read(record)


@pytest.fixture(autouse=True)
def _fresh_ring():
    spans.clear()
    spans.set_enabled(True)
    yield
    spans.clear()


class Ring:
    """Records with chosen stamps, parents and attributes."""

    def __init__(self):
        self.ids = iter(range(1, 10 ** 6))
        self.rounds = []

    def put(self, name, t0, t1, parent=None, rid=None, **attrs):
        ident = next(self.ids)
        spans._append((ident, parent, name, t0, t1, rid, attrs or None))
        return ident

    def round(self, t0, children, prefilled=0, decoded=4, cpu_s=None,
              trunks=()):
        """An ``engine.round`` from ``t0`` whose children follow one
        another: ``[(name, seconds)]``; its harness stamps enclose it by
        0.01 ms either side. ``cpu_s=None`` leaves the attribute out, as
        a parent commit does."""
        ident = next(self.ids)
        t, packs = t0, iter(trunks)
        for name, seconds in children:
            attrs = {}
            if name == "prefill.pack":
                attrs["trunk_rows"] = next(packs)
            self.put(name, t, t + seconds, parent=ident, **attrs)
            t += seconds
        attrs = dict(tick=len(self.rounds), prefilled=prefilled,
                     decoded=decoded, emitted=[])
        if cpu_s is not None:
            attrs["cpu_s"] = cpu_s
        spans._append((ident, None, "engine.round", t0, t, None, attrs))
        self.rounds.append({"t0": t0 - 1e-5, "t1": t + 1e-5,
                            "prefilled": prefilled, "decoded_slots": decoded})
        return t

    def request(self, rid, enqueue, admitted, first, rounds=0, blocked=None,
                counted=True):
        attrs = dict(prompt=8)
        if counted:
            attrs.update(rounds=rounds, blocked=blocked)
        self.put("request.queue", enqueue, admitted, rid=rid, **attrs)
        self.put("request.prefill", admitted, first, rid=rid)
        self.put("request.decode", first, first + 1.0, rid=rid, tokens=4)

    def record(self, requests=()):
        return {"rounds": self.rounds, "requests": [
            {"sent": s, "first": f, "finish": f + 1.0} for s, f in requests]}


DECODE = [("engine.schedule", 0.001), ("decode.stage", 0.001),
          ("decode.dispatch", 0.002), ("decode.fetch", 0.005),
          ("decode.commit", 0.001)]
PREFILL = [("engine.schedule", 0.001), ("prefill.pack", 0.002),
           ("prefill.stage", 0.001), ("prefill.dispatch", 0.003),
           ("prefill.fetch", 0.040), ("prefill.commit", 0.001)] + DECODE[1:]


def test_one_queued_request_and_the_three_parts_of_the_wait():
    """Two requests sent at 100.0; admission takes one a round. The first
    token of each is known 46 ms into its round's 57; the second waits
    the first's whole round in the queue."""
    ring = Ring()
    end = ring.round(100.0, PREFILL, prefilled=1, trunks=(128,))
    assert end == pytest.approx(100.057)
    ring.request(0, 100.0001, 100.0005, 100.046)
    end2 = ring.round(100.058, PREFILL, prefilled=1, trunks=(128,))
    ring.request(1, 100.0001, 100.0585, 100.104, rounds=1, blocked="budget")
    record = ring.record([(100.0, end + 1e-5), (100.0, end2 + 1e-5)])
    queue = (0.4 + 58.4) / 2
    assert read("queue_wait_mean_ms", record) == pytest.approx(queue)
    assert read("prefill_wait_mean_ms", record) == pytest.approx(45.5)
    assert read("token_held_mean_ms", record) == pytest.approx(11.0)
    assert read("queued_share", record) == pytest.approx(50.0)
    account = span_account.describe(record)
    assert account["blocked"] == {"budget": 1} and account["queued"] == 1
    assert account["queued_wait_mean_ms"] == pytest.approx(58.4)
    # what is left: sent -> enqueue_wall (0.1 ms) and the clock read
    assert account["unaccounted_ms"] == pytest.approx(0.11, abs=1e-6)
    assert account["ttft_mean_ms"] == pytest.approx(queue + 45.5 + 11.11)


def test_nobody_queued_reads_zero_not_nothing():
    ring = Ring()
    ring.round(10.0, PREFILL, prefilled=1, trunks=(128,))
    ring.request(0, 10.0001, 10.0002, 10.046)
    record = ring.record([(10.0, 10.06)])
    assert read("queued_share", record) == 0.0
    assert read("queue_wait_mean_ms", record) == pytest.approx(0.1)
    assert read("round_overrun_share", record) == 0.0
    # no request at all inside the window: the true mean of nothing is 0
    ring = Ring()
    ring.round(20.0, DECODE)
    assert read("queue_wait_mean_ms", ring.record()) == 0.0
    assert read("queued_share", ring.record()) is None


def test_one_overrun_round_is_held_by_its_fetch_with_the_thread_idle():
    """Six decode-only rounds of 10 ms and one of 120 whose fetch took
    115: the window lost 110 ms to it, the thread ran 3 ms of it."""
    ring, t = Ring(), 50.0
    for k in range(7):
        slow = k == 3
        kids = [(n, 0.115 if slow and n == "decode.fetch" else s)
                for n, s in DECODE]
        t = ring.round(t, kids, cpu_s=0.004) + 0.001
    record = ring.record()
    window = record["rounds"][-1]["t1"] - record["rounds"][0]["t0"]
    share = read("round_overrun_share", record)
    assert share == pytest.approx(100 * 0.110 / window)
    assert read("round_overrun_share.ttft", record) == share
    every, medians, over = span_account.overruns(record)
    assert len(every) == 7 and len(over) == 1
    assert medians == {"decode": pytest.approx(0.010)}
    name, excess = span_account.holder(over[0], every)
    assert (name, excess) == ("decode.fetch", pytest.approx(0.110))
    row, = span_account.describe(record)["overruns"]
    assert row["held_by"] == "decode.fetch" and row["kind"] == "decode"
    assert row["cpu_ms"] == pytest.approx(4.0)
    assert row["host_ms"] == pytest.approx(3.0)


def test_a_round_of_two_prefill_dispatches_is_a_kind_of_its_own():
    """Five rounds of one 128-row dispatch, one of a 256-row and a
    128-row dispatch three times as long: another kind, of one round, so
    it has no median to overrun; ``prefill_host_ms`` is the median over
    all six of the round less its four waits."""
    ring, t = Ring(), 5.0
    for _ in range(5):
        t = ring.round(t, PREFILL, prefilled=1, trunks=(128,))
    two = PREFILL[:6] + PREFILL[1:6] + PREFILL[1:6] + PREFILL[6:]
    t = ring.round(t, two, prefilled=3, trunks=(256, 128, 128))
    record = ring.record()
    every, medians, over = span_account.overruns(record)
    assert set(medians) == {(128,), (256, 128, 128)} and over == []
    assert medians[(128,)] == pytest.approx(0.057)
    assert medians[(256, 128, 128)] == pytest.approx(0.057 + 2 * 0.047)
    assert read("round_overrun_share", record) == 0.0
    # one dispatch: 57 - (3 + 40 + 2 + 5) = 7 ms; three: 7 + 2 x 4
    assert read("prefill_host_ms", record) == pytest.approx(7.0)
    assert [r.seconds - r.waited for r in every][-1] == pytest.approx(0.015)


def test_a_parent_commits_ring_reads_what_it_has_and_nothing_else():
    """No ``cpu_s``, no ``rounds``: the counter returns nothing (and does
    not raise), the span metrics read as on the change."""
    ring, t = Ring(), 7.0
    t = ring.round(t, PREFILL, prefilled=1, trunks=(128,))
    ring.request(0, 7.0001, 7.0002, 7.046, counted=False)
    for _ in range(5):
        t = ring.round(t, DECODE)
    record = ring.record([(7.0, 7.0571)])
    assert read("queued_share", record) is None
    for metric in ("queue_wait_mean_ms", "prefill_wait_mean_ms",
                   "token_held_mean_ms", "prefill_host_ms",
                   "round_overrun_share"):
        assert read(metric, record) is not None, metric


@pytest.mark.parametrize("metric", NEW)
def test_a_ring_that_lost_the_window_reads_nothing(metric):
    spans.clear(capacity=16)
    ring, t = Ring(), 3.0
    t = ring.round(t, PREFILL, prefilled=1, trunks=(128,))
    ring.request(0, 3.0001, 3.0002, 3.046, rounds=0)
    for _ in range(6):
        t = ring.round(t, DECODE, cpu_s=0.001)
    assert spans.dropped() > 0
    assert read(metric, ring.record([(3.0, 3.06)])) is None


@pytest.mark.parametrize("metric", NEW)
def test_a_record_without_rounds_reads_nothing(metric):
    assert read(metric, {"rounds": [], "requests": []}) is None


def test_the_three_parts_add_up_to_the_harness_mean_on_a_rehearsed_run():
    """``serve-large-batch`` rehearsed on the CPU through the account's
    own command: queue + prefill + held is the harness's ``ttft_mean_ms``
    to within 1% or 0.3 ms, and the traced line carries every new metric
    of the cell."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    done = subprocess.run(
        [sys.executable, "perf/span_account.py", "--workload",
         "serve-large-batch", "--seed", "2", "--seconds", "3", "--trace",
         "1", "--rehearse"], cwd=REPO, env=env, capture_output=True,
        text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-1500:]
    line, account = [json.loads(text) for text in
                     done.stdout.strip().splitlines()[-2:]]
    account = account["account"]
    assert line["correct"] is True and account["requests"] > 10
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    parts = ("queue_wait_mean_ms", "prefill_wait_mean_ms",
             "token_held_mean_ms")
    for name in parts + ("queued_share", "prefill_host_ms",
                         "round_overrun_share"):
        assert name in metrics, sorted(metrics)
    assert [metrics[p] for p in parts] == [account[p] for p in parts]
    ttft = account["ttft_mean_ms"]
    assert abs(account["unaccounted_ms"]) <= max(0.01 * ttft, 0.3), account
    assert 0.0 <= account["unaccounted_ms"]   # the client's stretch
    assert metrics["prefill_host_ms"] < metrics["prefill_round_ms"]
