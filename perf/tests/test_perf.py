"""The benchmark's own checks. Run by hand, on the CPU, from the repo's
root (about a minute; not part of tier 1, which this directory is outside):

    JAX_PLATFORMS=cpu python3 -m pytest perf/tests -q
"""

import copy
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import check_manifest, stats, traffic_gen  # noqa: E402


def _manifest():
    with open(check_manifest.MANIFEST) as fh:
        return json.load(fh)


def test_manifest_is_sound():
    assert check_manifest.check(_manifest()) == []


@pytest.mark.parametrize("edit, said", [
    (lambda m: m["per_layer"][0].update(layer="train model"), "layer"),
    (lambda m: m["per_layer"][0].update(moves="serve_tok_s"), "do not report"),
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["workloads"][0].update(traffic="no-such-mix"), "data file"),
    (lambda m: [w.update(chips=4) for w in m["workloads"]], "four-chip"),
    (lambda m: m["per_layer"][0].update(name="no_reader"), "no reader"),
    (lambda m: m["end_to_end"][0].update(why="because"), "metric keys"),
    (lambda m: m.update(run_seconds=52), "run_seconds"),
])
def test_manifest_faults_are_found(edit, said):
    manifest = copy.deepcopy(_manifest())
    edit(manifest)
    faults = check_manifest.check(manifest)
    assert any(said in fault for fault in faults), faults


def test_trace_reduction_on_the_recorded_trace():
    # four runs of a three-matmul program on one v5e chip, 20 ms of host
    # sleep ("client.refill") between them; the device's clock runs a
    # millisecond ahead, so the first run lies before the window
    from perf import reduce_trace

    got = reduce_trace.reduce(os.path.join(
        ROOT, "perf", "fixtures", "probe_tpu_v5e.xplane.pb"))
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(0.08699881, rel=1e-6)
    assert got["busy_s"] == pytest.approx(107.387e-6, rel=1e-4)
    assert got["idle_share"] == pytest.approx(1 - 107.387e-6 / 0.08699881)
    assert [op for op, _ in got["device_ops"][:3]] == [
        "fusion", "fusion.1", "fusion.2"]
    assert got["idle_gaps"][0][0] == "client.refill"
    assert sum(s for _, s in got["idle_gaps"]) == pytest.approx(
        got["window_s"] - got["busy_s"])


def test_percentile_and_spread():
    xs = list(range(1, 101))
    assert stats.percentile(xs, 95) == pytest.approx(95.05)
    assert stats.percentile([], 95) is None
    assert stats.iqr_share([98, 99, 100, 100, 101, 102]) == pytest.approx(
        (101.25 - 98.75) / 100)


def test_parameter_count_and_mfu():
    with open(os.path.join(ROOT, "perf/configs/gpt2-medium.json")) as fh:
        medium = json.load(fh)
    assert stats.gpt2_params(medium, 50304) == 354_871_296
    # 4096 tokens in 0.1 s on one 197 TFLOP/s chip
    assert stats.mfu_percent(354_871_296, 4096, 0.1, 1, 197e12) == \
        pytest.approx(100 * 6 * 354_871_296 * 4096 / 0.1 / 197e12)


def _mix():
    with open(os.path.join(ROOT, "perf/traffic/serve-closed-16.json")) as fh:
        return json.load(fh)


def _take(stream, n):
    return [stream.next() for _ in range(n)]


def test_same_seed_same_requests_and_every_seed_the_same_sizes():
    mix = _mix()
    a = _take(traffic_gen.RequestStream(mix, 50257, 2 ** 31 + 7), 300)
    b = _take(traffic_gen.RequestStream(mix, 50257, 2 ** 31 + 7), 300)
    assert json.dumps(a) == json.dumps(b)
    c = _take(traffic_gen.RequestStream(mix, 50257, 8), mix["pool"])
    sizes = lambda reqs: sorted((len(p), n) for p, n in reqs)  # noqa: E731
    assert sizes(a[:mix["pool"]]) == sizes(c) == sorted(
        traffic_gen.size_pool(mix))
    assert [p for p, _ in a[:mix["pool"]]] != [p for p, _ in c]
    for prompt, answer in a:
        assert mix["prompt"]["min"] <= len(prompt) <= mix["prompt"]["max"]
        assert 1 <= answer <= mix["answer"]["max"]
        assert len(prompt) + answer <= mix["max_total"]
        assert all(0 <= t < 50257 for t in prompt)


def _run(cell, *extra, devices=1, cwd=ROOT):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))
    return subprocess.run(
        [sys.executable, "perf/run.py", "--workload", cell, "--seed",
         str(2 ** 31 + 11), "--seconds", "2", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=600)


@pytest.mark.parametrize("cell, devices, trace", [
    ("train-medium", 1, 0), ("train-medium", 1, 1),
    ("serve-large-batch", 1, 0), ("serve-large-batch", 1, 1)])
def test_rehearsal_walks_the_whole_command(cell, devices, trace):
    done = _run(cell, "--trace", str(trace), "--rehearse", devices=devices)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearse"] is True and line["device"]["platform"] == "cpu"
    manifest = _manifest()
    want = {m["name"] for m in manifest["per_layer" if trace else "end_to_end"]
            if cell in check_manifest.cells_of(m, manifest)
            and not m["name"].startswith(("idle_share", "train_mfu"))}
    assert set(line["metrics"]) == want   # no device, no peak on the CPU


@pytest.mark.parametrize("trace", [0, 1])
def test_the_four_chip_mix_rehearses_on_four_virtual_devices(tmp_path, trace):
    # the cell PR 24 had to leave out (PERF.md section 7) is one entry of
    # `workloads` and its name in the train metrics' lists: walked here in
    # a tree of links whose manifest has them, so that the mix file and
    # the runner's path for several chips stay alive until it lands
    for name in ("perf", "apex_tpu", "examples"):
        os.symlink(os.path.join(ROOT, name), tmp_path / name)
    manifest = _manifest()
    manifest["workloads"].append({
        "name": "train-large-4chip", "config": "gpt2-large",
        "traffic": "train-mb4-tp2", "chips": 4, "why": "dp=2 x tp=2"})
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if "train-medium" in metric.get("workloads", []):
            metric["workloads"].append("train-large-4chip")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    done = _run("train-large-4chip", "--trace", str(trace), "--rehearse",
                devices=4, cwd=tmp_path)
    assert done.returncode == 0, done.stderr[-2000:]
    line = json.loads(done.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["count"] == 4
    assert ("train_step_ms" if trace else "train_tok_s") in line["metrics"]


def test_without_a_tpu_there_is_no_result():
    done = _run("train-medium", "--trace", "0")
    assert done.returncode != 0
    assert "{" not in done.stdout
    done = _run("train-medium", "--trace", "0", "--rehearse", devices=4)
    assert done.returncode != 0 and "{" not in done.stdout
