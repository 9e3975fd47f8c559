"""Checks of what PR 27 added to the benchmark (by hand, on the CPU, as
``test_perf.py``): both new cells walked with ``--rehearse``, the scope
account on the recorded trace, the byte counts, and the two readings
that each limit of ``serve-closed-64-decode``'s judge lies between.

    JAX_PLATFORMS=cpu python3 -m pytest perf/tests/test_new_cells.py -q
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import check_manifest, mimo_costs, scope_account  # noqa: E402


def _run(cell, trace, devices):
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=(
        f"--xla_force_host_platform_device_count={devices}"))
    done = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", cell, "--seed",
         str(2 ** 31 + 11), "--seconds", "2", "--trace", str(trace),
         "--rehearse"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell, devices, trace", [
    ("train-large-4chip", 4, 0), ("train-large-4chip", 4, 1),
    ("serve-mimo-decode", 1, 0), ("serve-mimo-decode", 1, 1)])
def test_rehearsal_walks_the_new_cells(cell, devices, trace):
    line = _run(cell, trace, devices)
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearse"] is True and line["device"]["count"] == devices
    manifest = check_manifest.load()
    device_only = ("idle_share", "train_mfu", "moe_device_ms",
                   "moe_experts_roofline", "decode_attend_roofline")
    want = {m["name"] for m in manifest["per_layer" if trace else "end_to_end"]
            if cell in check_manifest.cells_of(m, manifest)
            and not m["name"].startswith(device_only)}
    assert set(line["metrics"]) == want   # no device, no peak on the CPU


def test_the_new_configuration_keeps_every_published_number():
    with open(os.path.join(ROOT, "perf/configs/mimo-v2.5-ep16.json")) as fh:
        config = json.load(fh)
    published = {
        "hidden_size": 4096, "num_attention_heads": 64,
        "num_key_value_heads": 4, "swa_num_key_value_heads": 8,
        "head_dim": 192, "v_head_dim": 128, "swa_head_dim": 192,
        "swa_v_head_dim": 128, "moe_intermediate_size": 2048,
        "intermediate_size": 16384, "num_experts_per_tok": 8,
        "sliding_window": 128, "partial_rotary_factor": 0.334,
        "attention_value_scale": 0.707, "rope_theta": 10000000,
        "swa_rope_theta": 10000, "max_position_embeddings": 1048576,
        "published_n_routed_experts": 256, "layernorm_epsilon": 1e-05}
    assert {k: config[k] for k in published} == published
    assert config["hybrid_layer_pattern"] == [0, 1, 1, 1, 1, 1, 0]
    assert config["moe_layer_freq"] == [0, 1, 1, 1, 1, 1, 1]
    assert config["held_experts"] == [0, config["n_routed_experts"]] == [0, 16]
    assert config["vocab_size"] * 8 == config["published_vocab_size"]
    assert sorted(config["reduced"]) == sorted(
        ["num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
         "n_routed_experts", "vocab_size"])


def test_scope_account_on_the_recorded_trace():
    # four runs of jit_f (three fusions each); the window holds three
    path = os.path.join(ROOT, "perf", "fixtures", "probe_tpu_v5e.xplane.pb")
    tables = {"jit_f": {"fusion": "jit(f)/layer/moe/experts/gmm/dot",
                        "fusion.1": "jit(f)/layer/moe/route/top_k",
                        "fusion.2": "jit(f)/layer/attn_window/attend/x"}}
    got = scope_account.by_scope(
        path, tables, ("layer/moe/experts/gmm", "layer/moe/experts",
                       "layer/moe/route", "layer/attn_window/attend"))
    f = got["jit_f"]
    assert f["runs"] == 3
    assert set(f["seconds"]) == {
        "layer/moe/experts/gmm", "layer/moe/route",
        "layer/attn_window/attend", "other"}
    assert f["seconds"]["layer/moe/route"] == pytest.approx(
        3 * 11.5775e-6, rel=1e-3)
    assert sum(f["seconds"].values()) == pytest.approx(f["total_s"])
    assert scope_account.by_scope(path, {}, ()) is None
    assert scope_account.by_scope(path, {"jit_g": {}}, ()) is None


def test_byte_counts_of_a_decode_round():
    with open(os.path.join(ROOT, "perf/configs/mimo-v2.5-ep16.json")) as fh:
        config = json.load(fh)
    shapes = mimo_costs.model_shapes(config)
    assert (shapes["global_row_bytes"], shapes["window_row_bytes"]) == (
        2560, 5120)
    # every held expert of the six expert layers, no token: 4.83 GB
    assert mimo_costs.experts_bytes(shapes, 96, 0) == 96 * 3 * 4096 * 2048 * 2
    assert mimo_costs.experts_bytes(shapes, 0, 1) == 2 * (
        2 * 4096 + 3 * 2048 + 4096)
    # one slot of 700 tokens: 6 pages in each of the two global layers,
    # 2 ring pages in each of the five window layers
    assert mimo_costs.attend_bytes(shapes, 128, 6, 2) == 128 * (
        6 * 2560 * 2 + 2 * 5120 * 5)


def _twin(path):
    with open(os.path.join(ROOT, path)) as fh:
        data = json.load(fh)
    data.update(data.pop("rehearse"))
    return data


def test_both_limits_lie_between_bfloat16_and_fp8_weights():
    """The two readings of each limit of ``judge`` on the CPU twin. The
    engine as configured (bfloat16) is judged correct with room on both
    numbers. With every matrix rounded to fp8 (e4m3, the nearest
    precision below) it fails both. With the HELD EXPERTS alone at fp8
    the tie judge still passes (they give a token half an expert's
    output on average, a 16th of the routed sum), and the expert layer's
    own number, ``expert_rel_err_median``, fails it (PERF.md, PR 27)."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes
    import numpy as np

    from apex_tpu.serving import ServingEngine
    from apex_tpu.serving.scheduler import Request
    from perf.runners import serve_closed_family as scf
    from perf.traffic_gen import RequestStream

    config = _twin("perf/configs/mimo-v2.5-ep16.json")
    config["held_experts"] = tuple(config["held_experts"])
    mix = dict(_twin("perf/traffic/serve-closed-64-decode.json"),
               judge_requests=8)
    reference, cfg = scf._reference(config), scf._engine_config(config)
    params = scf._params(cfg, 11)

    def fp8(a):
        if a.dtype != jnp.bfloat16:
            return a
        return jnp.asarray(np.asarray(a, np.float32).astype(
            ml_dtypes.float8_e4m3fn).astype(np.float32), a.dtype)

    def experts_fp8(tree):
        return dict(tree, layers=[
            dict(lp, **{k: fp8(lp[k]) for k in ("w_gate", "w_up", "w_down")})
            if "router" in lp else lp for lp in tree["layers"]])

    read = {}
    for name, served in (("bfloat16", params),
                         ("fp8_experts", experts_fp8(params)),
                         ("fp8", jax.tree_util.tree_map(fp8, params))):
        engine = ServingEngine(cfg, params=served, **mix["engine"])
        stream = RequestStream(mix, config["vocab_size"], 11)
        requests = [Request(rid=i, prompt=p, max_new_tokens=a)
                    for i, (p, a) in enumerate(
                        stream.next() for _ in range(8))]
        engine.step(arrivals=list(requests))
        while not all(r.done() for r in requests):
            engine.step()
        ok, note = scf.judge(reference, config, mix, params,
                             [{"req": r} for r in requests], 11, engine)
        read[name] = (ok, note["worst_gap_bf16_steps"],
                      note["expert_rel_err_median"])
    tie, err = mix["judge_tie_steps"], mix["judge_expert_rel_err"]
    ok, gap, rel = read["bfloat16"]
    assert ok and gap < 2.0 and rel < err / 3, read
    ok, gap, rel = read["fp8_experts"]
    assert not ok and gap < tie and rel > 3 * err, read
    ok, gap, rel = read["fp8"]
    assert not ok and gap > tie and rel > 3 * err, read
