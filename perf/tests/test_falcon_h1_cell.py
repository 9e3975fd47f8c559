"""Checks of what the ``serve-falconh1-decode`` cell added to the
benchmark (by hand, on the CPU, as ``test_dots3_cell.py``): the cell
walked with ``--rehearse``, plain and traced; its names resolving to
files; the configuration's numbers against the catalog's; the cost
functions against hand counts at the published widths; the new readers
on synthetic records and on records without their scopes.

    JAX_PLATFORMS=cpu python3 -m pytest perf/tests/test_falcon_h1_cell.py -q
"""

import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import check_manifest, falcon_h1_costs  # noqa: E402

CELL = "serve-falconh1-decode"
CONFIG = "perf/configs/falcon-h1-34b-pp12.json"
MIX = "perf/traffic/serve-closed-192-decode.json"
# The published model catalog, one JSON object a line; the check of the
# configuration against it runs where MODEL_CATALOG names that file.
CATALOG = os.environ.get("MODEL_CATALOG", "")
NEW = ("ssm_decode_roofline", "ssm_prefill_roofline", "ssm_decode_share")


def _json(path):
    with open(os.path.join(ROOT, path)) as fh:
        return json.load(fh)


def _rehearse(trace, seed=2 ** 31 + 17):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", "2", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1]), done.stdout


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_walks_the_new_cell(trace):
    line, said = _rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["rehearse"] is True
    wanted = ({"serve_tok_s", "tpot_p95_ms", "setup_s"} if not trace else
              {"decode_round_ms", "decode_fetch_ms", "round_host_ms",
               "token_gap_p99_ms"})
    assert wanted <= set(line["metrics"])
    checks = next(text for text in said.splitlines()
                  if text.startswith("serve checks"))
    for key in ("state_rel_err", "decoded_state_rel_err", "mixer_rel_err"):
        assert f'"{key}": ' in checks


def test_the_new_names_resolve():
    manifest = check_manifest.load()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "serve-closed-192-decode"
    spec = next(c for c in manifest["configs"]
                if c["name"] == cell["config"])
    assert spec["file"] == CONFIG
    assert spec["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert check_manifest.traffic_file(cell["traffic"]).endswith(MIX)
    assert os.path.exists(os.path.join(
        ROOT, "perf", "runners", _json(MIX)["runner"] + ".py"))
    for name in NEW:
        metric = next(m for m in manifest["per_layer"] if m["name"] == name)
        assert metric["workloads"] == [CELL]
        assert check_manifest.reader_file("layer_metrics", name)
    for name in ("serve_tok_s", "tpot_p95_ms"):
        metric = next(m for m in manifest["end_to_end"] if m["name"] == name)
        assert CELL in metric["workloads"]
    ttft = next(m for m in manifest["end_to_end"]
                if m["name"] == "ttft_mean_ms")
    assert CELL not in ttft["workloads"]


def test_the_cell_and_its_mix_keep_their_traffic():
    mix = _json(MIX)
    assert mix["engine"] == {"num_slots": 192, "page_size": 128,
                             "num_pages": 2048, "max_seq": 3072,
                             "prefill_len": 2048}
    assert mix["clients"] == 192 and mix["pool"] == 192
    assert mix["prompt"] == {"dist": "lognormal", "median": 256,
                             "sigma": 0.8, "min": 32, "max": 1536}
    assert mix["answer"] == {"dist": "lognormal", "median": 640,
                             "sigma": 0.6, "min": 128, "max": 1536}
    assert mix["max_total"] == 3072 and mix["drain_cap_s"] == 60
    assert mix["judge_requests"] == 4


def test_the_configuration_keeps_every_number_of_the_catalogs():
    if not CATALOG or not os.path.exists(CATALOG):
        pytest.skip("no catalog here")
    with open(CATALOG) as fh:
        row = next(json.loads(line) for line in fh
                   if '"Falcon-H1-34B-Instruct"' in line)
    config = _json(CONFIG)
    changed = {k for k, v in row["config"].items() if config.get(k) != v}
    assert changed == set(config["reduced"]) == {"num_hidden_layers",
                                                 "vocab_size"}
    assert config["published_num_hidden_layers"] == 72
    assert config["published_vocab_size"] == 261120
    assert config["vocab_size"] * 8 == 261120
    assert config["kept_published_layers"] == list(range(6))


def test_the_stage_holds_2_915_billion_parameters():
    """The configuration's weights, counted from their shapes: 430.1M a
    layer, 2.915B with the slices of the embedding and the head."""
    import jax

    from apex_tpu.serving import falcon_h1, family

    config = _json(CONFIG)
    config.pop("rehearse")
    cfg = family.config_from_dict(config)
    shapes = jax.eval_shape(lambda: falcon_h1.init_params(
        cfg, jax.random.PRNGKey(0)))
    layer = sum(a.size for a in jax.tree_util.tree_leaves(
        shapes["layers"][0]))
    total = sum(a.size for a in jax.tree_util.tree_leaves(shapes))
    assert layer == 430_120_032
    assert round(total / 1e9, 3) == 2.915


def test_costs_at_the_published_widths():
    """A decode round at 192 live slots reads and writes 4.83 GB of
    state (32 x 128 x 256 bfloat16 a slot a layer, twice, six layers),
    and the recurrence is 4.19 MFLOP a token a layer."""
    shapes = falcon_h1_costs.model_shapes(_json(CONFIG))
    assert falcon_h1_costs.recurrence_flops(shapes, 1) \
        == 6 * 4 * 32 * 128 * 256 == 6 * 4_194_304
    state = 192 * 6 * 2 * 32 * 128 * 256 * 2
    assert round(state / 1e9, 2) == 4.83
    vectors = 192 * 6 * (2 * 4096 + 2 * 2 * 256 + 32) * 2
    assert falcon_h1_costs.decode_bytes(shapes, 192) == state + vectors
    assert falcon_h1_costs.prefill_bytes(shapes, 2048, 8) == 6 * (
        2048 * (2 * 4096 + 1024 + 32) * 2 + 8 * 32 * 128 * 256 * 2)


def _record(scopes, spans, peak=True):
    return {"scopes": scopes, "spans": spans,
            "model": falcon_h1_costs.model_shapes(_json(CONFIG)),
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
            if peak else None}


def test_the_new_readers_on_synthetic_records(monkeypatch):
    from perf.layer_metrics import (ssm_decode_roofline, ssm_decode_share,
                                    ssm_prefill_roofline,
                                    sparse_prefill_roofline)

    def span(name, t0, **attrs):
        return types.SimpleNamespace(name=name, t0=t0, t1=t0 + 0.01,
                                     attrs=attrs)

    records = [span("engine.round", 1.0, decoded=0, ssm_slots_live=192),
               span("engine.round", 1.1, decoded=192, ssm_slots_live=192),
               span("engine.round", 1.2, decoded=192, ssm_slots_live=190),
               span("prefill.fetch", 1.3, ssm_tokens=2048, ssm_segments=8)]
    cut = lambda record: (0.0, 10.0, records)   # noqa: E731
    monkeypatch.setattr(ssm_decode_roofline, "serve_window", cut)
    monkeypatch.setattr(sparse_prefill_roofline, "serve_window", cut)
    scopes = {"jit__decode": {"runs": 2, "total_s": 0.04, "seconds": {
        "layer/ssm/scan": 0.012, "layer/ssm/in_proj": 0.004,
        "layer/mlp": 0.02, "other": 0.004}},
        "jit__prefill": {"runs": 1, "total_s": 0.08, "seconds": {
            "layer/ssm/scan": 0.004, "layer/mlp": 0.05}}}
    record = _record(scopes, None)
    shapes = record["model"]
    slots = (192 + 190) / 2
    want = 100 * falcon_h1_costs.decode_bytes(shapes, slots) / 819e9 / 0.006
    assert ssm_decode_roofline.read(record) == pytest.approx(want)
    want = 100 * max(falcon_h1_costs.prefill_bytes(shapes, 2048, 8) / 819e9,
                     falcon_h1_costs.recurrence_flops(shapes, 2048)
                     / 197e12) / 0.004
    assert ssm_prefill_roofline.read(record) == pytest.approx(want)
    assert ssm_decode_share.read(record) == pytest.approx(40.0)


def test_the_new_readers_return_nothing_without_their_scopes():
    from perf.layer_metrics import (ssm_decode_roofline, ssm_decode_share,
                                    ssm_prefill_roofline)

    for record in ({}, {"scopes": None, "model": {}, "peak": None},
                   _record({"jit__decode": {"runs": 1, "total_s": 0.01,
                                            "seconds": {"other": 0.01}}},
                           None)):
        for reader in (ssm_decode_roofline, ssm_prefill_roofline,
                       ssm_decode_share):
            assert reader.read(record) is None
