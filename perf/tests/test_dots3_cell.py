"""Checks of what PR 33 added to the benchmark (by hand, on the CPU, as
``test_axk1_cell.py``): the new cell walked with ``--rehearse``, the
configuration's numbers against the catalog's, the cost functions, the
new readers on a recorded toy run and on records without their scopes,
and the controls of the judge's new checks on the CPU twin.

    JAX_PLATFORMS=cpu python3 -m pytest perf/tests/test_dots3_cell.py -q
"""

import importlib
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from perf import check_manifest, dots3_costs, mimo_costs  # noqa: E402

CELL = "serve-dots3-longcontext"
CONFIG = "perf/configs/dots3-note-ep16.json"
MIX = "perf/traffic/serve-closed-16-longcontext.json"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
NEW = ("sparse_prefill_roofline", "index_select_device_ms",
       "prefill_sparse_share", "sparse_decode_roofline",
       "window_latent_roofline", "selected_share_mean")


def _json(path):
    with open(os.path.join(ROOT, path)) as fh:
        return json.load(fh)


def _twin(path):
    data = _json(path)
    data.update(data.pop("rehearse"))
    return data


def _rehearse(trace, record=None, seed=2 ** 31 + 11):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1")
    done = subprocess.run(
        [sys.executable, "perf/run.py", "--workload", CELL, "--seed",
         str(seed), "--seconds", "2", "--trace", str(trace), "--rehearse"]
        + (["--record", record] if record else []),
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_walks_the_new_cell(trace):
    line = _rehearse(trace)
    assert line["correct"] is True and line["failed"] == 0
    assert line["rehearse"] is True and line["device"]["count"] == 1
    manifest = check_manifest.load()
    needs_a_device = {m["name"] for m in manifest["per_layer"]
                      if m["source"] == "device_trace"}
    want = {m["name"] for m in manifest["per_layer" if trace else "end_to_end"]
            if CELL in check_manifest.cells_of(m, manifest)
            and m["name"] not in needs_a_device}
    assert set(line["metrics"]) == want   # no device, no peak on the CPU
    if trace:
        # every context of the twin's traffic is past its index_topk
        assert 10 < line["metrics"]["selected_share_mean"]["value"] < 60


def test_the_cell_and_its_mix_are_the_issues():
    manifest = check_manifest.load()
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "dots3-note-ep16", "serve-closed-16-longcontext", 1)
    spec = next(c for c in manifest["configs"]
                if c["name"] == "dots3-note-ep16")
    assert spec["source"] == "https://huggingface.co/dots-studio/" \
        "dots3-note-prev/blob/main/config.json"
    assert sorted(spec["reduced"]) == sorted(
        ["num_hidden_layers", "layer_types", "n_routed_experts",
         "vocab_size"])
    mix = _json(MIX)
    assert mix["runner"] == "serve_closed_sparse" and mix["clients"] == 16
    assert mix["engine"] == {"num_slots": 16, "page_size": 128,
                             "num_pages": 1280, "max_seq": 8704,
                             "prefill_len": 8192}
    assert mix["prompt"] == {"dist": "lognormal", "median": 5120,
                             "sigma": 0.5, "min": 2048, "max": 8192}
    assert mix["answer"] == {"dist": "lognormal", "median": 128,
                             "sigma": 0.6, "min": 32, "max": 512}
    assert (mix["max_total"], mix["pool"], mix["ramp_completions"],
            mix["drain_cap_s"], mix["trace_seconds"]) == (8704, 48, 16, 40, 3)
    reports = {m["name"] for group in ("end_to_end", "per_layer")
               for m in manifest[group]
               if CELL in check_manifest.cells_of(m, manifest)}
    assert set(NEW) | {
        "prefill_round_ms", "ttft_p95_ms", "queue_wait_p95_ms",
        "decode_round_ms.ttft", "decode_fetch_ms.ttft", "round_host_ms.ttft",
        "idle_share.ttft", "moe_device_ms.ttft", "held_experts_roofline",
        "held_experts_touched", "ttft_mean_ms", "setup_s"} == reports
    assert {m["moves"] for m in manifest["per_layer"]
            if CELL in check_manifest.cells_of(m, manifest)} \
        == {"ttft_mean_ms"}
    # the twin's selection and window are SHORTER than its contexts
    twin, config = _twin(MIX), _twin(CONFIG)
    assert config["index_topk"] < twin["prompt"]["min"]
    assert config["sliding_window_size"] < twin["prompt"]["min"]


def test_the_new_configuration_keeps_every_number_of_the_catalogs():
    config = _json(CONFIG)
    with open(CATALOG) as fh:
        row = next(r for r in map(json.loads, fh)
                   if r["name"] == "dots3-note-prev")
    assert config["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if config.get(k) != v}
    assert differs == set(config["reduced"]) == {
        "num_hidden_layers", "layer_types", "n_routed_experts", "vocab_size"}
    assert config["layer_types"] == row["config"]["layer_types"][:9]
    assert config["layer_types"].count("sliding_attention") == 6
    assert config["held_experts"] == [0, config["n_routed_experts"]] == [0, 16]
    assert config["n_routed_experts"] * 16 == 256 \
        == config["published_n_routed_experts"]
    assert config["vocab_size"] * 8 == config["published_vocab_size"] \
        == row["config"]["vocab_size"]
    assert config["kept_published_layers"] == list(range(9))
    assert config["published_num_hidden_layers"] == 46
    assert set(config["assumed"]) >= {"rescale", "gate", "indexer", "window",
                                      "rotary", "weights"}
    assert len(config["not_built"]) >= 2


def test_the_share_holds_4_603_billion_parameters():
    import jax

    from apex_tpu.serving import dots3, family

    config = _json(CONFIG)
    config.pop("rehearse")
    cfg = family.config_from_dict(config)
    assert isinstance(cfg, dots3.Dots3Config) and cfg.held_experts == (0, 16)
    assert cfg.num_layers == 9
    shapes = jax.eval_shape(
        lambda: dots3.init_params(cfg, jax.random.PRNGKey(0)))
    matrices = sum(a.size for a in jax.tree_util.tree_leaves(shapes)
                   if a.ndim > 1)
    # full attention 144.05M, sliding 90.83M, an expert 23.59M, the
    # router 1.31M, the dense MLP 212.34M, embedding + head 194.64M:
    # 356.38 + 2 x 546.44 + 6 x 493.22 + 194.64
    assert matrices == 4_603_248_640
    cache = jax.eval_shape(lambda: dots3.init_cache(cfg, 16, 1280, 128))
    assert [a.shape for a in cache["latent"]] == [(1280, 128, 640)] * 3
    assert [a.shape for a in cache["index"]] == [(1280, 128, 128)] * 3
    assert [a.shape for a in cache["ring"]] == [(97, 128, 1152)] * 6
    held = sum(a.size * a.dtype.itemsize
               for a in jax.tree_util.tree_leaves(cache))
    assert round(held / 1e9, 2) == 0.93


def test_counts_of_a_round():
    config = _json(CONFIG)
    shapes = dots3_costs.model_shapes(config)
    assert shapes == {
        "hidden": 5120, "expert_width": 1536, "expert_layers": 8, "held": 16,
        "layers": 9, "full_layers": 3, "sliding_layers": 6, "heads": 128,
        "qk_width": 192, "v_width": 128, "latent_rank": 512,
        "latent_width": 576, "index_heads": 64, "index_width": 128,
        "index_topk": 2048, "window": 513, "window_heads": 64,
        "window_rank": 1024, "window_width": 1088}
    # one query against one key: 64 index heads x 128 x 2, three layers
    assert dots3_costs.index_flops(shapes, 1) == 64 * 128 * 2 * 3
    assert dots3_costs.sparse_prefill_flops(shapes, 0, 1) \
        == 128 * (192 + 128) * 2 * 3
    # a prompt of 8,192 tokens: every causal pair indexed, 2,048 a query
    # attended past the 2,048th
    pairs = 8192 * 8193 // 2
    chosen = 2048 * 2049 // 2 + (8192 - 2048) * 2048
    assert round(dots3_costs.sparse_prefill_flops(shapes, pairs, chosen)
                 / 1e12, 2) == 5.26
    # a masked dense form computes every causal pair at the attention's
    # price: the selected share is what it can read at most
    assert round(chosen / pairs, 3) == 0.437
    # a slot of 6,000 tokens: every index key, 2,048 latent rows
    assert dots3_costs.sparse_decode_bytes(shapes, 6000, 2048) \
        == (6000 * 128 + 2048 * 576) * 2 * 3
    assert dots3_costs.sparse_decode_flops(shapes, 0, 1) \
        == 128 * 2 * (576 + 512) * 3
    assert dots3_costs.window_bytes(shapes, 513) == 513 * 1088 * 2 * 6
    # the expert layer through the accepted cells' count
    assert mimo_costs.experts_bytes(shapes, 10, 0) == 10 * 3 * 5120 * 1536 * 2


def _record(spans):
    """A record of two traced prefill runs and three decode runs, with
    the spans the readers cut by."""
    return {
        "scopes": {
            "jit__prefill": {"runs": 2, "total_s": 1.0, "seconds": {
                "layer/attn_sparse/index": 0.04,
                "layer/attn_sparse/select": 0.06,
                "layer/attn_sparse/attend": 0.3, "layer/attn_sparse": 0.1,
                "layer/attn_window_latent/attend": 0.05,
                "layer/attn_window_latent": 0.05, "layer/moe/experts": 0.2}},
            "jit__decode": {"runs": 3, "total_s": 0.045, "seconds": {
                "layer/attn_sparse/index": 0.0015,
                "layer/attn_sparse/select": 0.0009,
                "layer/attn_sparse/attend": 0.0006,
                "layer/attn_window_latent/attend": 0.0012}}},
        "model": dict(dots3_costs.model_shapes(_json(CONFIG)), page_size=128),
        "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "rounds": [{"t0": 10.0, "t1": 10.5}, {"t0": 10.5, "t1": 20.0}],
        "_spans": spans}


def test_the_new_readers_on_a_recorded_run(monkeypatch):
    """The six readers on a record whose spans are written here: the
    traced stretch opens the window, so the first ``runs`` spans are the
    trace's; the later ones (another mix of work) are left out."""
    from perf import span_ring

    R = types.SimpleNamespace
    fetch = [R(name="prefill.fetch", t0=10.1 + i, t1=10.2 + i, parent=None,
               attrs={"index_pairs": p, "sparse_pairs": s})
             for i, (p, s) in enumerate([(8192 * 8193 // 2, 14680064),
                                         (4096 * 4097 // 2, 6291456),
                                         (10 ** 9, 10 ** 9)])]
    rounds = [R(name="engine.round", t0=10.0 + i, t1=10.9 + i, parent=None,
                attrs={"index_rows_scored": 16 * 6000,
                       "sparse_rows_selected": 16 * 2048,
                       "window_rows": 16 * 513, "decoded": 16,
                       "prefilled": 0})
              for i in range(5)]
    record = _record(fetch + rounds)
    fake = types.SimpleNamespace(covers=lambda t: True,
                                 snapshot=lambda t: record["_spans"])
    monkeypatch.setattr(span_ring, "_spans", lambda: fake)
    read = {name: importlib.import_module(
        f"perf.layer_metrics.{name}").read(record) for name in NEW}
    model = record["model"]
    flops = (dots3_costs.sparse_prefill_flops(
        model, 8192 * 8193 // 2, 14680064) + dots3_costs.sparse_prefill_flops(
            model, 4096 * 4097 // 2, 6291456)) / 2
    assert read["sparse_prefill_roofline"] == pytest.approx(
        100 * flops / 197e12 / 0.2)
    assert 0 < read["sparse_prefill_roofline"] < 100
    assert read["index_select_device_ms"] == pytest.approx(50.0)
    assert read["prefill_sparse_share"] == pytest.approx(60.0)
    floor = dots3_costs.sparse_decode_bytes(model, 96000, 32768) / 819e9
    assert read["sparse_decode_roofline"] == pytest.approx(
        100 * floor / 0.001)
    assert read["window_latent_roofline"] == pytest.approx(
        100 * dots3_costs.window_bytes(model, 16 * 513) / 819e9 / 0.0004)
    assert read["selected_share_mean"] == pytest.approx(100 * 2048 / 6000)


def test_the_new_readers_return_nothing_without_their_scopes():
    """On a parent commit (no ``layer/attn_sparse`` scope, no
    ``index_rows_scored`` counter, no run of this cell at all) the six
    readers this PR brings leave their metric out and do not raise."""
    record = {"scopes": {"jit__decode": {"runs": 3, "total_s": 0.03,
                                         "seconds": {"layer/mlp": 0.01}},
                         "jit__prefill": {"runs": 1, "total_s": 0.05,
                                          "seconds": {"layer/mlp": 0.02}}},
              "rounds": [], "model": {}, "peak": None}
    for name in NEW:
        reader = importlib.import_module(f"perf.layer_metrics.{name}")
        assert reader.read(record) is None
        assert reader.read({}) is None
        assert reader.read(dict(record, model={"index_topk": 8,
                                               "window_width": 8},
                                peak={"bf16_flops_per_s": 1.0,
                                      "hbm_bytes_per_s": 1.0})) is None


def test_a_conds_branch_names_are_cut_out_of_the_op_names():
    from perf.runners import serve_closed_sparse as scs
    from perf.scope_account import scope_of

    name = ("jit(_decode)/layer/attn_sparse/cond/branch_1_fun/index/"
            "dot_general")
    assert scope_of(name, scs.SCOPES) == "layer/attn_sparse"
    assert scope_of(scs._BRANCH.sub("", name), scs.SCOPES) \
        == "layer/attn_sparse/index"
    prefill = ("jit(_prefill)/cond/branch_3_fun/layer/moe/experts/"
               "jit(_held_rows_or_every_row)/cond/branch_1_fun/gmm/x")
    assert scs._BRANCH.sub("", prefill) == "layer/moe/experts/gmm/x"


@pytest.mark.parametrize("control,fails", [
    (None, None),
    ("selection_is_the_last_rows", "selection_share"),
    ("gate_left_out", "latent_rel_err_median"),
    ("rescale_left_out", "latent_rel_err_median"),
    ("fp8_rows", "latent_rel_err_median"),
])
def test_the_judges_controls_on_the_twin(control, fails):
    """The judge of ``serve_closed_sparse`` on the CPU twin, bfloat16 as
    configured: correct with room on the three new numbers; each control
    NOT correct, by the check that is there to catch it (the first full
    layer's block reads 0.46% as configured and 2.9% with the rows at
    fp8: the twin's limit, 1.5%, lies between)."""
    from apex_tpu.serving import ServingEngine
    from apex_tpu.serving.scheduler import Request
    from perf.runners import serve_closed_sparse as scs
    from perf.traffic_gen import RequestStream

    base = scs._base()
    base.engine_rows, base._Taps = scs.engine_rows, scs._taps_class(base)
    judge = scs._judge_of(base)
    config = _twin(CONFIG)
    config["held_experts"] = tuple(config["held_experts"])
    mix = dict(_twin(MIX), judge_requests=4)
    reference = base._reference(config)
    cfg = base._engine_config(config)
    params = base._params(cfg, 11)
    engine = ServingEngine(cfg, params=params, **mix["engine"])
    stream = RequestStream(mix, config["vocab_size"], 11)
    requests = [Request(rid=i, prompt=p, max_new_tokens=a)
                for i, (p, a) in enumerate(stream.next() for _ in range(4))]
    engine.step(arrivals=list(requests))
    while not all(r.done() for r in requests):
        engine.step()
    scs.CONTROL = control
    try:
        ok, note = judge(reference, config, mix, params,
                         [{"req": r} for r in requests], 11, engine)
    finally:
        scs.CONTROL = None
    share, block, deepest = (note["selection_share"],
                             note["latent_rel_err_median"],
                             note["block_rel_err_deepest"])
    limit, drift = mix["judge_latent_rel_err"], \
        mix["judge_block_drift_rel_err"]
    assert len(note["block_rel_err_by_layer"]) == 6
    if control is None:
        assert ok, note
        assert share > 0.95 and block < limit / 2 and deepest < drift, note
        assert note["selection_rows_judged"] >= 16 * 3 * 10
        return
    assert not ok and note["control"] == control, note
    if fails == "selection_share":
        assert share < mix["judge_selection_share"] - 0.2, note
    else:
        assert block > 1.5 * limit, note
