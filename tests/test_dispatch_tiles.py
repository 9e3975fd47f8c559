"""Tile-parameter dispatch (ISSUE 5): table ``params`` payloads,
the shared tile-validity model's checker surface, the consult log, the
jaxpr-level proof that an unpinned consult re-tiles every consuming op
family, and check 4 of tools/check_bench_labels.py.
"""

import importlib
import json
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import dispatch
from apex_tpu.dispatch import tiles
from apex_tpu.ops import attention, attention_pallas
from apex_tpu.telemetry import ledger
from apex_tpu.transformer.functional import fused_softmax as fsm

fln = importlib.import_module("apex_tpu.normalization.fused_layer_norm")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    for k in ("APEX_DISPATCH", "APEX_DISPATCH_TABLE",
              "APEX_PALLAS_INTERPRET", "APEX_ATTN_IMPL", "APEX_LN_PALLAS",
              "APEX_FUSED_LM_HEAD", "APEX_LN_BLOCK_ROWS",
              "APEX_SOFTMAX_BLOCK_ROWS", "APEX_ATTN_BLOCK_Q",
              "APEX_XENT_ROW_BLOCK"):
        monkeypatch.delenv(k, raising=False)

    def reset():
        dispatch._reset_for_tests()
        attention.reset_default_impl()
        attention_pallas.reset_bwd_impl()
        attention_pallas.set_block_q(None)
        fln.USE_PALLAS = None
        fsm.USE_PALLAS = None

    reset()
    yield
    reset()


def _jx(fn, *args):
    return re.sub(r"0x[0-9a-f]+", "0x",
                  str(jax.make_jaxpr(lambda *a: fn(*a))(*args)))


LID = "lg-" + "0" * 10


def _payload(value, ledger_id=LID, **kw):
    return dict({"value": value, "ledger": ledger_id, "pins": {}}, **kw)


def _entry(op, dims, dtype, choice, params=None, backend="cpu",
           ledger_id=LID, **kw):
    return dispatch.make_entry(op, dims, dtype, backend, choice,
                               ledger_id, params=params, **kw)


def _table(tmp_path, monkeypatch, *entries):
    path = tmp_path / "table.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in entries))
    monkeypatch.setenv("APEX_DISPATCH_TABLE", str(path))
    dispatch._reset_for_tests()
    return str(path)


# ------------------------------------------------- tile model (checker)

def test_parse_bucket_roundtrip():
    dims = dict(b=8, sq=1024, sk=1024, h=16, d=64)  # pow2 = fixpoint
    assert tiles.parse_bucket(dispatch.bucket(**dims)) == dims
    # non-pow2 dims parse back as their ROUNDED bucket values — the
    # shape the committed legality guarantee is stated at
    assert tiles.parse_bucket(dispatch.bucket(h=12)) == {"h": 16}
    assert tiles.parse_bucket("garbage!") is None
    assert tiles.parse_bucket("") is None


def test_validate_payload_legality_at_bucket_dims():
    bucket = dispatch.bucket(rows=8192, hidden=768)
    ok = tiles.validate_payload("layer_norm", bucket, "bfloat16",
                                _payload({"block_rows": 128}))
    assert ok == []
    bad = tiles.validate_payload("layer_norm", bucket, "bfloat16",
                                 _payload({"block_rows": 100}))
    assert any("multiple of 8" in p for p in bad)
    # over-budget tile
    over = tiles.validate_payload("layer_norm", bucket, "bfloat16",
                                  _payload({"block_rows": 8192}))
    assert any("VMEM budget" in p for p in over)
    # unknown param name
    unk = tiles.validate_payload("layer_norm", bucket, "bfloat16",
                                 _payload({"block_quux": 8}))
    assert any("unknown param" in p for p in unk)
    # missing citation
    nocite = tiles.validate_payload("layer_norm", bucket, "bfloat16",
                                    {"value": {"block_rows": 128}})
    assert any("cite" in p for p in nocite)


def test_runtime_value_skips_malformed_payloads():
    assert tiles.runtime_value("layer_norm",
                               _payload({"block_rows": 64})) \
        == {"block_rows": 64}
    for bad in ("x", {}, {"value": {}}, {"value": {"block_rows": "64"}},
                {"value": {"nope": 64}}, {"value": {"block_rows": True}}):
        assert tiles.runtime_value("layer_norm", bad) is None


def test_validate_params_citation_and_pins():
    rec = ledger.make_record("tile_sweep", "cpu", 0.5, 2,
                             knobs={"APEX_DISPATCH": "off"}, git="abc",
                             ts=1.0)
    by_id = {rec["id"]: rec}
    e = _entry("layer_norm", dict(rows=8192, hidden=768), "bfloat16",
               "pallas",
               params=_payload({"block_rows": 128}, rec["id"],
                               pins={"APEX_DISPATCH": "off"}),
               ledger_id=rec["id"])
    assert dispatch.validate_params(e, by_id) == []
    # no payload = no findings
    assert dispatch.validate_params(
        _entry("layer_norm", dict(rows=8192, hidden=768), "bfloat16",
               "pallas", ledger_id=rec["id"]), by_id) == []
    # unresolvable params citation
    stale = dict(e, params=_payload({"block_rows": 128}, "lg-ffffffffff"))
    assert any("no ledger record" in p
               for p in dispatch.validate_params(stale, by_id))
    # pin drift vs the cited record
    drift = dict(e, params=_payload({"block_rows": 128}, rec["id"],
                                    pins={"APEX_DISPATCH": "on"}))
    assert any("does not match" in p
               for p in dispatch.validate_params(drift, by_id))
    # fault-stamped citation is refused
    frec = dict(rec, fault_plan="fp-deadbeef")
    assert any("FAULT-INJECTED" in p
               for p in dispatch.validate_params(e, {rec["id"]: frec}))
    # illegal tile at the bucket dims is a finding
    illegal = dict(e, params=_payload({"block_rows": 100}, rec["id"]))
    assert any("multiple of 8" in p
               for p in dispatch.validate_params(illegal, by_id))


# --------------------------------------------- lookup_params + consults

def test_lookup_params_and_consult_log(tmp_path, monkeypatch):
    dims = dict(rows=64, hidden=256)
    _table(tmp_path, monkeypatch,
           _entry("layer_norm", dims, "float32", "pallas",
                  params=_payload({"block_rows": 16})))
    choice, params = dispatch.lookup_params(
        "layer_norm", dtype="float32", backend="cpu", **dims)
    assert choice == "pallas" and params == {"block_rows": 16}
    rows = dispatch.snapshot()["consulted"]
    assert rows == [{"op": "layer_norm", "bucket": "hidden256-rows64",
                     "dtype": "float32", "backend": "cpu",
                     "choice": "pallas", "params": {"block_rows": 16}}]


def test_lookup_params_malformed_payload_falls_back(tmp_path, monkeypatch):
    dims = dict(rows=64, hidden=256)
    _table(tmp_path, monkeypatch,
           _entry("layer_norm", dims, "float32", "pallas",
                  params={"value": {"block_rows": "not-an-int"}}))
    choice, params = dispatch.lookup_params(
        "layer_norm", dtype="float32", backend="cpu", **dims)
    assert choice == "pallas" and params is None  # skip-and-fallback
    # ...and the call still works end-to-end on the heuristic tile
    x = jnp.ones((64, 256), jnp.float32)
    y = fln.fused_layer_norm(x, 256)
    assert np.isfinite(np.asarray(y)).all()


# ------------------------- jaxpr proof: consult re-tiles every family

def test_layer_norm_table_params_change_lowered_blocks(tmp_path,
                                                      monkeypatch):
    """THE acceptance proof: an unpinned consult with a params payload
    lowers different block shapes than the same consult without it."""
    x = jnp.ones((64, 256), jnp.float32)
    dims = dict(rows=64, hidden=256)

    def f(x):
        return fln.fused_layer_norm(x, 256)

    _table(tmp_path, monkeypatch,
           _entry("layer_norm", dims, "float32", "pallas"))
    j_heuristic = _jx(f, x)
    assert "pallas_call" in j_heuristic
    _table(tmp_path, monkeypatch,
           _entry("layer_norm", dims, "float32", "pallas",
                  params=_payload({"block_rows": 8})))
    j_tiled = _jx(f, x)
    assert "pallas_call" in j_tiled
    assert j_tiled != j_heuristic
    # numerics unchanged by the re-tile
    got = np.asarray(f(x))
    monkeypatch.delenv("APEX_DISPATCH_TABLE")
    dispatch._reset_for_tests()
    np.testing.assert_allclose(got, np.asarray(f(x)), atol=1e-6)


def test_layer_norm_setter_and_per_call_beat_table_params(tmp_path,
                                                          monkeypatch):
    from apex_tpu.ops import layer_norm_pallas as lnp

    x = jnp.ones((64, 256), jnp.float32)
    dims = dict(rows=64, hidden=256)
    _table(tmp_path, monkeypatch,
           _entry("layer_norm", dims, "float32", "pallas",
                  params=_payload({"block_rows": 8})))

    def f(x, **kw):
        return fln.fused_layer_norm(x, 256, **kw)

    j_table = _jx(f, x)
    # kernel tile setter outranks the table payload
    lnp.set_block_rows(16)
    j_setter = _jx(f, x)
    assert j_setter != j_table
    # per-call block_rows outranks the setter
    assert _jx(lambda x: f(x, block_rows=8), x) == j_table
    lnp.set_block_rows(None)
    assert _jx(f, x) == j_table


def test_softmax_table_params_change_lowered_blocks(tmp_path, monkeypatch):
    from apex_tpu.transformer.enums import AttnMaskType

    x = jnp.ones((2, 2, 128, 128), jnp.bfloat16)
    dims = dict(b=2, h=2, sq=128, sk=128)

    def make(block_rows=None):
        return fsm.FusedScaleMaskSoftmax(
            input_in_fp16=False, input_in_bf16=True,
            attn_mask_type=AttnMaskType.padding,
            scaled_masked_softmax_fusion=True, mask_func=None,
            softmax_in_fp32=True, scale=None, block_rows=block_rows)

    _table(tmp_path, monkeypatch,
           _entry("softmax", dims, "bfloat16", "pallas"))
    j_heuristic = _jx(lambda x: make()(x, None), x)
    _table(tmp_path, monkeypatch,
           _entry("softmax", dims, "bfloat16", "pallas",
                  params=_payload({"block_rows": 16})))
    j_tiled = _jx(lambda x: make()(x, None), x)
    assert "pallas_call" in j_tiled and j_tiled != j_heuristic
    # the instance-level per-call demand beats the table payload
    assert _jx(lambda x: make(block_rows=16)(x, None), x) == j_tiled
    # an illegal instance demand raises (asymmetry preserved)
    with pytest.raises(ValueError, match="does not divide"):
        make(block_rows=48)(x, None)


def test_attention_table_params_change_lowered_blocks(tmp_path,
                                                      monkeypatch):
    q = jnp.zeros((1, 2, 256, 32), jnp.float32)
    dims = dict(b=1, h=2, sq=256, sk=256, d=32)

    def f(q):
        return attention.fused_attention(q, q, q, causal=True)

    _table(tmp_path, monkeypatch,
           _entry("attention", dims, "float32", "rows"))
    j_heuristic = _jx(f, q)
    assert "pallas_call" in j_heuristic
    _table(tmp_path, monkeypatch,
           _entry("attention", dims, "float32", "rows",
                  params=_payload({"block_q": 32})))
    j_tiled = _jx(f, q)
    assert "pallas_call" in j_tiled and j_tiled != j_heuristic


def test_attention_bwd_table_params_reach_backward(tmp_path, monkeypatch):
    """attention_bwd params (bwd_block_q) re-tile the BACKWARD of an
    unpinned rows call — even though the impl entry itself is the
    monolithic default."""
    q = jnp.ones((1, 1, 256, 32), jnp.float32)
    dims = dict(b=1, h=1, sq=256, sk=256, d=32)

    def loss(q):
        return jnp.sum(attention_pallas.fused_attention_rows(
            q, q, q, False, 0.2, None, True) ** 2)

    j_default = _jx(lambda x: jax.grad(loss)(x), q)
    _table(tmp_path, monkeypatch,
           _entry("attention_bwd", dims, "float32", "monolithic",
                  params=_payload({"bwd_block_q": 32})))
    j_tiled = _jx(lambda x: jax.grad(loss)(x), q)
    assert j_tiled != j_default
    # grads still reference-exact under the table tile
    from apex_tpu.ops.attention import _dense_attention

    g = jax.grad(loss)(q)
    r = jax.grad(lambda x: jnp.sum(
        _dense_attention(x, x, x, False, 0.2, None) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(r), atol=2e-4)


def test_attention_bwd_dropout_never_consults_the_table(tmp_path,
                                                        monkeypatch):
    """Dropout forces the monolithic backward BEFORE any attention_bwd
    table consult: a consult whose choice can never be honored must not
    land in the snapshot()/ledger consult log (pin-the-label)."""
    q = jnp.ones((1, 1, 256, 32), jnp.float32)
    seed = jnp.zeros((1, 1), jnp.int32)
    dims = dict(b=1, h=1, sq=256, sk=256, d=32)
    _table(tmp_path, monkeypatch,
           _entry("attention_bwd", dims, "float32", "split"))

    def loss(q):
        return jnp.sum(attention_pallas.fused_attention_rows(
            q, q, q, False, 0.2, None, True, None, None, 0.1, seed) ** 2)

    jax.grad(loss)(q)
    assert not any(r["op"] == "attention_bwd"
                   for r in dispatch.snapshot()["consulted"])
    # ...while the dropout-free backward does consult it
    def loss2(q):
        return jnp.sum(attention_pallas.fused_attention_rows(
            q, q, q, False, 0.2, None, True) ** 2)

    jax.grad(loss2)(q)
    assert any(r["op"] == "attention_bwd" and r["choice"] == "split"
               for r in dispatch.snapshot()["consulted"])


def test_lm_head_table_params_change_lowered_blocks(tmp_path,
                                                    monkeypatch):
    from tests.test_dispatch import _gpt

    f, args, cfg = _gpt()
    dims = dict(n=32, v=512, h=128)
    _table(tmp_path, monkeypatch,
           _entry("lm_head", dims, "float32", "fused"))
    j_heuristic = _jx(f, *args)
    assert "pallas_call" in j_heuristic
    _table(tmp_path, monkeypatch,
           _entry("lm_head", dims, "float32", "fused",
                  params=_payload({"row_block": 8})))
    j_tiled = _jx(f, *args)
    assert "pallas_call" in j_tiled and j_tiled != j_heuristic


# ----------------------------------------------------- check 4 (tool)

def test_check_tool_validates_params_payloads(tmp_path):
    """tools/check_bench_labels.py check 4 — in-process main() (the
    subprocess CLI path is already covered by test_dispatch.py)."""
    from tools import check_bench_labels as tool

    rec = ledger.make_record("tile_sweep", "cpu", 0.5, 2,
                             knobs={"APEX_DISPATCH": "off"}, git="abc",
                             ts=1.0)
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text("# fixture\n")
    ok_entry = _entry("layer_norm", dict(rows=8192, hidden=768),
                      "bfloat16", "pallas",
                      params=_payload({"block_rows": 128}, rec["id"],
                                      pins={"APEX_DISPATCH": "off"}),
                      ledger_id=rec["id"])

    def run(entry):
        tpath = tmp_path / "table.jsonl"
        tpath.write_text(json.dumps(entry) + "\n")
        dispatch._reset_for_tests()
        return tool.main(["--perf", str(perf), "--ledger", str(lpath),
                          "--table", str(tpath)])

    assert run(ok_entry) == 0
    # illegal tile at bucket dims
    assert run(dict(ok_entry, params=_payload(
        {"block_rows": 100}, rec["id"]))) == 1
    # unresolvable params citation
    assert run(dict(ok_entry, params=_payload(
        {"block_rows": 128}, "lg-ffffffffff"))) == 1
    # params pin drift
    assert run(dict(ok_entry, params=_payload(
        {"block_rows": 128}, rec["id"],
        pins={"APEX_DISPATCH": "on"}))) == 1
    # malformed payload (runtime would skip-and-fallback; here: FAIL)
    assert run(dict(ok_entry, params={"value": {"block_rows": "x"},
                                      "ledger": rec["id"]})) == 1


def test_committed_table_params_validate():
    """The shipped table's params payloads (the CPU demonstration
    sweep) validate against the committed ledger — tier-1 gate on the
    real artifacts."""
    entries, problems = dispatch.load_table(dispatch.default_path())
    assert problems == []
    recs = ledger.read_ledger()
    by_id = {r.get("id"): r for r in recs}
    with_params = [e for e in entries.values() if "params" in e]
    # the committed demonstration sweep: >= 2 op families carry params
    assert len({e["op"] for e in with_params}) >= 2, with_params
    for e in with_params:
        assert e["backend"] == "cpu"  # never leaks into TPU dispatch
        assert dispatch.validate_params(e, by_id) == [], e
