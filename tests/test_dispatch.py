"""apex_tpu.dispatch — the per-shape measured-dispatch table.

Pins the subsystem's contract: precedence (per-call knob > process-wide
setter > table entry > built-in default), the explicit-request-raises /
preference-falls-back asymmetry, table-miss and corrupt-line fallback,
and — the acceptance bar — that a table entry REALLY changes the traced
program end-to-end for every consulting op family (LN, softmax,
attention, LM head, remat, LAMB).
"""

import importlib
import json

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu import dispatch
from apex_tpu.ops import attention, attention_pallas
from apex_tpu.telemetry import ledger
from apex_tpu.transformer.functional import fused_softmax as fsm

# the REAL module, not the function the package re-exports under the
# same name — `from apex_tpu.normalization import fused_layer_norm`
# resolves to the function, and setting USE_PALLAS on it silently
# changes nothing (the pre-round-6 APEX_LN_PALLAS bug; see
# fused_layer_norm.set_use_pallas)
fln = importlib.import_module("apex_tpu.normalization.fused_layer_norm")


@pytest.fixture(autouse=True)
def _clean_dispatch(monkeypatch):
    """Unpin every process-wide knob and drop table caches around each
    test — precedence tests must start from the shipped (unpinned)
    state."""
    for k in ("APEX_DISPATCH", "APEX_DISPATCH_TABLE",
              "APEX_PALLAS_INTERPRET", "APEX_ATTN_IMPL", "APEX_LN_PALLAS",
              "APEX_FUSED_LM_HEAD", "APEX_REMAT", "APEX_LAMB_IMPL"):
        monkeypatch.delenv(k, raising=False)

    def reset():
        dispatch._reset_for_tests()
        attention.reset_default_impl()
        attention_pallas.reset_bwd_impl()
        fln.USE_PALLAS = None
        fsm.USE_PALLAS = None

    reset()
    yield
    reset()


def _jx(fn, *args):
    """Trace with a FRESH function object. jax's jit trace cache is
    keyed on the function identity, so re-tracing the same lambda after
    a table change would reuse the stale jaxpr — "trace-time consult"
    means exactly that: a process re-building its functions (as jit
    users do per trace) sees the table; an already-traced program does
    not."""
    return str(jax.make_jaxpr(lambda *a: fn(*a))(*args))


def _table(tmp_path, monkeypatch, *entries):
    path = tmp_path / "table.jsonl"
    path.write_text("".join(json.dumps(e) + "\n" for e in entries))
    monkeypatch.setenv("APEX_DISPATCH_TABLE", str(path))
    dispatch._reset_for_tests()
    return str(path)


def _entry(op, dims, dtype, choice, backend="cpu", ledger_id="lg-" + "0" * 10,
           **kw):
    return dispatch.make_entry(op, dims, dtype, backend, choice, ledger_id,
                               **kw)


# ------------------------- table mechanics ---------------------------------

def test_bucket_rounds_up_to_pow2_and_sorts_dims():
    assert dispatch.bucket(sq=1000, b=7) == "b8-sq1024"
    assert dispatch.bucket(b=8) == "b8"  # exact pow2 unchanged
    assert dispatch.bucket(n=1) == "n1"
    # producers and consumers cannot disagree on dim order
    assert dispatch.bucket(a=2, z=2) == dispatch.bucket(z=2, a=2)


def test_lookup_miss_and_off_switch(tmp_path, monkeypatch):
    _table(tmp_path, monkeypatch,
           _entry("layer_norm", dict(rows=64, hidden=256), "float32",
                  "pallas"))
    hit = dict(rows=64, hidden=256)
    assert dispatch.lookup("layer_norm", dtype="float32", backend="cpu",
                           **hit) == "pallas"
    # miss: different bucket / dtype / backend / op
    assert dispatch.lookup("layer_norm", dtype="float32", backend="cpu",
                           rows=8192, hidden=256) is None
    assert dispatch.lookup("layer_norm", dtype="bfloat16", backend="cpu",
                           **hit) is None
    assert dispatch.lookup("layer_norm", dtype="float32", backend="tpu",
                           **hit) is None
    assert dispatch.lookup("softmax", dtype="float32", backend="cpu",
                           **hit) is None
    # APEX_DISPATCH=off disables the table wholesale
    monkeypatch.setenv("APEX_DISPATCH", "off")
    assert dispatch.lookup("layer_norm", dtype="float32", backend="cpu",
                           **hit) is None


def test_corrupt_line_falls_back_but_good_lines_survive(tmp_path,
                                                        monkeypatch):
    path = tmp_path / "table.jsonl"
    good = _entry("layer_norm", dict(rows=64, hidden=256), "float32",
                  "pallas")
    path.write_text("{not json!!\n" + json.dumps(good) + "\n"
                    + json.dumps({"op": "softmax"}) + "\n")
    monkeypatch.setenv("APEX_DISPATCH_TABLE", str(path))
    dispatch._reset_for_tests()
    entries, problems = dispatch.load_table()
    assert len(entries) == 1 and len(problems) == 2  # corrupt + incomplete
    # runtime dispatch still serves the good entry — a corrupt line
    # degrades to the built-in default for ITS key only
    assert dispatch.lookup("layer_norm", dtype="float32", backend="cpu",
                           rows=64, hidden=256) == "pallas"


def test_invalid_choice_is_a_miss(tmp_path, monkeypatch):
    _table(tmp_path, monkeypatch,
           _entry("layer_norm", dict(rows=64, hidden=256), "float32",
                  "warp_shuffle"))
    assert dispatch.lookup("layer_norm", dtype="float32", backend="cpu",
                           rows=64, hidden=256) is None


def test_last_entry_wins_append_to_update(tmp_path, monkeypatch):
    dims = dict(rows=64, hidden=256)
    _table(tmp_path, monkeypatch,
           _entry("layer_norm", dims, "float32", "pallas"),
           _entry("layer_norm", dims, "float32", "jnp"))
    assert dispatch.lookup("layer_norm", dtype="float32", backend="cpu",
                           **dims) == "jnp"


def test_validate_entry_pins_against_ledger():
    rec = ledger.make_record("profile_gpt", "cpu", 0.5, 2,
                             knobs={"APEX_ATTN_IMPL": "rows"}, git="abc",
                             ts=1.0)
    by_id = {rec["id"]: rec}
    ok = _entry("attention", dict(b=8), "bfloat16", "rows",
                ledger_id=rec["id"], pins={"APEX_ATTN_IMPL": "rows"})
    assert dispatch.validate_entry(ok, by_id) == []
    # unresolvable citation
    bad = dict(ok, ledger="lg-ffffffffff")
    assert any("no ledger record" in p
               for p in dispatch.validate_entry(bad, by_id))
    # pin disagrees with what the record measured — label drift
    drift = dict(ok, pins={"APEX_ATTN_IMPL": "flash"})
    assert any("does not match" in p
               for p in dispatch.validate_entry(drift, by_id))
    # pin says unset but the record pinned it
    unset = dict(ok, pins={"APEX_ATTN_IMPL": None})
    assert any("pinned" in p for p in dispatch.validate_entry(unset, by_id))
    # unknown vocabulary
    vocab = dict(ok, choice="dense")
    assert any("not in" in p for p in dispatch.validate_entry(vocab, by_id))


# ------------------------- precedence: attention ----------------------------

def _q(b=1, h=2, s=128, d=32, dtype=jnp.float32):
    return jnp.zeros((b, h, s, d), dtype)


def test_attention_precedence(tmp_path, monkeypatch):
    q = _q()
    _table(tmp_path, monkeypatch,
           _entry("attention", dict(b=1, h=2, sq=128, sk=128, d=32),
                  "float32", "rows"))
    # table entry drives the unpinned choice
    assert attention._effective_impl(None, q, q) == ("rows", True)
    # process-wide setter beats the table
    attention.set_default_impl("flash")
    assert attention._effective_impl(None, q, q) == ("flash", False)
    # per-call knob beats everything
    assert attention._effective_impl("rows", q, q) == ("rows", False)
    # explicit un-honorable request raises (never silently falls back)
    with pytest.raises(ValueError):
        attention.fused_attention(q, q, q, impl="bogus")
    with pytest.raises(ValueError):
        attention.set_default_impl("bogus")


def test_attention_table_flip_changes_traced_program(tmp_path, monkeypatch):
    q = _q()

    def f(q):
        return attention.fused_attention(q, q, q, causal=True)

    default_jx = _jx(f, q)
    assert "pallas_call" not in default_jx  # cpu default: dense path
    _table(tmp_path, monkeypatch,
           _entry("attention", dict(b=1, h=2, sq=128, sk=128, d=32),
                  "float32", "rows"))
    table_jx = _jx(f, q)
    # the CPU-measured table choice runs the rows kernel in interpret
    # mode — the way it was measured
    assert "pallas_call" in table_jx


def test_attention_bwd_precedence(tmp_path, monkeypatch):
    q = _q()
    _table(tmp_path, monkeypatch,
           _entry("attention_bwd", dict(b=1, h=2, sq=128, sk=128, d=32),
                  "float32", "split"))
    assert attention_pallas._effective_bwd_impl(q, q) == "split"
    attention_pallas.set_bwd_impl("monolithic")
    assert attention_pallas._effective_bwd_impl(q, q) == "monolithic"
    attention_pallas.reset_bwd_impl()
    assert attention_pallas._effective_bwd_impl(q, q) == "split"
    # miss at another bucket -> built-in default
    big = jnp.zeros((1, 2, 256, 32), jnp.float32)
    assert attention_pallas._effective_bwd_impl(big, big) == "monolithic"


def test_attention_bwd_explicit_split_still_raises_when_ineligible():
    # the asymmetry survives the table layer: an explicit per-call
    # bwd_impl="split" on an ineligible shape raises (sq/bq > 32 chunks)
    q = jnp.zeros((1, 1, 8192, 64), jnp.bfloat16)

    def loss(q):
        return attention_pallas.fused_attention_rows(
            q, q, q, False, 1.0, None, True, None, "split").sum()

    with pytest.raises(ValueError, match="split bwd ineligible"):
        jax.grad(loss)(q)


# ------------------------- precedence: layer norm ---------------------------

def test_layer_norm_precedence_and_flip(tmp_path, monkeypatch):
    x = jnp.ones((64, 256), jnp.float32)

    def f(x):
        return fln.fused_layer_norm(x, 256)

    assert "pallas_call" not in _jx(f, x)
    _table(tmp_path, monkeypatch,
           _entry("layer_norm", dict(rows=64, hidden=256), "float32",
                  "pallas"))
    # table drives the unpinned choice; cpu entry -> interpret kernel
    assert "pallas_call" in _jx(f, x)
    # numerics parity: toggling the table never changes semantics
    got = np.asarray(f(x))
    dispatch._reset_for_tests()
    monkeypatch.delenv("APEX_DISPATCH_TABLE")
    want = np.asarray(f(x))
    np.testing.assert_allclose(got, want, atol=1e-6)


def test_layer_norm_setter_and_per_call_beat_table(tmp_path, monkeypatch):
    x = jnp.ones((64, 256), jnp.float32)
    _table(tmp_path, monkeypatch,
           _entry("layer_norm", dict(rows=64, hidden=256), "float32",
                  "pallas"))

    def f(x):
        return fln.fused_layer_norm(x, 256)

    # module-level setter (False) pins ABOVE the table
    fln.USE_PALLAS = False
    assert "pallas_call" not in _jx(f, x)
    # ...and True is still gated on a real TPU (preference falls back)
    fln.USE_PALLAS = True
    assert "pallas_call" not in _jx(f, x)
    fln.USE_PALLAS = None
    # per-call use_pallas=False pins below nothing — it wins outright
    assert "pallas_call" not in _jx(
        lambda x: fln.fused_layer_norm(x, 256, use_pallas=False), x)
    # table applies again once unpinned
    assert "pallas_call" in _jx(f, x)
    # a table hit for an UNSUPPORTED shape falls back silently
    # (preference semantics: hidden not lane-aligned)
    _table(tmp_path, monkeypatch,
           _entry("layer_norm", dict(rows=64, hidden=100), "float32",
                  "pallas"))
    x2 = jnp.ones((64, 100), jnp.float32)
    assert "pallas_call" not in _jx(
        lambda x: fln.fused_layer_norm(x, 100), x2)


# ------------------------- precedence: softmax ------------------------------

def _softmax_inst(use_pallas=None):
    from apex_tpu.transformer.enums import AttnMaskType

    return fsm.FusedScaleMaskSoftmax(
        input_in_fp16=False, input_in_bf16=True,
        attn_mask_type=AttnMaskType.padding,
        scaled_masked_softmax_fusion=True,
        mask_func=None, softmax_in_fp32=True, scale=None,
        use_pallas=use_pallas)


def test_softmax_precedence_and_flip(tmp_path, monkeypatch):
    x = jnp.ones((2, 2, 128, 128), jnp.bfloat16)
    sm = _softmax_inst()

    def f(x):
        return sm(x, None)

    assert "pallas_call" not in _jx(f, x)
    _table(tmp_path, monkeypatch,
           _entry("softmax", dict(b=2, h=2, sq=128, sk=128), "bfloat16",
                  "pallas"))
    assert "pallas_call" in _jx(f, x)
    # module setter beats table
    fsm.set_use_pallas(False)
    assert "pallas_call" not in _jx(f, x)
    fsm.set_use_pallas(None)
    # per-instance pin beats everything
    sm_pinned = _softmax_inst(use_pallas=False)
    assert "pallas_call" not in _jx(lambda x: sm_pinned(x, None), x)
    with pytest.raises(ValueError):
        fsm.set_use_pallas("yes")


# ------------------------- model: LM head + remat ---------------------------

def _gpt(tmp_path=None, monkeypatch=None, **cfg_kw):
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.transformer.testing import GPTModel, TransformerConfig

    cfg = TransformerConfig(
        hidden_size=128, num_layers=2, num_attention_heads=4,
        vocab_size=512, max_position_embeddings=32, hidden_dropout=0.0,
        attention_dropout=0.0, **cfg_kw)
    model = GPTModel(cfg)
    mesh = Mesh(np.array(jax.devices()[:1]), ("tp",))
    rs = np.random.RandomState(0)
    b, s = 2, 16
    ids = jnp.asarray(rs.randint(0, cfg.vocab_size, (b, s)))
    pos = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    labels = jnp.asarray(rs.randint(0, cfg.vocab_size, (b, s)))

    def run(ids, pos, labels):
        params = model.init(jax.random.PRNGKey(0), ids, pos, None)["params"]
        return model.apply({"params": params}, ids, pos, None, labels)

    from jax import shard_map

    f = shard_map(run, mesh=mesh, in_specs=(P(), P(), P()), out_specs=P(),
                  check_vma=False)
    return f, (ids, pos, labels), cfg


def test_lm_head_table_flip(tmp_path, monkeypatch):
    f, args, cfg = _gpt()
    assert "pallas_call" not in _jx(f, *args)
    # n = b*s = 32, v = 512, h = 128 (the model's trace-time lookup key)
    _table(tmp_path, monkeypatch,
           _entry("lm_head", dict(n=32, v=512, h=128), "float32", "fused"))
    assert "pallas_call" in _jx(f, *args)
    # config pin (False) beats the table
    f2, args2, _ = _gpt(fused_lm_head=False)
    assert "pallas_call" not in _jx(f2, *args2)


def test_remat_table_flip_and_none_pin(tmp_path, monkeypatch):
    f, args, cfg = _gpt()
    default_jx = _jx(f, *args)
    assert "remat" not in default_jx
    _table(tmp_path, monkeypatch,
           _entry("remat", dict(b=2, s=16, h=128, layers=2), "float32",
                  "full"))
    assert "remat" in _jx(f, *args)
    # explicit "none" pins recompute OFF above the table
    f2, args2, _ = _gpt(recompute_granularity="none")
    assert "remat" not in _jx(f2, *args2)
    # explicit "selective" still honored with the table present
    f3, args3, _ = _gpt(recompute_granularity="selective")
    assert "remat" in _jx(f3, *args3)


# ------------------------- precedence: FusedLAMB ----------------------------

def test_lamb_table_flip_and_precedence(tmp_path, monkeypatch):
    from apex_tpu.optimizers.fused_lamb import fused_lamb

    params = {"w": jnp.ones((128, 128), jnp.float32)}
    grads = {"w": jnp.full((128, 128), 1e-3, jnp.float32)}

    def jx_of(tx):
        st = tx.init(params)
        return str(jax.make_jaxpr(
            lambda g, s, p: tx.update(g, s, p))(grads, st, params))

    default_jx = jx_of(fused_lamb(1e-3))
    _table(tmp_path, monkeypatch,
           _entry("lamb", dict(n=16384), "float32", "one_pass"))
    table_jx = jx_of(fused_lamb(1e-3))
    assert table_jx != default_jx  # one_pass = segment-sum flat sweep
    assert "segment" in table_jx or "scatter" in table_jx
    # env preference beats table
    monkeypatch.setenv("APEX_LAMB_IMPL", "two_pass")
    assert jx_of(fused_lamb(1e-3)) == default_jx
    # per-call impl beats env
    monkeypatch.setenv("APEX_LAMB_IMPL", "one_pass")
    assert jx_of(fused_lamb(1e-3, impl="two_pass")) == default_jx


# ------------------------- tool integration ---------------------------------

def test_check_tool_validates_table(tmp_path):
    """tools/check_bench_labels.py check 3: unresolvable citations and
    pin drift in the dispatch table fail tier-1. Driven in-process
    (tests/test_bench_labels.py covers the CLI surface once) — each of
    the four invocations here used to be a ~3s subprocess."""
    from tests.conftest import run_check_bench_labels

    rec = ledger.make_record("profile_gpt", "cpu", 0.5, 2,
                             knobs={"APEX_ATTN_IMPL": "rows"}, git="abc",
                             ts=1.0)
    lpath = tmp_path / "ledger.jsonl"
    lpath.write_text(json.dumps(rec, sort_keys=True) + "\n")
    perf = tmp_path / "PERF.md"
    perf.write_text("# fixture\n")

    def run(table_lines):
        tpath = tmp_path / "table.jsonl"
        tpath.write_text("".join(table_lines))
        return run_check_bench_labels("--perf", str(perf), "--ledger",
                                      str(lpath), "--table", str(tpath))

    ok = _entry("attention", dict(b=8), "bfloat16", "rows",
                ledger_id=rec["id"], pins={"APEX_ATTN_IMPL": "rows"})
    out = run([json.dumps(ok) + "\n"])
    assert out.returncode == 0, out.stdout
    # unresolvable ledger id
    out = run([json.dumps(dict(ok, ledger="lg-ffffffffff")) + "\n"])
    assert out.returncode == 1 and "no ledger record" in out.stdout
    # pin drift vs the cited record
    out = run([json.dumps(dict(ok, pins={"APEX_ATTN_IMPL": "flash"}))
               + "\n"])
    assert out.returncode == 1 and "does not match" in out.stdout
    # a corrupt line is a finding here (runtime would fall back)
    out = run(["{corrupt\n", json.dumps(ok) + "\n"])
    assert out.returncode == 1 and "unparseable" in out.stdout


def test_committed_table_validates_against_committed_ledger():
    """The shipped apex_tpu/dispatch/table.jsonl resolves against
    benchmarks/ledger.jsonl — the tier-1 gate on the real artifacts
    (the full check also runs in test_bench_labels.py)."""
    entries, problems = dispatch.load_table(dispatch.default_path())
    assert problems == []
    assert len(entries) >= 6  # six step-level groups, CPU-measured
    recs = ledger.read_ledger()
    by_id = {r.get("id"): r for r in recs}
    for e in entries.values():
        assert dispatch.validate_entry(e, by_id) == [], e
    # the committed CPU pass demonstrates a real selection flip
    # end-to-end: the bench_batch rung's measured amortization win
    assert any(e["op"] == "bench_batch" and e["choice"] != "2"
               for e in entries.values())
