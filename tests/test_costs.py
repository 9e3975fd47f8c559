"""The attribution layer (ISSUE 7): apex_tpu.telemetry.costs cost-block
schema + derivations, the _compat readers of the installed jax's
cost/memory analysis surfaces, comm-volume accounting from jaxprs
(incl. the multichip training step), the tiles.py VMEM validation hook,
profiler-capture artifact stamps, the ledger inspection CLI, and the
PR-1 invariant: asking XLA to count a program's flops leaves the traced
jaxpr byte-identical. All CPU-tier, fast (jaxpr traces + one tiny AOT
compile; no subprocesses)."""

import contextlib
import io
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu import _compat
from apex_tpu.dispatch import tiles
from apex_tpu.telemetry import costs, ledger


# ---------------------------------------------------------------- build()


def test_build_derives_floors_and_mfu_bound():
    """The analytic roofline arithmetic: floors = flops/peak and
    bytes/bw, step floor = max, MFU bound = model flops at the floor
    over peak."""
    peak = costs.V5E_PEAK_BF16_FLOPS
    bw = costs.V5E_HBM_BYTES_PER_S
    block = costs.build(
        xla_flops=peak * 1e-3,            # 1 ms/step compute floor
        hbm_bytes=bw * 2e-3,              # 2 ms/step bandwidth floor
        steps=10, model_flops_per_step=peak * 0.9e-3,  # 0.9ms of "model"
        device_kind=costs.V5E_KIND, source="compiled")
    assert block["steps"] == 10  # metadata, never a divisor
    assert block["xla_flops_per_step"] == pytest.approx(peak * 1e-3)
    assert block["compute_floor_ms"] == pytest.approx(1.0)
    assert block["bandwidth_floor_ms"] == pytest.approx(2.0)
    assert block["step_floor_ms"] == pytest.approx(2.0)  # max of the two
    # mfu_bound = model_flops / floor_seconds / peak = 0.9ms-of-peak / 2ms
    assert block["mfu_bound"] == pytest.approx(0.45, abs=1e-4)
    assert costs.validate(block) == []


def test_build_peak_hbm_from_memory_analysis():
    mem = {"argument_size_in_bytes": 100, "output_size_in_bytes": 50,
           "temp_size_in_bytes": 30, "alias_size_in_bytes": 40,
           "generated_code_size_in_bytes": 5}
    block = costs.build(memory=mem, steps=1)
    assert block["peak_hbm_bytes"] == 100 + 50 + 30 + 5 - 40
    assert block["memory"]["temp_size_in_bytes"] == 30
    assert costs.validate(block) == []


def test_build_cpu_platform_has_no_roofline():
    """No committed envelope off-TPU: floors and bound stay None (the
    same rule as the benchmark's mfu=None on CPU)."""
    block = costs.build(xla_flops=1e9, hbm_bytes=1e6, steps=1,
                        device_kind="cpu", source="lowered")
    assert block["peak_flops"] is None
    assert block["compute_floor_ms"] is None
    assert block["mfu_bound"] is None
    assert costs.validate(block) == []


def test_null_block_is_valid_and_all_none():
    block = costs.null_block()
    assert set(block) == set(costs.FIELDS)
    assert all(v is None for v in block.values())
    assert costs.validate(block) == []


def test_capture_without_stage_degrades_not_raises():
    block = costs.capture(lowered=None, compiled=None, steps=4,
                          model_flops_per_step=123.0, device_kind="cpu")
    assert block["source"] is None
    assert block["xla_flops_per_step"] is None
    assert block["model_flops_per_step"] == 123.0
    assert costs.validate(block) == []


def test_capture_real_aot_stage_reports_xla_numbers():
    """One tiny real AOT pair on CPU: the capture path reads flops and
    memory from the actual jax surfaces through the _compat
    normalizers."""
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((16, 16), jnp.float32)
    lowered = f.lower(x)
    compiled = lowered.compile()
    block = costs.capture(lowered=lowered, compiled=compiled, steps=1,
                          device_kind="cpu")
    assert block["source"] in ("compiled", "lowered")
    assert block["xla_flops_per_step"] and block["xla_flops_per_step"] > 0
    assert costs.validate(block) == []


def test_memory_key_tuples_stay_in_sync():
    """costs._MEMORY_KEYS (consumer: build/validate) must equal
    _compat._MEMORY_FIELDS (producer: memory_analysis_dict) — the
    tuples are deliberately duplicated (costs stays stdlib-only at
    import; _compat imports jax at module top), so drift between them
    would silently null memory fields and skew peak_hbm_bytes with
    validate() still passing."""
    from apex_tpu import _compat

    assert costs._MEMORY_KEYS == _compat._MEMORY_FIELDS


def test_xla_counts_scan_body_once_calibration():
    """The calibration behind build()'s no-division rule: XLA's
    cost_analysis counts a lax.scan body ONCE, not × trip count, so
    the analyses' numbers are per-step already for a K-scan program.
    If a jax upgrade changes the counting, this fails loudly and
    build()'s semantics must be revisited — otherwise every stamped
    floor/mfu_bound silently goes ~K× wrong again."""
    from apex_tpu import _compat

    def body(c, _):
        return c @ c, None

    x = jnp.ones((64, 64), jnp.float32)
    one = jax.jit(lambda x: x @ x).lower(x)
    scan16 = jax.jit(
        lambda x: jax.lax.scan(body, x, None, length=16)[0]).lower(x)
    f_one = _compat.cost_analysis_dict(one)["flops"]
    f_scan = _compat.cost_analysis_dict(scan16)["flops"]
    assert f_one > 0
    # one body + loop overhead, nowhere near 16 bodies
    assert f_one <= f_scan < 2 * f_one

    block = costs.capture(lowered=scan16, steps=16, device_kind="cpu")
    assert block["steps"] == 16
    assert block["xla_flops_per_step"] == pytest.approx(f_scan)


def test_capture_escape_hatch_env(monkeypatch):
    """APEX_COST_ANALYSIS=0 skips the XLA reads outright but still
    stamps a (degraded) block — degradation, never omission."""
    monkeypatch.setenv("APEX_COST_ANALYSIS", "0")
    assert costs.enabled(default=True) is False
    f = jax.jit(lambda x: x + 1)
    lowered = f.lower(jnp.ones(4))
    block = costs.capture(lowered=lowered, compiled=None, steps=2,
                          device_kind="cpu")
    assert block["source"] is None
    assert block["xla_flops_per_step"] is None
    monkeypatch.setenv("APEX_COST_ANALYSIS", "1")
    assert costs.enabled(default=False) is True


# ------------------------------------------------------ validate() teeth


@pytest.mark.parametrize("mutate, frag", [
    (lambda b: b.pop("mfu_bound"), "missing field"),
    (lambda b: b.update(xla_flops_per_step=-1.0), "non-negative"),
    (lambda b: b.update(source="guessed"), "source"),
    (lambda b: b.update(steps=0), "steps"),
    (lambda b: b.update(memory={"argument_size_in_bytes": "big"}),
     "memory.argument_size_in_bytes"),
    (lambda b: b.update(comm_bytes_per_axis={"dp": -5}),
     "comm_bytes_per_axis"),
])
def test_validate_rejects_malformed(mutate, frag):
    block = costs.null_block()
    mutate(block)
    problems = costs.validate(block)
    assert problems and any(frag in p for p in problems), problems


def test_validate_record_polices_cost_block(tmp_path):
    """ledger.validate_record runs the cost validator on every record
    carrying the block — a malformed block is a schema finding."""
    rec = ledger.make_record("bench", "cpu", 0.5, 2, git="abc", ts=1.0,
                             extra={"cost": costs.null_block()})
    assert ledger.validate_record(rec) == []
    bad = dict(costs.null_block(), mfu_bound=-2.0)
    rec2 = ledger.make_record("bench", "cpu", 0.5, 2, git="abc", ts=1.0,
                              extra={"cost": bad})
    assert any("cost:" in p for p in ledger.validate_record(rec2))


# ------------------------------------------------- _compat readers


class _Stage:
    def __init__(self, raw=None):
        self._raw = raw
        self.cost_analysis = self.memory_analysis = lambda: self._raw


class _MemStats:
    """The CompiledMemoryStats shape: attributes, not keys."""
    argument_size_in_bytes = 64
    output_size_in_bytes = 32
    temp_size_in_bytes = 128
    alias_size_in_bytes = 16
    generated_code_size_in_bytes = 8


class _ZeroStats(_MemStats):
    argument_size_in_bytes = output_size_in_bytes = 0
    temp_size_in_bytes = alias_size_in_bytes = 0
    generated_code_size_in_bytes = 0


def test_cost_analysis_dict_none_and_dict():
    assert _compat.cost_analysis_dict(_Stage(raw=None)) is None
    assert _compat.cost_analysis_dict(_Stage(raw={})) is None
    assert _compat.cost_analysis_dict(
        _Stage(raw={"flops": 10.0})) == {"flops": 10.0}


def test_memory_analysis_dict_none_stats_and_zero():
    assert _compat.memory_analysis_dict(_Stage(raw=None)) is None
    out = _compat.memory_analysis_dict(_Stage(raw=_MemStats()))
    assert out == {"argument_size_in_bytes": 64,
                   "output_size_in_bytes": 32,
                   "temp_size_in_bytes": 128,
                   "alias_size_in_bytes": 16,
                   "generated_code_size_in_bytes": 8}
    # all-zero stats carry no information -> "can't report"
    assert _compat.memory_analysis_dict(_Stage(raw=_ZeroStats())) is None


def test_installed_jax_surfaces_read():
    """Calibration against the installed jax: Lowered and Compiled
    report flat numeric dicts and Compiled reports memory stats — the
    test that breaks loudly on a jax upgrade that changes the
    surface."""
    f = jax.jit(lambda x: jnp.tanh(x @ x))
    lowered = f.lower(jnp.ones((8, 8), jnp.float32))
    compiled = lowered.compile()
    for stage in (lowered, compiled):
        ca = _compat.cost_analysis_dict(stage)
        assert isinstance(ca, dict) and ca["flops"] > 0
        assert all(isinstance(v, (int, float)) for v in ca.values())
    ma = _compat.memory_analysis_dict(compiled)
    assert set(ma) == set(_compat._MEMORY_FIELDS)


# ---------------------------------------------------- comm accounting


def test_comm_from_jaxpr_counts_psum_per_axis():
    mesh = jax.sharding.Mesh(np.array(jax.devices()).reshape(4, 2),
                             ("dp", "tp"))

    def f(x):
        return jax.lax.psum(x, "dp") + jax.lax.psum(x, "tp")

    g = jax.shard_map(f, mesh=mesh,
                      in_specs=jax.sharding.PartitionSpec("dp"),
                      out_specs=jax.sharding.PartitionSpec("dp"),
                      check_vma=False)
    x = jnp.ones((8, 16), jnp.float32)
    comm = costs.comm_from_jaxpr(jax.make_jaxpr(g)(x))
    # per-participant payload: the (2,16) f32 shard = 128 bytes per psum
    assert comm == {"dp": 128, "tp": 128}


def test_comm_from_jaxpr_multiplies_scan_trip_count():
    mesh = jax.sharding.Mesh(np.array(jax.devices()), ("dp",))

    def body(c, _):
        return jax.lax.psum(c, "dp"), ()

    def f(x):
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    g = jax.shard_map(f, mesh=mesh,
                      in_specs=jax.sharding.PartitionSpec(),
                      out_specs=jax.sharding.PartitionSpec(),
                      check_vma=False)
    x = jnp.ones((4,), jnp.float32)  # 16 bytes per psum, x5 iterations
    comm = costs.comm_from_jaxpr(jax.make_jaxpr(g)(x))
    assert comm == {"dp": 80}


def test_comm_from_jaxpr_no_collectives_is_empty_and_never_raises():
    jaxpr = jax.make_jaxpr(lambda x: x * 2)(jnp.ones(4))
    assert costs.comm_from_jaxpr(jaxpr) == {}
    assert costs.comm_from_jaxpr(object()) == {}  # unknown shape: {}


def test_training_comm_bytes_multichip_topology():
    """The dryrun MULTICHIP comm accounting (ROADMAP item 3 seed): a
    (pp=2, dp=2, tp=2) minimal-GPT training step traced to a jaxpr
    reports nonzero collective payload on the axes that exist, and a
    size-1 axis is filtered (its collectives move nothing)."""
    from apex_tpu.transformer.testing.minimal import training_comm_bytes
    from apex_tpu.transformer.testing import TransformerConfig

    devices = jax.devices()
    cfg = TransformerConfig(
        hidden_size=32, num_layers=2, num_attention_heads=2,
        vocab_size=64, max_position_embeddings=8, hidden_dropout=0.0,
        attention_dropout=0.0, bf16=True,
        apply_query_key_layer_scaling=False)
    comm = training_comm_bytes(devices, cfg, (2, 2, 2),
                               num_microbatches=2, micro_batch_size=1,
                               seq_len=8)
    assert comm.get("tp", 0) > 0, comm   # tensor-parallel matmul psums
    assert comm.get("dp", 0) > 0, comm   # grad allreduce
    comm2 = training_comm_bytes(devices, cfg, (2, 4, 1),
                                num_microbatches=2, micro_batch_size=1,
                                seq_len=8)
    assert "tp" not in comm2, comm2      # size-1 axis filtered


# ------------------------------------------------------ peaks table


def test_peaks_keyed_by_device_kind_unknown_kind_raises():
    """The v5e row serves "TPU v5 lite" only: the CPU gets no roofline
    (None everywhere) and an accelerator kind without published peaks
    is an error, never the v5e constants."""
    assert costs.peak_flops_for(costs.V5E_KIND) == costs.V5E_PEAK_BF16_FLOPS
    assert costs.peaks_for("cpu") is None and costs.peaks_for(None) is None
    for fn in (costs.peak_flops_for, costs.hbm_bw_for,
               costs.hbm_capacity_for, costs.ici_bw_for):
        assert fn("cpu") is None
        with pytest.raises(ValueError, match="no published peaks"):
            fn("TPU v4")
    with pytest.raises(ValueError, match="no published peaks"):
        costs.build(xla_flops=1.0, device_kind="tpu")
    assert costs.build(xla_flops=1.0, device_kind="cpu")["mfu_bound"] is None


# ------------------------------------------------ starvation economics


def test_starvation_verdicts(monkeypatch):
    monkeypatch.delenv("APEX_STARVE_HBM_BYTES", raising=False)
    cap = costs.V5E_HBM_CAPACITY_BYTES
    assert costs.starvation(cap + 1, costs.V5E_KIND) == "exceeds-hbm"
    # no committed threshold: nothing below capacity is flagged
    assert costs.starvation(cap - 1, costs.V5E_KIND) is None
    monkeypatch.setenv("APEX_STARVE_HBM_BYTES", str(2 ** 30))
    assert costs.starvation(2 ** 30 + 1,
                            costs.V5E_KIND) == "starvation-risk"
    assert costs.starvation(2 ** 30 - 1, costs.V5E_KIND) is None
    assert costs.starvation(None, costs.V5E_KIND) is None
    assert costs.starvation(0, costs.V5E_KIND) is None


# ------------------------------------------- tiles VMEM validation hook


def test_tiles_model_vmem_and_compare():
    dims = {"rows": 4096, "hidden": 1024}
    model = tiles.model_vmem_bytes("layer_norm", dims, "float32")
    assert isinstance(model, int) and model > 0
    # within the coarse 4x band in either direction
    res = tiles.compare_vmem("layer_norm", dims, "float32", None,
                             xla_bytes=model * 3)
    assert res["within"] is True and res["ratio"] == 3.0
    # order-of-magnitude drift is the failure the hook exists to catch
    res = tiles.compare_vmem("layer_norm", dims, "float32", None,
                             xla_bytes=model * 10)
    assert res["within"] is False
    # either side unable to report -> None, never a crash
    assert tiles.compare_vmem("layer_norm", dims, "float32", None,
                              xla_bytes=None) is None
    assert tiles.compare_vmem("nope", dims, "float32", None,
                              xla_bytes=100) is None


# ------------------------------------------------- ledger inspection CLI


def _cli(*args):
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = ledger.main(list(args))
    return rc, buf.getvalue()


def _seed_ledger(tmp_path, n=3):
    path = str(tmp_path / "ledger.jsonl")
    ids = []
    for i in range(n):
        rec = ledger.append_record(
            "bench" if i else "profile_gpt", "cpu", 0.5, 2,
            path=path, extra={"cost": costs.null_block(),
                              "value": 100.0 + i})
        ids.append(rec)
    return path, ids


def test_ledger_cli_status_tail_show(tmp_path):
    path, ids = _seed_ledger(tmp_path)
    rc, out = _cli("--ledger", path, "status")
    assert rc == 0
    assert "3 record(s)" in out and "schema findings: 0" in out
    rc, out = _cli("--ledger", path, "tail", "2")
    assert rc == 0
    assert len(out.strip().splitlines()) == 2
    assert ids[-1] in out and "value=102.0" in out
    rc, out = _cli("--ledger", path, "show", ids[0])
    assert rc == 0
    shown = json.loads(out)
    assert shown["id"] == ids[0] and shown["harness"] == "profile_gpt"


def test_ledger_cli_missing_and_corrupt(tmp_path):
    rc, out = _cli("--ledger", str(tmp_path / "nope.jsonl"), "status")
    assert rc == 1 and "no ledger" in out
    path, ids = _seed_ledger(tmp_path, n=1)
    rc, out = _cli("--ledger", path, "show", "lg-nonexistent")
    assert rc == 1 and "no record" in out
    with open(path, "a") as f:
        f.write("{truncated\n")
    rc, out = _cli("--ledger", path, "status")
    assert rc == 1 and "CORRUPT" in out


def test_ledger_cli_flags_schema_findings(tmp_path):
    path = str(tmp_path / "ledger.jsonl")
    rec = ledger.make_record("bench", "cpu", 0.5, 2, git="abc", ts=1.0,
                             extra={"cost": {"not": "a block"}})
    with open(path, "w") as f:
        f.write(json.dumps(rec) + "\n")
    rc, out = _cli("--ledger", path, "status")
    assert rc == 1 and "schema findings: 1" in out
    rc, out = _cli("--ledger", path, "show", rec["id"])
    assert rc == 1 and "FINDING" in out


# ------------------------------------------- the disabled-is-free proof


def test_cost_capture_leaves_jaxpr_byte_identical():
    """PR-1 invariant for the attribution layer: running the XLA
    analyses (lower + cost_analysis + memory_analysis + a jaxpr comm
    walk) does not perturb the program it describes — the jaxpr traced
    after a capture is byte-identical to one traced before, and
    identical to a capture-disabled process's trace."""

    def step(params, x):
        h = jnp.tanh(x @ params["w"])
        return {"w": params["w"] - 1e-3 * (h.T @ x)}, h.sum()

    f = jax.jit(step)
    params = {"w": jnp.ones((16, 16), jnp.float32)}
    x = jnp.ones((8, 16), jnp.float32)
    before = str(jax.make_jaxpr(step)(params, x))
    lowered = f.lower(params, x)
    block = costs.capture(lowered=lowered, compiled=lowered.compile(),
                          steps=1, device_kind="cpu")
    costs.comm_from_jaxpr(jax.make_jaxpr(step)(params, x))
    assert block["source"] is not None
    after = str(jax.make_jaxpr(step)(params, x))
    assert before == after


# ------------------------------------------------- overlap_bound (ISSUE 11)


def test_overlap_bound_arithmetic_and_degradation():
    """compute floor vs comm+host: hideable = min, best overlapped
    step = max; absent inputs degrade field-by-field and an all-absent
    call returns None (the stamp only exists where it says
    something)."""
    assert costs.overlap_bound(1.0) is None
    ob = costs.overlap_bound(2.0, host_ms=0.5, comm_ms=1.0)
    assert ob["comm_host_ms"] == pytest.approx(1.5)
    assert ob["hideable_ms"] == pytest.approx(1.5)   # min(2.0, 1.5)
    assert ob["bound_step_ms"] == pytest.approx(2.0)  # max
    ob = costs.overlap_bound(None, host_ms=0.5)
    assert ob["compute_floor_ms"] is None
    assert ob["comm_ms"] is None
    assert ob["comm_host_ms"] == pytest.approx(0.5)
    assert ob["hideable_ms"] is None and ob["bound_step_ms"] is None


def test_build_stamps_overlap_bound_and_validates():
    peak = costs.V5E_PEAK_BF16_FLOPS
    block = costs.build(xla_flops=peak * 2e-3, steps=4,
                        device_kind=costs.V5E_KIND,
                        source="compiled", host_ms=0.7, comm_ms=0.3)
    ob = block["overlap_bound"]
    assert ob["compute_floor_ms"] == pytest.approx(2.0)
    assert ob["comm_host_ms"] == pytest.approx(1.0)
    assert ob["hideable_ms"] == pytest.approx(1.0)
    assert ob["bound_step_ms"] == pytest.approx(2.0)
    assert costs.validate(block) == []
    # a block WITHOUT the stamp stays clean (optional, like
    # comm_compression — legacy records keep validating)
    assert costs.validate(costs.build(steps=1)) == []


def test_attach_overlap_onto_existing_block():
    block = costs.build(xla_flops=costs.V5E_PEAK_BF16_FLOPS * 1e-3,
                        steps=2, device_kind=costs.V5E_KIND, source="compiled")
    out = costs.attach_overlap(block, host_ms=2.5)
    assert out["overlap_bound"]["hideable_ms"] == pytest.approx(1.0)
    assert out["overlap_bound"]["bound_step_ms"] == pytest.approx(2.5)
    assert "overlap_bound" not in block  # attach copies, never mutates
    # null-degraded base (CPU smoke): the measured host side survives
    out = costs.attach_overlap(costs.null_block(), host_ms=0.2)
    assert out["overlap_bound"]["host_ms"] == pytest.approx(0.2)
    assert out["overlap_bound"]["hideable_ms"] is None
    assert costs.validate(out) == []
    # nothing measured -> block returned untouched, no stamp
    assert "overlap_bound" not in costs.attach_overlap(
        costs.null_block())


def test_overlap_bound_validate_teeth():
    block = costs.build(steps=1, host_ms=1.0)
    good = costs.validate(block)
    assert good == []
    bad = dict(block, overlap_bound="fast")
    assert any("not a dict" in p for p in costs.validate(bad))
    bad = dict(block, overlap_bound=dict(block["overlap_bound"],
                                         host_ms=-1))
    assert any("host_ms" in p for p in costs.validate(bad))
    missing = dict(block["overlap_bound"])
    del missing["comm_host_ms"]
    bad = dict(block, overlap_bound=missing)
    assert any("comm_host_ms" in p for p in costs.validate(bad))
    # ledger.validate_record carries the same teeth via costs.validate
    rec = ledger.make_record("x", "cpu", 0.1, 2, extra={"cost": bad})
    assert any("comm_host_ms" in p for p in ledger.validate_record(rec))
