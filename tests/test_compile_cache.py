"""Persistent compile cache (apex_tpu.compile_cache).

The contract under test: a program compiled by ONE process must be
served from the persistent cache to a SECOND, cold process — and the
telemetry block proving it must be well-formed in the bench JSON line
and the run ledger, with the cache on and off. The cache is placed from
outside with JAX's own ``JAX_COMPILATION_CACHE_DIR``; no code may set
the directory or disable the cache then.

The two-process demonstration uses the real bench program (bench.py in
``APEX_WARM_ONLY=1`` CPU-smoke mode — the same make_one_step scan the
scored run measures, at smoke shapes).
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from bench import _last_json  # noqa: E402  (the ONE driver-line parser)


def _last_rec(text):
    return _last_json(text)[1]


def _spawn_bench(cache_dir, extra_env, args=(), timeout=420):
    env = dict(os.environ)
    # isolate from any ambient telemetry/ledger knobs (the caller's
    # extra_env below re-adds what the test actually wants)
    for k in ("APEX_TELEMETRY", "APEX_TELEMETRY_LEDGER",
              "JAX_COMPILATION_CACHE_DIR"):
        env.pop(k, None)
    env.update(APEX_BENCH_SMOKE="1", JAX_PLATFORMS="cpu", **extra_env)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench.py"), *args],
        capture_output=True, text=True, timeout=timeout, env=env)
    return out


def test_second_process_served_from_persistent_cache(tmp_path):
    """Process A compiles the bench-shaped program into a fresh cache
    dir; a cold process B gets every program — including the big step
    scan — as a cache hit, counted in the new telemetry."""
    cache = tmp_path / "cache"
    out1 = _spawn_bench(cache, {"APEX_WARM_ONLY": "1"})
    assert out1.returncode == 0, out1.stderr[-2000:]
    rec1 = _last_rec(out1.stdout)
    assert rec1 and rec1.get("warm_only") is True, out1.stdout[-2000:]
    assert rec1["warm"]["step_scan"]["cached"] is False  # cold compile
    assert rec1["compile_cache"]["enabled"] is True
    assert rec1["compile_cache"]["misses"] > 0

    out2 = _spawn_bench(cache, {"APEX_WARM_ONLY": "1"})
    assert out2.returncode == 0, out2.stderr[-2000:]
    rec2 = _last_rec(out2.stdout)
    assert rec2["warm"]["step_scan"]["cached"] is True, rec2
    cc = rec2["compile_cache"]
    assert cc["hits"] > 0, cc
    assert cc["misses"] == 0, cc  # identical process: every key warm
    assert cc["dir"] == str(cache)
    assert cc["warm_age_s"] is not None and cc["warm_age_s"] >= 0


def test_bench_json_carries_compile_cache_block_on_and_off(
        tmp_path, shared_smoke_cache_dir):
    """The scored smoke line (exactly ONE JSON line — the driver
    contract) carries a well-formed compile_cache block with the cache
    placed from outside (via the ``--smoke`` CLI alias) and with no
    cache at all (``APEX_COMPILE_CACHE=0``, nothing placed).
    The ON leg compiles into the suite-wide shared smoke cache
    (tests/conftest.py) — the chaos deep-path tests then reuse the
    executable instead of re-compiling it (fast-tier budget); the
    assertions here are cache-state-agnostic (hits + misses > 0)."""
    from apex_tpu.telemetry import ledger

    for on in (True, False):
        out = _spawn_bench(
            shared_smoke_cache_dir if on else None,
            {"APEX_BENCH_INNER": "1", "APEX_COMPILE_CACHE": "0",
             "APEX_TELEMETRY_LEDGER": str(tmp_path / "ledger.jsonl")},
            args=("--smoke",))
        assert out.returncode == 0, out.stderr[-2000:]
        lines = [l for l in out.stdout.splitlines() if l.startswith("{")]
        assert len(lines) == 1, out.stdout[-2000:]
        rec = json.loads(lines[0])
        assert "error" not in rec, rec
        cc = rec["compile_cache"]
        assert set(cc) == {"enabled", "dir", "hits", "misses",
                           "warm_age_s"}, cc
        assert cc["enabled"] is on
        if on:
            # the directory is the one JAX read from the environment:
            # APEX_COMPILE_CACHE=0 never overrides an outside placement
            assert cc["dir"] == shared_smoke_cache_dir
            assert cc["hits"] + cc["misses"] > 0
        else:
            assert cc["dir"] is None
            assert cc["hits"] == 0 and cc["misses"] == 0
            assert cc["warm_age_s"] is None
        # ...and the ledger record carrying the block validates.
        # warm_age_s is wall-clock (the two snapshots are taken ms
        # apart), so compare the block modulo that field.
        records = ledger.read_ledger(str(tmp_path / "ledger.jsonl"))
        mine = [r for r in records if r["id"] == rec["ledger_id"]]
        assert mine, records
        lcc = dict(mine[0]["compile_cache"])
        age = lcc.pop("warm_age_s")
        assert lcc == {k: v for k, v in cc.items() if k != "warm_age_s"}
        assert age is None or age >= 0
        assert ledger.validate_record(mine[0]) == []


def test_ledger_validates_compile_cache_block():
    """Schema teeth: a malformed compile_cache block (which could
    silently claim a number was compile-free) is a finding."""
    from apex_tpu.telemetry import ledger

    def rec_with(cc):
        return ledger.make_record("bench", "cpu", 1.0, 16,
                                  extra={"compile_cache": cc})

    good = {"enabled": True, "dir": "/x", "hits": 3, "misses": 0,
            "warm_age_s": 12.5}
    assert ledger.validate_record(rec_with(good)) == []
    off = {"enabled": False, "dir": None, "hits": 0, "misses": 0,
           "warm_age_s": None}
    assert ledger.validate_record(rec_with(off)) == []

    for bad in (
        "yes",                                      # not a dict
        dict(good, enabled="yes"),                  # enabled not bool
        dict(good, hits=-1),                        # negative counter
        dict(good, misses=None),                    # missing counter
        dict(good, dir=7),                          # dir not a string
        dict(good, warm_age_s="old"),               # age not numeric
    ):
        assert ledger.validate_record(rec_with(bad)) != [], bad


def test_activate_placement_rules(tmp_path, monkeypatch):
    """In-process unit surface of the placement rule. With the cache
    placed from outside, activate() touches neither the directory nor
    the enable switch; with nothing placed, APEX_COMPILE_CACHE=0 leaves
    the process without a cache and anything else puts it at the fixed
    in-checkout path. State is restored so the rest of the suite is
    unaffected."""
    import jax

    from apex_tpu import compile_cache as cc

    updates = []
    real_update = jax.config.update

    def spy(name, value):
        updates.append(name)
        real_update(name, value)

    prev_dir = jax.config.jax_compilation_cache_dir
    # a file that ran earlier in this worker may have placed the cache
    # (any ServingEngine does): start from a process without one
    real_update("jax_compilation_cache_dir", None)
    monkeypatch.setattr(jax.config, "update", spy)
    try:
        # nothing placed + the opt-out: no cache, nothing touched
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("APEX_COMPILE_CACHE", "0")
        assert cc.target_dir() is None
        assert cc.activate() is False and updates == []
        snap = cc.snapshot()
        assert snap == {"enabled": False, "dir": None,
                        "hits": snap["hits"], "misses": snap["misses"],
                        "warm_age_s": None}

        # nothing placed, no opt-out: the fixed in-checkout directory
        monkeypatch.delenv("APEX_COMPILE_CACHE")
        monkeypatch.setattr(cc, "default_dir",
                            lambda: str(tmp_path / "fixed"))
        assert cc.activate() is True
        assert cc.snapshot()["dir"] == str(tmp_path / "fixed")
        assert os.path.isdir(tmp_path / "fixed")  # created on activation
        assert "jax_compilation_cache_dir" in updates

        # placed from outside (JAX read the variable at import; mimic
        # that read): the directory and the switch are left alone, even
        # under APEX_COMPILE_CACHE=0
        outside = str(tmp_path / "outside")
        real_update("jax_compilation_cache_dir", outside)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        monkeypatch.setenv("APEX_COMPILE_CACHE", "0")
        del updates[:]
        assert cc.target_dir() == outside
        assert cc.activate() is True
        assert "jax_compilation_cache_dir" not in updates
        assert "jax_enable_compilation_cache" not in updates
        assert cc.snapshot()["dir"] == outside
    finally:
        real_update("jax_compilation_cache_dir", prev_dir)
        cc._reset_for_tests()
