"""Persistent compile cache (apex_tpu.compile_cache).

The contract under test: a program compiled by ONE process must be
served from the persistent cache to a SECOND, cold process — and the
telemetry block proving it must be well-formed, with the cache on and
off. The cache is placed from outside with JAX's own
``JAX_COMPILATION_CACHE_DIR``; no code may set the directory or disable
the cache then.

The two-process demonstration is a ten-line jitted script that places
the cache through ``compile_cache.activate()``, as the trainer and the
serving engine do, and prints ``compile_cache.snapshot()``.
"""

import json
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

_SCRIPT = """
import json, jax, jax.numpy as jnp
from apex_tpu import compile_cache
compile_cache.activate()
step = jax.jit(lambda w, x: jnp.tanh(x @ w).sum())
step(jnp.ones((64, 64)), jnp.ones((8, 64))).block_until_ready()
print(json.dumps(compile_cache.snapshot()))
"""


def _spawn(cache_dir, **extra_env):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               **extra_env)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if cache_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = str(cache_dir)
    out = subprocess.run([sys.executable, "-c", _SCRIPT],
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_second_process_served_from_persistent_cache(tmp_path):
    """Process A compiles the program into a fresh cache dir; a cold
    process B gets every program as a cache hit, counted in the
    telemetry block."""
    cache = tmp_path / "cache"
    cold = _spawn(cache)
    assert cold["enabled"] is True and cold["dir"] == str(cache)
    assert cold["misses"] > 0 and cold["hits"] == 0

    warm = _spawn(cache)
    assert warm["hits"] > 0, warm
    assert warm["misses"] == 0, warm   # identical process: every key warm
    assert warm["dir"] == str(cache)
    assert warm["warm_age_s"] is not None and warm["warm_age_s"] >= 0


def test_snapshot_block_with_no_cache_and_with_one_placed_from_outside(
        tmp_path):
    """The block is well-formed with no cache at all
    (``APEX_COMPILE_CACHE=0``, nothing placed), and a cache placed from
    outside is used even under ``APEX_COMPILE_CACHE=0``: the opt-out
    never overrides an outside placement. Both blocks validate inside a
    ledger record."""
    from apex_tpu.telemetry import ledger

    off = _spawn(None, APEX_COMPILE_CACHE="0")
    assert off == {"enabled": False, "dir": None, "hits": 0,
                   "misses": 0, "warm_age_s": None}
    placed = _spawn(tmp_path / "outside", APEX_COMPILE_CACHE="0")
    assert set(placed) == set(off)
    assert placed["enabled"] is True
    assert placed["dir"] == str(tmp_path / "outside")
    assert placed["hits"] + placed["misses"] > 0
    for block in (off, placed):
        rec = ledger.make_record("profile_gpt", "cpu", 1.0, 16,
                                 extra={"compile_cache": block})
        assert ledger.validate_record(rec) == []


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
