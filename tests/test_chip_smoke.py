"""chip_smoke.py refuses to stand in for the chip.

The chip check itself only runs through the builder's chip tool; what
the sandbox can pin is everything around it: the dry run walks the same
control flow and says it is a dry run, a run without a TPU fails and
prints no result, the script alone in a directory fails, and a compile cache placed from outside with
``JAX_COMPILATION_CACHE_DIR`` is the one the run uses.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    # one CPU device: the four-chip phase is the trainer phase again
    env.pop("XLA_FLAGS", None)
    return env


def _json_lines(text):
    return [line for line in text.splitlines() if line.startswith("{")]


def test_dry_run_passes_is_marked_and_uses_the_placed_cache(tmp_path):
    cache = tmp_path / "cache"
    env = _env(JAX_COMPILATION_CACHE_DIR=str(cache))
    env.pop("APEX_COMPILE_CACHE", None)
    out = subprocess.run(
        [sys.executable, SMOKE, "--cpu-dry-run", "--out",
         str(tmp_path / "out")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    lines = out.stdout.splitlines()
    assert "DRY RUN" in lines[0] and "platform=cpu" in lines[0]
    final = json.loads(lines[-1])
    assert final == {"ok": True, "dry_run": True,
                     "device": {"platform": "cpu", "kind": "cpu",
                                "count": 1}}
    record = json.load(open(tmp_path / "out" / "chip_smoke.json"))
    assert record["dry_run"] is True
    assert set(record["phases"]) == {"trainer", "server", "server_mimo",
                                     "server_axk1", "kernels"}
    assert record["phases"]["trainer"]["compiles_after_warmup"] == 0
    for engine in ("default", "jnp"):
        assert record["phases"]["server"][engine][
            "compiles_after_warmup"] == 0
    agreement = record["phases"]["server"]["agreement"]
    assert agreement["identical"] + len(agreement["bf16_ties"]) > 0
    # the directory is the one JAX read from the environment, and the
    # programs landed there (nowhere in the checkout)
    assert record["compile_cache"]["dir"] == str(cache)
    assert record["compile_cache"]["misses"] > 0
    assert any(name.endswith("-cache") for name in os.listdir(cache))


def test_without_a_tpu_no_result_is_printed():
    out = subprocess.run([sys.executable, SMOKE], env=_env(),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "no TPU" in out.stderr and "platform='cpu'" in out.stderr
    assert _json_lines(out.stdout) == []


def test_alone_in_a_directory_it_fails(tmp_path):
    shutil.copy(SMOKE, tmp_path / "chip_smoke.py")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py", "--cpu-dry-run"],
        cwd=tmp_path, env=_env(PYTHONPATH=""), capture_output=True,
        text=True, timeout=300)
    assert out.returncode != 0
    assert "apex_tpu" in out.stderr
    assert _json_lines(out.stdout) == []
