"""Multi-host (multi-process) smoke: the multiproc launcher spawns 2
localhost processes that form a jax.distributed cluster over DCN-equivalent
loopback and psum across it (reference:
apex/transformer/testing/distributed_test_base.py:27-78 spawns NCCL
process groups the same way)."""

import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.mark.slow  # two fresh jax processes (~15s); pure jax.distributed
# smoke orthogonal to repo code changes — slow tier keeps it exercised
def test_multiproc_two_process_psum():
    env = dict(os.environ)
    env["MASTER_PORT"] = "29531"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the launcher refuses N ranks on a chip
    out = subprocess.run(
        [sys.executable, "-m", "apex_tpu.parallel.multiproc", "--nproc", "2",
         os.path.join(REPO, "tests", "multiproc_worker.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, (
        f"launcher rc={out.returncode}\nstdout:\n{out.stdout}\n"
        f"stderr:\n{out.stderr}")
    assert out.stdout.count("MULTIPROC_OK") == 2, out.stdout


@pytest.mark.slow
def test_imagenet_example_two_process():
    """The flagship example multi-host: 2 processes x 1 device, global
    mesh, cross-process DDP psum + SyncBatchNorm stats, rank-0 checkpoint
    (the reference's 2-GPU torch.distributed.launch L1 configuration)."""
    env = dict(os.environ)
    env["MASTER_PORT"] = "29541"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the launcher refuses N ranks on a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    out = subprocess.run(
        [sys.executable, "-m", "apex_tpu.parallel.multiproc", "--nproc", "2",
         os.path.join(REPO, "tests", "imagenet_multiproc_worker.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (
        f"rc={out.returncode}\nstdout:\n{out.stdout[-3000:]}\n"
        f"stderr:\n{out.stderr[-3000:]}")
    assert out.stdout.count("IMAGENET_MULTIPROC_OK") == 2, out.stdout


@pytest.mark.slow
@pytest.mark.parametrize("tp,port", [("1", "29543"), ("2", "29545")])
def test_pretrain_example_two_process(tp, port):
    """The transformer pretrain entry multi-host over 2 processes:
    tp=1 -> (dp=2, tp=1): grad pmean + found_inf pmax cross the
    DCN-equivalent loopback; tp=2 -> (dp=1, tp=2): the TENSOR-parallel
    collectives (TP all-reduces, vocab-parallel CE) cross it."""
    env = dict(os.environ)
    env["MASTER_PORT"] = port
    env["APEX_TEST_TP"] = tp
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the launcher refuses N ranks on a chip
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    out = subprocess.run(
        [sys.executable, "-m", "apex_tpu.parallel.multiproc", "--nproc", "2",
         os.path.join(REPO, "tests", "pretrain_multiproc_worker.py")],
        env=env, capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, (
        f"rc={out.returncode}\nstdout:\n{out.stdout[-3000:]}\n"
        f"stderr:\n{out.stderr[-3000:]}")
    assert out.stdout.count("PRETRAIN_MULTIPROC_OK") == 2, out.stdout


@pytest.mark.slow
def test_simple_distributed_example_two_process():
    """The reference's examples/simple/distributed walkthrough, 2-process:
    DDP grad averaging + amp O1 must converge (final loss printed by rank
    0 and well below the ~1.3 starting MSE)."""
    env = dict(os.environ)
    env["MASTER_PORT"] = "29537"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["JAX_PLATFORMS"] = "cpu"  # the launcher refuses N ranks on a chip
    # one device per process: the conftest's 8-device flag would make a
    # 16-device gloo mesh and slow every one of the 500 dispatches
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    out = subprocess.run(
        [sys.executable, "-m", "apex_tpu.parallel.multiproc", "--nproc", "2",
         os.path.join(REPO, "examples", "simple", "distributed",
                      "distributed_data_parallel.py")],
        env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    import re
    m = re.search(r"final loss = ([0-9.]+)", out.stdout)
    assert m, out.stdout
    assert float(m.group(1)) < 1.0
