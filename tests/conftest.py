"""Test harness configuration.

Multi-chip behaviour is tested on a virtual 8-device CPU mesh
(``--xla_force_host_platform_device_count=8``) — the TPU analog of the
reference's single-node multi-process NCCL test base
(apex/transformer/testing/distributed_test_base.py:27-45).

The suite is held to the CPU backend whether or not ``JAX_PLATFORMS`` is
set (``jax.config.update("jax_platforms", "cpu")``). XLA_FLAGS must be
set before the backend initializes.
"""

import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"
if "xla_backend_optimization_level" not in _flags:
    # tests assert semantics, not speed: the CPU backend's O2 pipeline
    # roughly doubles suite compile time for identical pass/fail results
    _flags += " --xla_backend_optimization_level=0"
os.environ["XLA_FLAGS"] = _flags.strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}"
)


import pytest  # noqa: E402


@pytest.fixture(scope="session")
def shared_smoke_cache_dir(tmp_path_factory):
    """ONE persistent compile cache for every subprocess smoke-harness
    deep path in the suite (test_compile_cache's scored-line test seeds
    it; test_resilience's chaos deep-path tests reuse it; ISSUE 14
    extended it to test_overlap's profile_overlap smoke CLI — the PR 6
    fast-tier rule: deeper cache sharing, not demotion) — each smoke
    program is identical across its users, so each re-compile after
    the first was pure fast-tier wall time (CLAUDE.md ~5 min budget).
    Tests that assert cold-vs-warm cache SEMANTICS keep their own
    fresh dirs."""
    return str(tmp_path_factory.mktemp("shared_smoke_compile_cache"))


_CBL_MODULE = None


def run_check_bench_labels(*args):
    """Drive tools/check_bench_labels.py main() IN-PROCESS (module
    loaded once per session) and return a subprocess.run-shaped
    ``SimpleNamespace(returncode, stdout, stderr)``. The one shared
    implementation of the fast-tier trim that replaced ~20 × ~3-4s
    checker subprocesses (test_bench_labels keeps a single real CLI
    invocation for the script surface)."""
    import contextlib
    import importlib.util
    import io
    import types

    global _CBL_MODULE
    if _CBL_MODULE is None:
        tool = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "check_bench_labels.py")
        spec = importlib.util.spec_from_file_location(
            "check_bench_labels", tool)
        _CBL_MODULE = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_CBL_MODULE)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        try:
            rc = _CBL_MODULE.main(list(args))
        except SystemExit as e:  # argparse error paths
            rc = e.code if isinstance(e.code, int) else 1
    return types.SimpleNamespace(returncode=rc, stdout=buf.getvalue(),
                                 stderr="")
