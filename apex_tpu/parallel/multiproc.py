"""Per-host process launcher for multi-host TPU jobs.

Capability port of apex.parallel.multiproc (reference:
apex/parallel/multiproc.py:12-35 — spawns one training process per GPU with
RANK/WORLD_SIZE env). TPU analog: one process per *host* (JAX owns all
local chips per process). jax reads only ``JAX_COORDINATOR_ADDRESS`` from
the environment, so rank/world-size travel in APEX_TPU_* vars and spawned
scripts call ``init_distributed()`` (which passes them to
``jax.distributed.initialize`` explicitly).

A chip belongs to one process at a time and ONE process drives all the
chips of its host, so several ranks on one host are only a CPU
rehearsal of a multi-host job: the launcher refuses ``--nproc`` > 1
unless the children are held to the CPU (``JAX_PLATFORMS=cpu``) —
otherwise the second child would fail or hang waiting for the chip the
first one holds. On a TPU host run the script directly (one process,
every local chip in its mesh). The launcher itself never imports jax.

Usage:
    JAX_PLATFORMS=cpu python -m apex_tpu.parallel.multiproc [--nproc N] \
        script.py args
and in script.py:
    from apex_tpu.parallel.multiproc import init_distributed
    init_distributed()   # no-op when not launched by multiproc
"""

import os
import subprocess
import sys


def init_distributed():
    """Initialize jax.distributed from the launcher's environment.

    Reads APEX_TPU_{COORDINATOR,NUM_PROCESSES,PROCESS_ID} (set by ``main``)
    and calls ``jax.distributed.initialize`` with explicit arguments — jax
    has no generic env-var cluster detection outside Slurm/K8s/TPU pods.
    Returns True if distributed init ran, False if not under the launcher.
    """
    coord = os.environ.get("APEX_TPU_COORDINATOR")
    if coord is None:
        return False
    import jax

    jax.distributed.initialize(
        coordinator_address=coord,
        num_processes=int(os.environ["APEX_TPU_NUM_PROCESSES"]),
        process_id=int(os.environ["APEX_TPU_PROCESS_ID"]),
    )
    return True


def docstring_hack():
    """Retained for parity with the reference's module shape."""


def main():
    argv = sys.argv[1:]
    nproc = 2
    if argv and argv[0] == "--nproc":
        nproc = int(argv[1])
        argv = argv[2:]
    if not argv:
        print(__doc__)
        sys.exit(1)
    if nproc > 1 and os.environ.get("JAX_PLATFORMS") != "cpu":
        sys.exit(
            f"multiproc: --nproc {nproc} starts {nproc} processes on "
            "this host, but a chip belongs to one process and one "
            "process drives all local chips: set JAX_PLATFORMS=cpu for "
            "the CPU rehearsal, or run the script directly on a TPU "
            "host")
    port = int(os.environ.get("MASTER_PORT", "29500"))
    procs = []
    for rank in range(nproc):
        env = dict(os.environ)
        env.update({
            "APEX_TPU_COORDINATOR": f"127.0.0.1:{port}",
            "APEX_TPU_NUM_PROCESSES": str(nproc),
            "APEX_TPU_PROCESS_ID": str(rank),
            # reference compat names (apex/parallel/multiproc.py:20-27)
            "RANK": str(rank),
            "WORLD_SIZE": str(nproc),
        })
        procs.append(subprocess.Popen([sys.executable] + argv, env=env))
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    sys.exit(rc)


if __name__ == "__main__":
    main()
