"""Worker for the multiproc 2-process smoke test (launched by
tests/test_multiproc.py via ``python -m apex_tpu.parallel.multiproc``).

Mirrors what the reference's distributed test base does in each spawned
rank (apex/transformer/testing/distributed_test_base.py:58-78): init the
process group, run one collective, check the result.
"""

import os
import sys

import jax

# Hold this rank to the CPU backend BEFORE distributed init: two
# processes cannot share a chip (same switch as tests/conftest.py).
jax.config.update("jax_platforms", "cpu")

from apex_tpu.parallel.multiproc import init_distributed  # noqa: E402


def main():
    ran = init_distributed()
    assert ran, "worker must be launched by apex_tpu.parallel.multiproc"
    import jax.numpy as jnp

    rank = jax.process_index()
    world = jax.process_count()
    assert world == int(os.environ["APEX_TPU_NUM_PROCESSES"])

    n_local = jax.local_device_count()
    # psum over ALL global devices (2 processes x local devices)
    x = jnp.broadcast_to(jnp.float32(rank + 1), (n_local, 1))
    total = jax.pmap(lambda v: jax.lax.psum(v, "i"), axis_name="i")(x)
    want = sum((r + 1) * n_local for r in range(world))
    got = float(total[0, 0])
    assert got == want, f"psum mismatch: got {got}, want {want}"
    print(f"MULTIPROC_OK rank={rank}/{world} psum={got}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
