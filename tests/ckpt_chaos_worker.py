"""Subprocess driver for the checkpoint chaos twins
(tests/test_checkpoint_chaos.py): save a deterministic state at each
requested step through the REAL DurableCheckpointer, under whatever
``APEX_FAULT_PLAN`` rides the environment — the SIGKILL/corruption/
stale-manifest faults fire inside the real commit path, and the parent
test asserts the on-disk durability invariants afterwards.

Usage: JAX_PLATFORMS=cpu python tests/ckpt_chaos_worker.py <dir> <step>
[<step> ...]
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import jax.numpy as jnp  # noqa: E402

from apex_tpu import checkpoint as ckpt  # noqa: E402


def state_at(step):
    """Deterministic per-step state so the parent can assert the PRIOR
    checkpoint survived bitwise."""
    base = jnp.arange(32, dtype=jnp.float32).reshape(8, 4)
    return {"w": base + float(step),
            "emb": (base[:, :2] * step).astype(jnp.bfloat16),
            "count": jnp.asarray(step, jnp.int32)}


def main():
    directory = sys.argv[1]
    steps = [int(s) for s in sys.argv[2:]]
    writer = ckpt.DurableCheckpointer(directory, max_to_keep=10,
                                      async_save=False)
    for step in steps:
        writer.save(step, state_at(step), meta={"step": step})
        print(f"committed {step}", flush=True)
    print("DONE", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
