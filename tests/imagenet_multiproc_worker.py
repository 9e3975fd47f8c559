"""2-process worker for the multi-host ImageNet example test (launched by
``python -m apex_tpu.parallel.multiproc`` from tests/test_multiproc.py).

Each process owns 1 virtual CPU device; main_amp's mesh spans both, so the
DDP grad psum and the SyncBatchNorm Welford psum run across process
boundaries — the DCN analog of the reference's 2-GPU L1 runs.
"""

import os
import sys

import jax

# CPU backend BEFORE distributed init (two ranks cannot share a chip)
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))

import numpy as np  # noqa: E402

from examples.imagenet.main_amp import main  # noqa: E402


def run():
    loss = main(["--synthetic", "--arch", "resnet18", "--steps", "3",
                 "-b", "8", "--image-size", "32", "--num-classes", "10",
                 "--opt-level", "O2",
                 "--checkpoint", os.path.join(
                     os.environ.get("TMPDIR", "/tmp"),
                     f"imagenet_mp_{os.getpid()}.pkl")])
    assert np.isfinite(loss), loss
    assert jax.process_count() == 2
    print(f"IMAGENET_MULTIPROC_OK rank={jax.process_index()} "
          f"loss={loss:.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
