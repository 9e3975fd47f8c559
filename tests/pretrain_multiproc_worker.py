"""2-process worker for the multi-host transformer pretrain test
(launched by ``python -m apex_tpu.parallel.multiproc`` from
tests/test_multiproc.py). Each process owns 1 virtual CPU device; the
(dp=2, tp=1) mesh spans both, so grad pmean and found_inf pmax cross
process boundaries."""

import os
import sys

import jax

# CPU backend BEFORE distributed init (two ranks cannot share a chip)
jax.config.update("jax_platforms", "cpu")

sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__),
                                                "..")))

import numpy as np  # noqa: E402


def run():
    from apex_tpu.transformer.testing import global_vars
    from examples.transformer.pretrain import main

    tp = os.environ.get("APEX_TEST_TP", "1")  # tp=2 -> TP over DCN
    global_vars.destroy_global_vars()
    out = main(["--model", "gpt", "--num-layers", "2", "--hidden-size",
                "64", "--num-attention-heads", "4",
                "--max-position-embeddings", "64", "--seq-length", "32",
                "--micro-batch-size", "2", "--vocab-size", "256",
                "--make-vocab-size-divisible-by", "32",
                "--tensor-model-parallel-size", tp,
                "--optimizer", "adam", "--lr", "1e-3", "--bf16",
                "--train-iters", "4", "--log-interval", "2"])
    assert np.isfinite(out["loss"]), out
    assert jax.process_count() == 2
    print(f"PRETRAIN_MULTIPROC_OK rank={jax.process_index()} "
          f"loss={out['loss']:.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(run())
