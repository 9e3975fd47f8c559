#!/bin/bash
# Reference: torch.distributed.launch --nproc_per_node=2 → the multiproc
# launcher spawns one process per (virtual) host and wires the
# jax.distributed coordinator env. Two ranks on one host are a CPU
# rehearsal (a chip belongs to one process); on a TPU host run
# distributed_data_parallel.py directly — one process drives every chip.
JAX_PLATFORMS=cpu exec python -m apex_tpu.parallel.multiproc --nproc 2 \
    "$(dirname "$0")/distributed_data_parallel.py"
