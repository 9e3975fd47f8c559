"""Weights from the seed, made on the device in one jitted call whose
PRNG key is an ARGUMENT. ``serving.model.init_gpt_params(cfg, seed)`` and
``pretrain.main`` close over the seed, so every new seed is a new program
and a compile (45 s for gpt2-large, my chip run, PR 24); the same key
gives the same numbers either way."""

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def gpt_params(cfg, seed):
    """``GPTModel(cfg).init`` under ``PRNGKey(seed)`` on one device, as
    ``init_gpt_params`` and ``pretrain.main`` (dp = tp = 1) make it."""
    from apex_tpu.transformer.parallel_state import DATA_AXIS, TENSOR_AXIS
    from apex_tpu.transformer.testing import GPTModel

    model = GPTModel(cfg)
    mesh = Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                (DATA_AXIS, TENSOR_AXIS))
    ids = jnp.zeros((1, min(8, cfg.max_position_embeddings)), jnp.int32)
    return jax.jit(jax.shard_map(
        lambda key, i: model.init(key, i, i, None)["params"], mesh=mesh,
        in_specs=(P(), P()), out_specs=P(), check_vma=False))(
            jax.random.PRNGKey(seed), ids)
