"""The judge of ``correct``: the training stack's full forward,
``GPTModel.apply`` in float32 under
``jax.default_matmul_precision("highest")`` - no cache, no amp, no scan,
no serving code (the oracle of ``tests/test_serving.py::_oneshot_logits``,
copied). It shares ``standalone_transformer_lm.py`` with the trainer; a
pure ``jax.numpy`` reference is owed (PERF.md, Open questions)."""

import dataclasses
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from perf import weights


def _float32(cfg):
    return dataclasses.replace(cfg, fp16=False, bf16=False)


def _one_device_mesh():
    from apex_tpu.transformer.parallel_state import DATA_AXIS, TENSOR_AXIS

    return Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                (DATA_AXIS, TENSOR_AXIS))


def _shard_mapped(fn, n_args):
    return jax.jit(jax.shard_map(
        fn, mesh=_one_device_mesh(), in_specs=(P(),) * n_args,
        out_specs=P(), check_vma=False))


def best_and_chosen(cfg, params, ids):
    """For one sequence ``ids [seq]``: at every position the oracle's
    best next-token logit and its logit for the token that really
    follows (``ids`` shifted by one). Two float32 ``[seq - 1]`` arrays;
    the logits never leave the device."""
    from apex_tpu.transformer.testing import GPTModel

    model = GPTModel(_float32(cfg))

    def judge(p, i, po):
        out = model.apply({"params": p}, i, po, None)[0, :-1]
        out = out.astype(jnp.float32)
        chosen = jnp.take_along_axis(out, i[0, 1:, None], axis=-1)[:, 0]
        return jnp.max(out, axis=-1), chosen

    ids = jnp.asarray(ids, jnp.int32)[None, :]
    pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :]
    with jax.default_matmul_precision("highest"):
        best, chosen = _shard_mapped(judge, 3)(params, ids, pos)
    return np.asarray(best), np.asarray(chosen)


def initial_loss(cfg, seed, ids, labels):
    """Mean float32 loss of the weights ``pretrain.main`` starts from
    (``GPTModel.init`` under ``PRNGKey(seed)``) on its fixed batch."""
    from apex_tpu.transformer.testing import GPTModel

    ids = jnp.asarray(ids, jnp.int32)
    labels = jnp.asarray(labels, jnp.int32)
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32),
                           ids.shape)
    params = weights.gpt_params(cfg, seed)
    model = GPTModel(_float32(cfg))
    with jax.default_matmul_precision("highest"):
        per_token = _shard_mapped(
            lambda p, i, po, la: model.apply({"params": p}, i, po, None, la),
            4)(params, ids, pos, labels)
    return float(jnp.mean(per_token.astype(jnp.float32)))


def bf16_step(value):
    """The distance between neighbouring bfloat16 numbers at ``value``
    (8 bits of significand)."""
    return 2.0 ** (math.floor(math.log2(abs(value))) - 7)
