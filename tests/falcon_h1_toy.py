"""Shared by the Falcon-H1 tests: the plain reference (loaded from the
benchmark's file, which imports nothing from ``apex_tpu``) and a toy
configuration with every mechanism of the real one at the published
ratios (groups of query heads over KV heads, mixer heads in two groups
of B and C, a state wider than a head, chunks shorter than a sequence,
every multiplier of the published config)."""

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "falcon_h1_reference",
    os.path.join(_HERE, os.pardir, "perf", "references", "falcon_h1.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

TOY = dict(
    vocab_size=256, num_hidden_layers=2, max_position_embeddings=4096,
    hidden_size=64, num_attention_heads=5, num_key_value_heads=1,
    head_dim=16, intermediate_size=96, mamba_d_ssm=32, mamba_n_heads=4,
    mamba_d_head=8, mamba_d_state=16, mamba_n_groups=2, mamba_chunk_size=8,
    mamba_d_conv=4, rope_theta=1e4)


def toy_config(**changes):
    from apex_tpu.serving.falcon_h1 import FalconH1Config

    return FalconH1Config(**{**TOY, **changes})


def toy_params(cfg, seed=3, std=0.1):
    """Seeded weights; the norm gains are drawn too (the deployed ones
    are 1, which would hide a norm applied in the wrong place)."""
    import jax

    from apex_tpu.serving.falcon_h1 import init_params

    params = init_params(cfg, seed, std=std)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for lp in params["layers"]:
        for name in ("input_norm", "ff_norm", "gate_norm"):
            lp[name] = 1.0 + 0.2 * jax.random.normal(next(keys),
                                                     lp[name].shape)
    return params


def ref_config(cfg):
    """The dict the reference reads."""
    return cfg.to_dict()
