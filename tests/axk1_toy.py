"""Shared by the A.X-K1 tests: the plain reference (loaded from the
benchmark's file, which imports nothing from ``apex_tpu``) and a toy
configuration with every mechanism of the real one at the published
ratios (rope = nope / 2 = v / 2, kv rank = 4 x nope, q rank = 3 x kv
rank; YaRN over 16 original positions, so the sequences below run past
them; layer 0 dense, two expert layers of 4 of 16 experts + a shared
one)."""

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "axk1_reference",
    os.path.join(_HERE, os.pardir, "perf", "references", "axk1.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

YARN = dict(beta_fast=32, beta_slow=1, factor=32, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=16, type="yarn")
TOY = dict(
    vocab_size=512, num_hidden_layers=3, max_position_embeddings=4096,
    hidden_size=128, num_attention_heads=8, q_lora_rank=96, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    intermediate_size=256, moe_intermediate_size=64, n_routed_experts=16,
    num_experts_per_tok=4, held_experts=(4, 4),
    rope_scaling=tuple(sorted(YARN.items())))


def toy_config(**changes):
    from apex_tpu.serving.axk1 import AXK1Config

    return AXK1Config(**{**TOY, **changes})


def toy_params(cfg, seed=3, std=0.05):
    """Seeded weights; the norm gains are drawn too (the deployed ones
    are 1, which would hide a norm applied in the wrong place)."""
    import jax

    from apex_tpu.serving.axk1 import init_params

    params = init_params(cfg, seed, std=std)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    for lp in params["layers"]:
        for name in ("attn_norm", "ffn_norm", "q_norm", "kv_norm"):
            lp[name] = 1.0 + 0.2 * jax.random.normal(next(keys),
                                                     lp[name].shape)
    return params
