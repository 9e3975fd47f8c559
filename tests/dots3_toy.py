"""Shared by the dots3-note tests: the plain reference (loaded from the
benchmark's file, which imports nothing from ``apex_tpu``) and a toy
configuration with every mechanism of the real one: the published layer
order (a dense full layer, then full, three sliding, full), full and
sliding layers of different head counts and ranks, an indexer whose
``index_topk`` (8) and a window (5: odd, as 513 is) SHORTER than the
sequences the tests run, so the selection cuts and the ring wraps; 4 of
16 experts held + a shared one."""

import importlib.util
import os

_HERE = os.path.dirname(os.path.abspath(__file__))
_spec = importlib.util.spec_from_file_location(
    "dots3_note_reference",
    os.path.join(_HERE, os.pardir, "perf", "references", "dots3_note.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

FULL, SLIDING = "full_attention", "sliding_attention"
TOY = dict(
    vocab_size=512, max_position_embeddings=4096,
    layer_types=(FULL, FULL, SLIDING, SLIDING, SLIDING, FULL),
    hidden_size=128,
    num_attention_heads=8, q_lora_rank=64, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16, rope_theta=8e7,
    index_n_heads=4, index_head_dim=16, index_topk=8,
    swa_num_attention_heads=4, swa_q_lora_rank=64, swa_kv_lora_rank=64,
    swa_qk_nope_head_dim=24, swa_qk_rope_head_dim=8, swa_v_head_dim=16,
    swa_rope_theta=5e4, sliding_window_size=5,
    intermediate_size=256, moe_intermediate_size=64, n_routed_experts=16,
    num_experts_per_tok=4, held_experts=(4, 4))


def toy_config(**changes):
    from apex_tpu.serving.dots3 import Dots3Config

    return Dots3Config(**{**TOY, **changes})


def toy_params(cfg, seed=3, std=0.05):
    """Seeded weights; the norm gains and the index key's LayerNorm bias
    are drawn too (the deployed ones are 1 and 0, which would hide a norm
    applied in the wrong place), and the indexer's matrices are larger
    (its scores then lie apart by more than float32 rounding)."""
    import jax

    from apex_tpu.serving.dots3 import init_params

    params = init_params(cfg, seed, std=std)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 128))
    for lp in params["layers"]:
        for name in ("attn_norm", "ffn_norm", "q_norm", "kv_norm",
                     "idx_k_gain"):
            if name in lp:
                lp[name] = 1.0 + 0.2 * jax.random.normal(next(keys),
                                                         lp[name].shape)
        if "idx_k_bias" in lp:
            lp["idx_k_bias"] = 0.2 * jax.random.normal(
                next(keys), lp["idx_k_bias"].shape)
            for name in ("idx_wq", "idx_ww"):
                lp[name] = lp[name] * 8
    return params
