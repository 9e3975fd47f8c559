"""apex_tpu.ops — Pallas TPU kernels for the hot ops.

The L0 tier of the TPU build: where the reference ships CUDA kernels
(csrc/, contrib/csrc — SURVEY §2.6), this package ships Pallas kernels /
kernel wrappers with XLA-fusion fallbacks. Ops dispatch on the backend so
the same model code runs on the CPU test mesh and on TPU.
"""

from apex_tpu.ops.attention import fused_attention  # noqa: F401
from apex_tpu.ops.context_parallel import (  # noqa: F401
    ring_attention,
    ulysses_attention,
)
from apex_tpu.ops.decode_attention_pallas import (  # noqa: F401
    grouped_decode_attention,
)
from apex_tpu.ops import layer_norm_pallas  # noqa: F401
from apex_tpu.ops import softmax_pallas  # noqa: F401
