"""apex_tpu — a TPU-native training-accelerator framework.

A from-scratch JAX/XLA/Pallas re-design of the capability surface of NVIDIA
Apex (reference: /root/reference, gilshm/apex). Same layer map (see SURVEY.md):

  L1  multi_tensor_apply   — fused flat-buffer update substrate
  L2  amp / fp16_utils     — mixed precision (O0–O3 policies, dynamic loss scale)
  L3  optimizers / normalization / fused_dense / mlp / RNN — fused modules
  L4  parallel             — data parallel (psum over mesh axes) + SyncBatchNorm
  L5  transformer          — TP/SP/PP model parallelism over a jax.sharding.Mesh
  L6  contrib              — xentropy, fmha, multihead_attn, ZeRO optimizers, …

Unlike the reference (eager torch + CUDA extensions), everything here is
functional and jit-first: dtype policies instead of monkey-patching, sharding
specs + XLA collectives instead of NCCL process groups, XLA fusion + Pallas
kernels instead of hand-written CUDA.
"""

import logging as _logging
import os as _os


class RankInfoFormatter(_logging.Formatter):
    """Rank-aware log formatter (reference: apex/__init__.py:27-40)."""

    def format(self, record):
        import jax

        try:
            rank = jax.process_index()
            world = jax.process_count()
        except Exception:  # pre-init
            rank, world = 0, 1
        record.rank_info = f"[{rank}/{world}]"
        return super().format(record)


_logger = _logging.getLogger(__name__)
# apexlint: disable=APX001,APX002 — logging handlers must be installed
# before any import-time log line; a one-time package-init read, not a
# trace-time knob (the only sanctioned import-time env read)
if not _logger.handlers and _os.environ.get("APEX_TPU_VERBOSE_LOGGING", "0") == "1":
    _handler = _logging.StreamHandler()
    _handler.setFormatter(
        RankInfoFormatter("%(asctime)s %(rank_info)s %(name)s %(levelname)s: %(message)s")
    )
    _logger.addHandler(_handler)

from apex_tpu import amp  # noqa: E402,F401
from apex_tpu import multi_tensor_apply  # noqa: E402,F401
from apex_tpu import optimizers  # noqa: E402,F401
from apex_tpu import normalization  # noqa: E402,F401

__version__ = "0.3.0"  # keep in sync with pyproject.toml


def __getattr__(name):
    # Lazy import of the heavier sub-packages.
    import importlib

    if name in (
        "parallel",
        "transformer",
        "contrib",
        "fp16_utils",
        "fused_dense",
        "mlp",
        "RNN",
        "ops",
        "checkpoint",
        "telemetry",
    ):
        return importlib.import_module(f"apex_tpu.{name}")
    raise AttributeError(f"module 'apex_tpu' has no attribute {name!r}")
