"""Shared kernel-dispatch env knobs for the step-level A/B harnesses.

Consumed by ``benchmarks/profile_gpt.py``:

* ``APEX_ATTN_IMPL={flash|rows}`` — process-wide attention kernel
  (``ops.attention.set_default_impl``).
* ``APEX_LN_PALLAS={1|0}`` — pin every FusedLayerNorm to the Pallas
  row kernel (1) or the jnp path (0) (module-level ``USE_PALLAS``).
* ``APEX_FUSED_LM_HEAD={1|0}`` — pin the loss head to the Pallas fused
  linear-CE kernel / the materialized path
  (``TransformerConfig.fused_lm_head``); pass ``fused_head_requested()``
  into the config, with ``fused_lm_head_interpret`` True off-TPU so CPU
  smokes exercise it.
* ``APEX_REMAT={selective|full|none}`` — activation recompute on the
  trunk (``TransformerConfig.recompute_granularity``): the queued MFU
  lever for batch sizes the no-remat backward can't fit/compile;
  ``none`` pins recompute OFF.

Every knob here is a process-wide *pin*: set, it overrides the
per-shape dispatch table (``apex_tpu.dispatch``); UNSET, the resolver
returns the unpinned marker (None) and the consuming call site
consults the table at trace time, falling back to the built-in
measured default on a miss. ``APEX_DISPATCH=off`` disables the table
itself (the A/B harnesses set it so baseline rungs measure the
built-in defaults, not yesterday's table).
"""

import os


def remat_granularity():
    """Validated APEX_REMAT value (None when unset — the unpinned
    marker: the trunk then consults the dispatch table; "none" is the
    explicit recompute-OFF pin)."""
    v = os.environ.get("APEX_REMAT") or None
    if v not in (None, "selective", "full", "none"):
        raise ValueError(
            f"APEX_REMAT={v!r}: want 'selective', 'full' or 'none'")
    return v


def apply_dispatch_knobs():
    """Apply the process-wide knobs (attention impl, layernorm kernel).
    Call before building the model."""
    if os.environ.get("APEX_ATTN_IMPL"):
        from apex_tpu.ops.attention import set_default_impl

        set_default_impl(os.environ["APEX_ATTN_IMPL"])
    ln = os.environ.get("APEX_LN_PALLAS")
    if ln in ("0", "1"):
        # NB: must be the real module's setter — the package re-exports
        # the fused_layer_norm FUNCTION under the module's name, so
        # `from apex_tpu.normalization import fused_layer_norm as m;
        # m.USE_PALLAS = True` set a function attribute and silently
        # never flipped the dispatch (the pre-round-6 bug this replaced)
        from apex_tpu.normalization.fused_layer_norm import set_use_pallas

        set_use_pallas(ln == "1")


def fused_head_requested():
    """Tri-state APEX_FUSED_LM_HEAD: True ("1"), False ("0"), or None
    (unset — the head consults the dispatch table)."""
    v = os.environ.get("APEX_FUSED_LM_HEAD")
    if v == "1":
        return True
    if v == "0":
        return False
    return None
