"""FusedLayerNorm / FusedRMSNorm.

Capability port of apex.normalization (reference:
apex/normalization/fused_layer_norm.py:16-437; CUDA
csrc/layer_norm_cuda_kernel.cu — warp-shuffle Welford row statistics).

Two implementations, both real (measured head-to-head on TPU — PERF.md §4):
  * this jnp path — XLA fuses the row reductions; the default;
  * ``apex_tpu.ops.layer_norm_pallas`` — a hand-written Pallas row kernel
    (fp32 stats, boundary-only residuals, per-block affine-grad partials),
    selected by setting ``USE_PALLAS = True`` here (or per-call
    ``use_pallas=``) for shapes the kernel supports. LayerNorm is
    HBM-bandwidth-bound, so whichever side wins does so by small margins;
    the dispatch default follows the PERF.md measurement.

Dtype semantics mirror the reference:
  * plain ``FusedLayerNorm``/``FusedRMSNorm``: statistics + affine math in
    fp32, result cast back to input dtype.
  * ``Mixed*`` variants (fused_layer_norm.py:398/420): params are created in
    the input dtype (Megatron-compatible).
"""

import numbers
import os

import jax
import jax.numpy as jnp
from flax import linen as nn

# Process-wide Pallas-kernel preference: tri-state. None (the shipped
# state) = unpinned — the per-shape dispatch table (apex_tpu.dispatch,
# op "layer_norm") is consulted and a miss means the jnp path (the
# PERF.md §4 measured default). True/False (set_use_pallas, or
# benchmarks/_knobs APEX_LN_PALLAS=1) pins the choice above the table.
# Per-call ``use_pallas=`` wins over everything.
USE_PALLAS = None


def set_use_pallas(value):
    """Pin the process-wide Pallas-LN preference (True/False), or un-pin
    with None (the dispatch table then applies again).

    Use THIS, not ``module.USE_PALLAS = ...`` via a package import: the
    package re-exports the ``fused_layer_norm`` FUNCTION under the
    module's name, so ``from apex_tpu.normalization import
    fused_layer_norm as m; m.USE_PALLAS = True`` silently sets an
    attribute on the function and never reaches this module — the knob
    looked flipped while every call still ran the jnp path (caught by
    tests/test_dispatch.py; the round-≤5 APEX_LN_PALLAS step rows were
    affected)."""
    global USE_PALLAS
    if value not in (True, False, None):
        raise ValueError(f"use_pallas must be True/False/None, "
                         f"got {value!r}")
    USE_PALLAS = value


def _normalized_axes(x, normalized_shape):
    if isinstance(normalized_shape, numbers.Integral):
        normalized_shape = (int(normalized_shape),)
    n = len(normalized_shape)
    assert tuple(x.shape[-n:]) == tuple(normalized_shape), (
        f"input tail {x.shape[-n:]} != normalized_shape {normalized_shape}")
    return tuple(range(x.ndim - n, x.ndim)), tuple(normalized_shape)


def _resolve_pallas(x_shape, n_norm_axes, use_pallas, dtype=None):
    """``(use, interpret, block_rows_pref)`` for one call — THE
    dispatch decision.

    Resolution: per-call ``use_pallas`` > module ``USE_PALLAS`` >
    dispatch-table "layer_norm" entry for this (rows, hidden) bucket >
    False (the §4 measured jnp default). All resolutions are
    preferences: shapes the kernel can't handle fall back to jnp.
    A table entry is backend-keyed, so a CPU-measured "pallas" row was
    measured in interpret mode — it runs the same way (``interpret``
    True off-TPU); explicit True still requires a real TPU, unchanged.
    ``block_rows_pref`` is the table entry's tile payload (the kernel
    validates it per shape and falls back to its heuristic — strictly
    below its per-call ``block_rows`` and ``set_block_rows``).
    """
    if n_norm_axes != 1:
        return False, False, None
    hidden = x_shape[-1]
    rows = 1
    for d in x_shape[:-1]:
        rows *= d
    from_table = False
    tile_pref = None
    if use_pallas is None:
        use_pallas = USE_PALLAS
    if use_pallas is None:
        # the table key includes the input dtype; a caller that didn't
        # supply one gets the built-in default rather than a consult
        # under a guessed dtype that could diverge from the real call's
        # (fused_layer_norm always passes x.dtype)
        if dtype is None:
            return False, False, None
        from apex_tpu import dispatch

        choice, params = dispatch.lookup_params(
            "layer_norm", dtype=dtype, rows=rows, hidden=hidden)
        use_pallas = choice == "pallas"
        from_table = use_pallas
        if params:
            tile_pref = params.get("block_rows")
    if not use_pallas:
        return False, False, None
    # imports below the early return: the pure-jnp default path must not
    # require jax.experimental.pallas to be importable
    from apex_tpu.ops.attention import _on_cpu, _tpu_available
    from apex_tpu.ops import layer_norm_pallas as lnp

    if not lnp.supported(rows, hidden):
        return False, False, None
    if from_table:
        return True, _on_cpu(), tile_pref
    from apex_tpu.dispatch import tiles

    if _on_cpu() and tiles.env_flag("APEX_PALLAS_INTERPRET"):
        # the CPU leg of a pinned pallas A/B:
        # run the kernel in interpret mode instead of silently falling
        # back to jnp — a "pallas" label over a jnp run is label drift
        return True, True, tile_pref
    return _tpu_available(), False, tile_pref


def would_use_pallas(x_shape, n_norm_axes=1, use_pallas=None, dtype=None):
    """The exact predicate ``fused_layer_norm`` uses to dispatch to the
    Pallas row kernel — exposed so callers (benchmark harnesses, tests)
    can't drift from the real gate. ``use_pallas=None`` resolves to the
    module-level ``USE_PALLAS`` preference, then the dispatch table,
    same as ``fused_layer_norm`` — but the table consult needs the
    input ``dtype`` (part of the table key, ``fused_layer_norm`` passes
    ``x.dtype``); without it the unpinned answer is the built-in
    default, never a guessed-dtype consult that could diverge from the
    real call's."""
    return _resolve_pallas(x_shape, n_norm_axes, use_pallas, dtype)[0]


def fused_layer_norm(x, normalized_shape, weight=None, bias=None, eps=1e-5,
                     memory_efficient=False, use_pallas=None,
                     block_rows=None):
    """Functional layer norm, fp32 statistics (reference autograd fns:
    fused_layer_norm.py:32,59,84,103). ``use_pallas`` overrides the
    module-level ``USE_PALLAS`` dispatch to the Pallas row kernel;
    ``block_rows`` is the per-call tile demand forwarded to the kernel
    (raises on an illegal tile — apex_tpu.dispatch.tiles; the kernel's
    ``set_block_rows``/``APEX_LN_BLOCK_ROWS``/table-params tiles apply
    only when it is None)."""
    del memory_efficient  # remat is a jax.checkpoint policy decision here
    axes, _ = _normalized_axes(x, normalized_shape)
    orig_dtype = x.dtype

    use, interpret, block_rows_pref = _resolve_pallas(
        x.shape, len(axes), use_pallas, x.dtype)
    if use:
        from apex_tpu.ops import layer_norm_pallas as lnp

        hidden = x.shape[-1]
        rows = x.size // hidden
        y2d = lnp.layer_norm(
            x.reshape(rows, hidden),
            None if weight is None else weight.astype(jnp.float32),
            None if bias is None else bias.astype(jnp.float32), eps,
            interpret, block_rows, block_rows_pref)
        return y2d.reshape(x.shape)

    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=axes, keepdims=True)
    y = (xf - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(orig_dtype)


def fused_rms_norm(x, normalized_shape, weight=None, eps=1e-5,
                   memory_efficient=False):
    """Functional RMS norm (reference: fused_layer_norm.py:122,145 and the
    pure-python manual_rms_norm fallback :16-29)."""
    del memory_efficient
    axes, _ = _normalized_axes(x, normalized_shape)
    orig_dtype = x.dtype
    xf = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(xf), axis=axes, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    return y.astype(orig_dtype)


def manual_rms_norm(input, normalized_shape, weight, eps):
    """Reference: fused_layer_norm.py:16-29 — the pure-python RMS-norm
    fallback; identical math to :func:`fused_rms_norm` here (XLA fuses
    both the same way)."""
    return fused_rms_norm(input, normalized_shape, weight, eps)


# aliases matching the reference's functional names
fused_layer_norm_affine = fused_layer_norm
fused_rms_norm_affine = fused_rms_norm


def mixed_dtype_fused_layer_norm_affine(x, weight, bias, normalized_shape,
                                        eps=1e-5, memory_efficient=False):
    """Mixed-dtype path (params follow input dtype; fused_layer_norm.py:84)."""
    return fused_layer_norm(x, normalized_shape, weight, bias, eps,
                            memory_efficient)


def mixed_dtype_fused_rms_norm_affine(x, weight, normalized_shape, eps=1e-5,
                                      memory_efficient=False):
    return fused_rms_norm(x, normalized_shape, weight, eps, memory_efficient)


class FusedLayerNorm(nn.Module):
    """Module surface of apex.normalization.FusedLayerNorm
    (fused_layer_norm.py:204). ``use_pallas=True`` requests the Pallas row
    kernel (contrib FastLayerNorm sets this)."""

    normalized_shape: tuple
    eps: float = 1e-5
    elementwise_affine: bool = True
    memory_efficient: bool = False
    param_dtype: jnp.dtype = jnp.float32
    use_pallas: bool = None
    block_rows: int = None  # per-call tile demand (raises when illegal)

    @nn.compact
    def __call__(self, x):
        shape = self.normalized_shape
        if isinstance(shape, numbers.Integral):
            shape = (int(shape),)
        else:
            shape = tuple(int(s) for s in shape)
        weight = bias = None
        if self.elementwise_affine:
            weight = self.param(
                "weight", nn.initializers.ones, shape, self.param_dtype)
            bias = self.param(
                "bias", nn.initializers.zeros, shape, self.param_dtype)
        return fused_layer_norm(x, shape, weight, bias, self.eps,
                                self.memory_efficient,
                                use_pallas=self.use_pallas,
                                block_rows=self.block_rows)


class FusedRMSNorm(nn.Module):
    """Module surface of apex.normalization.FusedRMSNorm
    (fused_layer_norm.py:300)."""

    normalized_shape: tuple
    eps: float = 1e-5
    elementwise_affine: bool = True
    memory_efficient: bool = False
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        shape = self.normalized_shape
        if isinstance(shape, numbers.Integral):
            shape = (int(shape),)
        else:
            shape = tuple(int(s) for s in shape)
        weight = None
        if self.elementwise_affine:
            weight = self.param(
                "weight", nn.initializers.ones, shape, self.param_dtype)
        return fused_rms_norm(x, shape, weight, self.eps, self.memory_efficient)


class MixedFusedLayerNorm(FusedLayerNorm):
    """Params follow input dtype (reference: fused_layer_norm.py:398) —
    realized by constructing with ``param_dtype`` = model half dtype."""


class MixedFusedRMSNorm(FusedRMSNorm):
    """Reference: fused_layer_norm.py:420."""
