"""Fused scale + mask + softmax.

Capability port of apex/transformer/functional/fused_softmax.py:21-264 and
the three megatron CUDA kernels it dispatches to
(csrc/megatron/scaled_upper_triang_masked_softmax.cu,
scaled_masked_softmax.cu, generic_scaled_masked_softmax.cu).

On TPU the "fusion" is XLA's: scale, mask-add, row-max, exp, row-sum and
divide lower to one fused loop over the softmax rows (and fuse further into
the surrounding attention matmuls' epilogues), so the three hand-written
warp-level kernels collapse into straight jnp math. What we DO preserve:

  * the numerics contract: softmax computed in fp32 when
    ``softmax_in_fp32`` (or always for fp16/bf16 inputs on the "kernel"
    path, matching the CUDA kernels' internal fp32 accumulation), output
    cast back to the input dtype;
  * masked positions forced to exactly 0 probability, including the
    fully-masked-row case (the CUDA kernels emit 0 rows, not NaN);
  * the dispatch predicate ``is_kernel_available`` — ported verbatim
    (fused_softmax.py:186-200) so models exercise the same code paths and
    tests can assert on the dispatch decision;
  * the autograd contract: d(softmax) = y * (g - sum(g*y)) with the scale
    folded in, which XLA derives automatically.
"""

import os

import jax.numpy as jnp

from apex_tpu.transformer.enums import AttnMaskType

# Process-wide Pallas-kernel preference for the fused scale-mask
# softmax: tri-state. None (shipped) = unpinned — unpinned instances
# consult the per-shape dispatch table (apex_tpu.dispatch, op
# "softmax"); a miss means the jnp path (the PERF.md §4b measured
# default). set_use_pallas(True/False) pins above the table; a
# per-instance ``use_pallas=`` pins above everything.
USE_PALLAS = None


def set_use_pallas(value):
    """Pin the process-wide softmax-kernel preference (True/False), or
    un-pin with None (the dispatch table then applies again)."""
    global USE_PALLAS
    if value not in (True, False, None):
        raise ValueError(f"use_pallas must be True/False/None, "
                         f"got {value!r}")
    USE_PALLAS = value


def _softmax_fp32(x, where=None, scale=None):
    """Row softmax in fp32 with masked-row → all-zeros semantics.

    ``scale`` is applied AFTER the fp32 upcast, matching the CUDA kernels
    (they load half values and multiply by the fp32 scale in registers) —
    scaling in the input dtype can overflow fp16 / lose bf16 mantissa bits
    exactly in the qk-layer-scaling regime this class protects.
    """
    xf = x.astype(jnp.float32)
    if scale is not None:
        xf = xf * jnp.float32(scale)
    if where is not None:
        neg = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)
        xf = jnp.where(where, neg, xf)
    m = jnp.max(xf, axis=-1, keepdims=True)
    e = jnp.exp(xf - m)
    if where is not None:
        e = jnp.where(where, 0.0, e)
    s = jnp.sum(e, axis=-1, keepdims=True)
    # fully-masked rows: s == 0 → emit zeros (CUDA kernel behaviour)
    return jnp.where(s > 0, e / jnp.where(s > 0, s, 1.0), 0.0)


def scaled_upper_triang_masked_softmax(x, scale=1.0):
    """Causal-masked scaled softmax (reference:
    scaled_upper_triang_masked_softmax.h kernels; autograd fn
    fused_softmax.py:21-66). ``x``: [attn_batches, sq, sk] with sq == sk."""
    sq, sk = x.shape[-2], x.shape[-1]
    causal = jnp.arange(sk)[None, :] > jnp.arange(sq)[:, None]
    out = _softmax_fp32(x, where=causal, scale=scale)
    return out.astype(x.dtype)


def scaled_masked_softmax(x, mask, scale=1.0):
    """Explicit-mask scaled softmax (reference: scaled_masked_softmax.h;
    autograd fn fused_softmax.py:71-98). ``x``: [b, np, sq, sk]; ``mask``
    bool broadcastable to x, True = masked out."""
    where = None if mask is None else jnp.broadcast_to(
        mask.astype(bool), x.shape)
    return _softmax_fp32(x, where=where, scale=scale).astype(x.dtype)


def generic_scaled_masked_softmax(x, mask, scale=1.0):
    """Arbitrary-seq-len variant (reference:
    generic_scaled_masked_softmax.cu; fn fused_softmax.py:101-125). On TPU
    there is no shape constraint to lift — identical to
    :func:`scaled_masked_softmax`."""
    return scaled_masked_softmax(x, mask, scale)


class FusedScaleMaskSoftmax:
    """fused operation: scaling + mask + softmax
    (reference: fused_softmax.py:128-237).

    Arguments keep the reference names; ``input_in_fp16``/``input_in_bf16``
    describe the incoming activation dtype, ``attn_mask_type`` selects the
    causal kernel, ``scaled_masked_softmax_fusion`` enables the fused path,
    ``mask_func`` is the fallback's mask application, ``softmax_in_fp32``
    upcasts on the fallback path, ``scale`` pre-scales logits (only valid
    with softmax_in_fp32, as in the reference assert :183).
    """

    def __init__(self, input_in_fp16, input_in_bf16, attn_mask_type,
                 scaled_masked_softmax_fusion, mask_func, softmax_in_fp32,
                 scale, use_pallas=None, _pallas_interpret=False,
                 block_rows=None):
        self.input_in_fp16 = input_in_fp16
        self.input_in_bf16 = input_in_bf16
        assert not (input_in_fp16 and input_in_bf16), \
            "both fp16 and bf16 flags cannot be active at the same time."
        self.input_in_float16 = input_in_fp16 or input_in_bf16
        self.attn_mask_type = attn_mask_type
        self.scaled_masked_softmax_fusion = scaled_masked_softmax_fusion
        self.mask_func = mask_func
        self.softmax_in_fp32 = softmax_in_fp32
        self.scale = scale
        # guarantee the fusion with the Pallas kernel
        # (ops/softmax_pallas.py) instead of relying on XLA's fuser.
        # True/False pins this instance; None defers to the module
        # preference (set_use_pallas) then the per-shape dispatch table
        # — a miss lands on the jnp path, the PERF.md §4b measured
        # default (jnp won every measured shape)
        self.use_pallas = use_pallas
        self._pallas_interpret = _pallas_interpret
        # per-call tile demand handed to the kernel — raises on an
        # illegal tile (apex_tpu.dispatch.tiles); None defers to the
        # kernel's setter/env, then the table's params payload
        self.block_rows = block_rows
        assert self.scale is None or softmax_in_fp32, \
            "softmax should be in fp32 when scaled"

    def __call__(self, input, mask):
        assert input.ndim == 4  # [b, np, sq, sk]
        if self.is_kernel_available(mask, *input.shape):
            return self.forward_fused_softmax(input, mask)
        return self.forward_torch_softmax(input, mask)

    def is_kernel_available(self, mask, b, np_, sq, sk):
        """Ported dispatch predicate (reference: fused_softmax.py:186-200).
        The shape constraints came from the CUDA kernels' templated launch
        bounds; we keep them so dispatch decisions (and tests asserting on
        them) match the reference."""
        attn_batches = b * np_
        if (self.scaled_masked_softmax_fusion
                and self.input_in_float16
                and 16 < sk <= 4096
                and sq % 4 == 0
                and attn_batches % 4 == 0):
            batch_per_block = self.get_batch_per_block(sq, sk, b, np_)
            if self.attn_mask_type == AttnMaskType.causal:
                if attn_batches % batch_per_block == 0:
                    return True
            else:
                if sq % batch_per_block == 0:
                    return True
        return False

    def _resolve_pallas(self, input):
        """``(use, interpret, block_rows_pref)`` for one call: instance
        ``use_pallas`` > module ``USE_PALLAS`` (set_use_pallas) >
        dispatch-table "softmax" entry for this shape bucket > False. A
        table entry is backend-keyed: a CPU-measured "pallas" row was
        measured in interpret mode and runs the same way.
        ``block_rows_pref`` is the entry's tile payload — the kernel
        validates it per shape (strictly below its per-call knob and
        ``set_block_rows``) and falls back to its heuristic."""
        use = self.use_pallas
        if use is None:
            use = USE_PALLAS
        from_table = False
        tile_pref = None
        if use is None:
            from apex_tpu import dispatch

            b, np_, sq, sk = input.shape
            choice, params = dispatch.lookup_params(
                "softmax", dtype=input.dtype, b=b, h=np_, sq=sq, sk=sk)
            use = choice == "pallas"
            from_table = use
            if params:
                tile_pref = params.get("block_rows")
        interpret = self._pallas_interpret
        if use and not interpret:
            from apex_tpu.dispatch import tiles as _tiles
            from apex_tpu.ops.attention import _on_cpu

            if from_table or _tiles.env_flag("APEX_PALLAS_INTERPRET"):
                # a CPU-measured table entry, or the CPU leg of a
                # pinned pallas A/B (autotune --smoke): interpret mode
                # instead of a silent jnp fallback — on the CPU only
                interpret = _on_cpu()
        return bool(use), interpret, tile_pref

    def forward_fused_softmax(self, input, mask):
        """Reference: fused_softmax.py:202-223."""
        scale = self.scale if self.scale is not None else 1.0
        causal = self.attn_mask_type == AttnMaskType.causal
        if causal:
            assert input.shape[-2] == input.shape[-1], \
                "causal mask is only for self attention"
        use_pallas, p_interpret, block_rows_pref = \
            self._resolve_pallas(input)
        if use_pallas:
            from apex_tpu.ops import softmax_pallas
            from apex_tpu.ops.attention import _tpu_available
            # the fused causal path ignores an explicit mask (the
            # reference's scaled_upper_triang kernel takes none) — pass
            # None so toggling use_pallas never changes numerics
            m = None if causal or mask is None else mask.astype(bool)
            if ((p_interpret or _tpu_available())
                    and softmax_pallas.supported(input.shape[-2],
                                                 input.shape[-1])
                    and (m is None
                         or softmax_pallas.mask_supported(m, input.shape))):
                return softmax_pallas.scaled_masked_softmax(
                    input, m, scale, causal=causal,
                    interpret=p_interpret, block_rows=self.block_rows,
                    block_rows_pref=block_rows_pref)
        if causal:
            b, np_, sq, sk = input.shape
            out = scaled_upper_triang_masked_softmax(
                input.reshape(-1, sq, sk), scale)
            return out.reshape(b, np_, sq, sk)
        return scaled_masked_softmax(input, mask, scale)

    def forward_torch_softmax(self, input, mask):
        """Unfused fallback (reference: fused_softmax.py:225-237).

        The causal case must mask even when the caller passes ``mask=None``
        (the fused causal kernel never takes an explicit mask, so causal
        models legitimately pass None); the reference relies on the model
        always materializing a ltor mask — here the fallback synthesizes
        it, keeping fused/unfused numerically interchangeable."""
        if self.attn_mask_type == AttnMaskType.causal:
            sq, sk = input.shape[-2], input.shape[-1]
            causal = jnp.arange(sk)[None, :] > jnp.arange(sq)[:, None]
            mask = causal if mask is None else (mask.astype(bool) | causal)
        orig_dtype = input.dtype
        if self.input_in_float16 and self.softmax_in_fp32:
            input = input.astype(jnp.float32)
        if self.scale is not None:
            input = input * self.scale
        mask_output = self.mask_func(input, mask) if mask is not None else input
        m = jnp.max(mask_output, axis=-1, keepdims=True)
        e = jnp.exp(mask_output - m)
        probs = e / jnp.sum(e, axis=-1, keepdims=True)
        if self.input_in_float16 and self.softmax_in_fp32:
            probs = probs.astype(orig_dtype)
        return probs

    @staticmethod
    def get_batch_per_block(sq, sk, b, np_):
        """CUDA launch-geometry compat shim (reference:
        scaled_masked_softmax.cpp:93 — batches per 128-thread block given
        next_pow2(sk)). Kept for API parity; the TPU path has no blocks, so
        it only feeds the ported dispatch predicate."""
        pow2 = 1 << (sk - 1).bit_length()
        warp_size = pow2 if pow2 <= 32 else 32
        batches_per_warp = 2 if pow2 <= 128 else 1
        warps_per_block = 128 // warp_size
        return warps_per_block * batches_per_warp


class GenericFusedScaleMaskSoftmax(FusedScaleMaskSoftmax):
    """Generic (unbounded seq-len) variant (reference:
    fused_softmax.py:240-264)."""

    def __init__(self, input_in_fp16, input_in_bf16, mask_func,
                 softmax_in_fp32, scale, use_pallas=None,
                 _pallas_interpret=False, block_rows=None):
        super().__init__(input_in_fp16, input_in_bf16, AttnMaskType.padding,
                         True, mask_func, softmax_in_fp32, scale,
                         use_pallas=use_pallas,
                         _pallas_interpret=_pallas_interpret,
                         block_rows=block_rows)

    def is_kernel_available(self, mask, b, np_, sq, sk):
        return self.scaled_masked_softmax_fusion and self.input_in_float16

    def forward_fused_softmax(self, input, mask):
        if self._resolve_pallas(input)[0]:
            # same kernel dispatch (and fallback rules) as the base class
            return super().forward_fused_softmax(input, mask)
        scale = self.scale if self.scale is not None else 1.0
        return generic_scaled_masked_softmax(input, mask, scale)


class ScaledUpperTriangMaskedSoftmax:
    """autograd-Function-shaped surface (reference: fused_softmax.py:21-66
    — ``ScaledUpperTriangMaskedSoftmax.apply(x, scale)``). JAX AD
    differentiates through the function; the class exists so ported
    ``.apply`` call sites run."""

    @staticmethod
    def apply(x, scale=1.0):
        return scaled_upper_triang_masked_softmax(x, scale)


class ScaledMaskedSoftmax:
    """Reference: fused_softmax.py:71-98 — ``apply(x, mask, scale)``."""

    @staticmethod
    def apply(x, mask, scale=1.0):
        return scaled_masked_softmax(x, mask, scale)


class GenericScaledMaskedSoftmax:
    """Reference: fused_softmax.py:101-125 — ``apply(x, mask, scale)``."""

    @staticmethod
    def apply(x, mask, scale=1.0):
        return generic_scaled_masked_softmax(x, mask, scale)
