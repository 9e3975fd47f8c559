"""Flash-attention kernel tuning on TPU: block sizes + splash kernel.

Finds the best configuration for the GPT-2-small shape (b=8, h=12, s=1024,
d=64) fwd+bwd; results recorded in PERF.md and wired into
apex_tpu/ops/attention.py.
"""

import os
import sys

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from benchmarks._smoke import smoke_mode  # noqa: E402

SMOKE = smoke_mode("APEX_BENCH_SMOKE")  # force-CPU tiny sanity mode

from apex_tpu.dispatch import tiles  # noqa: E402
from benchmarks._timing import (Tracer, bench_k,  # noqa: E402
                                device_peak_flops)

B, H, S, D = (2, 2, 128, 32) if SMOKE else (8, 12, 1024, 64)
# APEX_ATTN_SEQ overrides s (batch rescaled toward constant b*s tokens)
# — measures the long-sequence crossover behind the ops.attention
# dispatch rule (rows kernel capped at sk<=2048 by default). The full
# 9-config flash block sweep is trimmed to the two known-good configs so
# the crossover decision rows (which run last) fit the window budget.
_ATTN_SEQ = tiles.env_int("APEX_ATTN_SEQ")
LONG_SEQ = not SMOKE and _ATTN_SEQ is not None
if LONG_SEQ:
    S = _ATTN_SEQ
    B = max(1, 8 * 1024 // S)
    if B * S != 8 * 1024:
        print(f"note: b*s = {B * S} tokens (baseline rows used 8192) — "
              f"compare MFU, not tokens/s, across seq lengths")
K = bench_k(SMOKE)  # see benchmarks/_timing.bench_k
# fwd = 4*b*h*s^2*d/2 (causal); bwd = 2x fwd
FLOPS = 4 * B * H * S * S * D * 3 // 2
PEAK = device_peak_flops()  # None on the CPU: no MFU is printed


def measure(name, attn_fn, wrt_qkv=False, fwd_only=False):
    """wrt_qkv=False: fwd + dq only (the original protocol, kept for
    comparability with the recorded r3 numbers). wrt_qkv=True: fwd + the
    full (dq, dk, dv) backward — what a training step actually pays.
    fwd_only=True: no grad at all — the inference protocol."""
    rs = np.random.RandomState(0)
    q0 = jnp.asarray(rs.randn(B, H, S, D), jnp.bfloat16)
    k0 = jnp.asarray(rs.randn(B, H, S, D), jnp.bfloat16)
    v0 = jnp.asarray(rs.randn(B, H, S, D), jnp.bfloat16)

    def run(q, eps, k0, v0):
        def body(qc, _):
            if fwd_only:
                y = attn_fn(qc, k0, v0)
                l = jnp.sum(y.astype(jnp.float32))
                g = y[..., :1].astype(qc.dtype)  # feedback, no backward
            elif wrt_qkv:
                def f(qq, kk, vv):
                    return jnp.sum(attn_fn(qq, kk, vv).astype(jnp.float32))
                l, (gq, gk, gv) = jax.value_and_grad(
                    f, argnums=(0, 1, 2))(qc, k0, v0)
                g = gq + gk + gv
            else:
                def f(qq):
                    return jnp.sum(attn_fn(qq, k0, v0).astype(jnp.float32))
                l, g = jax.value_and_grad(f)(qc)
            return qc - eps.astype(qc.dtype) * g.astype(qc.dtype), l
        qc, ls = lax.scan(body, q, jnp.arange(K))
        return qc, ls

    f = jax.jit(run)
    flops = FLOPS // 3 if fwd_only else FLOPS  # fwd is 1/3 of fwd+bwd
    protocol = ("fwd-only" if fwd_only
                else "fwd+d(q,k,v)" if wrt_qkv else "fwd+dq")
    span = TRACER.time_call(
        name, f, (q0, jnp.float32(0.0), k0, v0),
        (q0, jnp.float32(1e-30), k0, v0), flops_per_iter=flops,
        extra={"protocol": protocol}, on_fail="span")
    if span.seconds is None:
        print(f"{name:40s} FAILED: {span.error}")
        return None
    print(span.format_row(PEAK, width=40, ms_prec=3))
    MEASURED.append(name)
    return span.seconds


TRACER = Tracer(K, peak_flops=PEAK)
print(f"dispatch overhead {TRACER.overhead_ms:.1f} ms; "
      f"shape b={B} h={H} s={S} d={D}")

from jax.experimental.pallas.ops.tpu import flash_attention as fa

sm = 1.0 / np.sqrt(D)
MEASURED = []


def fa_with_blocks(bq, bk):
    bs = fa.BlockSizes(
        block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
        block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
        block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk, block_q_dq=bq)
    def f(q, k, v):
        return fa.flash_attention(q, k, v, causal=True, sm_scale=float(sm),
                                  block_sizes=bs)
    return f


if SMOKE:
    # the TPU flash/splash kernels cannot run on CPU (no interpret knob is
    # plumbed through jax's flash_attention API) — smoke validates the
    # harness + the dense path only and says so instead of printing a
    # wall of spurious FAILED kernel rows
    print("SMOKE: skipping TPU-only flash/splash kernel configs")

# current repo config (512/512) and alternatives
SWEEP = []
_SWEEP_CFGS = [(512, 512), (512, 256), (256, 512), (256, 256), (128, 256),
               (256, 128), (128, 128), (1024, 512), (512, 1024)]
if LONG_SEQ:
    _SWEEP_CFGS = [(512, 512), (512, 256)]
for bq, bk in ([] if SMOKE else _SWEEP_CFGS):
    dt = measure(f"flash blocks q={bq} k={bk}", fa_with_blocks(bq, bk))
    if dt is not None:
        SWEEP.append((dt, bq, bk))

if not SMOKE and not LONG_SEQ:
    measure("flash default blocks",
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                               sm_scale=float(sm)))

# splash attention (newer kernel)
try:
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as smask,
    )

    def splash(q, k, v):
        mask = smask.CausalMask((S, S))
        mmask = smask.MultiHeadMask([mask] * H)
        kernel = sk.make_splash_mha(
            mask=mmask, head_shards=1, q_seq_shards=1)
        # splash expects [h, s, d] per batch entry; vmap over batch
        return jax.vmap(lambda qq, kk, vv: kernel(qq * sm, kk, vv))(
            q.astype(jnp.float32).astype(jnp.bfloat16), k, v)

    if not SMOKE and not LONG_SEQ:
        measure("splash attention (default)", splash)
except Exception as e:
    print(f"splash attention unavailable: {type(e).__name__}: {str(e)[:120]}")

# XLA dense reference (skipped at long seq: the [b, h, s, s] fp32 scores
# are a GB-scale HBM object — the class the degraded relay starves on)
from apex_tpu.ops.attention import _dense_attention

if not LONG_SEQ:
    measure("XLA dense (materialized scores)",
            lambda q, k, v: _dense_attention(q, k, v, True, float(sm), None))

# self-authored VMEM-row kernel (ops/attention_pallas.py) vs the best
# flash config, under BOTH protocols — the row kernel computes dk/dv
# unconditionally, so the dq-only protocol understates it and the
# qkv protocol is the decision row for the training-step dispatch
from apex_tpu.ops import attention_pallas as ap

if not SMOKE and ap.supported(S, S, D):
    vmem_rows = lambda q, k, v: ap.fused_attention_rows(
        q, k, v, True, float(sm), None)
    # inference protocol: fwd kernels alone — the rows kernel's
    # single-pass structure vs flash's multi-pass fwd loop
    measure("vmem-rows kernel fwd-only", vmem_rows, fwd_only=True)
    # pin the actual (bq, bk) into the label: with an empty SWEEP this
    # row is the hardcoded fallback, and in LONG_SEQ mode "best" is only
    # best-of-the-trimmed-sweep — the label must say which config ran
    _fo_bq, _fo_bk = (min(SWEEP)[1:]) if SWEEP else (1024, 512)
    measure(f"flash q={_fo_bq} k={_fo_bk} fwd-only",
            fa_with_blocks(_fo_bq, _fo_bk),
            fwd_only=True)
    # dq-only protocol rows pin bwd_impl: custom_vjp runs the full
    # backward even under grad-wrt-q, so an unpinned row would silently
    # re-measure whatever BWD_IMPL defaults to (the committed r3 0.346 ms
    # number was monolithic)
    for impl in ("monolithic", "split"):
        measure(f"vmem-rows {impl}-bwd (dq-only protocol)",
                lambda q, k, v, impl=impl: ap.fused_attention_rows(
                    q, k, v, True, float(sm), None, False, None, impl))
    # backward-structure A/B (the PERF.md §3 decision row): monolithic
    # q-major accumulation vs split dq + k-major dkv passes
    for impl in ("monolithic", "split"):
        measure(f"vmem-rows {impl}-bwd fwd+d(q,k,v)",
                lambda q, k, v, impl=impl: ap.fused_attention_rows(
                    q, k, v, True, float(sm), None, False, None, impl),
                wrt_qkv=True)
    # block_q sweep: q-blocks below the VMEM-auto size trade smaller
    # matmuls for more causal-skip in the fwd and monolithic-bwd chunked
    # kernels; bwd_impl is pinned per row so the labels stay truthful
    # (and comparable with the pre-split rounds, which were monolithic)
    for rbq in (512, 256, 128):
        # skip the auto size — the un-overridden row above already is it
        if S % rbq == 0 and rbq < ap._q_block(S, S):
            for impl in ("monolithic", "split"):
                measure(f"vmem-rows block_q={rbq} {impl}-bwd fwd+d(q,k,v)",
                        lambda q, k, v, rbq=rbq, impl=impl:
                        ap.fused_attention_rows(
                            q, k, v, True, float(sm), None, False, rbq,
                            impl),
                        wrt_qkv=True)
    # in-kernel dropout (the fmha training path): hash-mask cost
    # isolated by pinning everything else — non-causal (so neither row
    # can take the chunked causal-skip kernels) at the DROPOUT path's
    # auto block size for both rows
    _dbq = ap._pick_bq(S, S, None, ap._DROP_BWD_ARRAYS)
    _dseed = jnp.asarray([[123]], jnp.int32)
    measure(f"vmem-rows noncausal block_q={_dbq} no-dropout fwd+d(q,k,v)",
            lambda q, k, v: ap.fused_attention_rows(
                q, k, v, False, float(sm), None, False, _dbq,
                "monolithic"),
            wrt_qkv=True)
    measure(f"vmem-rows noncausal block_q={_dbq} dropout=0.1 fwd+d(q,k,v)",
            lambda q, k, v: ap.fused_attention_rows(
                q, k, v, False, float(sm), None, False, _dbq, None,
                0.1, _dseed),
            wrt_qkv=True)
    # compare against whatever flash config actually won today's sweep
    _, best_bq, best_bk = min(SWEEP) if SWEEP else (None, 1024, 512)
    measure(f"flash q={best_bq} k={best_bk} fwd+d(q,k,v)",
            fa_with_blocks(best_bq, best_bk), wrt_qkv=True)
    if not LONG_SEQ:
        measure("XLA dense fwd+d(q,k,v)",
                lambda q, k, v: _dense_attention(q, k, v, True, float(sm),
                                                 None),
                wrt_qkv=True)

# ledger first, exit-check second: a window where every config failed
# is evidence that belongs in the ledger too (the spans carry errors)
TRACER.flush_ledger("profile_attention", extra={
    "shape": {"b": B, "h": H, "s": S, "d": D}})

if not MEASURED:
    print("ERROR: no configuration produced a measurement")
    sys.exit(1)
