"""Multihead-attention fwd/bwd timing — the TPU counterpart of the
reference's only published perf artifact.

The reference ships contrib/examples/multihead_attn/perf_test_multihead_attn.py
and two plots (MHA_fwd.png / MHA_bwd.png, TitanV, seq-len 64 — see
BASELINE.md): fast C++ MHA vs torch.nn.MultiheadAttention vs a Python
composition. Mirrored here: ``contrib.multihead_attn.SelfMultiheadAttn``
(impl="fast": routes this unmasked/no-dropout case through the flash
attention kernel on TPU; no materialized scores) against a naive jnp
composition (materialized [b*h, s, s] scores — what impl="default" also
computes), fwd and fwd+bwd, across sequence lengths. On non-TPU backends
both sides are XLA-fused dense programs and the ratio hovers near 1.

Run on TPU: PYTHONPATH=/root/repo python benchmarks/profile_multihead_attn.py
"""
# apexlint: disable-file=APX004 — pre-Tracer inline PERF.md §0 protocol (scan-chain + traced eps + 1-element sync + overhead subtract); Tracer migration queued — the BASELINE rows' stdout format is pinned by committed captions

import os
import sys
import time

import numpy as np
import jax

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from benchmarks._smoke import smoke_mode  # noqa: E402

SMOKE = smoke_mode("APEX_MHA_SMOKE")  # tiny CPU sanity mode

import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from benchmarks._timing import (bench_k, device_peak_flops,  # noqa: E402
                                measure_dispatch_overhead, sync)

from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn
from apex_tpu.ops.attention import flash_supported  # noqa: E402

K = bench_k(SMOKE)  # see benchmarks/_timing.bench_k
PEAK = device_peak_flops()  # None on the CPU: no MFU is printed

OVERHEAD = measure_dispatch_overhead(K)
print(f"dispatch overhead {OVERHEAD*1e3:.1f} ms")

# the reference perf script's shapes
HEADS, HIDDEN, BATCH = (2, 32, 2) if SMOKE else (16, 1024, 32)
SEQS = (8,) if SMOKE else (64, 512, 1024)


def naive_mha(in_w, out_w, x, heads):
    """Unfused composition (the reference's "python" competitor):
    materialized [b*h, s, s] scores, no flash kernel, fp32 softmax."""
    s, b, h = x.shape
    d = h // heads
    qkv = x @ in_w.astype(x.dtype)
    q, k, v = jnp.split(qkv, 3, axis=-1)

    def split(t):
        return t.reshape(s, b * heads, d).transpose(1, 0, 2)

    q, k, v = split(q), split(k), split(v)
    scores = (q @ k.transpose(0, 2, 1)) / np.sqrt(d)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
    ctx = (probs @ v).transpose(1, 0, 2).reshape(s, b, h)
    return ctx @ out_w.astype(x.dtype)


def run_case(name, seq, fwd_only, fast):
    rs = np.random.RandomState(0)
    x0 = jnp.asarray(rs.randn(seq, BATCH, HIDDEN) * 0.02, jnp.bfloat16)
    mha = SelfMultiheadAttn(num_heads=HEADS, embed_dim=HIDDEN, dropout=0.0,
                            impl="fast")
    params = mha.init(jax.random.PRNGKey(0), x0)

    if fast:
        def apply(p, x):
            return mha.apply(p, x)[0]
    else:
        def apply(p, x):
            return naive_mha(p["params"]["in_proj"]["kernel"],
                             p["params"]["out_proj"]["kernel"], x, HEADS)

    def make_body(eps, x0):
        def body(p, _):
            if fwd_only:
                out = apply(p, x0)
                metric = jnp.sum(out.astype(jnp.float32))
                p = jax.tree_util.tree_map(
                    lambda a: a + eps.astype(a.dtype) *
                    metric.astype(a.dtype), p)
            else:
                def f(p):
                    return jnp.sum(apply(p, x0).astype(jnp.float32) ** 2)
                metric, g = jax.value_and_grad(f)(p)
                p = jax.tree_util.tree_map(
                    lambda a, b: a - eps.astype(a.dtype) * b.astype(a.dtype),
                    p, g)
            return p, metric
        return body

    def run(p, eps, x0):
        return lax.scan(make_body(eps, x0), p, jnp.arange(K))

    f = jax.jit(run)
    sync(f(params, jnp.float32(0.0), x0))
    t0 = time.perf_counter()
    sync(f(params, jnp.float32(1e-30), x0))
    dt = (time.perf_counter() - t0 - OVERHEAD) / K

    # attention flops: qkv proj + 2 bmm + out proj (x3 for fwd+bwd)
    d = HIDDEN // HEADS
    proj = 2 * seq * BATCH * HIDDEN * 4 * HIDDEN
    bmm = 2 * BATCH * HEADS * seq * seq * d * 2
    fl = (proj + bmm) * (1 if fwd_only else 3)
    mfu = f"  MFU={fl/dt/PEAK*100:5.1f}%" if PEAK else ""
    print(f"{name:36s} {dt*1e3:8.3f} ms{mfu}")
    return dt


for seq in SEQS:
    # say when the fast side cannot take the flash kernel (e.g. the
    # reference's s=64 shape) instead of silently comparing dense vs dense
    flash = "" if flash_supported(seq, seq) else " [dense-fallback]"
    for fwd_only in (True, False):
        kind = "fwd" if fwd_only else "fwd+bwd"
        fast = run_case(f"fast   {kind} s={seq}{flash}", seq, fwd_only, True)
        ref = run_case(f"naive  {kind} s={seq}", seq, fwd_only, False)
        print(f"{'':36s} fast/naive = {fast/ref:.2f}x")
