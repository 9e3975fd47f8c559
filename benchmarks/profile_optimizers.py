"""Fused optimizer step-time on TPU (BASELINE tracked metric: optimizer
step-time FusedAdam/FusedLAMB).

Measures the pure optimizer update (gradients given) for a GPT-2-small
sized parameter set, with the calibrated scan methodology, and reports
achieved HBM bandwidth against the analytic floor:

  Adam:  read g, p, m, v; write p, m, v  ->  7 fp32 passes
  LAMB:  adds the per-tensor norm reductions (reads dominate the same way)
  SGD:   read g, p, buf; write p, buf    ->  5 fp32 passes

Every run flushes one ledger record (spans per optimizer row incl. the
"FusedLAMB 1pass" A/B rung plus ``n_params``).

Results recorded in PERF.md §2/§6.
Run:  PYTHONPATH=/root/repo:$PYTHONPATH python benchmarks/profile_optimizers.py
"""

import os
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from benchmarks._smoke import smoke_mode  # noqa: E402

SMOKE = smoke_mode("APEX_BENCH_SMOKE")  # force-CPU tiny sanity mode

from benchmarks._timing import Span, Tracer, bench_k, sync  # noqa: E402

from apex_tpu.optimizers.fused_adam import fused_adam  # noqa: E402
from apex_tpu.optimizers.fused_lamb import fused_lamb  # noqa: E402
from apex_tpu.optimizers.fused_sgd import fused_sgd  # noqa: E402

# SMOKE forces the CPU backend, so it implies the tiny branches
ON_TPU = not SMOKE and jax.devices()[0].platform == "tpu"
K = bench_k(not ON_TPU)  # see benchmarks/_timing.bench_k
HBM = 819e9  # v5e

# GPT-2-small-like parameter set: a few big 2D tensors + many small ones
rs = np.random.RandomState(0)
SHAPES = ([(50304, 768), (1024, 768)]
          + [(768, 2304), (768, 768), (768, 3072), (3072, 768)] * 12
          + [(768,)] * 50) if ON_TPU else [(256, 256), (256,)]
params = [jnp.asarray(rs.randn(*s) * 0.02, jnp.float32) for s in SHAPES]
grads = [jnp.asarray(rs.randn(*s) * 1e-3, jnp.float32) for s in SHAPES]
n = sum(p.size for p in params)
TRACER = Tracer(K)
print(f"{n/1e6:.1f}M params across {len(SHAPES)} tensors "
      f"(K={K}, overhead {TRACER.overhead_ms:.1f} ms)")


def bench(name, tx, passes):
    # fresh buffers per optimizer: the scan donates its inputs
    p0 = jax.tree_util.tree_map(jnp.copy, params)
    state0 = jax.jit(lambda p: tx.init(p))(p0)

    def run(params, state, eps, grads):
        def body(carry, _):
            p, s = carry
            g = jax.tree_util.tree_map(
                lambda x: x + eps.astype(x.dtype), grads)
            u, s = tx.update(g, s, p)
            p = jax.tree_util.tree_map(
                lambda a, b: a + b.astype(a.dtype), p, u)
            return (p, s), p[0].ravel()[0]
        (params, state), out = lax.scan(body, (params, state),
                                        jnp.arange(K))
        return params, state, out

    f = jax.jit(run, donate_argnums=(0, 1))
    traffic = passes * 4 * n
    floor = traffic / HBM
    p1, s1, out = f(p0, state0, jnp.float32(0.0), grads)
    sync(out)
    # apexlint: disable=APX004 — donated warm/timed pattern on Tracer's own calibration (the timed args ARE the warm call's outputs — time_call cannot express it)
    t0 = time.perf_counter()
    _, _, out = f(p1, s1, jnp.float32(1e-30), grads)
    sync(out)
    # apexlint: disable=APX004 — donated warm/timed pattern on Tracer's own calibration (the timed args ARE the warm call's outputs — time_call cannot express it)
    total = time.perf_counter() - t0
    dt = (total - TRACER.overhead) / K
    # the donated warm/timed pattern can't ride Tracer.time_call (the
    # timed args ARE the warm call's outputs), so the span is built here
    # with the same calibration metadata
    span = Span(name, dt, total, K, TRACER.overhead,
                extra={"passes": passes,
                       "gbps": round(traffic / dt / 1e9, 1),
                       "floor_pct": round(floor / dt * 100, 1)})
    TRACER.spans.append(span)
    print(f"{name:12s} {dt*1e3:7.2f} ms/step  "
          f"{traffic/dt/1e9:6.0f} GB/s effective "
          f"({floor/dt*100:5.1f}% of the {floor*1e3:.1f} ms HBM floor)")


bench("FusedAdam", fused_adam(1e-3), 7)
bench("FusedLAMB", fused_lamb(1e-3, impl="two_pass"), 7)
# one-pass flat-buffer A/B (PERF.md §2 queued row): LAMB is the worst
# fused-optimizer row at 54.9% of its HBM floor (Adam 81.9%, §10b) and
# the per-leaf loop's many small norm reductions are the suspect — the
# one_pass impl does ONE segment_sum sweep instead. Same state layout,
# so the row is directly comparable; both rows pin impl= per call so
# the labels can't drift whatever the table/env says.
bench("FusedLAMB 1pass", fused_lamb(1e-3, impl="one_pass"), 7)
bench("FusedSGD", fused_sgd(1e-2, momentum=0.9), 5)

TRACER.flush_ledger("profile_optimizers", extra={
    "n_params": int(n), "n_tensors": len(SHAPES)})
