"""DP gradient-sync A/B — the harness behind the "grad_comm" rung.

Measures the minimal-GPT FULL train step (1F1B + loss scaling +
found_inf-gated ZeRO-free fused Adam — apex_tpu.transformer.testing
.minimal) over a data-parallel mesh with the grad sync routed through
``apex_tpu.parallel.collectives``: the program whose algorithm the
``APEX_GRAD_COMPRESS`` / ``APEX_HIER_ALLREDUCE`` knobs select.
One variant per process: off / int8 / hier / int8_hier.

Honest-label notes (PERF.md §0):

* On the single-chip v5e window dp == 1 — the A/B measures the
  compression COMPUTE overhead bound (quantize → gather over one rank
  → dequantize; there is no bandwidth to win), which is exactly the
  number that keeps the default OFF until a pod-slice window offers
  dp > 1. The payload-cut claim itself is proven at trace time: the
  span's cost block stamps ``comm_bytes_per_axis`` next to the
  uncompressed twin (``collectives.disabled()`` re-trace) in
  ``comm_compression.uncompressed_bytes_per_axis``.
* Smoke mode runs a REAL dp=8 mesh over 8 virtual CPU devices, so the
  CPU table rows A/B the actual collective schedules; a hierarchical
  request factors the dp axis as (2, dp//2). With dp < 4 the
  hierarchical preference falls back to the flat axis — printed, never
  silent.
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# virtual devices BEFORE backend init: the smoke A/B drives a real dp>1
# mesh (same mechanism as tests/conftest.py's 8-device CPU mesh).
# apexlint: disable=APX002 — raw on purpose: XLA_FLAGS must be staged
# before ANY apex_tpu import loads jax, so the env_flag helper (whose
# import executes the package __init__) is not usable yet
if os.environ.get("APEX_BENCH_SMOKE") == "1":
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

from benchmarks._smoke import smoke_mode  # noqa: E402

SMOKE = smoke_mode("APEX_BENCH_SMOKE")

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from benchmarks._timing import Tracer, bench_k  # noqa: E402

from apex_tpu.dispatch.tiles import env_flag  # noqa: E402
from apex_tpu.parallel import collectives  # noqa: E402
from apex_tpu.telemetry import costs  # noqa: E402
from apex_tpu.transformer.parallel_state import (  # noqa: E402
    PIPELINE_AXIS,
    TENSOR_AXIS,
)
from apex_tpu.transformer.testing.minimal import (  # noqa: E402
    TransformerConfig,
    dp_axes_of,
    dp_axis_arg,
    gpt_train_step_fn,
    make_gpt_fns,
    toy_batch,
)

K = bench_k(SMOKE)
devices = jax.devices()
N = len(devices)

# pp=1 / tp=1: every device goes to dp — this harness measures the dp
# grad sync, nothing else.
S = 32 if SMOKE else 512
M, MBS = 2, (2 if SMOKE else 4)
cfg = TransformerConfig(
    hidden_size=64 if SMOKE else 768,
    num_layers=2 if SMOKE else 12,
    num_attention_heads=4 if SMOKE else 12,
    vocab_size=128 if SMOKE else 50304,
    max_position_embeddings=S,
    hidden_dropout=0.0, attention_dropout=0.0, bf16=True,
    apply_query_key_layer_scaling=False)

# a hierarchical request factors dp as (2, N//2); below 4 ranks there
# is no inner slice to stage over — the preference falls back (printed)
hier_req = env_flag("APEX_HIER_ALLREDUCE")
dp_decl = (2, N // 2) if hier_req and N >= 4 else N
if hier_req and N < 4:
    print(f"profile_comm: APEX_HIER_ALLREDUCE=1 with dp={N} < 4 — "
          f"no (inner, outer) factorization, hierarchical preference "
          f"falls back to the flat axis")
dp_size, dp_names, dp_sizes = dp_axes_of(dp_decl)
assert dp_size == N, (dp_decl, N)
mesh = Mesh(np.asarray(devices).reshape(1, *dp_sizes, 1),
            (PIPELINE_AXIS, *dp_names, TENSOR_AXIS))
dp_axes = dp_axis_arg(dp_names)
spec = P(None, dp_axes)

# the grad-overlap knob now shapes this step too (ISSUE 14:
# gpt_train_step_fn consults APEX_OVERLAP_GRAD like any measured
# dispatch) — resolve ONCE, pin the resolved values back into the env
# so the record's knobs name exactly the schedule the row measured
# (the same label discipline as the serving pins in profile_serving;
# an exported =bucketed must never reshape a row labeled terminal
# without a pin the checker can see)
from apex_tpu import overlap as overlap_mod  # noqa: E402

GRAD_OVERLAP = overlap_mod.pin_grad_overlap_env()

# ...and the ZeRO stage (ISSUE 18, check 11): resolved through the ONE
# paired resolution (zero_stage × overlap_grad — the overlap env was
# just pinned above, so this reads exactly what the step will) and
# pinned back, so a `zero3` rung's record names the gather-on-use
# program it measured and an exported APEX_ZERO_STAGE=3 can never
# reshape a row labeled unsharded
from apex_tpu.transformer.testing.minimal import (  # noqa: E402
    _resolve_zero_overlap,
)

ZERO_STAGE, _ = _resolve_zero_overlap(None, None, 1)
os.environ["APEX_ZERO_STAGE"] = str(ZERO_STAGE)

_, init_params = make_gpt_fns(cfg, 1)
step, tx, scaler = gpt_train_step_fn(cfg, 1, M, dp_axes=dp_axes)

global_mb = MBS * dp_size
batch = toy_batch(cfg.vocab_size, M, global_mb, S)
ids, labels = batch["ids"], batch["labels"]


def _init_all(ids, labels):
    params = init_params(jax.random.PRNGKey(0),
                         {"ids": ids[0], "labels": labels[0]})
    if ZERO_STAGE == 3:
        # dp-shard BEFORE tx.init: the optimizer state is shard-resident
        # (zero3_adam) — the full tree never coexists with its moments
        from apex_tpu.parallel import zero3 as zero3_mod

        params = zero3_mod.shard_params(params, dp_axes)
    return params, tx.init(params), scaler.init()


# state placement specs: replicated by default; under zero3 every
# non-scalar params/opt leaf is a per-rank flat shard that must cross
# the shard_map boundary dp-SHARDED on its leading axis (P() would
# silently collapse eight different shards onto device 0's) — the
# structure comes from eval_shape, nothing materialized, and the
# P(dp_axes) round trip preserves the `collectives.axes_index`
# row-major shard order
P_PARAMS = P_OPT = P()
if ZERO_STAGE == 3:
    _struct = jax.eval_shape(jax.shard_map(
        _init_all, mesh=mesh, in_specs=(spec, spec),
        out_specs=(P(), P(), P()), check_vma=False), ids, labels)

    def _dp_sharded(tree):
        return jax.tree_util.tree_map(
            lambda s: P(dp_axes) if getattr(s, "ndim", 0) else P(), tree)

    P_PARAMS, P_OPT = _dp_sharded(_struct[0]), _dp_sharded(_struct[1])

params, opt_state, scaler_state = jax.jit(jax.shard_map(
    _init_all, mesh=mesh, in_specs=(spec, spec),
    out_specs=(P_PARAMS, P_OPT, P()), check_vma=False))(ids, labels)
# model size from the UNSHARDED tree shapes (eval_shape inside the
# mesh context, nothing materialized): under zero3 the live `params`
# leaves are 1/dp flat shards, and a shard count would deflate the
# flops claim dp-fold


def _param_shapes(ids, labels):
    return init_params(jax.random.PRNGKey(0),
                       {"ids": ids[0], "labels": labels[0]})


n_params = sum(
    int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(
        jax.eval_shape(jax.shard_map(
            _param_shapes, mesh=mesh, in_specs=(spec, spec),
            out_specs=P(), check_vma=False), ids, labels)))

# bucket count resolved AT THE PAYLOAD and pinned (or popped) via
# the one-home helper — the same discipline as profile_overlap, one
# implementation (apex_tpu.overlap.pin_overlap_buckets_env)
OVERLAP_BUCKETS = overlap_mod.pin_overlap_buckets_env(
    GRAD_OVERLAP, nelems=n_params)

TRACER = Tracer(K)
# nelems: the table tier resolves in the stamp exactly as it does at
# the step's own trace time — a table-driven compressed run must
# stamp; axes: `hierarchical` reports whether the two-stage path
# actually ENGAGED on this mesh (an env=1 run over unfactored dp runs
# the flat collective and must not stamp otherwise)
snap = collectives.snapshot(
    nelems=n_params,
    axes=dp_axes)
print(f"params: {n_params/1e6:.2f}M  dp={dp_decl}  "
      f"scheme={snap['scheme']}  hierarchical={snap['hierarchical']}  "
      f"({K}-step lax.scan, dispatch overhead "
      f"{TRACER.overhead_ms:.1f} ms subtracted)")

# ---------------------------------------------------------- comm stamp
# per-step collective payload at jaxpr cost: one step traced (not the
# K-scan — no division needed), size-1 axes filtered like
# minimal.training_comm_bytes (their collectives move nothing)


_axis_sizes = {PIPELINE_AXIS: 1, TENSOR_AXIS: 1,
               **dict(zip(dp_names, dp_sizes))}


def _comm_bytes():
    # a FRESH closure per trace: the comm knobs resolve at trace time,
    # and jax caches traces by function identity — reusing one wrapped
    # fn would serve the compressed jaxpr to the disabled() twin
    def one_step(p, o, ss, ids, labels):
        return step(p, o, ss, {"ids": ids, "labels": labels})[3]

    wrapped = jax.shard_map(one_step, mesh=mesh,
                            in_specs=(P_PARAMS, P_OPT, P(), spec, spec),
                            out_specs=P(), check_vma=False)
    raw = costs.comm_from_jaxpr(jax.make_jaxpr(wrapped)(
        params, opt_state, scaler_state, ids, labels))
    return costs.wire_bytes(raw, _axis_sizes)


comm = comm_compression = None
try:
    comm = _comm_bytes()
    if snap.get("scheme") or snap.get("hierarchical"):
        with collectives.disabled():
            twin = _comm_bytes()
        comm_compression = costs.comm_compression_block(snap, twin)
    comm_s = " ".join(f"{ax}={int(v)}B" for ax, v in sorted(comm.items()))
    print(f"comm bytes/step [{comm_s or 'none: all axes size 1'}]"
          + (f"  uncompressed twin "
             f"[{' '.join(f'{ax}={int(v)}B' for ax, v in sorted(comm_compression['uncompressed_bytes_per_axis'].items()))}]"
             if comm_compression
             and comm_compression.get("uncompressed_bytes_per_axis")
             else ""))
except Exception as e:  # accounting must never sink the measurement
    print(f"profile_comm: comm accounting failed "
          f"({type(e).__name__}: {str(e)[:80]})")

# -------------------------------------------------------- measured row
model_flops_fb = 6 * n_params * M * global_mb * S


def make_step_body(eps, ids, labels):
    def body(carry, _):
        p, o, ss = carry
        np_, no, nss, loss = step(p, o, ss,
                                  {"ids": ids, "labels": labels})[:4]
        # eps(=0 at runtime, traced) chains iterations (§0 protocol)
        np_ = jax.tree_util.tree_map(
            lambda a: a + eps.astype(a.dtype) * loss.astype(a.dtype), np_)
        return (np_, no, nss), loss
    return body


span = TRACER.scan_time(
    "dp grad sync step", make_step_body,
    (params, opt_state, scaler_state), (ids, labels),
    wrap=lambda run: jax.shard_map(
        run, mesh=mesh,
        in_specs=((P_PARAMS, P_OPT, P()), P(), spec, spec),
        out_specs=((P_PARAMS, P_OPT, P()), P()), check_vma=False),
    flops_per_iter=model_flops_fb,
    capture_cost=costs.enabled(default=not SMOKE),
    comm=comm, comm_compression=comm_compression,
    extra={"n_params": n_params, "dp": str(dp_decl),
           "scheme": snap["scheme"],
           "hierarchical": snap["hierarchical"],
           "zero_stage": ZERO_STAGE})
print(span.format_row(TRACER.peak_flops))
if span.seconds:
    toks = M * global_mb * S
    print(f"{'':24s} -> {toks/span.seconds:.0f} tok/s")

TRACER.flush_ledger("profile_comm",
                    extra={"n_params": n_params, "dp": str(dp_decl),
                           # the overlap claim block (check 10): the
                           # grad schedule this row's step ran under
                           "overlap": {"grad": GRAD_OVERLAP,
                                       "buckets": OVERLAP_BUCKETS},
                           # the parallel claim block (check 11): the
                           # sharding program this row's step ran under
                           # — pinned above, both directions checked
                           "parallel": {"zero_stage": ZERO_STAGE}})
