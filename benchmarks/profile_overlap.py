"""Overlap-subsystem A/B harness (ISSUE 14, ROADMAP item 4).

Three rows per run, one per overlap path, each measured UNDER THE
RESOLVED KNOBS and pinned back into the environment before the ledger
write (the profile_serving check-8 discipline, here check 10), so the
A/B is two runs — everything off (terminal grad sync, synchronous
feed, serial serving loop) vs ``APEX_OVERLAP_GRAD=bucketed
APEX_PREFETCH=2 APEX_SERVE_OVERLAP=1`` — whose records differ ONLY in
the pinned schedule:

* **dp grad sync step** — the §0 Tracer K-scan of the minimal-GPT
  data-parallel train step (the profile_comm program) under the
  resolved ``APEX_OVERLAP_GRAD``, with the jaxpr-level
  ``costs.collective_schedule`` verdict (interleaved vs terminal,
  judged on the dp axes) stamped next to the time. Single-chip honest
  label: dp == 1 bounds the TAG/SCHEDULE overhead only (nothing to
  overlap on one chip — like the grad_comm rung, the win needs the
  pod-slice window); smoke mode runs a real dp=8 virtual mesh.
* **input pipeline** — a host-clocked per-dispatch feed loop (batch
  t+1 staged while step t runs) under the resolved ``APEX_PREFETCH``
  depth, vs the measured per-batch staging wall
  (``overlap.prefetch.staging_seconds`` — the ``host_ms`` the
  synchronous baseline pays and the pipeline hides).
* **serving replay** — the profile_serving trace replay under the
  resolved ``APEX_SERVE_OVERLAP`` (serial vs deferred-fetch pipelined
  engine), its host slice stamped into ``costs.overlap_bound`` like
  profile_serving's.

The record carries the ``overlap`` claim block ``{grad, buckets,
prefetch, serve}`` + ``collective_schedule`` verdicts;
``tools/check_bench_labels.py`` check 10 refuses citations whose
pins disagree with the claim. All defaults OFF (measured-dispatch
rule; PERF.md §2 queues the device rows).

``--smoke`` / ``APEX_BENCH_SMOKE=1`` is the CPU sanity mode (8 virtual
devices).
"""

import os
import sys

if "--smoke" in sys.argv[1:]:
    os.environ["APEX_BENCH_SMOKE"] = "1"

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

# virtual devices BEFORE backend init: the smoke A/B drives a real dp>1
# mesh (same mechanism as profile_comm.py).
# apexlint: disable=APX002 — raw on purpose: XLA_FLAGS must be staged
# before ANY apex_tpu import loads jax, so the env_flag helper (whose
# import executes the package __init__) is not usable yet
if os.environ.get("APEX_BENCH_SMOKE") == "1":
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

from benchmarks._smoke import smoke_mode  # noqa: E402

SMOKE = smoke_mode("APEX_BENCH_SMOKE")

import time  # noqa: E402

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh, PartitionSpec as P  # noqa: E402

from benchmarks._timing import Tracer, bench_k, sync  # noqa: E402

from apex_tpu import overlap as overlap_mod  # noqa: E402
from apex_tpu.overlap import prefetch as prefetch_mod  # noqa: E402
from apex_tpu.serving import ServingEngine, synthetic_trace  # noqa: E402
from apex_tpu.telemetry import costs as _costs  # noqa: E402
from apex_tpu.transformer.parallel_state import (  # noqa: E402
    PIPELINE_AXIS,
    TENSOR_AXIS,
)
from apex_tpu.transformer.testing import TransformerConfig  # noqa: E402
from apex_tpu.transformer.testing.minimal import (  # noqa: E402
    dp_axes_of,
    dp_axis_arg,
    gpt_train_step_fn,
    make_gpt_fns,
    toy_batch,
)

K = bench_k(SMOKE)

# ---------------------------------------------------------------- pins
# Resolve every overlap knob ONCE, pin the resolved values back into
# the environment BEFORE anything traces (the ledger record's knobs
# then carry exactly what the measured programs ran under — check 10),
# and build the claim block the record stamps next to its
# overlap_bound. An unpinned overlap row cannot be cited.
GRAD_MODE = overlap_mod.pin_grad_overlap_env()
PREFETCH_DEPTH = overlap_mod.resolve_prefetch()
os.environ["APEX_PREFETCH"] = str(PREFETCH_DEPTH)
# the serve-overlap resolution MIRRORS the engine's: a stale
# APEX_SPEC_DECODE export makes the engine fall back to the serial
# round, and the record must claim the schedule the replay actually
# ran — not the one a spec-blind resolve would have picked
from apex_tpu.serving import speculative as spec_mod  # noqa: E402

SPEC_K = spec_mod.resolve_k()
SERVE_OVERLAP = overlap_mod.resolve_serve_overlap(spec_k=SPEC_K)
os.environ["APEX_SERVE_OVERLAP"] = "1" if SERVE_OVERLAP else "0"

# ------------------------------------------------- dp grad sync row
# pp=1 / tp=1, every device to dp (the profile_comm shape): the ONLY
# collectives in the program are the grad sync — the schedule verdict
# needs no twin to be meaningful.
devices = jax.devices()
N = len(devices)
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
dp_size, dp_names, dp_sizes = dp_axes_of(N)
mesh = Mesh(np.asarray(devices).reshape(1, *dp_sizes, 1),
            (PIPELINE_AXIS, *dp_names, TENSOR_AXIS))
dp_axes = dp_axis_arg(dp_names)
spec = P(None, dp_axes)

_, init_params = make_gpt_fns(cfg, 1)
step, tx, scaler = gpt_train_step_fn(cfg, 1, M, dp_axes=dp_axes)

batch = toy_batch(cfg.vocab_size, M, MBS * dp_size, S)
ids, labels = batch["ids"], batch["labels"]


def _init_all(ids, labels):
    params = init_params(jax.random.PRNGKey(0),
                         {"ids": ids[0], "labels": labels[0]})
    return params, tx.init(params), scaler.init()


params, opt_state, scaler_state = jax.jit(jax.shard_map(
    _init_all, mesh=mesh, in_specs=(spec, spec),
    out_specs=(P(), P(), P()), check_vma=False))(ids, labels)
n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))

# bucket count resolved AT THE PAYLOAD and pinned (or popped)
# BEFORE anything traces, via the one-home helper shared with
# profile_comm (apex_tpu.overlap.pin_overlap_buckets_env)
BUCKETS = overlap_mod.pin_overlap_buckets_env(GRAD_MODE,
                                              nelems=n_params)

TRACER = Tracer(K)
print(f"params: {n_params/1e6:.2f}M  dp={N}  grad={GRAD_MODE}"
      + (f" buckets={BUCKETS}" if BUCKETS else "")
      + f"  prefetch={PREFETCH_DEPTH}  serve_overlap={SERVE_OVERLAP}  "
      f"({K}-step lax.scan, dispatch overhead "
      f"{TRACER.overhead_ms:.1f} ms subtracted)")

# the jaxpr-level schedule verdict of the measured step, judged on the
# dp axes (costs.collective_schedule — the ISSUE 14 proof surface),
# plus the SAME program's per-step dp payload → envelope comm_ms (the
# overlap_bound comm side must pair with the cost block of the very
# program it describes — pairing it with another row's floor would be
# attribution drift); traced at host cost, never dispatched
SCHEDULE = STEP_COMM = STEP_COMM_MS = None
try:
    def _one_step(p, o, ss, ids, labels):
        return step(p, o, ss, {"ids": ids, "labels": labels})[3]

    _wrapped = jax.shard_map(_one_step, mesh=mesh,
                             in_specs=(P(), P(), P(), spec, spec),
                             out_specs=P(), check_vma=False)
    _jaxpr = jax.make_jaxpr(_wrapped)(params, opt_state, scaler_state,
                                      ids, labels)
    SCHEDULE = _costs.collective_schedule(_jaxpr, axes=dp_names)
    _axis_sizes = {PIPELINE_AXIS: 1, TENSOR_AXIS: 1,
                   **dict(zip(dp_names, dp_sizes))}
    STEP_COMM = _costs.wire_bytes(
        _costs.comm_from_jaxpr(_jaxpr), _axis_sizes)
    STEP_COMM_MS = _costs.comm_ms_from_axis_bytes(
        STEP_COMM, jax.devices()[0].device_kind)
    print(f"{'collective schedule':28s} {SCHEDULE['verdict']} "
          f"({SCHEDULE['collectives']} dp collective(s), "
          f"{SCHEDULE['compute_after_first_collective']} compute eqn(s) "
          f"after the first)")
except Exception as e:  # accounting must never sink the measurement
    print(f"profile_overlap: schedule verdict failed "
          f"({type(e).__name__}: {str(e)[:80]})")

model_flops_fb = 6 * n_params * M * MBS * dp_size * S


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
    f"dp grad sync [{GRAD_MODE}]", make_step_body,
    (params, opt_state, scaler_state), (ids, labels),
    wrap=lambda run: jax.shard_map(
        run, mesh=mesh, in_specs=(P(), P(), spec, spec),
        out_specs=(P(), P()), check_vma=False),
    flops_per_iter=model_flops_fb,
    capture_cost=_costs.enabled(default=not SMOKE),
    comm=STEP_COMM, comm_ms=STEP_COMM_MS,
    extra={"n_params": n_params, "dp": N, "grad_overlap": GRAD_MODE,
           "buckets": BUCKETS, "collective_schedule": SCHEDULE},
    on_fail="span")
print(span.format_row(TRACER.peak_flops))

# ------------------------------------------------ input pipeline row
# A per-dispatch feed loop (one small jitted step per batch, synced
# per dispatch — how a production token pipeline actually runs): with
# APEX_PREFETCH=0 every batch's host→device staging serializes with
# its step; with depth>0 batch t+1 stages while step t executes. The
# loop is host-clocked because the host wall IS the measured quantity
# (the staging serialization the pipeline removes); the per-batch
# staging cost itself is measured separately (staging_seconds) and
# stamped as the record's overlap_bound host_ms.
N_BATCHES = 4 if SMOKE else 16
FB, FS = (2, 128) if SMOKE else (8, 1024)
rs = np.random.RandomState(1)
feed_batches = [rs.randint(0, 1024, (FB, FS)).astype(np.int32)
                for _ in range(N_BATCHES)]
emb = jnp.asarray(rs.randn(1024, 256) * 0.02, jnp.bfloat16)


def _feed_step(w, ids):
    h = jnp.take(w, ids, axis=0)
    return jnp.sum(h.astype(jnp.float32))


feed_step = jax.jit(_feed_step)

PIPE_MS = STAGE_MS = None
try:
    STAGE_MS = prefetch_mod.staging_seconds(feed_batches[0]) * 1e3
    # warm the feed step off the clock (compile + one dispatch)
    sync(feed_step(emb, jax.device_put(feed_batches[0])))
    # apexlint: disable=APX004 — host-clocked feed loop: the staging serialization is the measured quantity; the device rows ride Tracer
    t0 = time.perf_counter()
    for staged in prefetch_mod.prefetch(iter(feed_batches)):
        sync(feed_step(emb, staged))
    # apexlint: disable=APX004 — host-clocked feed loop: the staging serialization is the measured quantity; the device rows ride Tracer
    PIPE_MS = (time.perf_counter() - t0) / N_BATCHES * 1e3
    print(f"{'input pipeline [depth=' + str(PREFETCH_DEPTH) + ']':28s}"
          f" {PIPE_MS:8.2f} ms/batch over {N_BATCHES} dispatches "
          f"(staging {STAGE_MS:.2f} ms/batch)")
except Exception as e:
    print(f"profile_overlap: input pipeline row failed "
          f"({type(e).__name__}: {str(e)[:80]})")

# ------------------------------------------------- serving replay row
# The profile_serving trace replay under the resolved engine schedule
# (serial vs deferred-fetch pipelined); host-clocked for the same
# reason as profile_serving's — the host slice is the claim.
scfg = TransformerConfig(
    hidden_size=64 if SMOKE else 256,
    num_layers=2 if SMOKE else 4,
    num_attention_heads=4 if SMOKE else 8,
    vocab_size=256 if SMOKE else 1024,
    max_position_embeddings=64,
    hidden_dropout=0.0, attention_dropout=0.0,
    apply_query_key_layer_scaling=False, bf16=True)
SERVE_MS = None
serving_block = None
try:
    # warm the serving program set BEFORE the clock (PERF.md §6
    # warm-start discipline): a scratch engine runs a 2-request
    # mini trace so the prefill/decode/page-copy compiles land in
    # the persistent compile cache — the measured engine's own jit
    # compiles are then cache reads on BOTH rungs, instead of
    # overlap_base paying a cold remote compile inside its wall
    # that overlap_on would read back out of the cache
    scratch = ServingEngine(scfg, num_slots=4, page_size=8,
                            num_pages=48, max_seq=64,
                            prefill_len=32)
    warm_trace, _ = synthetic_trace(
        seed=1, n_requests=2, vocab=scfg.vocab_size, prompt_lo=4,
        prompt_hi=8, new_lo=2, new_hi=4, mean_interarrival=0.5)
    scratch.run_trace(warm_trace)
    replay = ServingEngine(scfg, params=scratch.params,
                           num_slots=4, page_size=8,
                           num_pages=48, max_seq=64, prefill_len=32)
    assert replay.overlap == SERVE_OVERLAP, (
        replay.overlap, SERVE_OVERLAP)
    trace, trace_id = synthetic_trace(
        seed=7, n_requests=8 if SMOKE else 24, vocab=scfg.vocab_size,
        prompt_lo=4, prompt_hi=16, new_lo=4, new_hi=24,
        mean_interarrival=0.5)
    # apexlint: disable=APX004 — host-clocked serving replay: the host slice is the measured quantity (profile_serving rule)
    t0 = time.perf_counter()
    done = replay.run_trace(trace)
    # apexlint: disable=APX004 — host-clocked serving replay: the host slice is the measured quantity (profile_serving rule)
    wall = time.perf_counter() - t0
    SERVE_MS = wall / max(1, replay.decode_steps) * 1e3
    host_ms = max(0.0, (wall - replay.device_dispatch_s)
                  / max(1, replay.decode_steps) * 1e3)
    serving_block = {
        "tokens_per_s": round(replay.tokens_generated / wall, 2),
        "scan_tokens_per_s": None,
        "p50_ms": None, "p99_ms": None,
        "trace_id": trace_id, "kv_pages": 48,
        "requests": len(done),
        "decode_steps": replay.decode_steps,
        "spec_acceptance_rate": None, "draft_len": None,
        "prefix_hit_rate": None,
        # the replay's measured host slice per round: it belongs
        # to THIS tiny serving program, so it rides here — never
        # attached to the grad row's cost block, whose floor
        # describes a different program (profile_serving owns the
        # same-program floor/host pairing for the real serving
        # stack)
        "host_ms_per_round": round(host_ms, 3),
    }
    print(f"{'serving replay [' + ('overlap' if SERVE_OVERLAP else 'serial') + ']':28s}"
          f" {SERVE_MS:8.2f} ms/round, host slice "
          f"{host_ms:.2f} ms/round over {replay.decode_steps} "
          f"round(s) [{trace_id}]")
    assert replay.decode_cache_size() == 1
except Exception as e:
    print(f"profile_overlap: serving replay row failed "
          f"({type(e).__name__}: {str(e)[:80]})")

# --------------------------------------------------------- the record
# the claim block check 10 pin-matches: resolved values, one knob set
# per record — the A/B is two rungs, not two rows under one label
OVERLAP_CLAIM = {
    "grad": GRAD_MODE,
    "buckets": BUCKETS,
    "prefetch": str(PREFETCH_DEPTH),
    "serve": "1" if SERVE_OVERLAP else "0",
}
rid = TRACER.flush_ledger("profile_overlap", extra={
    "overlap": OVERLAP_CLAIM,
    "collective_schedule": SCHEDULE,
    "serving": serving_block,
    "pipeline": None if PIPE_MS is None else {
        "ms_per_batch": round(PIPE_MS, 3),
        "staging_ms_per_batch": None if STAGE_MS is None
        else round(STAGE_MS, 3),
        "depth": PREFETCH_DEPTH, "batches": N_BATCHES},
    "config": {"dp": N, "s": S, "microbatches": M,
               "params_m": round(n_params / 1e6, 2)}})
if rid:
    print(f"ledger: {rid}")
