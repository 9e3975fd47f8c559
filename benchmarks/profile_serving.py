"""Serving-path measurement: decode tokens/s + per-request latency.

Two evidence classes in one Tracer run (ISSUE 10):

* **decode step (batch full)** — the §0 protocol (K chained decode
  steps in ONE ``lax.scan`` dispatch, traced-eps chain, overhead
  subtracted) over a full slot batch: the steady-state decode
  throughput headline, with a validated cost block captured off the
  same program.
* **trace replay** — the host-side serving loop (admit → prefill →
  decode → evict, ``apex_tpu.serving.ServingEngine``) replayed over
  the committed synthetic traffic trace, per-dispatch like production
  serving actually runs: per-request p50/p99 latency plus end-to-end
  tokens/s. The replay is host-clocked (each decode dispatch is a
  round trip — exactly the per-token cost a user sees), so its
  tokens/s is the honest lower line under the scan row's upper line.

* **SLO replay** (ISSUE 11) — the same replay with the request
  LIFECYCLE log on (``apex_tpu.serving.lifecycle``): the validated
  ``slo`` ledger block — TTFT/per-token p50/p99, goodput (tokens of
  SLO-attaining requests only), SLO attainment, arrival process +
  offered load, queue/KV-page high-waters — under a seeded
  Poisson/diurnal trace (``APEX_SERVE_ARRIVALS``), judged against
  the pinned thresholds (``APEX_SERVE_SLO_TTFT_MS`` /
  ``APEX_SERVE_SLO_TPOT_MS``) with the scheduler policy pinned too
  (``APEX_SERVE_SCHED``). The replay's host slice (run wall minus
  device dispatch time, per decode round) lands as the cost block's
  ``overlap_bound`` stamp — the ROADMAP 4c/4d gap, measured.

The ledger record carries the validated ``serving`` block
``{tokens_per_s, p50_ms, p99_ms, trace_id, kv_pages}`` and the
``slo`` block (``ledger.validate_record``) and PINS every shaping
knob — ``APEX_SERVE_WEIGHT_QUANT``,
``APEX_SERVE_KV_QUANT``, ``APEX_SERVE_KV_SWAP`` (check 8),
``APEX_SERVE_SLO_TTFT_MS``, ``APEX_SERVE_SLO_TPOT_MS``,
``APEX_SERVE_ARRIVALS``, ``APEX_SERVE_SCHED`` (check 9) — at their
RESOLVED values before the write, so every serving row is citable
under ``tools/check_bench_labels.py`` by construction.

``--smoke`` / ``APEX_BENCH_SMOKE=1`` is the CPU sanity mode.
"""

import os
import sys
import time

if "--smoke" in sys.argv[1:]:
    os.environ["APEX_BENCH_SMOKE"] = "1"

import numpy as np
import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from benchmarks._smoke import smoke_mode  # noqa: E402

SMOKE = smoke_mode("APEX_BENCH_SMOKE")

from benchmarks._timing import Tracer  # noqa: E402
from apex_tpu.dispatch import tiles as _tiles  # noqa: E402
from apex_tpu.serving import (  # noqa: E402
    ServingEngine,
    synthetic_trace,
)
from apex_tpu.serving import lifecycle  # noqa: E402
from apex_tpu.serving import model as smodel  # noqa: E402
from apex_tpu.serving import prefix_cache as prefix_mod  # noqa: E402
from apex_tpu.serving import quant as quant_mod  # noqa: E402
from apex_tpu.serving import sampling as sampling_mod  # noqa: E402
from apex_tpu.serving import scheduler as sched_mod  # noqa: E402
from apex_tpu.serving import speculative as spec_mod  # noqa: E402
from apex_tpu.telemetry import costs as _costs  # noqa: E402
from apex_tpu.transformer.testing import TransformerConfig  # noqa: E402

K = 2 if SMOKE else 32

if SMOKE:
    cfg = TransformerConfig(
        hidden_size=64, num_layers=2, num_attention_heads=4,
        vocab_size=256, max_position_embeddings=64,
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=True)
    SLOTS, PS, PAGES, MAX_SEQ, PRE_LEN = 4, 16, 24, 64, 64
else:
    cfg = TransformerConfig(
        hidden_size=768, num_layers=12, num_attention_heads=12,
        vocab_size=50304, max_position_embeddings=1024,
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=True)
    SLOTS, PS, PAGES, MAX_SEQ, PRE_LEN = 8, 128, 72, 1024, 512

# ---------------------------------------------------------------- pins
# Resolve the serving weight-quant knob and pin it into the
# environment BEFORE anything traces: the ledger record's knobs then
# carry exactly the values the measured program ran under (check 8),
# and the engine's own resolution (env > table > built-in) reads the
# very same pins — label and program cannot drift apart.
WQ = quant_mod.resolve()
os.environ["APEX_SERVE_WEIGHT_QUANT"] = "1" if WQ else "0"

# ...and the SLO label's knobs (ISSUE 11, check 9): arrival process,
# thresholds and scheduler policy resolved ONCE here and pinned back
# into the env, so the record's knobs name exactly the workload and
# the judgment the slo block carries — label and claim are one thing.
ARRIVALS = _tiles.env_choice("APEX_SERVE_ARRIVALS",
                             sched_mod.ARRIVALS) or "poisson"
os.environ["APEX_SERVE_ARRIVALS"] = ARRIVALS
POLICY = sched_mod.resolve_policy()
os.environ["APEX_SERVE_SCHED"] = POLICY

# ...and the GENERATION knobs (ISSUE 13, check 8 teeth): speculative
# draft length, sampling, prefix cache — resolved once, pinned back
# into the env BEFORE the engines build (they re-resolve from these
# very pins), so the record's knobs name exactly the programs the
# replay ran.
SPEC_K = spec_mod.resolve_k()
os.environ["APEX_SPEC_DECODE"] = str(SPEC_K)
SAMPLING = sampling_mod.resolve()
os.environ["APEX_SERVE_SAMPLING"] = "1" if SAMPLING else "0"
PREFIX = prefix_mod.resolve()
os.environ["APEX_SERVE_PREFIX_CACHE"] = "1" if PREFIX else "0"
# ...and the host/device overlap knob (ISSUE 14, check 10): the
# replay's host slice — the overlap_bound stamp below — is a FUNCTION
# of the engine schedule (serial vs deferred-fetch pipelined), so the
# resolved value is pinned and claimed like every other shaping knob.
# Resolution mirrors the engine's (spec engaged -> preference falls
# back to serial).
from apex_tpu import overlap as overlap_mod  # noqa: E402

SERVE_OVERLAP = overlap_mod.resolve_serve_overlap(spec_k=SPEC_K)
os.environ["APEX_SERVE_OVERLAP"] = "1" if SERVE_OVERLAP else "0"
# ...and the serving RESILIENCE knobs (ISSUE 15, check 9 teeth):
# admission bound, deadline shedder, KV-pressure preemption, dispatch
# watchdog — resolved once and pinned back BEFORE the engines build
# (they re-resolve from these pins), so the record's knobs name
# exactly the admission/preemption/recovery behavior the replay ran
# under.
from apex_tpu.serving import resilience as serve_res  # noqa: E402

ADMIT = serve_res.resolve_admit()
os.environ["APEX_SERVE_ADMIT"] = str(ADMIT)
SHED = serve_res.resolve_shed()
os.environ["APEX_SERVE_SHED"] = "1" if SHED else "0"
PREEMPT = serve_res.resolve_preempt()
os.environ["APEX_SERVE_PREEMPT"] = "1" if PREEMPT else "0"
RECOVER = serve_res.resolve_recover()
os.environ["APEX_SERVE_RECOVER"] = "1" if RECOVER else "0"
# ...and the TP width (ISSUE 18, check 11): the Megatron column/row
# NamedShardings re-partition the SAME two serving programs over a
# (tp,) mesh, so the resolved width is pinned back (the engine
# re-resolves from this pin) and claimed in the `parallel` block for
# both-direction agreement. tp x weight_quant COMPOSES (ISSUE 20
# satellite): the int8 decode records shard along the same Megatron
# split (tp.qparams_shardings), so neither knob drops the other.
from apex_tpu.serving import tp as tp_mod  # noqa: E402

SERVE_TP = tp_mod.resolve_serve_tp(n_heads=cfg.num_attention_heads)
os.environ["APEX_SERVE_TP"] = str(SERVE_TP)
# ...and the KV-tier knobs (ISSUE 20, check 8 teeth): int8 KV cache
# and the host swap tier — resolved once, pinned back BEFORE the
# engines build (they re-resolve from these pins), so the record's
# knobs name exactly the cache codec and preemption-restore path the
# replay ran. Resolution mirrors the engine's pairing: the swap
# preference falls back off without KV-pressure preemption (nothing
# ever preempts, so there is nothing to bank).
from apex_tpu.serving import kv_tier as kv_tier_mod  # noqa: E402

KV_QUANT = kv_tier_mod.resolve_kv_quant()
os.environ["APEX_SERVE_KV_QUANT"] = "1" if KV_QUANT else "0"
KV_SWAP = kv_tier_mod.resolve_kv_swap()
if KV_SWAP and not PREEMPT:
    KV_SWAP = False
os.environ["APEX_SERVE_KV_SWAP"] = "1" if KV_SWAP else "0"
# ...and the multi-token decode block size (ISSUE 17, check 8): K
# decode steps per dispatch amortize the ~65 ms relay floor — a
# DIFFERENT compiled decode program, so the resolved K is pinned and
# rides the slo block (decode_block_k) for both-direction agreement.
# Resolution mirrors the engine's env-vs-env pairing: speculative
# decode engaged -> the K preference falls back to 1 (the committed
# measurement backs the spec layer; the serving_multitok A/B rung
# sets APEX_SERVE_DECODE_K with spec off).
DECODE_K = smodel.resolve_decode_k()
if SPEC_K and DECODE_K > 1:
    DECODE_K = 1
os.environ["APEX_SERVE_DECODE_K"] = str(DECODE_K)
SLO_TTFT_MS = lifecycle.env_ms("APEX_SERVE_SLO_TTFT_MS",
                               lifecycle.DEFAULT_SLO_TTFT_MS)
SLO_TPOT_MS = lifecycle.env_ms("APEX_SERVE_SLO_TPOT_MS",
                               lifecycle.DEFAULT_SLO_TPOT_MS)
# repr round-trips a float exactly ("%g" truncates to 6 significant
# digits — a 1000.125 threshold would pin as "1000.12" and check 9
# would flag the harness's own record as label drift)
os.environ["APEX_SERVE_SLO_TTFT_MS"] = repr(SLO_TTFT_MS)
os.environ["APEX_SERVE_SLO_TPOT_MS"] = repr(SLO_TPOT_MS)

engine = ServingEngine(cfg, num_slots=SLOTS, page_size=PS,
                       num_pages=PAGES, max_seq=MAX_SEQ,
                       prefill_len=PRE_LEN)
IMPL = engine.decode_attn_impl
n_params = sum(x.size for x in jax.tree_util.tree_leaves(engine.params))
TRACER = Tracer(K)
print(f"serving: {n_params / 1e6:.1f}M params, {SLOTS} slots, "
      f"{PAGES} pages x {PS}, quant={'int8' if WQ else 'off'}, "
      f"kv={'int8' if KV_QUANT else 'off'}"
      f"{'+swap' if KV_SWAP else ''}, "
      f"decode-attn={IMPL}, sampling={'on' if SAMPLING else 'off'}, "
      f"spec={SPEC_K or 'off'}, "
      f"prefix={'on' if PREFIX else 'off'}   (method: {K}-step decode "
      f"scan, dispatch overhead {TRACER.overhead_ms:.1f} ms subtracted)")

# ------------------------------------------- row 1: decode scan (full)
# Fill every slot (prompt + one engine step), then harvest the cache /
# page-table arrays for the K-step scan. max_new covers the scan range
# so the page tables stay valid as lengths advance.
from apex_tpu.serving.scheduler import Request  # noqa: E402

rs = np.random.RandomState(0)
warm_reqs = [
    Request(rid=1000 + i,
            prompt=[int(t) for t in rs.randint(0, cfg.vocab_size, 8)],
            max_new_tokens=K + 4)
    for i in range(SLOTS)]
for r in warm_reqs:
    engine.submit(r)
engine.step()       # the round's prefill half,
engine.step()       # and its decode half
tokens0, lengths0 = engine.scheduler.decode_inputs()
pt0 = np.asarray(engine.scheduler.page_table_rows(), np.int32)
qparams = engine.qparams


def make_decode_scan(eps, pt):
    def body(carry, _):
        cache, tokens, lengths = carry
        # consume eps so warm and timed dispatches differ in a traced
        # value (the §0 result-caching rule); semantically zero
        tokens = tokens + (eps * 0.0).astype(jnp.int32)
        cache, nxt, logits = smodel.decode_step(
            engine.served_params, cache, tokens, lengths, pt, cfg=cfg,
            qparams=qparams, interpret=engine.interpret)
        if SAMPLING:
            # the pinned program includes the sampling ops (greedy
            # lane params — exact argmax) so the scan row times the
            # SAME decode program the sampling-on replay dispatches;
            # label and program stay one thing (check 8)
            nxt = sampling_mod.sample_tokens(
                logits, jnp.zeros((SLOTS,), jnp.float32),
                jnp.zeros((SLOTS,), jnp.int32),
                jnp.ones((SLOTS,), jnp.float32),
                jnp.zeros((SLOTS, 2), jnp.uint32),
                jnp.zeros((SLOTS,), jnp.int32), lengths > 0)
        return (cache, nxt, lengths + 1), nxt[0]
    return body


decode_flops = 2 * n_params * SLOTS
span = TRACER.scan_time(
    "decode step (batch full)", make_decode_scan,
    (engine.cache, jnp.asarray(tokens0, dtype=jnp.int32),
     jnp.asarray(lengths0, dtype=jnp.int32)),
    (jnp.asarray(pt0),), flops_per_iter=decode_flops,
    capture_cost=_costs.enabled(default=not SMOKE), on_fail="span")
print(span.format_row(TRACER.peak_flops))
scan_tps = None
if span.seconds:
    scan_tps = SLOTS / span.seconds
    print(f"{'':28s} -> {scan_tps:.0f} tok/s (scan upper line)")

# ----------------------------- row 2: trace replay + the slo block
n_req = 6 if SMOKE else 32
# with the prefix cache armed, the trace models the workload the
# cache exists for: one shared system prompt per fleet (content-
# hashed into the tr- id, so the label names the prepended trace)
sys_prompt = None
if PREFIX:
    # span one full page + a partial tail so BOTH sharing modes
    # (by-reference full pages, copy-on-write tail) are measured
    sys_len = PS + PS // 2
    sys_prompt = [int(t) for t in np.random.RandomState(123)
                  .randint(0, cfg.vocab_size, sys_len)]
new_hi = min(24, MAX_SEQ - 32)
prompt_hi = min(24, PRE_LEN // 2)
if sys_prompt:
    # the prepended system prompt rides inside the same max_seq /
    # prefill_len budgets — shrink the drawn part so no request
    # can overflow the per-slot page table
    prompt_hi = max(4, min(prompt_hi,
                           MAX_SEQ - new_hi - len(sys_prompt),
                           PRE_LEN - len(sys_prompt)))
trace, trace_id = synthetic_trace(
    seed=7, n_requests=n_req, vocab=cfg.vocab_size,
    prompt_lo=4, prompt_hi=prompt_hi,
    new_lo=4, new_hi=new_hi,
    mean_interarrival=0.5, arrival=ARRIVALS,
    system_prompt=sys_prompt)
# lifecycle collection ON for the replay engine only (the scan
# row above measured the device program, not host bookkeeping);
# reset to the env default right after the ctor captured the gate
lifecycle.enable()
try:
    replay = ServingEngine(cfg, params=engine.params,
                           num_slots=SLOTS, page_size=PS,
                           num_pages=PAGES, max_seq=MAX_SEQ,
                           prefill_len=PRE_LEN, policy=POLICY)
finally:
    lifecycle.reset_enabled()
# apexlint: disable=APX004 — host-clocked SLO replay: the host wall IS the measured quantity (slo block); the decode headline rides Tracer
t0 = time.perf_counter()
done = replay.run_trace(trace)
# apexlint: disable=APX004 — host-clocked SLO replay: the host wall IS the measured quantity (slo block); the decode headline rides Tracer
wall = time.perf_counter() - t0
lats = sorted((r.finish_wall - r.enqueue_wall) * 1e3 for r in done
              if r.finish_wall and r.enqueue_wall)
p50 = lats[len(lats) // 2]
p99 = lats[min(len(lats) - 1, int(len(lats) * 0.99))]
replay_tps = replay.tokens_generated / wall
gen = replay.generation_stats()

def _r4(v):
    return None if v is None else round(v, 4)

serving_block = {
    "tokens_per_s": round(replay_tps, 2),
    "scan_tokens_per_s": None if scan_tps is None
    else round(scan_tps, 2),
    "p50_ms": round(p50, 2), "p99_ms": round(p99, 2),
    "trace_id": trace_id, "kv_pages": PAGES,
    "requests": len(done),
    "decode_steps": replay.decode_steps,
    # decode_steps counts DISPATCHES (the ~65 ms relay unit);
    # tokens/dispatch is the K-block amortization the
    # serving_multitok rung (ISSUE 17) exists to measure
    "tokens_generated": replay.tokens_generated,
    # generation economics (ISSUE 13): None-when-disabled —
    # degradation, never omission (check 8 refuses a non-None
    # rate whose selecting knob is unpinned or off)
    "spec_acceptance_rate": _r4(gen["spec_acceptance_rate"]),
    "draft_len": _r4(gen["draft_len"]),
    "prefix_hit_rate": _r4(gen["prefix_hit_rate"]),
}
# KV-tier economics (ISSUE 20): None-when-disabled like the
# generation rates above — check 8 refuses a non-None value
# whose selecting knob is unpinned or off
serving_block.update({k: (_r4(v) if k == "swap_rate" else v)
                      for k, v in replay.kv_tier_rates().items()})
print(f"{'trace replay':28s} {len(done)} req, "
      f"{replay.tokens_generated} tok in {wall:.2f}s -> "
      f"{replay_tps:.0f} tok/s, p50 {p50:.1f} ms, p99 {p99:.1f} ms "
      f"[{trace_id}]")
gen_bits = []
if serving_block["spec_acceptance_rate"] is not None:
    gen_bits.append(
        f"spec acceptance {serving_block['spec_acceptance_rate']:.0%}"
        f" over {replay.verify_calls} verify call(s), mean draft "
        f"{serving_block['draft_len']:g}")
if serving_block["prefix_hit_rate"] is not None:
    gen_bits.append(
        f"prefix hit {serving_block['prefix_hit_rate']:.0%}")
if gen_bits:
    print(f"{'generation':28s} {', '.join(gen_bits)}")
assert replay.decode_cache_size() == 1, (
    "decode step recompiled during the trace — the scheduler "
    "changed a shape (jaxpr-stability contract broken)")
assert replay.prefill_cache_size() <= 1, (
    "prefill program compiled more than once — a speculative "
    "verify batch took a third compiled program (ISSUE 13 "
    "contract broken)")
order_problems = replay.events.validate_order()
assert not order_problems, (
    "lifecycle event-order invariant broken", order_problems)
slo_block = lifecycle.slo_block(
    done, wall, ttft_ms=SLO_TTFT_MS, tpot_ms=SLO_TPOT_MS,
    arrival_process=ARRIVALS,
    offered_load=sched_mod.offered_load(trace),
    log=replay.events, resilience=replay.resilience_rates(),
    decode_block_k=replay.decode_k)
print(f"{'slo (' + ARRIVALS + ')':28s} "
      f"ttft p50/p99 {slo_block['ttft_p50_ms']}/"
      f"{slo_block['ttft_p99_ms']} ms, per-token p50/p99 "
      f"{slo_block['per_token_p50_ms']}/"
      f"{slo_block['per_token_p99_ms']} ms, goodput "
      f"{slo_block['goodput_tok_s']} tok/s, attainment "
      f"{slo_block['slo_attainment']:.0%} "
      f"(ttft<={SLO_TTFT_MS:g}ms tpot<={SLO_TPOT_MS:g}ms), "
      f"qmax={slo_block['max_queue_depth']} "
      f"kv_hw={slo_block['kv_page_high_water']}/{PAGES}")
res_bits = []
if slo_block["shed_rate"] is not None:
    res_bits.append(f"shed {slo_block['shed_rate']:.0%}")
if slo_block["preempt_rate"] is not None:
    res_bits.append(f"preempt {slo_block['preempt_rate']:.0%}")
if slo_block["degraded_rounds"] is not None:
    res_bits.append(
        f"degraded rounds {slo_block['degraded_rounds']}")
if res_bits:
    print(f"{'resilience':28s} {', '.join(res_bits)} "
          f"(admit={ADMIT or 'off'}, {len(replay.rejected)} "
          f"rejected)")
# the measured host slice of the serving loop, per decode round
# (run wall minus device dispatch time) -> the cost block's
# overlap_bound stamp: what perfect host/device overlap
# (ROADMAP 4c) could hide behind the decode dispatch
if replay.decode_steps:
    host_ms = max(0.0, (wall - replay.device_dispatch_s)
                  / replay.decode_steps * 1e3)
    base = TRACER.cost if TRACER.cost is not None \
        else _costs.null_block()
    TRACER.cost = _costs.attach_overlap(base, host_ms=host_ms)
    ob = TRACER.cost["overlap_bound"]
    print(f"{'overlap bound':28s} host {ob['host_ms']:.2f} "
          f"ms/step vs compute floor "
          f"{'?' if ob['compute_floor_ms'] is None else ob['compute_floor_ms']} ms")

rid = TRACER.flush_ledger("profile_serving", extra={
    "serving": serving_block,
    "slo": slo_block,
    # the overlap claim block (ISSUE 14): which engine schedule the
    # replay's host slice was measured under — check 10 pin-matches
    # it against the record's knobs
    "overlap": {"serve": "1" if SERVE_OVERLAP else "0"},
    # the parallel claim block (ISSUE 18): which mesh width the replay's
    # programs were partitioned over — check 11 pin-matches it against
    # the record's APEX_SERVE_TP pin, both directions
    "parallel": {"tp": SERVE_TP},
    "config": {"slots": SLOTS, "page_size": PS, "pages": PAGES,
               "max_seq": MAX_SEQ, "prefill_len": PRE_LEN,
               "params_m": round(n_params / 1e6, 1),
               "weight_quant": WQ, "decode_impl": IMPL,
               "arrivals": ARRIVALS, "policy": POLICY,
               "sampling": SAMPLING, "spec_decode": SPEC_K,
               "prefix_cache": PREFIX,
               "slo_ttft_ms": SLO_TTFT_MS,
               "slo_tpot_ms": SLO_TPOT_MS,
               "admit": ADMIT, "shed": SHED, "preempt": PREEMPT,
               "recover": RECOVER, "decode_k": DECODE_K,
               "kv_quant": KV_QUANT, "kv_swap": KV_SWAP}})
if rid:
    print(f"ledger: {rid}")
