"""Fleet measurement: the multi-replica router replay (ISSUE 19).

Three evidence classes in one Tracer run:

* **policy sweep** — the SAME shared-system-prompt trace replayed
  through a fresh prefix-cache-armed fleet under EACH routing policy
  (``round_robin`` | ``least_loaded`` | ``prefix_affinity``): the
  fleet-wide prefix hit rate becomes a measured function of routing
  policy (``prefix_hit_rate_by_policy`` in the ``router`` block).
  Affinity routes by the same sha1 chain hash the cache keys pages on,
  so requests sharing a prefix land on ONE replica and prefill it once
  per replica instead of once per round-robin stripe — the delta this
  sweep quantifies (PERF.md §2).
* **fleet replay** — the pinned-policy headline: the trace through N
  real ServingEngine replicas under one Router, host-clocked like the
  serving replay (each decode dispatch is a round trip). Yields the
  validated ``router`` ledger block — fleet goodput, utilization
  spread, cross-replica TTFT/TPOT p99 tails, failover/replay/rejection
  accounts (``ledger.validate_record`` teeth).
* **autoscale A/B** — static-N vs :class:`AutoscalePolicy` lagged
  scale-out under the diurnal trace (the arXiv:2011.03641 concurrency
  framing): what the scale-out reaction lag costs in goodput and TTFT
  tail while the parked replica sits out the ramp.

The record PINS both fleet knobs — ``APEX_ROUTE_POLICY`` and
``APEX_ROUTE_REPLICAS`` — at their RESOLVED values before the write
(tools/check_bench_labels.py check 12: block and pins must agree both
directions), so every router row is citable by construction.

``--smoke`` / ``APEX_BENCH_SMOKE=1`` is the CPU sanity mode that also
produced the committed CPU-mesh hit-rate numbers in PERF.md §2.
"""

import os
import sys

if "--smoke" in sys.argv[1:]:
    os.environ["APEX_BENCH_SMOKE"] = "1"

import numpy as np
import jax  # noqa: F401 — backend init before Tracer calibration

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))
from benchmarks._smoke import smoke_mode  # noqa: E402

SMOKE = smoke_mode("APEX_BENCH_SMOKE")

from benchmarks._timing import Tracer  # noqa: E402
from apex_tpu.dispatch import tiles as _tiles  # noqa: E402
from apex_tpu.serving import ServingEngine, synthetic_trace  # noqa: E402
from apex_tpu.serving import lifecycle  # noqa: E402
from apex_tpu.serving import model as smodel  # noqa: E402
from apex_tpu.serving import prefix_cache as prefix_mod  # noqa: E402
from apex_tpu.serving import router as router_mod  # noqa: E402
from apex_tpu.serving import scheduler as sched_mod  # noqa: E402
from apex_tpu.serving.router import (  # noqa: E402
    AutoscalePolicy,
    Router,
    router_block,
)
from apex_tpu.transformer.testing import TransformerConfig  # noqa: E402

K = 2 if SMOKE else 8  # calibration scan length only — the fleet
#                        replay is host-clocked per dispatch

if SMOKE:
    cfg = TransformerConfig(
        hidden_size=64, num_layers=2, num_attention_heads=4,
        vocab_size=256, max_position_embeddings=64,
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=True)
    SLOTS, PS, PAGES, MAX_SEQ, PRE_LEN = 2, 16, 24, 64, 64
else:
    cfg = TransformerConfig(
        hidden_size=768, num_layers=12, num_attention_heads=12,
        vocab_size=50304, max_position_embeddings=1024,
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=True)
    SLOTS, PS, PAGES, MAX_SEQ, PRE_LEN = 4, 128, 48, 512, 256

# ---------------------------------------------------------------- pins
# Resolve BOTH fleet knobs and pin them into the environment BEFORE
# anything runs: the record's knobs then carry exactly the values the
# measured fleet ran under (check 12), and the Router's own resolution
# reads the very same pins — label and program cannot drift apart.
POLICY = router_mod.resolve_route_policy()
os.environ["APEX_ROUTE_POLICY"] = POLICY
N_REPLICAS = router_mod.resolve_route_replicas()
os.environ["APEX_ROUTE_REPLICAS"] = str(N_REPLICAS)
# the workload-shaping knobs the trace rides (informative pins — the
# router block names arrival_process/trace_id itself)
ARRIVALS = _tiles.env_choice("APEX_SERVE_ARRIVALS",
                             sched_mod.ARRIVALS) or "poisson"
os.environ["APEX_SERVE_ARRIVALS"] = ARRIVALS
PREFIX = prefix_mod.resolve()
os.environ["APEX_SERVE_PREFIX_CACHE"] = "1" if PREFIX else "0"

params = smodel.init_gpt_params(cfg)
n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
TRACER = Tracer(K)
print(f"router: {n_params / 1e6:.1f}M params x {N_REPLICAS} replicas "
      f"(shared), {SLOTS} slots, {PAGES} pages x {PS} each, "
      f"policy={POLICY}, arrivals={ARRIVALS} "
      f"(host-clocked fleet replay; calibration overhead "
      f"{TRACER.overhead_ms:.1f} ms)")


def build_fleet(n, *, prefix=None):
    """n interchangeable replicas over the ONE shared param tree —
    required for failover replay parity (greedy decode is a function
    of prompt + params)."""
    return [ServingEngine(cfg, params=params, num_slots=SLOTS,
                          page_size=PS, num_pages=PAGES,
                          max_seq=MAX_SEQ, prefill_len=PRE_LEN,
                          overlap=False, prefix_cache=prefix)
            for _ in range(n)]


def make_trace(arrival, *, seed=7):
    """The shared-system-prompt trace: one system prompt spanning a
    full page + a partial tail (both sharing modes exercised), content-
    hashed into the tr- id so the label names the prepended trace."""
    n_req = 8 if SMOKE else 32
    sys_len = PS + PS // 2
    sys_prompt = [int(t) for t in np.random.RandomState(123)
                  .randint(0, cfg.vocab_size, sys_len)]
    new_hi = min(24, MAX_SEQ - 32)
    prompt_hi = max(4, min(24, PRE_LEN // 2,
                           MAX_SEQ - new_hi - sys_len,
                           PRE_LEN - sys_len))
    return synthetic_trace(
        seed=seed, n_requests=n_req, vocab=cfg.vocab_size,
        prompt_lo=4, prompt_hi=prompt_hi, new_lo=4, new_hi=new_hi,
        mean_interarrival=0.5, arrival=arrival,
        system_prompt=sys_prompt)


import time  # noqa: E402

# -------------------------------------- row 1: the policy hit-rate sweep
# Fresh prefix-armed fleet per policy, same trace content (same seed):
# the fleet hit rate is the only moving part the policy can change.
hit_by_policy = {}
for pol in router_mod.ROUTE_POLICIES:
    fleet = build_fleet(N_REPLICAS, prefix=True)
    rt = Router(fleet, policy=pol)
    trace, sweep_trace_id = make_trace(ARRIVALS)
    rt.run_trace(trace)
    hits = sum(r.engine.prefix.hit_tokens for r in rt.replicas)
    looks = sum(r.engine.prefix.lookup_tokens for r in rt.replicas)
    hit_by_policy[pol] = round(hits / looks, 4) if looks else 0.0
print(f"{'prefix hit-rate sweep':28s} "
      + ", ".join(f"{k}={v:.1%}" for k, v in hit_by_policy.items())
      + f" [{sweep_trace_id}]")

# ------------------------------------ row 2: pinned-policy fleet replay
# Lifecycle collection ON for the headline fleet only: the ONE fleet
# event log covers the full cross-replica routed/failover/replayed
# chain, asserted clean below. Router ctor reads the same gate as the
# engines, so both sit inside the enable window.
lifecycle.enable()
try:
    fleet = build_fleet(N_REPLICAS)
    rt = Router(fleet, policy=POLICY)
finally:
    lifecycle.reset_enabled()
trace, trace_id = make_trace(ARRIVALS)
# apexlint: disable=APX004 — host-clocked fleet replay: the host wall IS the measured quantity (router block); the calibration overhead rides Tracer
t0 = time.perf_counter()
done = rt.run_trace(trace)
# apexlint: disable=APX004 — host-clocked fleet replay: the host wall IS the measured quantity (router block); the calibration overhead rides Tracer
wall = time.perf_counter() - t0
order_problems = rt.events.validate_order()
assert not order_problems, (
    "fleet lifecycle event-order invariant broken", order_problems)
for r in rt.replicas:
    health_problems = router_mod.validate_health(r.history)
    assert not health_problems, (
        f"replica {r.name} health history invalid", health_problems)
block = router_block(rt, done, wall, trace_id=trace_id,
                     arrival_process=ARRIVALS,
                     prefix_hit_rate_by_policy=hit_by_policy)
print(f"{'fleet replay (' + POLICY + ')':28s} "
      f"{block['completed']}/{block['requests']} req in {wall:.2f}s -> "
      f"{block['fleet_goodput_tok_s']} tok/s, util spread "
      f"{block['util_spread']:.1%}, ttft p99 {block['ttft_p99_ms']} ms, "
      f"tpot p99 {block['tpot_p99_ms']} ms [{trace_id}]")
print(f"{'':28s} failovers {block['failovers']}, replayed "
      f"{block['replayed_requests']}, rejected "
      f"fleet/replica {block['rejected_fleet']}/"
      f"{block['rejected_replica']}")

# ------------------------- row 3: static-N vs lagged scale-out (diurnal)
# Same fleet size, same diurnal trace; the lagged fleet starts with one
# replica parked and unparks it only after the load has held above the
# high-water for lag_rounds consecutive rounds — the reaction lag the
# A/B prices (arXiv:2011.03641 concurrency-limit framing).
autoscale_ab = None
if N_REPLICAS > 1:
    ab = {}
    for label, auto in (
            ("static", None),
            ("lagged", AutoscalePolicy(
                min_replicas=N_REPLICAS - 1, high_water=0.5,
                lag_rounds=2 if SMOKE else 8))):
        fleet = build_fleet(N_REPLICAS)
        rt_ab = Router(fleet, policy=POLICY, autoscale=auto)
        dtrace, dtrace_id = make_trace("diurnal", seed=11)
        # apexlint: disable=APX004 — host-clocked A/B: the host wall IS the measured quantity
        a0 = time.perf_counter()
        ab_done = rt_ab.run_trace(dtrace)
        # apexlint: disable=APX004 — host-clocked A/B: the host wall IS the measured quantity
        a_wall = time.perf_counter() - a0
        lats = lifecycle.request_latencies(ab_done)
        ttfts = [x["ttft_s"] * 1e3 for x in lats
                 if x["ttft_s"] is not None]
        ab[label] = {
            "wall_s": round(a_wall, 3),
            "goodput_tok_s": round(
                sum(x["n_out"] for x in lats) / a_wall, 2)
            if a_wall > 0 else None,
            "ttft_p99_ms": None if not ttfts
            else round(lifecycle.percentile(ttfts, 99), 2),
            "rounds": rt_ab.tick,
            "scale_outs": rt_ab.stats["scale_outs"],
        }
    autoscale_ab = dict(ab, trace_id=dtrace_id)
    print(f"{'autoscale A/B (diurnal)':28s} "
          f"static {ab['static']['goodput_tok_s']} tok/s "
          f"(ttft p99 {ab['static']['ttft_p99_ms']} ms) vs lagged "
          f"{ab['lagged']['goodput_tok_s']} tok/s "
          f"(ttft p99 {ab['lagged']['ttft_p99_ms']} ms, "
          f"{ab['lagged']['scale_outs']} scale-out(s)) "
          f"[{dtrace_id}]")

rid = TRACER.flush_ledger("profile_router", extra={
    "router": block,
    # the A/B ride-along (not schema-validated: a comparison row, not
    # a claim block — the citable numbers live in `router`)
    "autoscale_ab": autoscale_ab,
    "config": {"replicas": N_REPLICAS, "slots": SLOTS,
               "page_size": PS, "pages": PAGES, "max_seq": MAX_SEQ,
               "prefill_len": PRE_LEN,
               "params_m": round(n_params / 1e6, 1),
               "policy": POLICY, "arrivals": ARRIVALS,
               "prefix_cache": PREFIX}})
if rid:
    print(f"ledger: {rid}")
