"""Runner ``serve_closed``: ``ServingEngine`` under a closed loop on the
wall clock. ``clients`` callers each send their next request the moment
their last one finished. The runner owns the loop (the engine's own
clock is ticks): one ``engine.step(arrivals=due)`` a round, with every
stamp taken here on ``time.perf_counter`` when the round returns, which
is when a caller can first see its tokens.

Set-up is: weights from the seed, the engine, the two programs compiled
or loaded by the first round, one prefill batch of every size (see
``_warm_prefill_rows``), and the loop run unmeasured until
``ramp_completions`` requests have finished (every lane busy, finishes
staggered). Then the window opens for ``--seconds``. After it closes the
loop keeps running, unmeasured requests and all, until every request
sent inside the window has finished or ``drain_cap_s`` is over; what has
not finished by then counts as failed.
"""

import json
import time

import numpy as np

from perf import oracle, tracing, weights
from perf.traffic_gen import RequestStream


class _Loop:
    def __init__(self, engine, stream, clients):
        from apex_tpu.serving.scheduler import Request

        self._request = Request
        self.engine, self.stream = engine, stream
        self.current = [None] * clients
        self.records, self.rounds = [], []
        self.measuring = False

    def round(self):
        with tracing.span("client.refill"):
            due, now = [], time.perf_counter()
            for c, rec in enumerate(self.current):
                if rec is None or rec["finish"] is not None:
                    prompt, answer = self.stream.next()
                    req = self._request(rid=len(self.records), prompt=prompt,
                                        max_new_tokens=answer)
                    rec = {"req": req, "sent": now, "first": None,
                           "finish": None, "seen": 0,
                           "in_window": self.measuring}
                    self.records.append(rec)
                    self.current[c] = rec
                    due.append(req)
        label = "round.prefill" if due or self.engine.scheduler.queue_depth() \
            else "round.decode"
        t0 = time.perf_counter()
        with tracing.span(label):
            info = self.engine.step(arrivals=due)
        t1 = time.perf_counter()
        if info["shed"]:
            raise RuntimeError(f"the engine shed {info['shed']}")
        new_tokens = 0
        for rec in self.current:
            n = len(rec["req"].out_tokens)
            if n > rec["seen"]:
                new_tokens += n - rec["seen"]
                rec["seen"] = n
                if rec["first"] is None:
                    rec["first"] = t1
                if rec["req"].done() and rec["finish"] is None:
                    rec["finish"] = t1
        pool = self.engine.allocator
        self.rounds.append({"t0": t0, "t1": t1,
                            "prefilled": len(info["prefilled"]),
                            "decoded_slots": info["decoded_slots"],
                            "new_tokens": new_tokens,
                            "pages_live": pool.num_pages - 1
                            - pool.free_count})

    def finished(self):
        return sum(1 for r in self.records if r["finish"] is not None)


def _engine_config(config):
    from apex_tpu.transformer.testing import TransformerConfig

    return TransformerConfig(
        hidden_size=config["n_embd"], num_layers=config["n_layer"],
        num_attention_heads=config["n_head"],
        vocab_size=-(-config["vocab_size"] // 128) * 128,
        max_position_embeddings=config["n_positions"],
        layernorm_epsilon=config["layer_norm_epsilon"],
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=True)


def _warm_prefill_rows(engine, rows):
    """One prefill batch of every number of prompts from 1 to ``rows``.
    The engine picks each batch's first tokens with eager ``jnp`` ops
    shaped by the number of prompts in the batch, so each new count
    compiles half a dozen tiny programs the first time it is seen; met
    first inside the window, that is a compile in the window."""
    from apex_tpu.serving.scheduler import Request

    rid = 0
    for k in range(1, rows + 1):
        batch = []
        for _ in range(k):
            rid -= 1
            batch.append(Request(rid=rid, prompt=[1, 2, 3, 4],
                                 max_new_tokens=1))
        engine.step(arrivals=batch)   # prefilled and done at once
        engine.step()                 # evicted


def _judge(ctx, cfg, params, records):
    """Outside the window, against a path that shares nothing with what
    was timed: for a seeded sample of finished requests, every emitted
    token's float32 oracle logit lies within ``judge_tie_steps`` bfloat16
    steps of the oracle's best logit at that position. Random weights
    give near-flat logits, so the engine's bf16 greedy choice may be a
    near-tie of the oracle's; a wrong kernel or a lower precision picks
    tokens hundreds of steps below the best."""
    mix = ctx.traffic
    done = [r for r in records if r["in_window"] and r["finish"] is not None]
    rs = np.random.RandomState(ctx.seed % 2 ** 32)
    worst, judged = 0.0, 0
    for k in rs.permutation(len(done))[:mix["judge_requests"]]:
        req = done[k]["req"]
        seq = list(req.prompt) + list(req.out_tokens)
        ids = np.zeros(mix["max_total"], np.int32)   # causal: padding after
        ids[:len(seq)] = seq
        best, chosen = oracle.best_and_chosen(cfg, params, ids)
        at = slice(len(req.prompt) - 1, len(seq) - 1)
        steps = (best[at] - chosen[at]) / np.asarray(
            [oracle.bf16_step(b) for b in best[at]])
        worst, judged = max(worst, float(steps.max())), judged + len(steps)
    vocab_ok = all(0 <= t < cfg.vocab_size
                   for r in done for t in r["req"].out_tokens)
    note = {"judged_tokens": judged, "worst_gap_bf16_steps": worst,
            "allowed": mix["judge_tie_steps"]}
    return judged > 0 and vocab_ok and worst <= mix["judge_tie_steps"], note


def _describe(rounds, requests, t_open, pages):
    """Earlier lines for whoever reads a run that came out far off: the
    longest rounds (a stall shows as one round of seconds), how full the
    page pool got, and the first-token waits behind the percentile."""
    longest = sorted(rounds, key=lambda r: r["t0"] - r["t1"])[:5]
    live = [r["pages_live"] for r in rounds]
    waits = sorted(1e3 * (r["first"] - r["sent"]) for r in requests
                   if r["finish"] is not None)
    if not waits:   # a window too short for one request to finish
        return
    print("longest rounds (ms at s into the window, prompts prefilled):",
          [(round(1e3 * (r["t1"] - r["t0"]), 1), round(r["t0"] - t_open, 2),
            r["prefilled"]) for r in longest],
          f"| pages reserved of {pages}: mean {np.mean(live):.1f}, "
          f"max {max(live)}",
          f"| first-token waits ms: mean {np.mean(waits):.1f}, "
          f"median {waits[len(waits) // 2]:.1f}, "
          f"five longest {[round(w, 1) for w in waits[-5:]]}", flush=True)


def run(ctx):
    from apex_tpu.serving import ServingEngine

    mix = ctx.traffic
    cfg = _engine_config(ctx.config)
    seed = ctx.seed % 2 ** 32
    params = weights.gpt_params(cfg, seed)
    engine = ServingEngine(cfg, params=params, seed=seed, **mix["engine"])
    print(f"[{time.perf_counter() - ctx.t_start:.1f} s] engine built",
          flush=True)
    loop = _Loop(engine, RequestStream(mix, ctx.config["vocab_size"], seed),
                 mix["clients"])

    _warm_prefill_rows(engine, mix["warm_prefill_rows"])
    while loop.finished() < mix["ramp_completions"]:
        loop.round()
    programs = (engine.prefill_cache_size(), engine.decode_cache_size())
    print(f"[{time.perf_counter() - ctx.t_start:.1f} s] warm and ramped: "
          f"{len(loop.rounds)} rounds, {loop.finished()} requests",
          flush=True)

    loop.measuring = True
    first_round = len(loop.rounds)
    dispatch0 = engine.device_dispatch_s
    t_open = time.perf_counter()
    if ctx.trace:
        with tracing.window(ctx.trace_dir):
            while time.perf_counter() < t_open + mix["trace_seconds"]:
                loop.round()
    while time.perf_counter() < t_open + ctx.seconds:
        loop.round()
    t_close = time.perf_counter()
    loop.measuring = False
    dispatch_s = engine.device_dispatch_s - dispatch0
    window_rounds = loop.rounds[first_round:]

    in_window = [r for r in loop.records if r["in_window"]]
    while any(r["finish"] is None for r in in_window) \
            and time.perf_counter() < t_close + mix["drain_cap_s"]:
        loop.round()
    t_drained = time.perf_counter()
    engine.flush()

    compiles = ctx.compile_log.between(t_open, t_drained)
    programs_after = (engine.prefill_cache_size(), engine.decode_cache_size())
    engine.cache = None          # room for the oracle's float32 forward
    judged_ok, note = _judge(ctx, cfg, params, loop.records)
    failed = sum(1 for r in in_window if r["finish"] is None)
    checks = {
        "tokens_within_ties_of_oracle": judged_ok,
        "no_compile_in_window": compiles == 0,
        "two_programs": programs == (1, 1) == programs_after,
        "every_request_accounted": len(in_window) == failed + sum(
            1 for r in in_window if r["finish"] is not None
            and len(r["req"].out_tokens) == r["req"].max_new_tokens),
    }
    print("serve checks", json.dumps(checks), json.dumps(note),
          f"drain {t_drained - t_close:.2f} s; window {t_close - t_open:.2f} s,"
          f" {len(window_rounds)} rounds, "
          f"{sum(1 for r in window_rounds if r['prefilled'])} with a prefill, "
          f"{sum(r['new_tokens'] for r in window_rounds)} tokens, "
          f"{len(in_window)} requests sent", flush=True)
    _describe(window_rounds, in_window, t_open, engine.allocator.num_pages - 1)
    return {
        "kind": "serve", "setup_s": t_open - ctx.t_start,
        "window_s": t_close - t_open, "correct": all(checks.values()),
        "attempted": len(in_window), "failed": failed, "checks": checks,
        "rounds": window_rounds, "num_slots": engine.num_slots,
        "device_dispatch_s": dispatch_s,
        "requests": [{"sent": r["sent"], "first": r["first"],
                      "finish": r["finish"],
                      "tokens": len(r["req"].out_tokens)}
                     for r in in_window],
    }
