"""chip_smoke.py — does the system still start on the chip?

One process, no children. Drives the two main paths once, through the
entry points a user calls, at the full width of GPT-2-small (12 layers,
h=768, 12 heads, vocab 50304) with random weights from a seed:

* trainer  — ``examples.transformer.pretrain.main``: b=8, s=1024, bf16
  with a dynamic loss scale, fused Adam, four chunks of steps. Losses
  finite and falling, no skipped step, one compiled step program.
* server   — ``ServingEngine`` at ``benchmarks/profile_serving.py``'s
  shape (8 slots, 72 pages of 128 tokens, max_seq 1024, prefill 512)
  answering a ``synthetic_trace`` of prompts a few hundred tokens long;
  the default engine, which on the chip decodes through the paged
  Pallas kernel, gives greedy tokens equal to a second engine built
  with ``decode_impl="jnp"`` (the reference). Random weights give near-flat bf16 logits, so where two
  streams part the check asks the prefill program whether the two
  tokens were tied within bf16 resolution — a tie may fall either way,
  anything else is a failure.
* server, MiMo family — the same engine path over the rehearse twin of
  ``perf/configs/mimo-v2.5-ep16.json`` (two KV caches, grouped-query
  decode and prefill kernels, held experts through the grouped matmul,
  all compiled by Mosaic on the chip), greedy tokens judged against the
  plain float32 reference ``perf/references/mimo_v2.py``.
* server, A.X-K1 family — the same again over the rehearse twin of
  ``perf/configs/axk1-ep16.json`` (the latent cache, attention expanded
  per head in prefill and absorbed over latent pages in decode, the
  latent decode kernel compiled by Mosaic on the chip, a shared expert
  beside the held ones), judged against ``perf/references/axk1.py``.
* kernels  — each Pallas family the default path does not reach (rows
  attention fwd+bwd in both backward structures, with segment ids, with
  dropout; layer norm; scale-mask softmax; the fused LM head; paged
  decode attention at the served model's heads and at GPT-2 large's
  20) compiled by Mosaic at the GPT-2-small shape and compared with its
  ``jnp`` reference.
* four chips — when ``jax.device_count() >= 4``: the trainer again on a
  dp=2 x tp=2 mesh, loss trajectory against the one-chip run, memory in
  use on every device.

Each phase reports cold seconds, compilations after warm-up (must be
0), steady wall time around work that ends on the host, peak device
bytes, the compile cache's directory with hits and misses, and which
implementation ran — read from ``dispatch.snapshot()`` and the lowered
module text, not from intent. Nothing is caught: a failing phase is a
traceback and a nonzero exit code.

Without a TPU the script exits nonzero and prints no result.
``--cpu-dry-run`` walks the same control flow at toy sizes with the
kernels in interpret mode, so the sandbox and the tests can run it; its
output is marked as a dry run and is not a result.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform", "kind", "count"}}``; the full
record goes to ``<out>/chip_smoke.json`` (default ``chiprun_out/``).
The compile cache lives where ``JAX_COMPILATION_CACHE_DIR`` says, else
in ``benchmarks/.compile_cache/`` (``apex_tpu.compile_cache``); nothing
else is written.
"""

import argparse
import functools
import json
import math
import os
import statistics
import sys
import time

import numpy as np

import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)

# alone in a directory (no repo around it) this import is what fails
from apex_tpu import compile_cache, dispatch  # noqa: E402

FULL = dict(
    layers=12, hidden=768, heads=12, vocab=50304, seq=1024, batch=8,
    chunk_steps=8, chunks=4,
    slots=8, page_size=128, pages=72, max_seq=1024, prefill_len=512,
    requests=6, prompt=(200, 400), new_tokens=(8, 16),
    ln_rows=8192, xent_rows=8192)
# toy sizes that keep every code path (multi-page decode, packed
# prefill, multi-chunk vocab) but finish in seconds in interpret mode
DRY = dict(
    layers=1, hidden=128, heads=2, vocab=512, seq=128, batch=2,
    chunk_steps=2, chunks=3,
    slots=2, page_size=16, pages=16, max_seq=64, prefill_len=64,
    requests=3, prompt=(8, 24), new_tokens=(3, 6),
    ln_rows=64, xent_rows=64)

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# bf16 kernels against bf16 references: each leaf is held to its
# interpret-mode test's absolute tolerance AND to a norm-relative bound
# that stays meaningful at the large shapes (a wrong kernel is off by
# ~1, rounding by ~1e-2). The softmax backward works from the bf16
# output it saved while autodiff of the reference recomputes it in
# fp32, and y * (g - sum(y * g)) cancels: its bound is wider.
_REL = 5e-2
_REL_SOFTMAX_BWD = 2e-1
# two greedy streams may part where the logits tie. Each engine's bf16
# logits and the prefill program's (the judge) are each a rounding step
# or so off, so a tie is "within four steps of the best logit"; a wrong
# kernel picks a token nowhere near the top of 50304.
_TIE_STEPS = 4


class CompileLog:
    """Every XLA compile request of the process (persistent-cache hit
    or not), stamped with the clock the phases use."""

    def __init__(self):
        self.times = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == _COMPILE_EVENT:
            self.times.append(time.perf_counter())

    def between(self, t0, t1):
        return sum(1 for t in self.times if t0 < t <= t1)


def _peak_bytes():
    """``peak_bytes_in_use`` per device (None where the backend keeps no
    memory stats — the CPU)."""
    return {str(d.id): (d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()}


def _cache_delta(before):
    now = compile_cache.snapshot()
    return {"dir": now["dir"], "hits": now["hits"] - before["hits"],
            "misses": now["misses"] - before["misses"]}


def _consulted_since(seen):
    """Dispatch-table consults new since ``seen`` (a set of row keys):
    what each unpinned kernel choice resolved to at trace time (None =
    table miss, the built-in default applied)."""
    rows = []
    for row in dispatch.snapshot()["consulted"]:
        key = json.dumps(row, sort_keys=True)
        if key not in seen:
            seen.add(key)
            rows.append(f"{row['op']}[{row['bucket']}]->{row['choice']}")
    return rows


def _mosaic_calls(lowered):
    """Pallas kernels compiled by Mosaic in a lowered module (0 in
    interpret mode, where a kernel is plain HLO)."""
    return lowered.as_text().count("tpu_custom_call")


_say = functools.partial(print, flush=True)


# ------------------------------------------------------------------ trainer

def _trainer_argv(size, tp):
    return [
        "--model", "gpt", "--num-layers", str(size["layers"]),
        "--hidden-size", str(size["hidden"]),
        "--num-attention-heads", str(size["heads"]),
        "--max-position-embeddings", str(size["seq"]),
        "--seq-length", str(size["seq"]),
        "--micro-batch-size", str(size["batch"]),
        "--vocab-size", str(size["vocab"]),
        "--tensor-model-parallel-size", str(tp),
        "--optimizer", "adam", "--lr", "1e-4", "--bf16",
        "--train-iters", str(size["chunk_steps"] * size["chunks"]),
        "--log-interval", str(size["chunk_steps"]),
    ]


def run_trainer(size, log, seen, tp=1):
    from apex_tpu.ops.attention import flash_supported
    from examples.transformer import pretrain

    cache0 = compile_cache.snapshot()
    t0 = time.perf_counter()
    out = pretrain.main(_trainer_argv(size, tp))
    chunks = out["chunks"]
    steps = size["chunk_steps"] * size["chunks"]
    losses = [x for c in chunks for x in c["losses"]]

    assert len(chunks) == size["chunks"] >= 3, chunks
    assert len(losses) == steps and all(map(math.isfinite, losses)), losses
    first, last = chunks[0]["losses"], chunks[-1]["losses"]
    assert statistics.fmean(last) < statistics.fmean(first), (
        "loss did not fall", first, last)
    # the dynamic scaler counts every applied step: none was skipped
    assert not out["overflow"] and out["unskipped"] == steps, out
    assert [c["programs"] for c in chunks] == [1] * len(chunks), (
        "the train step compiled more than once",
        [c["programs"] for c in chunks])
    steady_compiles = log.between(chunks[0]["t_end"], chunks[-1]["t_end"])
    assert steady_compiles == 0, (
        f"{steady_compiles} compilation(s) after the first chunk")

    steady_s = statistics.median(c["seconds"] for c in chunks[1:])
    step_s = steady_s / size["chunk_steps"]
    dp = len(jax.devices()) // tp   # pretrain.main meshes every device
    rec = {
        "params_m": round(out["n_params"] / 1e6, 1),
        "mesh": f"dp={dp} tp={tp}",
        "losses": [round(x, 4) for x in losses],
        "loss_scale": out["loss_scale"], "unskipped": out["unskipped"],
        "cold_s": round(chunks[0]["seconds"] - steady_s, 2),
        "wall_s": round(time.perf_counter() - t0, 2),
        "steady_step_ms": round(step_s * 1e3, 2),
        "steady_tokens_per_s": round(
            dp * size["batch"] * size["seq"] / step_s),
        "compiles_after_warmup": steady_compiles,
        "peak_bytes": _peak_bytes(),
        "bytes_in_use": chunks[-1]["bytes_in_use"],
        "compile_cache": _cache_delta(cache0),
        "ran": {"attention": "pallas flash (bundled)"
                if flash_supported(size["seq"], size["seq"])
                else "xla dense",
                "dispatch": _consulted_since(seen)},
    }
    _say(f"  {rec['params_m']}M params, mesh {rec['mesh']}; loss "
         f"{losses[0]:.4f} -> {losses[-1]:.4f} over {steps} steps, "
         f"scale {rec['loss_scale']:.0f}, skipped 0")
    _say(f"  cold {rec['cold_s']} s; steady {rec['steady_step_ms']} "
         f"ms/step = {rec['steady_tokens_per_s']:,} tokens/s; "
         f"compilations after warm-up {steady_compiles}")
    _say(f"  ran: {rec['ran']}")
    _say(f"  peak bytes {rec['peak_bytes']}; cache {rec['compile_cache']}")
    return rec


# ------------------------------------------------------------------- server

def _drain(engine, requests, max_ticks=10000):
    """``ServingEngine.run_trace``'s loop with a clock around every
    round (a round ends with the tokens on the host). Returns the
    rounds as ``(seconds, prefilled, decoded)``."""
    pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
    rounds = []
    tick0, done0 = engine.tick, len(engine.scheduler.completed)
    while len(engine.scheduler.completed) - done0 < len(requests):
        assert engine.tick - tick0 < max_ticks, "trace did not drain"
        now = engine.tick - tick0
        due = [r for r in pending if r.arrival <= now]
        pending = [r for r in pending if r.arrival > now]
        t0 = time.perf_counter()
        info = engine.step(arrivals=due)
        rounds.append((time.perf_counter() - t0, len(info["prefilled"]),
                       info["decoded_slots"]))
    engine.flush()
    return rounds


def _server_lowerings(engine):
    """Mosaic kernel counts of the engine's two programs, lowered at
    the shapes the engine dispatches."""
    s, r = engine.prefill_len, engine.prefill_requests
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    prefill = engine._prefill_fn.lower(
        engine.params, engine.cache, i32(s), i32(s), i32(s), i32(s),
        i32(engine.num_slots + 1, engine.max_pages),
        i32(r * engine._gather_w))
    decode = engine._decode_fn.lower(
        engine.params, engine.qparams, engine.cache,
        i32(engine.num_slots), i32(engine.num_slots),
        i32(engine.num_slots, engine.max_pages))
    return {"prefill_mosaic_calls": _mosaic_calls(prefill),
            "decode_mosaic_calls": _mosaic_calls(decode)}


def _next_token_logits(cfg, params, context, size):
    """The prefill program's fp32 logits for the token after
    ``context`` — one sequence, a scratch cache."""
    from apex_tpu.serving import model as smodel
    from apex_tpu.serving.kv_cache import init_cache

    s, ps = size["prefill_len"], size["page_size"]
    n, max_pages = len(context), size["max_seq"] // ps
    assert n <= s, (n, s)
    cache = init_cache(cfg.num_layers, cfg.num_attention_heads,
                       size["pages"], ps, cfg.head_dim,
                       smodel.compute_dtype(cfg))
    real = (np.arange(s) < n).astype(np.int32)
    ids = np.zeros(s, np.int32)
    ids[:n] = context
    page_table = np.zeros((2, max_pages), np.int32)   # row 1: all null
    page_table[0] = np.arange(1, max_pages + 1)
    _, logits = jax.jit(functools.partial(smodel.prefill, cfg=cfg))(
        params, cache, ids, np.arange(s, dtype=np.int32) * real, real,
        1 - real, page_table, np.asarray([n - 1], np.int32))
    return np.asarray(logits[0], np.float32)


def _compare_streams(requests, streams, cfg, params, size):
    """Token-for-token agreement of the two engines' greedy streams.
    A stream may part from the other only at a bf16 tie: both tokens
    within ``_TIE_STEPS`` bf16 steps of the best logit the prefill
    program gives for that position (after that point the contexts
    differ and the rest of the stream is not compared). Returns
    ``(same, ties)``."""
    same, ties = 0, []
    for r in requests:
        a, b = streams["default"][r.rid], streams["jnp"][r.rid]
        assert len(a) == len(b)
        i = next((k for k in range(len(a)) if a[k] != b[k]), len(a))
        same += i
        if i == len(a):
            continue
        logits = _next_token_logits(cfg, params, list(r.prompt) + a[:i],
                                    size)
        best = float(logits.max())
        step = 2.0 ** (math.floor(math.log2(abs(best))) - 7)   # bf16
        gap = best - float(min(logits[a[i]], logits[b[i]]))
        assert gap <= _TIE_STEPS * step, (
            f"request {r.rid}: the engines part at token {i} "
            f"({a[i]} vs {b[i]}) and it is no tie: logit gap "
            f"{gap:.4f} > {_TIE_STEPS * step:.4f}", a, b)
        ties.append({"rid": r.rid, "token": i, "default": a[i],
                     "jnp": b[i], "gap": round(gap, 5),
                     "allowed": _TIE_STEPS * step})
    return same, ties


def run_server(size, log, seen, interpret):
    from apex_tpu.serving import ServingEngine, synthetic_trace
    from apex_tpu.serving import model as smodel
    from apex_tpu.transformer.testing import TransformerConfig

    cfg = TransformerConfig(
        hidden_size=size["hidden"], num_layers=size["layers"],
        num_attention_heads=size["heads"], vocab_size=size["vocab"],
        max_position_embeddings=size["max_seq"], hidden_dropout=0.0,
        attention_dropout=0.0, apply_query_key_layer_scaling=False,
        bf16=True)
    params = smodel.init_gpt_params(cfg, seed=0)
    recs, streams = {}, {}
    # the default engine (on the chip: the paged Pallas decode kernel,
    # by the family's rule) against the jnp reference. The dry run on
    # the CPU, where the rule takes the reference, demands the kernel
    # in interpret mode so that it still walks both programs.
    for name, impl in (("default", "pallas" if interpret else None),
                       ("jnp", "jnp")):
        cache0 = compile_cache.snapshot()
        t0 = time.perf_counter()
        engine = ServingEngine(
            cfg, params=params, num_slots=size["slots"],
            page_size=size["page_size"], num_pages=size["pages"],
            max_seq=size["max_seq"], prefill_len=size["prefill_len"],
            decode_impl=impl,
            interpret=interpret if impl == "pallas" else None)
        # warm-up: one request compiles prefill and decode
        warm, _ = synthetic_trace(
            seed=1, n_requests=1, vocab=cfg.vocab_size,
            prompt_lo=size["prompt"][0], prompt_hi=size["prompt"][1],
            new_lo=2, new_hi=2, mean_interarrival=0.0)
        warm[0].rid = -1   # apart from the measured trace's ids
        engine.run_trace(warm)
        cold_s = time.perf_counter() - t0

        requests, trace_id = synthetic_trace(
            seed=0, n_requests=size["requests"], vocab=cfg.vocab_size,
            prompt_lo=size["prompt"][0], prompt_hi=size["prompt"][1],
            new_lo=size["new_tokens"][0], new_hi=size["new_tokens"][1],
            mean_interarrival=1.0)
        t_run = time.perf_counter()
        rounds = _drain(engine, requests)
        t_end = time.perf_counter()

        answered = [r for r in requests
                    if len(r.out_tokens) == r.max_new_tokens]
        assert len(answered) == len(requests), (
            f"{name}: {len(answered)}/{len(requests)} requests answered")
        assert all(0 <= t < cfg.vocab_size
                   for r in requests for t in r.out_tokens)
        assert engine.prefill_cache_size() == 1 \
            and engine.decode_cache_size() == 1, (
                name, engine.prefill_cache_size(),
                engine.decode_cache_size())
        steady_compiles = log.between(t_run, t_end)
        assert steady_compiles == 0, (
            f"{name}: {steady_compiles} compilation(s) after warm-up")

        decode_rounds = [s for s, pre, dec in rounds if dec and not pre]
        streams[name] = {r.rid: list(r.out_tokens) for r in requests}
        recs[name] = {
            "trace": trace_id, "requests": len(requests),
            "prompt_tokens": sum(len(r.prompt) for r in requests),
            "new_tokens": sum(len(r.out_tokens) for r in requests),
            "cold_s": round(cold_s, 2),
            "rounds": len(rounds),
            "decode_round_ms": round(
                statistics.median(decode_rounds) * 1e3, 2),
            "prefill_round_ms": round(statistics.median(
                s for s, pre, _ in rounds if pre) * 1e3, 2),
            "compiles_after_warmup": steady_compiles,
            "peak_bytes": _peak_bytes(),
            "compile_cache": _cache_delta(cache0),
            "ran": dict(_server_lowerings(engine),
                        decode_attn_impl=engine.decode_attn_impl,
                        dispatch=_consulted_since(seen)),
        }
        rec = recs[name]
        _say(f"  [{name}] {rec['requests']} requests "
             f"({rec['prompt_tokens']} prompt tokens) all answered, "
             f"{rec['new_tokens']} tokens out in {rec['rounds']} rounds; "
             f"cold {rec['cold_s']} s; decode round "
             f"{rec['decode_round_ms']} ms, prefill round "
             f"{rec['prefill_round_ms']} ms; compilations after "
             f"warm-up {steady_compiles}")
        _say(f"  [{name}] ran: {rec['ran']}")
        _say(f"  [{name}] peak bytes {rec['peak_bytes']}; cache "
             f"{rec['compile_cache']}")
        del engine

    assert recs["default"]["ran"]["decode_attn_impl"] == "pallas" \
        and recs["jnp"]["ran"]["decode_attn_impl"] == "jnp", recs
    if not interpret:
        # the default engine runs the kernel and the demand was honored
        assert recs["default"]["ran"]["decode_mosaic_calls"] > 0 \
            and recs["jnp"]["ran"]["decode_mosaic_calls"] == 0, recs
    same, ties = _compare_streams(requests, streams, cfg, params, size)
    recs["agreement"] = {
        "tokens": sum(len(v) for v in streams["default"].values()),
        "identical": same, "bf16_ties": ties}
    _say(f"  greedy tokens: default engine vs decode_impl='jnp' "
         f"engine: {same}/{recs['agreement']['tokens']} identical "
         f"token for token"
         + "".join(f"; request {t['rid']} parts at token {t['token']} "
                   f"on a bf16 tie (logit gap {t['gap']} <= "
                   f"{t['allowed']})" for t in ties))
    return recs


# ------------------------------------- server, the MiMo and A.X-K1 families

MIMO = dict(slots=32, page_size=16, pages=96, max_seq=256, prefill_len=256,
            requests=6, prompt=(8, 120), new_tokens=(20, 40))
MIMO_DRY = dict(MIMO, slots=4, pages=48, requests=3, new_tokens=(6, 12))
# the rehearse twin of each configuration, its plain reference, and the
# distinct Mosaic kernels its decode / prefill programs must reach on
# the chip (a lowered module holds each distinct kernel once, however
# many layers call it): MiMo an attention kernel of each layer kind and
# the grouped matmul, A.X-K1 the latent (packed) kernel and the grouped
# matmul
FAMILY_TWINS = {
    "mimo": ("mimo-v2.5-ep16.json", "mimo_v2.py", 3),
    "axk1": ("axk1-ep16.json", "axk1.py", 2),
}


def run_family_server(log, interpret, name):
    """A model family other than GPT-2 through the same ``ServingEngine``
    path, at the rehearse size of its configuration under
    ``perf/configs/`` (``mimo``: published K 192 / V 128 head widths, 32
    heads on 2 and 4 KV heads, window 16, 16 experts top-4 of which 4
    are held; ``axk1``: latent attention with a 96-wide cache row in its
    expanded and absorbed forms, YaRN, 4 of 16 experts top-4 and a
    shared one): on the chip its attention kernels and the grouped
    expert matmul are compiled by Mosaic; the engine's greedy tokens are
    judged against the family's plain float32 reference under
    ``perf/references/`` (within ``_TIE_STEPS`` bfloat16 steps of its
    best logit at every position)."""
    import importlib.util

    from apex_tpu.serving import ServingEngine
    from apex_tpu.serving import family as family_mod
    from apex_tpu.serving.scheduler import Request

    config_file, reference_file, kernels = FAMILY_TWINS[name]
    with open(os.path.join(ROOT, "perf", "configs", config_file)) as f:
        config = json.load(f)
    config.update(config.pop("rehearse"))
    config["held_experts"] = tuple(config["held_experts"])
    spec = importlib.util.spec_from_file_location(
        name + "_reference",
        os.path.join(ROOT, "perf", "references", reference_file))
    reference = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reference)

    size = MIMO_DRY if interpret else MIMO
    cfg = family_mod.config_from_dict(config)
    params = family_mod.family_of(cfg).init_params(
        cfg, jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    engine = ServingEngine(
        cfg, params=params, num_slots=size["slots"],
        page_size=size["page_size"], num_pages=size["pages"],
        max_seq=size["max_seq"], prefill_len=size["prefill_len"],
        decode_impl="pallas" if interpret else None,
        interpret=True if interpret else None)
    engine.run_trace([Request(rid=-1, prompt=[1, 2, 3], max_new_tokens=2)])
    cold_s = time.perf_counter() - t0

    rs = np.random.RandomState(0)
    requests = [Request(
        rid=i, prompt=rs.randint(0, cfg.vocab_size,
                                 rs.randint(*size["prompt"])).tolist(),
        max_new_tokens=int(rs.randint(*size["new_tokens"])), arrival=i)
        for i in range(size["requests"])]
    t_run = time.perf_counter()
    rounds = _drain(engine, requests)
    t_end = time.perf_counter()
    assert all(len(r.out_tokens) == r.max_new_tokens for r in requests)
    assert engine.prefill_cache_size() == 1 \
        and engine.decode_cache_size() == 1
    steady_compiles = log.between(t_run, t_end)
    assert steady_compiles == 0, (
        f"{name}: {steady_compiles} compilation(s) after warm-up")

    worst, judged = 0.0, 0
    for r in requests:
        seq = list(r.prompt) + list(r.out_tokens)
        ids = np.zeros(size["max_seq"], np.int32)   # one shape for all:
        ids[:len(seq)] = seq                        # causal, padding after
        best, chosen = reference.best_and_chosen(config, params, ids)
        at = slice(len(r.prompt) - 1, len(seq) - 1)
        steps = (best[at] - chosen[at]) / np.asarray(
            [reference.bf16_step(b) for b in best[at]])
        worst, judged = max(worst, float(steps.max())), judged + len(steps)
    assert worst <= _TIE_STEPS, (
        f"{name}: an emitted token lies {worst:.2f} bf16 steps below the "
        f"reference's best logit (allowed {_TIE_STEPS})")

    ran = dict(_server_lowerings(engine),
               decode_attn_impl=engine.decode_attn_impl)
    if not interpret:
        assert ran["decode_attn_impl"] == "pallas" \
            and ran["decode_mosaic_calls"] >= kernels \
            and ran["prefill_mosaic_calls"] >= kernels, ran
    rec = {"requests": len(requests), "judged_tokens": judged,
           "worst_gap_bf16_steps": round(worst, 3),
           "cold_s": round(cold_s, 2), "rounds": len(rounds),
           "decode_round_ms": round(statistics.median(
               s for s, pre, dec in rounds if dec and not pre) * 1e3, 2),
           "compiles_after_warmup": steady_compiles, "ran": ran}
    _say(f"  [{name}] {rec['requests']} requests answered; {judged} tokens "
         f"judged against the float32 reference, worst gap "
         f"{rec['worst_gap_bf16_steps']} bf16 steps (allowed "
         f"{_TIE_STEPS}); cold {rec['cold_s']} s; decode round "
         f"{rec['decode_round_ms']} ms; ran: {ran}")
    return rec


# ------------------------------------------------------------------ kernels

def _close(got, want, atol, rel_bound=_REL):
    """Largest absolute and norm-relative error of ``got`` against
    ``want``; raises when either exceeds its bound."""
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, atol=atol, rtol=atol)
    rel = float(np.linalg.norm(got - want)
                / max(float(np.linalg.norm(want)), 1e-30))
    assert rel < rel_bound, (
        f"norm-relative error {rel:.3g} >= {rel_bound}")
    return float(np.max(np.abs(got - want))), rel


def _sin_sum(y):
    return jnp.sum(jnp.sin(y.astype(jnp.float32)))


def _with_grads(fn, n_args, loss_of=_sin_sum):
    """``fn``'s output followed by the gradients of ``loss_of(output)``
    with respect to each argument. The default loss keeps cotangents
    O(1) at any shape, so the gradient comparison means something."""
    def loss(*args):
        y = fn(*args)
        return loss_of(y), y

    vg = jax.value_and_grad(loss, argnums=tuple(range(n_args)),
                            has_aux=True)

    def run(*args):
        (_, y), grads = vg(*args)
        return (y,) + tuple(grads)

    return run


def _attention_cases(size, interpret):
    from apex_tpu.ops import attention_pallas as ap
    from apex_tpu.ops.attention import _dense_attention

    b, h, s = size["batch"], size["heads"], size["seq"]
    d = size["hidden"] // size["heads"]
    rs = np.random.RandomState(0)
    qkv = tuple(jnp.asarray(rs.randn(b, h, s, d), jnp.bfloat16)
                for _ in range(3))
    scale = 1.0 / math.sqrt(d)
    seg = jnp.asarray(np.sort(rs.randint(0, 3, (b, s)), axis=1), jnp.int32)
    assert ap.supported(s, s, d, dropout=True)

    def case(bwd_impl, segs, p):
        seed = jnp.asarray([[42]], jnp.int32) if p else None
        # dropout parity follows its test: non-causal, the dense mask
        # rebuilt from the kernel's own hash
        causal = not p

        def kernel(q, k, v):
            return ap.fused_attention_rows(
                q, k, v, causal, scale, segs, interpret, None, bwd_impl,
                p, seed)

        def reference(q, k, v):
            if not p:
                return _dense_attention(q, k, v, causal, scale, segs)
            mscale = jax.vmap(lambda ib: jax.vmap(
                lambda ih: ap._dropout_mscale(seed, ib, ih, 0, s, s, p, h)
            )(jnp.arange(h)))(jnp.arange(b))
            sc = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                            preferred_element_type=jnp.float32) * scale
            probs = jax.nn.softmax(sc, axis=-1) * mscale
            return jnp.einsum("bhqk,bhkd->bhqd", probs.astype(v.dtype), v,
                              preferred_element_type=jnp.float32
                              ).astype(q.dtype)

        return (_with_grads(kernel, 3), _with_grads(reference, 3), qkv,
                [(3e-2,), (4e-2,)])

    yield "attention rows fwd+bwd monolithic", case("monolithic", None, 0.0)
    yield "attention rows fwd+bwd split", case("split", None, 0.0)
    yield "attention rows monolithic +segments", case(
        "monolithic", (seg, seg), 0.0)
    yield "attention rows split +segments", case("split", (seg, seg), 0.0)
    yield "attention rows monolithic +dropout", case(None, None, 0.1)


def _row_kernel_cases(size, interpret):
    from apex_tpu.normalization.fused_layer_norm import fused_layer_norm
    from apex_tpu.ops import layer_norm_pallas as lnp
    from apex_tpu.ops import softmax_pallas as smp
    from apex_tpu.ops import xent_pallas as xp
    from apex_tpu.transformer.functional.fused_softmax import (
        scaled_upper_triang_masked_softmax as jnp_causal_softmax)

    rs = np.random.RandomState(1)
    rows, hidden = size["ln_rows"], size["hidden"]
    x = jnp.asarray(rs.randn(rows, hidden) * 2 + 1, jnp.bfloat16)
    w = jnp.asarray(rs.rand(hidden) + 0.5, jnp.float32)
    bias = jnp.asarray(rs.randn(hidden), jnp.float32)
    assert lnp.supported(rows, hidden)
    yield "layer_norm fwd+bwd", (
        _with_grads(
            lambda x, w, b: lnp.layer_norm(x, w, b, 1e-5, interpret), 3),
        _with_grads(lambda x, w, b: fused_layer_norm(
            x, (hidden,), w, b, 1e-5, use_pallas=False), 3),
        (x, w, bias), [(2e-2,), (5e-2,)])

    b, h, s = size["batch"], size["heads"], size["seq"]
    scores = jnp.asarray(rs.randn(b, h, s, s) * 2.0, jnp.bfloat16)
    assert smp.supported(s, s)

    yield "softmax causal fwd+bwd", (
        _with_grads(lambda x: smp.scaled_masked_softmax(
            x, None, 0.125, causal=True, interpret=interpret), 1),
        _with_grads(lambda x: jnp_causal_softmax(
            x.reshape(-1, s, s), 0.125).reshape(x.shape), 1),
        (scores,), [(2e-2,), (2e-2, _REL_SOFTMAX_BWD)])

    n, vocab = size["xent_rows"], size["vocab"]
    xs = jnp.asarray(rs.randn(n, hidden) * 0.3, jnp.bfloat16)
    emb = jnp.asarray(rs.randn(vocab, hidden) * 0.3, jnp.bfloat16)
    labels = jnp.asarray(rs.randint(0, vocab, (n,)), jnp.int32)
    weight = jnp.asarray(rs.rand(n) + 0.5, jnp.float32)
    assert xp.supported(n, vocab, hidden)

    def materialized(x, e):
        logits = x.astype(jnp.float32) @ e.astype(jnp.float32).T
        lse = jax.scipy.special.logsumexp(logits, axis=1)
        return lse - jnp.take_along_axis(logits, labels[:, None],
                                         axis=1)[:, 0]

    def weighted_sum(per_row):
        return jnp.sum(weight * per_row)

    yield "fused LM head fwd+bwd", (
        _with_grads(lambda x, e: xp.linear_cross_entropy(
            x, e, labels, interpret), 2, weighted_sum),
        _with_grads(materialized, 2, weighted_sum),
        (xs, emb), [(5e-2,)])


def _decode_cases(size, interpret):
    from apex_tpu.ops import decode_attention_pallas as dap

    b = size["slots"]
    d = size["hidden"] // size["heads"]
    pages, ps = size["pages"], size["page_size"]
    max_pages = size["max_seq"] // ps
    rs = np.random.RandomState(2)
    pt = jnp.asarray(np.stack([
        rs.permutation(np.arange(1, pages))[:max_pages]
        for _ in range(b)]), jnp.int32)
    # mid-page, page-aligned, full, inactive, then mixed
    edge = [5, ps, max_pages * ps, 0]
    lens = jnp.asarray(
        (edge + list(rs.randint(1, max_pages * ps, b)))[:b], jnp.int32)
    scale = 1.0 / math.sqrt(d)

    # the served model's heads, then GPT-2 large's 20 (serve-large-batch:
    # rows padded 20 -> 24): n_kv = h, every head's banded query against
    # the whole [page_size, h * d] page
    for h in dict.fromkeys((size["heads"], 20)):
        assert interpret or dap.grouped_supported(h, h, d, d, ps,
                                                  jnp.bfloat16)
        q = jnp.asarray(rs.randn(b, h, d), jnp.bfloat16)
        kf = rs.randn(pages, ps, h * d).astype(np.float32)
        vf = rs.randn(pages, ps, h * d).astype(np.float32)
        kf[0] = vf[0] = 0.0   # the null page
        yield f"decode attention bf16 paged, {h} heads", (
            lambda q, k, v, h=h: dap.grouped_decode_attention(
                q, k, v, pt, lens, n_kv=h, sm_scale=scale, impl="pallas",
                interpret=interpret),
            lambda q, k, v, h=h: dap.grouped_decode_attention_reference(
                q, k, v, pt, lens, scale, n_kv=h),
            (q, jnp.asarray(kf, jnp.bfloat16), jnp.asarray(vf, jnp.bfloat16)),
            [(5e-2,)])


def _selection_cases(size, interpret):
    """The kernels of a layer that SELECTS its keys (serving/dots3.py), at
    small shapes of whole tiles: the decode indexer over index-key pages,
    the latent kernel over a ring with its ``starts``, the prefill
    indexer, the packed kernel under an int8 selection."""
    from apex_tpu.ops import attention as attn
    from apex_tpu.ops import decode_attention_pallas as dap
    from apex_tpu.serving import kv_cache

    rs = np.random.RandomState(3)
    bf = lambda *shape: jnp.asarray(rs.randn(*shape), jnp.bfloat16)  # noqa: E731
    b, n, ps, hi, di = 4, 3, 128, 8, 128
    table = jnp.asarray(1 + rs.permutation(b * n).reshape(b, n), jnp.int32)
    lens = jnp.asarray([5, ps, n * ps, 0], jnp.int32)
    w = jnp.asarray(rs.randn(b, hi), jnp.float32)
    yield "index decode scores bf16 paged", (
        lambda q, pages: dap.index_decode_scores(
            q, w, pages, table, lens, impl="pallas", interpret=interpret),
        lambda q, pages: dap.index_decode_scores_reference(
            q, w, pages, table, lens),
        (bf(b, hi, di), bf(1 + b * n, ps, di)), [(2e-1,)])

    window, width, rank, hq = 200, 256, 128, 8
    ring = kv_cache.ring_pages(window, ps)
    ring_lens = jnp.asarray([0, 90, 300, 1000], jnp.int32)
    base, starts = kv_cache.ring_view(ring_lens, ring, ps, window)
    view = dict(rank=rank, page_base=base, starts=starts)
    yield "latent decode attention bf16 over a ring", (
        lambda q, pages: dap.latent_decode_attention(
            q, pages, kv_cache.ring_table(b, ring), ring_lens, sm_scale=0.1,
            impl="pallas", interpret=interpret, **view),
        lambda q, pages: dap.latent_decode_attention_reference(
            q, pages, kv_cache.ring_table(b, ring), ring_lens, 0.1, **view),
        (bf(b, hq, width), bf(1 + b * ring, ps, width)), [(5e-2,)])

    S, k = 512, 128
    seg = jnp.asarray(np.r_[np.ones(300), 2 * np.ones(180), np.zeros(32)],
                      jnp.int32)
    w_s = jnp.asarray(rs.randn(S, hi), jnp.float32)
    yield "packed index scores bf16", (
        lambda q, keys: attn.packed_index_scores(
            q, w_s, keys, seg, impl="pallas", interpret=interpret),
        lambda q, keys: attn.packed_index_scores(q, w_s, keys, seg,
                                                 impl="jnp"),
        (bf(hi, S, di), bf(S, di)), [(2e-1,)])
    selected = attn.select_keys(attn.packed_index_scores(
        bf(hi, S, di), w_s, bf(S, di), seg, impl="jnp"), k)
    yield "packed attention bf16 under a selection", (
        lambda q, keys, v: attn.selected_attention(
            q, keys, v, seg, selected, sm_scale=0.07, impl="pallas",
            interpret=interpret),
        lambda q, keys, v: attn.selected_attention(
            q, keys, v, seg, selected, sm_scale=0.07, impl="jnp"),
        (bf(4, S, 192), bf(4, S, 192), bf(4, S, 128)), [(5e-2,)])


def run_kernels(size, log, interpret):
    """Each case: ``(kernel_fn, reference_fn, args, tolerances)`` where
    both functions return the same pytree (output, then gradients) and
    the tolerances — ``(atol[, rel_bound])`` — go leaf by leaf, the
    last one repeating."""
    recs = {}
    cache0 = compile_cache.snapshot()
    for cases in (_attention_cases, _row_kernel_cases, _decode_cases,
                  _selection_cases):
        for name, (kernel, reference, args, tols) in cases(size, interpret):
            t0 = time.perf_counter()
            lowered = jax.jit(kernel).lower(*args)
            calls = _mosaic_calls(lowered)
            assert interpret or calls > 0, (
                f"{name}: no Mosaic kernel in the lowered module")
            fn = lowered.compile()
            got = jax.block_until_ready(fn(*args))
            cold_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            warm_s = time.perf_counter() - t0
            want = jax.jit(reference)(*args)
            got_l = jax.tree_util.tree_leaves(got)
            want_l = jax.tree_util.tree_leaves(want)
            assert len(got_l) == len(want_l)
            errs = [_close(g, r, *tols[min(i, len(tols) - 1)])
                    for i, (g, r) in enumerate(zip(got_l, want_l))]
            recs[name] = {
                "mosaic_calls": calls, "interpret": interpret,
                "cold_s": round(cold_s, 2),
                "warm_ms": round(warm_s * 1e3, 3),
                "max_abs_err": round(max(e[0] for e in errs), 5),
                "max_rel_err": round(max(e[1] for e in errs), 5),
            }
            _say(f"  {name:38s} "
                 f"{'interpret' if interpret else f'mosaic x{calls}'}; "
                 f"cold {cold_s:6.2f} s, warm {warm_s * 1e3:8.2f} ms; "
                 f"matches reference (abs {recs[name]['max_abs_err']}, "
                 f"rel {recs[name]['max_rel_err']})")
    recs["peak_bytes"] = _peak_bytes()
    recs["compile_cache"] = _cache_delta(cache0)
    _say(f"  peak bytes {recs['peak_bytes']}; cache "
         f"{recs['compile_cache']}")
    return recs


# --------------------------------------------------------------- four chips

def run_four_chips(size, log, seen, one_chip):
    """The trainer again on dp=2 x tp=2. Same model, same seed; each dp
    rank sees its own batch, so the trajectory is compared in shape
    (start, fall) and not step for step."""
    rec = run_trainer(size, log, seen, tp=2)
    ref = one_chip["losses"]
    assert abs(rec["losses"][0] - ref[0]) < 0.05 * ref[0], (
        "first loss differs from the tp=1 run", rec["losses"][0], ref[0])
    used = [v for v in rec["bytes_in_use"].values() if v is not None]
    if used:
        # the state lives on every device of the mesh: nothing piled
        # on device 0
        assert min(used) > 0 and max(used) < 2 * min(used), (
            "memory is not spread over the mesh", rec["bytes_in_use"])
    _say(f"  bytes in use per device with the state live "
         f"{rec['bytes_in_use']}")
    return rec


# --------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--cpu-dry-run", action="store_true",
                    help="toy sizes, kernels in interpret mode; walks "
                         "the control flow, proves nothing about a chip")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out"),
                    help="directory for chip_smoke.json")
    args = ap.parse_args(argv)
    dry = args.cpu_dry_run

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    _say(f"chip_smoke: jax {jax.__version__} platform={device['platform']} "
         f"device_kind={device['kind']!r} count={device['count']}"
         + ("  [CPU DRY RUN — not a result]" if dry else ""))
    if not dry and device["platform"] != "tpu":
        sys.exit(f"chip_smoke: no TPU (JAX found platform="
                 f"{device['platform']!r}); this check only runs on the "
                 f"chip. --cpu-dry-run walks the control flow here.")

    size = DRY if dry else FULL
    compile_cache.activate()
    _say(f"compile cache: {compile_cache.snapshot()}")
    log, seen = CompileLog(), set()
    record = {"dry_run": dry, "jax": jax.__version__, "device": device,
              "phases": {}}
    t_all = time.perf_counter()

    _say("phase trainer: pretrain.main, GPT "
         f"{size['layers']}L h={size['hidden']} b={size['batch']} "
         f"s={size['seq']}, bf16 + dynamic loss scale + fused Adam")
    record["phases"]["trainer"] = run_trainer(size, log, seen)

    _say(f"phase server: ServingEngine {size['slots']} slots, "
         f"{size['pages']} x {size['page_size']}-token pages, "
         f"prefill {size['prefill_len']}")
    record["phases"]["server"] = run_server(size, log, seen, interpret=dry)

    _say("phase server, MiMo family: grouped-query decode over the paged "
         "pool and the window rings, held experts")
    record["phases"]["server_mimo"] = run_family_server(log, dry, "mimo")

    _say("phase server, A.X-K1 family: latent attention (expanded in "
         "prefill, absorbed over the latent pages in decode), a shared "
         "expert beside the held ones")
    record["phases"]["server_axk1"] = run_family_server(log, dry, "axk1")

    _say("phase kernels: Pallas families "
         + ("in interpret mode" if dry else "compiled by Mosaic")
         + " against their jnp references")
    record["phases"]["kernels"] = run_kernels(size, log, interpret=dry)

    if device["count"] >= 4:
        _say("phase four chips: pretrain.main on dp=2 x tp=2")
        record["phases"]["four_chips"] = run_four_chips(
            size, log, seen, record["phases"]["trainer"])
    else:
        _say(f"phase four chips: not run ({device['count']} device(s))")

    record["wall_s"] = round(time.perf_counter() - t_all, 1)
    record["compile_cache"] = compile_cache.snapshot()
    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, "chip_smoke.json"), "w") as f:
        json.dump(record, f, indent=1)
    _say(f"all phases passed in {record['wall_s']} s; compile cache "
         f"{record['compile_cache']}")
    final = {"ok": True, "device": device}
    if dry:
        final["dry_run"] = True
    print(json.dumps(final), flush=True)


if __name__ == "__main__":
    main()
