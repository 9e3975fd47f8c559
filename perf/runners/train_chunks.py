"""Runner ``train_chunks``: the trainer's entry point,
``examples.transformer.pretrain.main``, driven from outside.

``main`` runs a COUNT of steps, not a duration, and each call builds a
new ``jit`` (re-trace, lower, reload the executable even on a cache
hit). So a run makes ONE call of ``chunk_steps * (1 + ceil(seconds *
rate / chunk_steps))`` steps, with ``rate`` (steps a second) read from a
calibration file beside the compile cache; a checkout without one (its
first run, which compiles anyway) makes a two-chunk call first and
writes it. The first chunk of the long call (trace, lower, cache load) is
set-up; the window is every chunk after it.

The harness keeps its own clock: ``main`` prints one `` iter N:`` line
per chunk right after it fetched the chunk's losses, and a tap on
``sys.stdout`` stamps ``time.perf_counter`` there. The same tap starts
and stops the profiler at chunk boundaries in a traced run.
"""

import contextlib
import json
import math
import os
import statistics
import sys
import time

import numpy as np

from perf import oracle, stats, tracing

_SCALE_WINDOW, _INIT_SCALE = 2000, 2.0 ** 16   # amp/scaler.py's dynamic scaler


class _ChunkTap:
    """``sys.stdout`` for the length of a ``main`` call: passes every
    write through, stamps the harness clock at each chunk's log line and
    runs ``on_chunk(index)`` there."""

    def __init__(self, real, on_chunk=None):
        self.real, self.on_chunk, self.stamps = real, on_chunk, []

    def write(self, text):
        if text.startswith(" iter "):
            self.stamps.append(time.perf_counter())
            if self.on_chunk:
                self.on_chunk(len(self.stamps) - 1)
        return self.real.write(text)

    def flush(self):
        self.real.flush()


def _argv(ctx, iters):
    config, mix = ctx.config, ctx.traffic
    return [
        "--model", "gpt", "--num-layers", str(config["n_layer"]),
        "--hidden-size", str(config["n_embd"]),
        "--num-attention-heads", str(config["n_head"]),
        "--max-position-embeddings", str(config["n_positions"]),
        "--vocab-size", str(config["vocab_size"]),
        "--seq-length", str(mix["seq"]),
        "--micro-batch-size", str(mix["micro_batch"]),
        "--tensor-model-parallel-size", str(mix["tp"]),
        "--seed", str(ctx.seed % (2 ** 32)),
        "--log-interval", str(mix["chunk_steps"]),
        "--train-iters", str(iters), *mix["argv"]]


def _call_main(ctx, n_chunks, on_chunk=None):
    from examples.transformer import pretrain

    tap = _ChunkTap(sys.stdout, on_chunk)
    t0 = time.perf_counter()
    print(f"[{t0 - ctx.t_start:.1f} s] pretrain.main, {n_chunks} chunks",
          flush=True)
    with contextlib.redirect_stdout(tap):
        out = pretrain.main(_argv(ctx, n_chunks * ctx.traffic["chunk_steps"]))
    if len(tap.stamps) != n_chunks or len(out["chunks"]) != n_chunks:
        raise RuntimeError(
            f"asked pretrain.main for {n_chunks} chunks, saw "
            f"{len(tap.stamps)} log lines and {len(out['chunks'])} records")
    # the program's own stamps ride the same clock: they must agree
    drift = max(abs(mine - c["t_end"])
                for mine, c in zip(tap.stamps, out["chunks"]))
    if drift > 0.05:
        raise RuntimeError(f"chunk stamps disagree by {drift:.3f} s")
    return out, [t0] + tap.stamps


def _steps_per_second(ctx):
    """The calibrated rate of this checkout, making it on a first run."""
    path = os.path.join(ctx.state_dir, "calibration",
                        ctx.workload + (".rehearse" if ctx.rehearse else "")
                        + ".json")
    if os.path.exists(path):
        with open(path) as fh:
            return json.load(fh)["steps_per_s"]
    _, stamps = _call_main(ctx, 2)
    rate = ctx.traffic["chunk_steps"] / (stamps[2] - stamps[1])
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as fh:
        json.dump({"steps_per_s": rate, "device": ctx.device["kind"]}, fh)
    print(f"calibrated {rate:.3f} steps/s -> {path}", flush=True)
    return rate


def _padded_vocab(config, tp):
    mult = 128 * tp   # MegatronArgs.pad_vocab_size
    return -(-config["vocab_size"] // mult) * mult


def _transformer_config(ctx):
    """The ``TransformerConfig`` ``main`` builds from the same argv."""
    from apex_tpu.transformer.testing import parse_args
    from examples.transformer import pretrain

    args = parse_args(_argv(ctx, 1), extra_args_provider=pretrain._extra_args,
                      world_size=ctx.chips)
    args.pad_vocab_size(args.vocab_size)
    return args.to_transformer_config()


def _first_loss_check(ctx, first_loss, vocab, dp):
    """(ok, note): one chip holds the first loss to the float32 oracle on
    the same batch and the same seed's initial weights (the batch is
    drawn as ``main`` draws it); several chips, where the initial weights
    are per tensor-parallel rank, to a band around ln(padded vocab)."""
    mix = ctx.traffic
    band = abs(first_loss - math.log(vocab)) <= mix["first_loss_band"]
    note = {"first_loss": first_loss, "ln_vocab": math.log(vocab)}
    if ctx.chips > 1 or "first_loss_oracle_tol" not in mix:
        return band, note
    rs = np.random.RandomState(ctx.seed % (2 ** 32))
    shape = (dp * mix["micro_batch"], mix["seq"])
    ids = rs.randint(0, vocab, shape).astype(np.int32)
    labels = rs.randint(0, vocab, shape).astype(np.int32)
    want = oracle.initial_loss(_transformer_config(ctx),
                               ctx.seed % (2 ** 32), ids, labels)
    note.update(oracle_loss=want, tol=mix["first_loss_oracle_tol"])
    return band and abs(first_loss - want) <= mix["first_loss_oracle_tol"], \
        note


def run(ctx):
    mix = ctx.traffic
    steps, tp = mix["chunk_steps"], mix["tp"]
    dp = ctx.chips // tp
    rate = _steps_per_second(ctx)
    n_chunks = 1 + max(2, math.ceil(ctx.seconds * rate / steps))

    stack = contextlib.ExitStack()

    def on_chunk(index):
        if index == 0:
            stack.enter_context(tracing.window(ctx.trace_dir))
        elif index == min(mix["trace_chunks"], n_chunks - 1):
            stack.close()

    out, stamps = _call_main(ctx, n_chunks, on_chunk if ctx.trace else None)
    t_open, t_close = stamps[1], stamps[-1]
    chunks = [{"seconds": b - a, "losses": c["losses"],
               "programs": c["programs"]}
              for a, b, c in zip(stamps[:-1], stamps[1:], out["chunks"])]
    steady = chunks[1:]
    losses = [x for c in chunks for x in c["losses"]]
    total = steps * n_chunks

    backoffs = math.log2(_INIT_SCALE / out["loss_scale"])
    skipped_late = total < _SCALE_WINDOW and \
        out["unskipped"] != total - backoffs
    compiles = ctx.compile_log.between(t_open, t_close)
    vocab = _padded_vocab(ctx.config, tp)
    loss_ok, loss_note = _first_loss_check(ctx, losses[0], vocab, dp)
    checks = {
        "losses_finite": all(map(math.isfinite, losses)),
        "loss_falls": statistics.fmean(steady[-1]["losses"])
        < statistics.fmean(chunks[0]["losses"]),
        "no_late_skip": not out["overflow"] and not skipped_late,
        "one_program": all(c["programs"] == 1 for c in chunks),
        "no_compile_in_window": compiles == 0,
        "first_loss": loss_ok,
    }
    print("train checks", json.dumps(checks), json.dumps(loss_note),
          f"loss {losses[0]:.4f} -> {losses[-1]:.4f}, scale "
          f"{out['loss_scale']:.0f}, unskipped {out['unskipped']}/{total}",
          flush=True)
    return {
        "kind": "train", "setup_s": t_open - ctx.t_start,
        "window_s": t_close - t_open, "correct": all(checks.values()),
        "attempted": steps * len(steady),
        "failed": sum(1 for c in steady for x in c["losses"]
                      if not math.isfinite(x)),
        "checks": checks, "chunks": steady, "chunk_steps": steps,
        "tokens_per_step": dp * mix["micro_batch"] * mix["seq"],
        "n_params": stats.gpt2_params(ctx.config, vocab),
    }
