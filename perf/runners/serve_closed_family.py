"""Runner ``serve_closed_family``: the closed loop of ``serve_closed``
over a model family other than GPT-2 (the engine picks the family from
the config object it is handed: ``apex_tpu/serving/family.py``).

The loop, the warm-up, the ramp, the window, the drain and the record
are ``serve_closed.py``'s own: that file is loaded and run, with the
three things in it that know GPT-2 replaced for the run (the engine's
config, the weights from the seed, the judge). So every serve metric's
reader finds the record it knows.

``correct`` is ``serve_closed``'s four checks with the tie judge held
against the plain reference ``perf/references/<family>.py`` (float32,
precision "highest", no cache, no kernels; nothing of ``apex_tpu``) over
prompt + answer of the sampled requests, every emitted id inside the
vocabulary slice, and the program's expert layer on the engine's weights
within ``judge_expert_rel_err`` of the reference's over the same
sequences (the number that lower-precision experts fail).

A traced run also sums the xplane's ``XLA Ops`` by the program's named
scopes into ``record["scopes"]`` (``perf/scope_account.py``), and every
run carries the shapes that the byte counts of ``perf/mimo_costs.py``
need in ``record["model"]``.
"""

# the program's family seam FIRST: a program without it (a parent
# commit) fails here, in seconds, before any weight is made
from apex_tpu.serving import family as _family  # noqa: F401

import importlib.util
import os
import types

import numpy as np

from perf import mimo_costs, reduce_trace, scope_account

_HERE = os.path.dirname(os.path.abspath(__file__))
_PROGRAMS = ("jit__decode", "jit__prefill")
SCOPES = ("layer/moe/experts/gmm", "layer/moe/experts", "layer/moe/route",
          "layer/attn_global/attend", "layer/attn_window/attend",
          "layer/attn_global", "layer/attn_window", "layer/mlp", "lm_head",
          "embed", "final_norm", "sample")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _reference(config):
    """The plain reference of the configuration's ``model_type``."""
    return _load(os.path.join(_HERE, os.pardir, "references",
                              config["model_type"] + ".py"),
                 "perf_reference_" + config["model_type"])


def _engine_config(config):
    from apex_tpu.serving.mimo import MiMoConfig

    return MiMoConfig.from_dict(config)


def _params(cfg, seed):
    import jax

    from apex_tpu.serving.mimo import init_params

    return init_params(cfg, jax.random.PRNGKey(seed))


class _ExpertTap:
    """The expert layer of the PROGRAM (``serving/mimo.py`` ``moe_ffn``:
    routing, the sort and the grouped matmul that the timed rounds ran,
    on the weights the engine holds, in its activations' precision) held
    to the plain reference's on the same input: as the reference walks a
    judged sequence it hands over what went into each expert layer and
    what came out (float32), and ``errors`` collects, for every judged
    token that a held expert served, the distance between the two
    outputs over the size of the reference's. The tie judge cannot see
    this layer (a 16th of a token's routed sum: PERF.md, PR 27)."""

    def __init__(self, engine):
        import jax
        import jax.numpy as jnp

        from apex_tpu.serving import mimo

        cfg = engine.cfg
        self._layers = engine.params["layers"]

        def layer_error(lp, inner, want, valid):
            got, _ = mimo.moe_ffn(inner.astype(lp["w_gate"].dtype), lp, cfg,
                                  valid, interpret=engine.interpret)
            got = got.astype(jnp.float32)
            return (jnp.linalg.norm(got - want, axis=-1),
                    jnp.linalg.norm(want, axis=-1),
                    jnp.linalg.norm(got, axis=-1))

        self._layer_error = jax.jit(layer_error)
        # the reference calls back from inside its own matmul precision
        # ("highest"); the program is traced at the precision its rounds
        # ran with, the process's own (Mosaic refuses a float32-precision
        # product of bfloat16 tiles: "Bad lhs type", PR 27)
        self._precision = jax.config.jax_default_matmul_precision
        self.length, self.errors, self.strays = 0, [], 0

    def __call__(self, i, inner, want):
        import jax
        import jax.numpy as jnp

        lp = {k: self._layers[i][k] for k in (
            "router", "router_bias", "w_gate", "w_up", "w_down")}
        valid = jnp.arange(inner.shape[0]) < self.length
        with jax.default_matmul_precision(self._precision):
            out = self._layer_error(lp, inner, want, valid)
        gap, size, got = (np.asarray(a)[:self.length] for a in out)
        served = size > 0
        self.errors += list(gap[served] / size[served])
        # a token the reference gives to no held expert and the program
        # gives to one (a routing flip across the share's edge)
        self.strays += int((got[~served] > 0).sum())


def judge(reference, config, mix, params, done, seed, engine):
    """``serve_closed._judge`` against the plain reference, plus the
    expert layer. For a seeded sample of finished requests: every
    emitted token's reference logit lies within ``judge_tie_steps``
    bfloat16 steps of the reference's best at that position; every
    emitted id lies in the slice; and over the same sequences the
    median relative error of the program's expert layer on the weights
    ``engine`` holds (:class:`_ExpertTap`) is at most
    ``judge_expert_rel_err``. ``params`` are the TRUE weights, the
    reference's; a control serves other weights through ``engine``."""
    rs = np.random.RandomState(seed % 2 ** 32)
    gaps, tap = [], _ExpertTap(engine)
    for k in rs.permutation(len(done))[:mix["judge_requests"]]:
        req = done[k]["req"]
        seq = list(req.prompt) + list(req.out_tokens)
        ids = np.zeros(mix["max_total"], np.int32)   # causal: padding after
        ids[:len(seq)] = seq
        tap.length = len(seq)
        best, chosen = reference.best_and_chosen(config, params, ids, tap)
        at = slice(len(req.prompt) - 1, len(seq) - 1)
        gaps += list((best[at] - chosen[at]) / np.asarray(
            [reference.bf16_step(b) for b in best[at]]))
    gaps = np.sort(np.asarray(gaps, np.float64))
    worst = float(gaps[-1]) if len(gaps) else 0.0
    vocab_ok = all(0 <= t < config["vocab_size"]
                   for r in done for t in r["req"].out_tokens)
    errors = np.asarray(tap.errors, np.float64)
    expert_err = float(np.median(errors)) if len(errors) else float("inf")
    # the five largest gaps and how many tokens were not the reference's
    # own choice: what a later reader needs to judge the width's room;
    # of the expert layer, the tail and the tokens whose routing flipped
    # (one expert's weight moved: tens of percent, not the median's)
    note = {"judged_tokens": len(gaps), "worst_gap_bf16_steps": worst,
            "allowed": mix["judge_tie_steps"], "ids_in_slice": vocab_ok,
            "largest_gaps": [round(float(g), 3) for g in gaps[-5:]],
            "tokens_off_best": int((gaps > 0).sum()),
            "expert_rel_err_median": expert_err,
            "expert_rel_err_allowed": mix["judge_expert_rel_err"],
            "expert_rel_err_p90": float(np.quantile(errors, 0.9))
            if len(errors) else None,
            "expert_layer_tokens": len(errors),
            "expert_tokens_flipped": int((errors > 0.1).sum()) + tap.strays}
    return (len(gaps) > 0 and vocab_ok and worst <= mix["judge_tie_steps"]
            and expert_err <= mix["judge_expert_rel_err"]), note


def _describe_rounds(record):
    """An earlier line for whoever asks why two seeds differ: the
    window's decode-only rounds as the program's spans and counters saw
    them (the device's share, the host's, the experts reached)."""
    from perf.layer_metrics.experts_touched_mean import decode_round_counts
    from perf.span_ring import decode_rounds
    from perf.stats import median

    rounds, counts = decode_rounds(record), decode_round_counts(record)
    if not rounds or not counts:
        return
    mean = lambda key: sum(c[key] for c in counts) / len(counts)  # noqa: E731
    print(f"decode-only rounds: {len(rounds)}, median ms "
          f"{1e3 * median(w for w, _ in rounds):.3f} of which waiting on "
          f"the device {1e3 * median(d for _, d in rounds):.3f}; a round "
          f"reached {mean('experts_touched'):.2f} of "
          f"{counts[0]['experts_held']} held experts with "
          f"{mean('expert_tokens_sum'):.2f} assignments, read "
          f"{mean('global_pages_live'):.1f} pool pages and "
          f"{mean('window_pages'):.1f} ring pages", flush=True)


def run(ctx):
    base = _load(os.path.join(_HERE, "serve_closed.py"),
                 "perf_runner_serve_closed_base")
    reference = _reference(ctx.config)
    config = dict(ctx.config, held_experts=tuple(ctx.config["held_experts"]))
    hlo = scope_account.HloNames(_PROGRAMS) if ctx.trace else None

    held = {}
    warm = base._warm_prefill_rows

    def _warm(engine, rows):   # the first the base runner does with it
        held["engine"] = engine
        return warm(engine, rows)

    def _judge(ctx, cfg, params, records):
        done = [r for r in records
                if r["in_window"] and r["finish"] is not None]
        return judge(reference, config, ctx.traffic, params, done, ctx.seed,
                     held["engine"])

    base._engine_config = _engine_config
    base.weights = types.SimpleNamespace(gpt_params=_params)
    base._warm_prefill_rows = _warm
    base._judge = _judge
    record = base.run(ctx)

    record["model"] = dict(mimo_costs.model_shapes(config),
                           page_size=ctx.traffic["engine"]["page_size"])
    _describe_rounds(record)
    if hlo is not None:
        record["scopes"] = scope_account.by_scope(
            reduce_trace.find_xplane(ctx.trace_dir), hlo.tables, SCOPES)
        if record["scopes"]:
            print("device ms a run, by scope:", {
                program: {"runs": acc["runs"], **{
                    scope: round(1e3 * s / acc["runs"], 4)
                    for scope, s in sorted(acc["seconds"].items())}}
                for program, acc in record["scopes"].items()}, flush=True)
    return record
