"""Runner ``serve_closed_model``: the closed loop of ``serve_closed``
over whatever model family the configuration's ``model_type`` names. The
config object, the weights and the programs come from the program's
family seam (``apex_tpu/serving/family.py`` ``config_from_dict``,
``family_of``), the plain reference from ``perf/references/<model_type>
.py``, the cost functions from ``perf/<family>_costs.py``: a further
family needs files of its own and no runner. Nothing here is imported
from ``serve_closed_family`` (the MiMo cell's runner, which goes once a
``benchmark`` issue may repoint that cell's mix at this one).

The loop, the warm-up, the ramp, the window, the drain and the record
are ``serve_closed.py``'s own (loaded and run with the three things in
it that know GPT-2 replaced), so every serve metric's reader finds the
record it knows. A program without ``family.config_from_dict`` (a parent
commit) fails in :func:`run`'s first line, before any weight is made.

``correct`` is ``serve_closed``'s four checks, with the tie judge held
against the plain reference over prompt + answer of the sampled
requests and every emitted id inside the vocabulary slice, and two
layers of the PROGRAM held to the reference's on the same inputs, on the
weights the engine holds.

The tie judge: every emitted token's reference logit lies within
``judge_tie_steps`` bfloat16 steps of the reference's best at its
position. A mix may spare the ``judge_tie_spared_share`` of the judged
tokens with the largest gaps that width and hold them to
``judge_tie_worst`` instead: where a held expert's score lies within
rounding of the top-k's edge the program may hold another expert's sum
than the reference, a discrete choice on a near-tie one level below the
token's, which moves that one position's logits by many steps in
bfloat16 as configured. The note carries the witness: the reference's
own smallest top-k edge margin (k-th score minus the next, over the
expert layers in which one of the two is held here) at the positions of
the largest gaps, beside the share of all judged positions with a margin
that small (PERF.md §6, PR 31).

The two layers:

* ``expert_rel_err_median``: the family's ``moe_ffn`` (routing, the
  grouped matmul over the held experts, and the shared expert where the
  model has one) within ``judge_expert_rel_err``;
* ``latent_rel_err_median`` (a mix that gives ``judge_latent_rel_err``):
  the family's attention block in its DECODE form, absorbed projections
  reading latent rows THE ENGINE WROTE, out of the engine's own cache
  leaves, against the reference's expanded attention. After the drain
  the idle engine is handed each judged sequence once more (its first
  ``prefill_len`` tokens, one dispatch of its compiled prefill program
  into its own cache), the pages its scheduler gave the sequence are
  copied out of every layer's leaf as they lie, and the block reads
  them through the engine's decode-attention kernel with the reference's
  activations as queries. So what the engine stores (dtype, layout, the
  write, the page table) is in the number, as is the drift of its
  bfloat16 trunk below the layer. The larger of two medians: over the
  answers' positions (those inside the replayed part) and over the
  sequence's first ``LATENT_CHUNK`` positions (contexts of 1-32 rows).

A traced run also sums the xplane's ``XLA Ops`` by the program's named
scopes into ``record["scopes"]`` (``perf/scope_account.py``), and every
run carries the shapes the cost functions need in ``record["model"]``.
Which end-to-end metrics judge a cell is the manifest's word; a
prompt-heavy closed loop such as ``serve-closed-32-longprompt`` is
judged on the first-token wait alone (PERF.md §6, PR 31: over a 51 s
window its tokens a second and its per-token tail spread by more than
their bounds with the ORDER of the same requests), so the run prints the
other two on a line of their own.
"""

import importlib
import importlib.util
import math
import os
import types

import numpy as np

from perf import reduce_trace, scope_account

_HERE = os.path.dirname(os.path.abspath(__file__))
_PROGRAMS = ("jit__decode", "jit__prefill")
SCOPES = ("layer/moe/experts/gmm", "layer/moe/experts", "layer/moe/route",
          "layer/moe/shared", "layer/attn_latent/attend",
          "layer/attn_latent", "layer/attn_global/attend",
          "layer/attn_window/attend", "layer/attn_global",
          "layer/attn_window", "layer/mlp", "lm_head", "embed",
          "final_norm", "sample")
LATENT_CHUNK = 32   # judged tokens an absorbed-attention call takes
EXPERT_ROWS = 1152  # rows of a judged sequence an expert-layer call takes


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
    from apex_tpu.serving import family

    return family.config_from_dict(config)


def _params(cfg, seed):
    import jax

    from apex_tpu.serving import family

    return family.family_of(cfg).init_params(cfg, jax.random.PRNGKey(seed))


def _say_memory(when):
    """The allocator's peak so far, for whoever asks which phase of a
    run set ``memory_peak_bytes``."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    print(f"memory {when}: {stats.get('bytes_in_use', 0) / 1e9:.3f} GB in "
          f"use, peak so far {stats.get('peak_bytes_in_use', 0) / 1e9:.3f}",
          flush=True)


def _rel_errors(got, want):
    gap = np.linalg.norm(got - want, axis=-1)
    return gap, np.linalg.norm(want, axis=-1), np.linalg.norm(got, axis=-1)


def engine_rows(engine, sequences):
    """The latent rows the ENGINE holds of each of ``sequences``, copied
    out of its own cache leaves: ``[(tokens replayed, [a layer's pages
    [1 + pages of a prompt, page size, row]])]``. The engine is emptied
    (whoever is still in flight after the drain leaves; a zeroed cache),
    handed each sequence's first ``prefill_len`` tokens as a prompt, and
    steps once: its compiled prefill program writes them into the pages
    its scheduler allots. Those pages are gathered as they lie, behind
    the pool's null page and padded with it to a full prompt's count (one
    shape, one compile of the block that reads them). The cache is
    dropped again on the way out: the reference needs the room."""
    import jax.numpy as jnp

    from apex_tpu.serving.scheduler import Request

    ps, most = engine.page_size, -(-engine.prefill_len // engine.page_size)
    engine.drain_for_failover(engine.tick)
    out = []
    for k, seq in enumerate(sequences):
        n = min(len(seq), engine.prefill_len)
        req = Request(rid=-10 ** 6 - k, prompt=list(seq[:n]),
                      max_new_tokens=2)
        engine.step(arrivals=[req])
        slot = next(s for s in engine.scheduler.slots
                    if s is not None and s.request is req)
        pages = list(slot.pages[:-(-n // ps)])
        index = jnp.asarray([0] + pages + [0] * (most - len(pages)),
                            jnp.int32)
        out.append((n, [leaf[index] for leaf in engine.cache["latent"]]))
    engine.drain_for_failover(engine.tick)
    engine.cache = None
    return out


class _Taps:
    """Two layers of the program beside the plain reference, as it walks
    a judged sequence (``expert`` and ``latent`` are its two hooks). Both
    run the family module's own functions, the ones the timed rounds
    ran, on the weights the engine holds and in its activations'
    precision; the reference hands over float32 inputs and outputs.
    ``latent`` reads the rows of :func:`engine_rows`. ``expert`` also
    notes the reference's own top-k edge margin at the judged positions
    (the tie judge's witness: the smallest, over the expert layers in
    which the k-th or the next expert is one the chip holds, of the gap
    between their scores), from the TRUE routers ``routers``."""

    def __init__(self, engine, rows, routers, top_k):
        import jax
        import jax.numpy as jnp

        from apex_tpu.serving import kv_cache

        cfg = self.cfg = engine.cfg
        model = importlib.import_module(type(cfg).__module__)
        self._layers = engine.params["layers"]
        self._rows, self._routers, self._top_k = rows, routers, top_k
        kernels = dict(interpret=engine.kernels.interpret)

        def expert_error(lp, inner, valid):
            got, _ = model.moe_ffn(inner.astype(lp["w_gate"].dtype), lp, cfg,
                                   valid, **kernels)
            return got.astype(jnp.float32)

        self._expert = jax.jit(expert_error)
        if rows is not None:
            ps = engine.page_size

            def absorbed(lp, leaf, inner, positions):
                # the decode form for LATENT_CHUNK tokens of one
                # sequence: each its own lane, all on the same pages
                # (the engine's, behind the null page)
                n = leaf.shape[0] - 1
                lengths = positions + 1
                pages = jnp.broadcast_to(
                    1 + jnp.arange(n, dtype=jnp.int32)[None, :],
                    (positions.shape[0], n))
                table, base = kv_cache.pool_view(pages, positions, lengths,
                                                 ps)

                def attend(q_nope, q_pe, row):
                    return model.attend_absorbed(
                        q_nope, q_pe, leaf, lp, cfg, lengths, table, base,
                        decode_impl=engine.kernels.decode_impl, **kernels)

                return model.latent_attention(
                    inner.astype(lp["wo"].dtype), lp, cfg, positions,
                    attend).astype(jnp.float32)

            self._absorbed = jax.jit(absorbed)
        # the reference calls back from inside its own matmul precision
        # ("highest"); the program is traced at the precision its rounds
        # ran with, the process's own (Mosaic refuses a float32-precision
        # product of bfloat16 tiles: "Bad lhs type", PR 27)
        self._precision = jax.config.jax_default_matmul_precision
        self.sequence, self.length, self.judged = 0, 0, slice(0, 0)
        self.expert_errors, self.strays = [], 0
        self.latent_early, self.latent_answers = [], []
        self.margins = []   # a judged sequence's [judged positions] each

    def start(self, k, length, judged):
        self.sequence, self.length, self.judged = k, length, judged
        self.margins.append(np.full(judged.stop - judged.start, np.inf))

    def expert(self, i, inner, want):
        import jax
        import jax.numpy as jnp

        lp = {k: v for k, v in self._layers[i].items()
              if k.startswith(("router", "w_", "shared_"))}
        valid = jnp.arange(inner.shape[0]) < self.length
        # in blocks of rows: a whole judged sequence at once costs the
        # program's expert layer ~3 GB of temporaries beside the
        # reference's float32 layer
        block = EXPERT_ROWS if inner.shape[0] % EXPERT_ROWS == 0 \
            else inner.shape[0]
        with jax.default_matmul_precision(self._precision):
            got = np.concatenate([
                np.asarray(self._expert(lp, inner[r0:r0 + block],
                                        valid[r0:r0 + block]))
                for r0 in range(0, inner.shape[0], block)])
        gap, size, got = (a[:self.length] for a in _rel_errors(
            got, np.asarray(want)))
        served = size > 0
        self.expert_errors += list(gap[served] / size[served])
        # a token the reference gives to no expert here and the program
        # gives to one (never, where a shared expert serves every token)
        self.strays += int((got[~served] > 0).sum())
        # the reference's own edge where a flip would show HERE: k-th
        # score minus the next, where either of the two experts is held
        x = np.asarray(inner[self.judged], np.float64)
        scores = 1.0 / (1.0 + np.exp(-x @ self._routers[i].T))
        order = np.argsort(-scores, axis=-1)[:, self._top_k - 1:
                                             self._top_k + 1]
        edge = np.take_along_axis(scores, order, axis=-1)
        first, count = self.cfg.held_experts
        held = ((order >= first) & (order < first + count)).any(axis=-1)
        self.margins[-1] = np.minimum(self.margins[-1], np.where(
            held, edge[:, 0] - edge[:, 1], np.inf))

    def latent(self, i, inner, want):
        import jax
        import jax.numpy as jnp

        lp = {k: v for k, v in self._layers[i].items()
              if k.startswith(("wq_", "q_norm", "wkv_", "kv_norm", "wo"))}
        n, leaves = self._rows[self.sequence]
        early = np.arange(min(LATENT_CHUNK, n))
        # the answers' positions as far as the replayed part holds them,
        # else its last positions
        answers = np.arange(
            max(0, min(self.judged.start, n - LATENT_CHUNK)),
            min(self.judged.stop, n))
        with jax.default_matmul_precision(self._precision):
            for at, errors in ((early, self.latent_early),
                               (answers, self.latent_answers)):
                for c0 in range(0, len(at), LATENT_CHUNK):
                    pos = at[c0:c0 + LATENT_CHUNK]
                    padded = np.resize(pos, LATENT_CHUNK)   # repeats, dropped
                    got = self._absorbed(lp, leaves[i], inner[padded],
                                         jnp.asarray(padded, jnp.int32))
                    gap, size, _ = _rel_errors(np.asarray(got)[:len(pos)],
                                               np.asarray(want[pos]))
                    errors += list(gap / size)


def judge(reference, config, mix, params, done, seed, engine):
    """``serve_closed._judge`` against the plain reference, plus the two
    layers of :class:`_Taps`. For a seeded sample of finished requests:
    every emitted token's reference logit lies within
    ``judge_tie_steps`` bfloat16 steps of the reference's best at that
    position (the spared share within ``judge_tie_worst``: module
    docstring); every emitted id lies in the slice; over the same
    sequences the median relative error of the program's expert layer is
    at most ``judge_expert_rel_err`` and, where the mix gives it, that
    of its absorbed attention block over the engine's own cache rows
    (over the answers' positions and over each sequence's first rows,
    the larger) at most ``judge_latent_rel_err``. ``params`` are the
    TRUE weights, the reference's; a control serves other weights, or
    another cache dtype, through ``engine``."""
    latent = "judge_latent_rel_err" in mix
    rs = np.random.RandomState(seed % 2 ** 32)
    sample = [done[k]["req"] for k in
              rs.permutation(len(done))[:mix["judge_requests"]]]
    sequences = [list(req.prompt) + list(req.out_tokens) for req in sample]
    routers = {i: np.asarray(lp["router"], np.float64)
               for i, lp in enumerate(params["layers"]) if "router" in lp}
    taps = _Taps(engine, engine_rows(engine, sequences) if latent else None,
                 routers, config["num_experts_per_tok"])
    hooks = dict(tap=taps.expert, **(
        {"attn_tap": taps.latent} if latent else {}))
    gaps = []
    for k, (req, seq) in enumerate(zip(sample, sequences)):
        ids = np.zeros(mix["max_total"], np.int32)   # causal: padding after
        ids[:len(seq)] = seq
        at = slice(len(req.prompt) - 1, len(seq) - 1)
        taps.start(k, len(seq), at)
        best, chosen = reference.best_and_chosen(config, params, ids, **hooks)
        gaps += list((best[at] - chosen[at]) / np.asarray(
            [reference.bf16_step(b) for b in best[at]]))
    order = np.argsort(np.asarray(gaps, np.float64))
    gaps = np.asarray(gaps, np.float64)[order]
    margins = np.concatenate(taps.margins)[order] if len(order) \
        else np.zeros(0)
    worst = float(gaps[-1]) if len(gaps) else 0.0
    # the tokens a routing near-tie may have moved: spared the width,
    # held to the wide cap (module docstring)
    spared = math.ceil(len(gaps) * mix.get("judge_tie_spared_share", 0.0))
    bulk = float(gaps[-1 - spared]) if len(gaps) > spared else 0.0
    cap = mix.get("judge_tie_worst", mix["judge_tie_steps"])
    vocab_ok = all(0 <= t < config["vocab_size"]
                   for r in done for t in r["req"].out_tokens)

    def median(errors):
        return float(np.median(errors)) if len(errors) else float("inf")

    expert_err = median(taps.expert_errors)
    latent_err = max(median(taps.latent_early), median(taps.latent_answers))
    errors = np.asarray(taps.expert_errors, np.float64)
    note = {"judged_tokens": len(gaps), "worst_gap_bf16_steps": worst,
            "worst_allowed": cap, "tokens_spared": spared,
            "gap_below_the_spared_bf16_steps": bulk,
            "allowed": mix["judge_tie_steps"], "ids_in_slice": vocab_ok,
            "largest_gaps": [round(float(g), 3) for g in gaps[-12:]],
            "gaps_at_1_50_99_percent": [
                round(float(g), 3)
                for g in np.quantile(gaps, (0.01, 0.5, 0.99))]
            if len(gaps) else None,
            # None: no held expert at the top-k's edge in any layer
            "held_edge_margin_at_largest_gaps": [
                float(f"{m:.3g}") if np.isfinite(m) else None
                for m in margins[-12:]],
            "share_of_positions_with_held_edge_margin_under_0.002":
            float((margins < 0.002).mean()) if len(margins) else None,
            "tokens_off_best": int((gaps > 0).sum()),
            "expert_rel_err_median": expert_err,
            "expert_rel_err_allowed": mix["judge_expert_rel_err"],
            "expert_rel_err_p90": float(np.quantile(errors, 0.9))
            if len(errors) else None,
            "expert_layer_tokens": len(errors),
            "expert_tokens_flipped": int((errors > 0.1).sum()) + taps.strays}
    ok = (len(gaps) > 0 and vocab_ok and bulk <= mix["judge_tie_steps"]
          and worst <= cap and expert_err <= mix["judge_expert_rel_err"])
    if latent:
        note.update(latent_rel_err_median=latent_err,
                    latent_rel_err_allowed=mix["judge_latent_rel_err"],
                    latent_rel_err_first_rows=median(taps.latent_early),
                    latent_rel_err_answers=median(taps.latent_answers),
                    latent_block_tokens=len(taps.latent_early)
                    + len(taps.latent_answers))
        ok = ok and latent_err <= mix["judge_latent_rel_err"]
    return ok, note


def _describe_rounds(record):
    """An earlier line for whoever asks why two seeds differ: the
    window's decode-only rounds as the program's spans and counters saw
    them (the device's share, the host's, the experts reached, the
    latent pages read), and the serve metrics whatever the manifest
    judges the cell on."""
    from perf.end_to_end import serve_tok_s, tpot_p95_ms, ttft_mean_ms
    from perf.layer_metrics.held_experts_touched import decode_round_counts
    from perf.span_ring import decode_rounds
    from perf.stats import median

    print("judged or not: serve_tok_s", serve_tok_s.read(record),
          "ttft_mean_ms", ttft_mean_ms.read(record),
          "tpot_p95_ms", tpot_p95_ms.read(record),
          "over", sum(1 for r in record["requests"]
                      if r["finish"] is not None), "requests", flush=True)
    rounds, counts = decode_rounds(record), decode_round_counts(record)
    if not rounds or not counts:
        return

    def mean(key):
        return sum(c.get(key, 0) for c in counts) / len(counts)

    print(f"decode-only rounds: {len(rounds)}, median ms "
          f"{1e3 * median(w for w, _ in rounds):.3f} of which waiting on "
          f"the device {1e3 * median(d for _, d in rounds):.3f}; a round "
          f"reached {mean('experts_touched'):.2f} of "
          f"{counts[0]['experts_held']} held experts with "
          f"{mean('expert_tokens_sum'):.2f} assignments and read "
          f"{mean('latent_pages_live'):.1f} latent pages", flush=True)


def run(ctx):
    config = dict(ctx.config, held_experts=tuple(ctx.config["held_experts"]))
    family = _engine_config(config).serving_family   # a parent fails HERE
    base = _load(os.path.join(_HERE, "serve_closed.py"),
                 "perf_runner_serve_closed_base")
    reference = _reference(ctx.config)
    costs = importlib.import_module(f"perf.{family}_costs")
    hlo = scope_account.HloNames(_PROGRAMS) if ctx.trace else None

    held = {}
    warm = base._warm_prefill_rows

    def _warm(engine, rows):   # the first the base runner does with it
        held["engine"] = engine
        _say_memory("with the engine built")
        return warm(engine, rows)

    def _judge(ctx, cfg, params, records):
        done = [r for r in records
                if r["in_window"] and r["finish"] is not None]
        _say_memory("after the drain, before the judge")
        verdict = judge(reference, config, ctx.traffic, params, done,
                        ctx.seed, held["engine"])
        _say_memory("after the judge")
        return verdict

    base._engine_config = _engine_config
    base.weights = types.SimpleNamespace(gpt_params=_params)
    base._warm_prefill_rows = _warm
    base._judge = _judge
    record = base.run(ctx)

    record["model"] = dict(costs.model_shapes(config),
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
