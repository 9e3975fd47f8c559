"""Runner ``serve_closed_hybrid``: the closed loop of ``serve_closed`` over
a family whose layers keep, beside the paged KV pool, a STATE EACH SLOT
OWNS (``apex_tpu/serving/falcon_h1.py``: a recurrent state and its
convolution's last inputs, updated in place by every decode step). The
config object, the weights and the programs come from the program's
family seam (``family.config_from_dict``, ``family_of``), the plain
reference from ``perf/references/<model_type>.py``, the cost functions
from ``perf/<family>_costs.py``.

The loop, the warm-up, the ramp, the window, the drain and the record
are ``serve_closed.py``'s own (loaded and run with the three things in
it that know GPT-2 replaced, as ``serve_closed_model`` does), so every
serve metric's reader finds the record it knows. A program whose seam
knows no such ``model_type`` (a parent commit) fails in :func:`run`'s
first lines, before any weight is made.

``correct`` is ``serve_closed``'s four checks and, over a seeded sample
of ``judge_requests`` requests finished inside the window, against the
plain reference as the published code serves (``cache_from``: the state
and the conv's inputs stored in the configuration's ``cache_dtype``
from the prompt's end on, every decode step reading and storing them):

* the TIE JUDGE: every emitted token's reference logit lies within
  ``judge_tie_steps`` bfloat16 steps of the reference's best at its
  position (the largest ``judge_tie_spared_share`` of the gaps within
  ``judge_tie_worst``), the reference's full forward of prompt +
  answer: the logits of the prefill, then of every decode step through
  the cache; every emitted id inside the vocabulary slice;
* the STATE: after the drain the idle engine serves the sampled
  requests again (their prompts, as many tokens, greedy: the packed
  prefill program, the prompts packed into its dispatches together,
  then every decode step through its in-place update), and the rows of
  the state leaves and the conv leaves it wrote for each, every layer,
  copied out of its own leaves twice, are held to the reference's state
  and conv inputs at the same positions: the largest relative error
  (Frobenius) over the layers and the requests, of either leaf, when
  its PREFILL returned (the prompt's last token; ``state_rel_err``) at
  most ``judge_state_rel_err``, and when it FINISHED (the last token
  fed; ``decoded_state_rel_err``) at most
  ``judge_decoded_state_rel_err``. What the engine stores (dtype, the
  write, the slot's row, the carry across chunks, the restart at every
  packed sequence, hundreds of in-place updates) is in the numbers, as
  is the drift of its bfloat16 trunk below each layer. The first sees
  what a prefill carries across a packed boundary, which the decay has
  washed out by the second; the second, what hundreds of updates pile
  up in a lower precision of the update. Held to the float32 math
  instead, a state stored in bfloat16 drifts from it by itself (a decay
  of 0.999 a token is below its rounding: 5-25% after hundreds of
  tokens, PERF.md §6), and the precision of the update is lost in that.
  The rows are read after the window, not in it: a copy of a sampled
  slot's rows at its prefill and at its finish would put device copies
  and a scan of the slots into the rounds the window times. The re-serve
  runs the window's own two programs (every slot's grid, the prompts
  packed into one dispatch), and a slot's update reads and writes its
  own row alone, whoever else is live;
* the MIXER (``mixer_rel_err``): layer 0's mixer in its DECODE form
  (``falcon_h1.mixer`` through ``decode_recur``: the conv over the
  copied conv row, one step of ``ops.ssm.ssm_decode_update`` on the
  copied state row, the gated norm, the projection), on the weights the
  engine holds, fed the reference's normed input at the next position,
  against the reference's mixer output there: the largest relative
  error over the requests at most ``judge_mixer_rel_err``. Layer 0's
  input is the embedding both sides hold alike, so the number is the
  state's and the mixer's own error.

A re-served request whose greedy tokens differ from the window's (a
near-tie of the logits, served in another packing) is held to the
reference over its own sequence for the state and the mixer; the note
counts them.

A traced run sums the xplane's ``XLA Ops`` by the program's named
scopes into ``record["scopes"]`` (``perf/scope_account.py``), with the
``while/body``, ``cond/``, ``branch_N_fun/`` and ``jit(...)/`` segments
that the prefill's switch and its loop of state writes put in the middle
of an op's name cut out; every run carries the shapes the cost
functions need in ``record["model"]``.
"""

import importlib
import importlib.util
import math
import os
import re
import types

import numpy as np

from perf import reduce_trace, scope_account

_HERE = os.path.dirname(os.path.abspath(__file__))
_PROGRAMS = ("jit__decode", "jit__prefill")
SCOPES = ("layer/ssm/scan", "layer/ssm/in_proj", "layer/ssm/conv",
          "layer/ssm/gate_norm", "layer/ssm/out", "layer/ssm",
          "layer/attn/attend", "layer/attn/kv_write", "layer/attn/qkv",
          "layer/attn/rope", "layer/attn/out", "layer/attn", "layer/mlp",
          "lm_head", "embed", "final_norm", "sample")
_NESTED = re.compile(r"(?:cond|branch_\d+_fun|while|body|jit\([^)]*\))/")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _say_memory(when):
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    print(f"memory {when}: {stats.get('bytes_in_use', 0) / 1e9:.3f} GB in "
          f"use, peak so far {stats.get('peak_bytes_in_use', 0) / 1e9:.3f}",
          flush=True)


def serve_again(engine, requests):
    """The ``requests`` served again by the idle engine, alone (whoever
    is still in flight after the drain leaves; a fresh cache): ``[(the
    tokens it emitted, [a layer's (state row, conv row)] after its
    prefill, the same when it finished)]``. A row is copied out of the
    engine's own leaves by the call that wrote it: the prefill half of
    the round that admitted the request (it returns at the first token,
    before the decode step that follows), and the round it finished in,
    before any later round could hand its slot on. The cache is dropped
    on the way out: the reference needs the room."""
    from apex_tpu.serving.scheduler import Request

    engine.drain_for_failover(engine.tick)
    reqs = [Request(rid=-10 ** 6 - k, prompt=list(r.prompt),
                    max_new_tokens=r.max_new_tokens)
            for k, r in enumerate(requests)]
    slots, prefilled, finished = {}, {}, {}

    def rows(k):
        return [(engine.cache["ssm"][i][slots[k]],
                 engine.cache["conv"][i][slots[k]])
                for i in range(len(engine.cache["ssm"]))]

    engine.step(arrivals=reqs)
    while True:
        for k, req in enumerate(reqs):
            if k not in slots:
                slots.update({k: i for i, s in enumerate(
                    engine.scheduler.slots)
                    if s is not None and s.request is req})
            if k in slots and k not in prefilled \
                    and len(req.out_tokens) == 1:
                prefilled[k] = rows(k)
            if req.done() and k not in finished:
                finished[k] = rows(k)
        if len(finished) == len(reqs):
            break
        engine.step()
    engine.cache = None
    return [(list(req.out_tokens), prefilled[k], finished[k])
            for k, req in enumerate(reqs)]


def _rel(got, want):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def _mixer_step(engine):
    """Layer 0's mixer in the decode form over ONE slot's copied rows:
    ``(lp, state row, conv row, u [hidden]) -> [hidden]`` float32."""
    import jax
    import jax.numpy as jnp

    model = importlib.import_module(type(engine.cfg).__module__)
    cfg = engine.cfg

    def step(lp, state, conv, u):
        cache = {"ssm": [jnp.stack([state, jnp.zeros_like(state)])],
                 "conv": [jnp.stack([conv, jnp.zeros_like(conv)])]}
        recur = model.decode_recur(cache, 0, lp, cfg, jnp.ones((1,), bool),
                                   interpret=engine.kernels.interpret)
        return model.mixer(u[None].astype(lp["w_in"].dtype), lp, cfg,
                           recur)[0]

    return jax.jit(step)


def judge(reference, config, mix, params, done, seed, engine):
    """The module docstring's three checks; ``params`` are the TRUE
    weights, the reference's; ``engine`` the one that served (a control
    serves other weights or another program through it)."""
    import jax.numpy as jnp

    rs = np.random.RandomState(seed % 2 ** 32)
    sample = [done[k]["req"] for k in
              rs.permutation(len(done))[:mix["judge_requests"]]]
    lp0 = engine.params["layers"][0]
    step = _mixer_step(engine)
    again = serve_again(engine, sample)
    gaps, prefill_errs, end_errs, mixer_errs, differed = [], [], [], [], 0
    for req, (tokens, first, rows) in zip(sample, again):
        seq = list(req.prompt) + list(req.out_tokens)
        mine = list(req.prompt) + tokens
        differed += mine != seq
        # the prompt's last token, and the last token the engine fed
        at = [len(req.prompt) - 1, len(mine) - 2]
        seen = {}

        def tap(i, u, m, seen=seen, pos=at[1] + 1):
            if i == 0:
                seen["u"], seen["m"] = np.asarray(u[pos]), np.asarray(m[pos])

        def padded(s):
            ids = np.zeros(mix["max_total"], np.int32)   # causal: after
            ids[:len(s)] = s
            return ids

        best, chosen, states = reference.best_and_chosen(
            config, params, padded(mine), at=at, mixer_tap=tap,
            cache_from=len(req.prompt))
        if mine != seq:
            best, chosen, _ = reference.best_and_chosen(
                config, params, padded(seq), cache_from=len(req.prompt))
        judged = slice(len(req.prompt) - 1, len(seq) - 1)
        gaps += list((best[judged] - chosen[judged]) / np.asarray(
            [reference.bf16_step(b) for b in best[judged]]))
        for j, got in enumerate((first, rows)):
            errs = (prefill_errs, end_errs)[j]
            errs.append([max(_rel(np.asarray(s, np.float32), S[j]),
                             _rel(np.asarray(c, np.float32).reshape(
                                 C[j].shape), C[j]))
                         for (s, c), (S, C) in zip(got, states)])
        got = step(lp0, rows[0][0], rows[0][1], jnp.asarray(seen["u"]))
        mixer_errs.append(_rel(np.asarray(got, np.float32), seen["m"]))
    gaps = np.sort(np.asarray(gaps, np.float64))
    spared = math.ceil(len(gaps) * mix.get("judge_tie_spared_share", 0.0))
    worst = float(gaps[-1]) if len(gaps) else 0.0
    bulk = float(gaps[-1 - spared]) if len(gaps) > spared else 0.0
    cap = mix.get("judge_tie_worst", mix["judge_tie_steps"])
    vocab_ok = all(0 <= t < config["vocab_size"]
                   for r in done for t in r["req"].out_tokens)
    state_err = max(max(e) for e in prefill_errs) if prefill_errs \
        else math.inf
    end_err = max(max(e) for e in end_errs) if end_errs else math.inf
    mixer_err = max(mixer_errs) if mixer_errs else math.inf

    def by_layer(errs):
        return [float(f"{max(e[i] for e in errs):.4g}")
                for i in range(len(errs[0]))] if errs else []

    note = {"judged_tokens": len(gaps), "worst_gap_bf16_steps": worst,
            "worst_allowed": cap, "tokens_spared": spared,
            "gap_below_the_spared_bf16_steps": bulk,
            "allowed": mix["judge_tie_steps"], "ids_in_slice": vocab_ok,
            "largest_gaps": [round(float(g), 3) for g in gaps[-12:]],
            "tokens_off_best": int((gaps > 0).sum()),
            "state_rel_err": state_err,
            "state_rel_err_allowed": mix["judge_state_rel_err"],
            "state_rel_err_by_layer": by_layer(prefill_errs),
            "decoded_state_rel_err": end_err,
            "decoded_state_rel_err_allowed":
                mix["judge_decoded_state_rel_err"],
            "decoded_state_rel_err_by_layer": by_layer(end_errs),
            "mixer_rel_err": mixer_err,
            "mixer_rel_err_by_request": [float(f"{e:.4g}")
                                         for e in mixer_errs],
            "mixer_rel_err_allowed": mix["judge_mixer_rel_err"],
            "served_again_with_other_tokens": differed}
    ok = (len(gaps) > 0 and vocab_ok and bulk <= mix["judge_tie_steps"]
          and worst <= cap
          and state_err <= mix["judge_state_rel_err"]
          and end_err <= mix["judge_decoded_state_rel_err"]
          and mixer_err <= mix["judge_mixer_rel_err"])
    return ok, note


def _say_serve_metrics(record):
    """A line for whoever asks why two seeds differ: the serve metrics
    whatever the manifest judges the cell on, and the window's
    decode-only rounds as the program's spans saw them."""
    from perf.end_to_end import serve_tok_s, tpot_p95_ms, ttft_mean_ms
    from perf.span_ring import decode_rounds
    from perf.stats import median

    rounds = decode_rounds(record) or []
    print("judged or not: serve_tok_s", serve_tok_s.read(record),
          "ttft_mean_ms", ttft_mean_ms.read(record),
          "tpot_p95_ms", tpot_p95_ms.read(record), "| decode-only rounds",
          len(rounds), "median ms", rounds and 1e3 * median(
              w for w, _ in rounds), "waiting on the device",
          rounds and 1e3 * median(d for _, d in rounds), flush=True)


def run(ctx):
    import jax

    from apex_tpu.serving import family

    config = dict(ctx.config)
    name = family.config_from_dict(config).serving_family  # a parent fails
    base = _load(os.path.join(_HERE, "serve_closed.py"),
                 "perf_runner_serve_closed_hybrid_base")
    reference = _load(os.path.join(_HERE, os.pardir, "references",
                                   config["model_type"] + ".py"),
                      "perf_reference_" + config["model_type"])
    costs = importlib.import_module(f"perf.{name}_costs")
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

    def _params(cfg, seed):
        return family.family_of(cfg).init_params(cfg,
                                                 jax.random.PRNGKey(seed))

    base._engine_config = family.config_from_dict
    base.weights = types.SimpleNamespace(gpt_params=_params)
    base._warm_prefill_rows = _warm
    base._judge = _judge
    record = base.run(ctx)

    record["model"] = costs.model_shapes(config)
    _say_serve_metrics(record)
    if hlo is not None:
        tables = {program: {op: _NESTED.sub("", op_name)
                            for op, op_name in table.items()}
                  for program, table in hlo.tables.items()}
        record["scopes"] = scope_account.by_scope(
            reduce_trace.find_xplane(ctx.trace_dir), tables, SCOPES)
        if record["scopes"]:
            print("device ms a run, by scope:", {
                program: {"runs": acc["runs"], **{
                    scope: round(1e3 * s / acc["runs"], 4)
                    for scope, s in sorted(acc["seconds"].items())}}
                for program, acc in record["scopes"].items()}, flush=True)
    return record
