"""Runner ``serve_closed_sparse``: ``serve_closed_model``'s closed loop,
record and judge (loaded and run as that runner loads ``serve_closed``,
with the things in it that know dense latent attention replaced) for a
family whose FULL layers select the rows they attend to and whose
SLIDING layers keep a latent ring (``apex_tpu/serving/dots3.py``; the
reference and the cost functions are found by ``model_type`` and family
name, as there).

``correct`` is ``serve_closed_model``'s (the four checks of
``serve_closed``, the tie judge against the plain reference over prompt +
answer of the sampled requests, ``expert_rel_err_median``) with its
latent-block check replaced by three of what is new, all on rows THE
ENGINE WROTE (``engine_rows``: after the drain the idle engine prefills
each judged sequence once more and the pages its scheduler gave it, of
all three kinds of state, are copied out of its own leaves):

* **the selection** (``selection_share``): at the judged positions past
  ``index_topk`` of every full layer, the program's indexer (its decode
  form: ``index_scores_paged`` over the engine's index-key pages, then
  ``select_rows``), given the reference's activations as the query, picks
  rows; the share of them that the reference's ``lax.top_k`` on its
  float32 scores picked too, over all judged queries and layers, is at
  least ``judge_selection_share``. A row at the ``index_topk``-th score's
  edge is a discrete choice on a near-tie: the note carries the witness,
  the reference's own edge margin (the k-th score minus the next) beside
  the spread of a query's scores;
* **the sparse block** (``latent_rel_err_median``, the key
  ``serve_closed_model`` reports it under): the FIRST full layer's
  attention block in its DECODE form (scores, selection, the gathered
  rows read in the absorbed form, gate, ``wo``) over the engine's latent
  and index leaves against the reference's block output: the larger of
  the medians over the answers' positions (contexts past ``index_topk``:
  the selection is live) and over the first ``CHUNK`` positions (the
  dense walk), at most ``judge_latent_rel_err``. The first layer's rows
  are made from the embeddings, which both sides hold alike, so the
  number is the block's own error (rounding, and the rows that flip at
  the selection's edge) and a lower precision shows in it;
* **every layer's block, the trunk's drift included**
  (``block_rel_err_deepest``): the same comparison for every full layer
  (the answers' positions) and every sliding layer (its decode form over
  the slot's ring, at the last replayed position of each judged
  sequence: the ring holds one window, its prefill keeps a segment's
  last ``sliding_window_size`` rows); the largest of the layers' medians
  is at most ``judge_block_drift_rel_err``. A deeper layer's rows come
  from the engine's own bfloat16 trunk, in which every flipped row and
  every flipped expert of the layers below has moved the hidden state:
  this limit is wide, and is there for a layer that reads another's
  leaf, a ring read out of place, a gate or a rescale left out
  (``block_rel_err_by_layer`` has each layer's number).

``CONTROL`` names one fault of the reference's (``_fault``) or of the
rows (``"fp8_rows"``: the copied pages rounded to fp8 and back), for the
builder's negative controls; the benchmark never sets it.

A traced run sums the device's ops by the family's scopes. The decode
program's selection lies under a ``lax.cond``, whose branch names
(``cond/branch_1_fun``) sit in the middle of an op's ``op_name``: they
are cut out before the join, so ``layer/attn_sparse/index`` reads the
same in both programs.
"""

import os
import re
import types

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
CHUNK = 32          # judged tokens a decode-form call takes
EXPERT_ROWS = 1088  # rows of a judged sequence an expert-layer call takes
CONTROL = None
SCOPES = ("layer/attn_sparse/index", "layer/attn_sparse/select",
          "layer/attn_sparse/attend", "layer/attn_sparse/expand",
          "layer/attn_sparse/absorb", "layer/attn_sparse/kv_write",
          "layer/attn_sparse", "layer/attn_window_latent/attend",
          "layer/attn_window_latent/expand",
          "layer/attn_window_latent/absorb", "layer/attn_window_latent")
_BRANCH = re.compile(r"(?:cond|branch_\d+_fun|jit\([^)]*\))/")


def _base():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "perf_runner_serve_closed_model_base",
        os.path.join(_HERE, "serve_closed_model.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def engine_rows(engine, sequences):
    """``serve_closed_model.engine_rows`` for three kinds of state:
    ``[(tokens replayed, {"latent" | "index": [a full layer's pages [1 +
    pages of a prompt, page size, row]], "ring": [a sliding layer's [1 +
    ring pages, page size, row]]})]``, each behind its null page."""
    import jax.numpy as jnp

    from apex_tpu.serving import kv_cache
    from apex_tpu.serving.scheduler import Request

    ps, most = engine.page_size, -(-engine.prefill_len // engine.page_size)
    ring = kv_cache.ring_pages(engine.cfg.sliding_window_size, ps)
    engine.drain_for_failover(engine.tick)
    out = []
    for k, seq in enumerate(sequences):
        n = min(len(seq), engine.prefill_len)
        req = Request(rid=-10 ** 6 - k, prompt=list(seq[:n]),
                      max_new_tokens=2)
        engine.step(arrivals=[req])
        at, slot = next((i, s) for i, s in enumerate(engine.scheduler.slots)
                        if s is not None and s.request is req)
        pages = list(slot.pages[:-(-n // ps)])
        pool = jnp.asarray([0] + pages + [0] * (most - len(pages)), jnp.int32)
        own = jnp.asarray([0] + [1 + at * ring + r for r in range(ring)],
                          jnp.int32)
        rows = {name: [leaf[index] for leaf in engine.cache[name]]
                for name, index in (("latent", pool), ("index", pool),
                                    ("ring", own))}
        if CONTROL == "fp8_rows":
            rows = {name: [leaf.astype(jnp.float8_e4m3fn).astype(leaf.dtype)
                           for leaf in leaves]
                    for name, leaves in rows.items()}
        out.append((n, rows))
    engine.drain_for_failover(engine.tick)
    engine.cache = None
    return out


def _taps_class(base):
    class Taps(base._Taps):
        """``serve_closed_model._Taps`` (the expert layer, the tie judge's
        witness) with the attention block's tap for the two kinds of
        layer and the indexer's."""

        def __init__(self, engine, rows, routers, top_k):
            import importlib

            import jax
            import jax.numpy as jnp

            from apex_tpu.serving import kv_cache

            super().__init__(engine, None, routers, top_k)
            cfg = self.cfg
            model = importlib.import_module(type(cfg).__module__)
            self._rows = rows
            self._kinds = model.layer_kinds(cfg)
            ps = engine.page_size
            kernels = dict(decode_impl=engine.kernels.decode_impl,
                           interpret=engine.kernels.interpret)
            full, sliding = model.kind(cfg, False), model.kind(cfg, True)

            def lanes(leaf, positions):
                # each judged position its own lane, all on the same
                # pages (the engine's, behind the null page)
                n = leaf.shape[0] - 1
                pages = jnp.broadcast_to(
                    1 + jnp.arange(n, dtype=jnp.int32)[None, :],
                    (positions.shape[0], n))
                return (pages,) + kv_cache.pool_view(pages, positions,
                                                     positions + 1, ps)

            def sparse(lp, leaf, index_leaf, inner, positions):
                pages, table, base_ = lanes(leaf, positions)
                inner = inner.astype(lp["wo"].dtype)

                def attend(q_nope, q_pe, row, c_q):
                    return model.attend_sparse(
                        q_nope, q_pe, c_q, inner, leaf, index_leaf, lp, cfg,
                        positions, positions + 1, pages, table, base_,
                        **kernels)

                return model.latent_attention(
                    inner, lp, cfg, full, positions, attend
                ).astype(jnp.float32)

            def selected(lp, index_leaf, inner, positions):
                pages, table, base_ = lanes(index_leaf, positions)
                inner = inner.astype(lp["wo"].dtype)
                scores = model.index_scores_paged(
                    model.query_latent(inner, lp, cfg, full), inner,
                    index_leaf, lp, cfg, positions, positions + 1, table,
                    base_, **kernels)
                return model.select_rows(scores, pages, cfg.index_topk,
                                         ps)[2]

            def window(lp, leaf, inner, positions):
                ring = leaf.shape[0] - 1
                lengths = positions + 1
                base_, starts = kv_cache.ring_view(
                    lengths, ring, ps, cfg.sliding_window_size)
                inner = inner.astype(lp["wo"].dtype)

                def attend(q_nope, q_pe, row, c_q):
                    return model.attend_ring(
                        q_nope, q_pe, leaf, lp, cfg, lengths,
                        kv_cache.ring_table(1, ring), base_, starts,
                        **kernels)

                return model.latent_attention(
                    inner, lp, cfg, sliding, positions, attend
                ).astype(jnp.float32)

            self._sparse, self._selected, self._window = (
                jax.jit(sparse), jax.jit(selected), jax.jit(window))
            self.window_errors = []
            self.by_layer = {}                 # layer -> the block's errors
            self.hits, self.picked = 0, 0      # the selection check
            self.margins_k, self.spreads = [], []

        def _block_weights(self, i):
            return {k: v for k, v in self._layers[i].items() if k.startswith(
                ("wq_", "q_norm", "wkv_", "kv_norm", "wo", "attn_gate",
                 "idx_"))}

        def _answers(self, n):
            # the answers' positions as far as the replayed part holds
            # them, else its last positions
            return np.arange(max(0, min(self.judged.start, n - CHUNK)),
                             min(self.judged.stop, n))

        def index(self, i, inner, scores, seen):
            """The reference's ``index_tap``: the selection check."""
            import jax
            import jax.numpy as jnp

            lp = self._block_weights(i)
            n, rows = self._rows[self.sequence]
            K = self.cfg.index_topk
            at = np.asarray([p for p in self._answers(n) if p >= K])
            if not len(at):
                return
            leaf = rows["index"][self._kinds[i][1]]
            with jax.default_matmul_precision(self._precision):
                for c0 in range(0, len(at), CHUNK):
                    pos = at[c0:c0 + CHUNK]
                    padded = np.resize(pos, CHUNK)   # repeats, dropped
                    mine = np.asarray(self._selected(
                        lp, leaf, inner[padded],
                        jnp.asarray(padded, jnp.int32)))[:len(pos)]
                    theirs = np.asarray(seen[pos])
                    self.hits += int(np.take_along_axis(theirs, mine,
                                                        axis=1).sum())
                    self.picked += mine.size
            # the witness: the reference's own edge, beside the spread
            live = np.sort(np.asarray(scores[at[-1], :at[-1] + 1]))[::-1]
            self.margins_k.append(float(live[K - 1] - live[K]))
            self.spreads.append(float(np.std(live)))

        def latent(self, i, inner, want):
            """The reference's ``attn_tap``: the block of either kind."""
            import jax
            import jax.numpy as jnp

            lp = self._block_weights(i)
            kd, at_kind = self._kinds[i]
            n, rows = self._rows[self.sequence]
            with jax.default_matmul_precision(self._precision):
                if kd.window is not None:
                    pos = np.asarray([n - 1])
                    got = self._window(lp, rows["ring"][at_kind], inner[pos],
                                       jnp.asarray(pos, jnp.int32))
                    gap, size, _ = base._rel_errors(np.asarray(got),
                                                    np.asarray(want[pos]))
                    self.window_errors += list(gap / size)
                    self.by_layer.setdefault(i, []).extend(gap / size)
                    return
                first = at_kind == 0      # its rows carry no drift
                for at, errors in (
                        (np.arange(min(CHUNK, n)) if first else (),
                         self.latent_early),
                        (self._answers(n), self.latent_answers)):
                    for c0 in range(0, len(at), CHUNK):
                        pos = at[c0:c0 + CHUNK]
                        padded = np.resize(pos, CHUNK)
                        got = self._sparse(
                            lp, rows["latent"][at_kind],
                            rows["index"][at_kind], inner[padded],
                            jnp.asarray(padded, jnp.int32))
                        gap, size, _ = base._rel_errors(
                            np.asarray(got)[:len(pos)], np.asarray(want[pos]))
                        if first:
                            errors += list(gap / size)
                        if errors is self.latent_answers:
                            self.by_layer.setdefault(i, []).extend(gap / size)

    return Taps


class _Reference:
    """The plain reference as ``serve_closed_model.judge`` calls it, with
    the indexer's tap (and a control's fault) passed along; keeps the
    taps for the checks the judge adds."""

    def __init__(self, module):
        self._module, self.taps = module, None
        self.bf16_step = module.bf16_step

    def best_and_chosen(self, config, params, ids, tap, **hooks):
        self.taps = tap.__self__
        fault = CONTROL if CONTROL != "fp8_rows" else None
        return self._module.best_and_chosen(
            config, params, ids, tap=tap, index_tap=self.taps.index,
            _fault=fault, **hooks)


def _judge_of(base):
    inner = base.judge

    def judge(reference, config, mix, params, done, seed, engine):
        wrapped = _Reference(reference)
        ok, note = inner(wrapped, config, mix, params, done, seed, engine)
        taps = wrapped.taps
        share = taps.hits / taps.picked if taps and taps.picked else 0.0

        def median(xs):
            return float(f"{np.median(xs):.4g}") if len(xs) else None

        by_layer = [median(taps.by_layer[i]) for i in sorted(taps.by_layer)] \
            if taps else []
        deepest = max(by_layer) if by_layer else float("inf")
        note.update(
            selection_share=share,
            selection_share_allowed=mix["judge_selection_share"],
            selection_rows_judged=taps.picked if taps else 0,
            selection_edge_margin_median=median(taps.margins_k),
            selection_scores_std_median=median(taps.spreads),
            # each layer's block at the answers' positions (sliding
            # layers: the last replayed one), in layer order
            block_rel_err_by_layer=by_layer,
            block_rel_err_deepest=deepest,
            block_rel_err_deepest_allowed=mix["judge_block_drift_rel_err"],
            window_rel_err_median=median(taps.window_errors),
            window_block_tokens=len(taps.window_errors) if taps else 0)
        if CONTROL:
            note["control"] = CONTROL
        return (ok and share >= mix["judge_selection_share"]
                and deepest <= mix["judge_block_drift_rel_err"]), note

    return judge


def run(ctx):
    base = _base()
    account = base.scope_account

    def by_scope(xplane, tables, scopes):
        # a cond's branch names out of the middle of the op names
        tables = {program: {op: _BRANCH.sub("", name)
                            for op, name in table.items()}
                  for program, table in tables.items()}
        return account.by_scope(xplane, tables, scopes)

    base.scope_account = types.SimpleNamespace(HloNames=account.HloNames,
                                               by_scope=by_scope)
    base.SCOPES = SCOPES + base.SCOPES
    base.EXPERT_ROWS = EXPERT_ROWS
    base.engine_rows = engine_rows
    base._Taps = _taps_class(base)
    base.judge = _judge_of(base)
    record = base.run(ctx)
    if ctx.trace:
        record["traced_s"] = ctx.traffic["trace_seconds"]
    return record

