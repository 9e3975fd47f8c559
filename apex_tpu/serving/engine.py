"""ServingEngine: cache + compiled steps + scheduler in one object.

The host/device shape follows the concurrency-paper discipline
(PAPERS.md arXiv:2011.03641): ALL host work — admission, eviction,
page accounting, array staging — happens between device dispatches,
and the device programs themselves are compiled exactly once each
(prefill at one packed bucket shape, decode at the slot shape), so
the steady-state loop is dispatch → host bookkeeping → dispatch with
no recompiles on the critical path. Scheduler events change array
VALUES only; ``decode_cache_size()`` / ``prefill_cache_size()``
expose the jit cache sizes so tests (and ``dryrun_serving``) can
assert the contract mechanically — ONE prefill + ONE decode program,
with sampling, speculative decode and the prefix cache all enabled.

Generation subsystem (ISSUE 13), three cooperating layers:

* **sampling** (``serving.sampling``) — per-request temperature /
  top-k / top-p with private threefry lanes. Enabled at engine build
  (``sampling=`` > ``set_sampling`` > ``APEX_SERVE_SAMPLING``); the
  per-request params ride the decode program as ``[B]`` ARRAYS
  restaged each round, so admit/evict/re-seed never recompiles.
  Temperature-0 lanes take the exact greedy argmax.
* **speculative decode** (``serving.speculative``) — self-drafting
  n-gram drafts of up to K tokens (``spec_decode=`` >
  ``APEX_SPEC_DECODE``), verified in ONE dispatch of the SAME packed
  varlen prefill program: the slot's full sequence + draft is one
  segment, already-cached context positions route their K/V writes to
  the null spare row (the cache keeps its decode-written values
  bit-exact), and the flat logits-gather (``prefill_requests *
  (K + 1)`` indices — the generalized ``last_idx``) reads the verify
  chain. Acceptance/rollback is pure page/length arithmetic
  (``speculative.accept``); rejected positions' K/V are never read
  (length-masked) and get overwritten as the sequence advances.
* **prefix cache** (``serving.prefix_cache``) — content-hashed
  refcounted page sharing (``prefix_cache=`` >
  ``APEX_SERVE_PREFIX_CACHE``): the scheduler admits cache hits by
  reference + admission-time copy-on-write of the partial tail page
  (:meth:`_copy_page` — a tiny donated jitted page copy, dispatched
  only at admission/registration, never on the per-token path; the
  VERIFY path adds no program — the prefill program serves it); the
  covered suffix replays through the decode program (which attends
  the shared pages — correct by construction), so a shared system
  prompt is PREFILLED ONCE per engine.

All three default OFF per the measured-dispatch rule — the device
A/Bs have not been run;
correctness (greedy parity, per-request determinism, refcount/COW
invariants, two-program stability) is pinned on CPU by
tests/test_serving_generation.py.

Knob resolution at engine build (the CLAUDE.md asymmetry):

* ``weight_quant=`` per-call True RAISES when the params cannot take
  the int8 path; None defers to ``quant.set_weight_quant`` /
  ``APEX_SERVE_WEIGHT_QUANT`` (preferences), default OFF.
* ``sampling=`` / ``prefix_cache=`` per-call non-bools RAISE; a
  stochastic request submitted to a sampling-OFF engine RAISES at
  ``submit`` (explicit request ≠ preference); None defers to
  setter/env.
* ``spec_decode=`` per-call RAISES on an un-honorable draft length
  (< 1, or deeper than the prefill bucket); the env preference falls
  back per shape.
* ``decode_impl=`` rides per-call into the decode-attention family on
  every step (raising semantics live there); None defers to the
  family's rule (the paged Pallas kernel on a TPU where it supports the
  cache geometry, the jnp reference otherwise; ``tp > 1`` passes
  ``"jnp"``). ``engine.decode_attn_impl`` says what the decode program
  was built with.
* ``policy=`` per-call unknown policies RAISE
  (``scheduler.resolve_policy``); None defers to ``APEX_SERVE_SCHED``
  (vocabulary ``fifo`` | ``priority``).

Host/device overlap (ISSUE 14, ``overlap=`` > ``APEX_SERVE_OVERLAP``,
knob home :mod:`apex_tpu.overlap`): the serial round serializes
dispatch → fetch → host bookkeeping → next round's planning, leaving
the device idle for the whole host slice ``profile_serving`` measures
into ``costs.overlap_bound``. The overlapped step DEFERS the decode
fetch one round: round t's decode is dispatched and the engine
returns; round t+1 runs the scheduler's admit/evict/prefix-cache
planning FIRST — while the device executes — and syncs only at the
result fetch, where round t's token values land. The contract making
this exact (token-for-token parity with the serial engine, pinned by
test): scheduler state transitions are COUNT functions — ``done()``
is ``len(out_tokens) >= max_new_tokens``, positions advance by one
per decode lane — so round t+1's planning never needs round t's token
VALUES, only its counts, which are advanced at dispatch time with
placeholder tokens the fetch later fills in. Token values are
consumed only where the serial engine consumes them (the next decode
round's input staging, after the fetch). Speculative decode breaks
the contract (acceptance length is a value function): per-call
``overlap=True`` with ``spec_decode`` RAISES; the env preference
falls back to the serial step. Lifecycle events keep their canonical
per-request order (``validate_order`` stays green): finished events
are recorded at the fetch that produced the token, and evicted events
are recorded after that fetch. ``decode_cache_size()==1`` is
untouched — the overlapped mode dispatches the SAME compiled
programs, only the host schedule moves. ``flush()`` resolves an
in-flight round for callers that stop stepping (``run_trace`` flushes
for you); until then the newest token per live request is a
placeholder.

Serving resilience (ISSUE 15, :mod:`apex_tpu.serving.resilience`) —
four default-OFF layers, disabled mode token-for-token identical:

* **admission control** (``admit=`` > ``APEX_SERVE_ADMIT``): a full
  submit queue returns a structured ``Rejected(reason,
  retry_after_ticks)`` instead of enqueueing — overload is load, not
  an exception, and the queue is bounded.
* **deadline shedding** (``shed=`` > ``APEX_SERVE_SHED``): queued
  requests whose TTFT SLO is already blown (waited past the
  threshold — attainment impossible) are dropped with a ``shed``
  lifecycle event before admission.
* **KV-pressure preemption** (``preempt=`` > ``APEX_SERVE_PREEMPT``):
  admission reserves PROMPT pages only and decode grows the table
  mid-stream; a refused grant preempts the lowest-effective-priority
  running slot (pages freed, prefix refcounts respected, stream
  requeued) and re-admission REPLAYS the preempted stream through
  the same packed prefill program (``_replay_prefill`` — no third
  program, token-for-token parity with the never-preempted stream).
  Per-call True raises when the pool cannot guarantee a lone
  survivor's progress; the env preference falls back.
* **dispatch watchdog + round recovery** (``recover=`` >
  ``APEX_SERVE_RECOVER``): every dispatch runs under the
  ``guarded_dispatch`` timeout (``resilience.
  SERVE_DISPATCH_TIMEOUT_S``); a wedged/crashed round requeues every
  in-flight request with ``degraded_round`` events, rebuilds the
  cache, and continues — bounded by ``SERVE_ROUND_ATTEMPTS``
  consecutive failures with ``RetryPolicy`` pacing.

Preemption/recovery demand the serial round (the deferred-fetch
step's placeholder tokens must never reach a requeued stream): the
pairing with ``overlap=`` follows the spec-decode precedent — two
demands raise, a demand drops the other side's env preference,
env-vs-env falls back to serial. The ``serve_*`` chaos sites
(``apex_tpu.resilience.faults``) fire inside the dispatch closures,
so ``tests/test_serving_chaos.py`` drives every recovery path through
the real engine.

Multi-token decode blocks (ISSUE 17, ``decode_k=`` >
``APEX_SERVE_DECODE_K``, default K=1 per the measured-dispatch rule —
the ``serving_multitok`` A/B is queued in PERF.md §2): ONE dispatch
runs K decode steps in a ``lax.scan`` (:func:`model.decode_block`),
amortizing the per-dispatch host round trip across K tokens. K is
a STATIC program constant — at most a second decode compile-cache
key; the per-lane step budgets, in-block warmup feed and sampling
counters ride as VALUES, so ``decode_cache_size()==1`` holds per
engine whatever the scheduler does. All host-side decisions — admit /
evict / shed / preempt / sampling-lane restage — coarsen to every-K
block boundaries; a lane finishing mid-block rides the rest of the
block as masked ballast (null-page writes, outputs discarded), a
preemption victim requeues with its mid-block partial tokens and
replays through the ordinary ``resume_tokens`` path, and the guarded
dispatch watchdog naturally treats the whole K-block as its unit.
Token-for-token parity with the K=1 engine is pinned by
tests/test_serving_multitok.py under every layer combination.
Speculative decode COMPETES for the same amortization (both batch
multiple tokens per dispatch) and its verify arithmetic assumes one
pending token per round, so the pairing follows the established
asymmetry: two per-call demands raise, a demand drops the other
side's env preference, env-vs-env falls back to K=1.

Observability (ISSUE 11): when ``lifecycle.enabled()`` the engine
keeps a request-lifecycle :class:`~apex_tpu.serving.lifecycle.EventLog`
(``self.events``) — submitted/admitted/prefill_done/first_token/
finished/evicted events plus per-round scheduler gauges (now incl.
cumulative draft/accept/prefix-hit counts) — appended strictly
BETWEEN device dispatches, so the jitted programs (and
``decode_cache_size()==1``) are untouched either way; disabled mode
allocates no log and is behavior-identical. ``device_dispatch_s``
accumulates the wall time spent inside device round trips (prefill +
decode fetch), so a harness can attribute the host slice of the
serving loop (``costs.overlap_bound`` — the ROADMAP 4c gap).
"""

import time

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu import compile_cache
from apex_tpu import resilience as res_mod
from apex_tpu.resilience import faults as faults_mod
from apex_tpu.serving import family as family_mod
from apex_tpu.serving import kv_tier as kv_tier_mod
from apex_tpu.serving import lifecycle
from apex_tpu.serving import model as smodel
from apex_tpu.serving import prefix_cache as prefix_mod
from apex_tpu.serving import quant as quant_mod
from apex_tpu.serving import resilience as serve_res
from apex_tpu.serving import sampling as sampling_mod
from apex_tpu.serving import speculative as spec_mod
from apex_tpu.serving import tp as tp_mod
from apex_tpu.serving.kv_cache import PageAllocator, pages_needed
from apex_tpu.serving.scheduler import ContinuousBatchingScheduler, Request
from apex_tpu.telemetry import spans


def detokenize(tokens):
    """Toy detokenizer for dryruns/smokes: token id -> letter."""
    return "".join(chr(97 + int(t) % 26) for t in tokens)


_DTYPE_TAGS = {"float32": "fp32", "bfloat16": "bf16", "float16": "fp16"}


def _weight_attrs(params, served):
    """``engine.weights``' attributes: the bytes of the tree the programs
    read by dtype (``bytes_bf16``, ``bytes_fp32``, ...) and how many of
    its leaves are copies made from the caller's tree."""
    own = {id(leaf) for leaf in jax.tree_util.tree_leaves(params)}
    attrs = {"leaves_cast": 0}
    for leaf in jax.tree_util.tree_leaves(served):
        key = "bytes_" + _DTYPE_TAGS.get(leaf.dtype.name, leaf.dtype.name)
        attrs[key] = attrs.get(key, 0) + int(leaf.nbytes)
        attrs["leaves_cast"] += id(leaf) not in own
    return attrs


class ServingEngine:
    def __init__(self, cfg, params=None, *, num_slots=4, page_size=16,
                 num_pages=64, max_seq=None, prefill_len=64,
                 prefill_requests=None, weight_quant=None, tp=None,
                 decode_impl=None, interpret=None,
                 policy=None, sampling=None, spec_decode=None,
                 decode_k=None, prefix_cache=None, overlap=None,
                 admit=None,
                 shed=None, preempt=None, recover=None,
                 kv_quant=None, kv_swap=None, kv_restore=None,
                 shed_ttft_ms=None, dispatch_timeout_s=None,
                 round_attempts=None, round_retry_wait_s=None, seed=0):
        # the model family behind the round (serving/family.py), chosen
        # by the config object alone: its check of the config, and the
        # engine options it cannot honour (a demand raises by name, an
        # environment preference is dropped)
        self.family = family_mod.family_of(cfg)
        self.family.check_config(cfg)
        if self.family.refused:
            opts = family_mod.settle_options(self.family, dict(
                tp=tp, weight_quant=weight_quant, kv_quant=kv_quant,
                kv_swap=kv_swap, prefix_cache=prefix_cache,
                spec_decode=spec_decode, decode_k=decode_k,
                overlap=overlap))
            # (spec_decode takes no per-call "off": its resolved K is
            # zeroed below)
            tp, weight_quant, kv_quant, kv_swap, prefix_cache, decode_k, \
                overlap = (opts[name] for name in (
                    "tp", "weight_quant", "kv_quant", "kv_swap",
                    "prefix_cache", "decode_k", "overlap"))
        # the prefill/decode programs are the expensive compiles of a
        # server start: keep them in the persistent cache
        compile_cache.activate()
        self.cfg = cfg
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        self.num_pages = int(num_pages)
        self.max_seq = int(max_seq or cfg.max_position_embeddings)
        if self.max_seq > cfg.max_position_embeddings:
            raise ValueError("max_seq exceeds the position table")
        self.max_pages = -(-self.max_seq // self.page_size)
        self.prefill_len = int(prefill_len)
        self.prefill_requests = int(prefill_requests or num_slots)
        # a family that prefills one dispatch a round: the serial
        # round's admission stops at one dispatch's tokens
        self._admit_tokens = self.prefill_len \
            if self.family.one_prefill_a_round else None
        # the row counts the ONE prefill program can stop its trunk at
        self._trunk_rows = self.family.prefill_rows(self.prefill_len)
        self.params = params if params is not None \
            else self.family.init_params(cfg, seed)

        # weight quant: per-call demand raises on un-honorable;
        # env/setter preferences fall back (quant.resolve)
        if weight_quant is True:
            for name, w in (("word_embeddings",
                             self.params["word_embeddings"]),):
                if not quant_mod.quantizable(w):
                    raise ValueError(
                        f"weight_quant=True cannot be honored: {name} "
                        f"has dtype {w.dtype}")
        self.weight_quant = quant_mod.resolve(weight_quant)
        # tensor-parallel serving (ISSUE 18, `tp=` > APEX_SERVE_TP,
        # default tp=1 — the serving_tp A/B is queued in PERF.md §2;
        # the capability exception for the >HBM config is argued
        # there too). tp x weight_quant COMPOSES (ISSUE 20 satellite,
        # formerly a two-demand raise): the int8 decode records shard
        # along the same Megatron split as their float weights
        # (tp.qparams_shardings — per-out-channel scales ride the
        # column split, replicate across the row split), device_put
        # below with the params.
        self.tp = tp_mod.resolve_serve_tp(
            tp, n_heads=cfg.num_attention_heads)
        self.qparams = self.family.quantize_decode_params(
            self.params, cfg) if self.weight_quant else None
        # a tensor-parallel engine partitions the decode jaxpr by GSPMD
        # from a head-sharded cache (serving/tp.py); a pallas_call is
        # not partitionable that way, so tp > 1 takes the jnp
        # reference unless the caller demanded otherwise
        if decode_impl is None and self.tp > 1:
            decode_impl = "jnp"
        self.decode_impl = decode_impl
        self.interpret = interpret
        # what a family is handed of the two (serving/family.py)
        self._kernels = family_mod.Kernels(decode_impl, interpret)

        # generation knobs (ISSUE 13): sampling / speculative decode /
        # prefix cache, each defaulting OFF (measured-dispatch rule)
        self.sampling = sampling_mod.resolve(sampling)
        k = spec_mod.resolve_k(spec_decode)
        if "spec_decode" in self.family.refused:
            k = 0   # (a demand raised above; this drops APEX_SPEC_DECODE)
        if spec_decode is not None and k and k + 1 > self.prefill_len:
            raise ValueError(
                f"spec_decode={k} cannot be honored: the verify window "
                f"(K+1 = {k + 1} tokens) exceeds "
                f"prefill_len={self.prefill_len}")
        if k and k + 1 > self.prefill_len:
            k = 0  # env preference: falls back per shape
        self.spec_k = k
        self.spec_stats = spec_mod.SpecStats() if self.spec_k else None
        # host/device overlap (ISSUE 14): the deferred-fetch contract
        # cannot run under speculation (value-dependent counts — see
        # the module docstring). Knob asymmetry across the pair: an
        # explicit overlap=True DEMAND against an env-PREFERENCE spec
        # drops the preference (speculation falls back to plain decode
        # — token-identical, so the demand IS honorable); against a
        # per-call spec_decode= DEMAND it raises (two demands, no
        # honorable order); the APEX_SERVE_OVERLAP preference falls
        # back to the serial step either way.
        from apex_tpu import overlap as overlap_mod

        if overlap is True and self.spec_k and spec_decode is None:
            self.spec_k = 0
            self.spec_stats = None
        # multi-token decode blocks (ISSUE 17): K decode steps per
        # device dispatch — ONE lax.scan program, K a static compile
        # key — amortizing the per-dispatch round trip. Default K=1
        # per the measured-dispatch rule (the serving_multitok A/B is
        # queued in PERF.md §2). Speculative decode competes for the
        # same amortization (both batch multiple tokens per dispatch)
        # and its verify/rollback arithmetic assumes ONE pending token
        # per decode round, so the pairing follows the established
        # asymmetry: two per-call demands raise, a demand drops the
        # other side's env preference, env-vs-env falls back to K=1
        # (the committed measurement backs the spec layer; the K-block
        # row is still queued).
        dk = smodel.resolve_decode_k(decode_k)
        if dk > 1 and self.spec_k:
            if decode_k is not None and spec_decode is not None:
                raise ValueError(
                    f"decode_k={dk} cannot be honored with "
                    f"spec_decode={self.spec_k}: the verify rollback "
                    f"assumes one pending token per decode round "
                    f"(two demands, no honorable order)")
            if decode_k is not None:
                # explicit K-block demand drops the env draft pref
                self.spec_k = 0
                self.spec_stats = None
            else:
                dk = 1  # APEX_SERVE_DECODE_K preference falls back
        self.decode_k = dk
        # serving resilience (ISSUE 15): four default-OFF layers.
        # Preemption and round recovery need the serial round (the
        # deferred-fetch step's placeholder tokens must never reach a
        # requeued stream), so the pairing follows the spec-decode
        # precedent: two per-call demands raise, a demand drops the
        # other side's env preference, env-vs-env falls back to the
        # serial step. Admission control and shedding are queue-side
        # and compose with every schedule.
        if overlap is True and (preempt is True or recover is True):
            raise ValueError(
                "overlap=True cannot be honored with preempt=True/"
                "recover=True: the deferred-fetch round holds "
                "placeholder tokens a preempted/requeued stream would "
                "replay as values (two demands, no honorable order)")
        self.preempt = serve_res.resolve_preempt(preempt)
        self.recover = serve_res.resolve_recover(recover)
        if overlap is True:
            # env resilience preferences drop before the explicit
            # overlap demand (preference semantics, never a raise)
            if preempt is None:
                self.preempt = False
            if recover is None:
                self.recover = False
        if self.preempt and self.num_pages - 1 < self.max_pages:
            # the progress guarantee of overcommit admission: with
            # everything else preempted, a lone request must still be
            # able to grow to max_seq pages — otherwise preemption
            # trades a head-of-line block for a genuine livelock
            if preempt is True:
                raise ValueError(
                    f"preempt=True cannot be honored: the page pool "
                    f"({self.num_pages - 1} allocatable) cannot cover "
                    f"one request's max_seq table ({self.max_pages} "
                    f"pages) — a lone preemption survivor could wedge")
            self.preempt = False  # env preference: falls back per shape
        # KV-cache memory hierarchy (ISSUE 20, serving.kv_tier): int8
        # KV quantization + host swap tier, both default OFF per the
        # measured-dispatch rule (the serving_kv_quant/serving_kv_swap
        # device A/Bs are queued in PERF.md §2). The swap tier banks
        # pages AT preemption, so kv_swap pairs with preempt by the
        # established asymmetry: kv_swap=True demanded with preemption
        # resolved off raises (nothing is ever preempted, so nothing
        # is ever banked); the APEX_SERVE_KV_SWAP preference falls
        # back off. Overlap pairing rides preempt's (a swap engine is
        # a preempting engine, which is already serial-only).
        self.kv_quant = kv_tier_mod.resolve_kv_quant(kv_quant)
        self.kv_swap = kv_tier_mod.resolve_kv_swap(kv_swap)
        if self.kv_swap and not self.preempt:
            if kv_swap is True:
                raise ValueError(
                    "kv_swap=True cannot be honored without "
                    "KV-pressure preemption (preempt=True / "
                    "APEX_SERVE_PREEMPT=1): the host tier banks pages "
                    "AT preemption — with it off nothing is ever "
                    "swapped")
            self.kv_swap = False  # env preference falls back
        if kv_restore is not None:
            # validate the per-call demand at BUILD: an unknown
            # vocabulary word or "swap" against a swap-less engine
            # raises here, not at the first preemption mid-serve
            kv_tier_mod.resolve_kv_restore(
                kv_restore, swap_enabled=self.kv_swap, tokens=1,
                dtype="bfloat16")
        self.kv_restore = kv_restore
        self.kv_stats = kv_tier_mod.KVTierStats() if self.kv_swap \
            else None
        # rids whose swap-OUT failed since the last preemption drain —
        # the drain stamps their classified ``swap_failed`` between
        # ``preempted`` and ``resubmitted``
        self._swap_failed_rids = set()
        self.admit_limit = serve_res.resolve_admit(admit)
        self.shed = serve_res.resolve_shed(shed)
        if shed_ttft_ms is not None:
            if not isinstance(shed_ttft_ms, (int, float)) \
                    or isinstance(shed_ttft_ms, bool) or shed_ttft_ms <= 0:
                raise ValueError(
                    f"shed_ttft_ms= wants a positive number, got "
                    f"{shed_ttft_ms!r}")
            self.shed_ttft_ms = float(shed_ttft_ms)
        else:
            self.shed_ttft_ms = lifecycle.env_ms(
                "APEX_SERVE_SLO_TTFT_MS", lifecycle.DEFAULT_SLO_TTFT_MS)
        self.dispatch_timeout_s = float(
            dispatch_timeout_s if dispatch_timeout_s is not None
            else res_mod.SERVE_DISPATCH_TIMEOUT_S)
        self.round_attempts = int(
            round_attempts if round_attempts is not None
            else res_mod.SERVE_ROUND_ATTEMPTS)
        # RetryPolicy pacing between failed rounds (the §6 serving
        # envelope); explicit args so the bench-attempt env knobs
        # never leak into the serving loop
        self._round_retry = res_mod.RetryPolicy(
            attempts=self.round_attempts,
            retry_wait_s=round_retry_wait_s
            if round_retry_wait_s is not None
            else res_mod.SERVE_ROUND_RETRY_WAIT_S)
        self._round_failures = 0   # consecutive; reset on any clean round
        self.resilience = serve_res.ResilienceStats()
        self.rejected = []         # [(request, Rejected)] at submit
        self.overlap = overlap_mod.resolve_serve_overlap(
            overlap, spec_k=self.spec_k)
        if self.overlap and (self.preempt or self.recover):
            # the APEX_SERVE_OVERLAP preference falls back to serial
            # when a resilience layer is engaged (same fall-back the
            # spec-decode pairing takes)
            self.overlap = False
        self._pending = None  # in-flight decode round (overlap mode)
        # the serial round that ran a prefill returned at its first
        # tokens and the next ``step`` call runs its decode half
        self._decode_owed = False
        self.prefix_enabled = prefix_mod.resolve(prefix_cache)
        self.prefix = prefix_mod.PrefixCache(
            PageAllocator(num_pages), self.page_size) \
            if self.prefix_enabled else None
        # width of the flat logits gather per packed request: the
        # verify chain needs K+1 rows; plain prefill reads row r*w
        self._gather_w = self.spec_k + 1

        self._cache_dtype = self.family.cache_dtype(cfg)
        # tp > 1: params + paged KV cache are device_put over the tp
        # mesh; the jitted programs below are UNTOUCHED — GSPMD
        # partitions them from these committed input shardings
        # (qkv/h_to_4h column-split on whole heads, attn-dense/
        # 4h_to_h row-split, cache on its last, heads' axis), so the
        # one-compile contract holds on the mesh and every host-side
        # layer composes unchanged (serving/tp.py docstring).
        self.mesh = tp_mod.mesh_for(self.tp) if self.tp > 1 else None
        if self.mesh is not None:
            self.params = jax.device_put(
                self.params,
                tp_mod.param_shardings(self.params, self.mesh))
            if self.qparams is not None:
                self.qparams = jax.device_put(
                    self.qparams,
                    tp_mod.qparams_shardings(self.qparams, self.mesh))
        # the tree both programs are handed (the family's
        # ``serve_params``, built once where the params lie);
        # ``self.params`` stays the caller's
        with spans.span("engine.weights") as sp:
            self.served_params = self.params \
                if self.family.serve_params is None \
                else self.family.serve_params(self.params, cfg)
            sp.set(**_weight_attrs(self.params, self.served_params))
        self.cache = self._fresh_cache()
        self.allocator = self.prefix.allocator if self.prefix \
            is not None else PageAllocator(num_pages)
        self.scheduler = ContinuousBatchingScheduler(
            num_slots, self.max_pages, page_size, self.allocator,
            policy=policy, prefix=self.prefix, preempt=self.preempt,
            swap_out=self._swap_out_slot if self.kv_swap else None)
        # lifecycle observability (gated, host-side only): None when
        # collection is off — disabled mode appends nothing and reads
        # no extra clocks beyond the per-round stamps below
        self.events = lifecycle.EventLog() if lifecycle.enabled() \
            else None

        # params (and the int8 decode records) are jit ARGUMENTS, not
        # closure captures: a closed-over array lowers to a dense
        # constant, which would embed the whole model in each program's
        # module, cache key and cache entry, and hold a second copy on
        # the device beside the live one.
        #
        # the quantized prefill takes ONE extra operand — the
        # keep_scale row staged per dispatch (_packed_call)
        family = self.family

        def _prefill(params, cache, ids, positions, seg, token_rows,
                     page_table, last_idx, keep_scale=None):
            return family.prefill(params, cache, ids, positions, seg,
                                  token_rows, page_table, last_idx,
                                  keep_scale, cfg=cfg,
                                  kernels=self._kernels)

        # which decode-attention program the decode program is built
        # with ("pallas" | "jnp")
        self.decode_attn_impl = family.decode_attention(
            cfg, self.cache, self._kernels)

        # the decode program: at K=1 the single decode step; at K>1 the
        # ONE lax.scan K-block program replaces it (K is static — at
        # most a second compile-cache key; the per-lane budgets/warmup
        # arrays are VALUES, so the one-compile contract holds)
        if self.decode_k > 1:
            def _decode(params, qparams, cache, tokens, lengths,
                        page_table, steps, warm_tokens, warm_steps,
                        *lanes):
                return family.decode_block(
                    params, cache, tokens, lengths, page_table, steps,
                    warm_tokens, warm_steps, lanes or None,
                    k=self.decode_k, cfg=cfg, qparams=qparams,
                    kernels=self._kernels)
        elif self.sampling:
            def _decode(params, qparams, cache, tokens, lengths,
                        page_table, temps, top_ks, top_ps, keys,
                        counters):
                # (cache, greedy tokens, logits[, extras]): the sampled
                # tokens take the greedy ones' place
                out = family.decode_step(
                    params, cache, tokens, lengths, page_table, cfg=cfg,
                    qparams=qparams, kernels=self._kernels)
                with jax.named_scope("sample"):
                    toks = sampling_mod.sample_tokens(
                        out[2], temps, top_ks, top_ps, keys, counters,
                        lengths > 0)
                return (out[0], toks) + tuple(out[2:])
        else:
            def _decode(params, qparams, cache, tokens, lengths,
                        page_table):
                return family.decode_step(
                    params, cache, tokens, lengths, page_table, cfg=cfg,
                    qparams=qparams, kernels=self._kernels)

        # the page hops below run over every cache leaf alike: each
        # carries its page axis FIRST (serving/kv_cache.py), the int8
        # tier's [pages, h] scales too, so a hop moves a page's codes
        # AND its scales
        def _copy(cache, src, dst):
            # one K/V page src -> dst in every layer; src/dst are
            # traced scalars, so every COW/snapshot hop reuses ONE
            # compiled copy and the donated cache updates in place —
            # an eager .at[].set here would materialize the ENTIRE
            # cache per copied page
            return jax.tree.map(lambda leaf: leaf.at[dst].set(leaf[src]),
                                cache)

        def _swap_gather(cache, page_idx):
            # host swap tier (ISSUE 20), device half of swap-OUT: one
            # victim's pages gathered from every layer's leaf at a
            # [max_pages] index row PADDED with null page 0 (zero
            # codes, zero scale) and stacked [layers, max_pages, ...]
            # a leaf name, so this program compiles exactly once
            # whatever the victim's live page count — the one-compile
            # contract holds; the host device_get of the result is the
            # staging copy, never a third serving program
            return {name: jnp.stack([leaf[page_idx] for leaf in leaves])
                    for name, leaves in cache.items()}

        def _swap_scatter(cache, page_idx, banked):
            # device half of swap-IN: the banked leaves scatter back
            # at the freshly granted pages; the padded tail entries
            # re-write null page 0 with its own zero content — benign,
            # and the program compiles exactly once
            return {name: [leaf.at[page_idx].set(banked[name][i])
                           for i, leaf in enumerate(leaves)]
                    for name, leaves in cache.items()}

        # donate the cache: the scatter-updated pages stay in place
        self._prefill_fn = jax.jit(_prefill, donate_argnums=(1,))
        self._decode_fn = jax.jit(_decode, donate_argnums=(2,))
        # the prefix cache's page-copy hop (admission/registration
        # only — never on the per-token path; the TWO serving
        # programs above stay the jaxpr-stability surfaces)
        self._copy_fn = jax.jit(_copy, donate_argnums=(0,))
        # swap-tier staging hops (preemption/re-admission only — same
        # auxiliary-program precedent as _copy_fn)
        self._swap_gather_fn = jax.jit(_swap_gather)
        self._swap_scatter_fn = jax.jit(_swap_scatter,
                                        donate_argnums=(0,))
        self.tick = 0
        self.decode_steps = 0
        self.verify_calls = 0
        self.prefill_batches = 0
        self.tokens_generated = 0
        # wall seconds spent inside device round trips (prefill +
        # decode fetch): run wall minus this is the HOST slice of the
        # serving loop — the overlap_bound input
        self.device_dispatch_s = 0.0
        # (rid, n_tokens, wall) of every fetch that gave a request
        # tokens since the last round closed: the ``emitted`` attribute
        # of the next ``engine.round`` span
        self._emitted = []
        # what a family's decode program returns beside its tokens
        # (``extras``: small device arrays), fetched with them, and the
        # attributes they and the family's own counts give the round
        self._decode_extras = None
        self._round_attrs = {}
        # the same of the last prefill dispatch, beside the trunk rows
        # it ran on and the tokens and sequences it packed
        # (``prefill.fetch``'s attributes)
        self._prefill_extras = (None, 0, 0, 0)
        # wall seconds inside swap-tier staging copies (device_get at
        # swap-out + scatter at swap-in) — the host-copy clock the
        # kv_restore crossover sweep measures against the replay
        # dispatch it saves
        self.swap_copy_s = 0.0

    # ---------------------------------------------------------- plumbing

    def _place_cache(self, cache):
        """Commit a (re)built KV cache where the params live: the tp
        mesh sharding, or at tp=1 the params' own device. The ONE
        placement home — the programs return a committed cache, so a
        fresh one that arrived uncommitted (or with a drifted sharding
        after a round-recovery rebuild) would re-enter the jit caches
        as a second program and break ``decode_cache_size()==1``."""
        if self.mesh is None:
            leaf = jax.tree_util.tree_leaves(self.params)[0]
            return jax.device_put(cache, leaf.sharding)
        return jax.device_put(
            cache, tp_mod.cache_shardings(cache, self.mesh))

    def _fresh_cache(self):
        """Build + place a zeroed cache — the ONE construction home
        (ctor, round recovery, failover drain), so a rebuild can never
        drop the int8 tier's scale leaves or drift the dtype (either
        would re-enter the jit caches as a second program)."""
        return self._place_cache(self.family.init_cache(
            self.cfg, family_mod.Geometry(
                self.num_slots, self.num_pages, self.page_size,
                self._cache_dtype, self.kv_quant)))

    @property
    def kernels(self):
        """The :class:`family.Kernels` the engine's programs were built
        with (``decode_impl``, ``interpret``): what a caller hands one
        of the family's public functions to run it as the rounds did."""
        return self._kernels

    def decode_cache_size(self):
        """jit-cache entry count of the decode step — the
        jaxpr-stability assertion surface (must stay 1 whatever the
        scheduler admits or evicts)."""
        return self._decode_fn._cache_size()

    def prefill_cache_size(self):
        """jit-cache entry count of the packed prefill program — with
        speculative decode on, admission prefills AND verify batches
        dispatch THIS one program (the no-third-program proof next to
        :meth:`decode_cache_size`)."""
        return self._prefill_fn._cache_size()

    def generation_stats(self):
        """The ledger-facing generation account (None-when-disabled,
        the degradation-not-omission rule): speculative acceptance
        rate + mean draft length, prefix-cache hit rate."""
        st = self.spec_stats
        pf = self.prefix
        return {
            "spec_acceptance_rate":
                st.acceptance_rate() if st is not None else None,
            "draft_len":
                st.mean_draft_len() if st is not None else None,
            "prefix_hit_rate":
                (pf.hit_tokens / pf.lookup_tokens)
                if pf is not None and pf.lookup_tokens else None,
        }

    def resilience_rates(self):
        """The ledger-facing resilience account (ISSUE 15), shaped for
        ``lifecycle.slo_block(resilience=)``: shed / preempt rates and
        the degraded-round count, each None when its layer is off —
        degradation, never omission (check 9 teeth)."""
        return self.resilience.rates(
            shed_on=self.shed, preempt_on=self.preempt,
            recover_on=self.recover)

    def kv_tier_rates(self):
        """The ledger-facing KV-tier account (ISSUE 20): ``kv_quant``
        (True with the int8 tier on, None off), ``swap_rate`` (banked
        swap-outs over preemptions) and ``swapped_pages_high_water``,
        the swap fields None when the host tier is off — degradation,
        never omission (the check 8 teeth)."""
        quant = True if self.kv_quant else None
        st = self.kv_stats
        if st is None:
            return {"kv_quant": quant, "swap_rate": None,
                    "swapped_pages_high_water": None}
        preempted = self.resilience.preempted
        return {
            "kv_quant": quant,
            "swap_rate": (st.swap_outs / preempted) if preempted
            else 0.0,
            "swapped_pages_high_water": st.swapped_pages_high_water,
        }

    def _dispatch(self, phase, fn):
        """One device dispatch (call + fetch, no engine-state writes
        inside) under the resilience layer: the ``serve_*`` chaos
        sites fire inside the dispatched closure (so an injected hang
        blocks exactly where a live dispatch would), and with
        ``recover`` on the whole closure runs under the
        :func:`~apex_tpu.serving.resilience.guarded_dispatch`
        watchdog — a timeout or crash surfaces as a classified
        :class:`~apex_tpu.serving.resilience.DispatchFailure` the
        round-recovery path catches. Without the knob the failure
        propagates (and a watchdog-less engine dies with it — the A/B
        the chaos suite pins). The ``verify`` phase dispatches the
        SAME compiled program as admission prefill, so it shares the
        ``serve_prefill`` chaos site — but keeps its own failure
        label, so a degraded round's verdict names the dispatch that
        actually wedged."""
        program = "prefill" if phase == "verify" else phase
        site = f"serve_{program}"

        def call():
            # the call until it returns its futures (with ``recover``
            # on, the fetch too: it is inside the watchdog)
            with spans.span(f"{program}.dispatch"):
                faults_mod.fire(site, tick=self.tick,
                                step=self.decode_steps,
                                call=self.prefill_batches)
                return fn()

        if not self.recover:
            return call()
        return serve_res.guarded_dispatch(
            call, self.dispatch_timeout_s, phase)

    def validate_request(self, request):
        """The front-door teeth, shared with the fleet router: the
        scheduler validates the page budget (max_seq); the engine
        additionally owns the packed prefill bucket, so the
        prompt-vs-prefill_len bound — which would otherwise crash
        _run_prefill mid-round AFTER admission had already filled a
        slot and allocated pages — is checked at the same front door.
        Sampling demands are validated here too: stochastic params
        against a sampling-OFF engine raise (an explicit request is a
        demand, not a preference); a validated stochastic request also
        gets its per-request sampling key stamped here, so the lane
        key exists from the first admission onward."""
        self.scheduler.validate(request)
        if len(request.prompt) > self.prefill_len:
            raise ValueError(
                f"request {request.rid}: prompt ({len(request.prompt)} "
                f"tokens) exceeds prefill_len={self.prefill_len}")
        sp = getattr(request, "sampling", None)
        if sp is not None:
            sp.validate()
            if not sp.greedy and not self.sampling:
                raise ValueError(
                    f"request {request.rid} demands stochastic "
                    f"sampling (temperature={sp.temperature}) but the "
                    f"engine was built without sampling "
                    f"(sampling=True / APEX_SERVE_SAMPLING=1)")
            if request.rng_key is None:
                request.rng_key = sampling_mod.request_key(sp.seed)

    def submit(self, request, *, quiet=False, replay=False):
        """Enqueue one request; impossible requests raise HERE, before
        anything is enqueued or allocated (``validate_request`` — the
        teeth run FIRST: a full queue rejects load, it must never mask
        a malformed request as a Rejected).

        Under admission control (ISSUE 15, ``admit=`` /
        ``APEX_SERVE_ADMIT``) a FULL queue is load, not a programming
        error: submit returns a structured
        :class:`~apex_tpu.serving.resilience.Rejected` (reason +
        retry-after estimate in ticks) instead of enqueueing — an
        exception never escapes the serving loop for overload, and
        the queue can never grow without bound. Returns None when the
        request was enqueued.

        The fleet router's hooks (ISSUE 19): ``quiet=True`` skips the
        engine's submitted/rejected lifecycle events — the router owns
        the request's front-of-chain events on the ONE fleet log, and
        a failover resubmission must not stamp a second ``submitted``.
        ``replay=True`` (implies the router path) additionally
        bypasses the admission bound and keeps an already-stamped
        ``enqueue_wall``: a failover replay is load the fleet ALREADY
        accepted — dropping it at requeue would break the zero-loss
        invariant, and re-stamping its wall would hide the latency the
        dead replica cost it."""
        self.resilience.submit_attempts += 1
        self.validate_request(request)
        if not replay and self.admit_limit \
                and self.scheduler.queue_depth() >= self.admit_limit:
            # explicit reject at the front door: nothing enqueued,
            # nothing allocated. The retry-after estimate is the
            # queued-ahead count over the slot drain width — a pacing
            # hint, not a promise.
            rej = serve_res.Rejected(
                "queue_full",
                max(1, -(-self.scheduler.queue_depth()
                         // self.num_slots)))
            self.resilience.rejected += 1
            self.rejected.append((request, rej))
            if self.events is not None and not quiet:
                wall = time.perf_counter()
                self.events.record("submitted", request.rid,
                                   tick=self.tick, wall=wall)
                self.events.record("rejected", request.rid,
                                   tick=self.tick, wall=wall)
            return rej
        if not (replay and request.enqueue_wall is not None):
            request.enqueue_wall = time.perf_counter()
        self.scheduler.submit(request, tick=self.tick)
        if self.events is not None and not quiet:
            self.events.record("submitted", request.rid, tick=self.tick,
                               wall=request.enqueue_wall)
        return None

    # -------------------------------------------------- page-level hops

    def _copy_page(self, src, dst):
        """Device copy of one K/V page (the prefix cache's COW hop and
        tail-snapshot registration): one tiny donated jitted helper,
        compiled once for any (src, dst) pair, dispatched BETWEEN the
        serving programs' steps — the prefill/decode jaxpr-stability
        surfaces are untouched and the copy moves one page, not the
        cache."""
        self.cache = self._copy_fn(self.cache, jnp.int32(src),
                                   jnp.int32(dst))

    def _assert_writable(self, slot, first_pos, last_pos):
        """Design guard: after admission-time COW, no write of any
        slot may land on a cache-shared page. Cheap host check; a
        failure here is a prefix-cache invariant bug, not a runtime
        condition."""
        if self.prefix is None:
            return
        ps = self.page_size
        for j in range(first_pos // ps, last_pos // ps + 1):
            if j < len(slot.pages):
                assert not self.prefix.is_shared(slot.pages[j]), (
                    f"rid {slot.request.rid}: write at positions "
                    f"[{first_pos}, {last_pos}] would hit shared page "
                    f"{slot.pages[j]} (COW failed)")

    # ------------------------------------------- host swap tier hops

    def _swap_out_slot(self, slot):
        """Bank a preemption victim's live pages device→host (the
        scheduler's ``swap_out`` callback, fired inside
        ``requeue_slot`` BEFORE the pages are freed). Returns a sealed
        :class:`~apex_tpu.serving.kv_tier.SwappedPages` handle, or
        None when there is nothing worth banking (no generated tokens
        — re-admission is a plain fresh prefill) or the copy failed
        (the ``serve_swap`` chaos site: the stream falls back to
        recompute preemption, classified ``swap_failed`` at the
        drain — tokens preserved either way). The banked extent is
        every page covering positions ``0..pos-1`` — including
        previously shared prefix pages' CONTENT (their refs release
        exactly as before; restore writes private pages, never
        aliases). The copy is host staging between dispatches
        (device_get of the one-compile gather) — never a third
        serving program."""
        req = slot.request
        t = slot.pos
        if not req.out_tokens or t < 1:
            return None
        n = pages_needed(t, self.page_size)
        try:
            faults_mod.fire("serve_swap", phase="swap_out",
                            tick=self.tick, rid=req.rid)
            idx = np.zeros((self.max_pages,), np.int32)
            idx[:n] = slot.pages[:n]
            t0 = time.perf_counter()
            gathered = self._swap_gather_fn(self.cache,
                                            jnp.asarray(idx))
            leaves = {name: np.asarray(jax.device_get(arr))
                      for name, arr in gathered.items()}
            self.swap_copy_s += time.perf_counter() - t0
        except Exception:
            self.kv_stats.swap_out_failures += 1
            self._swap_failed_rids.add(req.rid)
            return None
        handle = kv_tier_mod.SwappedPages(
            leaves=leaves, page_count=n, tokens=t,
            quant=self.kv_quant).seal()
        self.kv_stats.banked(handle)
        return handle

    def _swap_in_slot(self, si, handle):
        """Copy one banked stream's pages back into the slot's freshly
        granted device pages (host→device staging between dispatches —
        every restore reuses the one-compile scatter). True on
        success: the slot resumes decode directly past the banked
        content, skipping the replay dispatch entirely. False when the
        ``serve_swap`` chaos site fired or the handle no longer
        matches its seal (classified ``swap_failed``) — the caller
        replays by recompute; the integrity check runs BEFORE the
        scatter, so corrupt bytes never reach the device."""
        sch = self.scheduler
        slot = sch.slots[si]
        req = slot.request
        try:
            faults_mod.fire("serve_swap", phase="swap_in",
                            tick=self.tick, rid=req.rid)
            if faults_mod.corrupt("serve_swap", phase="swap_in",
                                  tick=self.tick, rid=req.rid):
                # scripted host rot: flip one banked byte in place —
                # the seal below must catch it
                name = sorted(handle.leaves)[0]
                handle.leaves[name].view(np.uint8).ravel()[0] ^= 0xFF
            if not handle.intact():
                raise RuntimeError(
                    f"rid {req.rid}: swapped pages failed their "
                    f"checksum — banked bytes rotted on the host")
            n = handle.page_count
            dst = np.zeros((self.max_pages,), np.int32)
            dst[:n] = slot.pages[:n]
            t0 = time.perf_counter()
            leaves = {name: jnp.asarray(arr)
                      for name, arr in handle.leaves.items()}
            self.cache = self._swap_scatter_fn(
                self.cache, jnp.asarray(dst), leaves)
            self.swap_copy_s += time.perf_counter() - t0
        except Exception:
            self.kv_stats.swap_in_failures += 1
            self.kv_stats.released(handle)
            req.swapped = None
            if self.events is not None:
                self.events.record("swap_failed", req.rid,
                                   tick=self.tick,
                                   wall=time.perf_counter())
            return False
        self.kv_stats.swap_ins += 1
        self.kv_stats.released(handle)
        req.swapped = None
        # resume exactly where the banked content ends: pos positions
        # are valid, the next known token feeds the first decode step
        # (for a stream banked mid-warmup this lands back inside the
        # warmup window — the decode loop's known-token bookkeeping
        # carries it the rest of the way, same as replay overflow)
        slot.pos = handle.tokens
        slot.next_token = int(req.resume_tokens[handle.tokens])
        return True

    def _restore_resumed(self, resumed):
        """Route each re-admitted preempted stream down its resolved
        restore path (ISSUE 20, dispatch op ``kv_restore`` keyed on
        the resumed stream's token length): ``"swap"`` scatters the
        banked pages back and resumes decode directly; ``"recompute"``
        — or any swap failure/corruption — falls back to the
        replay-prefill the preemption layer always had. Returns the
        slots still needing the replay dispatch."""
        sch = self.scheduler
        replay = []
        for si in resumed:
            req = sch.slots[si].request
            handle = getattr(req, "swapped", None)
            if handle is not None:
                choice = kv_tier_mod.resolve_kv_restore(
                    self.kv_restore, swap_enabled=self.kv_swap,
                    tokens=len(req.resume_tokens),
                    dtype=self._cache_dtype)
                if choice == "swap" and self._swap_in_slot(si, handle):
                    self.kv_stats.restores_swap += 1
                    continue
                if req.swapped is not None:
                    # recompute resolved: release the handle — the
                    # replay recomputes these pages (a failed swap-in
                    # already released it)
                    self.kv_stats.released(handle)
                    req.swapped = None
            if self.kv_stats is not None:
                self.kv_stats.restores_recompute += 1
            replay.append(si)
        return replay

    # ----------------------------------------------------------- prefill

    def _sample_first_tokens(self, logits, at, slot_indices):
        """First-token selection off the prefill program's gathered
        logits ``[G, vocab]``, rows ``at`` of which are the admitted
        slots' — the SAME lane semantics as the decode program's
        in-graph sampling (counter 0, the request's own key), run
        eagerly between dispatches. The greedy pick runs over all ``G``
        rows whatever the batch holds (one program, the verify's, not
        one a batch size) and the host keeps rows ``at``."""
        sch = self.scheduler
        if not self.sampling:
            return np.asarray(jnp.argmax(
                logits.astype(jnp.float32), axis=-1))[at]
        logits_rows = logits[at]
        temps, top_ks, top_ps, keys, counters = \
            sampling_mod.batch_lanes(
                [sch.slots[si].request for si in slot_indices])
        toks = sampling_mod.sample_tokens(
            logits_rows, jnp.asarray(temps), jnp.asarray(top_ks),
            jnp.asarray(top_ps), jnp.asarray(keys),
            jnp.asarray(counters),
            jnp.ones((len(slot_indices),), bool))
        return np.asarray(toks)

    def _commit_first_token(self, slot, tok, wall):
        """Per-slot bookkeeping of one prefilled prompt: its first
        token, the walls and lifecycle events of that seam, and the
        prompt's registration with the prefix cache."""
        req = slot.request
        slot.pos = len(req.prompt)
        req.out_tokens.append(tok)
        slot.next_token = tok
        self.tokens_generated += 1
        self._emitted.append((req.rid, 1, wall))
        # prefill always samples the request's FIRST token — this
        # dispatch's fetch wall IS the TTFT stamp
        if req.first_token_wall is None:
            req.first_token_wall = wall
        if self.events is not None:
            self.events.record("prefill_done", req.rid, tick=self.tick,
                               wall=wall)
            self.events.record("first_token", req.rid, tick=self.tick,
                               wall=wall)
        if req.done():
            self._finish(req, wall, self.tick)
        # register the fresh prompt's pages with the prefix cache
        # (between dispatches; tail snapshots copy here)
        if self.prefix is not None:
            adopted, copies = self.prefix.register(
                req.prompt, slot.pages, ("req", req.rid))
            if adopted:
                self.prefix.acquire(adopted)
                slot.shared_pages.extend(adopted)
            for src, dst in copies:
                self._copy_page(src, dst)

    def _finish(self, req, wall, tick):
        """The one seam at which a request's last token has landed:
        its finish wall, its ``finished`` event, and its three spans
        ``request.queue`` / ``request.prefill`` / ``request.decode``,
        stamped after the fact from the four walls it carries (they
        tile ``enqueue_wall .. finish_wall``; a stream that was
        preempted and admitted again after its first token has no
        such tiling and records none)."""
        if req.finish_wall is None:
            req.finish_wall = wall
        if self.events is not None:
            self.events.record("finished", req.rid, tick=tick, wall=wall)
        walls = (req.enqueue_wall, req.admitted_wall,
                 req.first_token_wall, req.finish_wall)
        if None in walls or list(walls) != sorted(walls):
            return
        spans.record("request.queue", walls[0], walls[1], rid=req.rid,
                     prompt=len(req.prompt), rounds=req.queued_rounds,
                     blocked=req.blocked)
        spans.record("request.prefill", walls[1], walls[2], rid=req.rid)
        spans.record("request.decode", walls[2], walls[3], rid=req.rid,
                     tokens=len(req.out_tokens))

    def _pack_greedy(self, items, sizes):
        """Greedy bucket split shared by admission prefill and the
        speculative verify: a batch closes when the next packed
        sequence would overflow the [prefill_len] bucket or the
        per-batch request cap — further items start another dispatch
        of the SAME compiled program."""
        S, R = self.prefill_len, self.prefill_requests
        batches, cur, used = [], [], 0
        for item, n in zip(items, sizes):
            if cur and (used + n > S or len(cur) >= R):
                batches.append(cur)
                cur, used = [], 0
            cur.append(item)
            used += n
        if cur:
            batches.append(cur)
        return batches

    def _packed_call(self, rows, phase="prefill"):
        """ONE dispatch of the packed prefill program for pre-split
        ``rows = [(slot_idx, fed_tokens, write_from, gather_pos)]`` —
        the single assembly both admission prefill and speculative
        verify go through, so the packing contract (segment ids 1..R,
        padding -> the all-null spare row, positions below
        ``write_from`` routing their K/V writes to that spare row,
        within-sequence ``gather_pos`` filling the flat logits gather
        at stride ``_gather_w``) cannot drift between the two callers.
        Returns ``(logits, t0)`` — the caller fetches what it needs
        and closes the ``device_dispatch_s`` timing seam."""
        S, R, W = self.prefill_len, self.prefill_requests, self._gather_w
        with spans.span("prefill.pack", rows=len(rows)) as sp:
            ids = np.zeros((S,), np.int32)
            positions = np.zeros((S,), np.int32)
            seg = np.zeros((S,), np.int32)
            token_rows = np.full((S,), self.num_slots, np.int32)
            gather_idx = np.zeros((R * W,), np.int32)
            pt = np.zeros((self.num_slots + 1, self.max_pages), np.int32)
            pt[:self.num_slots] = self.scheduler.page_table_rows()
            cursor = 0
            for r, (si, fed, write_from, gathers) in enumerate(rows):
                n = len(fed)
                ids[cursor:cursor + n] = fed
                positions[cursor:cursor + n] = np.arange(n)
                seg[cursor:cursor + n] = r + 1
                token_rows[cursor + write_from:cursor + n] = si
                for j, gp in enumerate(gathers):
                    gather_idx[r * W + j] = cursor + gp
                cursor += n
            keep = None
            if self.kv_quant:
                # keep_scale row (kv_tier.prefill_scatter_quant): 1 for
                # pages whose existing int8 content must survive this
                # dispatch's scale growth, 0 for pages this dispatch fully
                # rewrites (fresh pages — stale codes there must NOT pin
                # the scale). A row writing from write_from>0 (verify
                # replay) keeps the partially-valid page holding position
                # write_from-1 and zeroes only the pages past it.
                keep = np.ones((self.num_pages,), np.float32)
                for si, fed, write_from, _ in rows:
                    pages = self.scheduler.slots[si].pages
                    first = (0 if write_from == 0
                             else (write_from - 1) // self.page_size + 1)
                    for j in range(first,
                                   (len(fed) - 1) // self.page_size + 1):
                        if j < len(pages):
                            keep[pages[j]] = 0.0
            trunk_rows = next(R for R in self._trunk_rows if cursor <= R)
            sp.set(tokens=cursor, trunk_rows=trunk_rows)
        t0 = time.perf_counter()
        with spans.span("prefill.stage"):
            args = [self.served_params, self.cache, jnp.asarray(ids),
                    jnp.asarray(positions), jnp.asarray(seg),
                    jnp.asarray(token_rows), jnp.asarray(pt),
                    jnp.asarray(gather_idx)]
            if keep is not None:
                args.append(jnp.asarray(keep))

        def call():
            out = self._prefill_fn(*args)
            cache, logits = out[0], out[1]
            if self.recover:
                # fetch INSIDE the watchdog: the sync on the gathered
                # logits is where a wedged round actually blocks
                logits = np.asarray(logits)
            return cache, logits, (out[2] if len(out) > 2 else None)

        # state adopted only after a clean return: a timed-out round's
        # late result can never overwrite the recovered engine
        self.cache, logits, extras = self._dispatch(phase, call)
        self._prefill_extras = (extras, trunk_rows, cursor, len(rows))
        return logits, t0

    def _replay_prefill(self, resumed):
        """Re-admission replay of preempted/requeued slots (ISSUE 15)
        through the SAME packed prefill program: each slot's known
        stream (minus the still-pending last token) is one segment
        writing its fresh pages — the re-prefilled K/V is the same
        computation the decode path originally wrote, so the resumed
        greedy stream is token-for-token the never-preempted stream.
        No token is sampled and no first-token seam fires (the stream
        is already known; the gathered logits row is fixed-shape
        dispatch ballast). A stream longer than the prefill bucket
        replays its overflow through the decode warmup path (the
        ``slot.known`` bookkeeping), one token per round."""
        sch = self.scheduler
        items = []
        for si in resumed:
            slot = sch.slots[si]
            fed = slot.request.resume_tokens[:-1][:self.prefill_len]
            items.append((si, fed))
        for batch in self._pack_greedy(items,
                                       [len(f) for _, f in items]):
            rows = []
            for si, fed in batch:
                self._assert_writable(sch.slots[si], 0, len(fed) - 1)
                rows.append((si, fed, 0, [len(fed) - 1]))
            logits, t0 = self._packed_call(rows)
            self.prefill_batches += 1
            with spans.span("prefill.fetch"):
                _ = np.asarray(logits[:1, :1])  # close the dispatch seam
                wall = time.perf_counter()
            self.device_dispatch_s += wall - t0
            with spans.span("prefill.commit"):
                for si, fed in batch:
                    slot = sch.slots[si]
                    slot.pos = len(fed)
                    slot.next_token = int(slot.known[len(fed)])

    def _run_prefill(self, slot_indices):
        """Pack the newly admitted slots' prompts into [prefill_len]
        batches and fill the cache (every prompt position writes its
        slot's pages; the one logits gather per request reads the last
        prompt token). Sets each slot's first decode token, and
        registers fresh prompts with the prefix cache. Resumed slots
        (a preempted stream re-admitted, ISSUE 15) replay through
        :meth:`_replay_prefill` first — same compiled program, no
        sampling."""
        sch = self.scheduler
        resumed = [si for si in slot_indices
                   if sch.slots[si].request.resume_tokens]
        slot_indices = [si for si in slot_indices if si not in resumed]
        if resumed:
            # swap tier (ISSUE 20): streams with banked pages restore
            # by host->device copy and skip the replay dispatch; the
            # rest (recompute-resolved, swap-failed, never banked)
            # replay as before
            replay = (self._restore_resumed(resumed) if self.kv_swap
                      else resumed)
            if replay:
                self._replay_prefill(replay)
        if not slot_indices:
            return resumed
        for si in slot_indices:
            n = len(sch.slots[si].request.prompt)
            if n > self.prefill_len:
                raise ValueError(
                    f"prompt of request "
                    f"{sch.slots[si].request.rid} ({n} tokens) exceeds "
                    f"prefill_len={self.prefill_len}")
        batches = self._pack_greedy(
            slot_indices,
            [len(sch.slots[si].request.prompt) for si in slot_indices])
        for batch in batches:
            rows = [(si, sch.slots[si].request.prompt, 0,
                     [len(sch.slots[si].request.prompt) - 1])
                    for si in batch]
            logits, t0 = self._packed_call(rows)
            self.prefill_batches += 1
            with spans.span("prefill.fetch") as sp:
                # rows r*W hold each request's last-prompt-token logits
                next_toks = self._sample_first_tokens(
                    logits, np.arange(len(batch)) * self._gather_w, batch)
                extras, trunk_rows, tokens, seqs = self._prefill_extras
                if extras is not None and spans.enabled():
                    sp.set(**self.family.prefill_attrs(
                        self.cfg, extras, trunk_rows))
                if self.family.pack_attrs is not None and spans.enabled():
                    sp.set(**self.family.pack_attrs(self.cfg, tokens, seqs))
                wall = time.perf_counter()
            self.device_dispatch_s += wall - t0
            with spans.span("prefill.commit"):
                for r, si in enumerate(batch):
                    self._commit_first_token(sch.slots[si],
                                             int(next_toks[r]), wall)
        return resumed + slot_indices

    # ------------------------------------------------------- speculative

    def _propose_drafts(self, active):
        """Draft proposals for this round: ``[(slot_idx, draft)]`` for
        every greedy slot past its prompt whose n-gram draft exists,
        fits the remaining token budget AND the verify window fits
        the prefill bucket. Sampled (stochastic) slots never draft —
        speculation is a greedy-path optimization."""
        sch = self.scheduler
        out = []
        for i in active:
            slot = sch.slots[i]
            req = slot.request
            # known covers the prompt AND a resumed stream's warmup
            # (ISSUE 15): a slot still consuming known tokens never
            # drafts — the verify arithmetic assumes pos is past them
            if req.done() or slot.pos < len(slot.known):
                continue
            sp = getattr(req, "sampling", None)
            if sp is not None and not sp.greedy:
                continue
            remaining = req.max_new_tokens - len(req.out_tokens)
            k = min(self.spec_k, remaining - 1,
                    self.prefill_len - slot.pos - 1,
                    self.max_seq - slot.pos - 1)
            if k < 1:
                continue
            draft = spec_mod.propose(req.prompt + req.out_tokens, k)
            if draft:
                out.append((i, draft))
        return out

    def _run_verify(self, drafts):
        """Verify drafted slots in dispatches of the SAME packed
        prefill program: each slot's full sequence (prompt + generated
        + draft) is one segment — context positions write the null
        spare row (the cache keeps its decode-written K/V bit-exact),
        pending+draft positions write the slot's pages, and the flat
        gather reads the K+1 verify logits per slot. Acceptance and
        rollback are pure length/index arithmetic
        (``speculative.accept``); a slot gains 1..K+1 tokens."""
        sch = self.scheduler
        W = self._gather_w
        batches = self._pack_greedy(
            drafts,
            [sch.slots[i].pos + 1 + len(d) for i, d in drafts])
        verified = []
        for batch in batches:
            rows = []
            for i, draft in batch:
                slot = sch.slots[i]
                req = slot.request
                fed = req.prompt + req.out_tokens + draft
                pos = slot.pos
                assert len(fed) == pos + 1 + len(draft), (
                    len(fed), pos, len(draft))
                # context positions -> the all-null spare row (their
                # decode-written K/V must survive bit-exact); only the
                # pending token + draft positions write real pages
                self._assert_writable(slot, pos, len(fed) - 1)
                rows.append((i, fed, pos,
                             list(range(pos, pos + len(draft) + 1))))
            logits, t0 = self._packed_call(rows, phase="verify")
            self.verify_calls += 1
            with spans.span("prefill.fetch"):
                greedy = np.asarray(jnp.argmax(
                    logits.astype(jnp.float32), axis=-1))
                wall = time.perf_counter()
            self.device_dispatch_s += wall - t0
            for r, (i, draft) in enumerate(batch):
                slot = sch.slots[i]
                req = slot.request
                chain = [int(t) for t in
                         greedy[r * W:r * W + len(draft) + 1]]
                added = spec_mod.accept(draft, chain)
                # _propose_drafts capped k <= remaining - 1, so the
                # round can never overshoot the token budget — named
                # here so the stats line below stays honest by
                # construction (it counts only produced tokens)
                assert len(added) <= req.max_new_tokens \
                    - len(req.out_tokens), (req.rid, added)
                self.spec_stats.record(len(draft), len(added) - 1)
                req.out_tokens.extend(added)
                slot.pos = len(req.prompt) + len(req.out_tokens) - 1
                slot.next_token = req.out_tokens[-1]
                self.tokens_generated += len(added)
                self._emitted.append((req.rid, len(added), wall))
                if req.done():
                    self._finish(req, wall, self.tick)
                verified.append(i)
        return verified

    # ------------------------------------------------------------- steps

    def _lane_budget(self, slot):
        """``(warmup steps remaining, this block's step budget)`` for
        one live lane: warmup steps consume KNOWN tokens (a prefix-hit
        covered suffix or a resumed stream's replay overflow, outputs
        discarded), then emit steps count toward the request's
        remaining new tokens. The budget caps at ``decode_k`` and at
        the lane's own finish — a lane never decodes past its last
        token inside a block, so block writes stay within the
        request's admitted ``prompt + max_new_tokens`` page span."""
        req = slot.request
        warm = max(0, len(slot.known) - 1 - slot.pos)
        rem = req.max_new_tokens - len(req.out_tokens)
        return warm, min(self.decode_k, warm + rem)

    def _block_hi(self, slot):
        """Highest cache position this block writes for a live lane —
        the page-growth span (at K=1 this is exactly ``slot.pos``)."""
        return slot.pos + self._lane_budget(slot)[1] - 1

    def _stage_block(self, decode_lanes):
        """Per-lane staging of one K-block dispatch (ISSUE 17):
        returns ``(steps, steps_dev, warm_tokens, warm_steps)`` where
        ``steps`` maps lane -> host bookkeeping step count,
        ``steps_dev [B]`` is the device step budget (0 for
        done-ballast lanes: the whole block treats them as inactive —
        null-page writes, outputs discarded), ``warm_tokens [K, B]``
        is the in-block warmup feed and ``warm_steps [B]`` how many
        leading steps consume it. All VALUES — the compiled block
        never specializes on them (the one-compile contract)."""
        sch = self.scheduler
        k = self.decode_k
        steps = {}
        steps_dev = np.zeros(self.num_slots, np.int32)
        warm_tokens = np.zeros((k, self.num_slots), np.int32)
        warm_steps = np.zeros(self.num_slots, np.int32)
        for i in decode_lanes:
            slot = sch.slots[i]
            if slot.request.done():
                steps[i] = 1  # ballast: one count step, no device step
                continue
            warm, budget = self._lane_budget(slot)
            steps[i] = budget
            steps_dev[i] = budget
            w = min(warm, budget)
            warm_steps[i] = w
            for j in range(w):
                warm_tokens[j, i] = int(slot.known[slot.pos + j + 1])
        return steps, steps_dev, warm_tokens, warm_steps

    def _dispatch_decode(self, assert_lanes, zero_length_lanes=()):
        """Stage + dispatch ONE decode block for the current slots —
        the SHARED assembly of the serial and overlapped rounds, so
        their token-for-token parity is structural (one staging path)
        rather than maintained across twin code. At K=1 the staged
        program is the single decode step, byte-identical to the
        pre-block engine; at K>1 it is the ``decode_block`` scan with
        the per-lane budget/warmup arrays staged as values.
        ``zero_length_lanes`` are this round's verify-satisfied lanes
        (serial speculative path — K=1 only, the pairing rule).
        Returns ``(next_toks, t0, steps)`` with the fetch left to the
        caller (the serial round fetches immediately; the overlapped
        round defers it); ``steps`` maps lane -> how many of the
        block's scan steps that lane's bookkeeping consumes."""
        sch = self.scheduler
        with spans.span("decode.stage"):
            tokens, lengths = sch.decode_inputs()
            for i in zero_length_lanes:
                lengths[i] = 0  # this round's tokens came via verify
            pt = np.asarray(sch.page_table_rows(), np.int32)
            if self.decode_k > 1:
                steps, steps_dev, warm_tokens, warm_steps = \
                    self._stage_block(assert_lanes)
                for i in assert_lanes:
                    if steps_dev[i]:
                        self._assert_writable(
                            sch.slots[i], sch.slots[i].pos,
                            sch.slots[i].pos + int(steps_dev[i]) - 1)
            else:
                steps = {i: 1 for i in assert_lanes}
                for i in assert_lanes:
                    self._assert_writable(sch.slots[i], sch.slots[i].pos,
                                          sch.slots[i].pos)
            args = [self.served_params, self.qparams, self.cache,
                    jnp.asarray(tokens, dtype=jnp.int32),
                    jnp.asarray(lengths, dtype=jnp.int32),
                    jnp.asarray(pt)]
            if self.decode_k > 1:
                args += [jnp.asarray(steps_dev), jnp.asarray(warm_tokens),
                         jnp.asarray(warm_steps)]
            if self.sampling:
                temps, top_ks, top_ps, keys, counters = \
                    sampling_mod.lane_arrays(sch.slots, self.num_slots)
                args += [jnp.asarray(temps), jnp.asarray(top_ks),
                         jnp.asarray(top_ps), jnp.asarray(keys),
                         jnp.asarray(counters)]
        t0 = time.perf_counter()

        def call():
            out = self._decode_fn(*args)
            cache, toks = out[0], out[1]
            if self.recover:
                # fetch INSIDE the watchdog — the token sync is where
                # a wedged decode round actually blocks
                toks = np.asarray(toks)
            return cache, toks, (out[3] if len(out) > 3 else None)

        # state adopted only after a clean return (a timed-out
        # round's late result never overwrites the recovered engine)
        self.cache, next_toks, self._decode_extras = self._dispatch(
            "decode", call)
        return next_toks, t0, steps

    def _sample_gauges(self, tick):
        """One gauge sample per scheduler round, AFTER the round's
        device work (occupancy as the next round will see it) — shared
        by the serial and overlapped rounds."""
        if self.events is None:
            return
        sch = self.scheduler
        wall = time.perf_counter()
        st, pf = self.spec_stats, self.prefix
        self.events.sample_gauges(
            tick=tick, wall=wall,
            slots_active=len(sch.active_indices()),
            num_slots=self.num_slots,
            queue_depth=sch.queue_depth(),
            kv_pages_live=(self.allocator.num_pages - 1
                           - self.allocator.free_count),
            kv_pages_total=self.allocator.num_pages,
            hol_wait_s=sch.head_of_line_wait(wall, tick=tick),
            spec_drafted=st.drafted if st is not None else 0,
            spec_accepted=st.accepted if st is not None else 0,
            prefix_hit_tokens=pf.hit_tokens if pf is not None else 0,
            rejected=self.resilience.rejected,
            shed=self.resilience.shed,
            preempted=self.resilience.preempted,
            resubmitted=self.resilience.resubmitted,
            degraded_rounds=self.resilience.degraded_rounds)

    def step(self, arrivals=None):
        """One scheduler round: enqueue due arrivals, evict, admit (+
        prefill + prefix-hit COW), speculative verify, decode every
        remaining active slot. Returns a dict of what happened (the
        dryrun/trace-replay surface).

        A serial round that ran a prefill takes TWO calls. The first
        returns as soon as the prefill's first tokens are committed
        (each new request has exactly one token in ``out_tokens``; the
        info's ``decoded_slots`` is 0 and ``verified`` empty), so a
        caller sees a first token without waiting through the round's
        decode step. The next call is the same round's decode half: it
        evicts, sheds, admits and prefills nothing (``evicted``,
        ``admitted``, ``prefilled``, ``shed`` empty), whatever is
        queued, and runs the speculative verify, the page growth and
        the decode step of every active lane, the new ones included.
        Both calls return the round's ``tick``, and ``self.tick``
        advances once, after the decode half (after the prefill half
        when no lane is left active): ``info["tick"] == self.tick``
        says the round is still open. A round that prefilled nothing is
        one call, as is every round of the overlap mode. The rule is
        the same for every family and option. Nothing is in flight
        between the halves (``flush`` stays a no-op), and a round that
        is abandoned (round recovery, ``drain_for_failover``) owes no
        half. A direct ``step()`` driver loops until its requests are
        done and hands each request over once (two calls may see the
        same ``tick``). Requests handed to a decode half wait in the
        queue while it runs; the round that schedules them then opens
        in the same call and returns at ITS first tokens, with the owed
        half's ``decoded_slots`` and ``verified`` counted into its
        info: a caller that hands a call requests finds them scheduled
        when it returns.

        In overlap mode (``overlap=`` / ``APEX_SERVE_OVERLAP``) the
        round is the deferred-fetch pipelined variant — same schedule,
        same tokens (see the module docstring).

        The ``engine.round`` span carries ``cpu_s``: this thread's CPU
        seconds over the round (``time.thread_time``; a wait inside
        ``np.asarray`` does not advance it), so a round that ran tens
        of milliseconds over says whether the thread was running
        meanwhile. With ``recover`` on (default off) each dispatch runs
        on the watchdog's thread and its CPU is not the round's."""
        with spans.span("engine.round", tick=self.tick) as sp:
            recording = spans.enabled()
            cpu0 = recording and time.thread_time()
            info = self._step_overlap(arrivals) if self.overlap \
                else self._step_serial(arrivals)
            # every (rid, n_tokens, wall) whose tokens a fetch of this
            # round handed to a request (the overlapped round: the
            # fetch of the round before)
            emitted, self._emitted = self._emitted, []
            attrs, self._round_attrs = self._round_attrs, {}
            if recording:
                if self.family.round_attrs is not None:
                    attrs.update(self.family.round_attrs(self.cfg,
                                                         self.scheduler))
                attrs["cpu_s"] = time.thread_time() - cpu0
            if self._decode_owed:
                # this call was a round's prefill half
                attrs["returned"] = "prefill"
            sp.set(prefilled=len(info["prefilled"]),
                   decoded=info["decoded_slots"], emitted=emitted, **attrs)
        return info

    def _fire_burst(self, tick):
        """Chaos: the ``serve_burst`` site (ISSUE 15) — fabricate and
        submit a scripted request storm through the REAL submit path,
        so admission control's structured rejections (and the shedder
        behind them) are exercised by an actual overload, not a
        mocked queue."""
        spec = faults_mod.burst("serve_burst", tick=tick)
        if not spec:
            return
        base = int(spec.get("rid_base", 9_000_000))
        plen = int(spec.get("prompt_len", 4))
        for j in range(int(spec.get("count", 8))):
            self.submit(Request(
                rid=base + j, prompt=[1 + (j % 7)] * plen,
                max_new_tokens=int(spec.get("max_new", 4)),
                arrival=float(tick)))

    def _shed_queue(self, tick, wall):
        """The deadline shedder (ISSUE 15): drop queued requests whose
        SLO attainment is already IMPOSSIBLE — one that has waited
        past the TTFT threshold cannot attain whatever happens next
        (its TTFT is at least its wait), so decoding it would burn
        rounds on a lost cause while attainable requests queue behind
        it. Conservative by construction: a request with a first
        token already (a requeued preemption victim mid-stream) has
        its TTFT fixed and is never shed."""
        sch = self.scheduler
        dropped = []
        for req in list(sch.queue):
            if req.first_token_wall is not None \
                    or req.enqueue_wall is None:
                continue
            if (wall - req.enqueue_wall) * 1e3 > self.shed_ttft_ms:
                sch.queue.remove(req)
                req.shed_tick = tick
                sch.shed.append(req)
                self.resilience.shed += 1
                dropped.append(req)
                if self.events is not None:
                    self.events.record("shed", req.rid, tick=tick,
                                       wall=wall)
        return dropped

    def _drain_preempted(self, tick):
        """Record lifecycle events + counters for requests the
        scheduler preempted since the last drain (page-pressure
        growth, :meth:`ContinuousBatchingScheduler.grow`)."""
        preempted = self.scheduler.take_preempted()
        for req in preempted:
            self.resilience.preempted += 1
            self.resilience.resubmitted += 1
            if self.events is not None:
                wall = time.perf_counter()
                self.events.record("preempted", req.rid, tick=tick,
                                   wall=wall)
                if req.rid in self._swap_failed_rids:
                    # swap-out raised/hung at requeue (serve_swap chaos
                    # site): the stream still resubmits — it just
                    # replays by recompute instead of restoring banked
                    # pages. Classified, never silent (ISSUE 20).
                    self.events.record("swap_failed", req.rid,
                                       tick=tick, wall=wall)
                self.events.record("resubmitted", req.rid, tick=tick,
                                   wall=wall)
            self._swap_failed_rids.discard(req.rid)
        return preempted

    def _ensure_pages(self, lanes_pos, tick):
        """Mid-stream page growth (preemption mode): make every
        lane's table cover its highest write position this round,
        preempting the lowest-effective-priority slot when a grant is
        refused. Returns the lanes still alive — a lane preempted to
        make room (possibly by its own growth) drops out of the
        round."""
        sch = self.scheduler
        alive = []
        for i, hi in lanes_pos:
            if sch.slots[i] is None:
                continue  # preempted by an earlier lane's growth
            if sch.grow(i, hi // self.page_size + 1, tick):
                alive.append(i)
        self._drain_preempted(tick)
        return [i for i in alive if sch.slots[i] is not None]

    def _cow_prefix_hits(self, admitted):
        """Prefix-cache hits skip the packed prefill: their COW copies
        run here (between dispatches) and their covered suffix replays
        through the decode program. Returns the slots still to
        prefill."""
        to_prefill = []
        for i in admitted:
            slot = self.scheduler.slots[i]
            if slot.prefix_hit:
                for src, dst in slot.cow_copies:
                    self._copy_page(src, dst)
                slot.cow_copies = []
            else:
                to_prefill.append(i)
        return to_prefill

    def _step_serial(self, arrivals=None):
        owed, self._decode_owed = self._decode_owed, False
        try:
            result = self._owed_half(arrivals) if owed \
                else self._open_round(arrivals)
        except serve_res.DispatchFailure as failure:
            # only the guarded (recover=on) dispatch raises this —
            # without the watchdog the raw failure propagates and the
            # engine dies with it (the A/B the chaos suite pins).
            # ``self.tick`` is still the failed round's: it advances
            # after a round's last dispatch. Whichever half failed, no
            # half is owed: the round is abandoned whole
            return self._recover_round(self.tick, failure)
        if not self._decode_owed:
            self._round_failures = 0   # the whole round ran clean
        return result

    def _open_round(self, arrivals):
        now = self.tick
        self._fire_burst(now)
        for req in arrivals or ():
            self.submit(req)
        return self._round_serial(now)

    def _owed_half(self, arrivals):
        """The call after a prefill half: the same round's decode half.
        Requests handed to it wait in the queue while it runs; a caller
        that hands a call requests reads their slots when it returns
        (the benchmark's judge does), so the round that schedules them
        opens in this call too, which then reports both: the new
        round's info, the owed half's lanes and verifies counted in."""
        now = self.tick
        for req in arrivals or ():
            self.submit(req)
        half = self._decode_half(now)
        if not arrivals:
            return {"tick": now, "evicted": [], "admitted": [],
                    "prefilled": [], "shed": [], **half}
        result = self._open_round(None)
        result["verified"] = half["verified"] + result["verified"]
        result["decoded_slots"] += half["decoded_slots"]
        return result

    def _round_serial(self, now):
        sch = self.scheduler
        with spans.span("engine.schedule") as sp:
            wall = time.perf_counter()
            evicted = sch.evict_done(now, wall)
            shed = self._shed_queue(now, wall) if self.shed else []
            admitted = sch.admit(now, wall, self._admit_tokens)
            if self.events is not None:
                for r in evicted:
                    self.events.record("evicted", r.rid, tick=now,
                                       wall=wall)
                for i in admitted:
                    self.events.record("admitted",
                                       sch.slots[i].request.rid,
                                       tick=now, wall=wall)
            to_prefill = self._cow_prefix_hits(admitted)
            sp.set(admitted=len(admitted), queue_depth=sch.queue_depth(),
                   stopped=sch.stopped)
        self.resilience.admissions += len(admitted)
        prefilled = self._run_prefill(to_prefill) if to_prefill else []
        opened = {"tick": now, "evicted": [r.rid for r in evicted],
                  "admitted": admitted, "prefilled": prefilled,
                  "shed": [r.rid for r in shed]}
        if prefilled and sch.active_indices():
            # the first tokens are committed: return with them, and
            # leave the round's decode half to the next call
            self._decode_owed = True
            return dict(opened, verified=[], decoded_slots=0)
        return dict(opened, **self._decode_half(now))

    def _decode_half(self, now):
        """What a serial round does once its admissions are prefilled:
        speculative verify, page growth under ``preempt``, the decode
        step of every active lane, the gauges; ``self.tick`` advances
        here. The same call's for a round that prefilled nothing, the
        next call's for one that did (``step``)."""
        sch = self.scheduler
        active = sch.active_indices()
        verified = []
        if self.spec_k and active:
            drafts = self._propose_drafts(active)
            if self.preempt and drafts:
                # the verify window writes pos..pos+|draft| — grow the
                # tables first (a grown-out lane drops its draft)
                alive = set(self._ensure_pages(
                    [(i, sch.slots[i].pos + len(d)) for i, d in drafts],
                    now))
                drafts = [(i, d) for i, d in drafts if i in alive]
            if drafts:
                verified = self._run_verify(drafts)
            active = sch.active_indices()  # growth may have preempted
        decode_lanes = [i for i in active if i not in verified]
        if self.preempt and decode_lanes:
            # the decode step writes each lane's pending position —
            # grow under pressure, preempting the lowest-priority slot
            # on a refused grant instead of crashing the round. DONE
            # lanes (finished at this round's prefill, riding the
            # dispatch as ballast) are skipped: their write lands on
            # the absorbing null page and their output is discarded —
            # growing (let alone preempting a live stream) for them
            # would spend pages on a dead write
            grown = set(self._ensure_pages(
                [(i, self._block_hi(sch.slots[i])) for i in decode_lanes
                 if not sch.slots[i].request.done()], now))
            decode_lanes = [i for i in decode_lanes
                            if sch.slots[i] is not None
                            and (sch.slots[i].request.done()
                                 or i in grown)]
        decoded = 0
        if decode_lanes:
            next_toks, t0, steps = self._dispatch_decode(
                decode_lanes, zero_length_lanes=verified)
            with spans.span("decode.fetch") as sp:
                # the count bookkeeping runs while the device works:
                # it is part of the wait, not of the round's host time
                plan, decoded = self._advance_counts(decode_lanes, steps)
                next_toks = np.asarray(next_toks)
                if self._decode_extras is not None and spans.enabled():
                    self._round_attrs = self.family.fetch_attrs(
                        self._decode_extras)
                    sp.set(**self._round_attrs)
                wall2 = time.perf_counter()
            self.device_dispatch_s += wall2 - t0
            with spans.span("decode.commit"):
                self._fill_plan(plan, next_toks, wall2, now)
                self._sample_gauges(now)
        else:
            self._sample_gauges(now)
        # a slot whose LAST token was just produced frees at the next
        # round's evict — one round of slack, never a starved queue
        self.tick += 1
        return {"verified": verified, "decoded_slots": decoded}

    def _recover_round(self, now, failure):
        """Round recovery (ISSUE 15): a dispatch the watchdog timed
        out or caught crashing does NOT kill the engine — every
        in-flight request is requeued (pages freed, known stream
        stashed for the prefill replay), a ``degraded_round``
        lifecycle event is stamped per request with the classifier's
        verdict on the engine, the device cache is rebuilt (the
        wedged dispatch may have consumed the donated buffer — and a
        timed-out round's LATE result is never adopted, so a zeroed
        cache is the only sound state) and the prefix cache is
        flushed (its chains pointed into the abandoned buffer). The
        next rounds re-admit and replay; ``SERVE_ROUND_ATTEMPTS``
        consecutive failures exhaust the budget and raise — bounded
        recovery, a dead device still fails loudly."""
        sch = self.scheduler
        self._round_failures += 1
        self.resilience.degraded_rounds += 1
        self.resilience.last_verdict = failure.verdict
        # requeue every UNFINISHED active slot: whatever the failed
        # program was, the cache buffer's contents are no longer
        # trustworthy. A request that already finished this round
        # needs no further compute — it stays seated for the next
        # round's evict (requeuing it would stamp degraded_round
        # after finished, which the lifecycle machine forbids, and
        # replay a completed stream for nothing).
        requeued = []
        for i in sch.active_indices():
            if not sch.slots[i].request.done():
                # swap=False: the failed round's cache contents are
                # exactly what we no longer trust — banking them would
                # restore poison. (Handles banked BEFORE the failure
                # survive: host bytes are independent of the rebuilt
                # device buffer, so those streams still swap in.)
                requeued.append(sch.requeue_slot(i, now, swap=False))
        if self.prefix is not None:
            # finished slots keep their seats (evicted next round),
            # but the cache flush below refuses live references —
            # release theirs now and clear the list so the later
            # evict cannot double-release. Their page-table entries
            # still name the freed indices, but a done slot only
            # READS them as discarded ballast — never writes.
            for i in sch.active_indices():
                slot = sch.slots[i]
                if slot.shared_pages:
                    self.prefix.release(slot.shared_pages)
                    slot.shared_pages = []
            self.prefix.flush()
        self.cache = self._fresh_cache()
        if self.events is not None:
            wall = time.perf_counter()
            for req in requeued:
                self.events.record("degraded_round", req.rid, tick=now,
                                   wall=wall)
                self.events.record("resubmitted", req.rid, tick=now,
                                   wall=wall)
        self.resilience.resubmitted += len(requeued)
        if self._round_failures >= self.round_attempts:
            raise RuntimeError(
                f"serving round failed {self._round_failures} "
                f"consecutive times (last: {failure}) — the "
                f"SERVE_ROUND_ATTEMPTS budget is exhausted; the "
                f"device is {failure.verdict}") from failure
        # RetryPolicy pacing before re-driving the round (chaos tests
        # pin the wait to 0)
        wait = self._round_retry.pop_wait()
        if wait:
            time.sleep(wait)
        self._sample_gauges(now)
        self.tick += 1
        return {"tick": now, "evicted": [], "admitted": [],
                "prefilled": [], "verified": [], "decoded_slots": 0,
                "shed": [],
                "degraded": {"phase": failure.phase,
                             "verdict": failure.verdict,
                             "detail": failure.detail,
                             "requeued": [r.rid for r in requeued]}}

    def drain_for_failover(self, tick):
        """Evacuate this replica for the fleet router's failover
        (ISSUE 19): every unsettled request — queued AND in-flight —
        leaves the engine in replayable form, and the engine is left
        in the same clean state ``_recover_round`` rebuilds, so a
        later re-admission probe starts from a sound cache. In-flight
        slots requeue exactly like KV-pressure preemption (pages
        freed, prefix refcounts respected, the known stream stashed in
        ``resume_tokens`` for the prefill replay); finished-but-not-
        evicted slots settle here (their streams are complete — only
        their pages are reclaimed); the prefix cache is flushed (its
        chains point into the abandoned buffer) and the device cache
        rebuilt. Returns the drained requests in replay order
        (in-flight first — they hold the oldest streams), each ready
        for ``submit(..., replay=True)`` on a survivor. The router
        owns the ``failover``/``replayed`` lifecycle events; nothing
        is stamped here."""
        sch = self.scheduler
        wall = time.perf_counter()
        # finished streams settle (complete output, nothing to replay);
        # pages + prefix refs reclaim through the normal evict path
        for r in sch.evict_done(tick, wall):
            if self.events is not None:
                self.events.record("evicted", r.rid, tick=tick,
                                   wall=wall)
        queued = list(sch.queue)
        sch.queue.clear()
        # swap=False: the drained requests replay on a DIFFERENT
        # replica — a host-banked handle from this process cannot
        # restore into the survivor's cache, so bank nothing and
        # release any handle still riding a drained request below
        inflight = [sch.requeue_slot(i, tick, swap=False)
                    for i in sch.active_indices()]
        sch.queue.clear()  # requeue_slot re-appended them — the router
        #                    owns where these requests go next
        if self.prefix is not None:
            self.prefix.flush()
        self.cache = self._fresh_cache()
        self._round_failures = 0
        self._decode_owed = False  # its lanes left with the drain
        drained = inflight + queued
        for req in drained:
            handle = getattr(req, "swapped", None)
            if handle is not None:
                self.kv_stats.released(handle)
                req.swapped = None
        return drained

    # ------------- shared round bookkeeping (ISSUEs 14/17 one seam)

    def _advance_counts(self, decode_lanes, steps):
        """Post-dispatch COUNT bookkeeping of one decode block — the
        ONE round-bookkeeping seam shared by the serial and overlapped
        rounds (ISSUE 17 satellite: formerly twin code), walking the
        block's (step, lane) grid with a placeholder where each token
        VALUE lands (``_fill_plan`` fills it — immediately after the
        fetch on the serial round, at the deferred fetch on the
        overlapped one). ``steps`` maps lane -> how many of the
        block's K scan steps that lane's bookkeeping consumes (1
        everywhere at K=1). Every transition here is a count function
        — the overlapped round-t+1 planner never observes round-t
        token values early. Plan entries hold the slot/request REFS
        (eviction between dispatch and fetch detaches the slot, the
        refs stay valid). Returns ``(plan, decoded)``."""
        sch = self.scheduler
        plan = []
        decoded = 0
        for j in range(self.decode_k):
            for i in decode_lanes:
                if j >= steps.get(i, 0):
                    continue
                slot = sch.slots[i]
                req = slot.request
                k_len = len(slot.known)
                consumed_pos = slot.pos
                slot.pos += 1
                if consumed_pos < k_len - 1:
                    # warmup: the consumed token was a KNOWN token
                    # (prefix-hit covered suffix or a resumed stream's
                    # replay overflow) with more to come — the next one
                    # is fed (host-side here at K=1; the staged
                    # ``warm_tokens`` row inside the block at K>1) and
                    # the lane's output is discarded
                    slot.next_token = int(slot.known[consumed_pos + 1])
                    decoded += 1
                    continue
                if not req.done():
                    req.out_tokens.append(None)  # value lands at fill
                    self.tokens_generated += 1
                    plan.append({
                        "lane": i, "step": j, "slot": slot, "req": req,
                        "out_idx": len(req.out_tokens) - 1,
                        # the slot's FIRST output token: its warmup
                        # ended this step — the prefill-done /
                        # first-token seam of the cached path. A
                        # resumed stream's warmup end is NOT a first
                        # token (its seam fired in an earlier cycle —
                        # the wall guard keeps the chain single-shot)
                        "first": (consumed_pos == k_len - 1
                                  and req.first_token_wall is None),
                        "done": req.done(),
                    })
                decoded += 1
        # decode_steps counts DISPATCHES — the unit the K-block
        # amortizes; tokens-per-dispatch is the economics ratio
        self.decode_steps += 1
        return plan, decoded

    def _fill_plan(self, plan, next_toks, wall, tick):
        """The VALUE half of the round-bookkeeping seam: fill the
        block's placeholder tokens and stamp the walls / lifecycle
        events the counts deferred. ``next_toks`` is ``[B]`` from the
        single-step program or ``[K, B]`` from the K-block; entries
        index it by (step, lane). A lane with several emits in one
        block fills in step order, so its ``next_token`` (the NEXT
        block's feed) is the last step's token."""
        toks = np.asarray(next_toks)
        if toks.ndim == 1:
            toks = toks[None]
        for e in plan:
            tok = int(toks[e["step"], e["lane"]])
            e["req"].out_tokens[e["out_idx"]] = tok
            e["slot"].next_token = tok
            rid = e["req"].rid
            self._emitted.append((rid, 1, wall))
            if e["first"]:
                if e["req"].first_token_wall is None:
                    e["req"].first_token_wall = wall
                if self.events is not None:
                    self.events.record("prefill_done", rid,
                                       tick=tick, wall=wall)
                    self.events.record("first_token", rid,
                                       tick=tick, wall=wall)
            if e["done"]:
                self._finish(e["req"], wall, tick)

    # ----------------------------------- overlapped round (ISSUE 14)

    def _resolve_pending(self):
        """The sync point of the overlapped round: fetch the in-flight
        decode block's tokens and hand them to ``_fill_plan`` (stamped
        with the dispatching round's tick — the round the serial
        engine would have recorded them at)."""
        p = self._pending
        if p is None:
            return
        self._pending = None
        next_toks = np.asarray(p["next_toks"])   # blocks until ready
        wall = time.perf_counter()
        # planning time between dispatch and this fetch ran INSIDE the
        # device window — counting it as dispatch wall is the measured
        # claim (run wall minus this = the host slice overlap removed)
        self.device_dispatch_s += wall - p["t0"]
        self._fill_plan(p["plan"], next_toks, wall, p["tick"])

    def flush(self):
        """Resolve the in-flight decode round (overlap mode): fill the
        placeholder tokens and land their lifecycle events. A no-op on
        the serial engine or with nothing in flight; ``run_trace``
        calls it for you — direct ``step()`` drivers call it before
        reading ``out_tokens``."""
        self._resolve_pending()

    def _step_overlap(self, arrivals=None):
        """The deferred-fetch pipelined round: PLAN round t+1 (evict/
        admit/prefix-COW — count state only) while the device executes
        round t, sync at the fetch, then prefill + dispatch round
        t+1's decode and return with IT in flight. Same admissions,
        evictions and tokens per round as the serial engine (pinned by
        test); only the host schedule moves."""
        sch = self.scheduler
        now = self.tick
        if arrivals:
            for req in arrivals:
                self.submit(req)
        wall = time.perf_counter()
        # ---- the overlap window: host planning under the in-flight
        # decode. wall_time=None on evict: finish_wall belongs to the
        # fetch that produced the finishing token (_resolve_pending).
        evicted = sch.evict_done(now, None)
        # the deadline shedder composes with the overlapped schedule:
        # it touches QUEUED requests only (no placeholder tokens exist
        # before admission), so the count-function contract holds
        shed = self._shed_queue(now, wall) if self.shed else []
        admitted = sch.admit(now, wall)
        if self.events is not None:
            for i in admitted:
                self.events.record("admitted", sch.slots[i].request.rid,
                                   tick=now, wall=wall)
        # COW copies are device work: they queue behind the in-flight
        # decode and run before any dependent read
        to_prefill = self._cow_prefix_hits(admitted)
        # ---- sync point: round t's values land (finished /
        # first-token events), then the evictions planned above are
        # RECORDED — after the finished events they must follow
        self._resolve_pending()
        for r in evicted:
            if r.finish_wall is None:
                r.finish_wall = wall  # the evict_done backstop seam
        if self.events is not None and evicted:
            wall_e = time.perf_counter()
            for r in evicted:
                self.events.record("evicted", r.rid, tick=now,
                                   wall=wall_e)
        prefilled = self._run_prefill(to_prefill) if to_prefill else []
        decode_lanes = sch.active_indices()
        decoded = 0
        if decode_lanes:
            next_toks, t0, steps = self._dispatch_decode(decode_lanes)
            # NO fetch: the round returns with the decode in flight;
            # counts advance now so the next round can plan
            plan, decoded = self._advance_counts(decode_lanes, steps)
            self._pending = {"next_toks": next_toks, "plan": plan,
                             "t0": t0, "tick": now}
        self._sample_gauges(now)
        self.tick += 1
        return {"tick": now, "evicted": [r.rid for r in evicted],
                "admitted": admitted, "prefilled": prefilled,
                "verified": [], "decoded_slots": decoded,
                "shed": [r.rid for r in shed]}

    def run_trace(self, requests, max_ticks=10000):
        """Replay a synthetic trace to completion: requests are
        submitted when their arrival tick is due; returns the
        completed Request list (latency fields filled). A tick is a
        round, not a call: a round that prefills takes two ``step``
        calls at one tick (the second is handed nothing: what was due
        went to the first), so arrival ticks, the latency fields in
        ticks and ``max_ticks`` mean what they meant. Flushes the
        overlapped engine's in-flight round before returning, so the
        completed list never holds a placeholder token. A trace
        request SETTLES by completing, being shed (deadline shedder)
        or being rejected at submit (admission control) — the
        resilience layers drop load, they never hang the drain
        (rejected/shed requests are in ``self.rejected`` /
        ``scheduler.shed``, not the completed list)."""
        pending = sorted(requests, key=lambda r: (r.arrival, r.rid))
        n_total = len(pending)
        trace_set = {id(r) for r in requests}
        # incremental settle counter: the three lists only ever grow,
        # so each tick scans NEW entries only (the replay loop is the
        # measured host slice — a full rescan per tick would inflate
        # every serving row's host_ms). Counting trace requests only:
        # chaos bursts (serve_burst) complete/reject through the same
        # lists but must not inflate the trace's account.
        settled = 0
        cursors = [0, 0, 0]

        def _drain_settled():
            nonlocal settled
            lists = (self.scheduler.completed, self.rejected,
                     self.scheduler.shed)
            for k, lst in enumerate(lists):
                for idx in range(cursors[k], len(lst)):
                    item = lst[idx]
                    r = item[0] if k == 1 else item
                    if id(r) in trace_set:
                        settled += 1
                cursors[k] = len(lst)
            return settled

        while _drain_settled() < n_total:
            if self.tick >= max_ticks:
                raise RuntimeError(
                    f"trace did not drain in {max_ticks} ticks "
                    f"({settled}/{n_total} settled)")
            due = [r for r in pending if r.arrival <= self.tick]
            pending = [r for r in pending if r.arrival > self.tick]
            self.step(arrivals=due)
        self.flush()
        return list(self.scheduler.completed)
