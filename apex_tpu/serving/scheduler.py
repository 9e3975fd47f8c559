"""Continuous-batching scheduler (host-side, stdlib-only).

Runs BETWEEN decode steps: admit queued requests into free decode
slots (allocating their cache pages up front — all-or-nothing, so a
mid-stream request can never run out of pages), evict completed ones
(freeing pages), and materialize the static-shape arrays the jitted
decode step consumes. Only array VALUES change across admit/evict
events — shapes are fixed at construction, so the decode program
compiles exactly once (the ISSUE 10 jaxpr-stability contract).

Admission is strict FIFO with head-of-line blocking: if the oldest
queued request does not fit (no free slot, or the free list cannot
cover its ``prompt + max_new_tokens`` pages), nothing younger is
admitted over it — the no-starvation property
(tests/test_serving.py asserts completion order ⊇ arrival order under
the synthetic trace).

The synthetic traffic trace (:func:`synthetic_trace`) is the
deterministic workload every serving measurement pins: request
arrival ticks, prompt lengths and output lengths from one seeded
stdlib RNG, identified by a content hash (``trace_id``) that rides in
the ledger's serving block. Two ARRIVAL PROCESSES (the ISSUE 11
open-loop load harness, ROADMAP 2e): ``"poisson"`` — exponential
inter-arrivals at the constant offered rate (what the original trace
already drew, now named) — and ``"diurnal"`` — a non-homogeneous
Poisson process whose instantaneous rate swings sinusoidally around
the base rate (the day/night traffic shape heavy-traffic serving is
actually sized against). The process is a per-call argument of the
trace (unknown values raise) and a pinned knob of the measuring
harness (``APEX_SERVE_ARRIVALS``, check 9).

Scheduler POLICY is a dispatch choice, not an architecture constant
(ROADMAP 2e: FIFO vs priority vs chunked prefill as measured
dispatch): :func:`resolve_policy` keeps the CLAUDE.md asymmetry —
per-call unknown policies raise, the ``APEX_SERVE_SCHED`` env
preference warns once and falls back. The vocabulary is ``("fifo",
"priority")`` (ISSUE 13 — the PR 10 remainder): ``priority`` admits
the queued request with the highest EFFECTIVE priority
``request.priority + waiting_ticks / AGING_TICKS`` — the aging term
is the no-starvation rule (any waiter eventually outranks every fixed
priority; completion-of-everything is pinned by test) — with
head-of-line blocking ON THE SELECTED request, so an urgent large
request is never starved by smaller queue-jumpers either. The
priority-vs-fifo tail-latency A/B under the diurnal trace is queued
in PERF.md §2 (defaults stay ``fifo`` per the measured-dispatch
rule).

Prefix-cache hop (ISSUE 13): when the engine passes a
:class:`~apex_tpu.serving.prefix_cache.PrefixCache`, admission looks
the prompt up first — shared full pages enter the slot's table by
REFERENCE (refcounted; only the uncovered remainder allocates), a
matched partial tail page schedules a copy-on-write into the slot's
first private page (``Slot.cow_copies`` — the ENGINE performs device
copies), and a short free list asks the cache to ``reclaim``
unreferenced pages before blocking.
"""

import dataclasses
import hashlib
import math
import random
from collections import deque
from typing import Any, List, Optional, Tuple

from apex_tpu.dispatch import tiles as _tiles
from apex_tpu.resilience import faults as _faults

ARRIVALS = ("poisson", "diurnal")
POLICIES = ("fifo", "priority")
# priority aging: one effective-priority level per this many waiting
# ticks — the no-starvation clock of the priority policy
AGING_TICKS = 8.0


def resolve_policy(per_call=None):
    """The effective scheduler policy: per-call (raises on unknown —
    an explicit request is a demand) > ``APEX_SERVE_SCHED`` env
    preference (warn-once-and-ignore on unknown) > built-in FIFO."""
    if per_call is not None:
        if per_call not in POLICIES:
            raise ValueError(
                f"unknown scheduler policy {per_call!r} "
                f"(vocabulary: {POLICIES})")
        return per_call
    return _tiles.env_choice("APEX_SERVE_SCHED", POLICIES) or "fifo"


@dataclasses.dataclass
class Request:
    rid: int
    prompt: List[int]
    max_new_tokens: int
    arrival: float = 0.0          # logical tick the request appears at
    # scheduling priority (ISSUE 13, policy "priority": higher admits
    # first, aged by waiting time; ignored under "fifo")
    priority: int = 0
    # per-request sampling controls (apex_tpu.serving.sampling
    # .SamplingParams; None = greedy). Typed loosely: this module is
    # stdlib-only and never imports the jax-backed sampling module —
    # the ENGINE validates the params at submit.
    sampling: Optional[Any] = None
    # the request's private threefry key lane (uint32[2] host bytes,
    # stamped by engine.submit so per-round lane staging is numpy-only)
    rng_key: Optional[Any] = None
    # tick the request actually ENTERED the queue (stamped by
    # submit(tick=...) — the engine passes its round tick): the
    # priority policy's aging base. None falls back to ``arrival``,
    # so bare-scheduler callers keep today's semantics
    queued_tick: Optional[float] = None
    # KV-pressure preemption (ISSUE 15): a preempted request's full
    # known stream (prompt + generated tokens at preemption) — the
    # effective prompt its re-admission replays through the EXISTING
    # packed prefill program. None = never preempted past its first
    # token (re-admission is a plain fresh prefill).
    resume_tokens: Optional[List[int]] = None
    preemptions: int = 0
    # host swap tier (ISSUE 20): the banked device pages of a
    # preempted stream (an engine-owned ``kv_tier.SwappedPages``
    # handle). Typed loosely for the same stdlib-only reason as
    # ``sampling`` — this module never imports the jax-backed
    # kv_tier; the ENGINE banks at preemption (via the ``swap_out``
    # ctor callback) and restores or discards at re-admission.
    swapped: Optional[Any] = None
    shed_tick: Optional[int] = None   # deadline shedder drop point
    # filled in by the engine/scheduler:
    out_tokens: List[int] = dataclasses.field(default_factory=list)
    enqueue_wall: Optional[float] = None
    finish_wall: Optional[float] = None
    # lifecycle wall stamps (seconds, host clock — the engine threads
    # them through admit/prefill so replay latencies are seconds, not
    # tick counts; apex_tpu.serving.lifecycle derives TTFT/TPOT here)
    admitted_wall: Optional[float] = None
    first_token_wall: Optional[float] = None
    admitted_tick: Optional[int] = None
    finished_tick: Optional[int] = None
    # why it waited (``admit``): the admit calls that left it queued,
    # and the last one's reason, ``ContinuousBatchingScheduler.stopped``
    # for the candidate admission stopped at, "behind" for the rest
    queued_rounds: int = 0
    blocked: Optional[str] = None

    def done(self):
        return len(self.out_tokens) >= self.max_new_tokens


@dataclasses.dataclass
class Slot:
    request: Request
    pages: List[int]
    pos: int = 0                  # context length held in the cache
    next_token: int = 0           # token the next decode step consumes
    # the KNOWN token stream this slot must consume before generating
    # anything new: the prompt for a fresh admission, the preempted
    # stream (prompt + generated-so-far) for a resumed one. The decode
    # loop's warmup/seam bookkeeping keys on its length — one rule for
    # fresh, prefix-hit and resumed slots alike (ISSUE 15).
    known: List[int] = dataclasses.field(default_factory=list)
    # prefix-cache bookkeeping (ISSUE 13; all empty/zero when the
    # cache is off or the prompt missed):
    shared_pages: List[int] = dataclasses.field(default_factory=list)
    prefix_hit: int = 0           # prompt tokens covered by the cache
    # (src, dst) page copies the ENGINE must perform before the slot's
    # first write — the copy-on-write of a matched partial tail page
    cow_copies: List[Tuple[int, int]] = dataclasses.field(
        default_factory=list)


class ContinuousBatchingScheduler:
    def __init__(self, num_slots, max_pages_per_slot, page_size,
                 allocator, policy=None, prefix=None, preempt=False,
                 swap_out=None):
        self.num_slots = int(num_slots)
        self.max_pages = int(max_pages_per_slot)
        self.page_size = int(page_size)
        self.allocator = allocator
        self.policy = resolve_policy(policy)
        self.prefix = prefix      # PrefixCache or None (engine-owned)
        # KV-pressure preemption (ISSUE 15): with the flag on,
        # admission reserves PROMPT pages only (overcommit) and
        # :meth:`grow` extends the table mid-stream, preempting the
        # lowest-effective-priority running slot when a grant is
        # refused. Off = the all-or-nothing up-front reservation the
        # scheduler always had (disabled mode behavior-identical).
        self.preempt = bool(preempt)
        # host swap tier (ISSUE 20): ``swap_out(slot) -> handle or
        # None`` banks a victim's live pages device→host BEFORE they
        # are freed. Engine-owned callable (this module stays
        # stdlib-only); None = the tier is off and preemption is
        # vLLM-style recompute, exactly as before.
        self.swap_out = swap_out
        self.slots = [None] * self.num_slots
        self.queue = deque()
        self.completed = []
        self.shed = []            # deadline-shed requests (engine-fed)
        # why the last :meth:`admit` call left requests queued: "slots"
        # (no free slot), "budget" (``token_budget``), "pages" (the
        # allocator refused); None when the queue emptied
        self.stopped = None
        self._preempted = []      # requests preempted since last drain

    # ------------------------------------------------------- bookkeeping

    def submit(self, request, tick=None):
        """Enqueue one request. An impossible request (prompt +
        max_new_tokens over the per-slot page table, i.e. over
        max_seq) raises HERE — before anything is enqueued — so one
        malformed submission can never crash a later scheduler round
        mid-step and take the whole serving loop (and every other
        queued request) down with it. ``tick`` stamps
        ``queued_tick`` — the priority policy ages WAITING time, not
        absolute tick, so a late direct submission gets no spurious
        boost."""
        self.validate(request)
        if tick is not None and request.queued_tick is None:
            request.queued_tick = tick
        self.queue.append(request)

    def validate(self, request):
        """The impossible-request teeth, callable on their own: the
        ENGINE runs them before its admission-control gate (ISSUE 15)
        so a malformed request always raises — a full queue must
        reject load, never mask a programming error as a
        ``Rejected``."""
        if request.max_new_tokens < 1:
            raise ValueError(
                f"request {request.rid}: max_new_tokens must be >= 1 "
                f"(prefill always samples the first token)")
        need = self._request_pages(request)
        if need > self.max_pages:
            raise ValueError(
                f"request {request.rid}: {need} pages exceed the "
                f"per-slot table ({self.max_pages}) — prompt + "
                f"max_new_tokens over max_seq")

    def active_indices(self):
        return [i for i, s in enumerate(self.slots) if s is not None]

    def _request_pages(self, req):
        # deferred: kv_cache imports jax.numpy at module level for the
        # cache arrays, and this module's stdlib-only claim is
        # mechanically checked over the import graph (apexlint APX006)
        from apex_tpu.serving.kv_cache import pages_needed

        return pages_needed(len(req.prompt) + req.max_new_tokens,
                            self.page_size)

    def queue_depth(self):
        return len(self.queue)

    def head_of_line_wait(self, wall_time, tick=None):
        """Seconds the BLOCKING request has been waiting at
        ``wall_time`` (0.0 with an empty queue or an unstamped head)
        — the gauge that names head-of-line blocking as a number.
        Under ``fifo`` that is the oldest queued request; under
        ``priority`` admission blocks on :meth:`_select`'s pick, so
        the gauge follows it (``tick`` feeds the aging term — the
        engine passes its round tick)."""
        if not self.queue:
            return 0.0
        head = self._select(tick if tick is not None else 0)
        if head.enqueue_wall is None:
            return 0.0
        return max(0.0, wall_time - head.enqueue_wall)

    def _select(self, tick):
        """The admission candidate under the active policy: the queue
        head under ``fifo``; under ``priority`` the request with the
        highest EFFECTIVE priority (``priority + waiting_ticks /
        AGING_TICKS`` — the aging term is the no-starvation rule),
        oldest-first on ties. Head-of-line blocking applies to the
        SELECTED request either way."""
        if self.policy == "fifo" or len(self.queue) == 1:
            return self.queue[0]
        best, best_key = None, None
        for pos, r in enumerate(self.queue):
            queued = r.queued_tick if r.queued_tick is not None \
                else r.arrival
            eff = r.priority + max(0.0, tick - queued) / AGING_TICKS
            key = (-eff, pos)     # pos = submit order (FIFO tie-break)
            if best_key is None or key < best_key:
                best, best_key = r, key
        return best

    def _alloc_with_reclaim(self, owner, n, protect=(), tick=None,
                            phase="admit"):
        """Allocator grant with prefix-cache pressure relief: a short
        free list asks the cache to reclaim unreferenced pages first
        (pages with live refs are NEVER freed — the cache refuses;
        ``protect`` additionally fences the cover THIS admission just
        matched, so reclaim can never free-and-rehand the pages its
        own request is about to share), then retries once. The
        ``serve_alloc`` chaos site (ISSUE 15) can script a refusal at
        an exact (tick, phase) without shrinking the pool — the
        preemption path then runs under deterministic page pressure."""
        if _faults.denied("serve_alloc", tick=tick, phase=phase):
            return None
        pages = self.allocator.alloc(owner, n)
        if pages is None and self.prefix is not None:
            shortfall = n - self.allocator.free_count
            if self.prefix.reclaim(shortfall,
                                   protect=protect) >= shortfall:
                pages = self.allocator.alloc(owner, n)
        return pages

    def admit(self, tick, wall_time=None, token_budget=None):
        """Admission of every queued request that fits under the
        active policy, stopping at the first selected candidate that
        does not (head-of-line blocking — the no-starvation rule).
        Returns the newly filled slot indices. ``token_budget`` (None:
        no bound) also stops it at the first candidate whose known
        stream would take this round's admissions past that many
        tokens (the first always enters): the engine of a family that
        prefills one dispatch a round hands in ``prefill_len``, and
        what is left waits in the queue for the next round, so no
        round holds the decode lanes for more than one prefill
        dispatch. ``wall_time`` (the
        engine's host clock, one read per round) stamps each
        admission's ``admitted_wall`` — the same wall seam as
        :meth:`evict_done`, so replay latencies are seconds, not tick
        counts. With a prefix cache attached, the prompt's cached
        cover enters the slot by reference (full pages) and
        copy-on-write (partial tail), and only the remainder
        allocates. A call that leaves requests queued says why in
        ``self.stopped`` and on each of them (``Request.blocked``,
        ``Request.queued_rounds``): one write a queued request, none
        with the queue empty."""
        admitted = []
        stopped = None
        while self.queue:
            req = self._select(tick)
            free = [i for i, s in enumerate(self.slots) if s is None]
            need = self._request_pages(req)
            # submit() already refused impossible requests; anything
            # queued is admittable once slots/pages free up
            assert need <= self.max_pages, (req.rid, need)
            if not free:
                stopped = "slots"
                break
            known = req.resume_tokens or req.prompt
            if token_budget is not None and admitted \
                    and len(known) > token_budget:
                stopped = "budget"
                break
            shared, covered, tail = [], 0, None
            # a RESUMED request skips the prefix lookup: its effective
            # prompt is the preempted stream, not the prompt the cache
            # chains are keyed by — re-admission replays it through
            # the packed prefill program instead (ISSUE 15)
            if self.prefix is not None and req.resume_tokens is None:
                shared, covered, tail = self.prefix.lookup(req.prompt)
            matched = list(shared) + ([tail[0]] if tail else [])
            # under preemption (overcommit), admission reserves only
            # the KNOWN stream's pages — decode grows the table as
            # positions cross page boundaries (grow()); off, the
            # all-or-nothing full reservation stands
            from apex_tpu.serving.kv_cache import pages_needed

            reserve = pages_needed(len(known), self.page_size) \
                if self.preempt else need
            pages = self._alloc_with_reclaim(("req", req.rid),
                                             reserve - len(shared),
                                             protect=matched, tick=tick)
            if pages is None:
                stopped = "pages"
                break
            self.queue.remove(req)
            idx = free[0]
            slot = Slot(request=req, pages=shared + pages,
                        shared_pages=list(shared), prefix_hit=covered,
                        known=list(known))
            if covered:
                # the covered suffix replays through decode: position
                # `covered` is the first token the engine feeds
                slot.pos = covered
                slot.next_token = req.prompt[covered]
                if tail is not None:
                    # COW: the snapshot's content lands in the slot's
                    # first private page (same page index) before any
                    # write can alias another request's stream
                    slot.cow_copies.append((tail[0], pages[0]))
            if shared:
                self.prefix.acquire(shared)
            if self.prefix is not None:
                self.prefix.count(len(req.prompt), covered)
            self.slots[idx] = slot
            req.admitted_tick = tick
            if wall_time is not None:
                req.admitted_wall = wall_time
            admitted.append(idx)
            if token_budget is not None:
                token_budget -= len(known)
        self.stopped = stopped
        if stopped is not None:
            for waiting in self.queue:
                waiting.queued_rounds += 1
                waiting.blocked = "behind"
            req.blocked = stopped
        return admitted

    # -------------------------------------- KV-pressure preemption (15)

    def _select_victim(self, tick):
        """The slot index to preempt under page pressure: the LOWEST
        effective priority among running slots — base ``priority``
        (running requests do not age: aging rewards waiting), youngest
        admission first on ties (the latest arrival has the least sunk
        work to replay — vLLM's recompute-preemption order). A slot
        whose request already FINISHED this round (awaiting next
        round's evict) is never a victim: its pages free at the evict
        anyway, and requeuing it would stamp a preempted event after
        finished — a transition the lifecycle machine forbids. None
        when nothing preemptible is running."""
        best, best_key = None, None
        for i, slot in enumerate(self.slots):
            if slot is None or slot.request.done():
                continue
            r = slot.request
            key = (r.priority,
                   -(r.admitted_tick if r.admitted_tick is not None
                     else tick),
                   -r.rid)
            if best_key is None or key < best_key:
                best, best_key = i, key
        return best

    def requeue_slot(self, i, tick, swap=True):
        """Force running slot *i* back into the queue (preemption
        under page pressure, or round recovery after a wedged
        dispatch): free its private pages, decref its shared prefix
        pages (the cache refuses to free referenced pages — refcounts
        respected), stash the known stream for the re-prefill replay,
        and REQUEUE the request (it keeps its original
        ``queued_tick``, so priority aging preserves its seniority —
        a preempted request cannot be starved). Returns the
        request.

        ``swap=True`` offers the slot to the engine's ``swap_out``
        callback BEFORE its pages are freed (the host swap tier,
        ISSUE 20) — the handle rides on ``req.swapped`` next to
        ``resume_tokens``. The engine passes ``swap=False`` from its
        round-recovery and failover-drain paths, where the device
        cache is exactly what cannot be trusted."""
        slot = self.slots[i]
        req = slot.request
        req.swapped = (self.swap_out(slot)
                       if swap and self.swap_out is not None else None)
        self.allocator.free(("req", req.rid))
        if slot.shared_pages and self.prefix is not None:
            self.prefix.release(slot.shared_pages)
        # the full known stream (prompt + generated) is what
        # re-admission replays; a slot preempted before its first
        # token resumes as a plain fresh prefill
        req.resume_tokens = (list(req.prompt) + list(req.out_tokens)) \
            if req.out_tokens else None
        req.preemptions += 1
        self.slots[i] = None
        self.queue.append(req)
        return req

    def grow(self, i, min_pages, tick):
        """Mid-stream page growth for slot *i* (preemption mode): make
        its table hold >= ``min_pages`` pages, preempting the
        lowest-effective-priority running slot (possibly *i* itself —
        then False is returned and the caller drops the lane) each
        time a grant is refused. Preempted requests land in the
        :meth:`take_preempted` buffer for the engine's lifecycle
        events. Progress is guaranteed by the engine's pool check
        (``num_pages - 1 >= max_pages``): with everything else
        preempted and the prefix cache reclaimed, a lone slot can
        always reach ``max_seq`` pages."""
        slot = self.slots[i]
        while len(slot.pages) < min_pages:
            got = self._alloc_with_reclaim(
                ("req", slot.request.rid), 1, tick=tick, phase="grow")
            if got is not None:
                slot.pages.extend(got)
                continue
            victim = self._select_victim(tick)
            if victim is None:  # defensive: slot i itself is a candidate
                return False
            self._preempted.append(self.requeue_slot(victim, tick))
            if victim == i:
                return False
        return True

    def take_preempted(self):
        """Drain the requests preempted since the last call (the
        engine records their ``preempted``/``resubmitted`` lifecycle
        events and counters from this buffer)."""
        out, self._preempted = self._preempted, []
        return out

    def evict_done(self, tick, wall_time=None):
        """Free slots/pages of completed requests; returns them.
        Private pages return to the free list; shared prefix pages
        only DECREF (the cache refuses to free referenced pages — a
        completed request's shared system prompt stays warm for the
        next arrival). ``wall_time`` backstops ``finish_wall`` for
        requests whose finishing dispatch did not stamp it (the one
        wall-clock seam shared with :meth:`admit`)."""
        done = []
        for i, slot in enumerate(self.slots):
            if slot is not None and slot.request.done():
                self.allocator.free(("req", slot.request.rid))
                if slot.shared_pages and self.prefix is not None:
                    self.prefix.release(slot.shared_pages)
                slot.request.finished_tick = tick
                if wall_time is not None \
                        and slot.request.finish_wall is None:
                    slot.request.finish_wall = wall_time
                self.completed.append(slot.request)
                done.append(slot.request)
                self.slots[i] = None
        return done

    # ------------------------------------------- static-shape array views

    def page_table_rows(self):
        """int32 [num_slots, max_pages]; empty slots / unallocated
        tail -> null page 0."""
        rows = [[0] * self.max_pages for _ in range(self.num_slots)]
        for i, slot in enumerate(self.slots):
            if slot is not None:
                for j, p in enumerate(slot.pages):
                    rows[i][j] = p
        return rows

    def context_pages(self, window=None):
        """What a decode round reads of the two kinds of KV state
        (serving/kv_cache.py), summed over the live slots: ``(global
        pages, window pages)``. A global layer reads every page that
        holds context, ``ceil(pos / page_size)`` a slot (admission
        reserves more: the answer's pages too); a window layer reads the
        ring pages that hold part of the slot's last ``window``
        positions, one or two at a window no longer than a page. The
        ring itself is the slot's, written at ``position mod ring``,
        never allocated or freed: nothing of it is accounted here beyond
        this count."""
        ps = self.page_size
        global_pages = window_pages = 0
        for slot in self.slots:
            if slot is None or slot.pos < 1:
                continue
            last = (slot.pos - 1) // ps
            global_pages += last + 1
            if window is not None:
                window_pages += last - max(0, slot.pos - window) // ps + 1
        return global_pages, window_pages

    def decode_inputs(self):
        """(tokens, lengths) int lists for the decode step: length 0
        marks an inactive slot (the step zeros its lane)."""
        tokens = [0] * self.num_slots
        lengths = [0] * self.num_slots
        for i, slot in enumerate(self.slots):
            if slot is not None:
                tokens[i] = int(slot.next_token)
                lengths[i] = slot.pos + 1
        return tokens, lengths


def synthetic_trace(seed=0, n_requests=16, vocab=256, prompt_lo=4,
                    prompt_hi=24, new_lo=4, new_hi=32,
                    mean_interarrival=0.5, arrival="poisson",
                    diurnal_period=32.0, diurnal_depth=0.8,
                    system_prompt=None):
    """Deterministic request trace: ``(requests, trace_id)``. Arrival
    is in decode-step ticks; the id is a content hash of every
    request's (arrival, prompt, max_new) so a cited serving row names
    exactly the workload it measured.

    ``arrival`` selects the OPEN-LOOP arrival process (unknown values
    raise — a per-call argument is a demand):

    * ``"poisson"`` — exponential inter-arrivals at rate
      ``1/mean_interarrival`` (the process the original trace always
      drew; byte-identical stream and ``tr-`` id for existing seeds).
    * ``"diurnal"`` — non-homogeneous Poisson: the instantaneous rate
      swings sinusoidally around the base rate with period
      ``diurnal_period`` ticks and relative amplitude
      ``diurnal_depth`` in [0, 1) (floored at 5% of base so the
      trough never stalls the trace) — peak-hour bursts and
      night-trough droughts in one seeded, content-hashed trace.

    ``system_prompt`` (ISSUE 13): an optional shared token prefix
    prepended to EVERY request's prompt — the shared-system-prompt
    workload the prefix cache exists for. The content hash covers the
    final (prepended) prompts, so a trace with a system prompt never
    shares a ``tr-`` id with one without.
    """
    if arrival not in ARRIVALS:
        raise ValueError(f"unknown arrival process {arrival!r} "
                         f"(vocabulary: {ARRIVALS})")
    rng = random.Random(seed)
    t = 0.0
    reqs = []
    for rid in range(n_requests):
        if mean_interarrival > 0:
            rate = 1.0 / mean_interarrival
            if arrival == "diurnal":
                rate *= 1.0 + diurnal_depth * math.sin(
                    2.0 * math.pi * t / diurnal_period)
                rate = max(rate, 0.05 / mean_interarrival)
            t += rng.expovariate(rate)
        plen = rng.randint(prompt_lo, prompt_hi)
        prompt = [rng.randrange(vocab) for _ in range(plen)]
        if system_prompt:
            prompt = [int(t) for t in system_prompt] + prompt
        reqs.append(Request(
            rid=rid, prompt=prompt,
            max_new_tokens=rng.randint(new_lo, new_hi),
            arrival=round(t, 3)))
    h = hashlib.sha1(repr(
        [(r.arrival, tuple(r.prompt), r.max_new_tokens)
         for r in reqs]).encode()).hexdigest()[:10]
    return reqs, f"tr-{h}"


def offered_load(requests):
    """Offered load of a trace in requests per tick: request count
    over the arrival span (the open-loop intensity a cited slo row
    names next to its arrival process). 0.0 for an empty trace; a
    same-tick burst divides by the 1-tick floor."""
    if not requests:
        return 0.0
    span = max(r.arrival for r in requests)
    return len(requests) / max(span, 1.0)
