"""Self-drafting speculative decode: host-side draft + accept logic.

Stdlib-only (like ``scheduler``/``lifecycle``): this module owns the
n-gram draft proposal and the accept/rollback ARITHMETIC; the verify
dispatch itself is the engine's existing packed-varlen prefill program
(``model.prefill`` — the verify batch IS the packed segment-id shape,
so no third compiled program exists; tests assert the jit cache sizes
stay at one prefill + one decode with speculation on).

The draft has NO second model (ROADMAP 2b): the most recent earlier
occurrence of the sequence's trailing n-gram proposes the tokens that
followed it — free to compute, surprisingly effective on the looping
continuations greedy decode produces, and zero new device state. A
verify round feeds the slot's FULL sequence (prompt + generated +
draft) as one segment of the packed prefill bucket: segment-masked
causal attention over the segment is exactly full-context attention,
the already-cached context positions route their K/V writes to the
null spare row (the cache keeps its decode-written values bit-exact),
and only the pending-token + draft positions write real pages.
Acceptance then takes the longest draft prefix matching the verify
logits' greedy chain plus ONE bonus token; ROLLBACK is pure index
arithmetic — rejected positions' K/V stay in the pages as garbage
beyond the new length, never read (decode attention masks by context
length) and overwritten when the sequence advances (the same
null-page-0 discipline the allocator already guarantees).

Knob (the CLAUDE.md asymmetry): per-call ``spec_decode=K`` at engine
build RAISES when un-honorable (K < 1, or K+1 deeper than the prefill
bucket); the ``APEX_SPEC_DECODE`` env is a preference — 0/unset is
off, garbage warns once and is ignored. Default OFF per the
measured-dispatch rule (the verify-vs-decode device A/B has not been
run); correctness — speculative
output ≡ non-speculative greedy token-for-token — is pinned on CPU by
tests/test_serving_generation.py.
"""

NGRAM = 2  # trailing n-gram the draft matches (the self-draft context)


def resolve_k(per_call=None):
    """The effective draft length K: per-call (raises on un-honorable
    — an explicit request is a demand) > ``APEX_SPEC_DECODE`` env
    preference (``tiles.env_nonneg_int``: 0/unset = off — 0 is the
    legal explicit off-pin profile_serving stamps; garbage warns once
    and is ignored) > built-in OFF (0)."""
    if per_call is not None:
        if isinstance(per_call, bool) or not isinstance(per_call, int) \
                or per_call < 1:
            raise ValueError(
                f"spec_decode= wants a draft length >= 1 or None, "
                f"got {per_call!r}")
        return per_call
    from apex_tpu.dispatch import tiles as _tiles

    return _tiles.env_nonneg_int("APEX_SPEC_DECODE") or 0


def propose(history, k, ngram=NGRAM):
    """Up to ``k`` draft tokens for a sequence ending in ``history``
    (prompt + generated so far, oldest first): the tokens that
    followed an earlier occurrence of the trailing ``ngram`` —
    preferring the most recent occurrence with a FULL ``k``-token
    continuation (an occurrence at the very end of history can only
    contribute a truncated draft; on a period-1 loop the one-back
    match would cap every draft at a single token), falling back to
    the longest continuation found. An empty list when no earlier
    occurrence exists (the engine then runs a plain decode round — a
    draft is an optimization, never a requirement)."""
    n = len(history)
    if k < 1 or n < ngram + 1:
        return []
    tail = list(history[-ngram:])
    best = []
    for i in range(n - ngram - 1, -1, -1):
        if list(history[i:i + ngram]) == tail:
            cont = list(history[i + ngram:i + ngram + k])
            if len(cont) == k:
                return cont
            if len(cont) > len(best):
                best = cont
    return best


def accept(draft, greedy):
    """Accept/rollback arithmetic for one verified slot: ``draft`` is
    the proposed tokens d_1..d_k; ``greedy`` is the verify program's
    argmax chain g_0..g_k where ``g_j`` is the model's token AFTER
    consuming position j of the verify window (g_0 follows the pending
    token). Returns the tokens the round PRODUCES: the longest draft
    prefix matching the greedy chain plus the one bonus token — between
    1 (all rejected: the bonus is g_0, exactly the plain decode round's
    token) and ``len(draft) + 1`` tokens, always the same stream plain
    greedy decode would emit one token at a time."""
    out = []
    a = 0
    while a < len(draft) and draft[a] == greedy[a]:
        out.append(draft[a])
        a += 1
    out.append(greedy[a])  # the bonus token (g_a exists: len == k+1)
    return out


class SpecStats:
    """Per-engine speculation counters -> the ledger's
    ``spec_acceptance_rate`` / ``draft_len`` fields (None-when-off at
    the profile_serving seam)."""

    def __init__(self):
        self.rounds = 0          # verified slots (one per verify lane)
        self.drafted = 0         # draft tokens proposed
        self.accepted = 0        # draft tokens accepted
        self.bonus = 0           # bonus tokens (1 per verified slot)

    def record(self, drafted, accepted):
        self.rounds += 1
        self.drafted += int(drafted)
        self.accepted += int(accepted)
        self.bonus += 1

    def acceptance_rate(self):
        """Accepted fraction of drafted tokens (None before any
        draft)."""
        if not self.drafted:
            return None
        return self.accepted / self.drafted

    def mean_draft_len(self):
        if not self.rounds:
            return None
        return self.drafted / self.rounds
