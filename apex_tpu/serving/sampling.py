"""Batched stochastic sampling for the serving decode path (ISSUE 13).

Temperature / top-k / top-p sampling as ARRAY-VALUE ops inside the one
compiled decode program: every per-request parameter (temperature,
top_k, top_p, the threefry key lane, the per-request sample counter)
rides into :func:`sample_tokens` as a ``[B]``-shaped array the engine
re-stages each round — never a static argument — so admitting, evicting
or re-seeding requests changes array VALUES only and the decode step
keeps its one-compile contract (``decode_cache_size()==1``, asserted
with sampling on in tests/test_serving_generation.py).

Determinism is per REQUEST, not per batch: each request carries its own
threefry key (``PRNGKey(seed)``) and every sampled token folds in the
request's own generation index (``fold_in(key, n_generated)``), so the
token stream of a seeded request is identical whatever the batch
composition, slot placement or eviction order around it — the property
the per-slot-RNG determinism test pins.

Greedy exactness: a temperature-0 lane takes the exact
``argmax(logits.astype(f32))`` the pre-sampling decode step computed —
not a limit of the softmax path — so a sampling-enabled engine over
all-greedy requests reproduces the greedy engine token-for-token.

Knob: ``sampling=`` at engine build (per-call bool; a sampling-OFF
engine RAISES at ``submit`` when a request demands stochastic params —
explicit request ≠ preference) > ``set_sampling`` setter >
``APEX_SERVE_SAMPLING`` env preference > built-in OFF. Default OFF per
the measured-dispatch rule: with sampling compiled in, even all-greedy
batches pay the sort/top-p ops, so the decode program only grows them
when asked (the sampling-vs-greedy decode A/B has not been run).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.dispatch import tiles as _tiles

_SAMPLING = None  # process-wide tri-state preference


def set_sampling(value):
    """Pin the process-wide sampling preference (True/False), or un-pin
    with None (env then default apply). A setter CALL with a non-bool
    raises."""
    global _SAMPLING
    if value is not None and not isinstance(value, bool):
        raise ValueError(
            f"set_sampling wants True/False/None, got {value!r}")
    _SAMPLING = value


def resolve(per_call=None):
    """The effective sampling decision: per-call (the engine validates
    demands at submit — a stochastic request against a sampling-off
    engine raises there) > setter > ``APEX_SERVE_SAMPLING`` env
    (warn-once-and-ignore on unknown values) > built-in OFF."""
    if per_call is not None:
        if not isinstance(per_call, bool):
            raise ValueError(
                f"sampling= wants True/False/None, got {per_call!r}")
        return per_call
    if _SAMPLING is not None:
        return _SAMPLING
    v = _tiles.env_choice("APEX_SERVE_SAMPLING", ("1", "0"))
    if v is not None:
        return v == "1"
    return False


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling controls (the vLLM ``SamplingParams``
    analog — see docs/MIGRATING.md). ``temperature=0`` is EXACT greedy
    (the argmax path, not a softmax limit); ``top_k=0`` / ``top_p=1``
    disable their truncations. ``seed`` keys the request's private
    threefry lane."""
    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0

    def validate(self):
        problems = []
        if self.temperature < 0:
            problems.append(f"temperature {self.temperature} < 0")
        if self.top_k < 0:
            problems.append(f"top_k {self.top_k} < 0")
        if not 0.0 < self.top_p <= 1.0:
            problems.append(f"top_p {self.top_p} not in (0, 1]")
        if problems:
            raise ValueError("invalid SamplingParams: "
                             + "; ".join(problems))

    @property
    def greedy(self):
        return self.temperature == 0.0


GREEDY = SamplingParams()


def request_key(seed):
    """The request's private threefry key lane as raw host bytes
    (``uint32[2]``), computed ONCE at submit so the per-round lane
    staging is pure numpy. Determinism hangs off this: the lane is a
    function of the request's seed alone, never of the slot or batch
    it lands in."""
    return np.asarray(jax.random.PRNGKey(int(seed)))


def _lane_buffers(n):
    """Zeroed/off-valued lane arrays for ``n`` lanes: ``(temps,
    top_ks, top_ps, keys, counters)``."""
    return (np.zeros((n,), np.float32), np.zeros((n,), np.int32),
            np.ones((n,), np.float32), np.zeros((n, 2), np.uint32),
            np.zeros((n,), np.int32))


def fill_lane(request, i, temps, top_ks, top_ps, keys):
    """Stage ONE request's sampling params + key into lane ``i`` —
    the single fill both the per-round decode staging and the
    engine's prefill first-token sampling go through, so a request's
    first token can never be drawn under different truncation/key
    semantics than the rest of its stream. The key derives lazily and
    is CACHED on the request (greedy lanes never read theirs — the
    zero lane is fine and costs no dispatch)."""
    p = getattr(request, "sampling", None) or GREEDY
    temps[i] = p.temperature
    top_ks[i] = p.top_k
    top_ps[i] = p.top_p
    key = getattr(request, "rng_key", None)
    if key is None and p.temperature > 0:
        key = request_key(p.seed)
        request.rng_key = key
    if key is not None:
        keys[i] = key


def lane_arrays(slots, num_slots):
    """The per-round ``[B]`` sampling-lane arrays for the decode
    program, rebuilt from the live slots (array VALUES change across
    admit/evict; shapes never): ``(temps, top_ks, top_ps, keys,
    counters)``. The counter is the request's own generation index
    (``len(out_tokens)``) — eviction and re-admission elsewhere cannot
    perturb another request's stream."""
    temps, top_ks, top_ps, keys, counters = _lane_buffers(
        int(num_slots))
    for i, slot in enumerate(slots):
        if slot is None:
            continue
        fill_lane(slot.request, i, temps, top_ks, top_ps, keys)
        counters[i] = len(slot.request.out_tokens)
    return temps, top_ks, top_ps, keys, counters


def batch_lanes(requests):
    """Lane arrays for an explicit request list (the engine's
    first-token sampling over a packed prefill batch): counters stay
    0 — the first token IS generation index 0."""
    temps, top_ks, top_ps, keys, counters = _lane_buffers(
        len(requests))
    for i, req in enumerate(requests):
        fill_lane(req, i, temps, top_ks, top_ps, keys)
    return temps, top_ks, top_ps, keys, counters


def sample_tokens(logits, temps, top_ks, top_ps, keys, counters,
                  active):
    """One sampled token per lane from ``[B, V]`` logits — pure jnp,
    traced INSIDE the decode program (and run eagerly on the prefill
    logits for each request's first token, the existing host-argmax
    idiom).

    temps/top_ps ``[B] f32``, top_ks/counters ``[B] i32``, keys
    ``[B, 2] u32`` (raw threefry lanes), active ``[B] bool``. Lane
    semantics: ``temps[i] == 0`` -> the exact f32 argmax; else logits
    are temperature-scaled, truncated to the top-k set (0 = off) AND
    the top-p nucleus (1 = off; the crossing token is kept, so the set
    is never empty), and the token is drawn by Gumbel-max under
    ``fold_in(keys[i], counters[i])`` — a function of the request's
    own key and generation index only, never of the batch around it.
    Inactive lanes return 0.
    """
    lf = logits.astype(jnp.float32)
    V = lf.shape[-1]
    greedy = jnp.argmax(lf, axis=-1).astype(jnp.int32)

    scaled = lf / jnp.maximum(temps, 1e-6)[:, None]
    sorted_desc = -jnp.sort(-scaled, axis=-1)
    # top-k: the kth largest value is the keep threshold (k=0 -> V)
    k_eff = jnp.where(top_ks > 0, top_ks, V)
    k_idx = jnp.clip(k_eff - 1, 0, V - 1)
    kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
    keep_k = scaled >= kth
    # top-p nucleus over the sorted probabilities: a sorted position is
    # kept while the mass BEFORE it is under p (the crossing token is
    # kept — the nucleus always holds >= 1 token); the smallest kept
    # sorted value is then the unsorted keep threshold
    probs = jax.nn.softmax(sorted_desc, axis=-1)
    before = jnp.cumsum(probs, axis=-1) - probs
    keep_sorted = before < top_ps[:, None]
    cut_idx = jnp.maximum(jnp.sum(keep_sorted.astype(jnp.int32),
                                  axis=-1) - 1, 0)
    cut = jnp.take_along_axis(sorted_desc, cut_idx[:, None], axis=-1)
    keep_p = scaled >= cut
    masked = jnp.where(keep_k & keep_p, scaled, -jnp.inf)

    def _lane_gumbel(key, ctr):
        return jax.random.gumbel(jax.random.fold_in(key, ctr), (V,),
                                 jnp.float32)

    gumbel = jax.vmap(_lane_gumbel)(keys, counters)
    drawn = jnp.argmax(masked + gumbel, axis=-1).astype(jnp.int32)
    toks = jnp.where(temps <= 0.0, greedy, drawn)
    return jnp.where(active, toks, 0)
