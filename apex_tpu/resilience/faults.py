"""Deterministic fault injection (``APEX_FAULT_PLAN``) — TEST ONLY.

The failure modes the checkpointer, the serving engine and the fleet
router are built to survive can be replayed on the CPU,
deterministically, through the REAL code: the environment variable
holds a JSON fault plan (or a path to one), inherited by any
subprocess, and the code under test calls the hooks below at the
places a real failure strikes. The chaos suites
(``tests/test_checkpoint_chaos.py``, ``test_serving_chaos.py``,
``test_router_chaos.py``, ``test_kv_tier.py``) are built on this;
``tests/test_fault_sites.py`` holds every site named here to a caller.

NEVER set ``APEX_FAULT_PLAN`` for a measurement: every ledger record
written while a plan is active is stamped ``fault_plan: <hash>``
(inside the content-hashed id, so the stamp cannot be stripped after
the fact), and ``tools/check_bench_labels.py`` fails tier-1 if PERF.md
or the dispatch table ever cites a stamped record — an injected run
can never masquerade as a measurement.

Plan format — a JSON object ``{"faults": [...]}`` (or bare list); each
fault::

    {"site":  "ckpt_commit" | "ckpt_manifest" | "ckpt_data" |
              "serve_alloc" | "serve_prefill" | "serve_decode" |
              "serve_burst" | "serve_swap" |
              "router_kill" | "router_wedge" | "router_slow",
     "kind":  "hang" | "raise" | "sigkill" | "set_field" |
              "truncate_file" | "corrupt_file" | "deny" | "burst" |
              "corrupt",
     "match_env": {"VAR": "value" | null},   # null = must be unset
     "match_ctx": {"step": 2, "phase": "data_visible"},  # hook kwargs
     ... kind-specific fields ...}

Failure-mode map:

=======================================  ================================
failure mode                              scripted as
=======================================  ================================
SIGKILL mid-checkpoint-commit             ckpt_commit/sigkill with
                                            match_ctx phase
slow-disk commit stall                    ckpt_commit/hang (seconds)
truncated/corrupt checkpoint file         ckpt_data/truncate_file or
  (disk rot, torn write)                    corrupt_file
stale-step restore (tampered manifest)    ckpt_manifest/set_field
KV-page exhaustion at a chosen round      serve_alloc/deny with
  (serving, ISSUE 15)                       match_ctx tick/phase + times
decode dispatch hang / exception          serve_decode/hang or raise
  (a wedge mid-serving-round)               with match_ctx step
prefill failure mid-admission             serve_prefill/raise or hang
  (also fired by speculative VERIFY         (one site — verify rides
  dispatches of the same program)           the same compiled program)
trace burst overload (submit storm)       serve_burst/burst with
                                            match_ctx tick (the engine
                                            fabricates + submits the
                                            scripted burst)
whole-replica death mid-trace             router_kill/raise with
  (fleet serving, ISSUE 19; the             match_ctx tick/replica —
  router's failover drains + replays        fired inside the replica's
  through survivors)                        round closure
replica round wedge (the router's         router_wedge/hang — forever
  step watchdog times it out to a           under step_timeout_s, the
  classified DispatchFailure)               breaker trips at the cap
replica running slow, still serving       router_slow/hang with
  (degraded, NOT dead — the breaker         seconds=N + times (bounded
  must not trip on a bounded stall)         stall, round returns clean)
host-copy failure banking a preempted     serve_swap/raise or hang with
  victim's KV pages (swap tier,             match_ctx phase="swap_out"
  ISSUE 20 — falls back to recompute        — the engine classifies it
  preemption, a ``swap_failed`` event)      ``swap_failed``, never hangs
                                            the round (tokens preserved)
host-copy failure restoring swapped       serve_swap/raise or hang with
  pages at re-admission                     match_ctx phase="swap_in"
swapped page bytes rot on the host        serve_swap/corrupt with
  (the handle's checksum catches it;        match_ctx phase="swap_in" —
  restore falls back to recompute)          flips the banked bytes
=======================================  ================================

Kind-specific fields: ``seconds`` (hang: sleep N then continue; absent
= forever), ``message`` (raise), ``field``/``value`` (set_field: tamper
one JSON field pre-write), ``keep_bytes`` (truncate_file), ``offset``
(corrupt_file: XOR one byte), ``times`` (deny: fire at most N times —
one scripted refusal forces exactly one preemption), ``count``/
``prompt_len``/``max_new``/``rid_base`` (burst: the fabricated submit
storm's shape).

Stdlib-only, and every check is a no-op dict lookup when the env var is
unset — the hooks cost nothing on the measured path.
"""

import hashlib
import json
import os
import signal
import sys
import time

ENV = "APEX_FAULT_PLAN"

_cache = {"raw": None, "plan": None, "hash": None, "fired": {}}


def active():
    return bool(os.environ.get(ENV))


def plan():
    """The parsed fault list (possibly empty). Raises ValueError on an
    unparseable plan — a chaos test with a broken plan must fail, not
    silently run healthy."""
    raw = os.environ.get(ENV)
    if not raw:
        return []
    if _cache["raw"] == raw:
        return _cache["plan"]
    text = raw
    if not raw.lstrip().startswith(("{", "[")):
        with open(raw) as f:
            text = f.read()
    parsed = json.loads(text)
    faults = parsed.get("faults", []) if isinstance(parsed, dict) \
        else parsed
    if not isinstance(faults, list):
        raise ValueError(f"{ENV}: fault plan must be a list of faults")
    canon = json.dumps(faults, sort_keys=True)
    _cache.update(
        raw=raw, plan=faults, fired={},
        hash="fp-" + hashlib.sha1(canon.encode()).hexdigest()[:10])
    return faults


def plan_hash():
    """``fp-<sha1[:10]>`` of the canonical active plan, or None. Stamped
    by the ledger into every record written under injection."""
    if not active():
        return None
    plan()
    return _cache["hash"]


def _match(fault, ctx):
    for k, want in (fault.get("match_env") or {}).items():
        if os.environ.get(k) != want:
            return False
    for k, want in (fault.get("match_ctx") or {}).items():
        # hook-kwarg matcher (e.g. the checkpoint commit's step/phase):
        # a plan can target exactly "step 2's commit, after the data
        # rename" — determinism is the whole point of scripted chaos
        if ctx.get(k) != want:
            return False
    return True


def _say(fault, extra=""):
    print(f"# FAULT[{plan_hash()}] site={fault.get('site')} "
          f"kind={fault.get('kind')}{extra}", file=sys.stderr, flush=True)


def _hang(fault):
    _say(fault, f" (sleep {fault.get('seconds', 'forever')})")
    if "seconds" in fault:
        time.sleep(float(fault["seconds"]))
        return
    while True:
        time.sleep(60)


def fire(site, **ctx):
    """Execute any matching faults at *site*. May hang, raise, or kill
    the process — what a wedged device, a failed dispatch or the
    OOM-killer does to it at that point."""
    if not active():
        return
    for fault in plan():
        if fault.get("site") != site or not _match(fault, ctx):
            continue
        kind = fault.get("kind")
        if kind == "hang":
            _hang(fault)
        elif kind == "raise":
            _say(fault)
            raise RuntimeError(fault.get(
                "message", f"injected fault at {site}"))
        elif kind == "sigkill":
            # the un-catchable death (wedge teardown, OOM-killer): no
            # Python cleanup runs — exactly what the checkpoint commit
            # protocol's atomicity invariants are tested against
            _say(fault, " -> SIGKILL self")
            os.kill(os.getpid(), signal.SIGKILL)


def transform_json(site, obj, **ctx):
    """``set_field``-kind faults: tamper one field of a JSON-bound dict
    before it is written (e.g. the checkpoint manifest's ``step`` — the
    stale-step restore mode). Returns a (possibly modified) copy; the
    original is never mutated."""
    if not active():
        return obj
    for fault in plan():
        if fault.get("site") != site or not _match(fault, ctx):
            continue
        if fault.get("kind") == "set_field" and "field" in fault:
            _say(fault, f" ({fault['field']} -> {fault.get('value')!r})")
            obj = dict(obj, **{fault["field"]: fault.get("value")})
    return obj


def damage_file(site, path, **ctx):
    """File-damage faults fired AFTER a commit: ``truncate_file``
    (keep the first ``keep_bytes`` bytes — a torn write the rename
    protocol could not see) and ``corrupt_file`` (XOR the byte at
    ``offset`` — silent disk rot). The durability invariant under test:
    a file that no longer hashes to its manifest is never restored."""
    if not active():
        return
    for fault in plan():
        if fault.get("site") != site or not _match(fault, ctx):
            continue
        kind = fault.get("kind")
        if kind == "truncate_file":
            keep = int(fault.get("keep_bytes", 16))
            _say(fault, f" (truncate {path} to {keep}B)")
            with open(path, "r+b") as f:
                f.truncate(keep)
        elif kind == "corrupt_file":
            off = int(fault.get("offset", 0))
            _say(fault, f" (flip byte {off} of {path})")
            with open(path, "r+b") as f:
                f.seek(off)
                b = f.read(1)
                f.seek(off)
                f.write(bytes([b[0] ^ 0xFF]) if b else b"\xff")


def _spend(idx, fault):
    """True when *fault* (at plan index *idx*) still has budget under
    its optional ``times`` cap, consuming one firing. Unbounded faults
    always fire — the cap exists so a scripted refusal (``deny``) can
    force exactly N preemptions instead of denying every retry of the
    same round."""
    if "times" not in fault:
        return True
    n = _cache["fired"].get(idx, 0)
    if n >= int(fault["times"]):
        return False
    _cache["fired"][idx] = n + 1
    return True


def denied(site, **ctx):
    """``deny``-kind faults (serving KV-pressure chaos, ISSUE 15):
    True when a matching fault refuses this allocation — the scheduler
    treats it exactly like an empty free list, so the preemption path
    runs under scripted page pressure without shrinking the pool."""
    if not active():
        return False
    for idx, fault in enumerate(plan()):
        if fault.get("site") != site or fault.get("kind") != "deny" \
                or not _match(fault, ctx):
            continue
        if _spend(idx, fault):
            _say(fault, f" (alloc refused, ctx={ctx})")
            return True
    return False


def corrupt(site, **ctx):
    """``corrupt``-kind faults (host swap tier chaos, ISSUE 20): True
    when a matching fault wants the caller's in-memory banked bytes
    damaged — the ENGINE flips the swapped pages' host buffer so the
    handle's checksum catches exactly the silent-rot mode, and the
    restore falls back to recompute instead of resuming from garbage.
    Honors the ``times`` cap like :func:`denied`."""
    if not active():
        return False
    for idx, fault in enumerate(plan()):
        if fault.get("site") != site or fault.get("kind") != "corrupt" \
                or not _match(fault, ctx):
            continue
        if _spend(idx, fault):
            _say(fault, f" (corrupt banked bytes, ctx={ctx})")
            return True
    return False


def burst(site, **ctx):
    """``burst``-kind faults (serving overload chaos, ISSUE 15): the
    matching fault dict — the ENGINE fabricates and submits the
    scripted request storm (count/prompt_len/max_new/rid_base fields)
    so admission control is exercised through the real submit path —
    or None."""
    if not active():
        return None
    for idx, fault in enumerate(plan()):
        if fault.get("site") != site or fault.get("kind") != "burst" \
                or not _match(fault, ctx):
            continue
        if _spend(idx, fault):
            _say(fault, f" (burst ctx={ctx})")
            return fault
    return None
