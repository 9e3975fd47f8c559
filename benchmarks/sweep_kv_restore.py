"""kv_restore crossover sweep: recompute-replay vs swap-in restore.

The host swap tier (ISSUE 20, ``serving/kv_tier.py``) gives a
preempted stream two re-admission paths: **recompute** — replay the
known stream through the packed prefill program (the dispatch-bound
path preemption always had) — or **swap** — copy the banked pages
host→device through the one-compile scatter and resume decode
directly. Which is cheaper is shape-dependent (the replay pays the
per-dispatch floor once but recomputes O(s) attention; the swap pays
bytes ∝ s of host staging), so per the measured-dispatch rule the
resolver consults the ``kv_restore`` dispatch-table op at bucket
``s = len(resume_tokens)`` before its built-in.

This harness measures the crossover the honest way the engine pays
it: R interleaved REAL preemption → re-admission cycles per
prompt-length bucket on one live engine, each cycle's restore path
pinned via ``APEX_SERVE_KV_RESTORE``, timing the full re-admission
round (admit + restore + the one decode dispatch). The decode
dispatch and admission bookkeeping are IDENTICAL across the two
choices (both paths land the slot in the same ``(pos, next_token)``
state — the swap-parity acceptance), so the round-wall ordering IS
the restore ordering; the per-choice medians land in the entry's
``measured`` map labeled as round walls, never as bare copy times.
Interleaving (r-th swap cycle and r-th recompute cycle run at the
same stream length) keeps the +1-token-per-round drift fair, and an
assert pins every cycle of a bucket inside ONE pow2 bucket so the
committed key names exactly the lengths measured.

CPU demonstration sweep: entries land backend-keyed ``"cpu"``; the TPU
A/B at serving shapes has not been run.

Usage::

    APEX_DISPATCH=off python benchmarks/sweep_kv_restore.py \
        [--table PATH] [--ledger PATH] [--buckets 16,32,64] [--reps 4]

Writes one ledger record per (bucket, choice) and upserts one
``kv_restore`` table entry per bucket citing the winner's record.
"""

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# table-blind measurement: the sweep
# measures the two built-in paths, not yesterday's table — and the
# committed entry pins APEX_DISPATCH=off so the citation can be
# audited against exactly that
os.environ["APEX_DISPATCH"] = "off"
# the tier under measurement: KV-pressure preemption with the host
# swap tier armed (both pinned into every record's knobs)
os.environ["APEX_SERVE_PREEMPT"] = "1"
os.environ["APEX_SERVE_KV_SWAP"] = "1"

import jax  # noqa: E402

from apex_tpu import dispatch  # noqa: E402
from apex_tpu import resilience  # noqa: E402
from apex_tpu.serving import Request, ServingEngine  # noqa: E402
from apex_tpu.telemetry import ledger as ledger_mod  # noqa: E402
from apex_tpu.transformer.testing import TransformerConfig  # noqa: E402

CHOICES = ("recompute", "swap")


def build_engine():
    cfg = TransformerConfig(
        hidden_size=64, num_layers=2, num_attention_heads=4,
        vocab_size=256, max_position_embeddings=256,
        hidden_dropout=0.0, attention_dropout=0.0,
        apply_query_key_layer_scaling=False, bf16=True)
    return ServingEngine(cfg, num_slots=2, page_size=16, num_pages=24,
                         max_seq=256, prefill_len=128, preempt=True,
                         kv_swap=True)


def advance_to(eng, pos):
    """Step the engine until the live slot's cache covers ``pos``
    positions (prompt prefill + however many decode rounds)."""
    sch = eng.scheduler
    while True:
        active = sch.active_indices()
        if active and sch.slots[active[0]].pos >= pos:
            return active[0]
        eng.step()


def one_cycle(eng, si, choice):
    """One REAL preemption → re-admission cycle with the restore path
    pinned; returns (round_wall_s, stream_tokens) where stream_tokens
    is the ``s`` the resolver would bucket this restore under."""
    sch = eng.scheduler
    sch.requeue_slot(si, eng.tick)  # banks the pages (swap tier on)
    req = next(iter(sch.queue))
    tokens = len(req.resume_tokens)
    os.environ["APEX_SERVE_KV_RESTORE"] = choice
    # apexlint: disable=APX004 — host-clocked restore round: the host wall IS the measured quantity (the §0 scan protocol times device programs; this row compares two host-driven restore paths on one engine)
    t0 = time.perf_counter()
    eng.step()  # admit + restore(choice); the decode half is the next call's
    # apexlint: disable=APX004 — host-clocked restore round: the host wall IS the measured quantity (the §0 scan protocol times device programs; this row compares two host-driven restore paths on one engine)
    wall = time.perf_counter() - t0
    return wall, tokens


def sweep_bucket(eng, start_pos, reps):
    """Interleaved R-cycle A/B at one stream-length bucket; returns
    {choice: [wall_s, ...]} and the pow2 bucket key, with a guard
    asserting every cycle landed in ONE bucket."""
    si = advance_to(eng, start_pos)
    walls = {c: [] for c in CHOICES}
    buckets = set()
    for r in range(reps):
        for choice in CHOICES:
            (si,) = eng.scheduler.active_indices()
            wall, tokens = one_cycle(eng, si, choice)
            walls[choice].append(wall)
            buckets.add(dispatch.bucket(s=tokens))
    assert len(buckets) == 1, (
        f"cycle drift crossed a pow2 bucket boundary: {sorted(buckets)}"
        f" — lower start_pos or reps")
    return walls, buckets.pop()


def main(argv=None):
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0])
    ap.add_argument("--table", default=dispatch.default_path())
    ap.add_argument("--ledger", default=None,
                    help="ledger path (default: the committed "
                         "benchmarks/ledger.jsonl)")
    ap.add_argument("--buckets", default="16,32,64",
                    help="stream-length starts, comma-separated")
    ap.add_argument("--reps", type=int, default=4)
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    eng = build_engine()
    dtype = dispatch.normalize_dtype(eng._cache_dtype)
    # one long-lived stream re-preempted for every cycle: a short
    # prompt (every start_pos is reachable exactly by +1-token
    # rounds) and a generous token budget so it never finishes
    req = Request(rid=0, prompt=[3, 1, 4, 1], max_new_tokens=200)
    eng.submit(req)

    for start in sorted(int(b) for b in args.buckets.split(",")):
        # the cycles' stream lengths run start_pos+1 .. start_pos+2R
        # (+1 token per re-admission round) — start 2R below the pow2
        # top so every cycle lands inside ONE bucket (the guard in
        # sweep_bucket re-asserts it)
        start_pos = max(len(req.prompt) + 1, start - 2 * args.reps)
        walls, bucket_key = sweep_bucket(eng, start_pos, args.reps)
        med = {c: statistics.median(w) * 1e3 for c, w in walls.items()}
        rids = {}
        for choice in CHOICES:
            os.environ["APEX_SERVE_KV_RESTORE"] = choice
            rids[choice] = ledger_mod.append_record(
                "sweep_kv_restore", backend, 0.0, args.reps,
                extra={"kv_restore_sweep": {
                    "bucket": bucket_key, "choice": choice,
                    "readmit_round_ms": round(med[choice], 4),
                    "rounds": args.reps,
                    "swap_copy_s": round(eng.swap_copy_s, 6)}},
                path=args.ledger)
        winner = min(CHOICES, key=lambda c: med[c])
        entry = {
            "op": "kv_restore", "bucket": bucket_key, "dtype": dtype,
            "backend": backend, "choice": winner,
            "ledger": rids[winner],
            "measured": {c: {"ledger": rids[c], "unit": "ms",
                             "value": round(med[c], 4)}
                         for c in CHOICES},
            "pins": {"APEX_DISPATCH": "off",
                     "APEX_SERVE_PREEMPT": "1",
                     "APEX_SERVE_KV_SWAP": "1",
                     "APEX_SERVE_KV_RESTORE": winner},
            "rung": "serving_kv_restore",
        }
        _upsert(args.table, entry)
        print(f"{bucket_key:>6}: recompute {med['recompute']:.2f} ms "
              f"vs swap {med['swap']:.2f} ms -> {winner} "
              f"[{rids[winner]}]")
    os.environ.pop("APEX_SERVE_KV_RESTORE", None)


def _upsert(table_path, entry):
    """Replace-or-append the entry for its key (corrupt lines kept
    verbatim, atomic replace)."""
    key = (entry["op"], entry["bucket"], entry["dtype"],
           entry["backend"])
    lines = []
    if os.path.exists(table_path):
        with open(table_path) as f:
            for line in f:
                try:
                    e = json.loads(line)
                    if (e.get("op"), e.get("bucket"), e.get("dtype"),
                            e.get("backend")) == key:
                        continue  # superseded
                except ValueError:
                    pass
                if line.strip():
                    lines.append(line.rstrip("\n"))
    lines.append(json.dumps(entry, sort_keys=True))
    resilience.atomic_write(table_path, "\n".join(lines) + "\n")
    dispatch._reset_for_tests()  # drop the mtime cache


if __name__ == "__main__":
    main()
