"""What the accounts of a serve run share: the span ring cut to the
window (``perf/span_ring.py``), joined the two ways no single span is.

A request's first-token wait, taken apart inside the program. The engine
records ``request.queue`` (``enqueue_wall`` to ``admitted_wall``) and
``request.prefill`` (to ``first_token_wall``, stamped at the prefill's
fetch); the caller has the token when that round returns, which is the
``t1`` of the one ``engine.round`` that holds the ``request.prefill``'s
end. Queue + prefill + held is the harness's ``first`` - ``sent`` less
the client's stretch before ``engine.step`` and one clock read after it.

A round, taken apart by kind. Decode-only rounds are one kind; a round
that prefilled is of the kind of its dispatches' ``trunk_rows`` (the
``prefill.pack`` children, in order). A round OVERRAN if it took more
than ``OVERRUN`` times its kind's median and the kind has ``MIN_ROUNDS``
rounds; what it took over the median is lost time, and the child span
whose excess over ITS median is largest holds it. The round's ``cpu_s``
(the engine thread's CPU seconds, ``time.thread_time``) tells a pause of
the program (the thread ran) from one of a lock, the runtime or the
machine (it did not); on the chip's host that clock ticks at 10 ms, so
it says this of a pause of tens of milliseconds and nothing of a usual
round's 1.5 ms host part.

Every function returns None from a record without rounds, a program
without the recorder, or a ring that lost the window.

``python3 perf/span_account.py --workload <cell> --seed <n> --seconds
<s> [--trace 0|1]`` runs ``perf/run.py`` in THIS process (the ring is
the process's; nothing outside it can read it) with ``--record``, and
prints the run's account as one JSON line after the run's own. It is a
builder's tool; the driver never calls it.
"""

import bisect
import collections
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:     # run as a script: perf/ is first, not the root
    sys.path.insert(0, ROOT)

from perf.span_ring import _tree, serve_window  # noqa: E402

WAITS = ("prefill.dispatch", "prefill.fetch", "decode.dispatch",
         "decode.fetch")
OVERRUN, MIN_ROUNDS = 2.0, 5
DECODE_ONLY = "decode"


def requests(record):
    """``[(request.queue, request.prefill, engine.round)]`` for the
    requests that entered the engine inside the window and finished
    (``queue_wait_p95_ms``'s population, the harness's ``ttft_mean_ms``
    too); the round is the one that holds the prefill's end, None if the
    ring has none."""
    cut = serve_window(record)
    if cut is None:
        return None
    t_open, t_close, records = cut
    prefills = {r.rid: r for r in records if r.name == "request.prefill"}
    in_order = [r for r, _ in _tree(records, "engine.round")]
    starts = [r.t0 for r in in_order]
    out = []
    for queue in records:
        if queue.name != "request.queue" \
                or not t_open <= queue.t0 <= t_close:
            continue
        prefill = prefills.get(queue.rid)
        if prefill is None or prefill.t0 != queue.t1:
            continue
        k = bisect.bisect_right(starts, prefill.t1) - 1
        held_in = in_order[k] \
            if k >= 0 and prefill.t1 <= in_order[k].t1 else None
        out.append((queue, prefill, held_in))
    return out


def first_token_parts(record, joined=None):
    """``{"queue": [...], "prefill": [...], "held": [...]}`` in seconds,
    one entry a request of :func:`requests`; None where a request's
    round is not in the ring (the parts would not be one population)."""
    if joined is None:
        joined = requests(record)
    if joined is None or any(rnd is None for _, _, rnd in joined):
        return None
    return {"queue": [q.t1 - q.t0 for q, _, _ in joined],
            "prefill": [p.t1 - p.t0 for _, p, _ in joined],
            "held": [rnd.t1 - p.t1 for _, p, rnd in joined]}


def mean_ms(values):
    return 1e3 * statistics.fmean(values) if values else 0.0


Round = collections.namedtuple(
    "Round", ("record", "kind", "seconds", "kids", "waited"))


def rounds(record):
    """The window's ``engine.round``s that did something, oldest first,
    as ``Round(record, kind, seconds, {child name: seconds}, seconds in
    the four waits)``."""
    cut = serve_window(record)
    if cut is None:
        return None
    t_open, t_close, records = cut
    trunks = collections.defaultdict(list)    # in the dispatches' order
    for r in records:
        if r.name == "prefill.pack":
            trunks[r.parent].append(r.attrs["trunk_rows"])
    out = []
    for r, kids in _tree(records, "engine.round"):
        if r.t0 < t_open or r.t1 > t_close:
            continue
        if r.attrs.get("prefilled"):
            kind = tuple(trunks[r.id])
        elif r.attrs.get("decoded"):
            kind = DECODE_ONLY
        else:
            continue
        out.append(Round(r, kind, r.t1 - r.t0, kids,
                         sum(kids[w] for w in WAITS)))
    return out or None


def overruns(record):
    """``(rounds, {kind: median seconds}, [Round that overran])``."""
    every = rounds(record)
    if every is None:
        return None
    by_kind = collections.defaultdict(list)
    for rnd in every:
        by_kind[rnd.kind].append(rnd.seconds)
    medians = {kind: statistics.median(took)
               for kind, took in by_kind.items()}
    over = [rnd for rnd in every
            if len(by_kind[rnd.kind]) >= MIN_ROUNDS
            and rnd.seconds > OVERRUN * medians[rnd.kind]]
    return every, medians, over


def overrun_share(record, found=None):
    """``round_overrun_share``: the window's share lost to the rounds
    that overran, each counted for what it took over its kind's median."""
    found = found or overruns(record)
    if found is None:
        return None
    _, medians, over = found
    window = record["rounds"][-1]["t1"] - record["rounds"][0]["t0"]
    return 100.0 * sum(r.seconds - medians[r.kind] for r in over) / window


def _parts(rnd):
    # a round's children by name; ``engine.round`` stands for its own
    # stretches between them (a request's spans are stamped after the
    # fact and lie anywhere)
    kids = {name: took for name, took in rnd.kids.items()
            if not name.startswith("request.")}
    return dict(kids, **{"engine.round": rnd.seconds - sum(kids.values())})


def holder(rnd, peers):
    """``(child span name, its excess seconds)``: the part of an overrun
    round that took the most over its median among ``peers``, the rounds
    of its kind."""
    usual = [_parts(p) for p in peers]
    excess = {name: took - statistics.median(u.get(name, 0.0) for u in usual)
              for name, took in _parts(rnd).items()}
    name = max(excess, key=excess.get)
    return name, excess[name]


def describe(record):
    """One run's account, as plain numbers: the three parts of the
    first-token wait beside the harness's own mean, who queued and why,
    every kind of round, every overrun round with the span that held it
    and the thread's CPU, and the same for the client's stretches
    between two rounds."""
    joined, found = requests(record), overruns(record)
    parts = first_token_parts(record, joined)
    if parts is None or found is None:
        return None
    every, medians, over = found
    waits = [r["first"] - r["sent"] for r in record["requests"]
             if r["finish"] is not None]
    t_open = record["rounds"][0]["t0"]
    out = {"requests": len(joined),
           "ttft_mean_ms": mean_ms(waits),
           "queue_wait_mean_ms": mean_ms(parts["queue"]),
           "prefill_wait_mean_ms": mean_ms(parts["prefill"]),
           "token_held_mean_ms": mean_ms(parts["held"])}
    out["unaccounted_ms"] = out["ttft_mean_ms"] - sum(
        out[k] for k in ("queue_wait_mean_ms", "prefill_wait_mean_ms",
                         "token_held_mean_ms"))
    queued = [q for q, _, _ in joined if (q.attrs or {}).get("rounds")]
    out["prompt_tokens_mean"] = statistics.fmean(
        q.attrs["prompt"] for q, _, _ in joined) if joined else 0.0
    out["queued"] = len(queued)
    out["queued_wait_mean_ms"] = mean_ms([q.t1 - q.t0 for q in queued])
    out["blocked"] = dict(collections.Counter(
        q.attrs["blocked"] for q in queued))
    out["kinds"] = {str(kind): {
        "rounds": sum(1 for r in every if r.kind == kind),
        "median_ms": 1e3 * median} for kind, median in medians.items()}
    out["overrun_share"] = overrun_share(record, found)
    out["overruns"] = []
    for rnd in over:
        name, excess = holder(rnd, [p for p in every if p.kind == rnd.kind])
        cpu_s = rnd.record.attrs.get("cpu_s")
        out["overruns"].append({
            "kind": str(rnd.kind), "at_s": rnd.record.t0 - t_open,
            "ms": 1e3 * rnd.seconds, "median_ms": 1e3 * medians[rnd.kind],
            "held_by": name, "excess_ms": 1e3 * excess,
            "host_ms": 1e3 * (rnd.seconds - rnd.waited),
            "cpu_ms": None if cpu_s is None else 1e3 * cpu_s})
    # the client's: from one round's return to the next one's entry
    gaps = [(b.record.t0 - a.record.t1, a.record.t1 - t_open)
            for a, b in zip(every, every[1:])]
    if len(gaps) >= MIN_ROUNDS:
        between = statistics.median(g for g, _ in gaps)
        out["between_rounds"] = {
            "median_ms": 1e3 * between,
            "overruns": [{"at_s": at, "ms": 1e3 * g} for g, at in gaps
                         if g > max(OVERRUN * between, 1e-3)]}
    return out


def main(argv=None):
    """``perf/run.py``'s own arguments, handed on with its ``--record``."""
    import json
    import tempfile

    from perf import run

    argv = list(sys.argv[1:] if argv is None else argv)
    with tempfile.TemporaryDirectory() as folder:
        path = os.path.join(folder, "record.json")
        run.main(argv + ["--record", path])
        with open(path) as fh:
            record = json.load(fh)
    print(json.dumps({"account": describe(record)}), flush=True)


if __name__ == "__main__":
    main()
