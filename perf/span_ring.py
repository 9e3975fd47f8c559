"""What the span metrics share: the program's span ring
(``apex_tpu.telemetry.spans``: one process, same ``time.perf_counter``
as the harness) cut to the run's window. Every function returns None
where there is nothing sound to read: a program without the recorder
(a parent commit), a recorder switched off, or a ring that no longer
holds the whole window."""

import collections


def _spans():
    try:
        from apex_tpu.telemetry import spans
    except ImportError:   # the program has no recorder
        return None
    return spans


def _tree(records, root):
    """``[(root record, {child name: seconds})]`` for the records named
    ``root``, oldest first; children are found by parent id."""
    kids = collections.defaultdict(lambda: collections.defaultdict(float))
    for r in records:
        if r.parent is not None:
            kids[r.parent][r.name] += r.t1 - r.t0
    roots = sorted((r for r in records if r.name == root),
                   key=lambda r: r.t0)
    return [(r, kids[r.id]) for r in roots]


def serve_window(record):
    """``(t_open, t_close, records since t_open)`` of a serve run: the
    window is the harness's first round's start to its last round's end;
    the records run on past it, because a request's spans are stamped
    when it finishes, which may be in the drain."""
    spans = _spans()
    if spans is None or not record.get("rounds"):
        return None
    t_open, t_close = record["rounds"][0]["t0"], record["rounds"][-1]["t1"]
    if not spans.covers(t_open):
        return None
    return t_open, t_close, spans.snapshot(t_open)


def decode_rounds(record):
    """``[(seconds of the round, seconds the host waited on the device)]``
    for the window's rounds that decoded and prefilled nothing: the
    wait is ``decode.dispatch`` + ``decode.fetch``."""
    cut = serve_window(record)
    if cut is None:
        return None
    t_open, t_close, records = cut
    out = [(r.t1 - r.t0, kids["decode.dispatch"] + kids["decode.fetch"])
           for r, kids in _tree(records, "engine.round")
           if r.t0 >= t_open and r.t1 <= t_close
           and not r.attrs.get("prefilled") and r.attrs.get("decoded")]
    return out or None


def token_walls(record):
    """``{rid: [wall of each token]}`` for the tokens the engine handed
    out inside the window, from ``engine.round``'s ``emitted``."""
    cut = serve_window(record)
    if cut is None:
        return None
    t_open, t_close, records = cut
    walls = collections.defaultdict(list)
    for r in records:
        if r.name == "engine.round":
            for rid, n, wall in r.attrs.get("emitted", ()):
                if t_open <= wall <= t_close:
                    walls[rid] += [wall] * n
    return walls or None


def train_chunks(record):
    """``(first, steady)``: the ``trainer.chunk`` trees of the measured
    ``main`` call, i.e. the ring's last ``len(record["chunks"])`` chunks
    and the one before them (trace + lower + compile or cache load; a
    calibration call earlier in the process is older still)."""
    spans = _spans()
    n = len(record.get("chunks") or ())
    if spans is None or not n:
        return None
    chunks = _tree(spans.snapshot(), "trainer.chunk")[-(n + 1):]
    if len(chunks) != n + 1 or not spans.covers(chunks[0][0].t0):
        return None
    return chunks[0], chunks[1:]
