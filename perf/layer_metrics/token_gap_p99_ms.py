"""99th percentile of every gap between consecutive tokens of one
request, over the tokens the engine handed out inside the window (the
wall of the fetch that brought each, from ``engine.round``'s
``emitted``). A stall, or a prefill round that makes the other lanes
wait, shows here and in no per-request mean. Program spans."""

from perf.span_ring import token_walls
from perf.stats import percentile


def read(record):
    walls = token_walls(record)
    if walls is None:
        return None
    gaps = [1e3 * (b - a) for ws in walls.values()
            for a, b in zip(ws, ws[1:])]
    return percentile(gaps, 99)
