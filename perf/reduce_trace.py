"""From a profiler trace (``*.xplane.pb``) to numbers, with nothing but
JAX (``jax.profiler.ProfileData``). Owned by the benchmark so that every
PR reduces a trace the same way; checked on the small recorded trace
``perf/fixtures/probe_tpu_v5e.xplane.pb`` by ``perf/tests``.

What a TPU trace holds (looked at by hand, PR 24): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Ops`` has one event per executed
HLO op and ``XLA Modules`` one per program run; the plane ``/host:CPU``
has one line per host thread with the ``TraceAnnotation`` spans. Both
are in nanoseconds on one timebase; the device's runs about a
millisecond ahead of the host's (an op shows before its dispatch), which
is nothing against a window of seconds and is why gaps under two
milliseconds are not labelled.
"""

import collections
import glob
import os

from jax.profiler import ProfileData

from perf.tracing import LABELS, WINDOW

_MIN_LABELLED_GAP_NS = 2e6
# ops that only enclose others on the same line (a scan is one `while`)
_CONTAINERS = ("while", "conditional", "call")


def find_xplane(trace_dir):
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def _short(name):
    """``%fusion.2 = bf16[...] fusion(...)`` -> ``fusion.2``."""
    return name.split(" = ")[0].lstrip("%")[:80]


def _union(intervals):
    """Sorted, merged copies of ``[(start, end)]``."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def _overlap(a0, a1, b0, b1):
    return max(0.0, min(a1, b1) - max(a0, b0))


def reduce(path):
    """``{"window_s", "busy_s", "idle_share", "devices", "device_ops",
    "idle_gaps"}`` of one trace, or None when no device op is in it
    (a CPU rehearsal). ``busy_s`` is the union of the device-op intervals
    inside the traced window, averaged over the chips; ``device_ops`` the
    ten ops with most device time (seconds per chip); ``idle_gaps`` the
    idle time of the first chip by what the host was doing meanwhile."""
    space = ProfileData.from_file(path)
    devices, spans, window = {}, [], None
    for plane in space.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            line = lines.get("XLA Ops") or lines.get("XLA Modules")
            if line is not None:
                devices[plane.name] = [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in LABELS:
                        spans.append((e.start_ns,
                                      e.start_ns + e.duration_ns, e.name))
    events = [ev for evs in devices.values() for ev in evs]
    if not events:
        return None
    if window is None:
        window = (min(e[0] for e in events), max(e[1] for e in events))
    w0, w1 = window

    busy, by_op, first_merged = [], collections.Counter(), None
    for name in sorted(devices):
        inside = [(max(a, w0), min(b, w1), op)
                  for a, b, op in devices[name] if b > w0 and a < w1]
        merged = _union([(a, b) for a, b, _ in inside])
        busy.append(sum(b - a for a, b in merged))
        for a, b, op in inside:
            if not _short(op).startswith(_CONTAINERS):
                by_op[_short(op)] += b - a
        if first_merged is None:
            first_merged = merged
    n = len(devices)
    busy_ns = sum(busy) / n

    gaps = collections.Counter()
    edges = [w0] + [x for ab in first_merged for x in ab] + [w1]
    for g0, g1 in zip(edges[0::2], edges[1::2]):
        if g1 <= g0:
            continue
        label = "unlabelled"
        if g1 - g0 >= _MIN_LABELLED_GAP_NS:
            best = max(spans, default=None,
                       key=lambda s: _overlap(g0, g1, s[0], s[1]))
            if best and _overlap(g0, g1, best[0], best[1]) > 0:
                label = best[2]
        else:
            label = "gaps_under_2ms"
        gaps[label] += g1 - g0
    return {
        "window_s": (w1 - w0) / 1e9,
        "busy_s": busy_ns / 1e9,
        "idle_share": 1.0 - busy_ns / (w1 - w0),
        "devices": n,
        "device_ops": [[op, ns / n / 1e9] for op, ns in by_op.most_common(10)],
        "idle_gaps": [[label, ns / 1e9] for label, ns in gaps.most_common(10)],
    }
