"""The program's one span recorder: a named stretch of host time, the
span that caused it, and one identifier per request.

    with spans.span("decode.fetch"):
        next_toks = np.asarray(next_toks)

A span is stamped on ``time.perf_counter`` (the clock every harness in
this repo keeps) and, while a ``jax.profiler`` session runs, is also a
``jax.profiler.TraceAnnotation`` of the same name: it then sits on the
``/host:CPU`` plane of the xplane, in the timebase of the device's
``XLA Ops``. With no session the annotation is skipped. On exit one
record ``(id, parent, name, t0, t1, rid, attrs)`` joins a bounded
in-memory ring; ``parent`` is the id of the span open on the SAME thread
(``serving.resilience.guarded_dispatch`` runs its closure on a watchdog
thread, whose spans are therefore roots). Counts ride as attributes of
the span at whose boundary they are known (``span.set(...)`` before it
closes); there is no counter API, no exporter and no sink: read the ring
in the process (:func:`snapshot`) or open the profiler trace.

The ring holds ``CAPACITY`` = 2**17 records: a 51 s window at ten times
PR 24's round rate (~10 rounds/s x ~10 spans). The fastest cell since
(``serve-mimo-decode``, PR 27) makes 33 rounds/s x ~7 spans (6 a decode
round, 11 with a prefill, 3 a finished request) = ~240 records/s: a 51 s
window, a 40 s drain and the ramp before them are ~25,000 records, a
fifth of the ring, and ``covers(t_open)`` held there (every
``program_span`` metric of its traced run read). At that cell's 8.4 ms
byte floor (119 rounds/s) it would be ~80,000: still inside. PR 35 added
attributes, not records (``cpu_s`` on the round, ``stopped``,
``rounds`` and ``blocked``; ``attn_impl``, ``evicted`` and a family's
unread page count went), so the rate stands. A record is a
7-tuple of two floats, two ints, a shared name and an optional dict:
200 bytes bare, ~410 with one attribute, ~460 with three
(``sys.getsizeof`` over a served trace, CPython 3.12). ``engine.round``
is the heavy one: ~650 bytes of counts and ~100 more for every lane in
``emitted``, so ~7 kB at that cell's 64 lanes and ~9 kB for the six
records of its decode round, ~1.5 kB a record and ~300 kB/s (``cpu_s``
is one float of those 9 kB). The 25,000
records of a run are ~40 MB; a full ring there is bounded by ~200 MB
(~55 MB at GPT-2's 16 lanes), and a day-long server holds its last
2**17 spans, nine minutes at that rate, no more. What fell off is
counted in ``dropped()``, and :func:`covers` says whether everything
since a given stamp is still held, so that a reader returns nothing
rather than a number from a truncated window.

``set_enabled(False)`` makes :func:`span` return one shared null context
and :func:`record` return at once (measured per span, PERF.md section 6).
It is a function, not an environment variable: an operator switches it
from the process, and a builder measures on against off in one process.

stdlib only; ``jax.profiler`` is imported at the first span.
"""

import collections
import itertools
import threading
import time

CAPACITY = 2 ** 17

Record = collections.namedtuple(
    "Record", ("id", "parent", "name", "t0", "t1", "rid", "attrs"))

_ring = collections.deque(maxlen=CAPACITY)
_ids = itertools.count(1)
_local = threading.local()
_enabled = True
_appended = 0        # records ever appended (dropped = appended - held)
_lost_until = 0.0    # latest end stamp among the records that fell off
_annotation = None   # jax.profiler.TraceAnnotation, at the first span


def _stack():
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


def _append(rec):
    # plain tuples in the ring (a namedtuple costs 0.4 us to build);
    # snapshot() names the fields
    global _appended, _lost_until
    if len(_ring) == _ring.maxlen and _ring[0][4] > _lost_until:
        _lost_until = _ring[0][4]
    _ring.append(rec)
    _appended += 1


class _Span:
    __slots__ = ("name", "rid", "attrs", "id", "parent", "t0", "_ann",
                 "_stack")

    def __init__(self, name, rid, attrs):
        self.name, self.rid, self.attrs = name, rid, attrs

    def set(self, **attrs):
        """Attributes known only inside the span (what a round did)."""
        if self.attrs is None:
            self.attrs = attrs
        else:
            self.attrs.update(attrs)

    def __enter__(self):
        global _annotation
        if _annotation is None:
            from jax.profiler import TraceAnnotation as _annotation
        try:
            stack = _local.stack
        except AttributeError:
            stack = _local.stack = []
        self._stack = stack
        self.parent = stack[-1] if stack else None
        self.id = next(_ids)
        stack.append(self.id)
        self._ann = None
        if _annotation.is_enabled():    # a profiler session is running
            self._ann = _annotation(self.name)
            self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self._stack.pop()
        _append((self.id, self.parent, self.name, self.t0, t1, self.rid,
                 self.attrs))
        return False


class _Null:
    """What :func:`span` returns while the recorder is off."""
    __slots__ = ()

    def set(self, **attrs):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL = _Null()


def span(name, rid=None, **attrs):
    """A context manager around one named stretch of host time."""
    if not _enabled:
        return _NULL
    return _Span(name, rid, attrs or None)


def record(name, t0, t1, rid=None, **attrs):
    """A span stamped after the fact, from walls the program kept (a
    request's queue, prefill and decode stretches). No annotation: the
    profiler cannot be told of the past."""
    if _enabled:
        stack = _stack()
        _append((next(_ids), stack[-1] if stack else None, name, t0, t1,
                 rid, attrs or None))


def enabled():
    """Whether spans are recorded: a caller whose ATTRIBUTES cost host
    work asks before computing them."""
    return _enabled


def set_enabled(on):
    """Switch the recorder (default on); returns what it was."""
    global _enabled
    was, _enabled = _enabled, bool(on)
    return was


def snapshot(t0=None, t1=None):
    """The held records that overlap ``[t0, t1]`` (either end open), in
    the order they closed."""
    return [Record._make(r) for r in list(_ring)
            if (t0 is None or r[4] >= t0) and (t1 is None or r[3] <= t1)]


def covers(t):
    """Whether every record that ended at or after ``t`` is still held."""
    return t > _lost_until or _appended == len(_ring)


def dropped():
    """Records that fell off the ring since the last :func:`clear`."""
    return _appended - len(_ring)


def clear(capacity=CAPACITY):
    """A new, empty ring (tests; a reader never needs to)."""
    global _ring, _appended, _lost_until
    _ring = collections.deque(maxlen=capacity)
    _appended, _lost_until = 0, 0.0
