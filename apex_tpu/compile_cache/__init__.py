"""Persistent compile cache: where it lives, and whether a run used it.

A cold compile of the GPT-2-small train step or of the serving programs
takes far longer than running them, and a sealed machine keeps nothing
between runs except what is written to a directory that outlives the
process. JAX's persistent compilation cache is that directory; this
module only decides where it is and counts what it served.

Placement:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself and
  no code here touches ``jax_compilation_cache_dir`` or switches the
  cache off. Whoever runs the program placed the cache.
* unset — the cache lives at the fixed ``benchmarks/.compile_cache/`` of
  this checkout (git-ignored). The path is fixed because a cache that
  moves between runs never hits. ``APEX_COMPILE_CACHE=0`` leaves the
  checkout without a cache; it never overrides a directory placed from
  outside.

Pieces:

* :func:`activate` — place the cache as above, zero the
  min-compile-time / min-entry-size thresholds so every program lands
  in it, and start counting hits and misses via ``jax.monitoring``.
  Called by the library entries that compile the large programs
  (``examples.transformer.pretrain.main``, ``ServingEngine``) and by
  the harnesses; idempotent.
* :func:`snapshot` — ``{enabled, dir, hits, misses, warm_age_s}`` read
  from JAX's own config, stamped into every ledger record and
  ``chip_smoke.py``'s phases, so a number can prove whether it was
  taken compile-free.

Cache reuse never changes the measured program (the key is the lowered
module plus compile options; execution is identical).
"""

import functools
import glob
import os
import time

_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_MISS_EVENT = "/jax/compilation_cache/cache_misses"

# process-level counters, fed by the jax.monitoring listener
_counters = {"hits": 0, "misses": 0}


def default_dir():
    # the ONE in-repo path derivation lives in telemetry.ledger
    # (stdlib-only module — no import cycle, no backend touch)
    from apex_tpu.telemetry.ledger import repo_root

    return os.path.join(repo_root(), "benchmarks", ".compile_cache")


def target_dir():
    """Where :func:`activate` leaves the cache (module docstring), or
    None when this process gets none. Reads the environment only, so a
    launcher parent can report it without importing jax."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    if os.environ.get("APEX_COMPILE_CACHE") == "0":
        return None
    return default_dir()


def cache_dir():
    """The directory JAX's persistent cache is using, or None when the
    cache is off — read from JAX's own config, so it is the truth
    whether the environment or :func:`activate` placed it."""
    import jax

    if not jax.config.jax_enable_compilation_cache:
        return None
    return jax.config.jax_compilation_cache_dir or None


def _on_event(event, **kw):
    if event == _HIT_EVENT:
        _counters["hits"] += 1
    elif event == _MISS_EVENT:
        _counters["misses"] += 1


@functools.cache
def _listen():
    """Register the hit/miss listener, once per process."""
    import jax

    jax.monitoring.register_event_listener(_on_event)


def activate():
    """Place the persistent cache (module docstring) and count its
    traffic. Returns True when the cache is on. Safe to call many
    times and before backend init (config updates touch no device)."""
    import jax

    target = target_dir()
    if target is None:
        return enabled()
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        os.makedirs(target, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", target)
    # zero the thresholds: every program must land in the cache whatever
    # its compile time or size — the point is that the NEXT process
    # skips the compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _listen()
    return enabled()


def enabled():
    """True when JAX's persistent cache has a directory in this
    process."""
    return cache_dir() is not None


def _newest_entry_age_s(directory):
    """Age (seconds) of the newest ``*-cache`` entry in ``directory`` —
    how long ago the cache was last written. None when it is empty or
    missing."""
    try:
        entries = glob.glob(os.path.join(directory, "*-cache"))
        if not entries:
            return None
        newest = max(os.path.getmtime(e) for e in entries)
        return max(0.0, round(time.time() - newest, 1))
    except OSError:
        return None


def snapshot():
    """The compile-cache telemetry block: ``{enabled, dir, hits, misses,
    warm_age_s}``. Counters are process-wide (every jitted program in
    the process, not just the measured one) and start at
    :func:`activate`."""
    directory = cache_dir()
    return {
        "enabled": directory is not None,
        "dir": directory,
        "hits": _counters["hits"],
        "misses": _counters["misses"],
        "warm_age_s": _newest_entry_age_s(directory) if directory
        else None,
    }


def _reset_for_tests():
    """Zero the counters (test isolation only)."""
    _counters["hits"] = _counters["misses"] = 0
