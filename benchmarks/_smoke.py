"""Shared smoke-mode switch for the benchmark harnesses.

Call :func:`smoke_mode` BEFORE any jax.numpy / backend-touching import:
when the given env var is "1" it holds the process to the CPU backend
(``jax.config.update("jax_platforms", "cpu")``, which works whether or
not ``JAX_PLATFORMS`` is set), so a harness can walk its control flow at
tiny sizes without a chip.

It also activates the persistent compile cache
(``apex_tpu.compile_cache``) for harnesses whose programs are not built
by a library entry that already does.
"""

import jax

from apex_tpu import compile_cache


def smoke_mode(env_var):
    """True when ``env_var`` (or the generic ``APEX_BENCH_SMOKE``) is
    "1"; also forces the CPU backend in that case, and activates the
    persistent compile cache."""
    from apex_tpu.dispatch.tiles import env_flag

    on = env_flag(env_var) or env_flag("APEX_BENCH_SMOKE")
    if on:
        jax.config.update("jax_platforms", "cpu")
    compile_cache.activate()
    return on
