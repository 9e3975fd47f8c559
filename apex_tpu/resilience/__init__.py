"""What the serving path and the checkpointer share for surviving a
failed dispatch or a killed process: a durable small-file write, the
three-word verdict of a call that returned no result, the serving
round's retry budget, and (in :mod:`apex_tpu.resilience.faults`) the
test-only fault hooks that script those failures.

* :func:`atomic_write` — tmp + fsync + rename, for a state file a
  SIGTERM must not tear.
* :func:`classify_subprocess` — ``wedged`` for a call that rode its
  whole budget, ``degraded_relay`` for one that failed, ``healthy``
  otherwise. ``serving.resilience.guarded_dispatch`` stamps it on a
  :class:`~apex_tpu.serving.resilience.DispatchFailure`; the router's
  breaker counts them.
* ``SERVE_*`` — the engine's per-round dispatch budget, its bound on
  consecutive failed rounds, and the pause between them.
* :class:`RetryPolicy` — an attempt count and the wait before the next
  one; the engine paces failed rounds with it, the router its probes
  of a dead replica (there the wait is counted in rounds).

Both modules of this package import only the stdlib.
"""

import os

HEALTHY = "healthy"
DEGRADED_RELAY = "degraded_relay"   # the call failed; the device may live
WEDGED = "wedged"                   # no result within the budget

# A decode round at the judged sizes is O(100 ms); one that rides this
# long without producing its fetch is a hung device, not a slow step
# (the budget leaves room for a compile inside the round).
SERVE_DISPATCH_TIMEOUT_S = 300   # per-round device-dispatch budget
SERVE_ROUND_ATTEMPTS = 3         # consecutive failed rounds before the
#                                  engine gives up (bounded recovery —
#                                  a dead device must not spin forever)
SERVE_ROUND_RETRY_WAIT_S = 5     # pause before re-driving a failed
#                                  round (chaos tests pin 0)


def atomic_write(path, text):
    """Durable tmp+fsync+rename text write for a small state file a
    SIGTERM/timeout must not tear. os.replace is atomic on POSIX; the
    fsync makes the rename land on bytes, not cache."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(text)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)


class RetryPolicy:
    """An attempt budget and the wait before each retry."""

    def __init__(self, attempts, retry_wait_s):
        self.attempts = max(1, int(attempts))
        self.retry_wait = int(retry_wait_s)

    def pop_wait(self):
        """The wait before the next retry."""
        return self.retry_wait


def classify_subprocess(returncode, timed_out=False):
    """Verdict for a call that produced no record to judge: a timeout
    is the wedge signature; any other failure is ``degraded_relay``."""
    if timed_out:
        return WEDGED
    if returncode == 0:
        return HEALTHY
    return DEGRADED_RELAY
