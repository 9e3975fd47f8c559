"""apexlint — AST-level invariant checker for the repo's own rules.

Eleven PRs of conventions (CLAUDE.md / PERF.md §0) are load-bearing but
were enforced only by scattered per-feature tests, and each had already
been violated at least once before a human caught it (the round-3
``APEX_LN_PALLAS`` label-drift bug, the round-4 no-op-knob audit
findings, the round-5 import-time ``APEX_XENT_ROW_BLOCK`` read). This
package mechanizes them as one tier-1 gate — the measured-not-asserted
discipline the kernels get, applied to the code itself.

Rules (each grounded in an already-committed convention):

========  ==========================================================
APX001    no import-time ``os.environ``/``os.getenv`` in
          ``apex_tpu/`` — env knobs are read at TRACE time (the
          round-5 ``APEX_XENT_ROW_BLOCK`` class)
APX002    ``APEX_*`` reads outside tests go through the
          ``dispatch.tiles.env_int/env_choice/env_float/env_flag``
          one-home parsers, or the knob's designated-reader
          allowlist entry (``config.DESIGNATED_READERS``)
APX003    knob registry cross-check — the set of ``APEX_*`` names
          used anywhere in non-test code (python env ops) must
          exactly equal the docs/API.md
          knob table plus ``ledger.INFRA_KNOB_PREFIXES`` coverage
          (the round-4 no-op-knob audit, whole-namespace)
APX004    timing hygiene — no naked ``time.time()`` /
          ``perf_counter()`` / ``block_until_ready`` in
          ``benchmarks/``: the PERF.md §0 timing rules have ONE
          implementation (``apex_tpu.telemetry.tracing``)
APX005    citation resolver — every ``reference …py:line``
          docstring citation resolves against ``/root/reference``
          (file exists, line in range): ``check_api_parity``
          upgraded from presence to validity
APX006    stdlib-only enforcement — modules that claim it
          (``config.STDLIB_ONLY_CLAIMED``) must not import
          jax/numpy at module level, checked transitively over the
          in-package import graph
APX000    pragma hygiene — every ``# apexlint: disable=`` pragma
          names known rules AND states a reason
========  ==========================================================

Suppression is inline and itself accounted for (counted, reported,
and surfaced in ``--json``)::

    something_flagged()  # apexlint: disable=APX004 — why this is ok
    # apexlint: disable=APX002 — reason          (on the line above)
    # apexlint: disable-file=APX004 — whole-file reason

Run as a tier-1 test (tests/test_apexlint.py) and as a CLI::

    python -m tools.apexlint [--json] [--rule APXnnn] [--root DIR]

Exit status follows the checker convention (check_bench_labels):
0 clean, 1 findings, 2 crash-as-finding (a linter that dies must not
pass silently). Stdlib-only and import-free: every fact it needs from
the repo (INFRA_KNOB_PREFIXES, the knob table, the import graph) is
read via ``ast``/text, never by importing ``apex_tpu``.
"""

from tools.apexlint.core import Report, run  # noqa: F401
from tools.apexlint.cli import main  # noqa: F401
