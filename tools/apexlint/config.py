"""Rule scopes and allowlists. Every entry here is itself policed:
a DESIGNATED_READERS row no raw read matches, or a
STDLIB_ONLY_CLAIMED path that does not exist, is a finding (the
check_api_parity stale-allowlist discipline — config rot must not
accumulate silently).
"""

# ---------------------------------------------------------------------------
# scopes (repo-relative; dirs are walked recursively, .py only)
# ---------------------------------------------------------------------------

SCOPE_PKG = ("apex_tpu",)
SCOPE_BENCH = ("benchmarks",)
# "outside tests": the shipped package, the harnesses, and the tools —
# examples/ are reference-ported torch demos, out of knob scope
SCOPE_NONTEST = ("apex_tpu", "benchmarks", "tools",
                 "bench.py", "__graft_entry__.py")
# citation-bearing docstrings (APX005) live everywhere code does
SCOPE_CITED = ("apex_tpu", "benchmarks", "tools",
               "bench.py", "__graft_entry__.py")

SHELLS = ("benchmarks/run_all_tpu.sh", "benchmarks/probe_and_collect.sh")
# APX004 monotonic-home extension (ISSUE 16): the only non-benchmark
# files allowed to call time.monotonic/monotonic_ns — the beat stamp,
# its one other emitter, and the supervisor that ages beats
MONOTONIC_HOMES = (
    "apex_tpu/telemetry/flight.py",
    "apex_tpu/telemetry/tracing.py",
    "apex_tpu/resilience/flight_watch.py",
)
API_MD = "docs/API.md"
LEDGER_PY = "apex_tpu/telemetry/ledger.py"
KNOB_TABLE_BEGIN = "<!-- apexlint: knob-table begin -->"
KNOB_TABLE_END = "<!-- apexlint: knob-table end -->"
REFERENCE_ROOT = "/root/reference"

# the one-home env parsers (dispatch/tiles.py + the lifecycle delegate)
# — a knob read THROUGH these is never a raw read, wherever it happens
ENV_HELPERS = frozenset(
    {"env_int", "env_nonneg_int", "env_choice", "env_float",
     "env_flag", "env_ms"})

# ---------------------------------------------------------------------------
# APX002 — designated readers: (file, knob-or-prefix*, why this file is
# the knob's one home). Raw reads anywhere else are findings.
# ---------------------------------------------------------------------------

DESIGNATED_READERS = (
    # knob owners inside the package: semantics the typed helpers can't
    # express (paths, tri-states, present-vs-absent checks)
    ("apex_tpu/dispatch/__init__.py", "APEX_DISPATCH",
     "the dispatch gate itself: present-but-off semantics"),
    ("apex_tpu/dispatch/__init__.py", "APEX_DISPATCH_TABLE",
     "table-path override; path, not a typed value"),
    ("apex_tpu/compile_cache/__init__.py", "APEX_COMPILE_CACHE",
     "=0 leaves the checkout without a cache; read with JAX's own "
     "JAX_COMPILATION_CACHE_DIR in the one placement function"),
    ("apex_tpu/checkpoint.py", "APEX_CKPT_*",
     "durability knobs: retention 0 is legal (env_int is positive-only) "
     "and queue/async resolve once at ctor time"),
    ("apex_tpu/telemetry/ledger.py", "APEX_TELEMETRY_LEDGER",
     "ledger path override — the write-site home"),
    ("apex_tpu/telemetry/ledger.py", "APEX_FAULT_PLAN",
     "tamper-evident stamp: present-vs-absent, value hashed into ids"),
    ("apex_tpu/telemetry/metrics.py", "APEX_TELEMETRY_PATH",
     "metrics sink path"),
    ("apex_tpu/resilience/faults.py", "APEX_FAULT_PLAN",
     "the injection engine: reads the plan json/path itself"),
    ("apex_tpu/parallel/multiproc.py", "APEX_TPU_COORDINATOR",
     "multi-process launcher wiring (addresses, not typed knobs)"),
    ("apex_tpu/parallel/multiproc.py", "APEX_TPU_NUM_PROCESSES",
     "launcher wiring"),
    ("apex_tpu/parallel/multiproc.py", "APEX_TPU_PROCESS_ID",
     "launcher wiring"),
    ("apex_tpu/parallel/collectives.py", "APEX_GRAD_COMPRESS",
     "present-but-empty/off is an explicit off-pin that also blocks "
     "the table consult (PR 8) — richer than env_choice"),
    ("apex_tpu/parallel/collectives.py", "APEX_HIER_ALLREDUCE",
     "presence-sensitive tri-state with warn-once on non-1/0 (PR 8)"),
    ("apex_tpu/contrib/fmha/fmha.py", "APEX_FMHA_DROPOUT",
     "validated raise at first use: the escape hatch is an explicit "
     "request, not a preference"),
    ("apex_tpu/resilience/__init__.py", "APEX_BENCH_*",
     "the §6 timeout-envelope home; zero is a legal value here (chaos "
     "pins RETRY_WAIT=0) which the positive-only env_int cannot "
     "express"),
    ("apex_tpu/resilience/probe.py", "APEX_PROBE_STATE",
     "CLI state-path default (path, not a typed value)"),
    ("apex_tpu/resilience/manifest.py", "APEX_PROBE_STATE",
     "CLI --probe-state default (probe_and_collect.sh exports it per "
     "round)"),
    ("apex_tpu/telemetry/costs.py", "APEX_COST_ANALYSIS",
     "tri-state hard-on/hard-off/unset-follows-harness"),
    ("apex_tpu/optimizers/fused_lamb.py", "APEX_LAMB_IMPL",
     "validated raise on unknown values (committed semantics, "
     "test-pinned; predates env_choice)"),
    ("apex_tpu/transformer/pipeline_parallel/schedules.py",
     "APEX_PP_IMPL",
     "merged with per-call impl= then validated with a raise — a "
     "typo'd knob must not pass silently"),
    # harness-side owners: bench.py / the profile drivers are the
    # arming + label-pinning sites the records are stamped from
    ("benchmarks/_knobs.py", "APEX_REMAT",
     "the documented one-home resolver for the step-harness pins "
     "(validated raise)"),
    ("benchmarks/_knobs.py", "APEX_ATTN_IMPL",
     "one-home resolver; set_default_impl validates with a raise"),
    ("benchmarks/_knobs.py", "APEX_LN_PALLAS",
     "one-home resolver; tri-state 1/0/unset"),
    ("benchmarks/_knobs.py", "APEX_FUSED_LM_HEAD",
     "one-home resolver; tri-state 1/0/unset"),
    ("bench.py", "APEX_CKPT_DIR",
     "durability arming path, consumed host-side before any trace "
     "(checkpoint.py owns the other APEX_CKPT_* semantics)"),
    ("bench.py", "APEX_BENCH_BASELINE",
     "baseline-store path redirect (the chaos-test hook)"),
    ("bench.py", "APEX_ATTN_IMPL",
     "label pin: the scored line stamps the raw pin it ran under "
     "(_knobs.apply_dispatch_knobs already validated it)"),
    ("bench.py", "APEX_LN_PALLAS",
     "label pin (tri-state mirror of _knobs)"),
    ("benchmarks/profile_gpt.py", "APEX_CKPT_DIR",
     "durability arming path (same pattern as bench.py)"),
    ("benchmarks/warm_cache.py", "APEX_COLLECT_MANIFEST",
     "manifest-path handoff from probe_and_collect.sh"),
    # flight recorder + supervisor (ISSUE 16)
    ("apex_tpu/telemetry/flight.py", "APEX_FLIGHT_*",
     "the recorder itself: dir path + row label, read per-beat (unset "
     "= disabled is the whole zero-cost contract — a typed helper "
     "would be a second home)"),
    ("apex_tpu/telemetry/flight.py", "APEX_BENCH_ATTEMPT",
     "beats auto-stamp the watchdog's attempt index; raw int parse "
     "because a beat must NEVER raise on a malformed value"),
    ("apex_tpu/resilience/flight_watch.py", "APEX_FLIGHT_*",
     "supervisor clock thresholds: zero and fractional seconds are "
     "legal (chaos tests pin seconds-scale silence), which the "
     "positive-int helpers cannot express; plus the pool-restore "
     "marker handoff from run_all_tpu.sh"),
    ("tools/window_report.py", "APEX_FLIGHT_DIR",
     "CLI --flight default (probe_and_collect.sh exports it per "
     "round) — path, not a typed value"),
)

# ---------------------------------------------------------------------------
# APX006 — modules whose docstrings claim stdlib-only (module-level
# imports; jax in function bodies is the documented lazy pattern)
# ---------------------------------------------------------------------------

STDLIB_ONLY_CLAIMED = (
    "apex_tpu/resilience/",
    "apex_tpu/telemetry/flight.py",
    "apex_tpu/dispatch/tiles.py",
    "apex_tpu/dispatch/__init__.py",
    "apex_tpu/serving/scheduler.py",
    "apex_tpu/serving/lifecycle.py",
    "apex_tpu/serving/speculative.py",
    "apex_tpu/serving/prefix_cache.py",
    "apex_tpu/serving/router.py",
    "apex_tpu/compile_cache/__init__.py",
    "apex_tpu/telemetry/ledger.py",
    "apex_tpu/telemetry/costs.py",
)

STDLIB_DENYLIST = frozenset({
    "jax", "jaxlib", "numpy", "np", "flax", "optax", "orbax",
    "ml_dtypes", "chex", "torch", "scipy", "pandas", "absl",
})
