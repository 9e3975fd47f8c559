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
                 "__graft_entry__.py")
# citation-bearing docstrings (APX005) live everywhere code does
SCOPE_CITED = ("apex_tpu", "benchmarks", "tools",
               "__graft_entry__.py")

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
    ("apex_tpu/telemetry/costs.py", "APEX_COST_ANALYSIS",
     "tri-state hard-on/hard-off/unset-follows-harness"),
    ("apex_tpu/optimizers/fused_lamb.py", "APEX_LAMB_IMPL",
     "validated raise on unknown values (committed semantics, "
     "test-pinned; predates env_choice)"),
    ("apex_tpu/transformer/pipeline_parallel/schedules.py",
     "APEX_PP_IMPL",
     "merged with per-call impl= then validated with a raise — a "
     "typo'd knob must not pass silently"),
    # harness-side owners: the profile drivers are the arming +
    # label-pinning sites the records are stamped from
    ("benchmarks/_knobs.py", "APEX_REMAT",
     "the documented one-home resolver for the step-harness pins "
     "(validated raise)"),
    ("benchmarks/_knobs.py", "APEX_ATTN_IMPL",
     "one-home resolver; set_default_impl validates with a raise"),
    ("benchmarks/_knobs.py", "APEX_LN_PALLAS",
     "one-home resolver; tri-state 1/0/unset"),
    ("benchmarks/_knobs.py", "APEX_FUSED_LM_HEAD",
     "one-home resolver; tri-state 1/0/unset"),
    ("benchmarks/profile_gpt.py", "APEX_CKPT_DIR",
     "durability arming path, consumed host-side before any trace "
     "(checkpoint.py owns the other APEX_CKPT_* semantics)"),
)

# ---------------------------------------------------------------------------
# APX006 — modules whose docstrings claim stdlib-only (module-level
# imports; jax in function bodies is the documented lazy pattern)
# ---------------------------------------------------------------------------

STDLIB_ONLY_CLAIMED = (
    "apex_tpu/resilience/",
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
