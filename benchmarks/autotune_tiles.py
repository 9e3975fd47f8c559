"""Per-shape Pallas TILE autotuner: the kernel-geometry rung below
``autotune_steps.py``.

Step-level A/Bs pick the IMPL per shape; this driver picks the tile
geometry WITHIN the chosen kernel family — the block sizes every Pallas
kernel previously asserted from its VMEM heuristic (the
measured-dispatch rule one level down, ISSUE 5). TPU programs are
acutely tile-sensitive, and a tile candidate measures in seconds, so a
flaky §6 relay window converts into committed wins far more reliably
here than at step level.

One budgeted pass over ``sweep_groups``: per (op family, shape), the
legal candidate set from the shared tile model
(``apex_tpu.dispatch.tiles.candidates`` — a sweep can never submit a
tile that fails to lower), each measured in its own timeoutable
subprocess (``--child``: Tracer-timed K-scan of just that kernel — fwd+bwd for
the training families, fwd for the inference-only decode family —
ledger-flushed), best-of ``--repeats``, and the winner lands as
the ``params`` payload of the dispatch-table entry for that key —
citing the ledger record that measured it (``tools/
check_bench_labels.py`` check 4 validates payload legality, citation
and pins in tier-1).

Window discipline (same contract as autotune_steps):

* **budgeted** — a global ``--budget-s`` stops launching candidates
  when spent and LOUDLY names every dropped group (no silent caps);
  per-child timeouts from the resilience §6 envelope.
* **resumable** — a group whose table entry already carries a params
  payload with a resolving ledger id is skipped; re-run to continue.
* **table-blind** — every child runs ``APEX_DISPATCH=off`` and takes
  its tile as a PER-CALL knob, so no stale table entry can leak into a
  measurement.
* **hysteresis** — the heuristic default tile is always candidate 0;
  a challenger must beat it by the 3% flip margin or the entry records
  the heuristic (with the full sweep in ``params.measured``).
* **choice-preserving** — an existing entry for the key keeps its
  step-level ``choice``/citation; the sweep only attaches ``params``
  (and only when the entry's choice IS the swept kernel). A fresh key
  gets the swept kernel as its choice, measured payload attached.

Usage::

    python benchmarks/autotune_tiles.py           # TPU window pass
    python benchmarks/autotune_tiles.py --smoke   # CPU demonstration
                                                  # (interpret-mode,
                                                  # backend="cpu" rows)

``--only layer_norm,attention`` restricts op families; ``--table`` /
``--ledger`` redirect artifacts (tests use tmp paths).
"""

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from apex_tpu import dispatch  # noqa: E402
from apex_tpu import resilience  # noqa: E402
from apex_tpu.dispatch import tiles  # noqa: E402
from apex_tpu.resilience import faults  # noqa: E402
from apex_tpu.telemetry import flight  # noqa: E402
from apex_tpu.telemetry import ledger as ledger_mod  # noqa: E402
from benchmarks.autotune_steps import FLIP_MARGIN, _upsert_entry  # noqa: E402

# the kernel each family's tile sweep measures — and the choice a FRESH
# table entry records (an existing entry keeps its step-level choice)
FAMILY_CHOICE = {"attention": "rows", "layer_norm": "pallas",
                 "softmax": "pallas", "lm_head": "fused"}


def sweep_groups(smoke):
    """The per-shape sweep set: 2-3 shapes per op family. TPU shapes are
    the GPT-2 (and 345M-ladder) working set; smoke shapes are small,
    CPU-interpret-feasible, and picked to land in buckets no committed
    step entry or tier-1 fixture occupies (a cpu demonstration row must
    never silently re-dispatch an existing test program)."""
    if smoke:
        return [
            dict(op="attention", dtype="bfloat16",
                 dims=dict(b=1, h=2, sq=256, sk=256, d=32)),
            dict(op="layer_norm", dtype="bfloat16",
                 dims=dict(rows=1024, hidden=256)),
            dict(op="layer_norm", dtype="bfloat16",
                 dims=dict(rows=512, hidden=384)),
            dict(op="softmax", dtype="bfloat16",
                 dims=dict(b=1, h=4, sq=256, sk=256)),
            dict(op="lm_head", dtype="bfloat16",
                 dims=dict(n=512, v=1024, h=256)),
        ]
    return [
        dict(op="attention", dtype="bfloat16",
             dims=dict(b=8, h=12, sq=1024, sk=1024, d=64)),
        dict(op="attention", dtype="bfloat16",
             dims=dict(b=8, h=16, sq=512, sk=512, d=64)),
        dict(op="layer_norm", dtype="bfloat16",
             dims=dict(rows=8192, hidden=768)),
        dict(op="layer_norm", dtype="bfloat16",
             dims=dict(rows=8192, hidden=1024)),
        dict(op="softmax", dtype="bfloat16",
             dims=dict(b=8, h=12, sq=1024, sk=1024)),
        dict(op="lm_head", dtype="bfloat16",
             dims=dict(n=8192, v=50304, h=768)),
    ]


def group_key(group, backend):
    return (group["op"], dispatch.bucket(**group["dims"]),
            group["dtype"], backend)


def cashed(group, backend, table_path, ledger_ids):
    """The existing params payload for this group's key IF its ledger
    id resolves (the resume rule), else None."""
    entries, _ = dispatch.load_table(table_path)
    e = entries.get(group_key(group, backend))
    if e is None:
        return None
    payload = e.get("params")
    if isinstance(payload, dict) and payload.get("ledger") in ledger_ids:
        return payload
    return None


def missing_rungs(smoke=False, table_path=None, ledger_path=None,
                  backend=None):
    """Sweep groups whose params payload is absent or stale — the
    bounded warm set ``benchmarks/warm_cache.py`` AOT-warms before a
    window pass."""
    table_path = table_path or dispatch.default_path()
    ledger_path = ledger_path or ledger_mod.default_path()
    backend = backend or ("cpu" if smoke else "tpu")
    try:
        ids = {r.get("id") for r in ledger_mod.read_ledger(ledger_path)}
    except (OSError, ValueError):
        ids = set()
    return [g for g in sweep_groups(smoke)
            if cashed(g, backend, table_path, ids) is None]


# ---------------------------------------------------------------- child

def _child_program(op, dims, dtype, params, interpret):
    """``(make_body, carry0, ops, flops)`` for one Tracer.scan_time
    row: the kernel's fwd+bwd at the given shape, tiled by ``params``
    as PER-CALL knobs (illegal tiles raise — the parent only submits
    legal candidates, so a raise here is a model bug worth crashing
    on)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    rs = np.random.RandomState(0)
    jdt = dict(bfloat16=jnp.bfloat16, float32=jnp.float32)[dtype]

    if op == "layer_norm":
        from apex_tpu.ops import layer_norm_pallas as lnp

        rows, hidden = dims["rows"], dims["hidden"]
        x0 = jnp.asarray(rs.randn(rows, hidden), jdt)
        w0 = jnp.ones((hidden,), jnp.float32)
        b0 = jnp.zeros((hidden,), jnp.float32)

        def make_body(eps, x0, w0, b0):
            def body(carry, _):
                w, b = carry

                def f(w, b):
                    # per-call (raising) form: the measured label must
                    # be the submitted tile, never a silent fallback
                    y = lnp.layer_norm(x0, w, b, 1e-5, interpret,
                                       params.get("block_rows"))
                    return jnp.sum(y.astype(jnp.float32) ** 2)

                _, (gw, gb) = jax.value_and_grad(f, argnums=(0, 1))(w, b)
                return (w - eps * gw, b - eps * gb), ()
            return body

        return make_body, (w0, b0), (x0, w0, b0)

    if op == "softmax":
        from apex_tpu.ops import softmax_pallas as smp

        b, h, sq, sk = dims["b"], dims["h"], dims["sq"], dims["sk"]
        x0 = jnp.asarray(rs.randn(b, h, sq, sk), jdt)

        def make_body(eps):
            def body(x, _):
                def f(x):
                    y = smp.scaled_masked_softmax(
                        x, None, 1.0, True, interpret,
                        params.get("block_rows"))
                    return jnp.sum(y.astype(jnp.float32) ** 2)

                g = jax.grad(f)(x)
                return (x - eps * g).astype(x.dtype), ()
            return body

        return make_body, x0, ()

    if op == "attention":
        from apex_tpu.ops import attention_pallas as ap

        b, h, sq, sk, d = (dims[k] for k in ("b", "h", "sq", "sk", "d"))
        q0 = jnp.asarray(rs.randn(b, h, sq, d), jdt)
        k0 = jnp.asarray(rs.randn(b, h, sk, d), jdt)
        v0 = jnp.asarray(rs.randn(b, h, sk, d), jdt)
        bwd_impl = "split" if "block_k" in params else None

        def make_body(eps, k0, v0):
            def body(q, _):
                def f(q):
                    y = ap.fused_attention_rows(
                        q, k0, v0, True, 1.0 / float(np.sqrt(d)), None,
                        interpret, params.get("block_q"), bwd_impl, 0.0,
                        None, params.get("bwd_block_q"),
                        params.get("block_k"), None)
                    return jnp.sum(y.astype(jnp.float32) ** 2)

                g = jax.grad(f)(q)
                return (q - eps * g).astype(q.dtype), ()
            return body

        return make_body, q0, (k0, v0)

    if op == "lm_head":
        from apex_tpu.ops import xent_pallas as xp

        n, V, h = dims["n"], dims["v"], dims["h"]
        x0 = jnp.asarray(rs.randn(n, h), jdt)
        e0 = jnp.asarray(rs.randn(V, h), jdt)
        lab0 = jnp.asarray(rs.randint(0, V, (n,)), jnp.int32)

        def make_body(eps, e0, lab0):
            def body(x, _):
                def f(x, e):
                    return jnp.sum(xp.linear_cross_entropy(
                        x, e, lab0, interpret, 0.0,
                        params.get("row_block"),
                        params.get("vmem_budget")))

                gx, _ = jax.grad(f, argnums=(0, 1))(x, e0)
                return (x - eps * gx).astype(x.dtype), ()
            return body

        return make_body, x0, (e0, lab0)

    raise ValueError(f"unknown op {op!r}")


def run_child(spec_json):
    """``--child`` body: measure ONE (op, shape, tile) row and print a
    JSON line {value, unit, ledger, params}. Runs table-blind (the
    parent exports APEX_DISPATCH=off) with the tile as a per-call
    knob; the ledger record (harness "autotune_tiles") carries the
    spec, so the table payload's citation resolves to a record whose
    measured program is auditable."""
    from benchmarks._smoke import smoke_mode

    spec = json.loads(spec_json)
    smoke = bool(spec.get("smoke"))
    if smoke:
        smoke_mode("APEX_BENCH_SMOKE")
    else:
        smoke_mode("APEX_TILES_NEVER")  # activate cache, stay on TPU
    from benchmarks._timing import Tracer, bench_k

    import jax

    interpret = smoke or jax.default_backend() == "cpu"
    op, dims, dtype = spec["op"], spec["dims"], spec["dtype"]
    params = spec["params"]
    k = bench_k(smoke)
    tracer = Tracer(k)
    make_body, carry0, ops = _child_program(op, dims, dtype, params,
                                            interpret)
    tag = "-".join(f"{k_}{v}" for k_, v in sorted(params.items()))
    span = tracer.scan_time(f"{op} {tag}", make_body, carry0, ops,
                            extra={"op": op, "dims": dims,
                                   "tile_params": params}, on_fail="span")
    rid = tracer.flush_ledger("autotune_tiles",
                              extra={"op": op, "dims": dims,
                                     "tile_params": params})
    out = {"unit": "ms", "params": params, "ledger": rid,
           "value": span.ms}
    if span.error:
        out["error"] = span.error
    print(json.dumps(out), flush=True)
    return 0 if span.ms is not None else 1


# --------------------------------------------------------------- parent

def _child_env(smoke, ledger_path):
    env = dict(os.environ)
    env["APEX_DISPATCH"] = "off"  # table-blind measurement
    env["APEX_TELEMETRY_LEDGER"] = os.path.abspath(ledger_path)
    if smoke:
        env["APEX_BENCH_SMOKE"] = "1"
    return env


def run_candidate(group, params, smoke, ledger_path, timeout, log_dir,
                  tag):
    """One timeoutable child subprocess; returns the parsed JSON line
    or None (crash/timeout/no-measurement — the caller logs and moves
    on)."""
    spec = dict(op=group["op"], dims=group["dims"], dtype=group["dtype"],
                params=params, smoke=smoke)
    cmd = [sys.executable, os.path.abspath(__file__), "--child",
           json.dumps(spec)]
    flight.beat("attempt_start", label=tag, candidate=params)
    try:
        proc = subprocess.run(cmd, env=_child_env(smoke, ledger_path),
                              cwd=REPO, text=True, capture_output=True,
                              timeout=timeout)
        out = proc.stdout
        rc = proc.returncode
    except subprocess.TimeoutExpired as e:
        out = e.stdout if isinstance(e.stdout, str) else ""
        rc = None
        print(f"  {tag}: timed out after {timeout}s", flush=True)
    flight.beat("attempt_done", label=tag, rc=rc,
                timed_out=rc is None)
    if log_dir:
        try:
            with open(os.path.join(log_dir, f"{tag}.log"), "w") as f:
                f.write(out or "")
        except OSError:
            pass
    _, rec = resilience.last_json(out or "")
    if rc != 0 or rec is None or rec.get("value") is None \
            or not rec.get("ledger"):
        if rc not in (0, None):
            sys.stderr.write((proc.stderr or "")[-1500:])
            print(f"  {tag}: rc={rc}", flush=True)
        return None
    return rec


def _measure(group, params, ctx, tag):
    """Best-of-N child runs for one tile candidate (min ms — outliers
    on a contended host are slow). Tests monkeypatch THIS."""
    best = None
    for i in range(max(1, ctx["repeats"])):
        rec = ctx["runner"](group, params, ctx["smoke"], ctx["ledger"],
                            ctx["timeout"], ctx["log_dir"],
                            f"{tag}" + (f".r{i}" if ctx["repeats"] > 1
                                        else ""))
        if rec is None:
            continue
        if best is None or rec["value"] < best["value"]:
            best = rec
    return best


def main(argv=None, runner=run_candidate):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CPU interpret-mode demonstration sweep "
                         "(backend='cpu' rows)")
    ap.add_argument("--child", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--table", default=None)
    ap.add_argument("--ledger", default=None)
    ap.add_argument("--budget-s", type=float, default=None,
                    help="stop launching candidates once spent "
                         "(default resilience.AUTOTUNE_BUDGET_S / 2; "
                         "smoke 600)")
    ap.add_argument("--child-timeout", type=int, default=None,
                    help="per-candidate subprocess cap (default "
                         "resilience.RUNG_TIMEOUT_S: 900, smoke 180)")
    ap.add_argument("--only", default=None,
                    help="comma-separated op families")
    ap.add_argument("--repeats", type=int, default=None,
                    help="best-of-N child runs per candidate "
                         "(default 1)")
    ap.add_argument("--max-candidates", type=int, default=None,
                    help="cap the legal candidate set per shape "
                         "(default 6; smoke 3 — CPU interpret children "
                         "are slow)")
    ap.add_argument("--out", default=None, help="per-candidate log dir")
    args = ap.parse_args(argv)

    if args.child is not None:
        return run_child(args.child)

    smoke = args.smoke
    table_path = args.table or dispatch.default_path()
    ledger_path = args.ledger or ledger_mod.default_path()
    # the §6 timeout envelope has ONE home (apex_tpu.resilience); tile
    # candidates are kernel-level (seconds), so the default pass budget
    # is half the step autotuner's
    budget = args.budget_s if args.budget_s is not None \
        else (resilience.AUTOTUNE_BUDGET_SMOKE_S if smoke
              else resilience.AUTOTUNE_BUDGET_S / 2)
    timeout = args.child_timeout if args.child_timeout is not None \
        else (resilience.RUNG_TIMEOUT_SMOKE_S if smoke
              else resilience.RUNG_TIMEOUT_S)
    budget = faults.override_budget(budget)
    if faults.active():
        print(f"autotune_tiles: FAULT PLAN ACTIVE ({faults.plan_hash()}) "
              "— test-only pass; entries citing fault-stamped records "
              "fail tools/check_bench_labels.py", flush=True)
        if args.table is None:
            raise SystemExit(
                "autotune_tiles: refusing to write the committed "
                "dispatch table under APEX_FAULT_PLAN — pass --table to "
                "a scratch path for chaos runs")
    backend = "cpu" if smoke else "tpu"
    max_cand = args.max_candidates or (3 if smoke else 6)
    log_dir = args.out
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)

    groups = sweep_groups(smoke)
    if args.only:
        names = set(args.only.split(","))
        unknown = names - {g["op"] for g in groups}
        if unknown:
            raise SystemExit(f"unknown op family(s): {sorted(unknown)}")
        groups = [g for g in groups if g["op"] in names]

    try:
        ledger_ids = {r.get("id")
                      for r in ledger_mod.read_ledger(ledger_path)}
    except (OSError, ValueError):
        ledger_ids = set()

    ctx = {"runner": runner, "smoke": smoke, "ledger": ledger_path,
           "timeout": timeout, "log_dir": log_dir,
           "repeats": args.repeats or 1}
    # apexlint: disable=APX004 — sweep-budget wall clock, not a measured row (rung children are Tracer-timed)
    t0 = time.perf_counter()
    done, skipped, dropped, failed = [], [], [], []
    for group in groups:
        bucket = dispatch.bucket(**group["dims"])
        gtag = f"{group['op']}/{bucket}"
        existing = cashed(group, backend, table_path, ledger_ids)
        if existing is not None:
            print(f"{gtag}: cashed (params={existing.get('value')}, "
                  f"ledger:{existing.get('ledger')}) — skip", flush=True)
            skipped.append(gtag)
            continue
        # apexlint: disable=APX004 — sweep-budget wall clock, not a measured row (rung children are Tracer-timed)
        if time.perf_counter() - t0 > budget:
            dropped.append(gtag)  # no silent caps
            continue
        cands = tiles.candidates(group["op"], group["dims"],
                                 group["dtype"], max_cand)
        if not cands:
            print(f"{gtag}: no legal candidates (unsupported shape)",
                  flush=True)
            failed.append(gtag)
            continue
        print(f"{gtag}: sweeping {len(cands)} legal tiles "
              # apexlint: disable=APX004 — sweep-budget wall clock, not a measured row (rung children are Tracer-timed)
              f"(budget {budget - (time.perf_counter() - t0):.0f}s left)",
              flush=True)
        results = []
        for i, params in enumerate(cands):
            # apexlint: disable=APX004 — sweep-budget wall clock, not a measured row (rung children are Tracer-timed)
            if time.perf_counter() - t0 > budget:
                print(f"  {gtag}: budget spent mid-sweep — keeping "
                      f"{len(results)} measured candidates", flush=True)
                break
            ptag = "-".join(f"{k}{v}" for k, v in sorted(params.items()))
            rec = _measure(group, params, ctx, f"{group['op']}.{ptag}")
            if rec is None:
                print(f"  {gtag} {params}: no measurement", flush=True)
                continue
            results.append(rec)
            print(f"  {gtag} {params}: {rec['value']:.4g} ms "
                  f"(ledger:{rec['ledger']})", flush=True)
        if not results:
            failed.append(gtag)
            continue
        # hysteresis: candidate 0 is the heuristic incumbent — a
        # challenger tile must beat it by the flip margin
        best = min(results, key=lambda r: r["value"])
        incumbent = next((r for r in results
                          if r["params"] == cands[0]), None)
        if incumbent is not None and best is not incumbent:
            gain = (incumbent["value"] - best["value"]) \
                / incumbent["value"]
            if gain < FLIP_MARGIN:
                print(f"  {gtag}: {best['params']} ahead by only "
                      f"{gain * 100:.1f}% (< {FLIP_MARGIN * 100:.0f}% "
                      f"flip margin) — keeping the heuristic tile",
                      flush=True)
                best = incumbent
        payload = {
            "value": best["params"], "ledger": best["ledger"],
            # the one process-wide pin every child measured under —
            # check 4 verifies it against the cited record's knobs
            "pins": {"APEX_DISPATCH": "off"},
            "measured": {
                "-".join(f"{k}{v}" for k, v in sorted(r["params"].items())):
                    {"value": r["value"], "unit": "ms",
                     "ledger": r["ledger"]}
                for r in results},
        }
        entries, _ = dispatch.load_table(table_path)
        prior = entries.get(group_key(group, backend))
        if prior is not None \
                and prior.get("choice") == FAMILY_CHOICE[group["op"]]:
            entry = dict(prior, params=payload)
        elif prior is not None:
            # the step-level choice for this key is NOT the swept
            # kernel — attaching tile params to it would be incoherent;
            # keep the entry and say so
            print(f"{gtag}: entry choice {prior.get('choice')!r} is not "
                  f"{FAMILY_CHOICE[group['op']]!r} — sweep measured but "
                  f"NOT attached (step autotuner owns the choice)",
                  flush=True)
            failed.append(gtag)
            continue
        else:
            entry = dispatch.make_entry(
                group["op"], group["dims"], group["dtype"], backend,
                FAMILY_CHOICE[group["op"]], best["ledger"],
                pins={"APEX_DISPATCH": "off"}, params=payload,
                rung=f"tiles_{group['op']}")
        _upsert_entry(table_path, entry)
        print(f"{gtag}: WINNER {best['params']} -> params payload "
              f"({backend})", flush=True)
        done.append(gtag)
    summary = {"done": done, "skipped": skipped, "dropped": dropped,
               "failed": failed, "table": table_path,
               # apexlint: disable=APX004 — sweep-budget wall clock, not a measured row (rung children are Tracer-timed)
               "wall_s": round(time.perf_counter() - t0, 1)}
    if faults.plan_hash():
        summary["fault_plan"] = faults.plan_hash()
    if dropped:
        print(f"BUDGET DROPPED (re-run to resume): {dropped}", flush=True)
    print("autotune_tiles: " + json.dumps(summary), flush=True)
    return 1 if (failed or dropped) else 0


if __name__ == "__main__":
    sys.exit(main())
