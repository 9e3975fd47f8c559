"""The benchmark's one command.

    python3 perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, no children: checks ``BENCHMARK.json``, loads the cell's
configuration, traffic mix and runner by name, warms, measures for
``--seconds``, judges the outputs, and prints the contract's one JSON
object as the last line of standard output. Without a TPU, or with
another number of chips than the cell asks for, it exits nonzero and
prints no result. ``--rehearse`` (never passed by the driver) swaps in
the tiny twin each file carries under ``"rehearse"`` so that the whole
command can be walked on the CPU; such a line is stamped ``rehearse``
and carries the platform it ran on, so it cannot pass for a measurement.
``--record <file>`` (the driver never passes it either) also writes the
run's whole record, every round, request and chunk, for whoever has to
find out why a run read far off.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402

PERF = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF)
sys.path.insert(0, ROOT)

from perf import check_manifest  # noqa: E402

STATE = os.path.join(PERF, ".state")   # git-ignored: cache, calibration, traces


def _load_json(path, rehearse):
    with open(path) as fh:
        data = json.load(fh)
    twin = data.pop("rehearse", {})
    if rehearse:
        data.update(twin)
    return data


def _load_module(folder, name):
    path = check_manifest.reader_file(folder, name)
    spec = importlib.util.spec_from_file_location(
        f"perf_{folder}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _device_block(jax, chips, compile_log, t_open):
    """The device as JAX reports it. ``memory_peak_bytes`` is what the
    fullest chip holds while the largest program loaded in set-up runs,
    by XLA's memory analysis of that executable (arguments, outputs,
    temporaries, code): the allocator's own peak leaves the temporaries
    out, and is taken only where it is the larger."""
    devices = jax.devices()[:chips]
    buffers = max((d.memory_stats() or {}).get("peak_bytes_in_use") or 0
                  for d in devices)
    name, program = compile_log.largest(before=t_open)
    print(f"memory: largest program {name} {program / 1e9:.3f} GB, "
          f"allocator peak (buffers only) {buffers / 1e9:.3f} GB", flush=True)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": jax.device_count(),
            "memory_peak_bytes": max(program, buffers)}


def main(argv=None):
    parser = argparse.ArgumentParser()
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    parser.add_argument("--rehearse", action="store_true")
    parser.add_argument("--record", help="also write the run's whole record "
                        "(rounds, requests, chunks, reduced trace) here")
    args = parser.parse_args(argv)

    manifest = check_manifest.load()
    cell = next((w for w in manifest["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise SystemExit(f"no cell {args.workload!r} in BENCHMARK.json")
    spec = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = _load_json(os.path.join(ROOT, spec["file"]), args.rehearse)
    traffic = _load_json(check_manifest.traffic_file(cell["traffic"]),
                         args.rehearse)

    # the compile cache: where the caller placed it, else a fixed path
    # inside the checkout (the path is part of the cache's key); the
    # program's compile_cache.activate() takes the variable as given
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(STATE, "xla_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    platform = jax.devices()[0].platform
    print(f"[{time.perf_counter() - T_START:.1f} s] {jax.device_count()} "
          f"{platform} device(s) ready", flush=True)
    if not args.rehearse and platform != "tpu":
        raise SystemExit(f"no TPU (platform {platform!r}): no result")
    if jax.device_count() != cell["chips"]:
        raise SystemExit(f"cell {cell['name']} needs {cell['chips']} chip(s), "
                         f"JAX sees {jax.device_count()}: no result")

    from perf import reduce_trace
    from perf.compile_log import CompileLog

    with open(os.path.join(PERF, "peaks.json")) as fh:
        peaks = json.load(fh)
    kind = jax.devices()[0].device_kind
    if kind not in peaks and not args.rehearse:
        raise SystemExit(f"no published peak for device kind {kind!r}")

    trace_dir = os.path.join(STATE, "trace", cell["name"])
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = types.SimpleNamespace(
        workload=cell["name"], chips=cell["chips"], config=config,
        traffic=traffic, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), rehearse=args.rehearse, t_start=T_START,
        state_dir=STATE, trace_dir=trace_dir, compile_log=CompileLog(),
        device={"kind": kind}, peak=peaks.get(kind))
    record = _load_module("runners", traffic["runner"]).run(ctx)
    record.update(chips=cell["chips"], peak=ctx.peak)

    device = _device_block(jax, cell["chips"], ctx.compile_log,
                           T_START + record["setup_s"])
    result = {"correct": bool(record["correct"]),
              "attempted": record["attempted"], "failed": record["failed"]}
    if args.trace:
        xplane = reduce_trace.find_xplane(trace_dir)
        record["trace"] = reduce_trace.reduce(xplane) if xplane else None
        if record["trace"]:
            device.update(busy_s=record["trace"]["busy_s"],
                          window_s=record["trace"]["window_s"])
            result["breakdown"] = {
                "device_ops": record["trace"]["device_ops"],
                "idle_gaps": record["trace"]["idle_gaps"]}
        shutil.rmtree(trace_dir, ignore_errors=True)

    group, folder = (("per_layer", "layer_metrics") if args.trace
                     else ("end_to_end", "end_to_end"))
    metrics = {}
    for metric in manifest[group]:
        if cell["name"] not in check_manifest.cells_of(metric, manifest):
            continue
        value = _load_module(folder, metric["name"]).read(record)
        if value is not None:   # a reader that finds nothing returns nothing
            metrics[metric["name"]] = {"value": float(value),
                                       "unit": metric["unit"]}
    result.update(metrics=metrics, device=device)
    if args.record:
        with open(args.record, "w") as fh:
            json.dump(dict(record, result=result), fh)
    if args.rehearse:
        result["rehearse"] = True
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
