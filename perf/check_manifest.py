"""Hold ``BENCHMARK.json`` to the character and cross-reference rules of
the benchmark's contract before anything reaches the chip.

``python3 perf/check_manifest.py`` prints every fault and exits 1 on
any; ``perf/run.py`` calls :func:`load` at start-up, so a bad manifest
never runs. PR 22 was refused for a ``layer`` of plain words: a layer is
named like a metric, one token.
"""

import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MANIFEST = os.path.join(ROOT, "BENCHMARK.json")

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
WORKLOAD_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
# a full check: 2 + 14 runs a cell, run_seconds + 60 each, 2 x 90 s a
# cell to compile, 1200 s spare, inside 43200 s with all 24 cells
MAX_CELLS, RUNS_PER_CELL, CHECK_SECONDS = 24, 14, 43200


def _line(text, what, faults):
    if not (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text):
        faults.append(f"{what}: 1 to 200 characters on one line, no tab")


def _name(text, what, faults):
    if not (isinstance(text, str) and NAME.match(text)):
        faults.append(
            f"{what} {text!r}: 1 to 64 letters, digits, '_', '.', '-', "
            f"starting with a letter, digit or '_' (no space)")


def traffic_file(mix):
    """The data file of a traffic mix, by its name; None if it is missing."""
    path = os.path.join(ROOT, "perf", "traffic", mix + ".json")
    return path if os.path.exists(path) else None


def reader_file(folder, name):
    """The reader of a metric, by its name: ``<name>.py``, or for a
    quantity split by the cells that report it (``idle_share.train``,
    ``idle_share.serve``) the one reader ``<quantity>.py`` of the part
    before the first dot. None if there is neither."""
    for stem in (name, name.split(".")[0]):
        path = os.path.join(ROOT, "perf", folder, stem + ".py")
        if os.path.exists(path):
            return path
    return None


def cells_of(metric, manifest):
    """The cells that report ``metric``: those it lists, or every one."""
    return list(metric.get("workloads")
                or [w["name"] for w in manifest["workloads"]])


def check(manifest, raw_size=0):
    """Every fault of ``manifest`` as a list of strings (empty = sound)."""
    faults = []
    if raw_size > 64 * 1024:
        faults.append("BENCHMARK.json is over 64 KiB")
    if set(manifest) != TOP_KEYS:
        faults.append(f"top-level keys must be exactly {sorted(TOP_KEYS)}")
        return faults

    command, paths = manifest["command"], manifest["paths"]
    if not (isinstance(command, list) and 1 <= len(command) <= 32):
        faults.append("command: a list of 1 to 32 strings")
    if not (isinstance(paths, list) and 1 <= len(paths) <= 16):
        faults.append("paths: 1 to 16 directories")
    for p in paths:
        if not PATH.match(p) or p.startswith("/") or ".." in p.split("/"):
            faults.append(f"path {p!r}: relative, inside the repo")
    for word in command:
        _line(word, f"command word {word!r}", faults)
        if word.startswith("/") or ".." in word.split("/"):
            faults.append(f"command word {word!r} leads out of the repo")
        if os.path.exists(os.path.join(ROOT, word)) and not any(
                word == p or word.startswith(p.rstrip("/") + "/")
                for p in paths):
            faults.append(f"command names {word!r}, outside paths")

    seconds = manifest["run_seconds"]
    if not (isinstance(seconds, int) and 1 <= seconds <= 51):
        faults.append("run_seconds: a whole number from 1 to 51")
    elif ((2 + RUNS_PER_CELL * MAX_CELLS) * (seconds + 60)
          + MAX_CELLS * 180 + 1200 > CHECK_SECONDS):
        faults.append("run_seconds: a full check of 24 cells would not fit")

    configs, cells = manifest["configs"], manifest["workloads"]
    if not 1 <= len(configs) <= 24:
        faults.append("configs: 1 to 24")
    if not 1 <= len(cells) <= 24:
        faults.append("workloads: 1 to 24")
    files = []
    for c in configs:
        if set(c) != CONFIG_KEYS:
            faults.append(f"config keys must be {sorted(CONFIG_KEYS)}: {c}")
            continue
        _name(c["name"], "config name", faults)
        _line(c["source"], f"config {c['name']} source", faults)
        _line(c["why"], f"config {c['name']} why", faults)
        if len(c["reduced"]) > 16:
            faults.append(f"config {c['name']}: reduced has over 16 keys")
        for key in c["reduced"]:
            _name(key, f"config {c['name']} reduced key", faults)
        f = c["file"]
        files.append(f)
        if not any(f.startswith(p.rstrip("/") + "/") for p in paths):
            faults.append(f"config file {f!r} is not under paths")
        if not os.path.exists(os.path.join(ROOT, f)):
            faults.append(f"config file {f!r} is missing")
        if c["name"] not in {w.get("config") for w in cells}:
            faults.append(f"config {c['name']} is used by no cell")
    if len(set(files)) != len(files):
        faults.append("two configurations share a file")

    config_names = [c.get("name") for c in configs]
    pairs = []
    for w in cells:
        if set(w) != WORKLOAD_KEYS:
            faults.append(f"cell keys must be {sorted(WORKLOAD_KEYS)}: {w}")
            continue
        for key in ("name", "config", "traffic"):
            _name(w[key], f"cell {key}", faults)
        _line(w["why"], f"cell {w['name']} why", faults)
        if w["chips"] not in (1, 4):
            faults.append(f"cell {w['name']}: chips is 1 or 4")
        if w["config"] not in config_names:
            faults.append(f"cell {w['name']}: no config {w['config']!r}")
        pairs.append((w["config"], w["traffic"]))
        mix = traffic_file(w["traffic"])
        if mix is None:
            faults.append(f"cell {w['name']}: no perf/traffic/"
                          f"{w['traffic']}.json data file")
        else:
            with open(mix) as fh:
                runner = json.load(fh).get("runner", "")
            if not os.path.exists(os.path.join(
                    ROOT, "perf", "runners", f"{runner}.py")):
                faults.append(f"traffic {w['traffic']}: no runner "
                              f"perf/runners/{runner}.py")
    if len(set(pairs)) != len(pairs):
        faults.append("a pair of config and traffic appears twice")
    four = sum(1 for w in cells if w.get("chips") == 4)
    if four > max(1, len(cells) // 4):
        faults.append(f"{four} four-chip cells: at most a quarter, or one")

    cell_names = [w.get("name") for w in cells]
    e2e, layers = manifest["end_to_end"], manifest["per_layer"]
    if not 1 <= len(e2e) <= 16:
        faults.append("end_to_end: 1 to 16 metrics")
    if not 1 <= len(layers) <= 128:
        faults.append("per_layer: 1 to 128 metrics")
    for group, keys, folder in ((e2e, E2E_KEYS, "end_to_end"),
                                (layers, LAYER_KEYS, "layer_metrics")):
        for m in group:
            if set(m) - {"workloads"} != keys:
                faults.append(f"metric keys must be {sorted(keys)} "
                              f"(+ workloads): {m}")
                continue
            _name(m["name"], "metric name", faults)
            if not (isinstance(m["unit"], str) and UNIT.match(m["unit"])):
                faults.append(f"metric {m['name']}: unit {m['unit']!r}")
            if m["better"] not in ("lower", "higher"):
                faults.append(f"metric {m['name']}: better")
            if m["source"] not in SOURCES:
                faults.append(f"metric {m['name']}: source {m['source']!r}")
            for cell in m.get("workloads", []):
                if cell not in cell_names:
                    faults.append(f"metric {m['name']}: no cell {cell!r}")
            if reader_file(folder, m["name"]) is None:
                faults.append(f"metric {m['name']}: no reader "
                              f"perf/{folder}/{m['name']}.py")
    for m in e2e:
        if m.get("source") not in ("host_clock", "device_trace"):
            faults.append(f"end-to-end {m.get('name')}: the benchmark takes "
                          f"it itself, host_clock or device_trace")
        bound = m.get("bound")
        if not (isinstance(bound, (int, float)) and 0.01 <= bound <= 0.1):
            faults.append(f"end-to-end {m.get('name')}: bound in 0.01..0.1")
    names = [m.get("name") for m in e2e + layers]
    if len(set(names)) != len(names):
        faults.append("two metrics share a name")
    for what, group in (("cell", cell_names), ("config", config_names)):
        if len(set(group)) != len(group):
            faults.append(f"two {what}s share a name")
    if "setup_s" not in [m.get("name") for m in e2e]:
        faults.append("end_to_end lacks setup_s")

    by_name = {m.get("name"): m for m in e2e}
    for m in layers:
        if "layer" not in m:
            continue
        _name(m["layer"], f"per-layer {m['name']} layer", faults)
        target = by_name.get(m["moves"])
        if target is None:
            faults.append(f"per-layer {m['name']} moves {m['moves']!r}, "
                          f"which is no end-to-end metric")
            continue
        lacking = set(cells_of(m, manifest)) - set(cells_of(target, manifest))
        if lacking:
            faults.append(f"per-layer {m['name']} moves {m['moves']}, which "
                          f"cells {sorted(lacking)} do not report")
    for cell in cell_names:
        mine = [m["name"] for m in e2e if cell in cells_of(m, manifest)]
        if "setup_s" not in mine or len(mine) < 2:
            faults.append(f"cell {cell}: setup_s and one more end-to-end "
                          f"metric, has {mine}")
        if not any(cell in cells_of(m, manifest) for m in layers):
            faults.append(f"cell {cell}: reports no per-layer metric")
    return faults


def load():
    """The manifest, or SystemExit(2) listing its faults."""
    with open(MANIFEST) as fh:
        raw = fh.read()
    manifest = json.loads(raw)
    faults = check(manifest, len(raw.encode()))
    if faults:
        for fault in faults:
            print("BENCHMARK.json:", fault, file=sys.stderr)
        raise SystemExit(2)
    return manifest


if __name__ == "__main__":
    load()
    print("BENCHMARK.json: sound")
