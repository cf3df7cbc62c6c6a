"""Finds a cell's files by the names in ``BENCHMARK.json``: nothing here lists
a configuration, a mix or a metric. ``root`` is the checkout."""

from __future__ import annotations

import importlib
import importlib.util
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def manifest(root=None):
    with open(os.path.join(root or ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def bench_dir(root=None):
    return os.path.join(root or ROOT, "bench")


def load_json(*parts, root=None):
    with open(os.path.join(bench_dir(root), *parts)) as f:
        return json.load(f)


def cell(name, root=None):
    """{workload, config, traffic, end_to_end, per_layer} of one cell."""
    man = manifest(root)
    work = next((w for w in man["workloads"] if w["name"] == name), None)
    if work is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    entry = next(c for c in man["configs"] if c["name"] == work["config"])
    with open(os.path.join(root or ROOT, entry["file"])) as f:
        config = json.load(f)
    traffic = load_json("traffic", work["traffic"] + ".json", root=root)
    applies = lambda m: name in m.get("workloads", [name])
    return {"workload": work, "config": config, "traffic": traffic,
            "end_to_end": [m for m in man["end_to_end"] if applies(m)],
            "per_layer": [m for m in man["per_layer"] if applies(m)]}


def module_from(kind, name, root=None):
    """``bench/<kind>/<name>.py`` as a module (metric names may hold dots and dashes)."""
    fname = name.replace("-", "_") + ".py"
    path = os.path.join(bench_dir(root), kind, fname)
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{fname[:-3].replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def runner(kind):
    return importlib.import_module(f"bench.harness.{kind}")


def peaks(device_kind, root=None):
    table = load_json("peaks.json", root=root)
    if device_kind not in table["devices"]:
        raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json: add it with its source")
    return table["devices"][device_kind]


def resolve(dotted):
    mod, _, attr = dotted.rpartition(".")
    return getattr(importlib.import_module(mod), attr)
