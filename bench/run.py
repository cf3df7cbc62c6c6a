"""The benchmark's one command.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Finds the cell's configuration, traffic mix and per-layer metrics by the names
in ``BENCHMARK.json``, runs it on the chips jax finds (it never sets the
platform and refuses without a TPU), and prints one JSON object as the last
line of standard output. Everything else it prints is on earlier lines.
``--rate`` (requests/s, serving) and ``--control`` exist for the sweeps and
limit readings PERF.md reports; the driver passes neither. ``--control
<precision>`` also reads the reference at that precision; ``--control program``
switches on the program's own lower-precision path (the configuration's
``precision.control_engine``) and should come out not correct."""

from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from bench.harness import common, loader  # noqa: E402


def per_layer_metrics(cell, run):
    """Each per-layer metric of the cell through its own reader. A reader that
    finds nothing to read returns None: the metric is left out of the line and
    named in the second value returned, and the run is then not correct (a step
    program or kernel that was renamed takes its yardstick along, not away)."""
    out, missing = {}, []
    for entry in cell["per_layer"]:
        mod = loader.module_from("metrics", entry["name"])
        if (mod.NAME, mod.UNIT, mod.MOVES, mod.SOURCE, mod.LAYER) != (
                entry["name"], entry["unit"], entry["moves"], entry["source"], entry["layer"]):
            raise RuntimeError(f"bench/metrics file of {entry['name']} disagrees with BENCHMARK.json")
        value = mod.reduce(run)
        if value is not None and math.isfinite(value):
            out[entry["name"]] = {"value": value, "unit": entry["unit"]}
        else:
            missing.append(entry["name"])
    return out, missing


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None, help="override the mix's arrival rate (sweeps)")
    ap.add_argument("--control", default=None, help="a precision of the reference, or 'program' (limit setting)")
    ap.add_argument("--root", default=ROOT, help="another checkout root (tests)")
    args = ap.parse_args(argv)

    loader.ROOT = root = os.path.abspath(args.root)
    cell = loader.cell(args.workload, root=root)
    chips = cell["workload"]["chips"]
    device = common.require_tpu(chips)
    common.log(phase="device", workload=args.workload, seed=args.seed, seconds=args.seconds, trace=args.trace,
               **device)
    kind = cell["config"]["bench"]["kind"]
    res = loader.runner(kind).run(cell, args, T_PROCESS, root)

    run = res["run"]
    device = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    breakdown, missing = None, []
    if args.trace:
        from bench.harness import trace_reduce

        tracer = run["tracer"]
        reduced = trace_reduce.reduce_events(trace_reduce.read_xplane(tracer.xplane_path()))
        run["trace"] = reduced
        run["peaks"] = loader.peaks(device["kind"])
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        breakdown = trace_reduce.breakdown(reduced)
        common.log(phase="trace", window_s=reduced["window_s"], busy_s_by_device=reduced["busy_s_by_device"],
                   longest_gaps_s=reduced["longest_gaps_s"], host_step_spans=reduced["host_step_spans"],
                   modules={k: {"n": len(v), "median_s": sorted(v)[len(v) // 2]}
                            for k, v in reduced["module_runs_s"].items()})
        metrics, missing = per_layer_metrics(cell, run)
        if missing:
            common.log(phase="result", per_layer_metrics_without_a_value=missing)
    else:
        metrics = {m["name"]: {"value": res["end_to_end"][m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}
    bad = [k for k, v in metrics.items() if not math.isfinite(v["value"])]
    for k in bad:  # a tail that lands among failed requests: the worst, and not correct
        common.log(phase="result", not_finite=k)
        metrics[k]["value"] = 1e12
    correct = res["correct"] and not bad and not missing
    common.result_line(correct, res["attempted"], res["failed"], metrics, device, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
