"""``bench/run.py`` on a serving cell, with the server asked where the window's tail
requests spent their time to first token before it goes away, for the builder:
same arguments, same result line.

    python3 tools/ttft_tail_window.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The harness (``bench/harness/serve.py``) scrapes the server three times: as the
window opens, as it closes, and once the load generator has exited. This wraps
that scrape: at the third, ``GET /debug/requests?since_ts=<the first one's
instant>`` gives the window's finished requests and their ``ttft_tail`` block, and
the program's span ring gives the launches between the first two, with their
``dispatch`` / ``wait`` children (what each dispatch handed to the device:
``h2d_arrays``, ``h2d_bytes``). One line
``{"phase": "ttft_tail", ...}`` is printed before the result line, and the whole
reading is written to ``<--tail-out>/<cell>.<seed>.json`` (default
``chiprun_out/ttft_tail``). The five readings ISSUE 38 names, which join
``BENCHMARK.json`` with a ``benchmark`` PR (ROADMAP W0): the block's shares as
``ttft_tail_wait_share`` / ``_behind_share`` / ``_own_share`` / ``_host_share``, and
``chunk_backlog_mean`` (sum of ``prefill_waiting`` over the launches that carried
prompt tokens, by their count). The driver runs ``bench/run.py``, never this."""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

LAUNCHES = ("prefill", "decode", "mixed_step", "spec_verify")
WAIT = ("inbox", "queue", "admission_gate")


def launch_children(spans, t_open, t_close):
    """The ``dispatch`` and ``wait`` children of the window's launches, by program:
    how many, their mean duration, and what the dispatches handed to the device
    (``h2d_arrays``, ``h2d_bytes``: args of every ``dispatch`` span since PR 39;
    ``None`` on a tree that stamps neither)."""
    out = {}
    for s in spans:
        if s.get("cat") == "engine" and s["name"] in ("dispatch", "wait") and t_open <= s["ts"] < t_close:
            out.setdefault((s["name"], (s.get("args") or {}).get("program")), []).append(s)
    mean = lambda xs: sum(xs) / len(xs) if xs else None
    rows = {}
    for (name, program), group in sorted(out.items(), key=str):
        row = {"n": len(group), "ms_mean": mean([s["dur"] * 1e3 for s in group])}
        if name == "dispatch":
            arrays = [s["args"]["h2d_arrays"] for s in group if "h2d_arrays" in s["args"]]
            row.update(h2d_arrays_min=min(arrays, default=None), h2d_arrays_max=max(arrays, default=None),
                       h2d_bytes_mean=mean([s["args"]["h2d_bytes"] for s in group if "h2d_bytes" in s["args"]]),
                       without_h2d_args=len(group) - len(arrays))
        rows[f"{name}.{program}"] = row
    return rows


def read_window(requests, spans, t_open, t_close):
    """The reading, from the ``/debug/requests`` document of the window's
    requests and the span ring's dicts: the block, the five readings, and what
    the acceptance asks of every span (launches inside ``[t_open, t_close)`` on
    the tracer's clock)."""
    rows, block = requests["recent"], requests["ttft_tail"]
    launches = [s for s in spans if s.get("cat") == "engine" and s["name"] in LAUNCHES
                and t_open <= s["ts"] < t_close]
    launch_durs = {n: [s["dur"] for s in launches if s["name"] == n] for n in LAUNCHES}
    carrying = [s for s in launches if (s.get("args") or {}).get("carried")]
    prefills = [s for s in spans if s.get("cat") == "request" and s["name"] == "prefill" and s["ts"] >= t_open]
    share = block.get("share", {})
    return {
        "ttft_tail": block,
        "readings": {
            "ttft_tail_wait_share": sum(share.get(k, 0.0) for k in WAIT),
            "ttft_tail_behind_share": share.get("prefill_behind"),
            "ttft_tail_own_share": share.get("prefill_own"),
            "ttft_tail_host_share": share.get("prefill_host"),
            "chunk_backlog_mean": (sum(s["args"]["prefill_waiting"] for s in carrying) / len(carrying)
                                   if carrying else None),
        },
        "checks": {
            "requests": len(rows),
            "share_sum": sum(share.values()),
            "phases_sum_worst_error_s": max((abs(sum(r["attribution"].values()) - (r["finish_t"] - r["arrival_t"]))
                                             for r in rows), default=None),
            "launches": len(launches),
            "launches_by_name": {n: len(durs) for n, durs in launch_durs.items()},
            "launch_ms_mean": {n: sum(durs) * 1e3 / max(1, len(durs)) for n, durs in launch_durs.items()},
            "launches_without_both_args": sum(1 for s in launches if not {"carried", "prefill_waiting"}
                                              <= set(s.get("args") or {})),
            "decode_launches_that_carry": sum(1 for s in launches if s["name"] in ("decode", "spec_verify")
                                              and s["args"].get("carried")),
            "prefill_spans": len(prefills),
            "prefill_spans_without_the_split": sum(1 for s in prefills if not {"steps", "own_ms", "behind_ms"}
                                                   <= set(s.get("args") or {})),
            "prefill_spans_split_over_the_span": sum(
                1 for s in prefills if s["args"]["own_ms"] + s["args"]["behind_ms"] > s["dur"] * 1e3 + 1e-3),
        },
        "launch_children": launch_children(spans, t_open, t_close),
        "tail_rows": sorted(({"req_id": r["req_id"], "prompt_len": r["prompt_len"], "ttft_ms": r["ttft_s"] * 1e3,
                              "steps": r["prefill_steps"], "own_ms": r["prefill_own_s"] * 1e3,
                              **{k: r["attribution"][k] * 1e3 for k in (*WAIT, "promote_wait", "prefill_behind",
                                                                          "prefill")}}
                             for r in rows), key=lambda r: -r["ttft_ms"])[:block.get("count", 0)],
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--tail-out", default=os.path.join(ROOT, "chiprun_out", "ttft_tail"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", required=True)
    args, rest = ap.parse_known_args(argv)
    rest += ["--workload", args.workload, "--seed", args.seed]

    from bench import run as bench_run
    from bench.harness import common, serve
    from paddlenlp_tpu.observability.tracer import TRACER

    snapshot_of, log_of = serve.snapshot, serve.log
    stamps, doc = [], {}

    def snapshot(port):
        stamps.append((time.time(), TRACER.now()))
        if len(stamps) == 3:  # the load generator has exited: every request of the window is finished
            requests = json.loads(serve.scrape(port, f"/debug/requests?since_ts={stamps[0][0]!r}"))
            spans = [s.to_dict() for s in TRACER.snapshot(since_ts=stamps[0][1])]
            doc.update(read_window(requests, spans, stamps[0][1], stamps[1][1]), spans_dropped=TRACER.dropped)
        return snapshot_of(port)

    def log(**obj):
        if obj.get("phase") == "window":
            doc["window"] = {k: obj[k] for k in ("rate", "attempted", "failed", "window_compiles", "ttft_p90_ms",
                                                 "ttft_p50_ms", "ttft_ms_sorted", "tpot_mean_ms",
                                                 "inflight_at_quarters")}
            common.log(phase="ttft_tail", **{k: doc[k] for k in ("ttft_tail", "readings", "checks", "launch_children")})
        log_of(**obj)

    serve.snapshot, serve.log = snapshot, log
    try:
        rc = bench_run.main(rest)
    finally:
        serve.snapshot, serve.log = snapshot_of, log_of
    if doc:
        os.makedirs(args.tail_out, exist_ok=True)
        with open(os.path.join(args.tail_out, f"{args.workload}.{args.seed}.json"), "w") as f:
            json.dump(dict(doc, workload=args.workload, seed=args.seed), f, indent=1)
    return rc


if __name__ == "__main__":
    sys.exit(main())
