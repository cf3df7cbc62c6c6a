"""Runner for ``kind: serve``: InferenceEngine behind ServingServer on a local
port, an open-loop load generator in a child process, a window of
``--seconds``, then the comparison with the plain reference over every request
the window finished."""

from __future__ import annotations

import http.client
import json
import math
import os
import random
import subprocess
import sys
import time

import numpy as np

from . import loader, stats, traffic
from .common import (CompileCounter, Tracer, adopt_params, build_model, enable_compile_cache, log,
                     memory_peak_bytes)
from .loadgen import prompt_ids

TRACE_OFFSET_S = 3.0   # a traced run profiles this long into the window ...
TRACE_SECONDS = 6.0    # ... for this long


def scrape(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=60)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        body = resp.read().decode()
        if resp.status != 200:
            raise RuntimeError(f"GET {path}: {resp.status} {body[:200]}")
        return body
    finally:
        conn.close()


def prom_total(text, name):
    """Sum of the samples of one family in Prometheus text (no labels parsed)."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in (" ", "{"):
            total += float(line.rsplit(" ", 1)[1])
    return total


def snapshot(port):
    text = scrape(port, "/metrics")
    eff = json.loads(scrape(port, "/debug/efficiency"))
    return {"t": time.monotonic(),
            "queue_wait_sum": prom_total(text, "paddlenlp_serving_queue_wait_seconds_sum"),
            "queue_wait_count": prom_total(text, "paddlenlp_serving_queue_wait_seconds_count"),
            "engine_restarts": prom_total(text, "paddlenlp_serving_engine_restarts_total"),
            "brownout_level": prom_total(text, "paddlenlp_serving_brownout_level"),
            "ledger": eff["ledger"]["totals"], "shape_buckets": eff["ledger"]["shape_buckets"]}


def build_engine(config, seed, control=None):
    """``control == "program"`` switches on the program's own lower-precision path,
    as the configuration names it (``precision.control_engine``)."""
    import jax
    import jax.numpy as jnp

    from paddlenlp_tpu.experimental import InferenceEngine

    b = config["bench"]
    ref = loader.module_from("reference", b["reference"])
    dtype = jnp.dtype(b["precision"]["weights"])
    _, make = build_model(config, dtype, dtype)
    model = make()
    adopt_params(model, jax.jit(lambda s: ref.program_params(config, s, dtype))(ref.seed_array(seed)))
    lower = b["precision"]["control_engine"] if control == "program" else {}
    engine = InferenceEngine(model, dtype=dtype, **{**b["engine"], **lower})
    return model, engine


def warm_shapes(engine, mix, vocab, seed):
    """Every prefill program the mix reaches (every prompt-length bucket, with
    1, 2 and 4 rows; ``mix["warm_rows"]`` says how many arrivals one engine step
    can find waiting in one bucket), the per-group-size host-side updates, and
    the decode program: through the engine's own add_request/step."""
    from paddlenlp_tpu.experimental.engine import SamplingParams

    rng = random.Random(seed ^ 0x5EED)
    draw = lambda n: [rng.randrange(vocab) for _ in range(n)]

    def drive(lengths, max_new):
        for n in lengths:
            engine.add_request(draw(n), SamplingParams(max_new_tokens=max_new))
        while engine.has_work():
            engine.step()

    buckets = traffic.prefill_buckets(mix)
    most = min(mix["warm_rows"], engine.max_batch_size)
    rows, r = [], 1
    while r <= most:
        rows.append(r)
        r *= 2
    for length in buckets:
        for r in rows:
            drive([length] * r, 1)
    for r in range(1, rows[-1] + 1):
        if r not in rows:
            drive([buckets[0]] * r, 1)
    drive([buckets[0]] * 2, 2 * engine.decode_steps + 1)
    engine.clear_prefix_cache()


def window_metrics(recs, mix, t_win, seconds):
    """End-to-end numbers from the client's side. Times are seconds on the
    child's clock, relative to the plan's t0."""
    win = [r for r in recs if r["phase"] == "window"]
    ok = lambda r: (r["status"] == 200 and r["finish_reason"] == "length"
                    and len(r["token_ids"]) == r["max_tokens"] and "error" not in r)
    good = [r for r in win if ok(r)]
    ttft = [(r["token_times"][0] - r["due"]) * 1e3 for r in good]
    tpot = [(r["token_times"][-1] - r["token_times"][0]) / (len(r["token_times"]) - 1) * 1e3
            for r in good if len(r["token_times"]) > mix["tpot_min_tokens"]]
    n_long = sum(1 for r in win if r["max_tokens"] > mix["tpot_min_tokens"])
    # every token after a request's first, over the time they took: a failed request makes it the worst
    streamed = sum(len(r["token_times"]) - 1 for r in good)
    streaming_s = sum(r["token_times"][-1] - r["token_times"][0] for r in good)
    tpot_mean = streaming_s / streamed * 1e3 if streamed and len(good) == len(win) else math.inf
    in_window = sum(1 for r in recs for t in r["token_times"] if t_win <= t < t_win + seconds)
    late = [(r["sent"] - r["due"]) * 1e3 for r in recs if "sent" in r]

    def inflight(t):
        return sum(1 for r in recs if r.get("sent", 1e18) <= t < r.get("done", 1e18))

    q = [t_win + seconds * f for f in (0.25, 0.5, 0.75, 1.0)]
    return {
        "attempted": len(win), "failed": len(win) - len(good), "good": good,
        "bad": [{k: r.get(k) for k in ("index", "status", "finish_reason", "max_tokens", "error")}
                | {"got_tokens": len(r["token_ids"])} for r in recs if not ok(r)][:8],
        "warmup_failed": sum(1 for r in recs if r["phase"] != "window" and not ok(r)),
        "ttft_p90_ms": stats.percentile(ttft, 90, attempted=len(win)),
        "ttft_ms_sorted": [round(t) for t in sorted(ttft)],
        "ttft_p50_ms": stats.median(ttft),
        "ttft_mean_ms": sum(ttft) / len(ttft) if ttft else math.nan,
        "tpot_mean_ms": tpot_mean,
        "tpot_p90_ms": stats.percentile(tpot, 90, attempted=n_long),
        "tpot_p50_ms": stats.median(tpot),
        "serve_tokens_per_s": in_window / seconds,
        "completed_per_s": sum(1 for r in recs if ok(r) and t_win <= r["done"] < t_win + seconds) / seconds,
        "lateness_ms": {"p50": stats.median(late), "max": max(late) if late else 0.0},
        "inflight_at_quarters": [inflight(t) for t in q],
        "inflight_mean_q2": float(np.mean([inflight(t) for t in np.linspace(q[0], q[1], 20)])),
        "inflight_mean_q4": float(np.mean([inflight(t) for t in np.linspace(q[2], q[3], 20)])),
    }


def check_outputs(config, seed, finished, control=None):
    """The gap by which a served token's reference logit lies below the
    reference's best, over every token of every request the window finished:
    the widest and the mean (and the same of the control's first choices)."""
    b = config["bench"]
    ref = loader.module_from("reference", b["reference"])
    seqs = [(prompt_ids(seed, r["index"], r["prompt_tokens"], config["vocab_size"]), r["token_ids"])
            for r in finished]
    rows = ref.served_gaps(config, seed, seqs, b["precision"]["weights"], control=control)

    def numbers(key):
        gaps = np.concatenate([r[key] for r in rows]) if rows else np.full(1, np.nan)
        return {"served_token_gap": float(gaps.max()), "served_token_gap_mean": float(gaps.mean()),
                "not_the_best": int((gaps > 0).sum())}

    out = {"sequences": len(seqs), "served_tokens": sum(len(s[1]) for s in seqs),
           "longest": max((len(s[0]) + len(s[1]) for s in seqs), default=0),
           "compared": numbers("gaps"), "limits": b["limits"]}
    if control:
        out["control"] = numbers("control_gaps")
    return out


def run(cell, args, t_process, root):
    import jax

    config, mix = cell["config"], cell["traffic"]
    b = config["bench"]
    cache_dir = enable_compile_cache(root)
    compiles = CompileCounter()
    t0 = time.monotonic()
    model, engine = build_engine(config, args.seed, args.control)
    paged_kernel = engine.infer.use_paged_kernel is True
    jax.block_until_ready(engine.pool.kv)
    t_built = time.monotonic()
    warm_shapes(engine, mix, config["vocab_size"], args.seed)
    t_warm = time.monotonic()
    log(phase="setup", cache_dir=cache_dir, build_s=round(t_built - t0, 2), warm_shapes_s=round(t_warm - t_built, 2),
        compile=compiles.snapshot(), pool_gib=round(engine.pool.kv.size * engine.pool.kv.dtype.itemsize / 2**30, 2))

    from paddlenlp_tpu.serving import SchedulerConfig, ServingServer
    from paddlenlp_tpu.serving.metrics import MetricsRegistry

    server = ServingServer(engine, registry=MetricsRegistry(), scheduler_config=SchedulerConfig(**b["scheduler"]))
    port = server.start_in_thread()
    plan = traffic.open_loop_plan(mix, args.seconds, rate=args.rate)
    plan.update(seed=args.seed, vocab=config["vocab_size"])
    run_dir = os.path.join(root, "bench_trace", cell["workload"]["name"] + ".run")
    os.makedirs(run_dir, exist_ok=True)
    plan_path, out_path = os.path.join(run_dir, "plan.json"), os.path.join(run_dir, "results.jsonl")
    with open(plan_path, "w") as f:
        json.dump(plan, f)
    t_plan0 = time.monotonic() + 1.5  # the child builds its prompts before this
    child = subprocess.Popen([sys.executable, os.path.join(os.path.dirname(__file__), "loadgen.py"),
                              "--port", str(port), "--plan", plan_path, "--out", out_path, "--t0", repr(t_plan0)])
    tracer = Tracer(root, cell["workload"]["name"]) if args.trace else None
    try:
        t_win = t_plan0 + plan["warmup_s"]
        time.sleep(max(0.0, t_win - time.monotonic()))
        before, c_before = snapshot(port), compiles.snapshot()
        setup_s = time.monotonic() - t_process
        if tracer:
            time.sleep(max(0.0, t_win + TRACE_OFFSET_S - time.monotonic()))
            tracer.start()
            time.sleep(TRACE_SECONDS)
            tracer.stop()
        brownout = 0.0
        while time.monotonic() < t_win + args.seconds - 1.0:
            time.sleep(1.0)
            brownout = max(brownout, prom_total(scrape(port, "/metrics"), "paddlenlp_serving_brownout_level"))
        time.sleep(max(0.0, t_win + args.seconds - time.monotonic()))
        after, c_after = snapshot(port), compiles.snapshot()
        peak = memory_peak_bytes()
        if child.wait(timeout=240) != 0:
            raise RuntimeError("the load generator failed")
        final = snapshot(port)
    finally:
        if child.poll() is None:
            child.kill()
            child.wait()
        server.shutdown(drain_timeout_s=10)
    recs = [json.loads(line) for line in open(out_path)]
    m = window_metrics(recs, mix, plan["warmup_s"], args.seconds)
    good = m.pop("good")
    window_compiles = c_after["programs"] - c_before["programs"]
    log(phase="window", seconds=args.seconds, rate=plan["rate"], setup_s=round(setup_s, 2),
        prompt_tokens=traffic.describe([r["prompt_tokens"] for r in recs if r["phase"] == "window"]),
        output_tokens=traffic.describe([r["max_tokens"] for r in recs if r["phase"] == "window"]),
        window_compiles=window_compiles, drain_s=round(final["t"] - after["t"], 2),
        brownout_level_max=max(brownout, before["brownout_level"], after["brownout_level"], final["brownout_level"]),
        engine_restarts=final["engine_restarts"], **m)

    t_check = time.monotonic()
    check = check_outputs(config, args.seed, good, control=None if args.control == "program" else args.control)
    check["seconds"] = round(time.monotonic() - t_check, 2)
    reasons = []
    if m["failed"] or m["warmup_failed"]:
        reasons.append(f"{m['failed']} window and {m['warmup_failed']} warm-up requests not 200/length/exact count")
    if final["engine_restarts"]:
        reasons.append("engine restarted")
    if b.get("require_paged_kernel", True) and not paged_kernel:
        reasons.append("the paged attention kernel is off")
    if not check["sequences"]:
        reasons.append("no finished request to compare")
    else:
        reasons += [f"{name} {check['compared'][name]} over limit {limit}" for name, limit in check["limits"].items()
                    if not check["compared"][name] <= limit]
    log(phase="check", **check, reasons=reasons)

    run_info = {"kind": "serve", "before": before, "after": after, "window_compiles": window_compiles,
                "tpot_p90_ms": m["tpot_p90_ms"]["value"], "tracer": tracer}
    e2e = {"ttft_p90_ms": m["ttft_p90_ms"]["value"], "tpot_mean_ms": m["tpot_mean_ms"],
           "serve_tokens_per_s": m["serve_tokens_per_s"], "setup_s": setup_s}
    return {"correct": not reasons, "attempted": m["attempted"], "failed": m["failed"],
            "end_to_end": e2e, "memory_peak_bytes": peak, "run": run_info}
