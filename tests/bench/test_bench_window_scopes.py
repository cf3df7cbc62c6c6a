"""The windowed kinds' scopes reader on a hand-made document and hand-made launch spans, the byte function of the paged
kernel's two kinds of layer by hand, the new metric files against both, and the cell as ISSUE 35 names it."""

import json
import os
import types

import pytest

from bench.harness import loader, window_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "k-exaone-serve-ep16.mixedlen"
CFG = json.load(open(os.path.join(ROOT, "bench", "configs", "k-exaone-serve-ep16.json")))


def op(name, start, dur, scope_path, program="3", jit="_decode_impl"):
    return [name, float(start), float(dur), f"jit({jit})/{scope_path}/dot_general:", program]


#: the trace's clock runs 1e6 ns ahead of the program's: a launch span at ts seconds lies at ts * 1e9 + 1e6 ns
OFFSET = 1e6
DOC = {
    "modules": [["jit__decode_impl(3)", OFFSET, 1000.0], ["jit__mixed_flat_impl(7)", OFFSET + 2000.0, 600.0],
                ["jit__prefill_impl(9)", OFFSET + 9000.0, 100.0]],
    "extent_ns": [OFFSET - 500.0, OFFSET + 10000.0],
    "host": [["decode", OFFSET, 1000.0, {"step": 4}], ["mixed_step", OFFSET + 2000.0, 600.0, {"step": 5}],
             ["decode", OFFSET + 4000.0, 1000.0, {"step": 6}]],
    "ops": [
        op("ragged_paged_attention.1", OFFSET, 400, "while/body/closed_call/paged_attn"),
        op("ragged_paged_attention.2", OFFSET + 400, 50, "while/body/closed_call/paged_attn_window"),
        op("fusion.2", OFFSET + 450, 50, "while/body/closed_call/kv_write/window_plane"),
        op("fusion.3", OFFSET + 500, 100, "while/body/closed_call/kv_write"),
        op("fusion.4", OFFSET + 600, 100, "while/body/closed_call/qk_norm"),
        op("while.5", OFFSET + 700, 200, "while/body/closed_call/experts/while"),      # encloses the next: keeps 150
        op("fusion.5", OFFSET + 750, 50, "while/body/closed_call/experts/while/body"),
        op("fusion.6", OFFSET + 900, 100, "while/body/closed_call/sample"),
        op("ragged_paged_attention.3", OFFSET + 2000, 100, "paged_attn", "7", "_mixed_flat_impl"),
        op("ragged_paged_attention.4", OFFSET + 2100, 100, "paged_attn_window", "7", "_mixed_flat_impl"),
        op("fusion.9", OFFSET + 2200, 200, "qkv", "7", "_mixed_flat_impl"),
        op("fusion.10", OFFSET + 2400, 200, "closed_call", "7", "_mixed_flat_impl"),  # no scope
        op("ragged_paged_attention.1", OFFSET + 4000, 300, "while/body/closed_call/paged_attn"),  # the second decode launch
        op("ragged_paged_attention.9", OFFSET + 9000, 100, "paged_attn", "9", "_prefill_impl"),   # another program: not counted
    ],
}


def span(name, ts_ns, dur_ns, step, **args):
    return {"name": name, "cat": "engine", "ts": ts_ns / 1e9, "dur": dur_ns / 1e9, "args": dict(args, step=step)}


SPANS = [span("decode", 0.0, 1000.0, 4, attn_kv_full=1000, attn_kv_window=300, rows=16),
         span("mixed_step", 2000.0, 600.0, 5, attn_kv_full=77, attn_kv_window=7),
         span("decode", 4000.0, 1000.0, 6, attn_kv_full=1200, attn_kv_window=300, rows=16),
         span("decode", 9500.0, 1000.0, 7, attn_kv_full=5, attn_kv_window=5, rows=16)]  # ends outside the traced span


def test_scope_of_takes_the_innermost_known_scope():
    assert window_scopes.scope_of("jit(_decode_impl)/while/body/closed_call/kv_write/window_plane/scatter:") == "window_plane"
    assert window_scopes.scope_of("jit(_decode_impl)/while/body/closed_call/kv_write/scatter:") == "kv_write"
    assert window_scopes.scope_of("jit(_decode_impl)/while/body/closed_call/paged_attn_window/pallas_call:") == "paged_attn_window"
    assert window_scopes.scope_of("jit(_decode_impl)/while/body/add:") is None and window_scopes.scope_of(None) is None


def test_reduce_sums_own_time_by_scope_and_the_kernel_time_of_the_decode_launches():
    out = window_scopes.reduce(DOC, SPANS)
    assert out["ns"] == 1000.0 + 600.0 + 300.0
    assert out["ns_by_scope"] == {"paged_attn": 400.0 + 100.0 + 300.0, "paged_attn_window": 50.0 + 100.0,
                                  "window_plane": 50.0, "kv_write": 100.0, "qk_norm": 100.0, "experts": 200.0,
                                  "sample": 100.0, "qkv": 200.0, "unscoped": 200.0}
    # two decode launches lie inside the traced span: their counts, and the kernel's time in the decode program's
    # runs inside them, window calls and table walks alike (the mixed program's calls are no decode launch's)
    assert out["decode"] == {"launches": 2, "attn_kv_full": 2200, "attn_kv_window": 600, "kernel_ns": 400.0 + 50.0 + 300.0}
    # without the program's spans the shares are read all the same, the roofline's part is not
    assert window_scopes.reduce(DOC)["decode"] is None and window_scopes.reduce(DOC)["ns"] == 1900.0


def test_a_program_without_the_windowed_scopes_reads_nothing():
    dense = {"modules": [["jit__decode_impl(3)", 0.0, 100.0]], "extent_ns": [0.0, 100.0], "host": [],
             "ops": [["ragged_paged_attention.1", 0.0, 100.0, "jit(_decode_impl)/while/body/closed_call/paged_attn/x:", "3"]]}
    assert window_scopes.reduce(dense) is None  # the llama kind's paged_attn alone is not this kind's program
    assert window_scopes.share({"kind": "serve", "tracer": None}, ("paged_attn",)) is None
    for name in ("full_attn_share", "window_attn_share", "paged_kv_roofline", "attn_kv_window_share"):
        assert loader.module_from("metrics", name).reduce({"kind": "serve", "tracer": None, "before": {"ledger": {}},
                                                           "after": {"ledger": {}}}) is None


def test_paged_window_bytes_by_hand():
    k = loader.module_from("kernels", "paged_window")
    s = k.shape_of(CFG)
    assert s == {"kv_heads": 8, "head_dim": 128, "bytes": 2} and k.position_bytes(s) == 4096
    # a decode sub-step of 16 rows at 5,000 cached positions: 2 full layers see 5,000 each, 6 window layers 128 each
    full, window = 16 * 5000 * 2, 16 * 128 * 6
    assert k.bytes_read(full, window, s) == (160_000 + 12_288) * 4096 == 705_691_648
    assert k.least_seconds(full, window, s, {"hbm_bytes_per_s": 819e9}) == pytest.approx(8.6165e-4, rel=1e-4)


#: what the metric files read of a run's tracer: the directory its trace was written to, named for the cell
TRACED = types.SimpleNamespace(dir=os.path.join("bench_trace", CELL))


@pytest.mark.parametrize("name, want", [
    ("full_attn_share", 800.0 / 1900.0 * 100.0),
    ("window_attn_share", (150.0 + 50.0) / 1900.0 * 100.0),
    # 2,800 positions x 4,096 B / 819e9 = 14 ns of least time over 750 ns of the kernel: the hand-made counts are
    # tiny, the arithmetic is what is checked
    ("paged_kv_roofline", 2800 * 4096 / 819e9 / 750e-9 * 100.0),
    ("attn_kv_window_share", 20.0),
])
def test_metric_files_read_the_run(name, want):
    run = {"kind": "serve", "tracer": TRACED, "window_scopes": window_scopes.reduce(DOC, SPANS),
           "peaks": {"hbm_bytes_per_s": 819e9},
           "before": {"t": 10.0, "ledger": {"attn_kv_full": 1000, "attn_kv_window": 500}},
           "after": {"t": 50.0, "ledger": {"attn_kv_full": 9000, "attn_kv_window": 2500}}}
    mod = loader.module_from("metrics", name)
    assert mod.reduce(run) == pytest.approx(want)
    entry = next(m for m in loader.manifest()["per_layer"] if m["name"] == name)
    assert (mod.NAME, mod.UNIT, mod.MOVES, mod.SOURCE, mod.LAYER) == (
        entry["name"], entry["unit"], entry["moves"], entry["source"], entry["layer"])
    assert entry["workloads"] == [CELL] and entry["moves"] == "ttft_p90_ms"


def test_the_cell_reports_what_the_issue_names():
    cell = loader.cell(CELL)
    assert cell["workload"]["chips"] == 1
    # not serve_tokens_per_s (the rate pins it below the knee) and not tpot_mean_ms (one scheduling flip is 1% below
    # the knee: PERF.md section 7); decode_launch_ms and paged_attn_busy move tpot_mean_ms and stay off, mixed_launch_ms
    # and the expert metrics are held to longdoc by tests/bench/test_bench_latent_scopes.py
    assert sorted(m["name"] for m in cell["end_to_end"]) == ["setup_s", "ttft_p90_ms"]
    assert sorted(m["name"] for m in cell["per_layer"]) == sorted([
        "queue_wait_mean_ms", "window_compiles", "full_attn_share", "window_attn_share", "paged_kv_roofline",
        "attn_kv_window_share"])
    mix = cell["traffic"]
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"dist": "lognormal", "median": 1536, "sigma": 1.2, "min": 64, "max": 16384},
        {"dist": "lognormal", "median": 160, "sigma": 0.6, "min": 16, "max": 768})
    assert (mix["arrivals"], mix["warmup_s"], mix["tpot_min_tokens"], mix["warm_rows"], mix["order_seed"]) == (
        "poisson", 15, 16, 1, 23)
    assert 0.3 < mix["rate"] < 3.0 and "0.8" in mix["rate_note"] and f"{mix['rate']:g}/s" in cell["workload"]["why"]
    # every window's longest request fits a slot's tables, and every slot at the longest fits the pool
    e = cell["config"]["bench"]["engine"]
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest <= e["max_blocks_per_seq"] * e["block_size"]
    assert e["num_blocks"] - 1 >= e["max_batch_size"] * -(-longest // e["block_size"])  # no preemption for want of blocks
    assert max(loader.module_from("reference", "exaone_moe")._BUCKETS) >= longest
