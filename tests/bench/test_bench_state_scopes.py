"""The state-space scopes' reader on a hand-made document and hand-made launch spans, the byte function of the
state traffic by hand, and the new metric files against both."""

import json
import os
import types

import pytest

from bench.harness import loader, state_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "nemotron3-nano-serve-ep8.shortchat"
CFG = json.load(open(os.path.join(ROOT, "bench", "configs", "nemotron3-nano-serve-ep8.json")))


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
        op("while.1", OFFSET, 400, "while/body/closed_call/ssm_scan/while"),   # encloses the next: keeps 300
        op("fusion.1", OFFSET + 100, 100, "while/body/closed_call/ssm_scan/while/body"),
        op("fusion.2", OFFSET + 400, 100, "while/body/closed_call/state_rw"),
        op("fusion.3", OFFSET + 500, 100, "while/body/closed_call/ssm_proj"),
        op("fusion.4", OFFSET + 600, 100, "while/body/closed_call/experts/while/body"),
        op("fusion.5", OFFSET + 700, 100, "while/body/closed_call/paged_attn"),
        op("fusion.6", OFFSET + 800, 200, "while/body/closed_call/sample"),
        op("fusion.7", OFFSET + 2000, 200, "ssm_scan/while/body", "7", "_mixed_flat_impl"),
        op("fusion.8", OFFSET + 2200, 100, "ssm_conv", "7", "_mixed_flat_impl"),
        op("fusion.9", OFFSET + 2300, 100, "ssm_gate_norm", "7", "_mixed_flat_impl"),
        op("fusion.10", OFFSET + 2400, 200, "closed_call", "7", "_mixed_flat_impl"),  # no scope
        op("fusion.11", OFFSET + 4000, 300, "while/body/closed_call/ssm_scan"),      # the second decode launch
        op("fusion.12", OFFSET + 9000, 100, "ssm_scan", "9", "_prefill_impl"),       # another program: not counted
    ],
}


def span(name, ts_ns, dur_ns, step, **args):
    return {"name": name, "cat": "engine", "ts": ts_ns / 1e9, "dur": dur_ns / 1e9, "args": dict(args, step=step)}


SPANS = [span("decode", 0.0, 1000.0, 4, state_rows=256, rows=32),
         span("mixed_step", 2000.0, 600.0, 5, state_rows=33),
         span("decode", 4000.0, 1000.0, 6, state_rows=256, rows=32),
         span("decode", 9500.0, 1000.0, 7, state_rows=256, rows=32)]  # ends outside the traced span: left out


def test_scope_of_takes_the_innermost_known_scope():
    assert state_scopes.scope_of("jit(_decode_impl)/while/body/closed_call/ssm_scan/while/body/dot_general:") == "ssm_scan"
    assert state_scopes.scope_of("jit(_decode_impl)/while/body/closed_call/state_rw/gather:") == "state_rw"
    assert state_scopes.scope_of("jit(_decode_impl)/while/body/add:") is None
    assert state_scopes.scope_of(None) is None


def test_reduce_sums_own_time_by_scope_and_the_state_traffic_of_the_decode_launches():
    out = state_scopes.reduce(DOC, SPANS)
    assert out["ns"] == 1000.0 + 600.0 + 300.0
    assert out["ns_by_scope"] == {"ssm_scan": 400.0 + 200.0 + 300.0, "state_rw": 100.0, "ssm_proj": 100.0,
                                  "experts": 100.0, "paged_attn": 100.0, "sample": 200.0, "ssm_conv": 100.0,
                                  "ssm_gate_norm": 100.0, "unscoped": 200.0}
    # two decode launches lie inside the traced span: their rows, and the decode program's time under ssm_scan and
    # state_rw inside them (the mixed program's scan is no decode launch's)
    assert out["decode"] == {"launches": 2, "state_rows": 512, "traffic_ns": 400.0 + 100.0 + 300.0}
    # without the program's spans the shares are read all the same, the roofline's part is not
    assert state_scopes.reduce(DOC)["decode"] is None and state_scopes.reduce(DOC)["ns"] == 1900.0


def test_a_program_without_state_scopes_reads_nothing():
    dense = {"modules": [["jit__decode_impl(3)", 0.0, 100.0]], "extent_ns": [0.0, 100.0], "host": [],
             "ops": [["fusion.1", 0.0, 100.0, "jit(_decode_impl)/while/body/closed_call/qkv/dot_general:", "3"]]}
    assert state_scopes.reduce(dense) is None
    assert state_scopes.share({"kind": "serve", "tracer": None}, ("ssm_scan",)) is None
    assert state_scopes.counter_delta({"kind": "serve", "before": {"ledger": {}}, "after": {"ledger": {}}},
                                      "state_rows") is None
    for name in ("ssm_share", "ssm_state_roofline", "state_rows_live_share"):
        assert loader.module_from("metrics", name).reduce({"kind": "serve", "tracer": None, "before": {"ledger": {}},
                                                           "after": {"ledger": {}}}) is None


def test_state_bytes_by_hand():
    k = loader.module_from("kernels", "ssm_state")
    s = k.shape_of(CFG)
    assert s == {"layers": 23, "heads": 64, "head_dim": 64, "state": 128, "bytes": 4}
    assert k.row_bytes(s) == 64 * 64 * 128 * 4 == 2_097_152
    # a decode launch of 32 slots and 8 sub-steps: 256 row-steps, read and written in 23 scan layers
    assert k.bytes_moved(256, s) == 256 * 23 * 2_097_152 * 2 == 24_696_061_952
    assert k.least_seconds(256, s, {"hbm_bytes_per_s": 819e9}) == pytest.approx(0.030154, rel=1e-4)


#: what the metric files read of a run's tracer: the directory its trace was written to, named for the cell
TRACED = types.SimpleNamespace(dir=os.path.join("bench_trace", CELL))


@pytest.mark.parametrize("name, want", [
    ("ssm_share", (900.0 + 100.0 + 100.0 + 100.0 + 100.0) / 1900.0 * 100.0),
    # 512 row-steps x 23 x 2 MiB x 2 / 819e9 = 60.3 ms of least time over 800 ns under the two scopes: the
    # hand-made times are tiny, the arithmetic is what is checked
    ("ssm_state_roofline", 512 * 23 * 2_097_152 * 2 / 819e9 / 800e-9 * 100.0),
    ("state_rows_live_share", 62.5),
])
def test_metric_files_read_the_run(name, want):
    run = {"kind": "serve", "tracer": TRACED, "state_scopes": state_scopes.reduce(DOC, SPANS),
           "peaks": {"hbm_bytes_per_s": 819e9},
           "before": {"t": 10.0, "ledger": {"state_rows": 1000, "state_rows_live": 500}},
           "after": {"t": 50.0, "ledger": {"state_rows": 9000, "state_rows_live": 5500}}}
    mod = loader.module_from("metrics", name)
    assert mod.reduce(run) == pytest.approx(want)
    entry = next(m for m in loader.manifest()["per_layer"] if m["name"] == name)
    assert (mod.NAME, mod.UNIT, mod.MOVES, mod.SOURCE, mod.LAYER) == (
        entry["name"], entry["unit"], entry["moves"], entry["source"], entry["layer"])
    assert entry["workloads"] == [CELL]


def test_the_cell_reports_what_the_issue_names():
    cell = loader.cell(CELL)
    # not tpot_mean_ms: ten runs on ten seeds spread it 0.74% of the median (the last four, on the final tree, 1.0%),
    # where a new cell has to stay under 0.5% in each of two sets of six (PERF.md section 7, PR 33). ISSUE 33's way
    # out, as longdoc's: judged on TTFT, the three new metrics name ttft_p90_ms, and decode_launch_ms and
    # paged_attn_busy (which move tpot_mean_ms) stay off
    assert sorted(m["name"] for m in cell["end_to_end"]) == ["setup_s", "ttft_p90_ms"]
    assert sorted(m["name"] for m in cell["per_layer"]) == sorted([
        "queue_wait_mean_ms", "window_compiles", "ssm_share", "ssm_state_roofline", "state_rows_live_share"])
    # ISSUE 33 also asked for the cell on mixed_launch_ms, experts_share, expert_local_share and
    # expert_load_max_over_mean: tests/bench/test_bench_latent_scopes.py (PR 26's, not this PR's to edit) holds
    # each of those to ["dots3-note-serve-ep8.longdoc"], so they are left as they were (CHANGES.md, PR 33)
    mix = cell["traffic"]
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 16, "max": 2048},
        {"dist": "lognormal", "median": 128, "sigma": 0.6, "min": 16, "max": 512})
    assert (mix["arrivals"], mix["warmup_s"], mix["tpot_min_tokens"], mix["warm_rows"], mix["order_seed"]) == (
        "poisson", 15, 16, 1, 23)
    # every window's longest request fits a slot's tables: prompt + answer within blocks x block size
    e = cell["config"]["bench"]["engine"]
    assert mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"] <= e["max_blocks_per_seq"] * e["block_size"]
    assert e["num_blocks"] - 1 >= e["max_batch_size"] * e["max_blocks_per_seq"]  # no preemption for want of blocks
    assert e["prefill_chunk_tokens"] % cell["config"]["chunk_size"] == 0
