"""The diffusion kind's scopes reader on a hand-made document and hand-made launch spans, the byte function of the paged
kernel under a block mask by hand, the new metric files against both, and the cell as ISSUE 44 names it."""

import json
import os
import types

import pytest

from bench.harness import diffusion_scopes, loader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "sdar-30b-a3b-serve-ep8.cot"
CFG = json.load(open(os.path.join(ROOT, "bench", "configs", "sdar-30b-a3b-serve-ep8.json")))
BODY = "while/body/denoise/while/body/closed_call"  # a pass of the decode program's scan, a layer of the stack's


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
        op("ragged_paged_attention.1", OFFSET, 300, f"{BODY}/paged_attn"),
        op("fusion.2", OFFSET + 300, 100, f"{BODY}/qk_norm"),
        op("while.3", OFFSET + 400, 200, f"{BODY}/experts/while"),      # encloses the next: keeps 150
        op("fusion.4", OFFSET + 450, 50, f"{BODY}/experts/while/body"),
        op("fusion.5", OFFSET + 600, 100, "while/body/denoise/lm_head"),
        op("fusion.6", OFFSET + 700, 100, "while/body/denoise/confidence"),
        op("fusion.7", OFFSET + 800, 50, "while/body/denoise/unmask"),
        op("fusion.8", OFFSET + 850, 50, "while/body/commit"),
        op("fusion.9", OFFSET + 900, 100, "while/body/denoise"),        # the mask id laid over the masked positions
        op("ragged_paged_attention.3", OFFSET + 2000, 100, "while/body/closed_call/paged_attn", "7", "_mixed_flat_impl"),
        op("fusion.10", OFFSET + 2100, 300, "while/body/closed_call/qkv", "7", "_mixed_flat_impl"),
        op("fusion.11", OFFSET + 2400, 200, "closed_call", "7", "_mixed_flat_impl"),  # no scope
        op("ragged_paged_attention.1", OFFSET + 4000, 200, f"{BODY}/paged_attn"),     # the second decode launch
        op("fusion.6", OFFSET + 4200, 100, "while/body/denoise/confidence"),
        op("ragged_paged_attention.9", OFFSET + 9000, 100, "paged_attn", "9", "_prefill_impl"),  # another program: not counted
    ],
}


def span(name, ts_ns, dur_ns, step, **args):
    return {"name": name, "cat": "engine", "ts": ts_ns / 1e9, "dur": dur_ns / 1e9, "args": dict(args, step=step)}


SPANS = [span("decode", 0.0, 1000.0, 4, steps=10, attn_kv_visible=1000, rows=32),
         span("mixed_step", 2000.0, 600.0, 5, attn_kv_visible=77),
         span("decode", 4000.0, 1000.0, 6, steps=10, attn_kv_visible=1800, rows=32),
         span("decode", 9500.0, 1000.0, 7, steps=10, attn_kv_visible=5, rows=32)]  # ends outside the traced span


def test_scope_of_takes_the_innermost_known_scope():
    assert diffusion_scopes.scope_of(f"jit(_decode_impl)/{BODY}/paged_attn/pallas_call:") == "paged_attn"
    assert diffusion_scopes.scope_of("jit(_decode_impl)/while/body/denoise/confidence/reduce_max:") == "confidence"
    assert diffusion_scopes.scope_of("jit(_decode_impl)/while/body/denoise/select_n:") == "denoise"
    assert diffusion_scopes.scope_of("jit(_decode_impl)/while/body/add:") is None and diffusion_scopes.scope_of(None) is None


def test_reduce_sums_own_time_by_scope_and_the_decode_launches():
    out = diffusion_scopes.reduce(DOC, SPANS)
    assert out["ns"] == 1000.0 + 600.0 + 300.0
    assert out["ns_by_scope"] == {"paged_attn": 300.0 + 100.0 + 200.0, "qk_norm": 100.0, "experts": 200.0,
                                  "lm_head": 100.0, "confidence": 200.0, "unmask": 50.0, "commit": 50.0,
                                  "denoise": 100.0, "qkv": 300.0, "unscoped": 200.0}
    # two decode launches lie inside the traced span: their passes and counts, the decode program's device time in
    # its runs inside them and the kernel's part of it (the mixed program's operations are no decode launch's)
    assert out["decode"] == {"launches": 2, "steps": 20, "attn_kv_visible": 2800, "program_ns": 1300.0, "kernel_ns": 500.0}
    # without the program's spans the shares are read all the same, the per-pass and roofline parts are not
    assert diffusion_scopes.reduce(DOC)["decode"] is None and diffusion_scopes.reduce(DOC)["ns"] == 1900.0


NEW = ("denoise_passes_per_token", "denoise_pass_ms", "block_attn_share", "confidence_share", "block_attn_roofline")


def test_a_program_without_the_diffusion_scopes_reads_nothing():
    dense = {"modules": [["jit__decode_impl(3)", 0.0, 100.0]], "extent_ns": [0.0, 100.0], "host": [],
             "ops": [["ragged_paged_attention.1", 0.0, 100.0, "jit(_decode_impl)/while/body/closed_call/paged_attn/x:", "3"]]}
    assert diffusion_scopes.reduce(dense) is None  # the llama kind's paged_attn alone is not this kind's program
    assert diffusion_scopes.share({"kind": "serve", "tracer": None}, ("paged_attn",)) is None
    for name in NEW:  # a parent commit's run: no such ledger total, no such scope; nothing returned, nothing raised
        assert loader.module_from("metrics", name).reduce({"kind": "serve", "tracer": None, "before": {"ledger": {}},
                                                           "after": {"ledger": {"fed": 5}}}) is None


def test_paged_block_attention_bytes_by_hand():
    k = loader.module_from("kernels", "paged_block_attention")
    s = k.shape_of(CFG)
    assert s == {"kv_heads": 4, "head_dim": 128, "bytes": 2} and k.position_bytes(s) == 2048
    # a pass of 32 rows whose blocks start at 600 cached positions: each sees 604 in each of 48 layers
    visible = 32 * 604 * 48
    assert k.bytes_read(visible, s) == 927_744 * 2048 == 1_900_019_712
    assert k.least_seconds(visible, s, {"hbm_bytes_per_s": 819e9}) == pytest.approx(2.3199e-3, rel=1e-4)


#: what the metric files read of a run's tracer: the directory its trace was written to, named for the cell
TRACED = types.SimpleNamespace(dir=os.path.join("bench_trace", CELL))


@pytest.mark.parametrize("name, want", [
    ("denoise_passes_per_token", (4000 + 1000) / 3900),
    ("denoise_pass_ms", 1300.0 / 20 / 1e6),
    ("block_attn_share", 600.0 / 1900.0 * 100.0),
    ("confidence_share", 250.0 / 1900.0 * 100.0),
    # 2,800 positions x 2,048 B / 819e9 = 7 ns of least time over 500 ns of the kernel: the hand-made counts are tiny,
    # the arithmetic is what is checked
    ("block_attn_roofline", 2800 * 2048 / 819e9 / 500e-9 * 100.0),
])
def test_metric_files_read_the_run(name, want):
    run = {"kind": "serve", "tracer": TRACED, "diffusion_scopes": diffusion_scopes.reduce(DOC, SPANS),
           "peaks": {"hbm_bytes_per_s": 819e9},
           "before": {"t": 10.0, "ledger": {"denoise_passes": 1000, "commit_passes": 250, "tokens_emitted": 100}},
           "after": {"t": 50.0, "ledger": {"denoise_passes": 5000, "commit_passes": 1250, "tokens_emitted": 4000}}}
    mod = loader.module_from("metrics", name)
    assert mod.reduce(run) == pytest.approx(want)
    entry = next(m for m in loader.manifest()["per_layer"] if m["name"] == name)
    assert (mod.NAME, mod.UNIT, mod.MOVES, mod.SOURCE, mod.LAYER) == (
        entry["name"], entry["unit"], entry["moves"], entry["source"], entry["layer"])
    assert entry["workloads"] == [CELL] and entry["moves"] == "tpot_mean_ms"


def test_the_cell_reports_what_the_issue_names():
    man = loader.manifest()
    assert len(man["workloads"]) == 7 and man["workloads"][-1]["name"] == CELL
    cell = loader.cell(CELL)
    assert cell["workload"]["chips"] == 1
    # not ttft_p90_ms (a tail of a handful of requests, and the prefill it mostly reads is chat's) and not
    # serve_tokens_per_s (pinned by the rate)
    assert sorted(m["name"] for m in cell["end_to_end"]) == ["setup_s", "tpot_mean_ms"]
    assert sorted(m["name"] for m in cell["per_layer"]) == sorted(NEW + ("decode_launch_ms", "tpot_p90_ms"))
    mix = cell["traffic"]
    assert (mix["prompt_tokens"], mix["output_tokens"]) == (
        {"dist": "lognormal", "median": 192, "sigma": 0.7, "min": 32, "max": 1024},
        {"dist": "lognormal", "median": 384, "sigma": 0.5, "min": 128, "max": 1024})
    assert (mix["arrivals"], mix["warmup_s"], mix["tpot_min_tokens"], mix["warm_rows"], mix["order_seed"]) == (
        "poisson", 15, 16, 1, 23)
    assert 0.5 < mix["rate"] < 5.0 and "0.8" in mix["rate_note"] and f"{mix['rate']:g}/s" in cell["workload"]["why"]
    # every window's longest request fits a slot's tables; prompts are no multiples of 4 only: the partial block
    e = cell["config"]["bench"]["engine"]
    longest = mix["prompt_tokens"]["max"] + mix["output_tokens"]["max"]
    assert longest <= e["max_blocks_per_seq"] * e["block_size"]
    assert e["prefill_chunk_tokens"] % 4 == 0 and e["block_size"] % 4 == 0 and e["enable_prefix_cache"] is False
    assert max(loader.module_from("reference", "sdar_moe")._BUCKETS) >= longest + mix["output_tokens"]["max"] + 4
    from bench.harness import traffic

    plan = traffic.open_loop_plan(mix, 45)
    assert {r["prompt_tokens"] % 4 for r in plan["requests"]} == {0, 1, 2, 3}
