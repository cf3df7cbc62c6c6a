"""The reduction from trace events to numbers, on a hand-made document with
known answers and on a small trace recorded on the chip by this PR."""

import gzip
import json
import os

import pytest

from bench.harness import trace_reduce

FIXTURES = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures")
US = 1000  # ns


def _doc():
    ops = [
        ["while.1", 0, 100 * US],                    # encloses the next two
        ["fusion.7", 10 * US, 30 * US],
        ["flash_attention_fwd.3", 50 * US, 40 * US],
        ["fusion.9", 120 * US, 30 * US],             # 120-150, overlapped 140-150 by fusion.8
        ["fusion.8", 140 * US, 40 * US],             # 140-180
        ["flash_attention_fwd.4", 300 * US, 100 * US],
    ]
    modules = [["jit_train_step(123)", 0, 180 * US], ["jit_train_step(123)", 300 * US, 100 * US]]
    host = [["bench_step", 0, 200 * US], ["bench_step", 290 * US, 120 * US]]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": ops},
                                             {"name": "XLA Modules", "events": modules}]},
        {"name": "/host:CPU", "lines": [{"name": "main/1", "events": host}]}],
        "extent_ns": [0, 500 * US]}


def test_busy_idle_and_window():
    r = trace_reduce.reduce_events(_doc())
    assert r["window_s"] == pytest.approx(500e-6)
    # busy: 0-100, 120-180, 300-400 = 260 us
    assert r["busy_s"] == pytest.approx(260e-6)
    assert r["busy_s_by_device"] == [pytest.approx(260e-6)]
    assert trace_reduce.reduce_events(_doc(), window_ns=1000 * US)["window_s"] == pytest.approx(1e-3)


def test_time_by_name_is_own_time():
    r = trace_reduce.reduce_events(_doc())
    assert r["op_seconds"]["while.1"] == pytest.approx(30e-6)  # 100 less its two children
    assert r["op_seconds"]["fusion.7"] == pytest.approx(30e-6)
    seconds, calls = trace_reduce.kernel_seconds(r, ("flash_attention_fwd",))
    assert seconds == pytest.approx(140e-6) and calls == 2
    assert trace_reduce.kernel_seconds(r, ("ragged_paged_attention",)) == (0, 0)
    assert r["module_runs_s"] == {"jit_train_step": [pytest.approx(180e-6), pytest.approx(100e-6)]}


def test_gap_classes_and_breakdown():
    r = trace_reduce.reduce_events(_doc())
    # gaps: 100-120 (inside the first step span), 180-300 (midpoint 240: between spans)
    assert r["idle_gap_seconds"] == {
        "inside a step annotation (host scheduling)": pytest.approx(20e-6),
        "between step annotations (loop, streaming, feed)": pytest.approx(120e-6)}
    assert r["longest_gaps_s"][:2] == [pytest.approx(120e-6), pytest.approx(20e-6)]
    b = trace_reduce.breakdown(r)
    assert b["device_ops"][0] == ["flash_attention_fwd.4", pytest.approx(100e-6)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) == 2


def test_a_trace_without_device_operations_is_refused():
    doc = _doc()
    doc["planes"] = doc["planes"][1:]
    with pytest.raises(ValueError, match="no device operation"):
        trace_reduce.reduce_events(doc)


def test_short_names():
    line = "%flash_attention_fwd.15 = (bf16[56,2048,64]{2,1,0}) custom-call(bf16[56,2048,64] %bitcast.423)"
    assert trace_reduce.short_name(line) == "flash_attention_fwd.15"
    assert trace_reduce.short_name("jit_train_step(1)") == "jit_train_step(1)"
    assert trace_reduce.module_base("jit_train_step(16946301043931940608)") == "jit_train_step"


def test_recorded_chip_trace_one_engine_step():
    """One engine step of qwen2-1.5b-serve.chat recorded on a TPU v5 lite by PR 23:
    a prefill launch, then a decode launch (8 tokens, batch 16, 28 layers)."""
    with gzip.open(os.path.join(FIXTURES, "serve_chat_one_step.events.json.gz"), "rt") as f:
        doc = json.load(f)
    r = trace_reduce.reduce_events(doc)
    assert r["window_s"] == pytest.approx(1.18)
    assert r["busy_s"] == pytest.approx(1.093323689, rel=1e-9)
    assert r["host_step_spans"] == 1
    assert r["idle_gap_seconds"] == {"inside a step annotation (host scheduling)": pytest.approx(0.07422247)}
    assert r["longest_gaps_s"][0] == pytest.approx(0.042391646)
    seconds, calls = trace_reduce.kernel_seconds(r, ("ragged_paged_attention",))
    assert calls == 28 * 8 + 28 and seconds == pytest.approx(0.214379481, rel=1e-8)
    assert r["module_runs_s"]["jit__decode_impl"] == [pytest.approx(1.007070232)]
    assert r["module_runs_s"]["jit__prefill_impl"] == [pytest.approx(0.086206303)]
    assert trace_reduce.breakdown(r)["device_ops"][0][0] == "ragged_paged_attention.10"
    # the per-layer readers on the same numbers
    from bench.harness import loader

    run = {"kind": "serve", "trace": r}
    assert loader.module_from("metrics", "decode_launch_ms").reduce(run) == pytest.approx(1007.070232)
    assert loader.module_from("metrics", "prefill_launch_ms").reduce(run) == pytest.approx(86.206303)
    assert loader.module_from("metrics", "paged_attn_busy").reduce(run) == pytest.approx(19.6080, rel=1e-4)
    assert loader.module_from("metrics", "device_idle.serve").reduce(run) == pytest.approx(7.3454, rel=1e-4)


def test_idle_and_roofline_shares():
    r = trace_reduce.reduce_events(_doc())
    assert trace_reduce.idle_share({"trace": r}) == pytest.approx((1 - 260 / 500) * 100)
    assert trace_reduce.idle_share({}) is None
    config = {"num_attention_heads": 14, "num_key_value_heads": 2, "hidden_size": 896}
    run = {"trace": r, "config": config, "rows_per_chip": 4, "seq_len": 2048,
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}}
    # two forward calls, 140 us: each needs 2 * 4*14*2048*2048*64 FLOP at the least (compute bound)
    least = 2 * (2 * 4 * 14 * 2048 * 2048 * 64) / 197e12
    assert trace_reduce.roofline_share(run, "flash_attention", ("flash_attention_fwd",)) == \
        pytest.approx(least / 140e-6 * 100)
    assert trace_reduce.roofline_share(run, "flash_attention", ("flash_attention_bwd_dq",)) is None
    assert trace_reduce.roofline_share({}, "flash_attention", ("flash_attention_fwd",)) is None
