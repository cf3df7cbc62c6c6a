"""``bench/harness/program_spans.py`` on a hand-made document with known answers
(a gap split across two phases, an uncovered gap, an operation without a scope,
a ring that dropped spans), its wire-format reader on a trace recorded here,
each new reader with nothing to read, and the entries of ``bench/program_metrics.json``
as ``bench/run_program_metrics.py`` adds them to their cell."""

import glob
import json
import os
import re

import pytest

from bench.harness import loader, program_spans

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
US = 1000.0  # ns
T0 = 100.0   # the tracer's clock reads T0 seconds where the trace's reads 0
NEW = ("inbox_wait_mean_ms", "idle_loop_share", "idle_sched_share", "idle_launch_share", "batch_occupancy",
       "paged_attn_roofline", "decode_matmul_share", "decode_kv_pool_share", "decode_unscoped_share")
PATH = "jit(_decode_impl)/while/body/closed_call/while/body/closed_call/"


def _doc():
    ops = [  # [name, start, duration, op_name, program]
        ["while.1", 0, 100 * US, "jit(_decode_impl)/while:", "7"],             # encloses the next
        ["fusion.1", 10 * US, 80 * US, PATH + "qkv/dot_general:", "7"],
        ["ragged_paged_attention.3", 140 * US, 100 * US, PATH + "paged_attn/pallas_call:", "7"],
        ["copy.9", 240 * US, 20 * US, "jit(_decode_impl)/while:", "7"],        # the compiler's: no scope
        ["fusion.2", 300 * US, 100 * US, PATH + "mlp/dot_general:", "7"],
        ["fusion.5", 400 * US, 10 * US, "jit(_prefill_impl)/qkv/dot_general:", "8"],  # another program
    ]
    modules = [["jit__decode_impl(7)", 0, 400 * US], ["jit__prefill_impl(8)", 400 * US, 10 * US]]
    host = [  # [name, start, duration, args]: the loop thread
        ["engine_step", 80 * US, 210 * US, {"step_num": 1}],                  # 80-290
        ["launch_build", 90 * US, 30 * US, {"step": 1}],                      # 90-120
        ["decode", 120 * US, 150 * US, {"step": 1}],                          # 120-270
        ["dispatch", 120 * US, 10 * US, {}],
        ["wait", 130 * US, 140 * US, {}],                                     # 130-270
        ["emit", 270 * US, 10 * US, {"step": 1}],                             # 270-280; 280-290 is nobody's
        ["loop_finish", 290 * US, 5 * US, {}],                                # 290-295; 295-300 is nobody's
    ]
    return {"ops": ops, "modules": modules, "host": host, "extent_ns": [0.0, 500 * US]}


def _span(name, start_us, dur_us, cat="engine", trace=None, **args):
    return {"name": name, "cat": cat, "ts": T0 + start_us * 1e-6, "dur": dur_us * 1e-6, "trace": trace, "args": args}


def _spans():
    geometry = dict(rows_live=3, rows=4, kv_positions=1000)
    return [
        _span("decode", 120, 150, step=1, **geometry),
        _span("inbox", 10, 50, cat="request", trace="req-1", step=0),
        _span("queue", 60, 10, cat="request", trace="req-1"),
        _span("prefill", 70, 40, cat="request", trace="req-1"),
        _span("request", 10, 300, cat="request", trace="req-1"),
        _span("inbox", 900, 70, cat="request", trace="req-2", step=3),        # finished after the window
        _span("request", 900, 4000, cat="request", trace="req-2"),
    ]


RING = {"dropped": 0, "kept_since": T0 - 1.0}
WINDOW = (T0, T0 + 1e-3)


def test_a_gap_is_split_by_the_innermost_phase_open_at_it():
    out = program_spans.reduce(_doc(), _spans(), RING, WINDOW)
    h = out["host_phases"]
    # device gaps: 100-140 and 260-300; the last operation ends at 410, the trace at 500
    assert (h["window_ms"], h["busy_ms"], h["idle_ms"]) == (0.5, 0.33, 0.17)
    # 100-120 launch_build; 120-130 dispatch; 130-140 and 260-270 wait; 270-280 emit;
    # 280-290 inside the step and in no phase; 290-295 loop_finish; 295-300 in nothing at all
    assert h["idle_ms_by_phase"] == {"launch_build": 0.02, "wait": 0.02, "dispatch": 0.01, "emit": 0.01,
                                     "engine_step": 0.01, "loop_finish": 0.005, "decode": 0.0}
    assert h["idle_ms_by_class"] == {"loop": 0.005, "sched": 0.03, "launch": 0.03, "uncovered": 0.015}
    assert h["idle_ms_at_the_edges"] == pytest.approx(0.17 - 0.08)
    m = out["metrics"]
    assert m["idle_loop_share"] == pytest.approx(1.0) and m["idle_sched_share"] == pytest.approx(6.0)
    assert m["idle_launch_share"] == pytest.approx(6.0) and h["uncovered_share"] == pytest.approx(3.0)
    idle = h["idle_ms"] / h["window_ms"] * 100
    assert m["idle_loop_share"] + m["idle_sched_share"] + m["idle_launch_share"] <= idle


def test_device_time_by_scope_and_an_operation_without_one():
    out = program_spans.reduce(_doc(), _spans(), RING, WINDOW)
    d = out["device_scopes"]
    # the while keeps what its body does not cover (20 us), beside the copy the compiler inserted
    assert d["ms_by_scope"] == {"paged_attn": 0.1, "mlp": 0.1, "qkv": 0.08, "unscoped": 0.04}
    assert d["device_ms"] == 0.32 and ["copy.9", "unscoped", 0.02] in d["largest_ops"]
    m = out["metrics"]
    assert m["decode_matmul_share"] == pytest.approx(56.25)  # qkv + mlp; the prefill program's qkv is not counted
    assert m["decode_unscoped_share"] == pytest.approx(12.5) and m["decode_kv_pool_share"] == 0.0
    assert program_spans.scope_of(PATH + "sample/sort:") == "sample"
    assert program_spans.scope_of("jit(_decode_impl)/while:") is None and program_spans.scope_of(None) is None


def test_launch_geometry_the_kernel_s_time_and_the_request_clock():
    out = program_spans.reduce(_doc(), _spans(), RING, WINDOW)
    assert out["metrics"]["batch_occupancy"] == pytest.approx(75.0)
    assert out["kv_positions"] == 1000 and out["paged_kernel_s"] == pytest.approx(100e-6)
    assert out["metrics"]["inbox_wait_mean_ms"] == pytest.approx(0.05)       # req-2 finished after the window
    h = out["host_phases"]
    assert h["server_ttft_ms"]["n"] == 1 and h["server_ttft_ms"]["mean"] == pytest.approx(0.1)
    assert h["clock"] == {"mirrored_spans": 1, "offset_spread_ms": 0.0, "ring_dropped": 0}
    assert h["launch_span_ms"] == 0.15 and h["device_busy_in_launch_spans_ms"] == pytest.approx(0.12)
    # the roofline reader's arithmetic: 1000 positions x 28 layers x 2 x 2 heads x 128 x 2 bytes over 819 GB/s
    k = loader.module_from("kernels", "paged_attention", root=ROOT)
    config = json.load(open(os.path.join(ROOT, "bench", "configs", "qwen2-1.5b-serve.json")))
    shape = k.shape_of(config)
    assert shape == {"layers": 28, "kv_heads": 2, "head_dim": 128, "bytes": 2}
    # the element is the pool's, not the weights': an engine that quantises the pool reads half the bytes
    config["bench"]["engine"]["kv_cache_quant"] = "int8"
    assert k.shape_of(config)["bytes"] == 1
    assert k.bytes_read(1000, shape) == 28_672_000
    assert k.least_seconds(1000, shape, {"hbm_bytes_per_s": 819e9}) == pytest.approx(35.0e-6, rel=1e-3)


def test_nothing_where_the_ring_dropped_spans_or_the_program_mirrors_none():
    # spans fell off the ring after the traced span began: the tables would have holes
    assert program_spans.reduce(_doc(), _spans(), {"dropped": 3, "kept_since": T0 + 50e-6}, WINDOW) is None
    # drops that all lie before the traced span and the window do no harm
    kept = {"dropped": 3, "kept_since": T0 - 1.0}
    assert program_spans.reduce(_doc(), _spans(), kept, WINDOW) is not None
    # the window began 3 s before the capture and the ring wrapped in between: the request
    # clock (inbox, server-side TTFT) is read over the whole window, so its early requests are gone
    assert program_spans.reduce(_doc(), _spans(), kept, (T0 - 3.0, T0 + 1e-3)) is None
    assert program_spans.reduce(_doc(), _spans(), dict(kept, dropped=0), (T0 - 3.0, T0 + 1e-3)) is not None
    # what is known of the ring: everything recorded since the first engine span left in it ended
    # (a request's spans start long before they are recorded, at its finish)
    spans = [_span("request", -5e6, 4e6, cat="request", trace="r"), _span("loop_intake", 10, 5, cat="engine_loop")]
    assert program_spans.ring_state(2, spans) == {"dropped": 2, "kept_since": pytest.approx(T0 + 15e-6)}
    assert program_spans.ring_state(2, spans[:1]) == {"dropped": 2, "kept_since": None}
    assert program_spans.reduce(_doc(), _spans(), {"dropped": 2, "kept_since": None}, WINDOW) is None
    # the parent of PR 24: an engine_step annotation and no mirrored launch span, so no common clock
    doc = _doc()
    doc["host"] = [e for e in doc["host"] if e[0] == "engine_step"]
    assert program_spans.reduce(doc, _spans(), RING, WINDOW) is None
    # an executable without scopes under a program that has them (jax keeps metadata out of the
    # compile cache's key, so one cached by an older build is found again): the guard reads 100%
    doc = _doc()
    for op in doc["ops"]:
        op[3] = None
    m = program_spans.reduce(doc, _spans(), RING, WINDOW)["metrics"]
    assert m["idle_sched_share"] == pytest.approx(6.0)
    assert m["decode_unscoped_share"] == pytest.approx(100.0) and m["decode_matmul_share"] == 0.0
    # decode launches without geometry args
    spans = [dict(s, args={"step": 1}) if s["name"] == "decode" else s for s in _spans()]
    out = program_spans.reduce(_doc(), spans, RING, WINDOW)
    assert out["metrics"]["batch_occupancy"] is None and out["kv_positions"] is None


@pytest.mark.parametrize("name", NEW)
def test_each_new_reader_returns_nothing_without_its_span_or_scope(name, tmp_path):
    mod = loader.module_from("metrics", name, root=ROOT)
    assert mod.reduce({}) is None
    assert mod.reduce({"kind": "train", "tracer": object()}) is None

    class NoTrace:  # a serving run whose trace is not there: the reader says nothing and does not raise
        dir = str(tmp_path / "qwen2-1.5b-serve.chat")

        def xplane_path(self):
            raise FileNotFoundError(self.dir)

    run = {"kind": "serve", "tracer": NoTrace(), "before": {"t": 0.0}, "after": {"t": 1.0}}
    assert mod.reduce(run) is None and run["program_spans"] is None


MAN = loader.manifest(ROOT)
PROPOSED = loader.load_json("program_metrics.json", root=ROOT)["per_layer"]


@pytest.mark.parametrize("metric", PROPOSED, ids=lambda e: e["name"])
def test_each_kept_entry_could_be_appended_to_the_manifest_as_it_is(metric):
    assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    assert re.match(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$", metric["name"]) and re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", metric["unit"])
    assert metric["better"] in ("lower", "higher") and metric["source"] in ("device_trace", "program_span")
    assert metric["name"] not in [m["name"] for m in MAN["per_layer"] + MAN["end_to_end"]]
    assert metric["layer"] in [m["layer"] for m in MAN["per_layer"]]  # a layer the benchmark names, letter for letter
    mod = loader.module_from("metrics", metric["name"], root=ROOT)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == tuple(
        metric[k] for k in ("name", "unit", "layer", "moves", "source"))
    moved = next(m for m in MAN["end_to_end"] if m["name"] == metric["moves"])
    for w in metric["workloads"]:
        assert w in [x["name"] for x in MAN["workloads"]] and w in moved.get("workloads", [w])


def test_the_builders_command_adds_the_kept_entries_to_their_cell_only(monkeypatch):
    from bench import run as bench_run
    from bench import run_program_metrics

    assert [m["name"] for m in PROPOSED] == list(NEW)
    cell_of, seen = loader.cell, {}

    def main(argv):  # run.py's own main finds its cell through the loader
        seen.update((w["name"], [m["name"] for m in loader.cell(w["name"], root=ROOT)["per_layer"]])
                    for w in MAN["workloads"])
        return 0

    monkeypatch.setattr(bench_run, "main", main)
    assert run_program_metrics.main([]) == 0 and loader.cell is cell_of
    before = {w["name"]: [m["name"] for m in loader.cell(w["name"], root=ROOT)["per_layer"]] for w in MAN["workloads"]}
    assert seen["qwen2-1.5b-serve.chat"] == before["qwen2-1.5b-serve.chat"] + list(NEW)
    assert seen["qwen2-0.5b-pretrain.seq2k"] == before["qwen2-0.5b-pretrain.seq2k"]


def test_the_wire_reader_finds_host_annotations_and_their_args(tmp_path):
    """A capture made here, on the CPU: there is no device plane, and the host
    annotations come back with their args and on one clock."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x @ x)
    f(jnp.ones((8, 8))).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.StepTraceAnnotation("engine_step", step_num=41):
        with jax.profiler.TraceAnnotation("decode", step=41, rows=4, program="decode") as span:
            f(jnp.ones((8, 8))).block_until_ready()
            span.set_metadata(kv_positions=1234)
    with jax.profiler.TraceAnnotation("elsewhere"):
        pass
    jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    doc = program_spans.read_xplane(path)
    assert doc["ops"] == [] and doc["modules"] == []
    rows = {name: (start, dur, args) for name, start, dur, args in doc["host"]}
    assert rows["engine_step"][2]["step_num"] == 41
    assert rows["decode"][2] == {"step": 41, "rows": 4, "program": "decode", "kv_positions": 1234}
    step, decode = rows["engine_step"], rows["decode"]
    assert step[0] <= decode[0] and decode[0] + decode[1] <= step[0] + step[1]
    lo, hi = doc["extent_ns"]
    assert lo <= step[0] and step[0] + step[1] <= hi
    assert program_spans.reduce(doc, [], {"dropped": 0, "kept_since": None}, (0.0, 1.0)) is None  # no device operation
