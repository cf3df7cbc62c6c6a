"""One timeline from submission to device operation (ISSUE 24): the request
clock starts at submit (``inbox`` phase), live engine and loop spans are
mirrored to the profiler's clock, the serving step programs carry named
scopes, and every launch records its geometry."""

import glob
import os
import re
import threading
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # the described-chip compile below logs under /tmp otherwise

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams
from paddlenlp_tpu.experimental.backend import launch_geometry, samp_arrays
from paddlenlp_tpu.experimental.inference_model import PagedInferenceModel
from paddlenlp_tpu.experimental.launch_pack import layout_of, packed_size
from paddlenlp_tpu.experimental.paged_cache import PagedKVPool
from paddlenlp_tpu.observability.goodput import LAUNCH_GEOMETRY, GoodputLedger
from paddlenlp_tpu.observability.span_catalog import SPAN_CATALOG
from paddlenlp_tpu.observability.tracer import TRACER, SpanTracer
from paddlenlp_tpu.serving import EngineLoop, MetricsRegistry
from paddlenlp_tpu.serving.engine_loop import ATTRIBUTION_PHASES, request_attribution
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM, Qwen2Config, Qwen2ForCausalLM

LAYER_SCOPES = ("attn_norm", "qkv", "rope", "kv_write", "o_proj", "mlp_norm", "mlp")
PROGRAM_SCOPES = ("embed",) + LAYER_SCOPES + ("final_norm", "lm_head", "sample", "bookkeeping")


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=256,
                      eos_token_id=None, pad_token_id=0, use_scan_layers=True)
    return LlamaForCausalLM.from_config(cfg, seed=0)


def make_engine(model, **kw):
    defaults = dict(max_batch_size=4, block_size=4, num_blocks=128, max_blocks_per_seq=32, decode_steps=4)
    defaults.update(kw)
    return InferenceEngine(model, **defaults)


# --------------------------------------------------------------------- (a) scopes
def _program_text(infer, program, batch=2, vocab=96, table=8, pool=None, aval=None):
    """Compiled HLO text of one serving step program at a tiny size (nothing runs)."""
    aval = aval or (lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype))
    params = jax.tree.map(lambda a: aval(a.shape, a.dtype), infer.model.params)
    ints = lambda *shape: aval(shape, jnp.int32)
    if program == "decode":
        fn, counts, fields = infer._decode_impl, (ints(batch, vocab),), dict(
            tokens=ints(batch), block_tables=ints(batch, table), context_lens=ints(batch),
            done0=aval((batch,), jnp.bool_), remaining=ints(batch))
    else:
        fn, counts, fields = infer._prefill_impl, (ints(batch, vocab), ints(batch, vocab)), dict(
            input_ids=ints(batch, 16), block_tables=ints(batch, table), suffix_lens=ints(batch),
            cached_lens=ints(batch), slot_idx=ints(batch))
    # the launch's host inputs ride one packed buffer; its layout is the program's last, static argument
    layout = layout_of(dict(fields, **samp_arrays([None] * batch)))
    args = (params, pool, ints(packed_size(layout)), *counts, None, layout)
    return jax.jit(fn, static_argnums=(len(args) - 1,)).lower(*args).compile().as_text()


def _scoped(text):
    """Scope names found as a path component of some operation's op_name metadata."""
    paths = set(re.findall(r'op_name="([^"]+)"', text))
    return {part for p in paths for part in p.split("/")[:-1]}


@pytest.mark.parametrize("paged_kernel", [False, True], ids=["xla-gather", "paged-kernel"])
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_step_programs_carry_every_scope(model, program, paged_kernel):
    infer = PagedInferenceModel(model, 4, 32, 8, dtype=jnp.float32, decode_steps=2,
                                use_paged_kernel=paged_kernel)
    pool = PagedKVPool(kv=jax.ShapeDtypeStruct((2, 2, 32, 4, 2 * 16), jnp.float32))
    found = _scoped(_program_text(infer, program, pool=pool))
    want = set(PROGRAM_SCOPES) | {"paged_attn" if paged_kernel else "attn_gather"}
    assert want <= found, sorted(want - found)
    assert ("attn_gather" if paged_kernel else "paged_attn") not in found


def test_scopes_and_the_kernel_s_name_survive_the_chip_s_compiler(monkeypatch):
    """Compiled for a described v5e (no chip attached): the paged kernel is still
    the instruction ``ragged_paged_attention.<n>`` that the benchmark finds by
    name, and the scopes are on the operations' metadata."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"cannot describe a v5e topology here: {e!r}")
    chip = SingleDeviceSharding(topo.devices[0])
    config = Qwen2Config(vocab_size=1024, hidden_size=256, intermediate_size=512, num_hidden_layers=2,
                         num_attention_heads=2, num_key_value_heads=1)
    qwen = Qwen2ForCausalLM(config, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    qwen.params = qwen.param_shapes  # shapes only: there is no device to hold arrays
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")  # the kernel asks whether to interpret
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        infer = PagedInferenceModel(qwen, 16, 64, 8, dtype=jnp.bfloat16, decode_steps=2, use_paged_kernel=True)
        aval = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
        text = _program_text(infer, "decode", batch=4, vocab=1024, aval=aval,
                             pool=PagedKVPool(kv=aval((2, 2, 64, 16, 1 * 128), jnp.bfloat16)))
    finally:
        jax.config.update("jax_enable_compilation_cache", before)
        compilation_cache.reset_cache()
    assert re.search(r"%ragged_paged_attention\.\d+ = ", text)
    assert set(PROGRAM_SCOPES) | {"paged_attn"} <= _scoped(text)


# --------------------------------------------------------------------- (b) one clock
STEP_PHASES = ("admission", "prefix_cache", "launch_build", "prefill", "decode", "dispatch", "wait",
               "emit", "step_tail")
LOOP_PHASES = ("loop_intake", "loop_finish")


def test_new_span_names_are_catalogued():
    for name in STEP_PHASES + LOOP_PHASES + ("loop_idle", "inbox"):
        assert name in SPAN_CATALOG, name


@pytest.fixture(scope="module")
def traced_run(model, tmp_path_factory):
    """A tiny engine behind the loop under a profiler capture on the CPU: the
    xplane's host annotations and the tracer's spans of the same run."""
    from jax.profiler import ProfileData

    trace_dir = str(tmp_path_factory.mktemp("trace"))
    eng = make_engine(model)
    eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=6))  # compile outside the capture
    TRACER.clear()
    loop = EngineLoop(eng, registry=MetricsRegistry()).start()
    jax.profiler.start_trace(trace_dir)
    try:
        handles = [loop.submit([9 + i, 6, 7, 8], SamplingParams(max_new_tokens=10)) for i in range(2)]
        for h in handles:
            h.result(timeout=120)
    finally:
        jax.profiler.stop_trace()
        loop.stop()
    path = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    host = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = [(e.name, float(e.start_ns), float(e.duration_ns), dict(e.stats)) for e in line.events]
            if any(name == "engine_step" for name, *_ in events):
                host += events
    return host, [s.to_dict() for s in TRACER.snapshot()]


@pytest.mark.parametrize("phase", STEP_PHASES + LOOP_PHASES)
def test_each_phase_is_on_the_profiler_s_clock_with_its_args(traced_run, phase):
    host, spans = traced_run
    seen = [args for name, _, _, args in host if name == phase]
    mine = [s for s in spans if s["name"] == phase and s["cat"] in ("engine", "engine_loop")]
    assert seen and mine, phase
    if phase in STEP_PHASES:  # the tracer's args are the annotation's
        want = mine[0].get("args") or {}
        got = next(a for a in seen if all(str(a.get(k)) == str(v) for k, v in want.items()))
        assert set(want) <= set(got)
    if phase in ("decode", "prefill"):  # launch geometry rides both
        assert all(set(LAUNCH_GEOMETRY) <= set(a) for a in seen)


def test_phases_nest_under_engine_step_and_tile_it(traced_run):
    host, _ = traced_run
    steps = [(s, s + d) for name, s, d, _ in host if name == "engine_step"]
    assert steps
    for name, s, d, _ in host:
        if name in STEP_PHASES:
            assert any(a <= s and s + d <= b for a, b in steps), name
        if name in LOOP_PHASES:
            assert not any(a < s + d / 2 < b for a, b in steps), name
    # inside a step that launched, what no phase covers is small
    children = ("admission", "prefix_cache", "launch_build", "prefill", "decode", "emit", "step_tail")
    launched = [(a, b) for a, b in steps
                if any(n == "decode" and a <= s < b for n, s, _, _ in host)]
    covered = sum(d for n, s, d, _ in host if n in children and any(a <= s < b for a, b in launched))
    assert covered >= 0.9 * sum(b - a for a, b in launched)


def test_two_mirrored_spans_give_one_clock_offset(traced_run):
    host, spans = traced_run

    def offsets(name):
        trace = sorted((int(a["step"]), s) for n, s, _, a in host if n == name)
        prog = sorted((int(s["args"]["step"]), s["ts"] * 1e9) for s in spans
                      if s["name"] == name and s["cat"] == "engine")
        assert trace and [k for k, _ in trace] == [k for k, _ in prog], name
        return [t - p for (_, t), (_, p) in zip(trace, prog)]

    decode, tail = offsets("decode"), offsets("step_tail")
    assert max(decode + tail) - min(decode + tail) < 1e6  # ns: within 1 ms


def test_mirror_is_a_hook_and_tracer_stays_stdlib_only():
    import paddlenlp_tpu.observability.tracer as tracer_mod

    src = open(tracer_mod.__file__).read()
    assert not re.search(r"^\s*(import|from) jax", src, re.M)
    entered = []

    class Mirror:
        def __init__(self, name, **args):
            self.row = [name, dict(args)]

        def __enter__(self):
            entered.append(self.row)

        def __exit__(self, *exc):
            self.row.append("closed")

        def set_metadata(self, **kw):
            self.row[1].update(kw)

    t = SpanTracer()
    t.mirror_spans(Mirror, cats=("engine",))
    with t.span("decode", cat="engine", step=3) as span:
        span.set(rows=4)
    with t.span("route", cat="router"):
        pass
    with t.span("admission", cat="engine") as span:
        span.discard()
    t.add_span("queue", t.now(), 0.1, cat="engine")  # retrospective: never mirrored
    assert entered == [["decode", {"step": 3, "rows": 4}, "closed"], ["admission", {}, "closed"]]
    assert [s.name for s in t.snapshot()] == ["decode", "route", "queue"]
    assert span.dur >= 0.0 and t.snapshot()[0].args == {"step": 3, "rows": 4}


# --------------------------------------------------------------------- (c) the request clock
def test_a_request_submitted_during_a_step_reads_its_inbox_wait(model):
    """The loop thread takes a submission in only after the step that was
    running when it came: that wait is the ``inbox`` phase, it is inside
    queue_wait and TTFT, and the phases still sum to e2e exactly."""
    eng = make_engine(model)
    eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=4))  # compile first
    inside, real_step = threading.Event(), eng.step

    def slow_step():
        inside.set()
        time.sleep(0.2)
        return real_step()

    eng.step = slow_step
    TRACER.clear()
    loop = EngineLoop(eng, registry=MetricsRegistry()).start()
    try:
        first = loop.submit([5, 6, 7, 8], SamplingParams(max_new_tokens=2))
        assert inside.wait(timeout=30)
        inside.clear()
        second = loop.submit([9, 6, 7, 8], SamplingParams(max_new_tokens=2))
        req = second.result(timeout=60)
        first.result(timeout=60)
    finally:
        loop.stop()
    attr = request_attribution(req)
    assert set(attr) == set(ATTRIBUTION_PHASES) and ATTRIBUTION_PHASES[0] == "inbox"
    assert 0.1 < attr["inbox"] < 0.4, attr
    assert req.arrival_t == second.submitted_t and req.enqueued_t == second.enqueued_t
    assert req.queue_wait == pytest.approx(attr["inbox"] + attr["queue"] + attr["admission_gate"], abs=1e-9)
    assert req.ttft >= attr["inbox"]
    assert sum(attr.values()) == pytest.approx(req.finish_t - req.arrival_t, abs=1e-9)
    spans = {s.name: s for s in TRACER.snapshot(trace=second.trace) if s.cat == "request"}
    assert {"inbox", "queue", "prefill", "request"} <= set(spans)
    assert spans["inbox"].dur == pytest.approx(attr["inbox"], abs=1e-6)
    assert spans["inbox"].args["step"] == second.inbox_step  # the step it waited out
    assert spans["queue"].ts == pytest.approx(spans["inbox"].ts + spans["inbox"].dur, abs=1e-3)
    assert spans["request"].dur == pytest.approx(req.finish_t - req.arrival_t, abs=1e-6)


def test_a_bare_engine_request_has_no_inbox_wait(model):
    eng = make_engine(model)
    eng.add_request([5, 6, 7], SamplingParams(max_new_tokens=3))
    done = []
    while eng.has_work():
        done += eng.step()
    (req,) = done
    assert req.arrival_t == req.enqueued_t
    attr = request_attribution(req)
    assert attr["inbox"] == 0.0 and sum(attr.values()) == pytest.approx(req.finish_t - req.arrival_t, abs=1e-9)


def test_a_requeued_request_s_phases_sum_to_e2e_exactly():
    """First token before the last admission (a requeue across a rebuild): the
    stretch in between is queue, not decode twice."""
    class Req:
        arrival_t, enqueued_t, sched_t, first_token_t, finish_t = 10.0, 10.25, 13.0, 11.0, 15.0
        gated_t = None

    attr = request_attribution(Req)
    assert attr["inbox"] == 0.25 and attr["queue"] == 2.75 and attr["prefill"] == 0.0
    assert attr["decode"] == 2.0 and sum(attr.values()) == 5.0


# --------------------------------------------------------------------- (d) launch geometry
def _launches(name):
    return [s for s in TRACER.snapshot() if s.name == name and s.cat == "engine"]


def test_decode_launch_geometry_with_a_row_finishing_mid_launch(model):
    eng = make_engine(model)
    TRACER.clear()
    eng.add_request([5, 6, 7, 8, 9], SamplingParams(max_new_tokens=3))        # 2 tokens left after prefill
    eng.add_request([5, 6, 7, 8, 9, 10, 11], SamplingParams(max_new_tokens=10))
    eng.step()  # prefill both, then one decode launch of 4 sub-steps
    (prefill,), (decode,) = _launches("prefill"), _launches("decode")
    # decode: contexts 5 and 7 (the token fed sits there); the first row emits in 2 sub-steps, the second in 4
    assert {k: decode.args[k] for k in LAUNCH_GEOMETRY} == {
        "rows_live": 2, "rows": 4, "kv_positions": (6 + 7) + (8 + 9 + 10 + 11)}
    assert {k: prefill.args[k] for k in LAUNCH_GEOMETRY} == {"rows_live": 2, "rows": 2, "kv_positions": 5 + 7}
    by_kind = eng.ledger.snapshot()["by_kind"]
    assert {k: by_kind["decode"][k] for k in LAUNCH_GEOMETRY} == {k: decode.args[k] for k in LAUNCH_GEOMETRY}
    assert by_kind["decode"]["fed"] == 16 and by_kind["decode"]["useful"] == 2 + 4  # real query tokens
    assert eng.ledger.verify_conservation()
    assert eng.efficiency()["ledger"]["by_kind"]["prefill"]["kv_positions"] == 12  # /debug/efficiency
    assert eng.last_step_device_s == pytest.approx(prefill.dur + decode.dur)  # anatomy and span: one measurement


def test_prefill_launch_geometry_with_a_cached_prefix(model):
    eng = make_engine(model)
    prefix = list(range(10, 26))  # 16 tokens: four full blocks of 4
    eng.generate([prefix + [60, 61]], SamplingParams(max_new_tokens=2))
    TRACER.clear()
    before = dict(eng.ledger.by_kind["prefill"])
    eng.add_request(prefix + [70, 71, 72], SamplingParams(max_new_tokens=1))
    eng.step()
    (prefill,) = _launches("prefill")
    assert prefill.args["cached_tokens"] == 16
    want = {"rows_live": 1, "rows": 1, "kv_positions": 16 + 3}
    assert {k: prefill.args[k] for k in LAUNCH_GEOMETRY} == want
    assert {k: eng.ledger.by_kind["prefill"][k] - before[k] for k in LAUNCH_GEOMETRY} == want
    assert eng.ledger.verify_conservation()


@pytest.mark.parametrize("kw,kinds", [({"prefill_chunk_tokens": 8}, ("mixed", "decode")),
                                      ({"use_speculative": True}, ("prefill", "verify"))],
                         ids=["chunked", "speculative"])
def test_every_program_records_geometry_and_conserves(model, kw, kinds):
    eng = make_engine(model, **kw)
    eng.generate([[5, 6, 7, 8, 9], list(range(10, 30)), [30] * 12], SamplingParams(max_new_tokens=10))
    by_kind = eng.ledger.snapshot()["by_kind"]
    for kind in kinds:
        row = by_kind[kind]
        assert 0 < row["rows_live"] <= row["rows"] and row["kv_positions"] >= row["useful"] > 0
    assert eng.ledger.verify_conservation()


def test_launch_geometry_counts_live_rows_only():
    assert launch_geometry(4, [3, 0, 1, 0], [10, 99, 4, 99]) == {"rows_live": 2, "rows": 4, "kv_positions": 14}
    led = GoodputLedger()
    led.record("decode", 16, 6, padding=10,
               geometry={"rows_live": 2, "rows": 4, "kv_positions": 14, "fed": 16, "shape": ("decode",)})
    assert led.by_kind["decode"]["rows"] == 4 and led.by_kind["decode"]["kv_positions"] == 14
    assert led.verify_conservation()
