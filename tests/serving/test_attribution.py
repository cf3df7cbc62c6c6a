"""Per-request latency attribution (ISSUE 13): every finished request's e2e
latency decomposes into queue / admission_gate / prefill / chunk_stall /
migration_wait / decode phases that (a) sum to e2e within 5%, (b) agree with
the pre-existing queue_wait/ttft/decode_time request fields, (c) land in the
`paddlenlp_serving_latency_attribution_seconds{phase}` histogram family and
on GET /debug/requests. Also covers the /debug/requests kv_stage +
migration-wait-so-far fix (disagg visibility) and the flight recorder's
zero-cost disabled path at engine-step level."""

import http.client
import json
import time

import pytest

from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams
from paddlenlp_tpu.observability import RECORDER
from paddlenlp_tpu.serving import MetricsRegistry, SchedulerConfig, ServingServer
from paddlenlp_tpu.serving.engine_loop import ATTRIBUTION_PHASES, request_attribution
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM


@pytest.fixture(autouse=True)
def _clean_recorder():
    RECORDER.clear()
    RECORDER.set_enabled(True)
    yield
    RECORDER.clear()
    RECORDER.set_enabled(True)


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112,
                      num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=8,
                      max_position_embeddings=256, eos_token_id=None, pad_token_id=0,
                      use_scan_layers=True)
    return LlamaForCausalLM.from_config(cfg, seed=0)


@pytest.fixture(scope="module")
def server_port(model):
    engine = InferenceEngine(model, max_batch_size=4, block_size=4, num_blocks=256,
                             max_blocks_per_seq=32, decode_steps=4,
                             prefill_chunk_tokens=8)
    server = ServingServer(engine, registry=MetricsRegistry(),
                           scheduler_config=SchedulerConfig(max_inflight=16))
    port = server.start_in_thread()
    yield server, port
    server.shutdown(drain_timeout_s=10)


def _get(port, path):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    body = resp.read()
    conn.close()
    return resp.status, json.loads(body)


def _complete(port, prompt, max_tokens=8):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions",
                 body=json.dumps({"prompt": prompt, "max_tokens": max_tokens}),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = json.loads(resp.read())
    conn.close()
    assert resp.status == 200, out
    return out


class TestAttributionParity:
    def test_phases_sum_to_e2e_and_match_request_fields(self, server_port):
        """Acceptance: for every finished request the phases sum to e2e
        within 5%, verified against queue_wait/ttft/decode_time."""
        server, port = server_port
        for i in range(6):
            _complete(port, [5 + i, 6, 7, 8, 9, 10, 11, 12, 13, 14], max_tokens=6)
        _, doc = _get(port, "/debug/requests")
        rows = [r for r in doc["recent"] if r["finish_reason"] in ("stop", "length")]
        assert len(rows) >= 6
        for row in rows:
            attr = row["attribution"]
            assert set(attr) == set(ATTRIBUTION_PHASES)
            assert all(v >= 0 for v in attr.values()), attr
            e2e = row["finish_t"] - row["arrival_t"]
            assert abs(sum(attr.values()) - e2e) <= 0.05 * e2e + 1e-6, (attr, e2e)
            # parity with the pre-existing request timing fields
            assert attr["inbox"] + attr["queue"] + attr["admission_gate"] == \
                pytest.approx(row["queue_wait_s"], rel=0.05, abs=1e-6)
            assert attr["promote_wait"] + attr["prefill_behind"] + attr["prefill"] == \
                pytest.approx(row["ttft_s"] - row["queue_wait_s"], rel=0.05, abs=1e-6)
            assert attr["chunk_stall"] + attr["migration_wait"] + attr["decode"] == \
                pytest.approx(row["decode_time_s"], rel=0.05, abs=1e-6)

    def test_histogram_family_and_debug_requests(self, server_port):
        server, port = server_port
        _complete(port, [40, 41, 42], max_tokens=4)
        hist = server.registry.get("paddlenlp_serving_latency_attribution_seconds")
        n_finished = server.registry.get(
            "paddlenlp_serving_requests_total").value(status="length", priority="interactive", tenant="default")
        for phase in ATTRIBUTION_PHASES:
            # one observation per phase per finished request
            assert hist.count(phase=phase) == n_finished, phase
        # the per-phase sums reconstruct the e2e sum (histogram-level parity)
        e2e_sum = server.registry.get("paddlenlp_serving_e2e_seconds").sum()
        attr_sum = sum(hist.sum(phase=p) for p in ATTRIBUTION_PHASES)
        assert attr_sum == pytest.approx(e2e_sum, rel=0.05)

    def test_decision_trail_recorded_per_request(self, server_port):
        server, port = server_port
        RECORDER.clear()
        _complete(port, [60, 61, 62, 63, 64, 65, 66, 67, 68, 69], max_tokens=4)
        _, doc = _get(port, "/debug/requests")
        trace = doc["recent"][-1]["trace"]
        names = [e.name for e in RECORDER.snapshot(trace=trace)]
        assert "admit.accept" in names
        # a 10-token prompt through chunk budget 8 takes >= 2 chunk grants
        assert names.count("chunk.grant") >= 2


class TestChunkStallAttribution:
    def test_decode_rows_riding_chunk_steps_accumulate_stall(self, model):
        """Deterministic engine-level check: a decoding request sharing mixed
        steps with another request's prefill chunks accrues chunk_stall."""
        eng = InferenceEngine(model, max_batch_size=4, block_size=4, num_blocks=128,
                              max_blocks_per_seq=32, decode_steps=4,
                              prefill_chunk_tokens=4)
        a = eng.add_request([5, 6, 7], SamplingParams(max_new_tokens=24))
        eng.step()  # admit A; first chunk
        while not any(r is not None and r.req_id == a and r.output_ids
                      for r in eng.slots):
            eng.step()
        req_a = next(r for r in eng.slots if r.req_id == a)
        assert req_a.chunk_stall_s == 0.0  # nothing else prefilled yet
        eng.add_request(list(range(20, 44)), SamplingParams(max_new_tokens=2))
        finished = []
        while eng.has_work():
            finished.extend(eng.step())
        done_a = next(r for r in finished if r.req_id == a)
        assert done_a.chunk_stall_s > 0.0  # B's 24-token prefill rode A's decode steps
        attr = request_attribution(done_a)
        assert attr["chunk_stall"] == pytest.approx(
            min(done_a.chunk_stall_s, done_a.decode_time), rel=1e-6)
        e2e = done_a.finish_t - done_a.arrival_t
        assert sum(attr.values()) == pytest.approx(e2e, rel=1e-9)


class TestDisaggAttribution:
    @pytest.fixture(scope="class")
    def disagg_engine(self, model, eight_devices):
        return InferenceEngine(model, disagg_stages=(1, 1), max_batch_size=4,
                               block_size=4, num_blocks=128, max_blocks_per_seq=32,
                               decode_steps=4)

    def test_migration_wait_attributed(self, disagg_engine):
        eng = disagg_engine
        rid = eng.add_request([5, 6, 7, 8], SamplingParams(max_new_tokens=6))
        finished = []
        while eng.has_work():
            finished.extend(eng.step())
        req = next(r for r in finished if r.req_id == rid)
        assert req.migration_wait_s > 0.0  # prefill->decode handoff waited >= 1 poll
        assert req.migrate_start_t is None  # episode closed on land
        attr = request_attribution(req)
        assert attr["migration_wait"] == pytest.approx(
            min(req.migration_wait_s, req.decode_time), rel=1e-6)
        assert sum(attr.values()) == pytest.approx(
            req.finish_t - req.arrival_t, rel=1e-9)
        # the decision trail names the handoff
        names = [e.name for e in RECORDER.snapshot(req_id=rid)]
        assert "migrate.start" in names and "migrate.land" in names

    def test_debug_requests_surfaces_kv_stage_and_migration_wait(self, model,
                                                                 eight_devices):
        """Satellite fix: /debug/requests on a disagg engine shows
        Request.kv_stage and migration-wait-so-far for in-flight requests."""
        engine = InferenceEngine(model, disagg_stages=(1, 1), max_batch_size=4,
                                 block_size=4, num_blocks=128, max_blocks_per_seq=32,
                                 decode_steps=4)
        server = ServingServer(engine, registry=MetricsRegistry(),
                               scheduler_config=SchedulerConfig(max_inflight=8))
        port = server.start_in_thread()
        try:
            handle = server.scheduler.submit(
                [5, 6, 7, 8], SamplingParams(max_new_tokens=100), timeout_s=60)
            seen = None
            deadline = time.time() + 30
            while time.time() < deadline and not handle.done():
                _, doc = _get(port, "/debug/requests")
                rows = [r for r in doc["inflight"] if "kv_stage" in r]
                if rows:
                    seen = rows[0]
                    break
                time.sleep(0.005)
            assert seen is not None, "request never surfaced kv_stage"
            assert seen["kv_stage"] in ("prefill", "migrating", "decode")
            assert seen["migration_wait_s"] >= 0.0
        finally:
            server.scheduler.cancel(handle)
            handle.result(timeout=30)
            server.shutdown(drain_timeout_s=10)


class TestRecorderDisabledAtEngineLevel:
    def test_disabled_recorder_records_nothing_per_step(self, model):
        """Satellite 6 at engine level: with PDNLP_TPU_FLIGHT_RECORDER off,
        a full serve cycle (admissions, chunks, decode steps) records zero
        events — and steady-state decode steps hit no recorder call sites at
        all even when enabled."""
        eng = InferenceEngine(model, max_batch_size=4, block_size=4, num_blocks=128,
                              max_blocks_per_seq=32, decode_steps=4,
                              prefill_chunk_tokens=8)
        RECORDER.clear()
        RECORDER.set_enabled(False)
        try:
            eng.generate([[5, 6, 7, 8] * 3, [9, 10, 11]],
                         SamplingParams(max_new_tokens=8))
            assert len(RECORDER) == 0 and RECORDER.dropped == 0
        finally:
            RECORDER.set_enabled(True)
        # enabled, steady-state decode: admission already done, no chunks, no
        # migrations -> an engine step crosses zero decision edges
        rid = eng.add_request([30, 31, 32], SamplingParams(max_new_tokens=32))
        eng.step()  # admission + chunks land here
        while next(r for r in eng.slots if r.req_id == rid).needs_prefill:
            eng.step()
        RECORDER.clear()
        for _ in range(4):
            eng.step()
        assert len(RECORDER) == 0  # pure decode steps record nothing
        eng.abort(rid)
