"""Serving runtime end-to-end on CPU: engine loop + scheduler + HTTP API.

Acceptance path (ISSUE 1): >=8 concurrent HTTP requests through the
continuous-batching engine loop with SSE streaming, one cancelled mid-stream,
one rejected 429 at saturation, /metrics exposing nonzero TTFT / queue-depth /
KV-utilization series."""

import http.client
import json
import threading
import time

import numpy as np
import pytest

from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams
from paddlenlp_tpu.serving import (
    EngineLoop,
    MetricsRegistry,
    Scheduler,
    SchedulerConfig,
    ServingServer,
    ShuttingDownError,
)
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=256,
                      eos_token_id=None, pad_token_id=0, use_scan_layers=True)
    return LlamaForCausalLM.from_config(cfg, seed=0)


def make_engine(model, **kw):
    defaults = dict(max_batch_size=4, block_size=4, num_blocks=128, max_blocks_per_seq=32,
                    decode_steps=4)
    defaults.update(kw)
    return InferenceEngine(model, **defaults)


# --------------------------------------------------------------------- engine hooks
class TestEngineHooks:
    def test_timing_fields_on_finished_request(self, model):
        eng = make_engine(model)
        eng.add_request([5, 6, 7], SamplingParams(max_new_tokens=6))
        done = []
        while eng.has_work():
            done += eng.step()
        (req,) = done
        assert req.finish_reason == "length"
        assert req.sched_t is not None and req.first_token_t is not None and req.finish_t is not None
        assert req.arrival_t <= req.sched_t <= req.first_token_t <= req.finish_t
        assert req.queue_wait >= 0 and req.ttft >= req.queue_wait and req.decode_time >= 0

    def test_abort_waiting_request(self, model):
        eng = make_engine(model)
        rid = eng.add_request([5, 6, 7], SamplingParams(max_new_tokens=6))
        req = eng.abort(rid)
        assert req is not None and req.aborted and req.finish_reason == "abort"
        assert not eng.has_work()
        assert eng.abort(rid) is None  # already gone

    def test_abort_running_request_frees_blocks(self, model):
        eng = make_engine(model)
        total = eng.mgr.num_free
        rid = eng.add_request([5, 6, 7, 8], SamplingParams(max_new_tokens=32))
        eng.step()  # prefill + some decode; request now holds blocks
        assert eng.mgr.num_free < total
        req = eng.abort(rid)
        assert req is not None and req.aborted
        assert eng.mgr.num_free == total  # KV fully reclaimed
        assert not eng.has_work()


# --------------------------------------------------------------------- engine loop
class TestEngineLoop:
    def test_submit_matches_sync_generate(self, model):
        want = make_engine(model).generate([[5, 6, 7, 8, 9]], SamplingParams(max_new_tokens=8))[0]
        loop = EngineLoop(make_engine(model), registry=MetricsRegistry()).start()
        try:
            h = loop.submit([5, 6, 7, 8, 9], SamplingParams(max_new_tokens=8))
            streamed = list(h.tokens(timeout=120))
            req = h.result(timeout=5)
            np.testing.assert_array_equal(req.output_ids, want)
            np.testing.assert_array_equal(streamed, want)  # stream order == result order
        finally:
            loop.stop()

    def test_concurrent_submitters(self, model):
        loop = EngineLoop(make_engine(model), registry=MetricsRegistry()).start()
        prompts = [[5 + i, 6 + i, 7 + i] for i in range(6)]
        results = {}

        def worker(i):
            h = loop.submit(prompts[i], SamplingParams(max_new_tokens=6))
            results[i] = h.result(timeout=180).output_ids

        try:
            threads = [threading.Thread(target=worker, args=(i,)) for i in range(6)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=180)
            assert len(results) == 6 and all(len(v) == 6 for v in results.values())
            # each prompt's tokens must match a solo run (batch isolation)
            solo = make_engine(model).generate([prompts[0]], SamplingParams(max_new_tokens=6))[0]
            np.testing.assert_array_equal(results[0], solo)
        finally:
            loop.stop()

    def test_cancel_midstream_frees_blocks(self, model):
        # max_new_tokens must FIT the per-seq KV cap (128 tokens here) or the
        # engine fail-fasts the request with finish_reason="capacity"
        eng = make_engine(model)
        total = eng.mgr.num_free
        loop = EngineLoop(eng, registry=MetricsRegistry()).start()
        try:
            h = loop.submit([5, 6, 7], SamplingParams(max_new_tokens=96))
            it = h.tokens(timeout=120)
            next(it)  # at least one token streamed
            loop.cancel(h)
            req = h.result(timeout=30)
            assert req.aborted and req.finish_reason == "abort"
            assert 0 < len(req.output_ids) < 96
            deadline = time.time() + 10
            while eng.mgr.num_free != total and time.time() < deadline:
                time.sleep(0.01)
            assert eng.mgr.num_free == total
        finally:
            loop.stop()

    def test_capacity_fail_fast(self, model):
        """A request that can never fit resolves immediately (no hang)."""
        loop = EngineLoop(make_engine(model), registry=MetricsRegistry()).start()
        try:
            h = loop.submit([5, 6, 7], SamplingParams(max_new_tokens=4096))
            req = h.result(timeout=60)
            assert req.finish_reason == "capacity" and req.output_ids == []
        finally:
            loop.stop()

    def test_deadline_timeout_aborts(self, model):
        loop = EngineLoop(make_engine(model), registry=MetricsRegistry()).start()
        try:
            h = loop.submit([5, 6, 7], SamplingParams(max_new_tokens=96), deadline_s=0.0)
            req = h.result(timeout=60)
            assert h.timed_out and req.aborted
        finally:
            loop.stop()

    def test_scheduler_drain_rejects(self, model):
        loop = EngineLoop(make_engine(model), registry=MetricsRegistry()).start()
        sched = Scheduler(loop, SchedulerConfig(max_inflight=4))
        try:
            h = sched.submit([5, 6, 7], SamplingParams(max_new_tokens=4))
            assert sched.drain(timeout_s=120)  # waits for the in-flight request
            assert h.done()
            with pytest.raises(ShuttingDownError):
                sched.submit([5, 6, 7], SamplingParams(max_new_tokens=4))
        finally:
            loop.stop()


# --------------------------------------------------------------------- http helpers
def post_json(port, path, payload, timeout=180):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("POST", path, body=json.dumps(payload),
                     headers={"Content-Type": "application/json"})
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


class SSEStream:
    """One streaming completion over a raw HTTP connection."""

    def __init__(self, port, payload, timeout=180):
        self.conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
        self.conn.request("POST", "/v1/completions", body=json.dumps(payload),
                          headers={"Content-Type": "application/json"})
        self.resp = self.conn.getresponse()
        self.status = self.resp.status

    def events(self):
        """Yield parsed `data:` payloads until [DONE] or EOF."""
        while True:
            line = self.resp.readline()
            if not line:
                return
            line = line.strip()
            if not line.startswith(b"data: "):
                continue
            data = line[len(b"data: "):]
            if data == b"[DONE]":
                return
            yield json.loads(data)

    def close(self):
        self.conn.close()


@pytest.fixture(scope="module")
def server(model):
    registry = MetricsRegistry()
    srv = ServingServer(
        make_engine(model),
        scheduler_config=SchedulerConfig(max_inflight=9, default_timeout_s=300.0),
        registry=registry,
    )
    port = srv.start_in_thread()
    yield srv, port, registry
    srv.shutdown(drain_timeout_s=5)


# --------------------------------------------------------------------- http e2e
class TestServingHTTP:
    def test_e2e_concurrent_stream_cancel_saturate_metrics(self, server):
        srv, port, registry = server
        n_stream, gen_len = 8, 32
        # barrier releases once every stream's 200 response HEADERS arrived —
        # i.e. all 9 passed admission (window full) but none can have finished
        # yet (each needs >= gen_len tokens and the engine is still compiling)
        admitted = threading.Barrier(n_stream + 2, timeout=300)
        results = {}
        cancel_info = {"cid_ready": threading.Event()}

        def stream_worker(i):
            s = SSEStream(port, {"prompt": [5 + i, 6 + i, 7 + i],
                                 "max_tokens": gen_len, "stream": True})
            assert s.status == 200
            admitted.wait()
            toks, finish = [], None
            for ev in s.events():
                c = ev["choices"][0]
                if c.get("finish_reason"):
                    finish = c["finish_reason"]
                elif "token" in c:
                    toks.append(c["token"])
            results[i] = (toks, finish)
            s.close()

        def cancel_worker():
            s = SSEStream(port, {"prompt": [60, 61, 62], "max_tokens": 96, "stream": True})
            assert s.status == 200
            admitted.wait()
            n_toks = 0
            for ev in s.events():
                c = ev["choices"][0]
                if "token" in c:
                    n_toks += 1
                    if cancel_info.get("cid") is None:
                        cancel_info["cid"] = ev["id"]
                        cancel_info["cid_ready"].set()
                if c.get("finish_reason"):
                    cancel_info["finish"] = c["finish_reason"]
            cancel_info["n_toks"] = n_toks
            s.close()

        threads = [threading.Thread(target=stream_worker, args=(i,)) for i in range(n_stream)]
        ct = threading.Thread(target=cancel_worker)
        for t in threads + [ct]:
            t.start()

        admitted.wait()  # 9 in flight, window = 9: the next submit must shed
        status, body = post_json(port, "/v1/completions",
                                 {"prompt": [1, 2, 3], "max_tokens": 4})
        assert status == 429, body
        assert body["error"]["type"] == "rate_limit_exceeded"

        # cancel the long request once it is actually streaming
        assert cancel_info["cid_ready"].wait(timeout=300)
        status, body = post_json(port, "/v1/abort", {"id": cancel_info["cid"]})
        assert status == 200 and body["cancelled"] is True

        for t in threads + [ct]:
            t.join(timeout=600)

        # all 8 streams completed in order with the full token budget
        assert len(results) == n_stream
        for toks, finish in results.values():
            assert len(toks) == gen_len and finish == "length"
        # cancelled stream emitted some tokens then terminated with abort
        assert 0 < cancel_info["n_toks"] < 96
        assert cancel_info.get("finish") == "abort"

        # scrape the metrics plane
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=30)
        conn.request("GET", "/metrics")
        resp = conn.getresponse()
        assert resp.status == 200
        text = resp.read().decode()
        conn.close()

        def metric_value(name):
            for line in text.splitlines():
                if line.startswith(name + " ") or line.startswith(name + "{"):
                    return float(line.rsplit(" ", 1)[1])
            raise AssertionError(f"metric {name} missing from exposition:\n{text}")

        assert metric_value("paddlenlp_serving_ttft_seconds_count") >= 9
        assert metric_value("paddlenlp_serving_ttft_seconds_sum") > 0
        assert 'paddlenlp_serving_requests_total{status="length",priority="interactive",tenant="default"}' in text
        assert 'paddlenlp_serving_requests_total{status="abort",priority="interactive",tenant="default"}' in text
        assert metric_value("paddlenlp_serving_queue_depth") >= 0  # series present
        assert metric_value("paddlenlp_serving_kv_utilization") >= 0
        assert metric_value("paddlenlp_serving_tokens_generated_total") >= n_stream * gen_len
        # saturation rejection is visible via /health scheduler stats
        status, health = post_json_get(port, "/health")
        assert health["scheduler"]["rejected_saturated"] >= 1

    def test_batch_mode_with_timing(self, server):
        srv, port, _ = server
        status, body = post_json(port, "/v1/completions", {"prompt": [9, 10, 11], "max_tokens": 5})
        assert status == 200
        choice = body["choices"][0]
        assert len(choice["token_ids"]) == 5 and choice["finish_reason"] == "length"
        assert body["usage"] == {"prompt_tokens": 3, "cached_tokens": 0,
                                 "completion_tokens": 5, "total_tokens": 8}
        assert body["timing"]["ttft_s"] > 0

    def test_http_errors(self, server):
        srv, port, _ = server
        status, body = post_json(port, "/v1/completions", {"max_tokens": 4})
        assert status == 400  # missing prompt
        status, body = post_json(port, "/v1/completions", {"prompt": "hi"})
        assert status == 400  # string prompt without tokenizer
        status, body = post_json(port, "/nope", {})
        assert status == 404
        status, body = post_json(port, "/v1/abort", {"id": "cmpl-unknown"})
        assert status == 200 and body["cancelled"] is False

    def test_oversized_body_413(self, server):
        srv, port, _ = server
        old = srv.max_body_bytes
        srv.max_body_bytes = 64
        try:
            status, body = post_json(port, "/v1/completions",
                                     {"prompt": list(range(64)), "max_tokens": 1})
            assert status == 413
        finally:
            srv.max_body_bytes = old

    def test_health(self, server):
        srv, port, _ = server
        status, body = post_json_get(port, "/health")
        assert status == 200 and body["status"] == "ok"
        assert "free_blocks" in body["engine"] and "inflight" in body["scheduler"]


def post_json_get(port, path, timeout=30):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, json.loads(resp.read() or b"{}")
    finally:
        conn.close()


# --------------------------------------------------------------------- SimpleServer
class TestSimpleServerHardening:
    def test_oversized_body_413(self):
        from paddlenlp_tpu.server import SimpleServer

        srv = SimpleServer(max_body_bytes=32)
        srv._routes["/models/echo"] = lambda data, params: data
        port = srv.start_in_thread()
        try:
            status, body = post_json(port, "/models/echo", {"data": "x" * 128})
            assert status == 413
            status, body = post_json(port, "/models/echo", {"data": "hi"})
            assert status == 200 and body["result"] == "hi"
        finally:
            srv.shutdown()


# --------------------------------------------------------------------- drain propagation
class TestReplicaSideDrain:
    """POST /admin/drain: a drained ServingServer 503s new DIRECT traffic
    (with Retry-After) while accepted streams finish — the replica-side half
    of the router's admin-plane drain."""

    def test_direct_traffic_503_while_inflight_finishes(self, model):
        import http.client as hc

        srv = ServingServer(
            make_engine(model),
            scheduler_config=SchedulerConfig(max_inflight=8, default_timeout_s=300.0),
            registry=MetricsRegistry(),
        )
        port = srv.start_in_thread()
        try:
            # open a stream BEFORE the drain: it must finish normally
            s = SSEStream(port, {"prompt": [5, 6, 7], "max_tokens": 6, "stream": True})
            assert s.status == 200
            status, doc = post_json(port, "/admin/drain", {"retry_after_s": 12})
            assert status == 200 and doc["draining"] is True
            assert doc["retry_after_s"] == 12.0
            # new direct traffic: clean 503 + Retry-After, no connection reset
            conn = hc.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("POST", "/v1/completions",
                         body=json.dumps({"prompt": [1, 2], "max_tokens": 2}),
                         headers={"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read())
            assert resp.status == 503
            assert body["error"]["type"] == "shutting_down"
            assert int(resp.getheader("Retry-After")) == 12
            conn.close()
            # /health reports draining with the same hint
            conn = hc.HTTPConnection("127.0.0.1", port, timeout=30)
            conn.request("GET", "/health")
            resp = conn.getresponse()
            health = json.loads(resp.read())
            assert resp.status == 503 and health["status"] == "draining"
            assert int(resp.getheader("Retry-After")) == 12
            conn.close()
            # the pre-drain stream still completes token-for-token
            toks = [ev["choices"][0]["token"] for ev in s.events()
                    if "token" in ev["choices"][0]]
            s.close()
            assert len(toks) == 6
        finally:
            srv.shutdown(drain_timeout_s=5)

    def test_admin_drain_validates_body(self, model):
        srv = ServingServer(make_engine(model), registry=MetricsRegistry())
        port = srv.start_in_thread()
        try:
            status, doc = post_json(port, "/admin/drain", {"retry_after_s": "soon"})
            assert status == 400 and doc["error"]["type"] == "invalid_request"
            # the malformed request must NOT have drained the server
            status, doc = post_json(port, "/v1/completions",
                                    {"prompt": [1, 2], "max_tokens": 2})
            assert status == 200
        finally:
            srv.shutdown(drain_timeout_s=5)
