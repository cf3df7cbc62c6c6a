"""Where a tail request's time to first token went (ISSUE 38), on the request's
side: ``request_attribution`` carves ``prefill_behind`` out of ``prefill`` and the
phases still sum to the end-to-end time; the request's ``prefill`` span carries
``steps`` / ``own_ms`` / ``behind_ms``; ``ttft_tail`` sums the split over the worst
tenth of finished-request rows; ``GET /debug/requests`` returns it and takes
``?since_ts=``; a postmortem bundle carries it and ``tools/postmortem.py`` prints it."""

import http.client
import importlib.util
import json
import os
import threading
import time

import pytest

from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams
from paddlenlp_tpu.observability.tracer import TRACER
from paddlenlp_tpu.serving import MetricsRegistry, SchedulerConfig, ServingServer
from paddlenlp_tpu.serving.engine_loop import (ATTRIBUTION_PHASES, RECENT_FINISHED, TTFT_TAIL_PARTS,
                                               request_attribution, ttft_tail)
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


# --------------------------------------------------------------------- the carve
class Req:
    """A finished request's clock: 1 s on the inbox, 2 s queued, admitted at 13,
    first token at 17, finished at 20."""
    arrival_t, enqueued_t, sched_t, first_token_t, finish_t = 10.0, 11.0, 13.0, 17.0, 20.0
    gated_t = None
    prefill_behind_s = 1.5
    prefill_own_s = 2.0
    prefill_steps = 4


def test_prefill_behind_is_a_phase_of_the_vocabulary():
    assert "prefill_behind" in ATTRIBUTION_PHASES
    assert ATTRIBUTION_PHASES.index("promote_wait") < ATTRIBUTION_PHASES.index("prefill_behind") \
        < ATTRIBUTION_PHASES.index("prefill")
    assert TTFT_TAIL_PARTS[:-2] == ATTRIBUTION_PHASES[:5] and TTFT_TAIL_PARTS[-2:] == ("prefill_own", "prefill_host")


def test_prefill_behind_is_carved_out_of_prefill_and_the_phases_still_sum():
    attr = request_attribution(Req)
    assert attr["prefill_behind"] == 1.5 and attr["prefill"] == 2.5
    assert sum(attr.values()) == pytest.approx(Req.finish_t - Req.arrival_t, abs=1e-12)


@pytest.mark.parametrize("promote,behind,want", [
    (0.0, 9.0, (0.0, 4.0, 0.0)),   # more booked than the window holds: clipped to it
    (3.0, 9.0, (3.0, 1.0, 0.0)),   # after promote_wait, to what is left
    (1.0, 0.5, (1.0, 0.5, 2.5)),
    (0.0, -1.0, (0.0, 0.0, 4.0)),  # never negative
])
def test_the_carve_is_clipped_to_what_is_left(promote, behind, want):
    class R(Req):
        promote_wait_s, prefill_behind_s = promote, behind

    attr = request_attribution(R)
    assert (attr["promote_wait"], attr["prefill_behind"], attr["prefill"]) == want
    assert sum(attr.values()) == pytest.approx(10.0, abs=1e-12)


def test_a_request_without_the_bookkeeping_reads_no_prefill_behind():
    class Old:
        arrival_t, sched_t, first_token_t, finish_t = 1.0, 2.0, 3.0, 4.0

    attr = request_attribution(Old)
    assert attr["prefill_behind"] == 0.0 and attr["prefill"] == 1.0 and sum(attr.values()) == 3.0


# --------------------------------------------------------------------- the block
def row(i, ttft_s, *, inbox=0.0, queue=0.0, behind=0.0, own=0.0, host=0.0, steps=1, arrival_t=0.0):
    attr = dict.fromkeys(ATTRIBUTION_PHASES, 0.0)
    attr.update(inbox=inbox, queue=queue, prefill_behind=behind, prefill=own + host, decode=1.0)
    return {"req_id": i, "ttft_s": ttft_s, "arrival_t": arrival_t, "attribution": attr,
            "prefill_own_s": own, "prefill_steps": steps}


def even_rows(n):
    """n requests whose TTFT is their rank, a tenth of it on the inbox."""
    return [row(i, float(i + 1), inbox=0.1 * (i + 1), own=0.9 * (i + 1)) for i in range(n)]


@pytest.mark.parametrize("n,count", [(27, 3), (31, 4), (108, 11), (10, 1), (11, 2), (1, 1)])
def test_the_tail_is_the_worst_tenth_rounded_up(n, count):
    rows = even_rows(n)
    rows.reverse()  # the ring is in finish order, not TTFT order
    block = ttft_tail(rows)
    assert block["requests"] == n and block["count"] == count
    assert block["ttft_min_ms"] == pytest.approx((n - count + 1) * 1e3)
    assert block["ttft_sum_ms"] == pytest.approx(sum(range(n - count + 1, n + 1)) * 1e3)
    assert sum(block["share"].values()) == pytest.approx(100.0, abs=1e-9)


def test_the_shares_are_of_the_tail_s_summed_ttft():
    rows = [row(i, 0.1, own=0.1) for i in range(18)]
    rows.append(row(18, 2.0, inbox=0.25, queue=0.25, behind=0.5, own=0.75, host=0.25, steps=3))
    rows.append(row(19, 6.0, inbox=0.75, queue=0.75, behind=1.5, own=2.25, host=0.75, steps=9))
    block = ttft_tail(rows)
    assert block["count"] == 2 and block["ttft_min_ms"] == 2000.0 and block["steps_mean"] == 6.0
    assert tuple(block["share"]) == TTFT_TAIL_PARTS
    assert block["share"] == pytest.approx({
        "inbox": 12.5, "queue": 12.5, "admission_gate": 0.0, "promote_wait": 0.0,
        "prefill_behind": 25.0, "prefill_own": 37.5, "prefill_host": 12.5})


def test_own_time_is_clipped_to_the_prefill_phase():
    block = ttft_tail([row(0, 1.0, own=0.4, host=0.6) | {"prefill_own_s": 5.0}])
    assert block["share"]["prefill_own"] == pytest.approx(100.0) and block["share"]["prefill_host"] == 0.0


def test_an_empty_ring_gives_an_empty_block():
    assert ttft_tail([]) == {}
    # a request that failed before its first token has no TTFT to rank
    assert ttft_tail([{"req_id": 1, "ttft_s": None, "attribution": {"queue": 1.0}},
                      {"req_id": 2, "ttft_s": 0.5, "attribution": None}]) == {}


# --------------------------------------------------------------------- the server
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
                             max_blocks_per_seq=32, decode_steps=4, prefill_chunk_tokens=8)
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


def _complete(port, prompt, max_tokens=4):
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=120)
    conn.request("POST", "/v1/completions",
                 body=json.dumps({"prompt": prompt, "max_tokens": max_tokens}),
                 headers={"Content-Type": "application/json"})
    resp = conn.getresponse()
    out = json.loads(resp.read())
    conn.close()
    assert resp.status == 200, out
    return out


@pytest.fixture(scope="module")
def served(server_port):
    """One warm-up request, then a burst of twelve prompts of two and three
    chunks from as many clients: more prompts than chunk rows, so some wait."""
    server, port = server_port
    _complete(port, [5, 6, 7])
    cursor = time.time()
    prompts = [[10 + i] + list(range(20, 20 + 11 + 8 * (i % 2))) for i in range(12)]
    threads = [threading.Thread(target=_complete, args=(port, p)) for p in prompts]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120)
    return server, port, cursor


def test_debug_requests_returns_the_tail_of_the_window(served):
    server, port, cursor = served
    status, everything = _get(port, "/debug/requests")
    assert status == 200 and len(everything["recent"]) >= 13
    status, doc = _get(port, f"/debug/requests?since_ts={cursor}")
    assert status == 200 and set(doc) == {"inflight", "recent", "ttft_tail"}
    assert len(doc["recent"]) == 12 and all(r["arrival_t"] >= cursor for r in doc["recent"])
    block = doc["ttft_tail"]
    assert block["requests"] == 12 and block["count"] == 2
    assert sum(block["share"].values()) == pytest.approx(100.0, abs=0.1)
    assert set(block["share"]) == set(TTFT_TAIL_PARTS)
    worst = sorted(r["ttft_s"] for r in doc["recent"])[-2:]
    assert block["ttft_min_ms"] == pytest.approx(worst[0] * 1e3)
    assert block["ttft_sum_ms"] == pytest.approx(sum(worst) * 1e3, rel=1e-6)
    assert 2.0 <= block["steps_mean"] <= 3.0
    assert block == ttft_tail(doc["recent"])  # a plain function over the rows
    # twelve prompts for one chunk row a step: the tail waited behind others' chunks
    assert block["share"]["prefill_behind"] > 0.0
    status, late = _get(port, f"/debug/requests?since_ts={time.time() + 60}")
    assert status == 200 and late["recent"] == [] and late["ttft_tail"] == {}


def test_the_ring_holds_more_than_a_window_of_short_requests(server_port):
    # shortchat finishes 108 requests in a 45 s window at 2.4/s
    assert server_port[0].loop.recent_finished.maxlen == RECENT_FINISHED == 256


def test_a_bad_cursor_is_a_clean_400(served):
    status, doc = _get(served[1], "/debug/requests?since_ts=banana")
    assert status == 400 and "since_ts" in doc["error"]


def test_every_row_s_phases_sum_and_its_split_fits_its_prefill(served):
    _, doc = _get(served[1], "/debug/requests")
    for r in doc["recent"]:
        attr = r["attribution"]
        assert set(attr) == set(ATTRIBUTION_PHASES)
        assert sum(attr.values()) == pytest.approx(r["finish_t"] - r["arrival_t"], abs=1e-6)
        assert r["prefill_steps"] >= 1
        assert 0.0 < r["prefill_own_s"] <= attr["prefill"] + 1e-4
        assert attr["inbox"] + attr["queue"] + attr["admission_gate"] + attr["promote_wait"] \
            + attr["prefill_behind"] + attr["prefill"] == pytest.approx(r["ttft_s"], abs=1e-6)


def test_the_prefill_span_carries_the_split(served):
    _, doc = _get(served[1], "/debug/requests")
    for r in doc["recent"]:
        spans = {s.name: s for s in TRACER.snapshot(trace=r["trace"]) if s.cat == "request"}
        args = spans["prefill"].args
        assert args["steps"] == r["prefill_steps"]
        assert args["own_ms"] == pytest.approx(r["prefill_own_s"] * 1e3)
        assert args["behind_ms"] == pytest.approx(r["attribution"]["prefill_behind"] * 1e3, abs=1e-3)
        assert args["own_ms"] + args["behind_ms"] <= spans["prefill"].dur * 1e3 + 0.1
        assert "steps" not in (spans["queue"].args or {})


def test_the_histogram_family_has_the_phase_and_no_new_family(served):
    server = served[0]
    hist = server.registry.get("paddlenlp_serving_latency_attribution_seconds")
    assert hist.count(phase="prefill_behind") == hist.count(phase="prefill") >= 13
    assert hist.sum(phase="prefill_behind") > 0.0
    assert not [n for n in server.registry.expose().splitlines()
                if n.startswith("# TYPE") and ("ttft_tail" in n or "prefill_behind" in n)]


def test_a_bundle_carries_the_block_and_the_analyzer_prints_it(served, tmp_path, capsys):
    server = served[0]
    health = server.loop._postmortem_health()
    assert health["ttft_tail"] == ttft_tail(health["recent_finished"])
    bundle = {"tier": "replica", "trigger": "on_demand", "events": [], "spans": [], "health": health}
    path = tmp_path / "bundle.json"
    path.write_text(json.dumps(bundle))
    spec = importlib.util.spec_from_file_location("postmortem_tool", os.path.join(REPO, "tools", "postmortem.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert f"ttft tail: worst {health['ttft_tail']['count']} of {health['ttft_tail']['requests']}" in out
    assert "prefill_behind=" in out and "prefill_host=" in out
