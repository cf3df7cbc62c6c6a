"""``tools/ttft_tail_window.py``: the benchmark's serving run with the server asked,
before it goes away, where the window's tail spent its time to first token. At a
tiny size on the CPU, over the benchmark's own tiny checkout (the fixture of
``tests/bench/conftest.py``), and the arithmetic on hand-made spans."""

import importlib.util
import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(name, os.path.join(ROOT, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


tiny_root = _load("bench_conftest", "tests", "bench", "conftest.py").tiny_root
tool = _load("ttft_tail_window", "tools", "ttft_tail_window.py")


def test_a_tiny_window_reads_its_tail(capsys, tiny_root, tmp_path):
    rc = tool.main(["--workload", "tiny-serve.tinychat", "--seed", "3000000019", "--seconds", "4",
                    "--trace", "0", "--root", tiny_root, "--tail-out", str(tmp_path)])
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith("{")]
    assert rc == 0 and lines[-1]["correct"] is True  # the benchmark's result line stays the last
    (said,) = [l for l in lines if l.get("phase") == "ttft_tail"]
    doc = json.load(open(tmp_path / "tiny-serve.tinychat.3000000019.json"))
    assert doc["ttft_tail"] == said["ttft_tail"] and doc["workload"] == "tiny-serve.tinychat"
    block, checks, window = doc["ttft_tail"], doc["checks"], doc["window"]
    # the window's requests and no warm-up's: 12 at 3/s for 4 s, the worst 2 of them
    assert checks["requests"] == block["requests"] == window["attempted"] == 12 and block["count"] == 2
    assert checks["share_sum"] == pytest.approx(100.0, abs=0.1)
    assert checks["phases_sum_worst_error_s"] < 1e-6
    assert checks["launches"] > 0 and checks["launches_without_both_args"] == 0
    assert checks["decode_launches_that_carry"] == 0
    assert checks["prefill_spans"] >= 12 and checks["prefill_spans_without_the_split"] == 0
    assert checks["prefill_spans_split_over_the_span"] == 0
    assert len(doc["tail_rows"]) == 2 and doc["tail_rows"][1]["ttft_ms"] == pytest.approx(block["ttft_min_ms"])
    assert doc["tail_rows"][0]["steps"] == 1  # monolithic prefill
    assert doc["readings"]["chunk_backlog_mean"] >= 0.0
    # every launch's dispatch handed the device one host array: its packed buffer (no prefix is shared here)
    sent = {k: v for k, v in doc["launch_children"].items() if k.startswith("dispatch.")}
    assert set(sent) == {"dispatch.prefill", "dispatch.decode"} and set(doc["launch_children"]) - set(sent) == {
        "wait.prefill", "wait.decode"}
    assert all(v["h2d_arrays_min"] == v["h2d_arrays_max"] == 1 and v["without_h2d_args"] == 0 and v["h2d_bytes_mean"] > 0
               and v["ms_mean"] > 0 for v in sent.values())
    assert sum(v["n"] for v in sent.values()) == checks["launches"] and checks["launch_ms_mean"]["decode"] > 0
    assert doc["readings"]["ttft_tail_wait_share"] + doc["readings"]["ttft_tail_behind_share"] \
        + doc["readings"]["ttft_tail_own_share"] + doc["readings"]["ttft_tail_host_share"] \
        + block["share"]["promote_wait"] == pytest.approx(100.0, abs=0.1)


def test_the_backlog_is_read_off_the_launches_that_carry_prompt_tokens():
    launch = lambda name, ts, carried, waiting: {"name": name, "cat": "engine", "ts": ts, "dur": 0.05,
                                                 "args": {"carried": carried, "prefill_waiting": waiting}}
    spans = [launch("mixed_step", 9.0, [1], 5),   # before the window opened
             launch("mixed_step", 10.0, [1], 2), launch("mixed_step", 11.0, [1, 2], 1),
             launch("decode", 12.0, [], 0), launch("mixed_step", 13.0, [3], 0),
             launch("mixed_step", 21.0, [4], 7),  # after it closed
             {"name": "prefill", "cat": "request", "ts": 10.5, "dur": 0.2,
              "args": {"steps": 2, "own_ms": 100.0, "behind_ms": 50.0}},
             {"name": "prefill", "cat": "request", "ts": 12.5, "dur": 0.1,
              "args": {"steps": 1, "own_ms": 100.0, "behind_ms": 50.0}}]
    out = tool.read_window({"recent": [], "ttft_tail": {}}, spans, 10.0, 20.0)
    assert out["readings"]["chunk_backlog_mean"] == 1.0 and out["checks"]["launches"] == 4
    assert out["checks"]["launches_by_name"]["mixed_step"] == 3
    assert out["checks"]["prefill_spans"] == 2 and out["checks"]["prefill_spans_split_over_the_span"] == 1
    assert out["readings"]["ttft_tail_behind_share"] is None and out["tail_rows"] == []


def test_the_launches_children_are_read_by_program_and_a_tree_without_the_args_reads_none():
    child = lambda name, program, ts, ms, **args: {"name": name, "cat": "engine", "ts": ts, "dur": ms / 1e3,
                                                   "args": dict(program=program, **args)}
    spans = [child("dispatch", "mixed", 9.0, 50.0, h2d_arrays=1, h2d_bytes=8),     # before the window opened
             child("dispatch", "mixed", 10.0, 2.0, h2d_arrays=1, h2d_bytes=100),
             child("dispatch", "mixed", 11.0, 4.0, h2d_arrays=2, h2d_bytes=300),
             child("wait", "mixed", 11.1, 60.0), child("dispatch", "decode", 12.0, 1.0),  # a tree that stamps neither
             {"name": "dispatch", "cat": "other", "ts": 12.5, "dur": 1.0, "args": {}}]
    out = tool.launch_children(spans, 10.0, 20.0)
    assert out["dispatch.mixed"] == {"n": 2, "ms_mean": pytest.approx(3.0), "h2d_arrays_min": 1, "h2d_arrays_max": 2,
                                     "h2d_bytes_mean": 200.0, "without_h2d_args": 0}
    assert out["wait.mixed"] == {"n": 1, "ms_mean": pytest.approx(60.0)}
    assert out["dispatch.decode"] == {"n": 1, "ms_mean": pytest.approx(1.0), "h2d_arrays_min": None,
                                      "h2d_arrays_max": None, "h2d_bytes_mean": None, "without_h2d_args": 1}
