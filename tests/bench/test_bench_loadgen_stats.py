"""Load generation and order statistics: stdlib only, exact."""

import json
import math
import os

import pytest

from bench.harness import loadgen, stats, traffic

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MIXES = sorted(f[:-5] for f in os.listdir(os.path.join(ROOT, "bench", "traffic"))
               if json.load(open(os.path.join(ROOT, "bench", "traffic", f)))["kind"] == "open_loop")


def _mix(name, **over):
    mix = json.load(open(os.path.join(ROOT, "bench", "traffic", name + ".json")))
    mix["rate"] = mix["rate"] or 1.5
    return dict(mix, **over)


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    assert traffic.open_loop_plan(_mix(name), 40) == traffic.open_loop_plan(_mix(name), 40)
    assert loadgen.prompt_ids(3000000019, 5, 64, 151936) == loadgen.prompt_ids(3000000019, 5, 64, 151936)
    assert loadgen.prompt_ids(3000000019, 5, 64, 151936) != loadgen.prompt_ids(3000000019, 6, 64, 151936)


@pytest.mark.parametrize("name", MIXES)
def test_the_order_seed_orders_the_same_work(name):
    a = traffic.open_loop_plan(_mix(name, order_seed=1), 40)["requests"]
    b = traffic.open_loop_plan(_mix(name, order_seed=2), 40)["requests"]
    for key in ("prompt_tokens", "max_tokens"):
        for phase in ("warmup", "window"):
            assert sorted(r[key] for r in a if r["phase"] == phase) == \
                sorted(r[key] for r in b if r["phase"] == phase)
    assert [r["prompt_tokens"] for r in a] != [r["prompt_tokens"] for r in b]
    mix = _mix(name)
    assert len(a) == len(b) == round(mix["rate"] * mix["warmup_s"]) + round(mix["rate"] * 40)


@pytest.mark.parametrize("name", MIXES)
def test_lengths_are_the_distribution_drawn_and_reported(name):
    mix = _mix(name, rate=10.0)
    reqs = [r for r in traffic.open_loop_plan(mix, 40)["requests"] if r["phase"] == "window"]
    d = traffic.describe([r["prompt_tokens"] for r in reqs])
    spec = mix["prompt_tokens"]
    assert d["n"] == 400 and spec["min"] <= d["min"] and d["max"] <= spec["max"]
    assert abs(d["p50"] - spec["median"]) <= 0.05 * spec["median"]
    # arrivals: all inside the window, in order, at the asked rate
    due = [r["due"] for r in reqs]
    assert due == sorted(due) and mix["warmup_s"] < due[0] and due[-1] < mix["warmup_s"] + 40


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_sends_the_mix_s_one_schedule_and_other_tokens(name):
    mix = _mix(name)
    assert mix["order_seed"] == 23  # the schedule the bounds were measured on
    assert loadgen.prompt_ids(1, 0, 32, 151936) != loadgen.prompt_ids(2, 0, 32, 151936)
    mix.pop("order_seed")
    with pytest.raises(KeyError):
        traffic.open_loop_plan(mix, 40)


@pytest.mark.parametrize("key, value", [("arrivals", "uniform"), ("prompt_tokens", {"dist": "fixed", "value": 64})])
def test_a_process_that_was_never_measured_is_refused(key, value):
    with pytest.raises(ValueError, match="unknown"):
        traffic.open_loop_plan(_mix("chat", **{key: value}), 40)


def test_prefill_buckets_cover_the_mix():
    mix = _mix("chat")
    assert traffic.prefill_buckets(mix) == [32, 64, 128, 256, 512, 1024, 2048]
    assert traffic.prefill_buckets({"prompt_tokens": {"min": 256, "max": 2048}}) == [256, 512, 1024, 2048]
    assert traffic.prefill_buckets({"prompt_tokens": {"min": 300, "max": 1500}}) == [512, 1024, 2048]


def test_percentile_states_its_sample_count():
    p = stats.percentile(range(1, 101), 90)
    assert p == {"value": 90, "n": 100, "beyond": 10}
    assert stats.percentile([], 90)["n"] == 0 and math.isnan(stats.percentile([], 90)["value"])
    assert stats.percentile([5.0], 90) == {"value": 5.0, "n": 1, "beyond": 0}


def test_a_failed_request_counts_as_the_worst():
    # 20 attempted, 17 answered: the 90th percentile is the 18th of 20, one of the missing
    p = stats.percentile([float(i) for i in range(17)], 90, attempted=20)
    assert p["n"] == 20 and math.isinf(p["value"])
    # 19 answered: rank 18 is still a real sample
    assert stats.percentile([float(i) for i in range(19)], 90, attempted=20)["value"] == 17.0


def test_quartile_spread_is_the_drivers():
    import statistics

    v = [10.0, 10.2, 9.9, 10.1, 10.4, 9.8]
    q1, _, q3 = statistics.quantiles(v, n=4)
    assert stats.quartile_spread(v) == (q3 - q1) / statistics.median(v)
