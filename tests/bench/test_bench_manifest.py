"""BENCHMARK.json against the contract's limits and the files it names, and the
proof that a configuration, a mix and a per-layer metric can each be added as
new files plus new entries, with no edit to a file that is there."""

import json
import os
import re
import shutil

import pytest

from bench.harness import loader

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
MAN = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def _reports(metric, cell):
    return cell in metric.get("workloads", [w["name"] for w in MAN["workloads"]])


def test_keys_and_limits():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= MAN["run_seconds"] <= 51 and isinstance(MAN["run_seconds"], int)
    assert MAN["command"][1].startswith("bench/") and 1 <= len(MAN["paths"]) <= 16
    assert len(json.dumps(MAN)) < 64 * 1024
    assert 1 <= len(MAN["configs"]) <= 24 and 1 <= len(MAN["workloads"]) <= 24
    assert 1 <= len(MAN["end_to_end"]) <= 16 and 1 <= len(MAN["per_layer"]) <= 128


@pytest.mark.parametrize("entry", METRICS + MAN["configs"] + MAN["workloads"], ids=lambda e: e["name"])
def test_names_units_and_lines(entry):
    assert NAME.match(entry["name"])
    if "unit" in entry:
        assert UNIT.match(entry["unit"]) and entry["better"] in ("lower", "higher")
        assert entry["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for key in ("why", "layer", "source"):
        if key in entry:
            assert 1 <= len(entry[key]) <= 200 and "\n" not in entry[key] and "\t" not in entry[key]
    for key in ("config", "traffic"):
        if key in entry:
            assert NAME.match(entry[key])


@pytest.mark.parametrize("metric", MAN["end_to_end"], ids=lambda e: e["name"])
def test_end_to_end_entries(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert metric["source"] in ("host_clock", "device_trace")
    assert 0.01 <= metric["bound"] <= 0.1


def test_names_are_unique_and_setup_is_there():
    for group in (METRICS, MAN["configs"], MAN["workloads"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))
    assert "setup_s" in [m["name"] for m in MAN["end_to_end"]]
    pairs = [(w["config"], w["traffic"]) for w in MAN["workloads"]]
    assert len(pairs) == len(set(pairs))


@pytest.mark.parametrize("work", MAN["workloads"], ids=lambda w: w["name"])
def test_every_cell_has_its_files_and_its_metrics(work):
    cell = loader.cell(work["name"], root=ROOT)
    assert cell["config"]["bench"]["kind"] in ("serve", "train")
    assert cell["config"]["bench"]["chips"] == work["chips"] in (1, 4)
    assert os.path.exists(os.path.join(ROOT, "bench", "harness", cell["config"]["bench"]["kind"] + ".py"))
    assert os.path.exists(os.path.join(ROOT, "bench", "reference", cell["config"]["bench"]["reference"] + ".py"))
    e2e = [m["name"] for m in cell["end_to_end"]]
    assert "setup_s" in e2e and len(e2e) >= 2 and len(cell["per_layer"]) >= 1


@pytest.mark.parametrize("metric", MAN["per_layer"], ids=lambda e: e["name"])
def test_per_layer_metric_file_and_arrow(metric):
    assert set(metric) <= {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    mod = loader.module_from("metrics", metric["name"], root=ROOT)
    assert (mod.NAME, mod.UNIT, mod.LAYER, mod.MOVES, mod.SOURCE) == tuple(
        metric[k] for k in ("name", "unit", "layer", "moves", "source"))
    assert mod.reduce({}) is None  # nothing to read: nothing returned
    moved = next(m for m in MAN["end_to_end"] if m["name"] == metric["moves"])
    for w in MAN["workloads"]:
        if _reports(metric, w["name"]):
            assert _reports(moved, w["name"]), (metric["name"], w["name"])


def test_configs_are_used_and_four_chip_share():
    used = {w["config"] for w in MAN["workloads"]}
    assert used == {c["name"] for c in MAN["configs"]}
    four = sum(1 for w in MAN["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(MAN["workloads"]) // 4)
    files = [c["file"] for c in MAN["configs"]]
    assert len(files) == len(set(files))
    for c in MAN["configs"]:
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        assert c["file"].startswith("bench/") and doc["bench"]["source"] == c["source"]
        assert sorted(doc["bench"]["reduced"]) == sorted(c["reduced"]) and len(c["reduced"]) <= 16
        banned = re.compile(r"(hidden|intermediate|latent|state|proj).*size|_dim$|_rank$|head_dim|expansion|per_tok")
        assert not [k for k in c["reduced"] if banned.search(k)]


def test_files_under_paths_are_named_from_allowed_characters():
    for path in MAN["paths"]:
        for base, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(base, f), ROOT)
                assert re.match(r"^[A-Za-z0-9_.\-/]+$", rel) and len(rel) <= 200, rel


def test_a_config_a_mix_and_a_metric_are_added_as_files_and_entries_only(tmp_path):
    """A throw-away configuration, traffic mix and per-layer metric in a copy of
    the checkout: new files and new BENCHMARK.json entries, and the harness
    finds all three with no file of bench/ edited."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {f: open(os.path.join(b, f), "rb").read() for b, _, fs in os.walk(os.path.join(root, "bench")) for f in fs}
    cfg = json.load(open(os.path.join(ROOT, "bench", "configs", MAN["configs"][0]["file"].split("/")[-1])))
    cfg["bench"]["source"] = "https://example.org/throwaway"
    json.dump(cfg, open(os.path.join(root, "bench", "configs", "throwaway-model.json"), "w"))
    mix = json.load(open(os.path.join(ROOT, "bench", "traffic", "chat.json")))
    mix.update(rate=0.5, warmup_s=2)
    json.dump(mix, open(os.path.join(root, "bench", "traffic", "throwaway-mix.json"), "w"))
    with open(os.path.join(root, "bench", "metrics", "throwaway_metric.py"), "w") as f:
        f.write('NAME, UNIT, LAYER, MOVES, SOURCE = "throwaway_metric", "count", "Device", "ttft_p90_ms", '
                '"program_counter"\n\n\ndef reduce(run):\n    return run.get("answer")\n')
    man = json.loads(json.dumps(MAN))
    cell = "throwaway-model.throwaway-mix"
    man["configs"].append({"name": "throwaway-model", "source": cfg["bench"]["source"],
                           "file": "bench/configs/throwaway-model.json", "reduced": [], "why": "test"})
    man["workloads"].append({"name": cell, "config": "throwaway-model", "traffic": "throwaway-mix", "chips": 1,
                             "why": "test"})
    for m in man["end_to_end"]:
        if m["name"] in ("ttft_p90_ms", "serve_tokens_per_s"):
            m["workloads"].append(cell)
    man["per_layer"].append({"name": "throwaway_metric", "unit": "count", "better": "higher",
                             "source": "program_counter", "layer": "Device", "moves": "ttft_p90_ms",
                             "workloads": [cell]})
    json.dump(man, open(os.path.join(root, "BENCHMARK.json"), "w"))

    got = loader.cell(cell, root=root)
    assert got["config"]["bench"]["source"] == "https://example.org/throwaway"
    assert got["traffic"]["rate"] == 0.5
    assert [m["name"] for m in got["per_layer"]] == ["throwaway_metric"]
    assert sorted(m["name"] for m in got["end_to_end"]) == ["serve_tokens_per_s", "setup_s", "ttft_p90_ms"]
    import importlib.util

    spec = importlib.util.spec_from_file_location("bench_run_for_test", os.path.join(root, "bench", "run.py"))
    run = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(run)
    loader.ROOT = root
    try:
        assert run.per_layer_metrics(got, {"answer": 42.0}) == (
            {"throwaway_metric": {"value": 42.0, "unit": "count"}}, [])
        # a reader that finds nothing: left out of the line, and named, so that the run is not correct
        assert run.per_layer_metrics(got, {}) == ({}, ["throwaway_metric"])
    finally:
        loader.ROOT = ROOT
    after = {f: open(os.path.join(b, f), "rb").read() for b, _, fs in os.walk(os.path.join(root, "bench")) for f in fs
             if f in before}
    assert after == before
