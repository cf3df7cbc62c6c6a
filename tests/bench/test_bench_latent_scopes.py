"""The latent scopes' reader on a hand-made document, and the new metric files against it."""

import os
import types

import pytest

from bench.harness import latent_scopes, loader


def op(name, start, dur, scope_path, program="7"):
    return [name, float(start), float(dur), f"jit(_mixed_flat_impl)/{scope_path}/dot_general:", program]


DOC = {
    "modules": [["jit__mixed_flat_impl(7)", 0.0, 1000.0], ["jit__prefill_impl(9)", 2000.0, 100.0]],
    "ops": [
        op("while.1", 0, 400, "experts/while"),           # encloses the next two: keeps 100 of its own
        op("fusion.1", 50, 200, "experts/while/body"),
        op("fusion.2", 250, 100, "experts/while/body"),
        op("fusion.3", 400, 100, "router"),
        op("fusion.4", 500, 100, "indexer"),
        op("fusion.5", 600, 50, "index_topk"),
        op("fusion.6", 650, 150, "mla_attn/while/body"),
        op("fusion.7", 800, 50, "window_attn"),
        op("fusion.8", 850, 50, "latent_gather"),
        op("fusion.9", 900, 100, "closed_call"),          # no scope
        op("fusion.10", 2000, 100, "experts", program="9"),  # another program: not counted
    ],
}


def test_scope_of_takes_the_innermost_known_scope():
    assert latent_scopes.scope_of("jit(_decode_impl)/while/body/experts/while/body/dot_general:") == "experts"
    assert latent_scopes.scope_of("jit(_decode_impl)/kv_write/latent_plane/scatter:") == "kv_write"
    assert latent_scopes.scope_of("jit(_decode_impl)/while/body/add:") is None
    assert latent_scopes.scope_of(None) is None


def test_reduce_sums_own_time_by_scope_over_the_step_programs():
    out = latent_scopes.reduce(DOC)
    assert out["ns"] == 1000.0
    assert out["ns_by_scope"] == {"experts": 400.0, "router": 100.0, "indexer": 100.0, "index_topk": 50.0,
                                  "mla_attn": 150.0, "window_attn": 50.0, "latent_gather": 50.0, "unscoped": 100.0}


def test_a_program_without_latent_scopes_reads_nothing():
    dense = {"modules": [["jit__decode_impl(3)", 0.0, 100.0]],
             "ops": [["fusion.1", 0.0, 100.0, "jit(_decode_impl)/while/body/closed_call/qkv/dot_general:", "3"]]}
    assert latent_scopes.reduce(dense) is None
    assert latent_scopes.share({"kind": "serve", "tracer": None}, ("experts",)) is None
    assert latent_scopes.counter_delta({"kind": "serve", "before": {"ledger": {}}, "after": {"ledger": {}}},
                                       "expert_assignments") is None


#: what the metric files read of a run's tracer: the directory its trace was written to, named for the cell
TRACED = types.SimpleNamespace(dir=os.path.join("bench_trace", "dots3-note-serve-ep8.longdoc"))


def test_the_held_experts_are_the_run_configurations():
    """``expert_load_max_over_mean`` takes the experts held from the configuration of the cell the run was traced
    for, not from a constant: 32 here; a run that carries no trace directory reads nothing."""
    assert latent_scopes.config_of({"kind": "serve", "tracer": TRACED})["n_routed_experts"] == 32
    assert latent_scopes.config_of({"kind": "serve", "tracer": None}) is None
    assert latent_scopes.config_of({"kind": "train", "tracer": TRACED}) is None
    counts = {"expert_tokens_max": 10, "expert_assignments_local": 40}
    run = {"kind": "serve", "tracer": None, "before": {"ledger": dict.fromkeys(counts, 0)}, "after": {"ledger": counts}}
    assert loader.module_from("metrics", "expert_load_max_over_mean").reduce(run) is None
    assert loader.module_from("metrics", "expert_load_max_over_mean").reduce(dict(run, tracer=TRACED)) == 8.0


@pytest.mark.parametrize("name, want", [
    ("experts_share", 50.0), ("indexer_share", 15.0), ("latent_attn_share", 25.0),
    ("expert_local_share", 12.5), ("expert_load_max_over_mean", 2.0), ("index_kept_share", 40.0),
    ("mixed_launch_ms", 2.0),
])
def test_metric_files_read_the_run(name, want):
    run = {"kind": "serve", "tracer": TRACED, "latent_scopes": latent_scopes.reduce(DOC),
           "trace": {"module_runs_s": {"jit__mixed_flat_impl": [0.001, 0.002, 0.003]}},
           "before": {"t": 10.0, "ledger": {"expert_assignments": 0, "expert_assignments_local": 0,
                                             "expert_tokens_max": 0, "index_candidates": 0, "index_selected": 0}},
           "after": {"t": 50.0, "ledger": {"expert_assignments": 8000, "expert_assignments_local": 1000,
                                            "expert_tokens_max": 62.5, "index_candidates": 1000, "index_selected": 400}}}
    mod = loader.module_from("metrics", name)
    assert mod.reduce(run) == pytest.approx(want)
    entry = next(m for m in loader.manifest()["per_layer"] if m["name"] == name)
    assert (mod.NAME, mod.UNIT, mod.MOVES, mod.SOURCE, mod.LAYER) == (
        entry["name"], entry["unit"], entry["moves"], entry["source"], entry["layer"])
    assert entry["workloads"] == ["dots3-note-serve-ep8.longdoc"]
