"""The training step's scopes reader on a hand-made document and hand-made ``train_step`` spans, the counts of the
cell's kernels and FLOPs by hand at the cell's shapes, the new metric files against both, and the cell as ISSUE 40
names it."""

import json
import os
import types

import pytest

from bench.harness import loader, train_scopes

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELL = "kanana2-30b-a3b-pretrain-ep8.seq8k"
CFG = json.load(open(os.path.join(ROOT, "bench", "configs", "kanana2-30b-a3b-pretrain-ep8.json")))
PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("moe_train_mfu", "mla_flash_fwd_roofline", "mla_flash_bwd_roofline", "expert_mm_roofline", "train_experts_share",
       "train_mla_attn_share", "train_expert_local_share", "train_expert_load_max_over_mean")


def op(name, start, dur, scope_path, program="5"):
    return [name, float(start), float(dur), f"jit(train_step)/{scope_path}/dot_general:", program]


DOC = {
    "modules": [["jit_train_step(5)", 0.0, 5000.0], ["jit_train_step(5)", 6000.0, 5000.0], ["jit_norms(8)", 12000.0, 50.0]],
    "extent_ns": [-100.0, 13000.0], "host": [],
    "ops": [
        op("fusion.1", 0, 300, "jvp(DeepseekV3ForCausalLMModule)/model/layers_1/self_attn/mla_proj/q_proj"),
        op("flash_attention_fwd.2", 300, 500, "jvp(DeepseekV3ForCausalLMModule)/model/layers_1/self_attn/mla_attn"),
        op("fusion.3", 800, 100, "jvp(DeepseekV3ForCausalLMModule)/model/layers_1/self_attn/rope"),
        op("fusion.4", 900, 100, "jvp(DeepseekV3ForCausalLMModule)/model/layers_1/self_attn/o_proj/o_proj"),
        op("fusion.5", 1000, 50, "jvp(DeepseekV3ForCausalLMModule)/model/layers_1/mlp/router"),
        op("conditional.6", 1100, 900, "jvp(DeepseekV3ForCausalLMModule)/model/layers_1/mlp/while/body/cond"),  # encloses the next three: keeps 100
        op("gather.7", 1150, 100, "jvp(DeepseekV3ForCausalLMModule)/model/layers_1/mlp/while/body/cond/branch_1_fun/checkpoint/expert_dispatch"),
        op("gmm.8", 1250, 600, "jvp(DeepseekV3ForCausalLMModule)/model/layers_1/mlp/while/body/cond/branch_1_fun/checkpoint/expert_mm"),
        op("scatter.9", 1850, 100, "jvp(DeepseekV3ForCausalLMModule)/model/layers_1/mlp/while/body/cond/branch_1_fun/expert_combine"),
        op("fusion.10", 2000, 200, "jvp(DeepseekV3ForCausalLMModule)/model/layers_1/mlp/shared_expert/shared_experts/gate_proj"),
        op("fusion.11", 2200, 300, "jvp(DeepseekV3ForCausalLMModule)/model/layers_0/mlp/gate_proj"),
        op("fusion.12", 2500, 400, "jvp(DeepseekV3ForCausalLMModule)/lm_head"),
        op("tgmm.13", 3000, 400, "transpose(jvp(DeepseekV3ForCausalLMModule))/model/layers_1/mlp/while/body/cond/branch_1_fun/checkpoint/rematted_computation/expert_mm"),
        op("fusion.14", 3400, 600, "adamw"),  # no scope of the model's
        op("gmm.8", 7250, 600, "jvp(DeepseekV3ForCausalLMModule)/model/layers_1/mlp/while/body/cond/branch_1_fun/checkpoint/expert_mm"),  # the second run
        op("fusion.99", 12000, 50, "expert_mm", "8"),  # another program: not counted
    ],
}


def span(step, ts, dur, **args):
    return {"name": "train_step", "cat": "trainer", "ts": ts, "dur": dur, "args": dict(args, step=step, tokens=16384)}


COUNTS = dict(expert_assignments=393216.0, expert_assignments_local=49000.0, expert_tokens_max=4000.0)
SPANS = [span(7, 1.0, 0.9, **COUNTS), span(8, 2.0, 0.9, **COUNTS), span(9, 3.0, 0.9, **dict(COUNTS, expert_assignments_local=51000.0)),
         span(10, 4.0, 0.9), {"name": "evaluate", "ts": 2.0, "dur": 0.1, "args": COUNTS}]


def test_scope_of_takes_the_innermost_known_scope():
    path = "jit(train_step)/jvp(M)/model/layers_1/mlp/shared_expert/shared_experts/gate_proj/dot_general:"
    assert train_scopes.scope_of(path) == "shared_expert"
    assert train_scopes.scope_of("jit(train_step)/jvp(M)/model/layers_0/mlp/gate_proj/dot_general:") == "mlp"
    assert train_scopes.scope_of("jit(train_step)/transpose(jvp(M))/model/layers_2/self_attn/mla_attn/pallas_call:") == "mla_attn"
    assert train_scopes.scope_of("jit(train_step)/add:") is None and train_scopes.scope_of(None) is None


def test_reduce_sums_own_time_by_scope_over_the_step_programs_runs():
    out = train_scopes.reduce(DOC)
    assert out["runs"] == 2
    assert out["ns_by_scope"] == {"mla_proj": 300.0, "mla_attn": 500.0, "rope": 100.0, "o_proj": 100.0, "router": 50.0,
                                  "mlp": 100.0 + 300.0, "expert_dispatch": 100.0, "expert_mm": 600.0 + 400.0 + 600.0,
                                  "expert_combine": 100.0, "shared_expert": 200.0, "lm_head": 400.0, "unscoped": 600.0}
    assert out["ns"] == sum(out["ns_by_scope"].values())


def test_a_program_without_the_scopes_reads_nothing():
    dense = {"modules": [["jit_train_step(5)", 0.0, 100.0]], "extent_ns": [0.0, 100.0], "host": [],
             "ops": [op("fusion.1", 0, 100, "jvp(Qwen2ForCausalLMModule)/model/layers/mlp/gate_proj")]}
    assert train_scopes.reduce(dense) is None
    assert train_scopes.reduce({"modules": [], "extent_ns": [0.0, 1.0], "host": [], "ops": []}) is None


def test_counted_sums_the_steps_that_lie_inside_and_carry_the_counters():
    assert train_scopes.counted(SPANS, 0.5, 3.5) == dict(expert_assignments=2 * 393216.0, expert_assignments_local=98000.0,
                                                        expert_tokens_max=8000.0, steps=2)
    assert train_scopes.counted(SPANS, 0.5, 9.0)["steps"] == 3  # step 10 carries no counter (a step that did not log)
    assert train_scopes.counted(SPANS, 3.95, 9.0) is None and train_scopes.counted([], 0.0, 9.0) is None


def run_with(scopes=None, traced=None, window=None):
    return {"kind": "train", "config": CFG, "seq_len": 8192, "rows_per_chip": 2, "train_tokens_per_s": 5000.0,
            "peaks": PEAKS, "step_ends": [0.0, 3.0, 6.0], "tracer": None,
            "train_scopes": {"scopes": scopes, "traced": traced, "window": window}}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_that_finds_nothing_returns_none(name):
    mod = loader.module_from("metrics", name)
    assert mod.reduce({}) is None
    if name != "moe_train_mfu":
        assert mod.reduce(run_with()) is None
    seq2k = json.load(open(os.path.join(ROOT, "bench", "configs", "qwen2-0.5b-pretrain.json")))
    assert mod.reduce(dict(run_with(), config=seq2k)) is None  # the dense training cell's run: nothing of this to read


def test_the_shares_and_the_counters_metrics_read_the_table():
    scopes = train_scopes.reduce(DOC)
    window = train_scopes.counted(SPANS, 0.5, 9.0)
    run = run_with(scopes, train_scopes.counted(SPANS, 0.5, 3.5), window)
    m = lambda name: loader.module_from("metrics", name).reduce(run)
    assert m("train_experts_share") == pytest.approx((50 + 100 + 1600 + 100 + 200) / scopes["ns"] * 100)
    assert m("train_mla_attn_share") == pytest.approx((300 + 100 + 500) / scopes["ns"] * 100)
    assert m("train_expert_local_share") == pytest.approx(149000.0 / (3 * 393216.0) * 100)
    assert m("train_expert_load_max_over_mean") == pytest.approx(12000.0 * 16 / 149000.0)
    # the roofline: 49,000 held assignments a traced step, two runs of the step program in the trace, 4 expert layers
    k = loader.module_from("kernels", "expert_mm")
    least = k.least_seconds(CFG, 98000.0, 4 * 2, PEAKS)
    assert m("expert_mm_roofline") == pytest.approx(least / 1600e-9 * 100)


def test_expert_mm_counts_by_hand():
    k = loader.module_from("kernels", "expert_mm")
    # an assignment row: gate, up and down are 2048 x 768 each, 2 FLOPs a weight, forward and two backward products
    assert k.flops(CFG, 1) == 3 * 2 * 2048 * 768 * 3 == 28_311_552
    # a pass: the three held stacks (16 x 2048 x 768 bf16 each = 50,331,648 B) a layer-step, and a row's
    # 3 x 2048 + 3 x 768 elements in and out at 2 B = 16,896 B; three passes
    assert k.bytes_moved(CFG, 1000, 4) == 3 * (4 * 3 * 50_331_648 + 1000 * 16_896)
    assert k.least_seconds(CFG, 10000.0, 4, PEAKS) == pytest.approx(max(10000 * 28_311_552 / 197e12,
                                                                       3 * (12 * 50_331_648 + 10000 * 16_896) / 819e9))


def test_mla_moe_flops_by_hand_at_the_cells_shapes():
    k = loader.module_from("kernels", "mla_moe_flops")
    # attention: q 2048 x 32 x 192, kv_a 2048 x 576, kv_b 512 x 32 x 256, o 4096 x 2048
    assert k.attention_params(CFG) == 12_582_912 + 1_179_648 + 4_194_304 + 8_388_608 == 26_345_472
    # an expert layer's MLP on this chip: router 2048 x 128; 6 x 16/128 = 0.75 routed experts and 2 shared of 3 x 2048 x 768
    assert k.expert_layer_params(CFG) == 262_144 + 2.75 * 4_718_592 == 13_238_272
    # 5 layers: layer 0 dense at 6144, four expert layers; the head over 16,032
    assert k.matmul_params(CFG) == 5 * 26_345_472 + 3 * 2048 * 6144 + 4 * 13_238_272 + 2048 * 16032 == 255_262_720
    # scores at 192 and values at 128 over half of 8,192 positions, 32 heads: 8192 x 32 x 320 a layer
    assert k.attention_flops_per_token(CFG, 8192) == 83_886_080
    assert k.forward_flops_per_token(CFG, 8192) == 2 * 255_262_720 + 5 * 83_886_080 == 929_955_840
    assert k.train_flops_per_token(CFG, 8192) == 2_789_867_520
    run = run_with()
    assert loader.module_from("metrics", "moe_train_mfu").reduce(run) == pytest.approx(5000.0 * 2_789_867_520 / 197e12 * 100)


@pytest.mark.parametrize("kernel,at_qk,at_v,nbytes", [
    # q and k are 2*8192*32*192*2 = 201,326,592 B; v, o and dO 2*8192*32*128*2 = 134,217,728 B; a stats row 2,097,152 B
    ("flash_attention_fwd", 1, 1, 201_326_592 * 2 + 134_217_728 * 2 + 2_097_152),
    ("flash_attention_bwd_dq", 2, 1, 201_326_592 * 3 + 134_217_728 * 2 + 2_097_152 * 2),
    ("flash_attention_bwd_dkv", 2, 2, 201_326_592 * 4 + 134_217_728 * 4 + 2_097_152 * 2),
])
def test_flash_attention_mla_flops_and_bytes_by_hand(kernel, at_qk, at_v, nbytes):
    k = loader.module_from("kernels", "flash_attention_mla")
    shape = k.shape_of(CFG, 2, 8192)
    assert shape == {"batch": 2, "seq": 8192, "heads": 32, "qk_dim": 192, "v_dim": 128, "bytes": 2}
    # one causal [8192, 8192] product over 2 x 32 heads: 2*32*8192*8192 = 4,294,967,296 a unit of head width
    assert k.flops(kernel, shape) == 4_294_967_296 * (at_qk * 192 + at_v * 128)
    assert k.bytes_moved(kernel, shape) == nbytes
    assert k.least_seconds(kernel, shape, PEAKS) == pytest.approx(k.flops(kernel, shape) / 197e12)  # compute bound


def test_the_roofline_metrics_read_the_calls_seen_in_a_trace():
    trace = {"op_seconds": {"flash_attention_fwd.3": 0.2, "flash_attention_bwd_dq.4": 0.15, "flash_attention_bwd_dkv.5": 0.25,
                            "fusion.1": 1.0},
             "op_counts": {"flash_attention_fwd.3": 32, "flash_attention_bwd_dq.4": 16, "flash_attention_bwd_dkv.5": 16, "fusion.1": 4}}
    run = dict(run_with(), trace=trace)
    unit = 4_294_967_296
    fwd = loader.module_from("metrics", "mla_flash_fwd_roofline").reduce(run)
    assert fwd == pytest.approx(32 * unit * 320 / 197e12 / 0.2 * 100)
    bwd = loader.module_from("metrics", "mla_flash_bwd_roofline").reduce(run)
    assert bwd == pytest.approx(16 * unit * (512 + 640) / 197e12 / 0.4 * 100)


def test_the_cell_is_what_the_issue_names():
    cell = loader.cell(CELL)
    assert cell["workload"] == dict(cell["workload"], config="kanana2-30b-a3b-pretrain-ep8", traffic="seq8k", chips=1)
    assert [m["name"] for m in cell["end_to_end"]] == ["train_tokens_per_s", "setup_s"]
    names = [m["name"] for m in cell["per_layer"]]
    assert set(NEW) <= set(names) and {"train_step_ms", "device_idle.train"} <= set(names)
    assert not {"train_mfu", "flash_fwd_roofline", "flash_bwd_roofline"} & set(names)
    job, b = cell["traffic"], cell["config"]["bench"]
    assert (job["kind"], job["seq_len"], job["rows"], job["vocab_span"]) == ("train_stream", 8192, 4096, 4096)
    assert (job["check_steps"], job["warm_steps"], job["trace_steps"]) == (3, 5, 4)
    assert b["training"]["per_device_train_batch_size"] == 2 and b["training"]["gradient_accumulation_steps"] == 1
    assert b["training"]["use_scan_layers"] is False and b["kind"] == "train" and b["chips"] == 1
    assert sorted(b["reduced"]) == ["n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert cell["config"]["n_routed_experts_total"] == 128 and cell["config"]["num_experts_per_tok"] == 6
    man = loader.manifest()
    entry = next(c for c in man["configs"] if c["name"] == "kanana2-30b-a3b-pretrain-ep8")
    assert sorted(entry["reduced"]) == sorted(b["reduced"])
    # every width of the catalog's row is in the file as published
    for key, value in dict(hidden_size=2048, intermediate_size=6144, moe_intermediate_size=768, kv_lora_rank=512,
                           qk_nope_head_dim=128, qk_rope_head_dim=64, qk_head_dim=192, v_head_dim=128, head_dim=64,
                           num_attention_heads=32, num_key_value_heads=32, n_shared_experts=2, n_group=1, topk_group=1,
                           routed_scaling_factor=2.448, rope_theta=1000000, first_k_dense_replace=1).items():
        assert cell["config"][key] == value, key


def test_the_configuration_builds_the_programs_model_class():
    from bench.harness.common import NOT_MODEL_KEYS

    cfg = loader.resolve(CFG["bench"]["config_class"])(**{k: v for k, v in CFG.items() if k not in NOT_MODEL_KEYS})
    assert cfg.experts_held == (0, 16) and cfg.n_routed_experts_total == 128 and cfg.num_hidden_layers == 5
    assert cfg.qk_head_dim == 192 and cfg.v_head_dim == 128 and cfg.q_lora_rank is None
    assert loader.resolve(CFG["bench"]["model_class"]).config_class is type(cfg)
    assert types.ModuleType  # (keeps the import used where the run fixture is not)
