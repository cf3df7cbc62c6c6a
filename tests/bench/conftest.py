"""The benchmark's tests run on the CPU beside the repo's own; ``bench`` is
imported from the checkout root."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import json  # noqa: E402
import shutil  # noqa: E402

import pytest  # noqa: E402

TINY_MODEL = dict(hidden_size=64, intermediate_size=160, num_hidden_layers=2, num_attention_heads=4,
                  num_key_value_heads=2, vocab_size=2048, max_window_layers=2,
                  initializer_range=0.15)  # so that the layers, not the token's own embedding, decide the logits


@pytest.fixture
def tiny_root(tmp_path, monkeypatch):
    """A copy of the benchmark with a tiny serving cell and a tiny training cell
    beside the real ones (new files and entries only), and the harness's look
    for a chip and its compile cache switched off."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    load = lambda *p: json.load(open(os.path.join(ROOT, "bench", *p)))
    dump = lambda doc, *p: json.dump(doc, open(os.path.join(root, "bench", *p), "w"))
    serve = dict(load("configs", "qwen2-1.5b-serve.json"), **TINY_MODEL)
    serve["bench"] = dict(serve["bench"], require_paged_kernel=False,
                          limits={"served_token_gap": 1e-3, "served_token_gap_mean": 1e-5},
                          precision={"weights": "float32", "compute": "float32", "control": "int8",
                                     "control_engine": {"kv_cache_quant": "int8"}},
                          engine={"max_batch_size": 4, "block_size": 4, "num_blocks": 256,
                                  "max_blocks_per_seq": 32, "decode_steps": 4, "eos_token_id": []})
    dump(serve, "configs", "tiny-serve.json")
    mix = dict(load("traffic", "chat.json"), rate=3.0, warmup_s=2, tpot_min_tokens=4,
               prompt_tokens={"dist": "lognormal", "median": 24, "sigma": 0.6, "min": 8, "max": 64},
               output_tokens={"dist": "lognormal", "median": 24, "sigma": 0.4, "min": 8, "max": 48})
    dump(mix, "traffic", "tinychat.json")
    train = dict(load("configs", "qwen2-0.5b-pretrain.json"), **TINY_MODEL)
    train["bench"] = dict(train["bench"], limits={"loss_gap": 5e-5, "first_grad_gap": 2.5e-3,
                                                  "param_delta_gap": 0.06},
                          precision={"weights": "float32", "compute": "float32", "control": "bfloat16"})
    train["bench"]["training"] = dict(train["bench"]["training"], use_flash_attention=False, bf16=False,
                                      per_device_train_batch_size=2)
    dump(train, "configs", "tiny-train.json")
    dump(dict(load("traffic", "seq2k.json"), seq_len=32, rows=256, vocab_span=128), "traffic", "tinyseq.json")
    peaks = load("peaks.json")
    peaks["devices"]["cpu"] = peaks["devices"]["TPU v5 lite"]
    dump(peaks, "peaks.json")

    man = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cells = {"serve": "tiny-serve.tinychat", "train": "tiny-train.tinyseq"}
    for name, kind, mixname in (("tiny-serve", "serve", "tinychat"), ("tiny-train", "train", "tinyseq")):
        man["configs"].append({"name": name, "source": "test", "file": f"bench/configs/{name}.json",
                               "reduced": [], "why": "test"})
        man["workloads"].append({"name": cells[kind], "config": name, "traffic": mixname, "chips": 1,
                                 "why": "test"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            if any("serve.chat" in w for w in m["workloads"]):
                m["workloads"].append(cells["serve"])
            if any("pretrain" in w for w in m["workloads"]):
                m["workloads"].append(cells["train"])
    json.dump(man, open(os.path.join(root, "BENCHMARK.json"), "w"))

    from bench.harness import common, loader, serve as serve_mod, train as train_mod

    monkeypatch.setattr(common, "require_tpu", lambda chips: common.device_info())
    monkeypatch.setattr(serve_mod, "enable_compile_cache", lambda r: "off")
    monkeypatch.setattr(train_mod, "enable_compile_cache", lambda r: "off")
    monkeypatch.setattr(loader, "ROOT", loader.ROOT)  # run.main repoints it; put it back afterwards
    return root
