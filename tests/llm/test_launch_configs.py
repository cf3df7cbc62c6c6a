"""The shipped launch artifacts (llm/config/<model>/*.json — the reference's
canonical launch interface) must parse and drive their entry points end-to-end.

Each test loads the SHIPPED json, overrides only model/data/output/size knobs
to tiny fixtures, and runs the real entry main on the 8-device CPU mesh —
the pretrain config keeps its tp2 x sharding4 stage2 topology (the baseline
row's layout, /root/reference/llm/docs/pretrain.rst:188)."""

import json
import numpy as np
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "llm"))
sys.path.insert(0, os.path.join(REPO, "llm", "alignment", "dpo"))

CONFIG_DIR = os.path.join(REPO, "llm", "config", "llama")

from test_entrypoints import tiny_hub  # noqa: E402,F401  (shared fixture)


def _load(name, **overrides):
    with open(os.path.join(CONFIG_DIR, name)) as f:
        cfg = json.load(f)
    cfg.update(overrides)
    return cfg


class TestShippedConfigs:
    def test_pretrain_tp2sd4_stage2(self, tiny_hub, tmp_path, monkeypatch):
        """The headline-row artifact: tp2 x sharding4 stage2 preserved on the
        8-device CPU mesh, tiny model/data substituted."""
        import run_pretrain

        cfg = _load(
            "pretrain-llama_7b-tp2sd4_stage2.json",
            model_name_or_path=str(tiny_hub["model"]),
            tokenizer_name_or_path=str(tiny_hub["model"]),
            input_dir=str(tiny_hub["corpus"]),
            output_dir=str(tmp_path / "out"),
            max_seq_length=32,
            gradient_accumulation_steps=1,
            max_steps=2,
            save_steps=2,
            eval_steps=2,
            warmup_steps=1,
            do_eval=False,
            bf16=False,
            dtype="float32",
            use_flash_attention=False,
        )
        assert cfg["tensor_parallel_degree"] == 2 and cfg["sharding_parallel_degree"] == 4
        assert cfg["sharding"] == "stage2"
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        monkeypatch.setattr(sys, "argv", ["run_pretrain.py", str(p)])
        trainer = run_pretrain.main()
        assert trainer.state.global_step == 2
        mesh = trainer.mesh
        assert mesh.shape.get("tp") == 2 and mesh.shape.get("fsdp") == 4

    def test_sft_argument(self, tiny_hub, tmp_path, monkeypatch):
        import run_finetune

        cfg = _load(
            "sft_argument.json",
            model_name_or_path=str(tiny_hub["model"]),
            dataset_name_or_path=str(tiny_hub["sft"]),
            output_dir=str(tmp_path / "out"),
            max_length=32,
            src_length=16,
            gradient_accumulation_steps=1,
            per_device_train_batch_size=1,
            max_steps=2,
            evaluation_strategy="no",
            save_strategy="no",
            do_eval=False,
            bf16=False,
            dtype="float32",
            use_flash_attention=False,
        )
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        monkeypatch.setattr(sys, "argv", ["run_finetune.py", str(p)])
        trainer = run_finetune.main()
        assert trainer.state.global_step == 2

    def test_dpo_argument(self, tiny_hub, tmp_path, monkeypatch):
        import run_dpo

        data_dir = tmp_path / "pref"
        data_dir.mkdir()
        with open(data_dir / "train.json", "w") as f:
            for _ in range(16):
                f.write(json.dumps({"src": "a b", "chosen": "c d", "rejected": "e f"}) + "\n")
        cfg = _load(
            "dpo_argument.json",
            model_name_or_path=str(tiny_hub["model"]),
            dataset_name_or_path=str(data_dir),
            output_dir=str(tmp_path / "out"),
            max_length=16,
            max_prompt_length=8,
            gradient_accumulation_steps=1,
            max_steps=2,
            evaluation_strategy="no",
            save_strategy="no",
            do_eval=False,
            bf16=False,
            dtype="float32",
            use_flash_attention=False,
            tensor_parallel_degree=2,  # tiny model has 2 heads; the 7B artifact says 8
        )
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg))
        monkeypatch.setattr(sys, "argv", ["run_dpo.py", str(p)])
        trainer = run_dpo.main()
        assert trainer.state.global_step == 2


# ---------------------------------------------------------------- config zoo
ZOO_ROOT = os.path.join(REPO, "llm", "config")
ZOO_DIRS = sorted(d for d in os.listdir(ZOO_ROOT)
                  if os.path.isdir(os.path.join(ZOO_ROOT, d)))

sys.path.insert(0, os.path.join(REPO, "tests", "transformers"))
from test_modeling_common import CAUSAL_CASES  # noqa: E402

# config-zoo dir -> tiny family case (test_modeling_common registry)
ZOO_FAMILY = {
    "qwen": "qwen", "qwen2": "qwen2", "mixtral": "mixtral", "mistral": "mistral",
    "baichuan": "baichuan", "deepseek-v2": "deepseek_v2", "deepseek-v3": "deepseek_v3", "gpt-3": "gpt",
    "opt": "opt", "bloom": "bloom", "chatglm": "chatglm", "chatglm2": "chatglm_v2",
    "gemma": "gemma", "yuan": "yuan", "llama": "llama",
}


def _zoo_files():
    out = []
    for d in ZOO_DIRS:
        for f in sorted(os.listdir(os.path.join(ZOO_ROOT, d))):
            if f.endswith(".json"):
                out.append((d, f))
    return out


class TestConfigZoo:
    def test_every_family_has_a_config_dir(self):
        assert len(ZOO_DIRS) >= 12, ZOO_DIRS
        for d in ZOO_DIRS:
            assert d in ZOO_FAMILY, f"no tiny-family mapping for llm/config/{d}"

    @pytest.mark.parametrize("dirname,fname", _zoo_files())
    def test_config_parses_into_entry_dataclasses(self, dirname, fname):
        """Every shipped JSON must round-trip through the SAME dataclasses its
        entry point uses — unknown or mistyped keys fail here."""
        import run_finetune
        import run_pretrain
        from paddlenlp_tpu.trainer import PdArgumentParser

        path = os.path.join(ZOO_ROOT, dirname, fname)
        if "pretrain" in fname:
            parser = PdArgumentParser((run_pretrain.ModelArguments, run_pretrain.DataArguments,
                                       run_pretrain.PreTrainingArguments))
        elif "dpo" in fname:
            import run_dpo
            parser = PdArgumentParser((run_dpo.ModelArguments, run_dpo.DPOArguments,
                                       run_dpo.TrainingArguments))
        else:  # sft / lora
            parser = PdArgumentParser((run_finetune.ModelArguments, run_finetune.DataArguments,
                                       run_finetune.TrainingArguments))
        parsed = parser.parse_json_file(path)
        assert parsed[0].model_name_or_path

    @pytest.mark.parametrize("dirname", [d for d in ZOO_DIRS if d != "llama"])
    def test_sft_smoke_trains_tiny(self, dirname, tmp_path, monkeypatch):
        """The shipped sft artifact drives run_finetune end-to-end on a tiny
        checkpoint of ITS OWN family (2 steps, degrees shrunk to fit)."""
        import run_finetune
        from tokenizers import Tokenizer
        from tokenizers.models import WordLevel
        from tokenizers.pre_tokenizers import Whitespace

        from paddlenlp_tpu.transformers import PretrainedTokenizer

        cls, cfg_fn = CAUSAL_CASES[ZOO_FAMILY[dirname]]
        model_dir = tmp_path / "tiny"
        cfg = cfg_fn()
        cfg.eos_token_id = 2
        cfg.pad_token_id = 0
        cls.from_config(cfg, seed=0).save_pretrained(str(model_dir))
        vocab = {"<pad>": 0, "<s>": 1, "</s>": 2, "<unk>": 3}
        for i, w in enumerate("a b c d e f g h i j k l m n o p".split()):
            vocab[w] = i + 4
        t = Tokenizer(WordLevel(vocab, unk_token="<unk>"))
        t.pre_tokenizer = Whitespace()
        PretrainedTokenizer(tokenizer_object=t, pad_token="<pad>", bos_token="<s>",
                            eos_token="</s>", unk_token="<unk>").save_pretrained(str(model_dir))
        data_dir = tmp_path / "sft"
        data_dir.mkdir()
        rows = [{"src": "a b c", "tgt": "d e"}, {"src": "f g", "tgt": "h i j"}] * 16
        with open(data_dir / "train.json", "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")

        with open(os.path.join(ZOO_ROOT, dirname, "sft_argument.json")) as f:
            cfg_json = json.load(f)
        cfg_json.update(
            model_name_or_path=str(model_dir),
            dataset_name_or_path=str(data_dir),
            output_dir=str(tmp_path / "out"),
            max_length=32, src_length=16,
            per_device_train_batch_size=1, gradient_accumulation_steps=1,
            max_steps=2, num_train_epochs=1,
            evaluation_strategy="no", save_strategy="no", do_eval=False,
            bf16=False, dtype="float32", use_flash_attention=False,
            tensor_parallel_degree=1, pipeline_parallel_degree=1,
            sharding_parallel_degree=1, recompute=False, zero_padding=False,
        )
        p = tmp_path / "cfg.json"
        p.write_text(json.dumps(cfg_json))
        monkeypatch.setattr(sys, "argv", ["run_finetune.py", str(p)])
        trainer = run_finetune.main()
        assert trainer.state.global_step == 2
        losses = [h["loss"] for h in trainer.state.log_history if "loss" in h]
        assert losses and all(np.isfinite(l) for l in losses)
