"""SFT / LoRA finetuning entry point.

Counterpart of ``/root/reference/llm/run_finetune.py`` (main :77): chat-template
tokenization, ZeroPadding packing (+ segment-mask attention = the flashmask path),
optional LoRA/prefix wrapping, Trainer.
"""

from __future__ import annotations

import json
import os
import sys
from dataclasses import dataclass, field
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from paddlenlp_tpu.data import DataCollatorForSeq2Seq
from paddlenlp_tpu.datasets import ZeroPaddingMapDataset
from paddlenlp_tpu.trainer import PdArgumentParser, Trainer, TrainingArguments
from paddlenlp_tpu.transformers import AutoConfig, AutoModelForCausalLM, AutoTokenizer, LlmMetaConfig
from paddlenlp_tpu.utils.env import enable_compile_cache
from paddlenlp_tpu.utils.log import logger


@dataclass
class ModelArguments:
    model_name_or_path: str = "facebook/llama-7b"
    dtype: str = "bfloat16"
    # PEFT (reference run_finetune.py:437; peft/lora/lora_config.py)
    lora: bool = False
    lora_rank: int = 8
    lora_alpha: int = 16
    lora_dropout: float = 0.0
    lora_target_modules: Optional[List[str]] = None
    rslora: bool = False
    prefix_tuning: bool = False
    num_prefix_tokens: int = 64


@dataclass
class DataArguments:
    dataset_name_or_path: str = field(default="data", metadata={"help": "dir with train.json/dev.json (jsonl)"})
    max_length: int = 2048
    src_length: int = 1024
    zero_padding: bool = True
    eval_with_do_generation: bool = False


def load_sft_dataset(path: str, tokenizer, data_args: DataArguments):
    """jsonl rows {src,tgt} or {messages:[...]} -> token dicts with masked prompts
    (reference llm/utils/data.py tokenization)."""
    examples = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            row = json.loads(line)
            if "messages" in row:
                text = tokenizer.apply_chat_template(row["messages"], add_generation_prompt=False)
                ids = tokenizer.encode(text)[: data_args.max_length]
                labels = list(ids)
            else:
                src = tokenizer.encode(str(row.get("src", row.get("instruction", ""))))[: data_args.src_length]
                tgt = tokenizer.encode(str(row.get("tgt", row.get("output", ""))))
                eos = tokenizer.eos_token_id
                tgt = (tgt + ([eos] if eos is not None else []))[: data_args.max_length - len(src)]
                ids = src + tgt
                labels = [-100] * len(src) + list(tgt)  # prompt tokens excluded from loss
            examples.append({
                "input_ids": np.asarray(ids, dtype=np.int32),
                "labels": np.asarray(labels, dtype=np.int32),
            })
    return examples


class ListDataset:
    def __init__(self, rows):
        self.rows = rows

    def __len__(self):
        return len(self.rows)

    def __getitem__(self, i):
        return self.rows[i]


def main():
    enable_compile_cache()
    parser = PdArgumentParser((ModelArguments, DataArguments, TrainingArguments))
    model_args, data_args, training_args = parser.parse_args_into_dataclasses()

    tokenizer = AutoTokenizer.from_pretrained(model_args.model_name_or_path)
    config = AutoConfig.from_pretrained(model_args.model_name_or_path)
    LlmMetaConfig.set_llm_config(config, training_args)
    model = AutoModelForCausalLM.from_pretrained(
        model_args.model_name_or_path, config=config, dtype=model_args.dtype, param_dtype="float32"
    )

    if model_args.lora:
        from paddlenlp_tpu.peft import LoRAConfig, LoRAModel

        lora_config = LoRAConfig(
            r=model_args.lora_rank,
            lora_alpha=model_args.lora_alpha,
            lora_dropout=model_args.lora_dropout,
            target_modules=model_args.lora_target_modules,
            rslora=model_args.rslora,
        )
        model = LoRAModel(model, lora_config)
        model.mark_only_lora_as_trainable()
        model.print_trainable_parameters()
    elif model_args.prefix_tuning:
        from paddlenlp_tpu.peft import PrefixConfig, PrefixModelForCausalLM

        model = PrefixModelForCausalLM(model, PrefixConfig(num_prefix_tokens=model_args.num_prefix_tokens))

    train_rows = load_sft_dataset(os.path.join(data_args.dataset_name_or_path, "train.json"), tokenizer, data_args)
    dev_path = os.path.join(data_args.dataset_name_or_path, "dev.json")
    eval_rows = load_sft_dataset(dev_path, tokenizer, data_args) if os.path.isfile(dev_path) else None

    if data_args.zero_padding:
        train_ds = ZeroPaddingMapDataset(ListDataset(train_rows), tokenizer, data_args.max_length)
        eval_ds = ZeroPaddingMapDataset(ListDataset(eval_rows), tokenizer, data_args.max_length) if eval_rows else None
        collator = None  # packed rows are already fixed-length
    else:
        train_ds, eval_ds = ListDataset(train_rows), ListDataset(eval_rows) if eval_rows else None
        collator = DataCollatorForSeq2Seq(tokenizer, pad_to_multiple_of=8)

    trainer = Trainer(
        model=model,
        args=training_args,
        train_dataset=train_ds,
        eval_dataset=eval_ds,
        tokenizer=tokenizer,
        data_collator=collator,
    )
    if training_args.do_train:
        result = trainer.train(resume_from_checkpoint=training_args.resume_from_checkpoint)
        trainer.save_model()
        logger.info(f"finetune done: {result.metrics}")
    if training_args.do_eval and eval_ds is not None:
        logger.info(f"eval: {trainer.evaluate()}")
    return trainer


if __name__ == "__main__":
    main()
