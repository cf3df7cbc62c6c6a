"""Causal-LM pretraining entry point.

Counterpart of ``/root/reference/llm/run_pretrain.py`` (main :358): JSON/CLI config
-> tokenizer/config -> LlmMetaConfig bridge -> model -> mmap GPT dataset ->
Trainer. Launch: ``python llm/run_pretrain.py config.json`` or CLI flags.
"""

from __future__ import annotations

import os
import sys
from dataclasses import dataclass, field
from typing import List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from paddlenlp_tpu.data import build_train_valid_test_datasets
from paddlenlp_tpu.trainer import PdArgumentParser, Trainer, TrainingArguments, get_last_checkpoint
from paddlenlp_tpu.transformers import AutoConfig, AutoModelForCausalLM, AutoTokenizer, LlmMetaConfig
from paddlenlp_tpu.utils.env import enable_compile_cache
from paddlenlp_tpu.utils.log import logger


@dataclass
class ModelArguments:
    model_name_or_path: str = field(default="__internal_testing__/tiny-random-llama")
    tokenizer_name_or_path: Optional[str] = None
    dtype: str = "bfloat16"
    from_scratch: bool = field(default=True, metadata={"help": "init weights instead of loading"})
    num_hidden_layers: Optional[int] = None
    vocab_size: Optional[int] = None


@dataclass
class DataArguments:
    input_dir: str = field(default="data", metadata={"help": "dir or prefix of .bin/.idx corpus"})
    data_prefix: Optional[List[str]] = field(default=None, metadata={"help": "[w1, prefix1, w2, prefix2...]"})
    split: str = "949,50,1"
    max_seq_length: int = 2048
    data_cache_dir: Optional[str] = None


@dataclass
class PreTrainingArguments(TrainingArguments):
    min_learning_rate: float = 1e-5
    decay_steps: int = 0


def create_pretrained_dataset(data_args: DataArguments, training_args: TrainingArguments, tokenizer=None):
    """reference run_pretrain.py:193."""
    train_samples = training_args.max_steps * training_args.global_train_batch_size
    eval_steps = max(training_args.eval_steps, 1)
    eval_samples = (
        (training_args.max_steps // eval_steps + 1) * training_args.global_eval_batch_size
        if training_args.evaluation_strategy != "no"
        else training_args.global_eval_batch_size
    )
    prefix = data_args.data_prefix or _resolve_prefix(data_args.input_dir)
    return build_train_valid_test_datasets(
        prefix,
        seq_length=data_args.max_seq_length,
        train_valid_test_num_samples=(train_samples, eval_samples, 0),
        splits_string=data_args.split,
        seed=training_args.seed,
        cache_dir=data_args.data_cache_dir,
    )


def _resolve_prefix(input_dir: str) -> str:
    if os.path.isfile(input_dir + ".bin"):
        return input_dir
    if os.path.isdir(input_dir):
        bins = [f[:-4] for f in os.listdir(input_dir) if f.endswith(".bin")]
        if len(bins) == 1:
            return os.path.join(input_dir, bins[0])
        if bins:
            raise ValueError(f"multiple corpora in {input_dir}; pass data_prefix with weights")
    raise FileNotFoundError(f"no .bin/.idx corpus found at {input_dir}")


def main():
    enable_compile_cache()
    parser = PdArgumentParser((ModelArguments, DataArguments, PreTrainingArguments))
    model_args, data_args, training_args = parser.parse_args_into_dataclasses()

    tokenizer = None
    if model_args.tokenizer_name_or_path or not model_args.from_scratch:
        tokenizer = AutoTokenizer.from_pretrained(
            model_args.tokenizer_name_or_path or model_args.model_name_or_path
        )

    config = AutoConfig.from_pretrained(model_args.model_name_or_path)
    LlmMetaConfig.set_llm_config(config, training_args)
    if model_args.num_hidden_layers is not None:
        config.num_hidden_layers = model_args.num_hidden_layers
    if model_args.vocab_size is not None:
        config.vocab_size = model_args.vocab_size
    config.use_cache = False

    if model_args.from_scratch:
        model = AutoModelForCausalLM.from_config(
            config, dtype=model_args.dtype, param_dtype="float32", seed=training_args.seed
        )
    else:
        model = AutoModelForCausalLM.from_pretrained(
            model_args.model_name_or_path, config=config, dtype=model_args.dtype, param_dtype="float32"
        )
    logger.info(f"model: {type(model).__name__} ({model.num_parameters():,} params)")

    train_ds, valid_ds, _ = create_pretrained_dataset(data_args, training_args, tokenizer)

    trainer = Trainer(
        model=model,
        args=training_args,
        train_dataset=train_ds,
        eval_dataset=valid_ds,
        tokenizer=tokenizer,
    )

    checkpoint = training_args.resume_from_checkpoint
    if checkpoint is None and not training_args.overwrite_output_dir:
        checkpoint = get_last_checkpoint(training_args.output_dir)
    if training_args.do_train:
        result = trainer.train(resume_from_checkpoint=checkpoint)
        trainer.save_model()
        logger.info(f"training done: {result.metrics}")
    if training_args.do_eval:
        metrics = trainer.evaluate()
        logger.info(f"eval: {metrics}")
    return trainer


if __name__ == "__main__":
    main()
