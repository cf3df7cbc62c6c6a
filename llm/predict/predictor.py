"""Inference predictors + CLI.

Counterpart of ``/root/reference/llm/predict/predictor.py`` (1725 LoC):
``PredictorArgument`` :54, the class ladder Dygraph/Static/Block predictors
:232-1023, ``create_predictor`` :1163, ``predict()`` :1620, ``benchmark()`` :1687.
TPU-native: "static graph export" is just jit (no to_static split), so the ladder
collapses to two predictors:

- ``EagerPredictor``  — training-side ``model.generate`` (jitted while_loop);
- ``BlockPredictor``  — the paged continuous-batching ``InferenceEngine``.
"""

from __future__ import annotations

import json
import os
import sys
import time
from dataclasses import dataclass, field
from typing import List, Optional

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import numpy as np

from paddlenlp_tpu.trainer import PdArgumentParser
from paddlenlp_tpu.transformers import AutoConfig, AutoModelForCausalLM, AutoTokenizer
from paddlenlp_tpu.utils.env import enable_compile_cache
from paddlenlp_tpu.utils.log import logger


@dataclass
class PredictorArgument:
    model_name_or_path: str = "facebook/llama-7b"
    dtype: str = "bfloat16"
    mode: str = field(default="block", metadata={"help": "eager | block (paged continuous batching)"})
    src_length: int = 1024
    max_length: int = 256
    batch_size: int = 4
    top_k: int = 0
    top_p: float = 0.7
    temperature: float = 0.95
    decode_strategy: str = field(default="sampling", metadata={"help": "sampling | greedy_search"})
    block_size: int = 16
    num_kv_blocks: int = 1024
    max_blocks_per_seq: int = 128
    cachekv_int8_type: Optional[str] = field(
        default=None,
        metadata={"help": "quantize the paged KV cache: 'dynamic' (int8) or 'fp8' "
                          "(reference predictor.py:775-791 cachekv_int8 knob)"})
    speculate_method: Optional[str] = field(
        default=None,
        metadata={"help": "speculative decoding: 'ngram' (prompt-lookup drafts, greedy "
                          "only) or 'draft_model' (small-model proposer; greedy OR plain "
                          "temperature sampling via rejection-sampling acceptance — the "
                          "reference's csrc/gpu/append_attn + top_p_sampling_reject path)"})
    speculate_max_draft_tokens: int = 4
    draft_model_name_or_path: Optional[str] = field(
        default=None, metadata={"help": "checkpoint for the draft model (speculate_method=draft_model)"})
    enable_prefix_cache: bool = field(
        default=True,
        metadata={"help": "share KV blocks across requests with a common prompt prefix "
                          "(refcounted blocks + copy-on-write; prefill runs only on the "
                          "uncached suffix). Disable to force full prefill per request."})
    prefill_chunk_tokens: Optional[int] = field(
        default=None,
        metadata={"help": "split prompt processing into chunks of at most this many "
                          "tokens, interleaved with decode tokens in ragged mixed "
                          "engine steps (256-512 is a good TPU range) — a long prompt "
                          "no longer stalls running decodes for its whole prefill. "
                          "None/0 = monolithic prefill."})
    mesh_shape: Optional[str] = field(
        default=None,
        metadata={"help": "shard the serving forward + KV pool over a device mesh: "
                          "'R,C' (dp x tp) or a bare tp degree 'T'. Weights/KV lay "
                          "out with NamedSharding on the tp axis and the jitted "
                          "steps compile with explicit in/out shardings — one "
                          "replica spans several chips (CPU smoke: "
                          "XLA_FLAGS=--xla_force_host_platform_device_count=N). "
                          "None = single device."})
    disagg_stages: Optional[str] = field(
        default=None,
        metadata={"help": "disaggregated prefill/decode serving: 'P,D' device counts "
                          "— prompt work runs on a P-device prefill stage, decode on "
                          "a D-device decode stage, KV blocks migrating between the "
                          "stage pools (mutually exclusive with --mesh_shape; CPU "
                          "smoke: XLA_FLAGS=--xla_force_host_platform_device_count="
                          "P+D). None = single-stage."})
    data_file: Optional[str] = None
    output_file: Optional[str] = None
    benchmark: bool = False
    apply_chat_template: bool = False
    lora_path: Optional[str] = None
    weight_quantize_algo: Optional[str] = field(
        default=None,
        metadata={"help": "weight-only serving quantization: wint8 | wint4 | fp8 "
                          "(fp8 = float8_e4m3fn weights + per-channel scales, the "
                          "XLA-native twin of the reference's cutlass fp8 GEMM)"})


class BasePredictor:
    def __init__(self, args: PredictorArgument, model=None, tokenizer=None):
        self.args = args
        self.tokenizer = tokenizer or AutoTokenizer.from_pretrained(args.model_name_or_path)
        self.tokenizer.padding_side = "left"
        if model is None:
            config = AutoConfig.from_pretrained(args.model_name_or_path)
            config.use_scan_layers = True
            model = AutoModelForCausalLM.from_pretrained(
                args.model_name_or_path, config=config, dtype=args.dtype, param_dtype=args.dtype
            )
            if args.lora_path:
                from paddlenlp_tpu.peft import LoRAModel

                model = LoRAModel.from_pretrained(model, args.lora_path).merge_and_unload()
        if args.weight_quantize_algo:
            from paddlenlp_tpu.quantization import QuantizationConfig, QuantizedModel

            model = QuantizedModel(
                model, QuantizationConfig(weight_quantize_algo=args.weight_quantize_algo))
        self.model = model

    def _preprocess(self, texts: List[str]):
        if self.args.apply_chat_template and self.tokenizer.chat_template:
            texts = [
                self.tokenizer.apply_chat_template([{"role": "user", "content": t}]) for t in texts
            ]
        enc = self.tokenizer(texts, padding=True, truncation=True, max_length=self.args.src_length,
                             padding_side="left", return_tensors="np")
        return enc

    def _postprocess(self, token_lists: List[List[int]]) -> List[str]:
        return [self.tokenizer.decode(t, skip_special_tokens=True) for t in token_lists]


class EagerPredictor(BasePredictor):
    """reference DygraphPredictor (:232): plain model.generate."""

    def predict(self, texts: List[str]) -> List[str]:
        import jax.numpy as jnp

        enc = self._preprocess(texts)
        out, _ = self.model.generate(
            jnp.asarray(enc["input_ids"]),
            attention_mask=jnp.asarray(enc["attention_mask"]),
            max_new_tokens=self.args.max_length,
            do_sample=self.args.decode_strategy == "sampling",
            top_p=self.args.top_p,
            top_k=self.args.top_k,
            temperature=self.args.temperature,
        )
        return self._postprocess([np.asarray(o) for o in out])


class BlockPredictor(BasePredictor):
    """reference Dygraph/StaticBlockInferencePredictor (:953/:1023): paged engine."""

    def __init__(self, args: PredictorArgument, model=None, tokenizer=None):
        super().__init__(args, model, tokenizer)
        import jax.numpy as jnp

        from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams

        if args.speculate_method not in (None, "ngram", "draft_model"):
            raise ValueError(f"speculate_method={args.speculate_method!r} unsupported "
                             "(pick 'ngram' or 'draft_model')")
        if args.speculate_method == "draft_model" and args.decode_strategy == "sampling" \
                and (args.top_p < 1.0 or args.top_k):
            logger.warning(
                "speculate_method=draft_model with top_p<1.0/top_k>0: rejection-sampling "
                "acceptance only covers PLAIN temperature sampling, so speculation will "
                "be bypassed at runtime. Set --top_p 1.0 --top_k 0 (or greedy_search) "
                "to actually engage the draft model.")
        draft_model = None
        if args.speculate_method == "draft_model":
            if not args.draft_model_name_or_path:
                raise ValueError("speculate_method=draft_model needs --draft_model_name_or_path")
            from paddlenlp_tpu.transformers.auto import AutoModelForCausalLM as _Auto

            draft_model = _Auto.from_pretrained(args.draft_model_name_or_path,
                                                dtype=args.dtype, param_dtype=args.dtype)
        self.engine = InferenceEngine(
            self.model,
            tokenizer=self.tokenizer,
            max_batch_size=args.batch_size,
            block_size=args.block_size,
            num_blocks=args.num_kv_blocks,
            max_blocks_per_seq=args.max_blocks_per_seq,
            dtype=jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32,
            kv_cache_quant=self._kv_quant(args.cachekv_int8_type),
            enable_prefix_cache=args.enable_prefix_cache,
            prefill_chunk_tokens=args.prefill_chunk_tokens,
            mesh_shape=self._parse_mesh_shape(args.mesh_shape),
            disagg_stages=self._parse_disagg_stages(args.disagg_stages),
            use_speculative=args.speculate_method == "ngram",
            spec_draft_len=args.speculate_max_draft_tokens,
            draft_model=draft_model,
        )
        self._sampling = SamplingParams(
            max_new_tokens=args.max_length,
            do_sample=args.decode_strategy == "sampling",
            top_p=args.top_p,
            top_k=args.top_k,
            temperature=args.temperature,
        )

    @staticmethod
    def _parse_mesh_shape(raw: Optional[str]):
        """'R,C' -> (dp, tp); bare 'T' -> (1, T); None stays single-device."""
        if not raw:
            return None
        parts = [int(x) for x in str(raw).split(",")]
        if len(parts) == 1:
            parts = [1, parts[0]]
        if len(parts) != 2 or any(p < 1 for p in parts):
            raise ValueError(
                f"--mesh_shape must be 'T' or 'R,C' with positive degrees, got {raw!r}")
        return tuple(parts)

    @staticmethod
    def _parse_disagg_stages(raw: Optional[str]):
        """'P,D' -> (prefill_devices, decode_devices); None stays single-stage."""
        if not raw:
            return None
        parts = [int(x) for x in str(raw).split(",")]
        if len(parts) != 2 or any(p < 1 for p in parts):
            raise ValueError(
                f"--disagg_stages must be 'P,D' with positive device counts, got {raw!r}")
        return tuple(parts)

    @staticmethod
    def _kv_quant(cachekv_int8_type):
        if cachekv_int8_type is None:
            return None
        mapping = {"dynamic": "int8", "int8": "int8", "fp8": "fp8"}
        if cachekv_int8_type not in mapping:
            raise ValueError(
                f"cachekv_int8_type={cachekv_int8_type!r} unsupported; pick from "
                f"{sorted(mapping)} (the reference's 'static' calibrated scales are "
                "not implemented — dynamic per-token scales quantize at write time)")
        return mapping[cachekv_int8_type]

    def predict(self, texts: List[str]) -> List[str]:
        prompts = [self.tokenizer.encode(t)[-self.args.src_length:] for t in texts]
        outs = self.engine.generate(prompts, self._sampling)
        return self._postprocess(outs)

    def stream_predict(self, text: str):
        """Yield decoded text pieces as tokens land (serving path)."""
        import queue

        q: "queue.Queue" = queue.Queue()
        prompt = self.tokenizer.encode(text)[-self.args.src_length:]
        self.engine.add_request(prompt, self._sampling, stream_cb=lambda tok, done: q.put((tok, done)))
        toks: List[int] = []
        emitted = 0
        while True:
            while self.engine.has_work() and q.empty():
                self.engine.step()
            tok, done = q.get()
            toks.append(tok)
            text_so_far = self.tokenizer.decode(toks, skip_special_tokens=True)
            if len(text_so_far) > emitted:
                yield text_so_far[emitted:]
                emitted = len(text_so_far)
            if done:
                break


def create_predictor(args: PredictorArgument, model=None, tokenizer=None) -> BasePredictor:
    """reference create_predictor (:1163)."""
    if args.mode == "eager":
        return EagerPredictor(args, model, tokenizer)
    if args.mode == "block":
        return BlockPredictor(args, model, tokenizer)
    raise ValueError(f"unknown predictor mode {args.mode!r} (eager|block)")


def benchmark(predictor: BasePredictor, texts: List[str], warmup: int = 1, iters: int = 3):
    """reference benchmark (:1687): tokens/sec + latency stats."""
    for _ in range(warmup):
        predictor.predict(texts[: predictor.args.batch_size])
    t0 = time.time()
    n_tokens = 0
    for _ in range(iters):
        outs = predictor.predict(texts[: predictor.args.batch_size])
        n_tokens += sum(len(predictor.tokenizer.encode(o)) for o in outs)
    dt = time.time() - t0
    stats = {"output_tokens_per_second": round(n_tokens / dt, 2), "latency_s": round(dt / iters, 3)}
    logger.info(f"benchmark: {stats}")
    return stats


def main():
    enable_compile_cache()
    parser = PdArgumentParser((PredictorArgument,))
    (args,) = parser.parse_args_into_dataclasses()
    predictor = create_predictor(args)
    if args.data_file:
        with open(args.data_file) as f:
            texts = [json.loads(line).get("src", "") for line in f if line.strip()]
    else:
        texts = ["hello"]
    if args.benchmark:
        benchmark(predictor, texts)
        return
    outputs = []
    bs = args.batch_size
    for i in range(0, len(texts), bs):
        outputs.extend(predictor.predict(texts[i : i + bs]))
    if args.output_file:
        with open(args.output_file, "w") as f:
            for src, out in zip(texts, outputs):
                f.write(json.dumps({"src": src, "output": out}, ensure_ascii=False) + "\n")
    else:
        for src, out in zip(texts, outputs):
            print(json.dumps({"src": src, "output": out}, ensure_ascii=False))


if __name__ == "__main__":
    main()
