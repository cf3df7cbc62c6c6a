"""Auto model classes (reference: paddlenlp/transformers/auto/modeling.py —
``AutoModelForCausalLM`` incl. the ``AutoModelForCausalLMPipe`` variant; under one
mesh-driven network per model there is no separate Pipe class to dispatch to)."""

from __future__ import annotations

from typing import Dict, Type

from ..configuration_utils import PretrainedConfig
from .configuration import CONFIG_MAPPING, AutoConfig, _populate

__all__ = [
    "AutoModel",
    "AutoModelForCausalLM",
    "AutoModelForSequenceClassification",
    "AutoModelForMaskedLM",
    "AutoModelForSeq2SeqLM",
    "AutoModelForConditionalGeneration",
    "AutoModelForCausalLMPipe",
]

_MODEL_MAPPING: Dict[str, Dict[str, type]] = {}


def register_model(model_type: str, task: str, model_class: type):
    _MODEL_MAPPING.setdefault(model_type, {})[task] = model_class


def _populate_models():
    if _MODEL_MAPPING:
        return
    _populate()
    from ..bert import modeling as bert
    from ..ernie import modeling as ernie
    from ..gemma import modeling as gemma
    from ..gpt import modeling as gpt
    from ..llama import modeling as llama
    from ..mistral import modeling as mistral
    from ..mixtral import modeling as mixtral
    from ..qwen2 import modeling as qwen2
    from ..qwen2_moe import modeling as qwen2_moe

    register_model("llama", "base", llama.LlamaModel)
    register_model("llama", "causal_lm", llama.LlamaForCausalLM)
    register_model("llama", "sequence_classification", llama.LlamaForSequenceClassification)
    register_model("gpt", "base", gpt.GPTModel)
    register_model("gpt", "causal_lm", gpt.GPTForCausalLM)
    register_model("gpt2", "base", gpt.GPTModel)
    register_model("gpt2", "causal_lm", gpt.GPTForCausalLM)
    from ..baichuan import modeling as baichuan
    from ..bloom import modeling as bloom
    from ..opt import modeling as opt
    from ..qwen import modeling as qwen

    from ..chatglm_v2 import modeling as chatglm_v2

    register_model("chatglm_v2", "base", chatglm_v2.ChatGLMv2Model)
    register_model("chatglm_v2", "causal_lm", chatglm_v2.ChatGLMv2ForCausalLM)
    register_model("baichuan", "base", baichuan.BaichuanModel)
    register_model("baichuan", "causal_lm", baichuan.BaichuanForCausalLM)
    register_model("bloom", "base", bloom.BloomModel)
    register_model("bloom", "causal_lm", bloom.BloomForCausalLM)
    register_model("opt", "base", opt.OPTModel)
    register_model("opt", "causal_lm", opt.OPTForCausalLM)
    register_model("qwen", "base", qwen.QWenModel)
    register_model("qwen", "causal_lm", qwen.QWenForCausalLM)
    register_model("qwen2", "base", qwen2.Qwen2Model)
    register_model("qwen2", "causal_lm", qwen2.Qwen2ForCausalLM)
    register_model("qwen2", "sequence_classification", qwen2.Qwen2ForSequenceClassification)
    register_model("mistral", "base", mistral.MistralModel)
    register_model("mistral", "causal_lm", mistral.MistralForCausalLM)
    register_model("gemma", "base", gemma.GemmaModel)
    register_model("gemma", "causal_lm", gemma.GemmaForCausalLM)
    register_model("bert", "base", bert.BertModel)
    register_model("bert", "masked_lm", bert.BertForMaskedLM)
    register_model("bert", "sequence_classification", bert.BertForSequenceClassification)
    register_model("bert", "token_classification", bert.BertForTokenClassification)
    register_model("ernie", "base", ernie.ErnieModel)
    register_model("ernie", "masked_lm", ernie.ErnieForMaskedLM)
    register_model("ernie", "sequence_classification", ernie.ErnieForSequenceClassification)
    register_model("ernie", "token_classification", ernie.ErnieForTokenClassification)
    from ..albert import modeling as albert
    from ..electra import modeling as electra
    from ..roberta import modeling as roberta

    register_model("roberta", "base", roberta.RobertaModel)
    register_model("roberta", "masked_lm", roberta.RobertaForMaskedLM)
    register_model("roberta", "sequence_classification", roberta.RobertaForSequenceClassification)
    register_model("roberta", "token_classification", roberta.RobertaForTokenClassification)
    register_model("electra", "base", electra.ElectraModel)
    register_model("electra", "sequence_classification", electra.ElectraForSequenceClassification)
    register_model("electra", "token_classification", electra.ElectraForTokenClassification)
    register_model("albert", "base", albert.AlbertModel)
    register_model("albert", "masked_lm", albert.AlbertForMaskedLM)
    register_model("albert", "sequence_classification", albert.AlbertForSequenceClassification)
    register_model("albert", "token_classification", albert.AlbertForTokenClassification)
    register_model("mixtral", "causal_lm", mixtral.MixtralForCausalLM)
    register_model("qwen2_moe", "causal_lm", qwen2_moe.Qwen2MoeForCausalLM)
    from ..deepseek_v2 import modeling as deepseek_v2

    register_model("deepseek_v2", "base", deepseek_v2.DeepseekV2Model)
    register_model("deepseek_v2", "causal_lm", deepseek_v2.DeepseekV2ForCausalLM)
    from ..deepseek_v3 import modeling as deepseek_v3

    register_model("deepseek_v3", "base", deepseek_v3.DeepseekV3Model)
    register_model("deepseek_v3", "causal_lm", deepseek_v3.DeepseekV3ForCausalLM)
    from ..dots3_note import modeling as dots3_note

    register_model("dots3_note", "base", dots3_note.Dots3NoteModel)
    register_model("dots3_note", "causal_lm", dots3_note.Dots3NoteForCausalLM)
    from ..exaone_moe import modeling as exaone_moe

    register_model("exaone_moe", "base", exaone_moe.ExaoneMoeModel)
    register_model("exaone_moe", "causal_lm", exaone_moe.ExaoneMoeForCausalLM)
    from ..sdar_moe import modeling as sdar_moe

    register_model("sdar_moe", "base", sdar_moe.SdarMoeModel)
    register_model("sdar_moe", "causal_lm", sdar_moe.SdarMoeForCausalLM)
    # state-space families: nemotron_h is served by the engine (its configuration names the step programs,
    # experimental/state_model.py); mamba and jamba are whole-sequence only (model.generate with their own caches)
    from ..nemotron_h import modeling as nemotron_h

    register_model("nemotron_h", "base", nemotron_h.NemotronHModel)
    register_model("nemotron_h", "causal_lm", nemotron_h.NemotronHForCausalLM)
    from ..mamba import modeling as mamba

    register_model("mamba", "base", mamba.MambaModel)
    register_model("mamba", "causal_lm", mamba.MambaForCausalLM)
    from ..rw import modeling as rw

    register_model("rw", "base", rw.RWModel)
    register_model("rw", "causal_lm", rw.RWForCausalLM)
    register_model("falcon", "base", rw.RWModel)
    register_model("falcon", "causal_lm", rw.RWForCausalLM)
    from ..chatglm import modeling as chatglm

    register_model("chatglm", "base", chatglm.ChatGLMModel)
    register_model("chatglm", "causal_lm", chatglm.ChatGLMForCausalLM)
    from ..yuan import modeling as yuan

    register_model("yuan", "base", yuan.YuanModel)
    register_model("yuan", "causal_lm", yuan.YuanForCausalLM)
    from ..jamba import modeling as jamba

    register_model("jamba", "base", jamba.JambaModel)
    register_model("jamba", "causal_lm", jamba.JambaForCausalLM)
    from ..t5 import modeling as t5

    register_model("t5", "base", t5.T5Model)
    register_model("t5", "seq2seq_lm", t5.T5ForConditionalGeneration)
    from ..bart import modeling as bart

    register_model("bart", "base", bart.BartModel)
    register_model("bart", "seq2seq_lm", bart.BartForConditionalGeneration)
    from ..mt5 import modeling as mt5

    register_model("mt5", "base", mt5.MT5Model)
    register_model("mt5", "seq2seq_lm", mt5.MT5ForConditionalGeneration)
    from ..mbart import modeling as mbart

    register_model("mbart", "base", mbart.MBartModel)
    register_model("mbart", "seq2seq_lm", mbart.MBartForConditionalGeneration)
    from ..pegasus import modeling as pegasus

    register_model("pegasus", "base", pegasus.PegasusModel)
    register_model("pegasus", "seq2seq_lm", pegasus.PegasusForConditionalGeneration)
    from ..clip import modeling as clip

    register_model("clip", "base", clip.CLIPModel)
    from ..chineseclip import modeling as chineseclip

    register_model("chinese_clip", "base", chineseclip.ChineseCLIPModel)
    from ..blip import modeling as blip

    register_model("blip", "base", blip.BlipModel)
    from ..ernie_vil import modeling as ernie_vil

    register_model("ernie_vil", "base", ernie_vil.ErnieViLModel)
    from ..minigpt4 import modeling as minigpt4

    register_model("minigpt4", "base", minigpt4.MiniGPT4ForConditionalGeneration)
    from ..distilbert import modeling as distilbert

    register_model("distilbert", "base", distilbert.DistilBertModel)
    register_model("distilbert", "masked_lm", distilbert.DistilBertForMaskedLM)
    register_model("distilbert", "sequence_classification", distilbert.DistilBertForSequenceClassification)
    from ..nezha import modeling as nezha

    register_model("nezha", "base", nezha.NezhaModel)
    register_model("nezha", "masked_lm", nezha.NezhaForMaskedLM)
    register_model("nezha", "sequence_classification", nezha.NezhaForSequenceClassification)
    register_model("nezha", "token_classification", nezha.NezhaForTokenClassification)
    from ..mpnet import modeling as mpnet

    register_model("mpnet", "base", mpnet.MPNetModel)
    register_model("mpnet", "masked_lm", mpnet.MPNetForMaskedLM)
    register_model("mpnet", "sequence_classification", mpnet.MPNetForSequenceClassification)
    from ..gptj import modeling as gptj

    register_model("gptj", "base", gptj.GPTJModel)
    register_model("gptj", "causal_lm", gptj.GPTJForCausalLM)
    from ..codegen import modeling as codegen

    register_model("codegen", "base", codegen.CodeGenModel)
    register_model("codegen", "causal_lm", codegen.CodeGenForCausalLM)
    from ..roformer import modeling as roformer

    register_model("roformer", "base", roformer.RoFormerModel)
    register_model("roformer", "masked_lm", roformer.RoFormerForMaskedLM)
    register_model("roformer", "sequence_classification", roformer.RoFormerForSequenceClassification)
    from ..tinybert import modeling as tinybert

    register_model("tinybert", "base", tinybert.TinyBertModel)
    register_model("tinybert", "sequence_classification", tinybert.TinyBertForSequenceClassification)
    from ..ppminilm import modeling as ppminilm

    register_model("ppminilm", "base", ppminilm.PPMiniLMModel)
    register_model("ppminilm", "sequence_classification", ppminilm.PPMiniLMForSequenceClassification)
    from ..fnet import modeling as fnet

    register_model("fnet", "base", fnet.FNetModel)
    register_model("fnet", "masked_lm", fnet.FNetForMaskedLM)
    register_model("fnet", "sequence_classification", fnet.FNetForSequenceClassification)
    from ..ernie_m import modeling as ernie_m

    from ..squeezebert import modeling as squeezebert

    register_model("squeezebert", "base", squeezebert.SqueezeBertModel)
    register_model("squeezebert", "masked_lm", squeezebert.SqueezeBertForMaskedLM)
    register_model("squeezebert", "sequence_classification",
                   squeezebert.SqueezeBertForSequenceClassification)
    from ..rembert import modeling as rembert

    register_model("rembert", "base", rembert.RemBertModel)
    register_model("rembert", "masked_lm", rembert.RemBertForMaskedLM)
    register_model("rembert", "sequence_classification", rembert.RemBertForSequenceClassification)
    from ..layoutlm import modeling as layoutlm

    register_model("layoutlm", "base", layoutlm.LayoutLMModel)
    register_model("layoutlm", "masked_lm", layoutlm.LayoutLMForMaskedLM)
    register_model("layoutlm", "token_classification", layoutlm.LayoutLMForTokenClassification)
    from ..megatronbert import modeling as megatronbert

    register_model("megatron-bert", "base", megatronbert.MegatronBertModel)
    register_model("megatron-bert", "masked_lm", megatronbert.MegatronBertForMaskedLM)
    register_model("megatron-bert", "sequence_classification",
                   megatronbert.MegatronBertForSequenceClassification)
    register_model("ernie_m", "base", ernie_m.ErnieMModel)
    register_model("ernie_m", "sequence_classification", ernie_m.ErnieMForSequenceClassification)
    register_model("ernie_m", "token_classification", ernie_m.ErnieMForTokenClassification)
    from ..deberta_v2 import modeling as deberta_v2

    register_model("deberta-v2", "base", deberta_v2.DebertaV2Model)
    register_model("deberta-v2", "masked_lm", deberta_v2.DebertaV2ForMaskedLM)
    register_model("deberta-v2", "sequence_classification", deberta_v2.DebertaV2ForSequenceClassification)
    register_model("deberta-v2", "token_classification", deberta_v2.DebertaV2ForTokenClassification)


class _AutoBase:
    task = "base"

    @classmethod
    def _resolve(cls, pretrained_model_name_or_path, config=None, **kwargs):
        _populate_models()
        if config is None:
            config = AutoConfig.from_pretrained(pretrained_model_name_or_path)
        model_type = config.model_type
        task_map = _MODEL_MAPPING.get(model_type)
        if not task_map or cls.task not in task_map:
            raise ValueError(f"no {cls.task} model registered for model_type={model_type!r}")
        return task_map[cls.task], config

    @classmethod
    def from_pretrained(cls, pretrained_model_name_or_path, config=None, **kwargs):
        model_class, config = cls._resolve(pretrained_model_name_or_path, config)
        return model_class.from_pretrained(pretrained_model_name_or_path, config=config, **kwargs)

    @classmethod
    def from_config(cls, config, **kwargs):
        _populate_models()
        task_map = _MODEL_MAPPING.get(config.model_type)
        if not task_map or cls.task not in task_map:
            raise ValueError(f"no {cls.task} model registered for model_type={config.model_type!r}")
        return task_map[cls.task].from_config(config, **kwargs)


class AutoModel(_AutoBase):
    task = "base"


class AutoModelForCausalLM(_AutoBase):
    task = "causal_lm"


class AutoModelForSequenceClassification(_AutoBase):
    task = "sequence_classification"


class AutoModelForTokenClassification(_AutoBase):
    task = "token_classification"


class AutoModelForMaskedLM(_AutoBase):
    task = "masked_lm"


class AutoModelForSeq2SeqLM(_AutoBase):
    task = "seq2seq_lm"


class AutoModelForConditionalGeneration(_AutoBase):
    task = "seq2seq_lm"


# The reference exposes AutoModelForCausalLMPipe for pipeline-parallel runs
# (auto/modeling.py); here pipelining is a mesh axis on the SAME model class.
AutoModelForCausalLMPipe = AutoModelForCausalLM
