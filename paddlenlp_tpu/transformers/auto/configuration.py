"""AutoConfig (reference: paddlenlp/transformers/auto/configuration.py)."""

from __future__ import annotations

from typing import Dict, Type

from ..configuration_utils import PretrainedConfig

__all__ = ["AutoConfig", "CONFIG_MAPPING", "register_config"]

CONFIG_MAPPING: Dict[str, Type[PretrainedConfig]] = {}


def register_config(model_type: str, config_class: Type[PretrainedConfig]):
    CONFIG_MAPPING[model_type] = config_class


def _populate():
    if CONFIG_MAPPING:
        return
    from ..albert.configuration import AlbertConfig
    from ..bert.configuration import BertConfig
    from ..electra.configuration import ElectraConfig
    from ..roberta.configuration import RobertaConfig
    from ..ernie.configuration import ErnieConfig
    from ..gemma.configuration import GemmaConfig
    from ..gpt.configuration import GPTConfig
    from ..llama.configuration import LlamaConfig
    from ..mistral.configuration import MistralConfig
    from ..mixtral.configuration import MixtralConfig
    from ..baichuan.configuration import BaichuanConfig
    from ..chatglm_v2.configuration import ChatGLMv2Config
    from ..bloom.configuration import BloomConfig
    from ..opt.configuration import OPTConfig
    from ..qwen.configuration import QWenConfig
    from ..qwen2.configuration import Qwen2Config
    from ..qwen2_moe.configuration import Qwen2MoeConfig
    from ..bart.configuration import BartConfig
    from ..deepseek_v2.configuration import DeepseekV2Config
    from ..deepseek_v3.configuration import DeepseekV3Config
    from ..dots3_note.configuration import Dots3NoteConfig
    from ..exaone_moe.configuration import ExaoneMoeConfig
    from ..sdar_moe.configuration import SdarMoeConfig
    from ..mamba.configuration import MambaConfig
    from ..nemotron_h.configuration import NemotronHConfig
    from ..rw.configuration import RWConfig
    from ..chatglm.configuration import ChatGLMConfig
    from ..yuan.configuration import YuanConfig
    from ..jamba.configuration import JambaConfig
    from ..t5.configuration import T5Config
    from ..mt5.configuration import MT5Config
    from ..mbart.configuration import MBartConfig
    from ..pegasus.configuration import PegasusConfig
    from ..distilbert.configuration import DistilBertConfig
    from ..nezha.configuration import NezhaConfig
    from ..mpnet.configuration import MPNetConfig
    from ..deberta_v2.configuration import DebertaV2Config
    from ..gptj.configuration import GPTJConfig
    from ..codegen.configuration import CodeGenConfig
    from ..roformer.configuration import RoFormerConfig
    from ..tinybert.configuration import TinyBertConfig
    from ..ppminilm.configuration import PPMiniLMConfig
    from ..fnet.configuration import FNetConfig
    from ..ernie_m.configuration import ErnieMConfig
    from ..megatronbert.configuration import MegatronBertConfig
    from ..layoutlm.configuration import LayoutLMConfig
    from ..rembert.configuration import RemBertConfig
    from ..squeezebert.configuration import SqueezeBertConfig
    from ..clip.configuration import CLIPConfig
    from ..chineseclip.configuration import ChineseCLIPConfig
    from ..blip.configuration import BlipConfig
    from ..ernie_vil.configuration import ErnieViLConfig
    from ..minigpt4.configuration import MiniGPT4Config

    for cfg in (LlamaConfig, GPTConfig, Qwen2Config, MistralConfig, GemmaConfig, BertConfig,
                ErnieConfig, MixtralConfig, Qwen2MoeConfig, BaichuanConfig, BloomConfig,
                OPTConfig, QWenConfig, ChatGLMv2Config, T5Config, BartConfig, DeepseekV2Config, DeepseekV3Config, Dots3NoteConfig, ExaoneMoeConfig, SdarMoeConfig,
                NemotronHConfig,
                MambaConfig, RWConfig, ChatGLMConfig, YuanConfig, JambaConfig,
                AlbertConfig, ElectraConfig, RobertaConfig,
                MT5Config, MBartConfig, PegasusConfig,
                CLIPConfig, ChineseCLIPConfig, BlipConfig, ErnieViLConfig,
                DistilBertConfig, NezhaConfig, MPNetConfig, DebertaV2Config,
                GPTJConfig, CodeGenConfig, RoFormerConfig, TinyBertConfig, PPMiniLMConfig,
                MiniGPT4Config, FNetConfig, ErnieMConfig, MegatronBertConfig,
                LayoutLMConfig, RemBertConfig, SqueezeBertConfig):
        register_config(cfg.model_type, cfg)
    register_config("gpt2", GPTConfig)


class AutoConfig:
    @classmethod
    def from_pretrained(cls, pretrained_model_name_or_path, **kwargs) -> PretrainedConfig:
        _populate()
        config_dict, kwargs = PretrainedConfig.get_config_dict(pretrained_model_name_or_path, **kwargs)
        model_type = config_dict.get("model_type")
        if model_type in CONFIG_MAPPING:
            return CONFIG_MAPPING[model_type].from_dict(config_dict, **kwargs)
        # fall back: architectures hint
        for arch in config_dict.get("architectures") or []:
            for mt, ccls in CONFIG_MAPPING.items():
                if arch.lower().startswith(mt.replace("_", "")):
                    return ccls.from_dict(config_dict, **kwargs)
        raise ValueError(
            f"unrecognized model_type {model_type!r}; known: {sorted(CONFIG_MAPPING)}"
        )

    @staticmethod
    def register(model_type: str, config_class):
        register_config(model_type, config_class)
