from .auto import (  # noqa: F401
    AutoConfig,
    AutoModel,
    AutoModelForCausalLM,
    AutoModelForCausalLMPipe,
    AutoModelForMaskedLM,
    AutoModelForSequenceClassification,
    AutoModelForTokenClassification,
    AutoTokenizer,
)
from .albert import (  # noqa: F401
    AlbertConfig,
    AlbertForMaskedLM,
    AlbertForSequenceClassification,
    AlbertForTokenClassification,
    AlbertModel,
)
from .bert import (  # noqa: F401
    BertConfig,
    BertForMaskedLM,
    BertForSequenceClassification,
    BertForTokenClassification,
    BertModel,
)
from .cache_utils import KVCache, init_cache  # noqa: F401
from .configuration_utils import LlmMetaConfig, PretrainedConfig  # noqa: F401
from .electra import (  # noqa: F401
    ElectraConfig,
    ElectraDiscriminator,
    ElectraForSequenceClassification,
    ElectraForTokenClassification,
    ElectraModel,
)
from .ernie import (  # noqa: F401
    ErnieConfig,
    ErnieForMaskedLM,
    ErnieForSequenceClassification,
    ErnieForTokenClassification,
    ErnieModel,
)
from .deepseek_v2 import DeepseekV2Config, DeepseekV2ForCausalLM, DeepseekV2Model  # noqa: F401
from .deepseek_v3 import DeepseekV3Config, DeepseekV3ForCausalLM, DeepseekV3Model  # noqa: F401
from .dots3_note import Dots3NoteConfig, Dots3NoteForCausalLM, Dots3NoteModel  # noqa: F401
from .exaone_moe import ExaoneMoeConfig, ExaoneMoeForCausalLM, ExaoneMoeModel  # noqa: F401
from .sdar_moe import SdarMoeConfig, SdarMoeForCausalLM, SdarMoeModel  # noqa: F401
from .gemma import GemmaConfig, GemmaForCausalLM, GemmaModel  # noqa: F401
from .gpt import GPTConfig, GPTForCausalLM, GPTModel  # noqa: F401
from .llama import (  # noqa: F401
    LlamaConfig,
    LlamaForCausalLM,
    LlamaForSequenceClassification,
    LlamaModel,
    LlamaPretrainingCriterion,
)
# state-space families: nemotron_h (Mamba-2 hybrid) is the one the serving engine computes (recurrent state rows
# beside the paged KV pool, experimental/state_model.py); mamba and jamba (Mamba-1) are whole-sequence only, with
# cache classes of their own for model.generate()
from .mamba import MambaConfig, MambaForCausalLM, MambaModel  # noqa: F401
from .nemotron_h import NemotronHConfig, NemotronHForCausalLM, NemotronHModel  # noqa: F401
from .roberta import (  # noqa: F401
    RobertaConfig,
    RobertaForMaskedLM,
    RobertaForSequenceClassification,
    RobertaForTokenClassification,
    RobertaModel,
)
from .rw import RWConfig, RWForCausalLM, RWModel  # noqa: F401
from .chatglm import ChatGLMConfig, ChatGLMForCausalLM, ChatGLMModel  # noqa: F401
from .yuan import YuanConfig, YuanForCausalLM, YuanModel  # noqa: F401
from .jamba import JambaConfig, JambaForCausalLM, JambaModel  # noqa: F401
from .mistral import MistralConfig, MistralForCausalLM, MistralModel  # noqa: F401
from .mixtral import MixtralConfig, MixtralForCausalLM, MixtralModel  # noqa: F401
from .model_outputs import (  # noqa: F401
    BaseModelOutput,
    BaseModelOutputWithPast,
    CausalLMOutputWithPast,
    ModelOutput,
)
from .model_utils import PretrainedModel  # noqa: F401
from .chatglm_v2 import ChatGLMv2Config, ChatGLMv2ForCausalLM, ChatGLMv2Model  # noqa: F401
from .baichuan import BaichuanConfig, BaichuanForCausalLM, BaichuanModel  # noqa: F401
from .bloom import BloomConfig, BloomForCausalLM, BloomModel  # noqa: F401
from .opt import OPTConfig, OPTForCausalLM, OPTModel  # noqa: F401
from .qwen import QWenConfig, QWenForCausalLM, QWenModel  # noqa: F401
from .qwen2 import Qwen2Config, Qwen2ForCausalLM, Qwen2ForSequenceClassification, Qwen2Model  # noqa: F401
from .qwen2_moe import Qwen2MoeConfig, Qwen2MoeForCausalLM, Qwen2MoeModel  # noqa: F401
from .bart import (  # noqa: F401
    BartConfig,
    BartForConditionalGeneration,
    BartModel,
)
from .t5 import (  # noqa: F401
    T5Config,
    T5EncoderModel,
    T5ForConditionalGeneration,
    T5Model,
)
from .mt5 import (  # noqa: F401
    MT5Config,
    MT5EncoderModel,
    MT5ForConditionalGeneration,
    MT5Model,
)
from .mbart import (  # noqa: F401
    MBartConfig,
    MBartForConditionalGeneration,
    MBartModel,
)
from .pegasus import (  # noqa: F401
    PegasusConfig,
    PegasusForConditionalGeneration,
    PegasusModel,
)
from .clip import (  # noqa: F401
    CLIPConfig,
    CLIPModel,
    CLIPProcessor,
    CLIPTextConfig,
    CLIPTextModel,
    CLIPTextModelWithProjection,
    CLIPVisionConfig,
    CLIPVisionModel,
    CLIPVisionModelWithProjection,
)
from .image_processing_utils import (  # noqa: F401
    BaseImageProcessor,
    BlipImageProcessor,
    CLIPImageProcessor,
)
from .chineseclip import (  # noqa: F401
    ChineseCLIPConfig,
    ChineseCLIPModel,
    ChineseCLIPTextConfig,
    ChineseCLIPVisionConfig,
)
from .blip import (  # noqa: F401
    BlipConfig,
    BlipForConditionalGeneration,
    BlipForImageTextRetrieval,
    BlipModel,
    BlipTextConfig,
    BlipTextModel,
    BlipVisionConfig,
    BlipVisionModel,
)
from .ernie_vil import (  # noqa: F401
    ErnieViLConfig,
    ErnieViLModel,
)
from .minigpt4 import (  # noqa: F401
    MiniGPT4Config,
    MiniGPT4ForConditionalGeneration,
)
from .distilbert import (  # noqa: F401
    DistilBertConfig,
    DistilBertForMaskedLM,
    DistilBertForSequenceClassification,
    DistilBertModel,
)
from .nezha import (  # noqa: F401
    NezhaConfig,
    NezhaForMaskedLM,
    NezhaForSequenceClassification,
    NezhaForTokenClassification,
    NezhaModel,
)
from .mpnet import (  # noqa: F401
    MPNetConfig,
    MPNetForMaskedLM,
    MPNetForSequenceClassification,
    MPNetModel,
)
from .gptj import GPTJConfig, GPTJForCausalLM, GPTJModel  # noqa: F401
from .codegen import CodeGenConfig, CodeGenForCausalLM, CodeGenModel  # noqa: F401
from .roformer import (  # noqa: F401
    RoFormerConfig,
    RoFormerForMaskedLM,
    RoFormerForSequenceClassification,
    RoFormerModel,
)
from .tinybert import TinyBertConfig, TinyBertForSequenceClassification, TinyBertModel  # noqa: F401
from .fnet import FNetConfig, FNetForMaskedLM, FNetForSequenceClassification, FNetModel  # noqa: F401
from .squeezebert import (  # noqa: F401
    SqueezeBertConfig,
    SqueezeBertForMaskedLM,
    SqueezeBertForSequenceClassification,
    SqueezeBertModel,
)
from .rembert import (  # noqa: F401
    RemBertConfig,
    RemBertForMaskedLM,
    RemBertForSequenceClassification,
    RemBertModel,
)
from .layoutlm import (  # noqa: F401
    LayoutLMConfig,
    LayoutLMForMaskedLM,
    LayoutLMForTokenClassification,
    LayoutLMModel,
)
from .megatronbert import (  # noqa: F401
    MegatronBertConfig,
    MegatronBertForMaskedLM,
    MegatronBertForSequenceClassification,
    MegatronBertModel,
)
from .ernie_m import (  # noqa: F401
    ErnieMConfig,
    ErnieMForSequenceClassification,
    ErnieMForTokenClassification,
    ErnieMModel,
)
from .ppminilm import PPMiniLMConfig, PPMiniLMForSequenceClassification, PPMiniLMModel  # noqa: F401
from .deberta_v2 import (  # noqa: F401
    DebertaV2Config,
    DebertaV2ForMaskedLM,
    DebertaV2ForSequenceClassification,
    DebertaV2ForTokenClassification,
    DebertaV2Model,
)
from .tokenizer_utils import BatchEncoding, PretrainedTokenizer  # noqa: F401
