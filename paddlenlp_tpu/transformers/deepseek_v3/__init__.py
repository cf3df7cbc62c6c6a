from .configuration import DeepseekV3Config  # noqa: F401
from .modeling import (  # noqa: F401
    DeepseekV3ForCausalLM,
    DeepseekV3Model,
    DeepseekV3PretrainedModel,
)
