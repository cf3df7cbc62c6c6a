from .configuration import ExaoneMoeConfig  # noqa: F401
from .modeling import (ExaoneMoeForCausalLM, ExaoneMoeModel,  # noqa: F401
                       ExaoneMoePretrainedModel)

__all__ = ["ExaoneMoeConfig", "ExaoneMoeModel", "ExaoneMoeForCausalLM", "ExaoneMoePretrainedModel"]
