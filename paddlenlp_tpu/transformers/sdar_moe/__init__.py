from .configuration import SdarMoeConfig  # noqa: F401
from .modeling import (SdarMoeForCausalLM, SdarMoeModel,  # noqa: F401
                       SdarMoePretrainedModel)

__all__ = ["SdarMoeConfig", "SdarMoeModel", "SdarMoeForCausalLM", "SdarMoePretrainedModel"]
