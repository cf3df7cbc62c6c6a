from .configuration import NemotronHConfig  # noqa: F401
from .modeling import (NemotronHForCausalLM, NemotronHModel,  # noqa: F401
                       NemotronHPretrainedModel)

__all__ = ["NemotronHConfig", "NemotronHModel", "NemotronHForCausalLM", "NemotronHPretrainedModel"]
