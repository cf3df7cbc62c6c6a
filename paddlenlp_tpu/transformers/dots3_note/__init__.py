from .configuration import Dots3NoteConfig  # noqa: F401
from .modeling import (  # noqa: F401
    Dots3NoteForCausalLM,
    Dots3NoteModel,
    Dots3NotePretrainedModel,
)
