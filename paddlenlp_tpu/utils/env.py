"""Cache-dir / environment contract.

Counterpart of ``paddlenlp/utils/env.py`` (MODEL_HOME etc.) and
``paddlenlp/utils/tools.py::get_env_device``, re-targeted at JAX platforms.
"""

from __future__ import annotations

import os
from typing import Optional

__all__ = [
    "MODEL_HOME",
    "DATA_HOME",
    "PDNLP_TPU_HOME",
    "get_env_device",
    "device_peak_flops",
    "PEAK_FLOPS_BY_DEVICE_KIND",
    "enable_compile_cache",
    "DEFAULT_COMPILE_CACHE_DIR",
    "CONFIG_NAME",
    "GENERATION_CONFIG_NAME",
    "MODEL_WEIGHTS_NAME",
    "SAFE_WEIGHTS_NAME",
    "SAFE_WEIGHTS_INDEX_NAME",
    "TOKENIZER_CONFIG_NAME",
    "CHAT_TEMPLATE_NAME",
]


def _get_home() -> str:
    home = os.environ.get("PDNLP_TPU_HOME")
    if home is None:
        home = os.path.join(os.path.expanduser("~"), ".paddlenlp_tpu")
    return home


PDNLP_TPU_HOME = _get_home()
MODEL_HOME = os.path.join(PDNLP_TPU_HOME, "models")
DATA_HOME = os.path.join(PDNLP_TPU_HOME, "datasets")

# Canonical artifact filenames (reference: paddlenlp/utils/env.py:55-86).
CONFIG_NAME = "config.json"
GENERATION_CONFIG_NAME = "generation_config.json"
MODEL_WEIGHTS_NAME = "model_weights.msgpack"
SAFE_WEIGHTS_NAME = "model.safetensors"
SAFE_WEIGHTS_INDEX_NAME = "model.safetensors.index.json"
TOKENIZER_CONFIG_NAME = "tokenizer_config.json"
CHAT_TEMPLATE_NAME = "chat_template.json"


def get_env_device() -> str:
    """Return the active JAX platform name ("tpu", "cpu", "gpu")."""
    import jax

    return jax.devices()[0].platform


# Peak dense bf16 FLOP/s of ONE jax device, keyed by ``device.device_kind``
# exactly as jax reports it. The one peak table of the repo: MFU in the trainer
# metrics (reference trainer_utils.py:351-380) and the serving goodput ledger
# both read it. Source: Google Cloud TPU documentation, system-architecture
# page of each generation (bf16 peak per chip; each kind below is one device
# per chip). A kind that is not listed is an error, never a guessed default:
# add its row with its source.
PEAK_FLOPS_BY_DEVICE_KIND = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,  # v5e
    "TPU v5": 459e12,  # v5p
    "TPU v6 lite": 918e12,  # v6e
}


def device_peak_flops(device_kind: Optional[str] = None) -> float:
    """Peak bf16 FLOP/s of the current (or named) jax device kind.

    NaN on the CPU (tests, tools): an MFU there is not a number. Raises
    ``ValueError`` for an accelerator the table does not know."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    if device_kind.lower() == "cpu":
        return float("nan")
    try:
        return PEAK_FLOPS_BY_DEVICE_KIND[device_kind]
    except KeyError:
        raise ValueError(
            f"no peak FLOP/s known for device kind {device_kind!r}: add it to "
            "paddlenlp_tpu.utils.env.PEAK_FLOPS_BY_DEVICE_KIND with its source "
            f"(known: {sorted(PEAK_FLOPS_BY_DEVICE_KIND)})") from None


# ---------------------------------------------------------------- compile cache
#: fixed in-checkout location of the persistent compilation cache when
#: ``JAX_COMPILATION_CACHE_DIR`` does not place it elsewhere. The directory is
#: part of the cache key, so it must not move between runs (never a temporary
#: name, pid or time).
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compilation cache; returns the directory in use.

    Every entry point that compiles for a device calls this once before its
    first jit. With ``JAX_COMPILATION_CACHE_DIR`` set, jax already uses that
    directory and nothing is set here; otherwise the cache goes to
    :data:`DEFAULT_COMPILE_CACHE_DIR`. The minimum compile time is lowered so
    the step programs (seconds each) are kept, not only the minute-long ones."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.1)
    return cache_dir
