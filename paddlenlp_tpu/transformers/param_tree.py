"""A flax module that declares a nested tree of parameters from its shapes, for
the families whose layer mathematics is plain functions over a parameter tree
(``latent_layers.py``, ``state_layers.py``) rather than a module a layer."""

from __future__ import annotations

from typing import Callable, Dict

import jax.numpy as jnp
from flax import linen as nn

__all__ = ["ParamTree"]


class ParamTree(nn.Module):
    """Declares the parameters ``shapes`` names (a nested dict of name -> shape)
    and returns them as the same tree. ``float32_init(name)`` gives the
    initializer of a leaf that stays float32 whatever the weights' dtype (norm
    scales, biases) and None for every other, which draws normal(``std``) in
    ``param_dtype``."""

    shapes: Dict
    std: float
    float32_init: Callable
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self):
        out = {}
        for name, shape in self.shapes.items():
            if hasattr(shape, "items"):
                out[name] = ParamTree(dict(shape), self.std, self.float32_init, self.param_dtype, name=name)()
            elif (init := self.float32_init(name)) is not None:
                out[name] = self.param(name, init, tuple(shape), jnp.float32)
            else:
                out[name] = self.param(name, nn.initializers.normal(self.std), tuple(shape), self.param_dtype)
        return out
