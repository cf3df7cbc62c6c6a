"""One buffer a launch: the small host arrays a step program takes (token ids,
block tables, lengths, flags, the sampling parameters of every row, adapter
rows) cross to the device in ONE transfer and are taken apart inside the
program.

A host-to-device transfer costs the host a third to half a millisecond whatever
its bytes (140 KB in one: 0.45 ms), and a launch sent 13 to 19 of them
(PERF.md section 6, PR 39). ``pack`` lays the
fields end to end in one 1-D ``int32`` array: an ``int32`` field as it is, a
``float32`` field as its bits, a ``bool`` field as 0 / 1. The layout (names,
shapes, dtypes, in order) follows from the launch's shapes alone, so it is a
static argument of the step program, where ``unpack`` slices, reshapes and
converts every field back before anything else runs: the values the model sees
are bit for bit the host's.
"""

from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["Layout", "layout_of", "pack", "unpack", "packed_size"]

#: ((name, shape, dtype name), ...) in buffer order: hashable, so a jit takes it as a static argument
Layout = Tuple[Tuple[str, Tuple[int, ...], str], ...]

_DTYPES = ("int32", "float32", "bool")


def layout_of(fields) -> Layout:
    """The layout of ``fields`` ({name: anything with a shape and a dtype}), in the dict's order."""
    layout = tuple((name, tuple(a.shape), np.dtype(a.dtype).name) for name, a in fields.items())
    for name, _, dtype in layout:
        if dtype not in _DTYPES:
            raise TypeError(f"launch field {name!r} is {dtype}: a launch buffer holds int32, float32 and bool")
    return layout


def packed_size(layout: Layout) -> int:
    """Elements of the buffer ``layout`` describes."""
    return sum(math.prod(shape) for _, shape, _ in layout)


def pack(fields: Dict[str, np.ndarray]) -> Tuple[np.ndarray, Layout]:
    """``fields`` (host arrays of ``int32`` / ``float32`` / ``bool``, any
    shape) end to end in one ``int32`` buffer, and the layout that takes it
    apart again. Field order is the dict's."""
    layout = layout_of(fields)
    buf = np.empty(packed_size(layout), np.int32)
    off = 0
    for (_, _, dtype), a in zip(layout, fields.values()):
        part = buf[off:off + a.size]
        if dtype == "float32":
            part = part.view(np.float32)  # the bits, not the value
        part[:] = a.reshape(-1)  # bool lands as 0 / 1
        off += a.size
    return buf, layout


def unpack(buf, layout: Layout) -> Dict[str, jnp.ndarray]:
    """Inside a step program: every field of ``layout`` out of ``buf`` with its
    shape and dtype. Under ``bookkeeping`` so that a profile attributes the few
    slices, and under a scope of its own within it."""
    out, off = {}, 0
    with jax.named_scope("bookkeeping"), jax.named_scope("launch_unpack"):
        for name, shape, dtype in layout:
            n = math.prod(shape)
            x = jax.lax.slice(buf, (off,), (off + n,)).reshape(shape)
            if dtype == "float32":
                x = jax.lax.bitcast_convert_type(x, jnp.float32)
            elif dtype == "bool":
                x = x != 0
            out[name] = x
            off += n
    return out
