"""Device mesh construction — the substrate for every parallelism strategy.

The reference expresses hybrid parallelism as a stack of fleet wrappers over NCCL
process groups with a configurable axis order
(``paddlenlp/trainer/training_args.py:1265-1303``, axes dp/pp/sharding/sep/mp and
``fleet.get_hybrid_communicate_group()`` accessors at 1744-1797). TPU-native, all of
that collapses into ONE ``jax.sharding.Mesh`` whose named axes are the strategies:

=========  =====================================================================
axis       strategy it carries
=========  =====================================================================
``dp``     pure data parallel (replicated params; batch sharded)
``fsdp``   ZeRO / "sharding stage 1-3": params+grads+opt state sharded over it,
           batch also sharded over it (it is a data axis for activations)
``pp``     pipeline parallel (layer-stacked scan over stages, collective_permute)
``sep``    Ulysses/segment parallel (seq<->heads all-to-all inside attention)
``cp``     context parallel (ring attention over seq chunks)
``tp``     tensor parallel (Megatron column/row sharding; innermost => ICI-nearest)
=========  =====================================================================

Axis ORDER is ICI-locality: later axes vary fastest over the physical device
order, so ``tp`` neighbours are ICI neighbours; the outermost ``dp`` axis is the
one to map onto DCN for multi-slice.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence, Tuple

import numpy as np

__all__ = ["MESH_AXES", "MeshConfig", "create_mesh", "mesh_axis_size", "get_abstract_mesh"]

MESH_AXES: Tuple[str, ...] = ("dp", "fsdp", "pp", "sep", "cp", "tp")

# Axes over which the global batch is sharded (activation batch dim).
BATCH_AXES: Tuple[str, ...] = ("dp", "fsdp")
# Axes over which the sequence dim of activations is sharded.
SEQ_AXES: Tuple[str, ...] = ("sep", "cp")


@dataclasses.dataclass
class MeshConfig:
    """Degrees for each mesh axis (product must divide the device count)."""

    dp: int = -1  # -1: absorb remaining devices
    fsdp: int = 1
    pp: int = 1
    sep: int = 1
    cp: int = 1
    tp: int = 1

    def resolve(self, n_devices: int) -> "MeshConfig":
        fixed = self.fsdp * self.pp * self.sep * self.cp * self.tp
        dp = self.dp
        if dp == -1:
            if n_devices % fixed != 0:
                raise ValueError(f"device count {n_devices} not divisible by fixed axes product {fixed}")
            dp = n_devices // fixed
        if dp * fixed != n_devices:
            raise ValueError(
                f"mesh {dp}x{self.fsdp}x{self.pp}x{self.sep}x{self.cp}x{self.tp} != {n_devices} devices"
            )
        return dataclasses.replace(self, dp=dp)

    @property
    def shape(self) -> Tuple[int, ...]:
        return (self.dp, self.fsdp, self.pp, self.sep, self.cp, self.tp)

    @property
    def data_degree(self) -> int:
        return self.dp * self.fsdp

    @classmethod
    def from_training_args(cls, args) -> "MeshConfig":
        return cls(
            dp=-1,
            fsdp=args.sharding_parallel_degree if args.sharding_parallel_degree > 0 else 1,
            pp=args.pipeline_parallel_degree,
            sep=args.sep_parallel_degree,
            cp=args.context_parallel_degree,
            tp=args.tensor_parallel_degree,
        )


def create_mesh(config: Optional[MeshConfig] = None, devices: Optional[Sequence] = None):
    """Build the named Mesh; uses ``mesh_utils`` for ICI-aware device placement.

    All axes are ``AxisType.Auto``: GSPMD propagates shardings from the hints the
    models emit (``shard_constraint``) — the moral equivalent of the reference's
    semi-auto parallel (``auto_trainer.py``), but applied to every strategy.
    """
    import jax
    from jax.sharding import AxisType, Mesh

    if devices is None:
        devices = jax.devices()
    config = (config or MeshConfig()).resolve(len(devices))
    shape = config.shape
    try:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_device_mesh(shape, devices=np.asarray(devices))
    except Exception:
        dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, MESH_AXES, axis_types=(AxisType.Auto,) * len(MESH_AXES))


def use_mesh(mesh):
    """Context manager activating ``mesh`` for bare-PartitionSpec sharding hints."""
    import jax

    return jax.sharding.set_mesh(mesh)


def mesh_axis_size(mesh, axis) -> int:
    """Product size of one axis or tuple of axes (absent axes count as 1)."""
    if mesh is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return math.prod(mesh_axis_size(mesh, a) for a in axis)
    return mesh.shape.get(axis, 1)


def get_abstract_mesh(config: MeshConfig, n_devices: int):
    """An AbstractMesh for shape-only compilation (AOT/topology runs)."""
    from jax.sharding import AbstractMesh

    config = config.resolve(n_devices)
    return AbstractMesh(config.shape, MESH_AXES)
