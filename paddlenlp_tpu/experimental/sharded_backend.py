"""Tensor-parallel serving backend: one engine replica spans a device mesh.

The router (serving/router/) scales the fleet *out* over identical
single-chip replicas; this backend scales a replica *up* — weights and the
paged KV pool are laid out with ``jax.sharding.NamedSharding`` over a
``parallel.mesh`` Mesh, and every jitted step program is compiled with
explicit ``in_shardings``/``out_shardings`` so XLA inserts the collectives
(the serving twin of *Scalable Training of Language Models using JAX pjit
and TPUv4*). The engine's scheduler, BlockManager, prefix cache, chunked
prefill and supervisor all run unchanged on top: they only ever see host
numpy and the backend interface.

Layout — all-gather tensor parallelism on the ``tp`` axis:

=========================  =================================================
tensor                     sharding (when the dim divides tp; else replicated)
=========================  =================================================
embed_tokens.embedding     vocab rows sharded
q/k/v_proj kernels+bias    output (heads) sharded — column parallel
o_proj / down_proj kernel  output (hidden) sharded — column parallel
gate/up_proj kernels+bias  output (ffn) sharded
lm_head kernel             output (vocab) sharded
KV pool [L,2,nb,K,bs,H]    kv-heads axis sharded; blocks/batch replicated
activations                heads/ffn dims sharded between anchors; the
                           residual stream, logits, penalty counts replicated
=========================  =================================================

Every contraction reads *replicated* operands on its contraction dim (the
``_hint(..., "full")`` anchors in inference_model.py force an all-gather
first), so each output element is the SAME floating-point reduction as the
single-device program — the sharded engine is bitwise token-identical to
:class:`~.backend.SingleDeviceBackend`, which is what the parity suite
asserts. The classic row-parallel alternative (partial dots + psum) moves
less data but reorders the o_proj/down_proj reductions; flipping those two
rules to ``P("tp", None)`` buys it back where bit-exactness doesn't matter.

``dp`` (the leading axis of ``mesh_shape=(dp, tp)``) currently replicates —
it is the seam for data-parallel batch sharding and for the two-stage MPMD
prefill/decode split (stage = dp slice, KV migrating between stage pools;
see backend.py's seam note) without another engine refactor.

Testable anywhere: ``XLA_FLAGS=--xla_force_host_platform_device_count=8``
gives an 8-way CPU mesh.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.mesh import MeshConfig, create_mesh
from ..parallel.partition import spec_tree_from_rules
from ..utils.faults import FaultPoint
from ..utils.log import logger
from .backend import SingleDeviceBackend
from .kv_host_tier import gather_blocks, scatter_blocks
from .inference_model import PagedInferenceModel
from .paged_cache import PagedKVPool

__all__ = ["ShardedBackend", "ShardedPagedInferenceModel", "serving_partition_rules"]

_F_SHARD_INIT = FaultPoint("engine.shard_init")

#: identity logical->physical mapping: the serving rules below name mesh axes
#: directly ("tp"); `layers` is the auto-prepended leading axis of scanned
#: param stacks and stays unsharded here (pp is a training concern).
_IDENTITY_RULES = {"tp": "tp", "layers": None}


def serving_partition_rules(config, tp: int):
    """[(param-path regex, physical PartitionSpec)] for the serving layout.

    Head-bearing dims are gated on head-count divisibility (an aligned split
    keeps the per-head attention compute local to a shard); vocab/ffn/hidden
    dims rely on `resolve_spec`'s shape check to fall back to replication.
    ``(kernel|qweight)`` covers weight-only-quantized serving params — their
    per-channel scales replicate via the catch-all."""
    n_heads = config.num_attention_heads
    n_kv = getattr(config, "num_key_value_heads", n_heads)
    rules = []
    if n_heads % tp == 0:
        rules += [
            (r"self_attn/q_proj/(kernel|qweight)$", P(None, "tp")),
            (r"self_attn/q_proj/bias$", P("tp")),
        ]
    if n_kv % tp == 0:
        rules += [
            (r"self_attn/[kv]_proj/(kernel|qweight)$", P(None, "tp")),
            (r"self_attn/[kv]_proj/bias$", P("tp")),
        ]
    rules += [
        (r"embed_tokens/embedding$", P("tp", None)),
        (r"(lm_head|score)/kernel$", P(None, "tp")),
        (r"mlp/(gate_proj|up_proj)/(kernel|qweight)$", P(None, "tp")),
        (r"mlp/(gate_proj|up_proj)/bias$", P("tp")),
        (r"self_attn/o_proj/(kernel|qweight)$", P(None, "tp")),
        (r"self_attn/o_proj/bias$", P("tp")),
        (r"mlp/down_proj/(kernel|qweight)$", P(None, "tp")),
        (r"mlp/down_proj/bias$", P("tp")),
        (r".*", P()),
    ]
    return rules


def _normalize_mesh_shape(mesh_shape) -> MeshConfig:
    """int tp | (dp, tp) | MeshConfig -> MeshConfig."""
    if isinstance(mesh_shape, MeshConfig):
        return mesh_shape
    if isinstance(mesh_shape, int):
        return MeshConfig(dp=1, tp=mesh_shape)
    if isinstance(mesh_shape, (tuple, list)) and len(mesh_shape) == 2:
        return MeshConfig(dp=int(mesh_shape[0]), tp=int(mesh_shape[1]))
    raise ValueError(
        f"mesh_shape must be an int tp degree, a (dp, tp) pair or a MeshConfig; "
        f"got {mesh_shape!r}")


class ShardedPagedInferenceModel(PagedInferenceModel):
    """PagedInferenceModel whose jitted steps carry explicit shardings.

    Construction needs the model params (to build the param sharding tree)
    and whether the pool is quantized (its structure). The activation
    ``_hint`` anchors implement the all-gather layout described in the
    module docstring."""

    def __init__(self, model, *args, mesh, kv_quantized: bool = False,
                 lora_enabled: bool = False, **kw):
        self.mesh = mesh
        self.tp = int(mesh.shape["tp"])
        self._repl = NamedSharding(mesh, P())
        rules = serving_partition_rules(model.config, self.tp)
        self.param_specs = spec_tree_from_rules(model.params, rules, mesh, _IDENTITY_RULES)
        self.param_shardings = jax.tree.map(lambda s: NamedSharding(mesh, s), self.param_specs)
        n_kv = getattr(model.config, "num_key_value_heads", model.config.num_attention_heads)
        # token-major rows [L, 2, nb, bs, K*H] (scales [.., K]): splitting the
        # last axis tp ways puts whole kv heads on each shard
        self.pool_spec = (P(None, None, None, None, "tp")
                          if n_kv % self.tp == 0 else P())
        pool_ns = NamedSharding(mesh, self.pool_spec)
        self.pool_shardings = PagedKVPool(kv=pool_ns, scale=pool_ns if kv_quantized else None)
        self.lora_specs, self.lora_shardings = self._lora_layout(model.config, lora_enabled)
        super().__init__(model, *args, **kw)

    def _lora_layout(self, config, lora_enabled: bool):
        """(spec tree, sharding tree) for the adapter pool argument.

        Adapter weights follow the column-parallel rules of the projections
        they patch: ``B`` [L, P, r, d_out] shards its output dim on ``tp``
        exactly when the base kernel's output dim does (else replication —
        the same fallback `serving_partition_rules` uses), and ``A`` is
        always replicated (its output dim is the tiny rank r). ``x @ A``
        then reads a replicated operand, and ``(xA) @ B`` produces a
        tp-sharded delta that lands on ``base(x)``'s identical layout before
        the `_hint` anchors re-gather — the reduction ORDER matches the
        single-device program, preserving bitwise token identity.

        LoRA off -> the lora argument is always None (an empty pytree), and
        a single replicated leaf serves as its universal tree prefix."""
        if not lora_enabled:
            return None, self._repl
        from ..serving.tenancy.adapters import adapter_dims_from_config
        specs = {}
        for proj, (_d_in, d_out) in adapter_dims_from_config(config).items():
            b_spec = P(None, None, None, "tp") if d_out % self.tp == 0 else P()
            specs[proj] = {"A": P(), "B": b_spec}
        shardings = jax.tree.map(lambda s: NamedSharding(self.mesh, s), specs,
                                 is_leaf=lambda x: isinstance(x, P))
        return specs, shardings

    def _hint(self, x, kind: str):
        if self.tp == 1:
            return x
        if kind == "full":
            spec = P()
        elif kind in ("heads", "kv_heads"):
            if x.shape[2] % self.tp != 0:
                return x
            spec = P(None, None, "tp", None)
        elif kind == "mlp":
            if x.shape[-1] % self.tp != 0:
                return x
            spec = P(None, None, "tp")
        else:
            return x
        return jax.lax.with_sharding_constraint(x, NamedSharding(self.mesh, spec))

    def _paged_attention(self, q, kv, kv_scale, block_tables, q_start, q_lens, layer):
        # GSPMD cannot partition a Mosaic kernel ("wrap the call in a
        # shard_map"): run it manually per shard — each tp shard attends its
        # own kv heads and their query groups against its slice of the pool;
        # with a replicated pool (kv heads not divisible) every shard computes
        # all heads. Per-head math is untouched, so identity with the
        # single-device kernel holds.
        heads = "tp" if self.pool_spec != P() else None
        q_spec = P(None, None, heads, None)  # [B, T, N, H]
        return jax.shard_map(
            super()._paged_attention, mesh=self.mesh,
            in_specs=(q_spec, self.pool_spec, self.pool_spec, P(), P(), P(), P()),
            out_specs=q_spec, check_vma=False,
        )(q, kv, kv_scale, block_tables, q_start, q_lens, layer)

    def _build_jits(self):
        # a step takes its host inputs as one packed buffer, replicated (the
        # backend lands it so: ShardedBackend._place_launch), then the counts
        # and the multi-LoRA pool (column-parallel / replicated per
        # _lora_layout; a replicated prefix when LoRA is off and the arg is
        # always None); the buffer's layout is static by position.
        ps, pool_s, r = self.param_shardings, self.pool_shardings, self._repl
        lora_s = self.lora_shardings
        self._prefill = jax.jit(
            self._prefill_impl, donate_argnums=(1,), static_argnums=(6,),
            in_shardings=(ps, pool_s, r, r, r, lora_s),
            out_shardings=(r, r, pool_s))
        self._decode = jax.jit(
            self._decode_impl, donate_argnums=(1,), static_argnums=(5,),
            in_shardings=(ps, pool_s, r, r, lora_s),
            out_shardings=(r, r, r, r, r, pool_s))
        self._verify = jax.jit(
            self._verify_impl, donate_argnums=(1,), static_argnums=(4, 5),
            in_shardings=(ps, pool_s, r, lora_s),
            out_shardings=(r, r, pool_s))
        self._mixed_flat = jax.jit(
            self._mixed_flat_impl, donate_argnums=(1,), static_argnums=(5,),
            in_shardings=(ps, pool_s, r, r, lora_s),
            out_shardings=(r, r, pool_s))


class ShardedBackend(SingleDeviceBackend):
    """Engine backend running the forward + KV pool over a device mesh.

    ``InferenceEngine(mesh_shape=...)`` selects it. Params are device_put
    once with their NamedShardings and re-put only when ``model.params`` is
    rebound (a serving weight update); the pool and counts live sharded /
    replicated on the mesh for their whole life."""

    def __init__(self, model, *, mesh_shape, devices=None, stage=None, **kw):
        # surfaced as a named fault point: mesh/layout init is the first
        # thing a supervisor rebuild of a sharded engine replays, and chaos
        # coverage needs it to fail deterministically. Staged (disagg)
        # backends construct one ShardedBackend per stage, so the fault fires
        # once per stage rebuild — `stage` labels which one.
        _F_SHARD_INIT.fire(stage=stage or "all")
        self.stage = stage  # None = whole-replica backend; "prefill"/"decode" = disagg stage
        config = _normalize_mesh_shape(mesh_shape)
        if devices is None:
            devices = jax.devices()
        else:
            devices = list(devices)
        if config.dp == -1:  # MeshConfig callers may leave dp to absorb
            config = config.resolve(len(devices))
        n_dev = config.dp * config.fsdp * config.pp * config.sep * config.cp * config.tp
        if n_dev > len(devices):
            raise ValueError(
                f"mesh_shape {mesh_shape!r} needs {n_dev} devices, "
                f"{len(devices)} available (CPU runs: set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count={n_dev})")
        self.mesh = create_mesh(config, devices=devices[:n_dev])
        self.mesh_config = config
        self._kv_quantized = kw.get("kv_cache_quant") is not None
        super().__init__(model, **kw)
        self._params_src = model.params
        self._params = jax.device_put(model.params, self.infer.param_shardings)
        n_kv = getattr(model.config, "num_key_value_heads", model.config.num_attention_heads)
        if n_kv % config.tp != 0:
            logger.warning(
                f"sharded backend: num_key_value_heads={n_kv} not divisible by "
                f"tp={config.tp}; KV pool and attention run replicated")

    # ---------------------------------------------------------------- setup
    def _build_infer(self, model, block_size, num_blocks, max_blocks_per_seq,
                     dtype, decode_steps, eos_ids):
        return ShardedPagedInferenceModel(
            model, block_size, num_blocks, max_blocks_per_seq, dtype=dtype,
            decode_steps=decode_steps, eos_ids=eos_ids,
            mesh=self.mesh, kv_quantized=self._kv_quantized,
            lora_enabled=self.adapter_registry is not None,
        )

    def _init_pool(self, config, num_blocks, block_size, dtype, quant):
        # made on the mesh, every shard in place. A pool made on the default
        # device and re-put holds there, for a moment, the pool and a slice of
        # it for every device of the mesh: on a 2x2 of 16 GB chips a 4.4 GB
        # pool took the first chip to 16.3 GB.
        make = functools.partial(super()._init_pool, config, num_blocks, block_size, dtype, quant)
        return jax.jit(make, in_shardings=(), out_shardings=self.infer.pool_shardings,
                       donate_argnums=())()

    def _place_lora(self, host_pool):
        # adapter pool lands with its column-parallel/replicated layout so
        # dispatch never re-shards it against the jits' in_shardings
        return jax.device_put(host_pool, self.infer.lora_shardings)

    def _init_counts(self):
        return jax.device_put(super()._init_counts(), self.infer._repl)

    def _place_launch(self, host):
        # replicated, as the step programs' in_shardings take it: one transfer
        # call, and dispatch never re-shards it
        return jax.device_put(host, self.infer._repl)

    def _build_host_tier_jits(self):
        # host-tier spill/promote with the step programs' explicit-placement
        # contract: gather/scatter on the pool's sharding (the block-slice
        # layout equals the pool layout — the kv heads split the last axis,
        # blocks replicate), ids and the marker replicated, scatter pool
        # donated. The kv sharding serves the scale plane too: same
        # NamedSharding, same last-axis split.
        kv_s = self.infer.pool_shardings.kv
        r = self.infer._repl
        gather = jax.jit(gather_blocks, donate_argnums=(),
                         in_shardings=(kv_s, r), out_shardings=kv_s)
        scatter = jax.jit(scatter_blocks, donate_argnums=(0,),
                          in_shardings=(kv_s, kv_s, r), out_shardings=(kv_s, r))
        return gather, scatter

    def _place_host_blocks(self, data):
        # promoted rows land pre-placed on the pool layout so the scatter jit
        # never reshards its data operand at dispatch
        return jax.device_put(data, self.infer.pool_shardings.kv)

    @property
    def params(self):
        # a weight update rebinds model.params: re-place it on the mesh once,
        # not per step (id check is one pointer compare on the hot path)
        if self.model.params is not self._params_src:
            self._params_src = self.model.params
            self._params = jax.device_put(self.model.params, self.infer.param_shardings)
        return self._params

    def sync_params(self, new_params):
        # eager re-place with the EXISTING NamedSharding layout: placement
        # failures surface here (inside a swap's rollback window), and the
        # id-check in the params property then sees a settled rebind
        placed = jax.device_put(new_params, self.infer.param_shardings)
        self.model.params = new_params
        self._params_src = new_params
        self._params = placed

    def describe(self) -> dict:
        axes = {k: int(v) for k, v in self.mesh.shape.items()}
        out = {
            "kind": "sharded",
            "devices": int(self.mesh.size),
            "tp_degree": axes.get("tp", 1),
            "mesh": axes,
            "mesh_shape": [self.mesh_config.dp, self.mesh_config.tp],
            "kv_pool_sharded": self.infer.pool_spec != P(),
        }
        if self.stage is not None:
            out["stage"] = self.stage
        return out

