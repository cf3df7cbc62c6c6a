"""Paged-attention inference forward, by layer kind.

A model's configuration yields its layer kinds (``config.layer_kinds()``) and
names the class whose step programs compute them (``config.inference_model``);
a configuration that says neither is all ``llama`` layers, computed by this
file's :class:`PagedInferenceModel`: identical layers under one ``lax.scan``,
one pool of per-head K and V. A class of other kinds subclasses it and owns its
parameter names, its pool (``init_pool``), its forward (``_run_layers``) and
its door: what of the engine it does not serve (``refuse_engine_features``)
and what of a configuration it does not compute (``refuse_unserved``), each
refused by the mechanism's name. No model's name is tested here.

Counterpart of ``paddlenlp/experimental/transformers/fused_transformer_layers.py``
(``FusedBlockMultiTransformer`` :2192) + per-model ``*BlockInferenceModel`` classes:
a decode-optimized forward that REUSES the training params (scanned [L] layout)
but runs its own fused loop — mirroring the reference's split between training
models and the experimental inference runtime.

TPU-native: one ``lax.scan`` over the stacked layer params with the whole paged
pool in its carry, written and read in place by layer index; block-table
gathers/scatters instead of CUDA append-attention kernels; the whole
prefill/decode step is a single jit.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.rope import apply_rotary_pos_emb, rope_frequencies, rope_tables
from .launch_pack import unpack
from .paged_cache import PagedKVPool, gather_kv, init_paged_pool, write_kv_block

__all__ = ["PagedInferenceModel", "LaunchCounts", "sample_tokens", "SAMP_FIELDS", "layer_kinds", "refuse_unserved",
           "inference_model_class"]

#: the per-row sampling parameters a launch carries: ``sample_tokens``' keyword arrays, in buffer order
SAMP_FIELDS = ("seeds", "temperature", "top_k", "top_p", "do_sample",
               "repetition_penalty", "presence_penalty", "frequency_penalty")


def layer_kinds(config):
    """The kind of every layer of ``config``, first to last: what the
    configuration yields, ``llama`` throughout where it yields nothing."""
    own = getattr(config, "layer_kinds", None)
    return list(own()) if callable(own) else ["llama"] * config.num_hidden_layers


def refuse_unserved(config, max_context: int):
    """The door of the ``llama`` kind: a configuration whose layers need a
    mechanism this kind does not compute is refused by that mechanism's name,
    never served with something else in its place. ``max_context`` is the
    longest sequence the engine's tables can hold: a window at least that long
    is not in use."""
    name = type(config).__name__
    if getattr(config, "ssm_state_size", None) or getattr(config, "state_size", None):
        raise ValueError(f"{name}: state-space layers (a recurrent state a sequence, ssm_state_size / state_size) "
                         "are not computed by the llama layer kind: its pool holds blocks of tokens, and a scan "
                         "layer's past is in no block (paged_cache.StatePool keeps state rows by slot)")
    other = sorted(set(layer_kinds(config)) - {"llama"})
    if other:
        raise ValueError(f"{name}: layer kinds {other} are not computed by the llama kind's step programs "
                         "(the configuration names the class that does: config.inference_model)")
    window = getattr(config, "sliding_window", None)
    if window is not None and window < max_context:
        raise ValueError(
            f"{name}: sliding_window={window} is in use (sequences reach {max_context} positions) and the "
            "llama layer kind attends the whole context; a kind with a window cache is needed (per-head K and V: "
            "window_model.WindowedInferenceModel over paged_cache.WindowKVPool; latent rows: paged_cache.LatentKVPool)")
    if getattr(config, "kv_lora_rank", None):
        raise ValueError(f"{name}: kv_lora_rank={config.kv_lora_rank} (latent attention) is not computed by the "
                         "llama layer kind: its pool holds per-head K and V")
    for key in ("n_routed_experts", "num_local_experts", "num_experts"):
        if getattr(config, key, None):
            raise ValueError(f"{name}: {key}={getattr(config, key)} (routed experts) is not computed by the "
                             "llama layer kind: its MLP is one dense SwiGLU")


def inference_model_class(config):
    """The class whose step programs compute ``config``'s layer kinds: the one
    the configuration names by its dotted path (``config.inference_model``; a
    path, so that a configuration imports no serving code), else the llama kind's."""
    path = getattr(config, "inference_model", None)
    if path is None:
        return PagedInferenceModel
    import importlib

    module, _, attr = path.rpartition(".")
    return getattr(importlib.import_module(module), attr)


def sample_tokens(
    logits: jnp.ndarray,  # [B, V] fp32
    *,
    positions: jnp.ndarray,  # [B] absolute position of the token being sampled
    seeds: jnp.ndarray,  # [B] int32 per-slot seeds
    temperature: jnp.ndarray,  # [B]
    top_k: jnp.ndarray,  # [B] int32 (<=0: off)
    top_p: jnp.ndarray,  # [B]
    do_sample: jnp.ndarray,  # [B] bool
    counts: Optional[jnp.ndarray] = None,  # [B, V] token counts (prompt+generated)
    repetition_penalty: Optional[jnp.ndarray] = None,  # [B]
    presence_penalty: Optional[jnp.ndarray] = None,  # [B]
    frequency_penalty: Optional[jnp.ndarray] = None,  # [B]
) -> jnp.ndarray:
    """Fully on-device sampling: penalties + temperature + top-k/top-p + draw.

    Counterpart of the reference's in-kernel sampling path
    (``csrc/gpu/sample_kernels/top_p_sampling_reject.cu``,
    ``csrc/gpu/token_penalty_multi_scores.cu``): one [B,V] sort serves both
    top-k and top-p, the draw is a per-row categorical, and randomness is keyed
    on (seed, absolute position) so a preempted-and-recomputed sequence
    resamples identical tokens. Host never sees logits — only ids.
    """
    B, V = logits.shape
    logits = logits.astype(jnp.float32)
    if counts is not None:
        seen = counts > 0
        rp = repetition_penalty[:, None]
        logits = jnp.where(seen, jnp.where(logits > 0, logits / rp, logits * rp), logits)
        logits = logits - seen.astype(jnp.float32) * presence_penalty[:, None]
        logits = logits - counts.astype(jnp.float32) * frequency_penalty[:, None]
    greedy = jnp.argmax(logits, axis=-1)

    warped = logits / jnp.maximum(temperature, 1e-6)[:, None]
    order = jnp.argsort(-warped, axis=-1)
    sorted_logits = jnp.take_along_axis(warped, order, axis=-1)
    ranks = jnp.arange(V)[None, :]
    # top-k first, RENORMALIZE, then the nucleus cutoff over the renormalized
    # distribution — the composition the host sampler / warper chain defines
    keep_k = jnp.where(top_k[:, None] > 0, ranks < top_k[:, None], True)
    k_masked = jnp.where(keep_k, sorted_logits, -jnp.inf)
    probs = jax.nn.softmax(k_masked, axis=-1)
    csum = jnp.cumsum(probs, axis=-1)
    keep = keep_k & ((csum - probs) < top_p[:, None])
    keep |= ranks == 0  # top-1 always kept
    masked = jnp.where(keep, sorted_logits, -jnp.inf)

    def draw(seed, pos, row):
        key = jax.random.fold_in(jax.random.key(seed), pos)
        return jax.random.categorical(key, row)

    picked = jax.vmap(draw)(seeds, positions, masked)
    sampled = jnp.take_along_axis(order, picked[:, None], axis=-1)[:, 0]
    return jnp.where(do_sample, sampled, greedy).astype(jnp.int32)


def _rms(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)).astype(x.dtype)


def _launch_fields(packed, layout):
    """A launch's host inputs out of its one buffer (``launch_pack.unpack``): the
    fields by name, and the sampling parameters as the dict ``sample_tokens``
    takes. The first thing every step program does."""
    fields = unpack(packed, layout)
    return fields, {k: fields.pop(k) for k in SAMP_FIELDS if k in fields}


class LaunchCounts:
    """For a kind whose layers count on the device and whose prompts enter in
    chunks only (mix in ahead of :class:`PagedInferenceModel`): ``STATS`` names
    what rides the pool's ``stats`` [n] int32, zeroed as a launch begins, added
    to by ``_count`` and read back after the launch's own sync as launch-span
    args and ledger totals (``goodput.KIND_COUNTERS``)."""

    STATS = ()

    def launch_counts(self, pool) -> dict:
        """What the layers of the launch just synced counted on the device."""
        return dict(zip(self.STATS, (int(x) for x in np.asarray(pool.stats))))  # sync-ok: a few ints, after the launch's own sync

    def _count(self, pool, **counts):
        add = jnp.stack([jnp.asarray(counts.get(name, 0), jnp.int32) for name in self.STATS])
        return dataclasses.replace(pool, stats=pool.stats + add)

    def _prefill_impl(self, *args, **kwargs):
        raise NotImplementedError(f"{type(self).__name__} prefills in chunks only (prefill_chunk_tokens)")

    def _verify_impl(self, *args, **kwargs):
        raise NotImplementedError(f"{type(self).__name__} has no speculative verify program")

    def _mixed_flat_body(self, params, pool, *args, **kwargs):
        return super()._mixed_flat_body(params, dataclasses.replace(pool, stats=jnp.zeros_like(pool.stats)),
                                        *args, **kwargs)

    def _decode_body(self, params, pool, *args, **kwargs):
        return super()._decode_body(params, dataclasses.replace(pool, stats=jnp.zeros_like(pool.stats)),
                                    *args, **kwargs)

    def _decode_q_lens(self, done):
        return (~done).astype(jnp.int32)

    def _count_experts(self, pool, chosen, valid):
        """Count an expert layer's routed choices ``chosen`` [N, k] of the tokens that are ``valid`` [B, T]
        (``expert_assignments_local``, ``expert_assignments``, ``expert_tokens_max`` of ``STATS``)."""
        from ..transformers.latent_layers import held_counts

        with jax.named_scope("router"):
            first, count = self.config.experts_held
            per_expert = held_counts(jnp.where(valid.reshape(-1, 1), chosen, -1), first, count)
            return self._count(pool, expert_assignments_local=per_expert.sum(),
                               expert_assignments=valid.sum() * chosen.shape[-1],
                               expert_tokens_max=per_expert.max())


class PagedInferenceModel:
    """Holds jitted prefill/decode over (params, pool): the ``llama`` layer kind
    (llama/qwen2/mistral: config-driven biases + GQA + rope), every layer alike."""

    #: (chunk rows, chunk tokens, decode rows) of the one mixed program a
    #: model compiles, or None: the backend buckets each to a power of two
    fixed_mixed_shape = None
    #: a window cache's needs (``BlockManager``), or None
    window_spec = None
    #: a class whose layers count on the device gives the counts after a launch's
    #: sync, as launch-span args (``launch_counts(pool)``)
    launch_counts = None

    @classmethod
    def refuse_engine_features(cls, **features):
        """The engine's door, called for every kind with what the engine was
        asked for (``kv_cache_quant``, ``adapter_registry``, ``use_speculative``,
        ``mesh_shape``, ``disagg_stages``, ``host_kv_blocks``,
        ``enable_prefix_cache``, ``prefill_chunk_tokens``): a class raises
        ``ValueError`` naming each mechanism its programs do not serve. The
        llama kind serves them all."""

    def __init__(self, model, block_size: int = 16, num_blocks: int = 512, max_blocks_per_seq: int = 64,
                 dtype=jnp.bfloat16, decode_steps: int = 8, eos_ids=(), use_paged_kernel=None,
                 max_batch_size: Optional[int] = None, prefill_chunk_tokens: Optional[int] = None):
        self.model = model
        self.config = model.config
        # the engine's geometry, for a kind whose mixed program has one fixed
        # shape (``fixed_mixed_shape``); the llama kind buckets and reads neither
        self.max_batch_size = max_batch_size
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.dtype = dtype
        self.block_size = block_size
        self.num_blocks = num_blocks
        self.max_blocks_per_seq = max_blocks_per_seq
        self.decode_steps = decode_steps
        # [-1] sentinel when no eos: never matches a sampled id
        self.eos_arr = jnp.asarray(sorted(eos_ids) or [-1], jnp.int32)
        self.eps = self.config.rms_norm_eps
        self._setup_kind(use_paged_kernel)
        self._build_jits()

    def init_pool(self, num_blocks: int, block_size: int, dtype, quant=None):
        return init_paged_pool(self.config, num_blocks, block_size, dtype=dtype, quant=quant)

    def _setup_kind(self, use_paged_kernel):
        """What the llama kind needs beside the common fields; first its door."""
        model = self.model
        refuse_unserved(self.config, self.block_size * self.max_blocks_per_seq)
        if "layers" not in model.params.get("model", {}):
            raise ValueError("PagedInferenceModel requires the scanned-layer param layout (use_scan_layers)")
        self._setup_attention(use_paged_kernel)
        # serving a QuantizedModel: its params carry qweight/scales leaves
        # (stacked [L, ...] — lax.scan slices per layer); _mm dispatches per
        # projection (reference int8_gemm_with_cutlass serving path)
        self.quant_cfg = getattr(model, "quantization_config", None)

    def _setup_attention(self, use_paged_kernel):
        """What ``_attention`` reads: the head counts, the kernel switch and
        whether q and k are rotated. A configuration's class says where its
        attention carries no position embedding (``rotary_attention = False``);
        the llama kind's do."""
        cfg = self.config
        self.n_heads, self.n_kv, self.head_dim = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
        # Pallas ragged paged kernel: default-on for TPU when the tile shapes
        # are Mosaic-safe (one head's (block_size, head_dim) tile is cut out
        # of the pool's n_kv * head_dim lane rows, so head_dim must fill whole
        # 128-lane tiles); otherwise the XLA gather path. On TPU a default
        # that comes out off is said once, so it is never a silent choice.
        if use_paged_kernel is None:
            on_tpu = jax.default_backend() == "tpu"
            use_paged_kernel = on_tpu and self.head_dim % 128 == 0 and self.block_size % 8 == 0
            if on_tpu and not use_paged_kernel:
                from ..utils.log import logger

                logger.warning_once(
                    f"paged attention kernel off for {type(self.model).__name__} "
                    f"(head_dim={self.head_dim}, block_size={self.block_size}): needs "
                    "head_dim % 128 == 0 and block_size % 8 == 0; using the XLA gather path")
        self.use_paged_kernel = use_paged_kernel
        self.rotary = getattr(cfg, "rotary_attention", True)
        if self.rotary:
            self.inv_freq = jnp.asarray(rope_frequencies(self.head_dim, cfg.rope_theta, cfg.rope_scaling))

    def _build_jits(self):
        """Compile the step entry points. The sharded subclass overrides this
        to attach explicit ``in_shardings``/``out_shardings``; the base keeps
        the historical un-annotated jits."""
        # the launch buffer's layout (and verify's need_logits) is static BY
        # POSITION: a jit with in_shardings (the sharded subclass) takes no
        # keyword arguments
        self._prefill = jax.jit(self._prefill_impl, donate_argnums=(1,), static_argnums=(6,))
        self._decode = jax.jit(self._decode_impl, donate_argnums=(1,), static_argnums=(5,))
        self._verify = jax.jit(self._verify_impl, donate_argnums=(1,), static_argnums=(4, 5))
        self._mixed_flat = jax.jit(self._mixed_flat_impl, donate_argnums=(1,), static_argnums=(5,))

    def _hint(self, x, kind: str):
        """Activation-layout hook: identity here; the sharded subclass turns
        ``kind`` ("heads" / "kv_heads" / "mlp" / "full") into
        ``with_sharding_constraint`` anchors so GSPMD keeps per-head compute
        local and gathers before every cross-shard contraction (the all-gather
        layout keeps the sharded forward bitwise-identical to this one)."""
        return x

    def _mm(self, p, x):
        """x @ kernel with quantized-leaf dispatch: a8w8 -> int8 x int8 MXU dot;
        weight-only -> dequant fused into the matmul operand read."""
        if "qweight" not in p:
            y = x @ p["kernel"].astype(self.dtype)
        elif self.quant_cfg is not None and self.quant_cfg.is_activation_quantize:
            from ..quantization.a8w8 import int8_linear

            return int8_linear(x, p["qweight"], p["scales"], bias=p.get("bias"),
                               act_scale=p.get("act_scale"), out_dtype=self.dtype)
        else:
            from ..quantization.quantization_utils import dequantize_leaf

            bits = self.quant_cfg.bits if self.quant_cfg is not None else 8
            y = x @ dequantize_leaf(p["qweight"], p["scales"], bits, self.dtype)
        if "bias" in p:
            y = y + p["bias"].astype(self.dtype)
        return y

    def _lora_mm(self, p, x, lora_layer, adapter_idx, name: str):
        """Base matmul + per-row LoRA delta gathered from the adapter pool.

        ``lora_layer`` is one layer's slice of the pool: ``{proj: {"A":
        [P, d_in, r], "B": [P, r, d_out]}}`` (P = slots, slot 0 = identity
        zeros, scaling pre-folded into B); ``adapter_idx`` [B] maps each batch
        row to its slot. The delta is per-row — ``base(x) + B[idx] @ (A[idx]
        @ x)`` computed row-independently — so a row's tokens are bitwise
        identical whether its adapter shares the batch with others or runs
        solo, the same independence the sampler's (seed, position) keying
        provides. fp32 accumulation matches the merged-LoRA training math."""
        y = self._mm(p, x)
        if lora_layer is None or name not in lora_layer:
            return y
        a = lora_layer[name]["A"][adapter_idx].astype(jnp.float32)  # [B, d_in, r]
        b = lora_layer[name]["B"][adapter_idx].astype(jnp.float32)  # [B, r, d_out]
        xr = jnp.einsum("btd,bdr->btr", x.astype(jnp.float32), a)
        delta = jnp.einsum("btr,bro->bto", xr, b)
        return y + delta.astype(y.dtype)

    # ------------------------------------------------------------------ forward core
    def _attend(self, q, k, v, q_positions, kv_len_mask, block=None):
        """q [B,T,N,H]; k/v [B,S,K,H]; causal by absolute position + length mask. ``block`` (static, a power
        of two; None but for a kind that generates by diffusion over blocks): causal over blocks of that many
        positions, a query seeing its own block whole."""
        B, T, N, H = q.shape
        S = k.shape[1]
        if self.n_kv != N:
            k = jnp.repeat(k, N // self.n_kv, axis=2)
            v = jnp.repeat(v, N // self.n_kv, axis=2)
        logits = jnp.einsum("btnh,bsnh->bnts", q.astype(jnp.float32), k.astype(jnp.float32)) * (H**-0.5)
        kv_pos = jnp.arange(S)[None, :]
        if block is not None:
            q_positions = q_positions | (block - 1)
        mask = (kv_pos[:, None, :] <= q_positions[:, :, None]) & kv_len_mask[:, None, :]
        logits = jnp.where(mask[:, None], logits, -1e30)
        probs = jax.nn.softmax(logits, axis=-1)
        out = jnp.einsum("bnts,bsnh->btnh", probs, v.astype(jnp.float32))
        return out.astype(q.dtype)

    def _paged_attention(self, q, kv, kv_scale, block_tables, q_start, q_lens, layer, window=None):
        """Fused block-table walk (or, with ``window``, window walk) + attend over layer ``layer`` of the whole
        pool: the Pallas ragged kernel streams addressed KV blocks instead of
        materializing the gathered cache (dequant rides in-kernel for int8/fp8
        pools). One launch covers the whole ragged batch — decode rows
        (q_lens=1), prefill chunks (q_lens up to T), and inactive padding
        (q_lens=0) together. The sharded subclass runs it under ``shard_map``."""
        from ..ops.pallas.paged_attention import ragged_paged_attention

        return ragged_paged_attention(q, kv, block_tables, q_start=q_start, q_lens=q_lens,
                                      layer=layer, kv_scale=kv_scale, window=window)

    def _attention(self, x, pool: PagedKVPool, attn, lora_layer, adapter_idx, block_tables, q_positions,
                   kv_len_mask, write_pos, q_lens, layer):
        """The attention mixer on the normed input ``x`` [B, T, D], through layer
        ``layer`` of the per-head pool: projections, rotary embedding where the
        configuration has one, the fed tokens' K and V written at their
        positions, the ragged paged kernel (or the XLA gather), the output
        projection. Returns (what the residual adds, the pool)."""
        B, T, _ = x.shape

        # jax.named_scope is metadata only: each operation's op_name carries
        # the scope, which is how a device profile names what a fusion is for
        # (the serving programs get no scope from a module system)
        def proj(p, x, heads, name):
            return self._lora_mm(p, x, lora_layer, adapter_idx, name) \
                .reshape(B, T, heads, self.head_dim)

        with jax.named_scope("qkv"):
            q = self._hint(proj(attn["q_proj"], x, self.n_heads, "q_proj"), "heads")
            k = self._hint(proj(attn["k_proj"], x, self.n_kv, "k_proj"), "kv_heads")
            v = self._hint(proj(attn["v_proj"], x, self.n_kv, "v_proj"), "kv_heads")
        if self.rotary:
            with jax.named_scope("rope"):
                cos, sin = rope_tables(q_positions, self.inv_freq)
                q, k = apply_rotary_pos_emb(q, k, cos, sin)

        with jax.named_scope("kv_write"):
            pool = write_kv_block(pool, k, v, block_tables, write_pos, layer)
        if self.use_paged_kernel:
            with jax.named_scope("paged_attn"):
                attn_out = self._paged_attention(q, pool.kv, pool.scale, block_tables,
                                                 q_positions[:, 0], q_lens, layer)
        else:
            with jax.named_scope("attn_gather"):
                k_all, v_all = gather_kv(pool, block_tables, layer, self.n_kv)
                attn_out = self._attend(q, k_all, v_all, q_positions, kv_len_mask)
        with jax.named_scope("o_proj"):
            attn_out = attn_out.reshape(B, T, self.n_heads * self.head_dim)
            # gather before the contraction (o_proj stays column-parallel: full
            # dot per output column, no cross-shard partial sums), gather after
            # so the residual/norms see a replicated stream
            attn_out = self._hint(attn_out, "full")
            return self._hint(
                self._lora_mm(attn["o_proj"], attn_out, lora_layer, adapter_idx, "o_proj"),
                "full"), pool

    def _layer(self, carry, scanned, block_tables, q_positions, kv_len_mask, write_pos,
               q_lens, adapter_idx):
        """One decoder layer inside lax.scan: carry = (h, whole pool), written
        and read in place at this layer's index; scanned = (layer_params,
        lora_layer-or-None for multi-LoRA batches, layer index)."""
        h, pool = carry
        lp, lora_layer, layer = scanned

        with jax.named_scope("attn_norm"):
            x = _rms(h, lp["input_layernorm"]["scale"], self.eps)
        attn_out, pool = self._attention(x, pool, lp["self_attn"], lora_layer, adapter_idx, block_tables,
                                         q_positions, kv_len_mask, write_pos, q_lens, layer)
        with jax.named_scope("o_proj"):
            h = h + attn_out

        with jax.named_scope("mlp_norm"):
            x = _rms(h, lp["post_attention_layernorm"]["scale"], self.eps)
        with jax.named_scope("mlp"):
            mlp = lp["mlp"]
            gate = self._hint(
                self._lora_mm(mlp["gate_proj"], x, lora_layer, adapter_idx, "gate_proj"), "mlp")
            up = self._hint(
                self._lora_mm(mlp["up_proj"], x, lora_layer, adapter_idx, "up_proj"), "mlp")
            act = self._hint(jax.nn.silu(gate) * up, "full")
            h = h + self._hint(
                self._lora_mm(mlp["down_proj"], act, lora_layer, adapter_idx, "down_proj"),
                "full")
        return (h, pool), None

    def _forward(self, params, pool: PagedKVPool, input_ids, block_tables, q_positions,
                 kv_len_mask, write_pos, last_pos, q_lens=None, lora=None,
                 adapter_idx=None, slots=None):
        """input_ids [B,T]; returns (logits at last_pos [B,V], new PagedKVPool).

        ``slots`` [B] is the engine slot of each row, for a kind that keeps
        something by slot (``StatePool``); None where the rows are the slots in
        order (the decode program). The llama kind keeps nothing by slot.

        ``last_pos=None`` returns full-sequence logits [B,T,V] (the speculative
        verify step needs the model's prediction after EVERY draft position).
        ``q_lens`` [B] = valid new tokens per row (defaults to T everywhere);
        only the Pallas ragged kernel consumes it — the XLA path masks padded
        rows implicitly (their outputs are never read).

        ``lora`` is the adapter pool tree ``{proj: {"A": [L, P, d_in, r],
        "B": [L, P, r, d_out]}}`` (or None for an adapter-free program);
        ``adapter_idx`` [B] maps each row to a pool slot (0 = identity). The
        adapter pool rides the layer scan as xs: its [L] axis slices per layer
        alongside the params, and None is a valid empty pytree — the
        adapter-free program carries no extra operands at all. The KV pool
        does NOT: it rides the carry whole, addressed by layer index."""
        if q_lens is None:
            q_lens = jnp.full((input_ids.shape[0],), input_ids.shape[1], jnp.int32)
        if lora is not None and adapter_idx is None:
            adapter_idx = jnp.zeros((input_ids.shape[0],), jnp.int32)
        m = params["model"]
        embed = m["embed_tokens"]["embedding"]
        with jax.named_scope("embed"):
            h = self._hint(embed[input_ids].astype(self.dtype), "full")
            if getattr(self.config, "scale_embeddings", False):
                h = h * jnp.asarray(self.config.hidden_size**0.5, h.dtype)

        h, new_pool = self._run_layers(m, h, pool, block_tables, q_positions, kv_len_mask,
                                       write_pos, q_lens, lora, adapter_idx, slots)
        with jax.named_scope("final_norm"):
            h = _rms(h, m["norm"]["scale"], self.eps)
        with jax.named_scope("lm_head"):
            last = h if last_pos is None else h[jnp.arange(h.shape[0]), last_pos]
            if "lm_head" in params:
                logits = last @ params["lm_head"]["kernel"].astype(self.dtype)
            else:
                logits = last @ embed.T.astype(self.dtype)
        # logits stay in compute dtype: every consumer either casts to fp32
        # itself (sample_tokens) or explicitly opts out of the cast (greedy
        # verify reads only the argmax, sparing the [B, T, V] fp32 buffer).
        # Sharded layouts leave them vocab-sharded here; the gather to the
        # replicated sampler happens once at this anchor.
            logits = self._hint(logits, "full")
        return logits, new_pool

    def _run_layers(self, m, h, pool, block_tables, q_positions, kv_len_mask, write_pos,
                    q_lens, lora, adapter_idx, slots=None):
        """Every layer of the stack, by kind. Here all are ``llama``: one scan."""
        def body(carry, scanned):
            return self._layer(carry, scanned, block_tables, q_positions, kv_len_mask,
                               write_pos, q_lens, adapter_idx)

        # the pool rides the carry, addressed by the scanned layer index: as
        # xs/ys every layer would slice its pool out and stack it back. A None
        # lora is an empty pytree lax.scan slices to None per layer
        scanned = (m["layers"], lora, jnp.arange(pool.kv.shape[0], dtype=jnp.int32))
        (h, new_pool), _ = jax.lax.scan(body, (h, pool), scanned)
        return h, new_pool

    # ------------------------------------------------------------------ entry points
    # A step program is ``_<step>_impl``: it takes the launch's host inputs as
    # ONE packed int32 buffer with its static layout (launch_pack.py; the
    # backend sends it in one transfer), takes it apart and runs
    # ``_<step>_body`` on the fields, which is the step itself on plain arrays.
    # The benchmark keys on the jitted names (jit__decode_impl, ...; "flat"
    # stays in the mixed step's for that).
    def _prefill_impl(self, params, pool, packed, cached_counts, counts, lora, layout):
        """The prefill program. ``counts`` [B, V] is the running per-slot count:
        the batch's rows land in it at ``slot_idx`` (padded past the last slot,
        those rows dropped). Returns (tokens [n], counts', new pool)."""
        f, samp = _launch_fields(packed, layout)
        tokens, rows, pool = self._prefill_body(
            params, pool, f["input_ids"], f["block_tables"], f["suffix_lens"], f["cached_lens"],
            cached_counts, samp, lora, f.get("adapter_idx"))
        with jax.named_scope("bookkeeping"):
            counts = counts.at[f["slot_idx"]].set(rows, mode="drop")
        return tokens, counts, pool

    def _decode_impl(self, params, pool, packed, counts, lora, layout):
        f, samp = _launch_fields(packed, layout)
        return self._decode_body(params, pool, f["tokens"], f["block_tables"], f["context_lens"], f["done0"],
                                 f["remaining"], counts, samp, lora, f.get("adapter_idx"))

    def _verify_impl(self, params, pool, packed, lora, layout, need_logits: bool = True):
        f, _ = _launch_fields(packed, layout)
        return self._verify_body(params, pool, f["tokens"], f["block_tables"], f["start_pos"], lora,
                                 f.get("adapter_idx"), need_logits)

    def _mixed_flat_impl(self, params, pool, packed, counts, lora, layout):
        f, samp = _launch_fields(packed, layout)
        return self._mixed_flat_body(
            params, pool, f["chunk_ids"], f["chunk_tables"], f["chunk_qlens"], f["chunk_start"], f["chunk_slots"],
            f["chunk_emit"], f["dec_tokens"], f["dec_tables"], f["dec_start"], f["dec_slots"], f["dec_live"],
            counts, samp, lora, f.get("chunk_adapter"), f.get("dec_adapter"))

    def _prefill_body(self, params, pool, input_ids, block_tables, suffix_lens,
                      cached_lens, cached_counts, samp, lora=None, adapter_idx=None):
        """Batched prefill: [n, T_pad] SUFFIX sequences; samples the first token
        on device.

        Prefix caching feeds only the uncached tail of each prompt:
        ``input_ids`` row j holds prompt tokens ``[cached_lens[j]:]`` (padded to
        T), attention reads the cached span straight from the shared blocks in
        ``block_tables``, and new KV is written starting at ``cached_lens[j]``.
        ``cached_lens = 0`` everywhere reproduces the uncached full prefill.
        ``cached_counts`` [n, V] int32 are the token counts of the CACHED span
        only (host-side — suffix-only input can't see the cached tokens the
        penalty kernels must still count); the fed suffix is counted on device
        as before, so the cache-off / cache-miss path ships only zeros.

        Returns (tokens [n], counts [n, V] incl. prompt + sampled token, new pool).
        """
        n, T = input_ids.shape
        positions = cached_lens[:, None] + jnp.arange(T)[None, :]
        total_lens = cached_lens + suffix_lens
        S = block_tables.shape[1] * self.block_size
        kv_len_mask = jnp.arange(S)[None, :] < total_lens[:, None]
        logits, new_pool = self._forward(
            params, pool, input_ids, block_tables, positions,
            kv_len_mask, cached_lens,
            jnp.maximum(suffix_lens - 1, 0),  # last VALID token (input may be padded)
            q_lens=suffix_lens, lora=lora, adapter_idx=adapter_idx,
        )
        V = cached_counts.shape[-1]
        with jax.named_scope("bookkeeping"):
            valid = (jnp.arange(T)[None, :] < suffix_lens[:, None]).astype(jnp.int32)
            # out-of-vocab ids one_hot to zero rows — same degrade as the old
            # full-prompt device count
            counts = cached_counts + (jax.nn.one_hot(input_ids, V, dtype=jnp.int32)
                                      * valid[..., None]).sum(axis=1)
        with jax.named_scope("sample"):
            tokens = sample_tokens(logits, positions=total_lens, counts=counts, **samp)
        with jax.named_scope("bookkeeping"):
            counts = counts + jax.nn.one_hot(tokens, V, dtype=jnp.int32)
        return tokens, counts, new_pool

    def _mixed_flat_body(self, params, pool, chunk_ids, chunk_tables, chunk_qlens,
                         chunk_start, chunk_slots, chunk_emit, dec_tokens, dec_tables,
                         dec_start, dec_slots, dec_live, counts, samp, lora=None,
                         chunk_adapter=None, dec_adapter=None):
        """One ragged mixed prefill/decode step: two packed segments in one jit,
        ``C x T + D`` positions. A chunk row feeds ``chunk_qlens[j]`` prompt
        tokens starting at absolute position ``chunk_start[j]`` (= tokens
        already prefilled), in a [C, T] matrix; a decode row feeds its last
        sampled token at ``dec_start[j]``, in a [D, 1] segment. KV for every
        fed token is written into the paged pool at its absolute position;
        attention covers ``[0, start + t]`` per fed token t, causal across
        chunk boundaries because earlier chunks' KV is already in the pool. A
        padding row (``chunk_qlens = 0`` / ``~dec_live``) writes only into the
        sentinel block and adds zeros.

        Sampling fires for EVERY row at its next position from the logits
        after the last valid fed token; the caller keeps the token only where
        the row emits (``chunk_emit``: final chunks; live decode rows): the
        sampler fires only when the last chunk lands.

        ``counts`` [B, V] is the running per-SLOT token count, reached through
        ``chunk_slots``/``dec_slots`` and updated by scatter. Chunk rows add
        their fed tokens (the count survives to the next chunk through the
        returned array); decode rows don't (theirs was counted when sampled).
        Emitting rows add the sampled token. Penalties see counts INCLUDING
        the fed tokens, matching the monolithic prefill exactly.

        Returns (tokens [C + D], counts', new pool) — tokens in segment
        order, the caller slices live rows back out.
        """
        C, T = chunk_ids.shape
        S = chunk_tables.shape[1] * self.block_size
        positions_c = chunk_start[:, None] + jnp.arange(T)[None, :]
        kv_mask_c = jnp.arange(S)[None, :] < (chunk_start + chunk_qlens)[:, None]
        logits_c, pool = self._forward(
            params, pool, chunk_ids, chunk_tables, positions_c, kv_mask_c,
            chunk_start, jnp.maximum(chunk_qlens - 1, 0), q_lens=chunk_qlens,
            lora=lora, adapter_idx=chunk_adapter, slots=chunk_slots,
        )
        D = dec_tokens.shape[0]
        positions_d = dec_start[:, None]
        kv_mask_d = jnp.arange(S)[None, :] <= dec_start[:, None]
        logits_d, pool = self._forward(
            params, pool, dec_tokens[:, None], dec_tables, positions_d, kv_mask_d,
            dec_start, jnp.zeros((D,), jnp.int32), q_lens=dec_live.astype(jnp.int32),
            lora=lora, adapter_idx=dec_adapter, slots=dec_slots,
        )
        V = counts.shape[-1]
        with jax.named_scope("bookkeeping"):
            valid = (jnp.arange(T)[None, :] < chunk_qlens[:, None]).astype(jnp.int32)
            fed = (jax.nn.one_hot(chunk_ids, V, dtype=jnp.int32) * valid[..., None]).sum(axis=1)
            counts = counts.at[chunk_slots].add(fed)
            rows = jnp.concatenate([chunk_slots, dec_slots])
            logits_all = jnp.concatenate([logits_c, logits_d], axis=0)
            pos_all = jnp.concatenate([chunk_start + chunk_qlens, dec_start + 1])
        with jax.named_scope("sample"):
            tokens = sample_tokens(logits_all, positions=pos_all, counts=counts[rows], **samp)
        with jax.named_scope("bookkeeping"):
            emit_all = jnp.concatenate([chunk_emit, dec_live]).astype(jnp.int32)
            counts = counts.at[rows].add(
                jax.nn.one_hot(tokens, V, dtype=jnp.int32) * emit_all[:, None])
        return tokens, counts, pool

    def _decode_q_lens(self, done):
        """What a decode sub-step tells the forward about its rows. The llama
        kind says nothing (finished rows rewrite their slot in place); a kind
        that counts or routes its live tokens is told which rows are."""
        return None

    def _decode_body(self, params, pool, tokens, block_tables, context_lens, done0,
                     remaining, counts, samp, lora=None, adapter_idx=None):
        """Multi-step decode: advance every slot up to ``decode_steps`` tokens in ONE
        jit — the host round-trip carries ids and flags only (the reference's whole
        per-token op chain ``update_inputs.cu``/``stop_generation_multi_ends.cu``/
        sampling runs in here). Finished rows freeze: ctx stops advancing and their
        KV slot is rewritten in place, never read again.

        Returns (tokens [steps, B], valid [steps, B], done, ctx, counts, pool).
        """
        B = tokens.shape[0]
        S = block_tables.shape[1] * self.block_size
        eos = self.eos_arr

        def one(carry, _):
            pool_c, tok, ctx, done, counts, n_out = carry
            kv_mask = jnp.arange(S)[None, :] <= ctx[:, None]
            logits, pool_c = self._forward(
                params, pool_c, tok[:, None], block_tables, ctx[:, None],
                kv_mask, ctx, jnp.zeros((B,), jnp.int32),
                q_lens=self._decode_q_lens(done), lora=lora, adapter_idx=adapter_idx,
            )
            with jax.named_scope("sample"):
                nxt = sample_tokens(logits, positions=ctx + 1, counts=counts, **samp)
            with jax.named_scope("bookkeeping"):
                emit = ~done
                hit_eos = (nxt[:, None] == eos[None, :]).any(axis=-1)
                newly_done = emit & (hit_eos | (n_out + 1 >= remaining))
                nxt = jnp.where(done, tok, nxt)
                counts = counts + jax.nn.one_hot(nxt, counts.shape[-1], dtype=counts.dtype) * emit[:, None]
                ctx = jnp.where(done, ctx, ctx + 1)
                n_out = n_out + emit
                done = done | newly_done
            return (pool_c, nxt, ctx, done, counts, n_out), (nxt, emit)

        init = (pool, tokens, context_lens, done0, counts,
                jnp.zeros((B,), jnp.int32))
        (pool, _, ctx, done, counts, _), (toks, valid) = jax.lax.scan(
            one, init, None, length=self.decode_steps
        )
        return toks, valid, done, ctx, counts, pool

    def _verify_body(self, params, pool, tokens, block_tables, start_pos,
                     lora=None, adapter_idx=None, need_logits: bool = True):
        """Speculative-decoding verify: one forward over ``[last_token, d_1..d_K]``.

        Counterpart of the reference's speculative write path
        (``csrc/gpu/append_attn/`` speculative decoding ops): the draft tokens
        are scored in a single [B, K+1] forward over the paged cache and the
        host accepts the longest matching prefix. KV for every fed position is
        written optimistically; rejected positions need no rollback — they are
        masked by absolute position until the next step overwrites them
        in place (the same property the reference's block cache relies on).

        tokens [B, K+1] (row = last accepted token then drafts, 0-padded);
        start_pos [B] absolute position of tokens[:, 0]. Returns
        (argmax [B, K+1] int32, logits [B, K+1, V] fp32 or None, new pool) —
        position i scores the token AFTER consuming tokens[:, i]. Greedy
        acceptance reads only the argmax, and ``need_logits=False`` skips the
        [B, K+1, V] fp32 materialization entirely (it doubled the verify
        buffer per speculative step for a tensor greedy mode never read);
        rejection sampling passes ``need_logits=True`` for the full logits.
        """
        B, T = tokens.shape
        positions = start_pos[:, None] + jnp.arange(T)[None, :]
        S = block_tables.shape[1] * self.block_size
        kv_len_mask = jnp.arange(S)[None, :] <= (start_pos[:, None] + T - 1)
        logits, new_pool = self._forward(
            params, pool, tokens, block_tables, positions, kv_len_mask,
            start_pos, last_pos=None, lora=lora, adapter_idx=adapter_idx,
        )
        with jax.named_scope("sample"):
            argmax = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if not need_logits:
            return argmax, None, new_pool
        return argmax, logits.astype(jnp.float32), new_pool

    def verify(self, params, pool: PagedKVPool, packed, layout, lora=None, need_logits: bool = True):
        return self._verify(params, pool, packed, lora, layout, need_logits)

    def prefill(self, params, pool: PagedKVPool, packed, layout, cached_counts, counts, lora=None):
        return self._prefill(params, pool, packed, cached_counts, counts, lora, layout)

    def decode(self, params, pool: PagedKVPool, packed, layout, counts, lora=None):
        return self._decode(params, pool, packed, counts, lora, layout)

    def mixed_step_flat(self, params, pool: PagedKVPool, packed, layout, counts, lora=None):
        return self._mixed_flat(params, pool, packed, counts, lora, layout)
