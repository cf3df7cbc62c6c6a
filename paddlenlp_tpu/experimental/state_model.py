"""The state-space layer kinds' step programs: the class a configuration names
(``config.inference_model``) when its ``layer_kinds()`` are

- ``ssm``        a Mamba-2 mixer. Its whole past is a recurrent state and the
                 last few inputs of its convolution: **state rows addressed by
                 the engine's slot** (``paged_cache.StatePool``), in no block
                 of tokens;
- ``experts``    sigmoid-routed relu² experts of which this process holds a
                 share, and one shared expert (``latent_layers.moe`` told the
                 ``RELU2`` body);
- ``attention``  grouped-query attention through the llama kind's own path
                 (``PagedInferenceModel._attention``: per-head pool, ragged
                 paged Pallas kernel) without rotary embedding, which the
                 configuration's class says (``rotary_attention = False``).

Every block is one mixer under a pre-norm residual
(``transformers/state_layers.py`` has the scan layer's mathematics). Blocks
differ, so the stack is unrolled and each block addresses its plane by its
index among the blocks of its kind.

Two forms of the scan, picked by the static number of tokens a row feeds: a
chunk of a prompt runs the chunked (SSD) form from the slot's state in
sub-chunks of ``chunk_size``, padded positions leaving the state untouched
(``dt = 0``); one token runs the one-step recurrence. A row that feeds
position 0 starts from zeros whatever its slot held (an admission, a
re-prefill after preemption: recompute, as KV, no snapshot), and a row that
feeds nothing changes no slot's rows.

The entry points, their jit names, the donated pool and the sampler are the
``llama`` kind's. It compiles two programs: ``_mixed_flat_impl`` at one fixed
shape (one chunk row of ``prefill_chunk_tokens``, ``max_batch_size`` decode
rows addressed through their slots) and ``_decode_impl`` (its rows are the
slots in order: the state is sliced, not gathered).

Refused at the door, by name: monolithic prefill, a quantized KV cache, LoRA
pools, speculative verify, sharded and disaggregated backends, the host KV
tier and the prefix cache (a shared prefix's state is in no block: nothing a
second request could reuse)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..transformers import state_layers as S
from ..transformers.latent_layers import RELU2, moe
from .inference_model import LaunchCounts, PagedInferenceModel, _rms, layer_kinds
from .paged_cache import PagedKVPool, StatePool, init_state_pool, read_state_rows, write_state_rows

__all__ = ["StateSpaceInferenceModel"]


class StateSpaceInferenceModel(LaunchCounts, PagedInferenceModel):
    #: what a launch's layers count on the device, in ``pool.stats``'s order (``LaunchCounts``): routed choices of
    #: live tokens that landed on held experts and all of them, the busiest held expert's tokens summed over expert
    #: layers and sub-steps; rows whose state the scan layers read and wrote (rows x sub-steps, dead ones too: what
    #: the program computes), those that fed a token, and those that started from zeros
    STATS = ("expert_assignments_local", "expert_assignments", "expert_tokens_max",
             "state_rows", "state_rows_live", "state_resets")

    @classmethod
    def refuse_engine_features(cls, **features):
        """Engine features the state-space kinds do not compute raise here, at construction."""
        named = {
            "kv_cache_quant": "a quantized KV cache (kv_cache_quant): state rows are float32 by design and have no "
                              "quantized form, and the attention blocks' planes are a twentieth of the pool",
            "adapter_registry": "LoRA adapter pools (adapter_registry): the scan and expert projections take no per-row delta",
            "use_speculative": "speculative verify (use_speculative / draft_model): a rejected draft's steps cannot "
                               "be taken back out of a recurrent state, and no snapshot is kept",
            "mesh_shape": "a sharded backend (mesh_shape): the state rows and experts have no partition rules yet",
            "disagg_stages": "a disaggregated backend (disagg_stages): no migration of state rows",
            "host_kv_blocks": "the host KV tier (host_kv_blocks): spill and promote copy blocks of tokens, and a scan "
                              "layer's past is in no block",
            "enable_prefix_cache": "the prefix cache (enable_prefix_cache): a shared prefix's recurrent state is in no "
                                   "block, and no snapshot of it is kept; pass enable_prefix_cache=False",
        }
        for key, why in named.items():
            if features.get(key):
                raise ValueError(f"the state-space layer kinds do not serve {why}")
        if not features.get("prefill_chunk_tokens"):
            raise ValueError("the state-space layer kinds prefill in chunks only: pass prefill_chunk_tokens "
                             "(a prompt enters the recurrent state chunk by chunk, in the mixed step)")

    def _setup_kind(self, use_paged_kernel):
        cfg = self.config
        cfg.check()  # the configuration refuses what these kinds do not compute
        self._setup_attention(use_paged_kernel)  # the attention blocks are the llama kind's own path, kernel rule and all
        self.chunk = int(self.prefill_chunk_tokens or 0)
        self.kinds = layer_kinds(cfg)
        # a block's index among the blocks of its kind: where its rows live in its plane
        self.plane_index = [self.kinds[:i].count(k) for i, k in enumerate(self.kinds)]
        self.dims = cfg.ssm_dims()
        if self.chunk:
            self.fixed_mixed_shape = (1, self.chunk, self.max_batch_size)
        self.quant_cfg = None

    def init_pool(self, num_blocks: int, block_size: int, dtype, quant=None) -> StatePool:
        d = self.dims
        return init_state_pool(
            self.kinds.count(S.ATTENTION), num_blocks, block_size, self.n_kv * self.head_dim,
            self.kinds.count(S.SSM), self.max_batch_size,
            (d["groups"], d["heads"] // d["groups"], d["head_dim"], d["state"]), (d["conv"] - 1, d["conv_dim"]),
            len(self.STATS), dtype)

    # ------------------------------------------------------------------ the stack
    def _run_layers(self, m, h, pool, block_tables, q_positions, kv_len_mask, write_pos,
                    q_lens, lora, adapter_idx, slots=None):
        """``slots`` [B]: the engine slot of each row, whose state rows it reads
        and writes; None where the rows are the slots in order (decode)."""
        if lora is not None:
            raise ValueError("the state-space layer kinds take no LoRA pool")
        b, t = h.shape[:2]
        valid = jnp.arange(t)[None, :] < q_lens[:, None]
        live = q_lens > 0
        fresh = q_positions[:, 0] == 0  # a sequence's first token: whatever the slot held is someone else's
        pool = self._count(pool, state_rows=b, state_rows_live=live.sum(), state_resets=(live & fresh).sum())
        for layer, kind in enumerate(self.kinds):
            lp, li = m[f"layers_{layer}"], self.plane_index[layer]
            with jax.named_scope("attn_norm"):
                u = _rms(h, lp["norm"]["scale"], self.eps)
            if kind == S.SSM:
                y, pool = self._scan_layer(lp["mixer"], u, pool, li, slots, valid, live, fresh, q_lens)
            elif kind == S.EXPERTS:
                y, chosen = moe(lp["mixer"], u, self.config, live=valid.reshape(-1), body=RELU2)
                pool = self._count_experts(pool, chosen, valid)
            else:
                y, view = self._attention(u, PagedKVPool(kv=pool.kv), lp["mixer"], None, adapter_idx, block_tables,
                                          q_positions, kv_len_mask, write_pos, q_lens, li)
                pool = dataclasses.replace(pool, kv=view.kv)
            h = h + y
        return h, pool

    def _scan_layer(self, p, u, pool, li, slots, valid, live, fresh, q_lens):
        """One Mamba-2 mixer on u [B, T, hidden] from and to the rows' state:
        ``state_rw`` is the read of the slots' rows and the write back, the
        ``ssm_*`` scopes are ``state_layers.ssm_mixer``'s."""
        b, t = u.shape[:2]
        k = self.dims["conv"] - 1
        with jax.named_scope("state_rw"):
            h_old = read_state_rows(pool.ssm, li, slots, b)
            conv_old = read_state_rows(pool.conv, li, slots, b)
            h0 = jnp.where(fresh[:, None, None, None, None], 0.0, h_old.astype(jnp.float32))
            before = jnp.where(fresh[:, None, None], jnp.zeros((), conv_old.dtype), conv_old)
        y, window, h1 = S.ssm_mixer(p, u, valid, before.astype(u.dtype), h0, self.dims, self.eps)
        with jax.named_scope("state_rw"):
            # the last K - 1 inputs up to the row's last real token: the window holds K - 1 + T in order
            tail = jax.vmap(lambda w, n: jax.lax.dynamic_slice_in_dim(w, n, k, 0))(window, q_lens)
            pool = dataclasses.replace(pool, ssm=write_state_rows(pool.ssm, li, slots, h_old, h1, live),
                                       conv=write_state_rows(pool.conv, li, slots, conv_old, tail, live))
        return y, pool
