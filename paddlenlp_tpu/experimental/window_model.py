"""The windowed grouped-query layer kinds' step programs: the class a
configuration names (``config.inference_model``) when its ``layer_kinds()`` are

- ``gqa_window``  grouped-query attention over a window, q and k rotated: its
                  plane (``WindowKVPool.win``) holds per-head K and V for the
                  window only, under the sequence's second table, whose blocks
                  the ``BlockManager`` gives back behind the window;
- ``gqa_full``    grouped-query attention over the whole context, nothing
                  rotated: its plane (``WindowKVPool.kv``) is the llama kind's
                  per-head pool, under the block table.

Both planes have ``PagedKVPool``'s layout, so both kinds write through
``write_kv_block`` and read through the ragged paged Pallas kernel, each kind
through a walk sized by what it reads: a window layer's call
(``ops/pallas/paged_attention.py``, ``window=``) a grid sized by the window,
with the window plane as its whole pool and the layer's index among window
layers as its layer; a full layer's call (``ops/pallas/paged_run_attention.py``)
its table by runs of 512 keys for every KV head at once, as many turns as the
row has runs. The layer kind chooses the walk, at ``_attention_kind`` and
nowhere else: the llama and state kinds (``PagedInferenceModel._attention``) keep
the walk of the whole table a block and head a step until the benchmark can
judge a faster decode in their cells (ROADMAP S2, W1a). ``attn_kv_fetched``
counts what the full layers' walk fetched beside what its rows could see
(``attn_kv_full``). In both kinds q and k pass an RMS norm over each head's
dims; the MLP is dense SwiGLU or sigmoid-routed SwiGLU experts held in part
(``transformers/window_layers.py`` and ``latent_layers.py`` have the layer
mathematics). Layers differ, so the stack is unrolled and each layer addresses
its plane by its index among the layers of its kind.

The entry points, their jit names, the donated pool and the sampler are the
``llama`` kind's. It compiles two programs: ``_mixed_flat_impl`` at one fixed
shape (one chunk row of ``prefill_chunk_tokens``, ``max_batch_size`` decode
rows) and ``_decode_impl``.

Refused at the door, by name: monolithic prefill, a quantized KV cache, LoRA
pools, speculative verify (the family's prediction block is not loaded, and a
rejected draft's positions would have to be taken back out of a window table),
sharded and disaggregated backends, the host KV tier and the prefix cache (a
shared prefix's window blocks are gone by the time a second request could
reuse its pool blocks, and nothing rebuilds them)."""

from __future__ import annotations

import contextlib
import dataclasses

import jax
import jax.numpy as jnp

from ..ops.pallas.paged_run_attention import ragged_paged_run_attention, run_blocks, runs_visited
from ..transformers import latent_layers as M
from ..transformers import window_layers as W
from ..transformers.window_layers import GQA_FULL as FULL
from ..transformers.window_layers import GQA_WINDOW as WINDOW
from .inference_model import LaunchCounts, PagedInferenceModel, _rms, layer_kinds
from .paged_cache import PagedKVPool, WindowKVPool, init_window_pool, write_kv_block

__all__ = ["WindowedInferenceModel"]


class WindowedInferenceModel(LaunchCounts, PagedInferenceModel):
    #: what a launch's layers count on the device, in ``pool.stats``'s order (``LaunchCounts``): routed choices of
    #: live tokens that landed on held experts and all of them, the busiest held expert's tokens summed over expert
    #: layers and sub-steps; cached positions visible to the live rows, summed over the full layers and over the
    #: window layers (a row that feeds n tokens from position s: s + n a full layer, min(s, window - 1) + n a window layer)
    #: and the cached positions the full layers' table walk fetched for them (``_fetched``)
    STATS = ("expert_assignments_local", "expert_assignments", "expert_tokens_max", "attn_kv_full", "attn_kv_window",
             "attn_kv_fetched")

    @classmethod
    def refuse_engine_features(cls, **features):
        """Engine features the windowed kinds do not compute raise here, at construction."""
        named = {
            "kv_cache_quant": "a quantized KV cache (kv_cache_quant): the window plane has no scale plane, and two "
                              "of the pool's planes in two precisions is not computed",
            "adapter_registry": "LoRA adapter pools (adapter_registry): the projections take no per-row delta",
            "use_speculative": "speculative verify (use_speculative / draft_model): the kinds have no verify program, "
                               "and a rejected draft's positions cannot be taken back out of a window table",
            "mesh_shape": "a sharded backend (mesh_shape): the two planes and the experts have no partition rules yet",
            "disagg_stages": "a disaggregated backend (disagg_stages): no migration of the window plane",
            "host_kv_blocks": "the host KV tier (host_kv_blocks): spill and promote copy the block table's blocks "
                              "only, and a window layer's are under the second table",
            "enable_prefix_cache": "the prefix cache (enable_prefix_cache): a shared prefix's window blocks cannot be "
                                   "shared or rebuilt; pass enable_prefix_cache=False",
        }
        for key, why in named.items():
            if features.get(key):
                raise ValueError(f"the windowed layer kinds do not serve {why}")
        if not features.get("prefill_chunk_tokens"):
            raise ValueError("the windowed layer kinds prefill in chunks only: pass prefill_chunk_tokens "
                             "(the window plane holds a window plus one launch's tokens, not a prompt)")

    def _setup_kind(self, use_paged_kernel):
        cfg = self.config
        cfg.check()  # the configuration refuses what these kinds do not compute
        self._setup_attention(use_paged_kernel)  # head counts and the kernel rule are the llama kind's
        self.chunk = int(self.prefill_chunk_tokens or 0)
        self.kinds = layer_kinds(cfg)
        # a layer's index among the layers of its kind: where its rows live in its plane
        self.plane_index = [self.kinds[:i].count(k) for i, k in enumerate(self.kinds)]
        self.n_full, self.n_window = self.kinds.count(FULL), self.kinds.count(WINDOW)
        self.dims = cfg.attention_dims()
        self.window = self.dims["window"]
        if self.chunk:
            self.fixed_mixed_shape = (1, self.chunk, self.max_batch_size)
        if self.n_window:
            fed = max(self.chunk, self.decode_steps, 1)
            per_slot = (self.window - 1 + fed + self.block_size - 1) // self.block_size + 2
            self.window_spec = {"window_back": self.window - 1,
                                "num_window_blocks": self.max_batch_size * per_slot + 1}

    def init_pool(self, num_blocks: int, block_size: int, dtype, quant=None) -> WindowKVPool:
        return init_window_pool(self.n_full, self.n_window, num_blocks,
                                (self.window_spec or {}).get("num_window_blocks", 1), block_size,
                                self.n_kv * self.head_dim, len(self.STATS), dtype)

    # ------------------------------------------------------------------ the stack
    def _run_layers(self, m, h, pool, block_tables, q_positions, kv_len_mask, write_pos,
                    q_lens, lora, adapter_idx, slots=None):
        """``block_tables`` [B, 2, M]: a row's block table and its window table
        ([B, M] where no layer keeps a window: the block table alone).
        ``kv_len_mask`` is the llama kind's and unused: the positions say what
        a row may read. The residual form (pre-norm) lives here and in the
        module's ``decoder_forward``."""
        if lora is not None:
            raise ValueError("the windowed layer kinds take no LoRA pool")
        if block_tables.ndim == 2:
            block_tables = block_tables[:, None]
        cfg = self.config
        tables = {FULL: block_tables[:, 0], WINDOW: block_tables[:, -1]}
        valid = jnp.arange(h.shape[1])[None, :] < q_lens[:, None]
        seen = jnp.where(q_lens > 0, write_pos + q_lens, 0).sum()
        seen_window = jnp.where(q_lens > 0, jnp.minimum(write_pos, self.window - 1) + q_lens, 0).sum()
        pool = self._count(pool, attn_kv_full=self.n_full * seen, attn_kv_window=self.n_window * seen_window,
                           attn_kv_fetched=self.n_full * self._fetched(tables[FULL], write_pos, q_lens))
        for layer, kind in enumerate(self.kinds):
            lp = m[f"layers_{layer}"]
            with jax.named_scope("attn_norm"):
                x = _rms(h, lp["input_layernorm"]["scale"], self.eps)
            o, pool = self._attention_kind(lp["self_attn"], x, pool, kind, self.plane_index[layer], tables[kind],
                                           q_positions, write_pos, q_lens)
            with jax.named_scope("o_proj"):
                h = h + o.reshape(h.shape[:2] + (-1,)) @ lp["self_attn"]["o_proj"]["kernel"].astype(h.dtype)
            with jax.named_scope("mlp_norm"):
                x = _rms(h, lp["post_attention_layernorm"]["scale"], self.eps)
            y, chosen = M.mlp(lp["mlp"], x, cfg, layer, live=valid.reshape(-1))
            if chosen is not None:
                pool = self._count_experts(pool, chosen, valid)
            h = h + y
        return h, pool

    def _fetched(self, table, start, q_lens):
        """Cached positions a full layer's table walk fetches for the live rows: with the kernel a row's own runs
        (``runs_visited`` x positions a run; a chunk row's query tiles each walk theirs, the row counts once), through
        the XLA gather the whole table. ``attn_kv_full`` over it is the share of what was read that a row could see."""
        if not self.use_paged_kernel:
            return (q_lens > 0).sum() * (table.shape[1] * self.block_size)
        run = run_blocks(self.block_size, table.shape[1]) * self.block_size
        return runs_visited(start, q_lens, run).sum() * run

    def _attention_kind(self, attn, x, pool, kind, li, table, positions, start, q_lens):
        """One layer's attention on the normed input x [B, T, hidden] through
        layer ``li`` of its kind's plane: projections, the norm of q and k,
        rotation on window layers, the fed tokens' K and V written at their
        positions, the ragged paged kernel (a full layer walks its table by
        runs, ``paged_run_attention.py``; a window layer the window, the llama
        kind's kernel with ``window``) or the XLA gather. The layer kind
        chooses the walk, here and nowhere else. -> ([B, T, heads, head_dim], pool)."""
        windowed = kind == WINDOW
        q, k, v = W.project_qkv(attn, x, positions, self.dims, kind, self.eps)
        plane = pool.win if windowed else pool.kv
        with jax.named_scope("kv_write"), jax.named_scope("window_plane") if windowed else contextlib.nullcontext():
            plane = write_kv_block(PagedKVPool(kv=plane), k, v, table, start, li).kv
        pool = dataclasses.replace(pool, **{"win" if windowed else "kv": plane})
        if self.use_paged_kernel:
            if windowed:
                with jax.named_scope("paged_attn_window"):
                    return self._paged_attention(q, plane, None, table, start, q_lens, li, window=self.window), pool
            with jax.named_scope("paged_attn"):
                return ragged_paged_run_attention(q, plane, table, start, q_lens, li), pool
        with jax.named_scope("attn_gather"):
            return self._gathered(q, plane, li, table, positions, windowed), pool

    def _gathered(self, q, plane, li, table, positions, windowed):
        """The XLA path (no kernel): the table's blocks, or with a window the
        blocks from ``first - (window - 1)`` to the last position fed through
        the window table, gathered and attended under the mask."""
        bs = self.block_size
        b, t = positions.shape
        m = table.shape[1]
        if windowed:
            n_blocks = min((self.window - 1 + t + bs - 1) // bs + 1, m)
            first = jnp.maximum(positions[:, 0] - (self.window - 1), 0) // bs  # [B]
            logical = first[:, None] + jnp.arange(n_blocks)[None, :]
            blocks = jnp.where(logical < m, jnp.take_along_axis(table, jnp.minimum(logical, m - 1), axis=1), 0)
        else:
            n_blocks, first, blocks = m, jnp.zeros((b,), jnp.int32), table
        k, v = (plane[li, side, blocks].reshape(b, n_blocks * bs, self.n_kv, self.head_dim) for side in (0, 1))
        k_pos = (first * bs)[:, None] + jnp.arange(n_blocks * bs)[None, :]
        return W.attend(q, k, v, W.window_mask(positions, k_pos, self.window if windowed else None))
