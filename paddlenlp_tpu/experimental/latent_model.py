"""The latent layer kinds' step programs: the class a configuration names
(``config.inference_model``) when its ``layer_kinds()`` are

- ``latent_full``    latent attention over a learned top-k selection of the
                     cached positions: its planes hold one latent row
                     ``(c_kv | roped k_pe)`` and one indexer key a token;
- ``latent_window``  latent attention with its own sizes over a window: its
                     plane holds the window's rows only, under the sequence's
                     second table.

Either kind's MLP is dense or sigmoid-routed experts that know which they hold
(``transformers/latent_layers.py`` has the layer mathematics and lists what it
reads of a configuration; here are the forms that read paged planes). Layers
differ, so the stack is unrolled and each layer addresses its plane by its
index among the layers of its kind.

Two forms of each attention, picked by the static number of tokens a row
feeds: one token (decode) runs absorbed, the query folded into the latent, the
indexer's top-k gathered (``latent_gather``) or the window's blocks; a chunk
of a prompt runs expanded, dense under the selection mask. The full layers'
chunk form is one Pallas kernel (``ops/pallas/latent_attention.py``: keys and
values expanded from the latent rows tile by tile, the score tile in VMEM
only, as many key tiles visited as the longest row needs, a number the device
decides and ``attn_key_tiles`` counts); the window layers' is XLA over the
window's gathered rows. Off the chip the kernel runs in interpret mode.

The entry points, their jit names, the donated pool and the sampler are the
``llama`` kind's: this class only replaces what runs between the embedding and
the final norm. It compiles two programs: ``_mixed_flat_impl`` at one fixed
shape (one chunk row of ``prefill_chunk_tokens``, ``max_batch_size`` decode
rows) and ``_decode_impl``.

Refused at the door, by name: monolithic prefill, a quantized KV cache, LoRA
pools, speculative verify, sharded and disaggregated backends, the host KV
tier and the prefix cache (a shared prefix's window rows are gone by the time
a second request could reuse its blocks, and nothing rebuilds them yet)."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from ..ops.pallas.latent_attention import latent_chunk_attention
from ..transformers import latent_layers as M
from ..transformers.latent_layers import LATENT_FULL as FULL
from ..transformers.latent_layers import LATENT_WINDOW as WINDOW
from .inference_model import LaunchCounts, PagedInferenceModel, _rms, layer_kinds
from .paged_cache import LatentKVPool, init_latent_pool, write_rows

__all__ = ["LatentInferenceModel"]

KEY_TILE = 512  # cached positions the chunk form's indexer loop and attention kernel take in at a time
NEG = M.NEG


class LatentInferenceModel(LaunchCounts, PagedInferenceModel):
    #: what a launch's layers count on the device, in ``pool.stats``'s order (``LaunchCounts``): routed choices of
    #: live tokens that landed on held experts and all of them, the busiest held expert's tokens summed over expert
    #: layers and sub-steps, positions the indexer scored and kept for live queries over full layers, key tiles
    #: the chunk form's attention kernel visited (rows x tiles, summed over full layers)
    STATS = ("expert_assignments_local", "expert_assignments", "expert_tokens_max",
             "index_candidates", "index_selected", "attn_key_tiles")

    @classmethod
    def refuse_engine_features(cls, **features):
        """Engine features the latent kinds do not compute raise here, at construction."""
        named = {
            "kv_cache_quant": "a quantized KV cache (kv_cache_quant): the latent planes have no quantized form",
            "adapter_registry": "LoRA adapter pools (adapter_registry): the latent projections take no per-row delta",
            "use_speculative": "speculative verify (use_speculative / draft_model): the latent kinds have no verify program",
            "mesh_shape": "a sharded backend (mesh_shape): the latent planes and experts have no partition rules yet",
            "disagg_stages": "a disaggregated backend (disagg_stages): no migration of latent and window planes",
            "host_kv_blocks": "the host KV tier (host_kv_blocks): spill and promote copy per-head K/V blocks only",
            "enable_prefix_cache": "the prefix cache (enable_prefix_cache): window planes cannot be shared or rebuilt; "
                                   "pass enable_prefix_cache=False",
        }
        for key, why in named.items():
            if features.get(key):
                raise ValueError(f"the latent layer kinds do not serve {why}")
        if not features.get("prefill_chunk_tokens"):
            raise ValueError("the latent layer kinds prefill in chunks only: pass prefill_chunk_tokens "
                             "(monolithic prefill would score every prompt position against every other at once)")

    def _setup_kind(self, use_paged_kernel):
        cfg = self.config
        cfg.check()  # the latent kinds' door: the configuration refuses what they do not compute
        if use_paged_kernel:
            raise ValueError("no Pallas kernel reads the latent planes: use_paged_kernel must stay off")
        self.use_paged_kernel = False
        self.chunk = int(self.prefill_chunk_tokens or 0)
        self.kinds = layer_kinds(cfg)
        # a layer's index among the layers of its kind: where its rows live in its plane
        self.plane_index = [self.kinds[:i].count(k) for i, k in enumerate(self.kinds)]
        self.n_full, self.n_window = self.kinds.count(FULL), self.kinds.count(WINDOW)
        self.dims = {kind: cfg.attention_dims(kind) for kind in (FULL, WINDOW)}
        self.window_back = self.dims[WINDOW]["window"] - 1
        if self.chunk:
            self.fixed_mixed_shape = (1, self.chunk, self.max_batch_size)
        if self.n_window:
            fed = max(self.chunk, self.decode_steps, 1)
            per_slot = (self.window_back + fed + self.block_size - 1) // self.block_size + 2
            self.window_spec = {"window_back": self.window_back,
                                "num_window_blocks": self.max_batch_size * per_slot + 1}
        self.quant_cfg = None

    def init_pool(self, num_blocks: int, block_size: int, dtype, quant=None) -> LatentKVPool:
        full, win = self.dims[FULL], self.dims[WINDOW]
        widths = {"kv": full["kv_lora"] + full["rope"], "idx": self.config.index_head_dim,
                  "win": win["kv_lora"] + win["rope"]}
        return init_latent_pool(self.n_full, self.n_window, num_blocks,
                                (self.window_spec or {}).get("num_window_blocks", 1), block_size, widths,
                                len(self.STATS), dtype)

    # ------------------------------------------------------------------ the stack
    def _run_layers(self, m, h, pool, block_tables, q_positions, kv_len_mask, write_pos,
                    q_lens, lora, adapter_idx, slots=None):
        """``block_tables`` [B, 2, M]: a row's block table and its window table
        ([B, M] where no layer keeps a window: the block table alone).
        ``kv_len_mask`` and ``write_pos`` are the llama kind's and unused: the
        positions say where a row writes and what it may read."""
        if lora is not None:
            raise ValueError("the latent layer kinds take no LoRA pool")
        if block_tables.ndim == 2:
            block_tables = block_tables[:, None]
        valid = jnp.arange(h.shape[1])[None, :] < q_lens[:, None]
        for layer, kind in enumerate(self.kinds):
            lp = m[f"layers_{layer}"]
            h, pool = self._layer(h, pool, lp, layer, kind, block_tables, q_positions, valid)
        return h, pool

    def _layer(self, h, pool, lp, layer, kind, tables, positions, valid):
        cfg, attn = self.config, lp["self_attn"]
        li = self.plane_index[layer]
        with jax.named_scope("attn_norm"):
            x = _rms(h, lp["input_layernorm"]["scale"], self.eps)
        if kind == FULL:
            d = self.dims[FULL]
            with jax.named_scope("mla_proj"):
                c_q, q_nope, q_pe, row = M.mla_project(attn, x, positions, d, self.eps)
            with jax.named_scope("indexer"):
                q_i, w_i = M.indexer_query(attn["indexer"], c_q, x, positions, cfg, d["theta"])
                k_i = M.indexer_key(attn["indexer"], x, positions, cfg, d["theta"])
            with jax.named_scope("kv_write"):
                with jax.named_scope("latent_plane"):
                    kv = write_rows(pool.kv, row, tables[:, 0], positions, valid, li)
                with jax.named_scope("index_plane"):
                    idx = write_rows(pool.idx, k_i, tables[:, 0], positions, valid, li)
            pool = dataclasses.replace(pool, kv=kv, idx=idx)
            form = self._full_decode if h.shape[1] == 1 else self._full_chunk
            o, scored, kept, tiles = form(attn, pool, li, tables[:, 0], positions, valid, q_nope, q_pe, q_i, w_i, d)
            with jax.named_scope("index_topk"):
                pool = self._count(pool, index_candidates=scored.sum(), index_selected=kept.sum(),
                                   attn_key_tiles=tiles)
        else:
            d = self.dims[WINDOW]
            with jax.named_scope("mla_proj"):
                _, q_nope, q_pe, row = M.mla_project(attn, x, positions, d, self.eps)
            with jax.named_scope("kv_write"), jax.named_scope("window_plane"):
                win = write_rows(pool.win, row, tables[:, 1], positions, valid, li)
            pool = dataclasses.replace(pool, win=win)
            with jax.named_scope("window_attn"):
                o = self._window_attention(attn, pool.win, li, tables[:, 1], positions, q_nope, q_pe, d)
        with jax.named_scope("attn_gate"):
            gate = jax.nn.sigmoid((x @ attn["gate_proj"]["kernel"].astype(x.dtype)).astype(jnp.float32))
            o = o * gate.astype(o.dtype)[..., None]
        with jax.named_scope("o_proj"):
            h = h + o.reshape(h.shape[:2] + (-1,)) @ attn["o_proj"]["kernel"].astype(h.dtype)
        with jax.named_scope("mlp_norm"):
            x = _rms(h, lp["post_attention_layernorm"]["scale"], self.eps)
        y, chosen = M.mlp(lp["mlp"], x, cfg, layer, live=valid.reshape(-1))
        if chosen is not None:
            pool = self._count_experts(pool, chosen, valid)
        return h + y, pool

    # ------------------------------------------------------------------ full layers
    def _full_decode(self, attn, pool, li, table, positions, valid, q_nope, q_pe, q_i, w_i, d):
        """One query a row, absorbed: the indexer scores the row's whole table,
        its top-k positions' latent rows are gathered, the query folded into
        the latent attends them. q_* [B, 1, H, .] -> ([B, 1, H, v], positions
        scored, positions kept, key tiles visited: none in this form)."""
        cfg, bs = self.config, self.block_size
        b, m = table.shape
        s = m * bs
        with jax.named_scope("indexer"):
            k_i = pool.idx[li, table].reshape(b, s, -1)
            scores = M.index_scores(q_i, w_i, k_i)[:, 0]  # [B, S]
            can = jnp.arange(s)[None, :] <= positions[:, :1]
        with jax.named_scope("index_topk"):
            top, chosen = jax.lax.top_k(jnp.where(can, scores, -jnp.inf), min(cfg.index_topk, s))
            kept = top > -jnp.inf
        with jax.named_scope("latent_gather"):
            blocks = jnp.take_along_axis(table, chosen // bs, axis=1)
            rows = pool.kv[li, blocks, chosen % bs]  # [B, K, kv_lora + rope]
        with jax.named_scope("mla_attn"):
            return _absorbed(attn, rows, kept[:, None, :], q_nope, q_pe, d), can & valid, kept & valid, 0

    def _full_chunk(self, attn, pool, li, table, positions, valid, q_nope, q_pe, q_i, w_i, d):
        """A chunk of queries a row, expanded, over tiles of the cached
        positions: the indexer scores tile by tile, the k-th largest score a
        query is found by counting (``kth_largest``), and attention runs dense
        under the selection mask in one kernel (``latent_chunk_attention``),
        which visits as many key tiles as the longest row needs. q_* [B, T, H, .]."""
        cfg, bs = self.config, self.block_size
        b, t = positions.shape
        m = table.shape[1]
        tile = min(KEY_TILE, m * bs)
        per_tile = tile // bs
        n_tiles_max = -(-m // per_tile)
        table = jnp.pad(table, ((0, 0), (0, n_tiles_max * per_tile - m)))
        s = n_tiles_max * tile
        last = jnp.max(jnp.where(valid, positions, 0))
        n_tiles = last // tile + 1
        kpos = jnp.arange(s)[None, None, :]
        can = (kpos <= positions[:, :, None]) & valid[:, :, None]  # [B, T, S]

        with jax.named_scope("indexer"):
            def score_tile(j, acc):
                blocks = jax.lax.dynamic_slice_in_dim(table, j * per_tile, per_tile, 1)
                part = M.index_scores(q_i, w_i, pool.idx[li, blocks].reshape(b, tile, -1))
                return jax.lax.dynamic_update_slice_in_dim(acc, part, j * tile, 2)

            scores = jax.lax.fori_loop(0, n_tiles, score_tile, jnp.full((b, t, s), -jnp.inf, jnp.float32))
        with jax.named_scope("index_topk"):
            # no query of this launch sees more than index_topk positions: all are kept
            keep = jax.lax.cond(
                last >= cfg.index_topk,
                lambda: (lambda keys, thr: (keys >= thr) & can)(*M.kth_largest(scores, can, cfg.index_topk)),
                lambda: can)
        w_k, w_v = M.kv_b_split(attn, d)
        with jax.named_scope("mla_attn"):
            rows = pool.kv[li, table].reshape(b, s, -1)  # the table's latent rows, contiguous for the kernel
            o = latent_chunk_attention(q_nope, q_pe, rows, w_k, w_v, keep, n_tiles,
                                       scale=(d["nope"] + d["rope"]) ** -0.5, tile=tile)
            return o, can, keep, n_tiles * b

    # ------------------------------------------------------------------ window layers
    def _window_attention(self, attn, plane, li, wtable, positions, q_nope, q_pe, d):
        """Queries at ``positions`` [B, T] (consecutive from a row's first)
        attend the window's rows: the blocks from ``first - window_back`` to
        the last position fed are gathered through the window table, one query
        absorbed, a chunk expanded. -> [B, T, H, v]."""
        bs, back = self.block_size, self.window_back
        b, t = positions.shape
        m = wtable.shape[1]
        n_blocks = min((back + t + bs - 1) // bs + 1, m)
        first = jnp.maximum(positions[:, 0] - back, 0) // bs  # [B]
        logical = first[:, None] + jnp.arange(n_blocks)[None, :]
        blocks = jnp.where(logical < m, jnp.take_along_axis(wtable, jnp.minimum(logical, m - 1), axis=1), 0)
        rows = plane[li, blocks].reshape(b, n_blocks * bs, -1)
        kpos = (first * bs)[:, None] + jnp.arange(n_blocks * bs)[None, :]  # [B, S]
        allowed = (kpos[:, None, :] <= positions[:, :, None]) & (kpos[:, None, :] >= positions[:, :, None] - back)
        if t == 1:
            return _absorbed(attn, rows, allowed, q_nope, q_pe, d)
        c_kv, k_pe = rows[..., : d["kv_lora"]], rows[..., d["kv_lora"]:]
        w_k, w_v = M.kv_b_split(attn, d)
        k_nope = jnp.einsum("bsc,chn->bshn", c_kv, w_k.astype(self.dtype))
        v = jnp.einsum("bsc,chv->bshv", c_kv, w_v.astype(self.dtype))
        sc = (jnp.einsum("bthn,bshn->bhts", q_nope, k_nope, preferred_element_type=jnp.float32)
              + jnp.einsum("bthr,bsr->bhts", q_pe, k_pe, preferred_element_type=jnp.float32))
        sc = jnp.where(allowed[:, None], sc * (d["nope"] + d["rope"]) ** -0.5, NEG)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhts,bshv->bthv", p.astype(self.dtype), v)


def _absorbed(attn, rows, allowed, q_nope, q_pe, d):
    """One query a row against gathered latent rows [B, K, kv_lora + rope]:
    ``q_nope`` through W^K into the latent, scores against ``c_kv`` and
    ``k_pe``, the weighted latent through W^V. allowed [B, 1, K]."""
    dtype = q_nope.dtype
    c_kv, k_pe = rows[..., : d["kv_lora"]], rows[..., d["kv_lora"]:]
    w_k, w_v = M.kv_b_split(attn, d)
    q_lat = jnp.einsum("bthn,chn->bthc", q_nope, w_k.astype(dtype))
    sc = (jnp.einsum("bthc,bsc->bhts", q_lat, c_kv, preferred_element_type=jnp.float32)
          + jnp.einsum("bthr,bsr->bhts", q_pe, k_pe, preferred_element_type=jnp.float32))
    sc = jnp.where(allowed[:, None], sc * (d["nope"] + d["rope"]) ** -0.5, NEG)
    p = jax.nn.softmax(sc, axis=-1)
    o_lat = jnp.einsum("bhts,bsc->bthc", p.astype(dtype), c_kv)
    return jnp.einsum("bthc,chv->bthv", o_lat, w_v.astype(dtype))
