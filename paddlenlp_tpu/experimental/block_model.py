"""Generation by diffusion over blocks: the step programs of the class a
configuration names (``config.inference_model``) when its ``layer_kinds()`` are
all ``gqa_block`` (today ``sdar_moe``: grouped-query attention under a block
mask with a per-head norm of q and k and RoPE, softmax-routed SwiGLU experts
held in part; ``transformers/window_layers.py`` and ``latent_layers.py`` have
the layer mathematics).

**A step does not yield one token a sequence.** A sequence advances by blocks of
``B = block_length`` positions counted from position 0. The prompt's whole
blocks enter in chunks under the block mask (a position sees its whole block
and every block before it); the ``len mod B`` tokens left over open the first
generated block as fixed positions. A decode sub-step is **a pass** over
``[slots, B]`` rows: every row feeds its block's B positions (its tokens where
known, the mask id elsewhere) over the cache of the earlier blocks, K and V of
the B positions written every pass, rewritten in place:

- **a denoising pass** (some position of the block is masked): at every masked
  position the best token ``x0`` and its confidence (its softmax probability),
  from one ``argmax`` and one ``logsumexp`` a row, the mask id's logit left out
  of both so that it is never emitted; then the unmasking rule
  (``remasking``): ``low_confidence_static`` the ``B / denoising_steps`` most
  confident masked positions; ``low_confidence_dynamic`` every masked position
  whose confidence is over ``confidence_threshold``, and never fewer than the
  static rule would;
- **a commit pass** (no position masked): the block's final tokens fed once
  more, so that the K and V later blocks read are those of the final tokens;
  the block is handed on (its tokens leave together, ``max_tokens`` inside it
  truncating what is emitted) and the row opens the next block, all masked.

A full block is ``denoising_steps`` denoising passes and a commit pass under the
static rule: 1.25 passes a token at B = 4. The passes of a launch run inside the
decode program's ``lax.scan`` with the block's tokens and masks in the carry;
between launches the host keeps them by slot (``InferenceEngine._block_tokens``
/ ``_block_masked``). Rows are at different passes of their blocks in one
sub-step; the program computes the forward once and each row takes its own
branch.

All layers are alike, so the stack is one ``lax.scan`` over the stacked layer
parameters with the pool's K/V plane in its carry, as the llama kind's. The pool
is ``WindowKVPool`` with an empty window plane: the per-head plane under the
block table and ``stats``. Attention reads the plane through the ragged paged
kernel's walk by runs (``ops/pallas/paged_run_attention.py``, ``block=B``) or
the XLA gather.

It compiles two programs under the llama kind's jit names:
``_mixed_flat_impl`` at one fixed shape (one chunk row of
``prefill_chunk_tokens``, ``max_batch_size`` decode rows of one pass each; no
sampler fires when a prompt's last chunk lands) and ``_decode_impl``
(``decode_steps`` passes). Both return the launch's results as one packed
int32 buffer (``unpack_results``).

Refused at the door, by name: monolithic prefill, sampling inside a block
(top-k, top-p, temperature, penalties: greedy only), speculative verify, LoRA
pools, a quantized KV cache, sharded and disaggregated backends, the host KV
tier and the prefix cache."""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.paged_run_attention import ragged_paged_run_attention
from ..transformers import window_layers as W
from ..transformers.latent_layers import held_counts
from ..transformers.sdar_moe.modeling import sparse_mlp
from .inference_model import LaunchCounts, PagedInferenceModel, _rms
from .launch_pack import unpack
from .paged_cache import PagedKVPool, WindowKVPool, init_window_pool, write_kv_block

__all__ = ["BlockDiffusionInferenceModel", "unpack_results"]

EXPERT_STATS = ("expert_assignments_local", "expert_assignments", "expert_tokens_max")


def unpack_results(buf: np.ndarray, passes: int, rows: int, block: int) -> dict:
    """A launch's packed results (``BlockDiffusionInferenceModel._results``) as host arrays:
    ``tokens`` / ``valid`` [passes, rows, block] (what each pass handed on), the rows' state after the last
    pass (``block_tokens`` / ``block_masked`` [rows, block]) and the passes each row took by kind
    (``denoise`` / ``commit`` [rows])."""
    out, at = {}, 0
    for name, shape in (("tokens", (passes, rows, block)), ("valid", (passes, rows, block)),
                        ("block_tokens", (rows, block)), ("block_masked", (rows, block)),
                        ("denoise", (rows,)), ("commit", (rows,))):
        n = int(np.prod(shape))
        out[name] = buf[at:at + n].reshape(shape)
        at += n
    out["valid"], out["block_masked"] = out["valid"].astype(bool), out["block_masked"].astype(bool)
    return out


class BlockDiffusionInferenceModel(LaunchCounts, PagedInferenceModel):
    #: what a launch counts on the device, in ``pool.stats``'s order (``LaunchCounts``): the three expert counts of
    #: the held-expert kinds; cached positions visible to the live rows, summed over layers and passes (a row that
    #: feeds n positions from s: s + n, its own block whole); rows x passes that denoised and that committed;
    #: positions unmasked; tokens handed on and tokens of a committed block past the request's ``max_tokens``
    STATS = EXPERT_STATS + ("attn_kv_visible", "denoise_passes", "commit_passes", "tokens_unmasked",
                            "tokens_emitted", "tokens_discarded")

    @classmethod
    def refuse_engine_features(cls, **features):
        """Engine features generation by diffusion over blocks does not compute raise here, at construction."""
        named = {
            "kv_cache_quant": "a quantized KV cache (kv_cache_quant): a block's K and V are rewritten every pass, "
                              "and a rounded K would feed the next pass's confidences",
            "adapter_registry": "LoRA adapter pools (adapter_registry): the projections take no per-row delta",
            "use_speculative": "speculative verify (use_speculative / draft_model): a pass already feeds a block of "
                               "positions, and there is no verify program",
            "mesh_shape": "a sharded backend (mesh_shape): the experts and the block state have no partition rules yet",
            "disagg_stages": "a disaggregated backend (disagg_stages): no migration of a block in progress",
            "host_kv_blocks": "the host KV tier (host_kv_blocks): it is the prefix cache's second level",
            "enable_prefix_cache": "the prefix cache (enable_prefix_cache): a cached page would have to end on a "
                                   "whole block of a whole-block prompt to be sound under the block mask; pass "
                                   "enable_prefix_cache=False",
        }
        for key, why in named.items():
            if features.get(key):
                raise ValueError(f"generation by diffusion over blocks does not serve {why}")
        if not features.get("prefill_chunk_tokens"):
            raise ValueError("generation by diffusion over blocks prefills in chunks only: pass prefill_chunk_tokens "
                             "(a multiple of the block length: a chunk ends on a whole block)")

    @staticmethod
    def refuse_sampling(sampling):
        """A request's door: what a block's unmasking does not compute, by name (greedy only)."""
        for key, off in (("do_sample", False), ("top_k", 0), ("top_p", 1.0), ("repetition_penalty", 1.0),
                         ("presence_penalty", 0.0), ("frequency_penalty", 0.0)):
            if getattr(sampling, key, off) != off:
                raise ValueError(f"generation by diffusion over blocks does not serve {key}={getattr(sampling, key)}: "
                                 "a position is unmasked to its most probable token (greedy), and sampling inside a "
                                 "block (do_sample, top_k, top_p, penalties) is not computed")

    def _setup_kind(self, use_paged_kernel):
        cfg = self.config
        cfg.check()  # the configuration refuses what this kind does not compute
        self._setup_attention(use_paged_kernel)  # head counts and the kernel rule are the llama kind's
        self.chunk = int(self.prefill_chunk_tokens or 0)
        self.block_length = b = cfg.block_length
        if self.chunk % b or self.block_size % b:
            raise ValueError(f"generation by diffusion over blocks: prefill_chunk_tokens={self.chunk} and the pool's "
                             f"block_size={self.block_size} must be multiples of block_length={b} (a chunk and a page "
                             "end on a whole block)")
        self.transfer = b // cfg.denoising_steps  # positions a denoising pass unmasks under the static rule
        self.dynamic = cfg.remasking == "low_confidence_dynamic"
        self.threshold = float(cfg.confidence_threshold)
        self.mask_id = int(cfg.mask_token_id)
        self.dims = cfg.attention_dims()
        self.n_layers = cfg.num_hidden_layers
        if self.chunk:
            self.fixed_mixed_shape = (1, self.chunk, self.max_batch_size)
        self.quant_cfg = None

    def blocks_a_launch(self, passes: int) -> int:
        """The most blocks one row can touch in ``passes`` passes: the one it is at (one pass may commit it), and
        one more for every ``least`` passes after that (a fresh block takes at least a denoising and a commit pass;
        under the static rule ``denoising_steps`` + 1). What the engine reserves pages for ahead of a launch."""
        least = 2 if self.dynamic else self.config.denoising_steps + 1
        return 1 + -(-(passes - 1) // least)

    def init_pool(self, num_blocks: int, block_size: int, dtype, quant=None) -> WindowKVPool:
        return init_window_pool(self.n_layers, 0, num_blocks, 1, block_size, self.n_kv * self.head_dim,
                                len(self.STATS), dtype)

    # ------------------------------------------------------------------ the stack
    def _hidden(self, params, pool, ids, tables, start, q_lens):
        """ids [R, T] fed from position ``start`` [R] (``q_lens`` [R] of them real) -> (hidden states [R, T, hidden]
        after the final norm, pool): every layer under the block mask, K and V of the fed positions written."""
        m = params["model"]
        cfg, b = self.config, self.block_length
        positions = start[:, None] + jnp.arange(ids.shape[1])[None, :]
        valid = jnp.arange(ids.shape[1])[None, :] < q_lens[:, None]
        with jax.named_scope("embed"):
            h = m["embed_tokens"]["embedding"][ids].astype(self.dtype)

        def layer(carry, scanned):
            h, kv, counted = carry
            lp, li = scanned
            attn = lp["self_attn"]
            with jax.named_scope("attn_norm"):
                x = _rms(h, lp["input_layernorm"]["scale"], self.eps)
            q, k, v = W.project_qkv(attn, x, positions, self.dims, W.GQA_BLOCK, self.eps)
            with jax.named_scope("kv_write"):
                kv = write_kv_block(PagedKVPool(kv=kv), k, v, tables, start, li).kv
            if self.use_paged_kernel:
                with jax.named_scope("paged_attn"):
                    o = ragged_paged_run_attention(q, kv, tables, start, q_lens, li, block=b)
            else:
                with jax.named_scope("attn_gather"):
                    o = self._gathered(q, kv, li, tables, positions)
            with jax.named_scope("o_proj"):
                h = h + o.reshape(h.shape[:2] + (-1,)) @ attn["o_proj"]["kernel"].astype(h.dtype)
            with jax.named_scope("mlp_norm"):
                x = _rms(h, lp["post_attention_layernorm"]["scale"], self.eps)
            y, chosen = sparse_mlp(lp["mlp"], x, cfg, live=valid.reshape(-1))
            with jax.named_scope("router"):
                first, count = cfg.experts_held
                per_expert = held_counts(jnp.where(valid.reshape(-1, 1), chosen, -1), first, count)
                counted = counted + jnp.stack([per_expert.sum(), valid.sum() * chosen.shape[-1], per_expert.max()])
            return (h + y, kv, counted), None

        init = (h, pool.kv, jnp.zeros((3,), jnp.int32))
        (h, kv, counted), _ = jax.lax.scan(layer, init, (m["layers"], jnp.arange(self.n_layers, dtype=jnp.int32)))
        seen = jnp.where(q_lens > 0, start + q_lens, 0).sum()
        pool = self._count(dataclasses.replace(pool, kv=kv), attn_kv_visible=self.n_layers * seen,
                           **dict(zip(EXPERT_STATS, counted)))
        with jax.named_scope("final_norm"):
            return _rms(h, m["norm"]["scale"], self.eps), pool

    def _gathered(self, q, kv, li, table, positions):
        """The XLA path (no kernel): the table's blocks gathered and attended under the block mask."""
        b, _ = positions.shape
        k, v = (kv[li, side, table].reshape(b, -1, self.n_kv, self.head_dim) for side in (0, 1))
        k_pos = jnp.broadcast_to(jnp.arange(k.shape[1])[None, :], (b, k.shape[1]))
        return W.attend(q, k, v, W.window_mask(positions, k_pos, None, self.block_length))

    def _logits(self, params, h):
        with jax.named_scope("lm_head"):
            return h @ params["lm_head"]["kernel"].astype(self.dtype)

    # ------------------------------------------------------------------ a pass
    def _pass(self, params, pool, tables, state):
        """One pass of every live row over its block. ``state`` is (tokens [R, B], masked [R, B], start [R] the
        block's first position, fixed [R] its leading positions that are the prompt's, live [R], left [R] tokens the
        request is still owed, denoise_n / commit_n [R] passes taken). -> (pool, state', (tokens, valid) [R, B]: the
        block a committing row hands on and which of its positions are emitted)."""
        tok, msk, start, fixed, live, left, n_den, n_com = state
        bk = self.block_length
        any_masked = msk.any(-1)
        denoise, commit = live & any_masked, live & ~any_masked
        with jax.named_scope("denoise"):
            h, pool = self._hidden(params, pool, jnp.where(msk, self.mask_id, tok), tables, start,
                                   live.astype(jnp.int32) * bk)
            logits = self._logits(params, h)
            with jax.named_scope("confidence"):
                # the mask id is never a choice: its logit is left out of the argmax and of the normaliser
                lg = logits.astype(jnp.float32)
                lg = jnp.where(jnp.arange(lg.shape[-1]) == self.mask_id, -jnp.inf, lg)
                x0 = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                conf = jnp.exp(jnp.max(lg, axis=-1) - jax.nn.logsumexp(lg, axis=-1))
            with jax.named_scope("unmask"):
                c = jnp.where(msk, conf, -1.0)
                idx = jnp.arange(bk)
                # a masked position's rank by confidence, the earlier position first among equals
                ahead = (c[:, None, :] > c[:, :, None]) | ((c[:, None, :] == c[:, :, None]) & (idx[None, :] < idx[:, None]))
                take = msk & (ahead.sum(-1) < self.transfer)
                if self.dynamic:
                    take |= msk & (conf > self.threshold)
                take &= denoise[:, None]
                tok = jnp.where(take, x0, tok)
                msk = msk & ~take
        with jax.named_scope("commit"):
            new = idx[None, :] >= fixed[:, None]
            within = idx[None, :] - fixed[:, None] < left[:, None]
            is_eos = (tok[..., None] == self.eos_arr).any(-1) & new & within
            valid = commit[:, None] & new & within & (jnp.cumsum(is_eos, -1) - is_eos == 0)  # nothing past an EOS
            left = left - valid.sum(-1)
            finished = commit & ((valid & is_eos).any(-1) | (left <= 0))
            advance = commit & ~finished
            pool = self._count(pool, denoise_passes=denoise.sum(), commit_passes=commit.sum(),
                               tokens_unmasked=take.sum(), tokens_emitted=valid.sum(),
                               tokens_discarded=(commit[:, None] & new & ~valid).sum())
            state = (tok, msk | advance[:, None], jnp.where(advance, start + bk, start), jnp.where(commit, 0, fixed),
                     live & ~finished, left, n_den + denoise, n_com + commit)
        return pool, state, (tok, valid)

    @staticmethod
    def _results(tokens, valid, state):
        """One int32 buffer of what the host reads back (``unpack_results``)."""
        tok, msk, _, _, _, _, n_den, n_com = state
        return jnp.concatenate([x.astype(jnp.int32).reshape(-1) for x in (tokens, valid, tok, msk, n_den, n_com)])

    # ------------------------------------------------------------------ entry points
    def _decode_impl(self, params, pool, packed, counts, lora, layout):
        f = unpack(packed, layout)
        return self._decode_body(params, pool, f["block_tokens"], f["block_masked"], f["block_tables"], f["start"],
                                 f["fixed"], f["done0"], f["remaining"], counts)

    def _decode_body(self, params, pool, tokens, masked, tables, start, fixed, done0, remaining, counts):
        """``decode_steps`` passes of every live slot. tokens / masked [slots, B]: the block each slot is at;
        ``start`` its first position, ``fixed`` how many of its leading positions are the prompt's, ``remaining``
        the tokens the request is still owed. -> (packed results, counts, pool)."""
        pool = dataclasses.replace(pool, stats=jnp.zeros_like(pool.stats))
        zeros = jnp.zeros_like(start)

        def one(carry, _):
            pool, state = carry
            pool, state, out = self._pass(params, pool, tables, state)
            return (pool, state), out

        init = (pool, (tokens, masked, start, fixed, ~done0, remaining, zeros, zeros))
        (pool, state), (toks, valid) = jax.lax.scan(one, init, None, length=self.decode_steps)
        return self._results(toks, valid, state), counts, pool

    def _mixed_flat_impl(self, params, pool, packed, counts, lora, layout):
        f = unpack(packed, layout)
        return self._mixed_flat_body(
            params, pool, f["chunk_ids"], f["chunk_tables"], f["chunk_qlens"], f["chunk_start"], f["dec_tokens"],
            f["dec_masked"], f["dec_tables"], f["dec_start"], f["dec_fixed"], f["dec_live"], f["dec_remaining"], counts)

    def _mixed_flat_body(self, params, pool, chunk_ids, chunk_tables, chunk_qlens, chunk_start, dec_tokens,
                         dec_masked, dec_tables, dec_start, dec_fixed, dec_live, dec_remaining, counts):
        """One mixed step: chunk rows feed whole blocks of their prompts (K and V written, nothing sampled: the
        first generated block starts from masks, not from the prompt's last logits), decode rows take one pass."""
        pool = dataclasses.replace(pool, stats=jnp.zeros_like(pool.stats))
        _, pool = self._hidden(params, pool, chunk_ids, chunk_tables, chunk_start, chunk_qlens)
        zeros = jnp.zeros_like(dec_start)
        state = (dec_tokens, dec_masked, dec_start, dec_fixed, dec_live, dec_remaining, zeros, zeros)
        pool, state, (toks, valid) = self._pass(params, pool, dec_tables, state)
        return self._results(toks[None], valid[None], state), counts, pool
