"""Paged inference runtime: block manager accounting, paged-vs-dense decode
parity, continuous batching with staggered arrivals, preemption recovery."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddlenlp_tpu.experimental import BlockManager, InferenceEngine, SamplingParams
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM


def whole_pool(pk, pv, layer=0, layers=1, seed=0):
    """K and V planes of one layer, [nb, K, bs, H] as the references read them,
    laid into the pool the program keeps: [L, 2, nb, bs, K*H] token-major rows,
    every other layer filled with different values."""
    nb, K, bs, H = pk.shape
    rows = lambda plane: plane.transpose(0, 2, 1, 3).reshape(nb, bs, K * H)
    if jnp.issubdtype(pk.dtype, jnp.floating):
        pool = jax.random.normal(jax.random.PRNGKey(seed), (layers, 2, nb, bs, K * H)).astype(pk.dtype)
    else:
        pool = jax.random.randint(jax.random.PRNGKey(seed), (layers, 2, nb, bs, K * H), -127, 128
                                  ).astype(pk.dtype)
    return pool.at[layer, 0].set(rows(pk)).at[layer, 1].set(rows(pv))


def whole_scale(ks, vs, layer=0, layers=1):
    """Per-token-per-head scales [nb, K, bs, 1] laid into [L, 2, nb, bs, K]."""
    rows = lambda plane: plane[..., 0].transpose(0, 2, 1)
    scale = jnp.full((layers, 2) + rows(ks).shape, 7.0, jnp.float32)
    return scale.at[layer, 0].set(rows(ks)).at[layer, 1].set(rows(vs))


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=256,
                      eos_token_id=None, pad_token_id=0, use_scan_layers=True)
    return LlamaForCausalLM.from_config(cfg, seed=0)


class TestBlockManager:
    def test_alloc_free_cycle(self):
        mgr = BlockManager(num_blocks=17, block_size=4, max_blocks_per_seq=8)
        assert mgr.num_free == 16  # block 0 is the sentinel
        mgr.allocate(1, 10)  # 3 blocks
        assert mgr.num_free == 13
        mgr.extend(1, 3)  # 13 tokens -> 4 blocks
        assert mgr.num_free == 12
        mgr.free_seq(1)
        assert mgr.num_free == 16

    def test_oom_returns_none_on_extend(self):
        mgr = BlockManager(num_blocks=3, block_size=4, max_blocks_per_seq=8)
        mgr.allocate(1, 8)  # uses both free blocks
        assert mgr.extend(1, 1) is None

    def test_table_array_sentinel_padding(self):
        mgr = BlockManager(num_blocks=9, block_size=4, max_blocks_per_seq=6)
        mgr.allocate(5, 6)
        t = mgr.table_array(5)
        assert t.shape == (6,)
        assert (t[2:] == 0).all() and (t[:2] > 0).all()


class TestPagedParity:
    def test_greedy_matches_generate(self, model):
        """Engine greedy decode == the training-side generate() greedy decode."""
        prompt = [5, 6, 7, 8, 9]
        ref, _ = model.generate(jnp.asarray([prompt], jnp.int32), max_new_tokens=8,
                                do_sample=False, eos_token_id=None)
        eng = InferenceEngine(model, max_batch_size=2, block_size=4, num_blocks=64, max_blocks_per_seq=16)
        out = eng.generate([prompt], SamplingParams(max_new_tokens=8))
        np.testing.assert_array_equal(np.asarray(ref[0]), np.asarray(out[0]))

    def test_batch_isolation(self, model):
        """Two sequences decoded together == each decoded alone."""
        p1, p2 = [5, 6, 7], [40, 41, 42, 43, 44, 45]
        eng = InferenceEngine(model, max_batch_size=4, block_size=4, num_blocks=64, max_blocks_per_seq=16)
        together = eng.generate([p1, p2], SamplingParams(max_new_tokens=6))
        eng1 = InferenceEngine(model, max_batch_size=1, block_size=4, num_blocks=64, max_blocks_per_seq=16)
        alone1 = eng1.generate([p1], SamplingParams(max_new_tokens=6))[0]
        eng2 = InferenceEngine(model, max_batch_size=1, block_size=4, num_blocks=64, max_blocks_per_seq=16)
        alone2 = eng2.generate([p2], SamplingParams(max_new_tokens=6))[0]
        np.testing.assert_array_equal(together[0], alone1)
        np.testing.assert_array_equal(together[1], alone2)

    def test_staggered_arrivals(self, model):
        """A request arriving mid-decode (continuous batching) must not disturb
        the running request's tokens."""
        p1, p2 = [5, 6, 7, 8], [30, 31, 32]
        ref_eng = InferenceEngine(model, max_batch_size=1, block_size=4, num_blocks=64, max_blocks_per_seq=16)
        ref1 = ref_eng.generate([p1], SamplingParams(max_new_tokens=8))[0]

        eng = InferenceEngine(model, max_batch_size=4, block_size=4, num_blocks=64, max_blocks_per_seq=16)
        eng.add_request(p1, SamplingParams(max_new_tokens=8))
        done = []
        done += eng.step()  # prefill p1 + first decode
        done += eng.step()
        eng.add_request(p2, SamplingParams(max_new_tokens=4))  # arrives mid-flight
        while eng.has_work():
            done += eng.step()
        by_id = {r.req_id: r.output_ids for r in done}
        np.testing.assert_array_equal(by_id[0], ref1)
        assert len(by_id[1]) == 4

    def test_sampling_seeded(self, model):
        eng = InferenceEngine(model, max_batch_size=2, block_size=4, num_blocks=64, max_blocks_per_seq=16)
        a = eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=6, do_sample=True, top_p=0.9, seed=7))
        eng2 = InferenceEngine(model, max_batch_size=2, block_size=4, num_blocks=64, max_blocks_per_seq=16)
        b = eng2.generate([[5, 6, 7]], SamplingParams(max_new_tokens=6, do_sample=True, top_p=0.9, seed=7))
        np.testing.assert_array_equal(a[0], b[0])

    def test_streaming_callback(self, model):
        eng = InferenceEngine(model, max_batch_size=1, block_size=4, num_blocks=64, max_blocks_per_seq=16)
        got = []
        eng.add_request([5, 6, 7], SamplingParams(max_new_tokens=5),
                        stream_cb=lambda tok, done: got.append((tok, done)))
        while eng.has_work():
            eng.step()
        assert len(got) == 5
        assert got[-1][1] is True and not any(d for _, d in got[:-1])


class TestDeviceSampling:
    def test_sample_tokens_top_k1_is_greedy(self):
        from paddlenlp_tpu.experimental.inference_model import sample_tokens

        logits = jnp.asarray(np.random.default_rng(0).normal(size=(3, 16)), jnp.float32)
        kw = dict(positions=jnp.zeros(3, jnp.int32), seeds=jnp.arange(3, dtype=jnp.int32),
                  temperature=jnp.ones(3), top_k=jnp.full(3, 1, jnp.int32), top_p=jnp.ones(3),
                  do_sample=jnp.ones(3, bool))
        toks = sample_tokens(logits, **kw)
        np.testing.assert_array_equal(np.asarray(toks), np.asarray(jnp.argmax(logits, -1)))

    def test_sample_tokens_penalties_shift_argmax(self):
        from paddlenlp_tpu.experimental.inference_model import sample_tokens

        logits = jnp.asarray([[2.0, 1.9, 0.0, -1.0]], jnp.float32)
        counts = jnp.asarray([[3, 0, 0, 0]], jnp.int32)  # token 0 heavily repeated
        kw = dict(positions=jnp.zeros(1, jnp.int32), seeds=jnp.zeros(1, jnp.int32),
                  temperature=jnp.ones(1), top_k=jnp.zeros(1, jnp.int32), top_p=jnp.ones(1),
                  do_sample=jnp.zeros(1, bool), counts=counts,
                  repetition_penalty=jnp.asarray([2.0]), presence_penalty=jnp.asarray([0.5]),
                  frequency_penalty=jnp.asarray([0.1]))
        tok = sample_tokens(logits, **kw)
        assert int(tok[0]) == 1  # penalized 2.0/2 - 0.5 - 0.3 < 1.9

    def test_engine_repetition_penalty_changes_greedy(self, model):
        prompt = [5, 6, 5, 6, 5, 6]
        eng = InferenceEngine(model, max_batch_size=1, block_size=4, num_blocks=64, max_blocks_per_seq=16)
        plain = eng.generate([prompt], SamplingParams(max_new_tokens=8))
        eng2 = InferenceEngine(model, max_batch_size=1, block_size=4, num_blocks=64, max_blocks_per_seq=16)
        pen = eng2.generate([prompt], SamplingParams(max_new_tokens=8, repetition_penalty=5.0,
                                                     presence_penalty=1.0))
        assert len(pen[0]) == 8
        # a strong penalty must perturb the greedy continuation of a looping prompt
        assert plain[0] != pen[0], (plain, pen)
        # and the penalized run must not emit the same token twice in a row
        assert all(a != b for a, b in zip(pen[0], pen[0][1:])), pen[0]

    def test_multistep_single_host_iteration(self, model):
        """decode_steps=8 finishes an 8-token request in one engine.step()."""
        eng = InferenceEngine(model, max_batch_size=2, block_size=4, num_blocks=64,
                              max_blocks_per_seq=16, decode_steps=8)
        eng.add_request([5, 6, 7], SamplingParams(max_new_tokens=8))
        finished = eng.step()
        assert len(finished) == 1 and len(finished[0].output_ids) == 8
        assert not eng.has_work()


class TestPagedKernel:
    def test_kernel_matches_gather_path(self):
        from paddlenlp_tpu.ops.pallas.paged_attention import ragged_paged_attention

        rng = np.random.default_rng(1)
        B, N, K, H, nb, bs, mb = 2, 4, 2, 64, 12, 8, 4
        q = jnp.asarray(rng.standard_normal((B, N, H)), jnp.float32)
        pk = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        pv = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        tables = jnp.asarray(rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32)
        ctx = jnp.asarray([7, 22], jnp.int32)
        out = ragged_paged_attention(q[:, None], whole_pool(pk, pv), tables, ctx,
                                     jnp.ones((B,), jnp.int32), 0, interpret=True)[:, 0]

        def flat(pool):  # [nb,K,bs,H] gathered -> [B, mb*bs, K, H]
            return pool[tables].transpose(0, 1, 3, 2, 4).reshape(B, mb * bs, K, H)

        k_all = jnp.repeat(flat(pk), N // K, axis=2)
        v_all = jnp.repeat(flat(pv), N // K, axis=2)
        s = jnp.einsum("bnh,bsnh->bns", q, k_all) * H**-0.5
        mask = jnp.arange(mb * bs)[None, :] <= ctx[:, None]
        ref = jnp.einsum("bns,bsnh->bnh",
                         jax.nn.softmax(jnp.where(mask[:, None, :], s, -1e30), axis=-1), v_all)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    def test_engine_parity_with_kernel(self, model):
        """Whole-engine greedy decode through the Pallas paged kernel (interpret)
        must equal the XLA gather path."""
        prompts = [[5, 6, 7, 8, 9], [40, 41, 42]]
        ref_eng = InferenceEngine(model, max_batch_size=2, block_size=4, num_blocks=64,
                                  max_blocks_per_seq=16)
        want = ref_eng.generate(prompts, SamplingParams(max_new_tokens=6))
        eng = InferenceEngine(model, max_batch_size=2, block_size=4, num_blocks=64,
                              max_blocks_per_seq=16)
        eng.infer.use_paged_kernel = True  # interpret mode on CPU
        got = eng.generate(prompts, SamplingParams(max_new_tokens=6))
        np.testing.assert_array_equal(want[0], got[0])
        np.testing.assert_array_equal(want[1], got[1])


class TestRaggedKernel:
    def _ref(self, q, pk, pv, tables, q_start, q_lens):
        """XLA reference: gather + per-row causal mask + zeroed dead rows."""
        B, T, N, H = q.shape
        nb, K, bs, _ = pk.shape
        mb = tables.shape[1]

        def flat(pool):
            return pool[tables].transpose(0, 1, 3, 2, 4).reshape(B, mb * bs, K, H)

        k_all = jnp.repeat(flat(pk), N // K, axis=2)
        v_all = jnp.repeat(flat(pv), N // K, axis=2)
        s = jnp.einsum("btnh,bsnh->bnts", q, k_all) * H**-0.5
        q_pos = q_start[:, None] + jnp.arange(T)[None, :]
        mask = jnp.arange(mb * bs)[None, None, :] <= q_pos[:, :, None]
        out = jnp.einsum("bnts,bsnh->btnh",
                         jax.nn.softmax(jnp.where(mask[:, None], s, -1e30), axis=-1),
                         v_all)
        live = jnp.arange(T)[None, :, None, None] < q_lens[:, None, None, None]
        return jnp.where(live, out, 0.0)

    def test_mixed_prefill_decode_rows(self):
        """One launch over a ragged batch: a mid-prompt chunk, a decode row
        (q_lens=1) and an inactive row (q_lens=0) against the same pool."""
        from paddlenlp_tpu.ops.pallas.paged_attention import ragged_paged_attention

        rng = np.random.default_rng(2)
        B, T, N, K, H, nb, bs, mb = 3, 8, 4, 2, 64, 16, 8, 5
        q = jnp.asarray(rng.standard_normal((B, T, N, H)), jnp.float32)
        pk = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        pv = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        tables = jnp.asarray(rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb),
                             jnp.int32)
        q_start = jnp.asarray([9, 22, 0], jnp.int32)  # chunk @9, decode @22, dead
        q_lens = jnp.asarray([8, 1, 0], jnp.int32)
        out = ragged_paged_attention(q, whole_pool(pk, pv), tables, q_start, q_lens, 0,
                                     interpret=True)
        ref = self._ref(q, pk, pv, tables, q_start, q_lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        assert np.all(np.asarray(out)[2] == 0.0)  # dead row is exact zeros
        assert np.all(np.asarray(out)[1, 1:] == 0.0)  # decode row padding zeroed

    def test_chunk_boundary_on_block_boundary(self):
        """q_start on an exact block boundary: the first kv block of the chunk
        is fully visible, later in-chunk positions unmask one column at a time."""
        from paddlenlp_tpu.ops.pallas.paged_attention import ragged_paged_attention

        rng = np.random.default_rng(3)
        B, T, N, K, H, nb, bs, mb = 1, 8, 2, 2, 64, 10, 8, 4
        q = jnp.asarray(rng.standard_normal((B, T, N, H)), jnp.float32)
        pk = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        pv = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        tables = jnp.asarray([[3, 7, 1, 5]], jnp.int32)
        q_start = jnp.asarray([8], jnp.int32)  # exactly one full block prefilled
        q_lens = jnp.asarray([8], jnp.int32)
        out = ragged_paged_attention(q, whole_pool(pk, pv), tables, q_start, q_lens, 0,
                                     interpret=True)
        ref = self._ref(q, pk, pv, tables, q_start, q_lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    @pytest.mark.parametrize("layer", [0, 13, 27])
    def test_kernel_is_aimed_by_layer_index(self, layer):
        """The operand is the whole 28-layer pool, every layer holding other
        values: the kernel aimed at ``layer`` equals plain attention over that
        layer's K and V, and over no other layer's."""
        from paddlenlp_tpu.ops.pallas.paged_attention import ragged_paged_attention

        rng = np.random.default_rng(6)
        L, B, T, N, K, H, nb, bs, mb = 28, 3, 4, 4, 2, 16, 14, 4, 4
        q = jnp.asarray(rng.standard_normal((B, T, N, H)), jnp.float32)
        pool = jnp.asarray(rng.standard_normal((L, 2, nb, bs, K * H)), jnp.float32)
        tables = jnp.asarray(rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb),
                             jnp.int32)
        q_start = jnp.asarray([5, 11, 0], jnp.int32)
        q_lens = jnp.asarray([4, 1, 0], jnp.int32)
        planes = lambda l: [pool[l, p].reshape(nb, bs, K, H).transpose(0, 2, 1, 3) for p in (0, 1)]
        out = ragged_paged_attention(q, pool, tables, q_start, q_lens, layer, interpret=True)
        ref = self._ref(q, *planes(layer), tables, q_start, q_lens)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
        other = self._ref(q, *planes((layer + 1) % L), tables, q_start, q_lens)
        assert np.abs(np.asarray(out) - np.asarray(other)).max() > 1e-2

    def test_kernel_default_off_on_tpu_is_said(self, model, monkeypatch):
        """On a TPU the kernel is on by default; where the shape gate turns
        it off (head_dim 16 here) the model says so once at construction."""
        from paddlenlp_tpu.experimental.inference_model import PagedInferenceModel
        from paddlenlp_tpu.utils.log import logger

        said = []
        monkeypatch.setattr(logger, "warning_once", said.append)
        infer = PagedInferenceModel(model, block_size=8, num_blocks=16, max_blocks_per_seq=4)
        assert infer.use_paged_kernel is False and said == []  # off the TPU: nothing to say
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        infer = PagedInferenceModel(model, block_size=8, num_blocks=16, max_blocks_per_seq=4)
        assert infer.use_paged_kernel is False
        assert len(said) == 1 and "head_dim=16" in said[0] and "block_size=8" in said[0]

    @pytest.mark.parametrize("max_rows", [16, 8])
    def test_query_tiling_changes_nothing(self, monkeypatch, max_rows):
        """The query-row grid axis (the chip refuses a whole long prompt as one
        VMEM tile): 16 tokens x group 2 cut into 2 or 4 query tiles must give
        every row bit for bit what the single tile gives — a block skipped
        because it lies past a tile's last live row is exactly a fully masked
        one. Rows cover a chunk ending inside a tile, a decode row, a dead row
        and a chunk filling every tile."""
        from paddlenlp_tpu.ops.pallas import paged_attention as pa

        rng = np.random.default_rng(5)
        B, T, N, K, H, nb, bs, mb = 4, 16, 4, 2, 64, 24, 8, 5
        q = jnp.asarray(rng.standard_normal((B, T, N, H)), jnp.float32)
        pk = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        pv = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        tables = jnp.asarray(rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb),
                             jnp.int32)
        q_start = jnp.asarray([9, 22, 0, 3], jnp.int32)
        q_lens = jnp.asarray([11, 1, 0, 16], jnp.int32)
        assert pa._q_tile_tokens(T, N // K) == T  # default bound: one tile
        pool = whole_pool(pk, pv)
        whole = pa.ragged_paged_attention(q, pool, tables, q_start, q_lens, 0, interpret=True)
        monkeypatch.setattr(pa, "_MAX_Q_ROWS", max_rows)
        assert pa._q_tile_tokens(T, N // K) == max_rows // (N // K)
        tiled = pa.ragged_paged_attention(q, pool, tables, q_start, q_lens, 0, interpret=True)
        np.testing.assert_array_equal(np.asarray(tiled), np.asarray(whole))
        np.testing.assert_allclose(
            np.asarray(tiled), np.asarray(self._ref(q, pk, pv, tables, q_start, q_lens)),
            atol=2e-5)

    def test_decode_rows_match_reference(self):
        """``q_lens`` of ones is the classic paged decode kernel: one query
        token a sequence, at the position its context has reached."""
        from paddlenlp_tpu.ops.pallas.paged_attention import ragged_paged_attention

        rng = np.random.default_rng(4)
        B, N, K, H, nb, bs, mb = 2, 4, 2, 64, 12, 8, 4
        q = jnp.asarray(rng.standard_normal((B, 1, N, H)), jnp.float32)
        pk = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        pv = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        tables = jnp.asarray(rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb),
                             jnp.int32)
        ctx = jnp.asarray([7, 22], jnp.int32)
        ones = jnp.ones((B,), jnp.int32)
        out = ragged_paged_attention(q, whole_pool(pk, pv), tables, ctx, ones, 0, interpret=True)
        np.testing.assert_allclose(np.asarray(out),
                                   np.asarray(self._ref(q, pk, pv, tables, ctx, ones)), atol=2e-5)


class TestPoolWrite:
    @pytest.mark.parametrize("quant", [None, "int8"])
    def test_write_then_gather_round_trips_in_place(self, quant):
        """A ragged batch written at layer 2 of 4, across a block boundary,
        reads back through ``gather_kv``; every other layer, every block the
        batch does not own and every row it did not reach stay bit for bit."""
        from paddlenlp_tpu.experimental.paged_cache import (
            PagedKVPool, gather_kv, quantize_kv, write_kv_block)

        rng = np.random.default_rng(7)
        L, B, T, K, H, nb, bs, mb, layer = 4, 3, 6, 2, 8, 12, 4, 3, 2
        dtype = jnp.float32 if quant is None else jnp.int8
        kv = jnp.asarray(rng.integers(-100, 100, (L, 2, nb, bs, K * H)), dtype)
        scale = None if quant is None else jnp.asarray(
            rng.uniform(0.5, 2.0, (L, 2, nb, bs, K)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((B, T, K, H)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((B, T, K, H)), jnp.float32)
        tables = jnp.asarray([[3, 7, 1], [5, 2, 9], [0, 0, 0]], jnp.int32)  # row 2: padding
        start = jnp.asarray([2, 5, 0], jnp.int32)  # tokens 2..7 span blocks 3 and 7; 5..10 all three
        written = write_kv_block(PagedKVPool(kv, scale), k, v, tables, start, layer)
        new_kv, new_scale = written.kv, written.scale

        k_all, v_all = gather_kv(written, tables, layer, K)
        assert k_all.shape == (B, mb * bs, K, H)
        for b in (0, 1):
            span = slice(int(start[b]), int(start[b]) + T)
            for got, want in ((k_all, k), (v_all, v)):
                if quant:
                    q, s = quantize_kv(want[b], jnp.int8)
                    want_b = (q.astype(jnp.float32) * s).astype(jnp.bfloat16)
                else:
                    want_b = want[b]
                np.testing.assert_array_equal(np.asarray(got[b, span], np.float32),
                                              np.asarray(want_b, np.float32))

        touched = np.zeros((nb, bs), bool)
        for b in range(B):
            for t in range(T):
                pos = int(start[b]) + t
                touched[int(tables[b, pos // bs]), pos % bs] = True
        for before, after in ((kv, new_kv),) + (((scale, new_scale),) if quant else ()):
            before, after = np.asarray(before), np.asarray(after)
            others = [l for l in range(L) if l != layer]
            np.testing.assert_array_equal(after[others], before[others])
            np.testing.assert_array_equal(after[layer][:, ~touched], before[layer][:, ~touched])
            assert (after[layer][:, touched] != before[layer][:, touched]).any()


class TestPreemption:
    def test_preempt_and_recover(self, model):
        """Tiny pool forces preemption; the preempted request must still finish
        with identical output (recompute path)."""
        ref_eng = InferenceEngine(model, max_batch_size=2, block_size=4, num_blocks=128, max_blocks_per_seq=32)
        want = ref_eng.generate([[5, 6, 7], [40, 41, 42]], SamplingParams(max_new_tokens=10))

        # 9 usable blocks; two seqs decoding 10 tokens each will collide
        eng = InferenceEngine(model, max_batch_size=2, block_size=4, num_blocks=10, max_blocks_per_seq=32)
        got = eng.generate([[5, 6, 7], [40, 41, 42]], SamplingParams(max_new_tokens=10))
        np.testing.assert_array_equal(got[0], want[0])
        np.testing.assert_array_equal(got[1], want[1])


class TestQuantizedKVCache:
    def test_engine_parity_int8_and_fp8(self, model):
        """Quantized-cache greedy decode must stay close to the fp path
        (cosine > 0.99 on sampled logprob trajectories is
        approximated here by token-level agreement on short continuations +
        quantize/dequant cosine on the pool content)."""
        prompts = [[5, 6, 7, 8, 9], [40, 41, 42]]
        ref_eng = InferenceEngine(model, max_batch_size=2, block_size=8, num_blocks=64,
                                  max_blocks_per_seq=16)
        want = ref_eng.generate(prompts, SamplingParams(max_new_tokens=6))
        for quant in ("int8", "fp8"):
            eng = InferenceEngine(model, max_batch_size=2, block_size=8, num_blocks=64,
                                  max_blocks_per_seq=16, kv_cache_quant=quant)
            got = eng.generate(prompts, SamplingParams(max_new_tokens=6))
            assert len(got) == 2 and all(len(g) == 6 for g in got)
            # tiny random models have near-uniform logits; require agreement on
            # the first tokens (cache content identical at step 1) and finite IDs
            assert got[0][0] == want[0][0] and got[1][0] == want[1][0], (quant, got, want)

    def test_quantize_roundtrip_cosine(self):
        from paddlenlp_tpu.experimental.paged_cache import quantize_kv

        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(32, 2, 64)), jnp.float32)
        for qd in (jnp.int8, jnp.float8_e4m3fn):
            q, s = quantize_kv(x, qd)
            deq = q.astype(jnp.float32) * s
            num = float(jnp.sum(x * deq))
            den = float(jnp.linalg.norm(x) * jnp.linalg.norm(deq))
            assert num / den > 0.99, (qd, num / den)

    def test_pool_memory_halved(self, model):
        from paddlenlp_tpu.experimental.paged_cache import init_paged_pool

        fp = init_paged_pool(model.config, num_blocks=32, block_size=8, dtype=jnp.bfloat16)
        q8 = init_paged_pool(model.config, num_blocks=32, block_size=8, quant="int8")
        fp_bytes = fp.kv.size * fp.kv.dtype.itemsize
        q_bytes = q8.kv.size * q8.kv.dtype.itemsize + q8.scale.size * q8.scale.dtype.itemsize
        # int8 payload is half of bf16; fp32 per-token scales add 4/(2H) overhead
        # (this tiny model's H=16 -> 0.625x; real models H>=128 -> ~0.52x)
        assert q_bytes <= 0.63 * fp_bytes, (q_bytes, fp_bytes)

    def test_paged_kernel_dequant_matches_gather(self):
        from paddlenlp_tpu.experimental.paged_cache import quantize_kv
        from paddlenlp_tpu.ops.pallas.paged_attention import ragged_paged_attention

        rng = np.random.default_rng(3)
        B, N, K, H, nb, bs, mb = 2, 4, 2, 64, 12, 8, 4
        q = jnp.asarray(rng.standard_normal((B, N, H)), jnp.float32)
        pk = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        pv = jnp.asarray(rng.standard_normal((nb, K, bs, H)), jnp.float32)
        pk_q, pk_s = quantize_kv(pk, jnp.int8)
        pv_q, pv_s = quantize_kv(pv, jnp.int8)
        tables = jnp.asarray(rng.permutation(np.arange(1, nb))[: B * mb].reshape(B, mb), jnp.int32)
        ctx = jnp.asarray([7, 22], jnp.int32)
        # layer 1 of 3: the scale planes are addressed by layer like the pool
        out = ragged_paged_attention(q[:, None], whole_pool(pk_q, pv_q, 1, 3), tables, ctx,
                                     jnp.ones((B,), jnp.int32), 1, interpret=True,
                                     kv_scale=whole_scale(pk_s, pv_s, 1, 3))[:, 0]

        def flat(pool):
            return pool[tables].transpose(0, 1, 3, 2, 4).reshape(B, mb * bs, K, H)

        k_all = jnp.repeat(flat(pk_q.astype(jnp.float32) * pk_s), N // K, axis=2)
        v_all = jnp.repeat(flat(pv_q.astype(jnp.float32) * pv_s), N // K, axis=2)
        s = jnp.einsum("bnh,bsnh->bns", q, k_all) * H**-0.5
        mask = jnp.arange(mb * bs)[None, :] <= ctx[:, None]
        ref = jnp.einsum("bns,bsnh->bnh",
                         jax.nn.softmax(jnp.where(mask[:, None, :], s, -1e30), axis=-1), v_all)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


class TestQuantizedServing:
    """Scan-layout quantized weights through the paged engine (quantized
    serving must be reachable in the DEFAULT layout)."""

    def _engine_tokens(self, m, prompt, **kw):
        eng = InferenceEngine(m, max_batch_size=2, block_size=4, num_blocks=64,
                              max_blocks_per_seq=16, **kw)
        return eng.generate([prompt], SamplingParams(max_new_tokens=6))[0]

    @pytest.mark.parametrize("algo", ["wint8", "a8w8", "fp8"])
    def test_scan_quantized_engine_close_to_fp(self, model, algo):
        from paddlenlp_tpu.quantization import QuantizationConfig, QuantizedModel

        prompt = [5, 6, 7, 8, 9]
        ref = self._engine_tokens(model, prompt)
        qm = QuantizedModel(model, QuantizationConfig(weight_quantize_algo=algo))
        # stacked layout preserved: qweight leaves are [L, in, out]
        from paddlenlp_tpu.transformers.conversion_utils import flatten_params
        qflat = flatten_params(qm.params)
        assert any(p.endswith("/qweight") and v.ndim == 3 for p, v in qflat.items())
        got = self._engine_tokens(qm, prompt)
        # int8 on a tiny random model: most tokens agree with fp greedy
        agree = np.mean(np.asarray(ref) == np.asarray(got))
        assert agree >= 0.5, (ref, got)
        assert len(got) == len(ref)
