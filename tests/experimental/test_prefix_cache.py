"""Prefix KV-block caching: chained-hash full-block matching, refcounted
sharing, copy-on-write, LRU eviction under allocation pressure — and the
engine-level contract that cache-on output is token-identical to cache-off
while skipping the shared span's prefill.

Acceptance criteria covered here:
(a) cache-on vs cache-off outputs token-identical on a shared-prefix batch;
(b) a second request with a shared prefix skips >= the shared full-block token
    count of prefill (asserted via prefix_cache_cached_tokens_total);
(c) no KV-block leak after mixed finish/abort/preempt + eviction churn
    (free + idle-cached returns to total);
(d) eviction keeps admission behavior identical to the uncached allocator
    under pressure.
"""

import numpy as np
import pytest

from paddlenlp_tpu.experimental import BlockManager, InferenceEngine, SamplingParams
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM

BS = 4  # block size used throughout


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=256,
                      eos_token_id=None, pad_token_id=0, use_scan_layers=True)
    return LlamaForCausalLM.from_config(cfg, seed=0)


def mgr_conserved(mgr):
    """free + idle-cached + distinct-owned == total; no block in two states."""
    owned = {b for blocks in mgr.tables.values() for b in blocks}
    idle_cached = set(mgr._lru)
    assert 0 not in owned and 0 not in mgr.free and 0 not in idle_cached
    assert not (owned & set(mgr.free))
    assert not (idle_cached & set(mgr.free))
    assert not (owned & idle_cached)
    assert len(mgr.free) + len(idle_cached) + len(owned) == mgr.total_usable_blocks
    # every owned block carries a positive refcount; idle cached blocks none
    assert all(mgr.ref.get(b, 0) >= 1 for b in owned)
    assert all(b not in mgr.ref for b in idle_cached)


def _mgr(num_blocks=33, max_per_seq=16):
    return BlockManager(num_blocks=num_blocks, block_size=BS,
                        max_blocks_per_seq=max_per_seq, enable_prefix_cache=True)


class TestBlockManagerPrefixCache:
    def test_register_then_share_with_refcounts(self):
        mgr = _mgr()
        tokens = list(range(10, 22))  # 12 tokens = 3 full blocks
        shared, n_cached, new = mgr.allocate(1, 12, token_ids=tokens)
        assert (shared, n_cached) == ([], 0) and len(new) == 3  # cold cache
        seq1_blocks = list(mgr.tables[1])
        mgr.finish_seq_cached(1, tokens)
        assert mgr.num_cached_blocks == 3
        assert mgr.num_free == mgr.total_usable_blocks  # idle cached == capacity
        mgr_conserved(mgr)

        # identical prompt: full cover -> share all but the tail, COW the tail
        shared, n_cached, new = mgr.allocate(2, 12, token_ids=tokens)
        assert shared == seq1_blocks[:2]
        assert n_cached == 11  # one token left to prefill
        assert len(new) == 1
        pairs = mgr.drain_cow_pairs()
        assert pairs == [(seq1_blocks[2], new[0])]
        assert mgr.ref[seq1_blocks[0]] == 1 and mgr.ref[seq1_blocks[1]] == 1
        assert mgr.cache_hits == 1 and mgr.cached_tokens_total == 11
        mgr_conserved(mgr)

    def test_partial_and_divergent_match(self):
        mgr = _mgr()
        tokens = list(range(10, 22))
        mgr.allocate(1, 12, token_ids=tokens)
        mgr.finish_seq_cached(1, tokens)

        longer = tokens[:8] + [90, 91, 92, 93, 94]  # first 2 blocks shared
        shared, n_cached, new = mgr.allocate(2, len(longer), token_ids=longer)
        assert len(shared) == 2 and n_cached == 8
        assert not mgr.drain_cow_pairs()  # suffix starts in a fresh block

        divergent = [50] + tokens[1:]  # first block differs -> chain dead at 0
        shared, n_cached, _ = mgr.allocate(3, 12, token_ids=divergent)
        assert shared == [] and n_cached == 0
        mgr_conserved(mgr)

    def test_shrink_and_free_are_refcount_correct(self):
        mgr = _mgr()
        tokens = list(range(10, 22))
        mgr.allocate(1, 12, token_ids=tokens)
        mgr.finish_seq_cached(1, tokens)
        shared, _, new = mgr.allocate(2, 12, token_ids=tokens)
        mgr.drain_cow_pairs()
        # drop the private COW block: it must land on the free list
        mgr.shrink(2, 5)
        assert new[0] in mgr.free
        # drop a SHARED cached block: back to the idle (evictable) list
        mgr.shrink(2, 3)
        assert shared[1] in mgr._lru and shared[1] not in mgr.free
        mgr.free_seq(2)  # abort-style release: nothing unregistered
        assert mgr.num_cached_blocks == 3
        assert mgr.num_free == mgr.total_usable_blocks
        mgr_conserved(mgr)

    def test_lru_eviction_only_under_pressure(self):
        mgr = _mgr(num_blocks=7, max_per_seq=8)  # 6 usable
        a, b = list(range(10, 18)), list(range(30, 38))  # 2 blocks each
        mgr.allocate(1, 8, token_ids=a)
        mgr.finish_seq_cached(1, a)
        mgr.allocate(2, 8, token_ids=b)
        mgr.finish_seq_cached(2, b)
        assert mgr.num_cached_blocks == 4 and mgr.evictions == 0
        # idle cached blocks ARE capacity: a 24-token request still fits
        assert mgr.can_allocate(24)
        mgr.allocate(3, 24, token_ids=list(range(60, 84)))
        assert mgr.evictions == 4  # both cached prefixes recycled, LRU first
        assert mgr.num_cached_blocks == 0
        mgr_conserved(mgr)

    def test_admission_parity_with_uncached_allocator(self):
        """(d) a full cache never rejects an allocation the uncached allocator
        would have accepted."""
        cached = _mgr(num_blocks=9, max_per_seq=8)  # 8 usable
        plain = BlockManager(num_blocks=9, block_size=BS, max_blocks_per_seq=8)
        # fill the cache with two finished prompts (all 8 blocks cached, idle)
        for sid, lo in ((1, 10), (2, 40)):
            cached.allocate(sid, 16, token_ids=list(range(lo, lo + 16)))
            cached.finish_seq_cached(sid, list(range(lo, lo + 16)))
        assert cached.num_cached_blocks == 8
        for n in range(1, 40):
            assert cached.can_allocate(n) == plain.can_allocate(n), n
        # and the actual allocation succeeds by evicting
        cached.allocate(3, 32, token_ids=list(range(70, 102)))
        plain.allocate(3, 32)
        assert cached.num_free == plain.num_free
        mgr_conserved(cached)

    def test_idle_matched_blocks_not_double_counted(self):
        """A matched idle block can't be both 'no fresh capacity needed' and
        'evictable free capacity': can_admit must refuse exactly what
        allocate cannot satisfy (the uncached allocator would also refuse)."""
        mgr = _mgr(num_blocks=5, max_per_seq=8)  # 4 usable
        mgr.allocate(1, 4)  # one block privately held
        toks = list(range(10, 22))  # 3 full blocks
        mgr.allocate(2, 12, token_ids=toks)
        mgr.finish_seq_cached(2, toks)  # 3 idle cached; free list empty
        long = toks + list(range(90, 95))  # needs 5 blocks, matches the 3 cached
        assert not mgr.can_admit(len(long), token_ids=long)
        with pytest.raises(RuntimeError):
            mgr.allocate(3, len(long), token_ids=long)
        mgr_conserved(mgr)
        # uncached twin agrees: 4 usable - 1 held < 5 needed
        plain = BlockManager(num_blocks=5, block_size=BS, max_blocks_per_seq=8)
        plain.allocate(1, 4)
        assert not plain.can_allocate(len(long))

    def test_clear_prefix_cache_blocks_stale_registration(self):
        """A sequence allocated BEFORE clear_prefix_cache() holds KV computed
        under superseded params: it must release without re-registering, or
        the next match would serve stale KV the clear was meant to drop."""
        mgr = _mgr()
        tokens = list(range(10, 22))
        mgr.allocate(1, 12, token_ids=tokens)  # in flight across the clear
        mgr.clear_prefix_cache()
        mgr.finish_seq_cached(1, tokens)
        assert mgr.num_cached_blocks == 0
        assert mgr.match_prefix(tokens, 12) == ([], 0, None)
        assert mgr.num_free == mgr.total_usable_blocks
        mgr_conserved(mgr)
        # a post-clear sequence registers normally into the fresh index
        mgr.allocate(2, 12, token_ids=tokens)
        mgr.finish_seq_cached(2, tokens)
        assert mgr.num_cached_blocks == 3
        mgr_conserved(mgr)

    def test_copy_blocks_pads_without_corruption(self):
        """copy_blocks pads the pair list to a power of two with (0, 0)
        sentinel self-copies (bounded retraces): real copies land, block 0
        stays zero, untouched blocks stay put."""
        import jax.numpy as jnp

        from paddlenlp_tpu.experimental.paged_cache import PagedKVPool, copy_blocks

        kv = jnp.arange(2 * 2 * 6 * BS * 2, dtype=jnp.float32).reshape(2, 2, 6, BS, 2)
        kv = kv.at[:, :, 0].set(0.0)  # zero sentinel
        before = np.asarray(kv)
        pool = copy_blocks(PagedKVPool(kv=kv), [(1, 4), (2, 5), (3, 1)])  # 3 -> pads to 4
        after = np.asarray(pool.kv)
        np.testing.assert_array_equal(after[:, :, 4], before[:, :, 1])
        np.testing.assert_array_equal(after[:, :, 5], before[:, :, 2])
        np.testing.assert_array_equal(after[:, :, 1], before[:, :, 3])
        np.testing.assert_array_equal(after[:, :, 0], 0.0)
        np.testing.assert_array_equal(after[:, :, 2], before[:, :, 2])
        np.testing.assert_array_equal(after[:, :, 3], before[:, :, 3])

    def test_mixed_churn_no_leak(self):
        """(c) randomized finish-cached / abort / shrink / eviction churn
        conserves every block."""
        rng = np.random.default_rng(0)
        mgr = _mgr(num_blocks=17, max_per_seq=8)
        prompts = [list(range(lo, lo + 12)) for lo in (10, 10, 30, 50)]  # dup on purpose
        live = {}
        next_id = 0
        for _ in range(400):
            op = rng.choice(["alloc", "finish", "abort", "shrink", "extend"])
            if op == "alloc":
                toks = prompts[int(rng.integers(len(prompts)))]
                if mgr.can_admit(len(toks), token_ids=toks):
                    mgr.allocate(next_id, len(toks), token_ids=toks)
                    mgr.drain_cow_pairs()
                    live[next_id] = toks
                    next_id += 1
            elif op == "finish" and live:
                sid = int(rng.choice(list(live)))
                mgr.finish_seq_cached(sid, live.pop(sid))
            elif op == "abort" and live:
                sid = int(rng.choice(list(live)))
                mgr.free_seq(sid)
                del live[sid]
            elif op == "shrink" and live:
                sid = int(rng.choice(list(live)))
                mgr.shrink(sid, int(rng.integers(1, mgr.lengths[sid] + 1)))
            elif op == "extend" and live:
                sid = int(rng.choice(list(live)))
                mgr.extend(sid, int(rng.integers(1, 6)))
            mgr_conserved(mgr)
        for sid in list(live):
            mgr.free_seq(sid)
        # free + cached count returns to total
        assert len(mgr.free) + len(mgr._lru) == mgr.total_usable_blocks


def _engine(model, cache: bool, **kw):
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("block_size", BS)
    kw.setdefault("num_blocks", 64)
    kw.setdefault("max_blocks_per_seq", 16)
    return InferenceEngine(model, enable_prefix_cache=cache, **kw)


# jit compiles dominate this suite's wall clock, so the standard-pool engines
# are module-scoped and shared; each test works in a DISJOINT token range, and
# the content-addressed cache keeps the ranges from ever colliding
@pytest.fixture(scope="module")
def eng_on(model):
    return _engine(model, cache=True)


@pytest.fixture(scope="module")
def eng_off(model):
    return _engine(model, cache=False)


PREFIX = [5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15, 16, 17, 18, 19, 20]  # 4 full blocks


class TestEnginePrefixCache:
    def test_cache_on_off_token_identical_shared_prefix_batch(self, eng_on, eng_off):
        """(a) greedy + seeded sampling, warm cache vs no cache: identical."""
        first = [PREFIX + [60, 61]]
        batch = [PREFIX + [70, 71, 72],        # 4 cached blocks after warmup
                 PREFIX[:8] + [80, 81],        # 2 cached blocks
                 list(PREFIX)]                 # exact repeat -> COW tail
        samp = SamplingParams(max_new_tokens=8)
        samp_s = SamplingParams(max_new_tokens=8, do_sample=True, top_p=0.9, seed=7)

        warm_on = eng_on.generate(first, samp)
        got = eng_on.generate(batch, samp)
        got_s = eng_on.generate([PREFIX + [33]], samp_s)
        assert eng_on.mgr.cache_hits >= 3
        assert eng_on.mgr.cached_tokens_total >= 16 + 8 + 15

        warm_off = eng_off.generate(first, samp)
        want = eng_off.generate(batch, samp)
        want_s = eng_off.generate([PREFIX + [33]], samp_s)
        assert eng_off.mgr.cached_tokens_total == 0
        np.testing.assert_array_equal(warm_on[0], warm_off[0])
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)
        np.testing.assert_array_equal(got_s[0], want_s[0])

    def test_prefill_skip_counted_via_metric(self, eng_on):
        """(b) the shared full-block span lands in
        paddlenlp_serving_prefix_cache_cached_tokens_total."""
        from paddlenlp_tpu.serving.engine_loop import ServingMetrics
        from paddlenlp_tpu.serving.metrics import MetricsRegistry

        p2 = list(range(21, 37))  # 4 full blocks, disjoint from PREFIX
        registry = MetricsRegistry()
        metrics = ServingMetrics(eng_on, registry=registry)

        def run(prompts):
            for p in prompts:
                eng_on.add_request(p, SamplingParams(max_new_tokens=4))
            while eng_on.has_work():
                eng_on.step()
                metrics.on_step(eng_on.stats(), 0)  # what EngineLoop does per step

        run([p2 + [60, 61]])
        hits0 = metrics.prefix_hits.value()
        cached0 = metrics.prefix_cached_tokens.value()
        run([p2 + [70, 71]])  # shares 4 full blocks = 16 tokens
        assert metrics.prefix_cached_tokens.value() - cached0 >= 16
        assert metrics.prefix_hits.value() == hits0 + 1
        assert registry.get("paddlenlp_serving_kv_cached_blocks").value() \
            == eng_on.mgr.num_cached_blocks > 0

    def test_exact_repeat_cow_identical(self, eng_on, eng_off):
        p = list(range(40, 56))  # multiple of block size: full-cover COW path
        samp = SamplingParams(max_new_tokens=6)
        cached0 = eng_on.mgr.cached_tokens_total
        a = eng_on.generate([p], samp)
        b = eng_on.generate([p], samp)
        # the repeat skips all but the re-fed tail token
        assert eng_on.mgr.cached_tokens_total - cached0 == len(p) - 1
        wa = eng_off.generate([p], samp)
        wb = eng_off.generate([p], samp)
        np.testing.assert_array_equal(a[0], wa[0])
        np.testing.assert_array_equal(b[0], wb[0])

    def test_penalty_counts_cover_cached_span(self, eng_on, eng_off):
        """Repetition/presence penalties count the FULL prompt even when the
        cached span is never fed to prefill (suffix counted on device, cached
        span host-side): warm-cache output == cache-off output."""
        p = [88, 88, 88, 89, 89, 89, 89, 90]  # 2 full blocks, repetition-heavy
        samp = SamplingParams(max_new_tokens=8, repetition_penalty=5.0,
                              presence_penalty=1.0)
        eng_on.generate([p + [91, 92]], samp)  # warm the cache
        cached0 = eng_on.mgr.cached_tokens_total
        got = eng_on.generate([p + [93, 94]], samp)  # shares 2 full blocks
        assert eng_on.mgr.cached_tokens_total - cached0 == 8
        eng_off.generate([p + [91, 92]], samp)
        want = eng_off.generate([p + [93, 94]], samp)
        np.testing.assert_array_equal(got[0], want[0])

    def test_out_of_vocab_prompt_does_not_crash_step(self, eng_on):
        """Direct callers can feed ids outside the vocab; the penalty-count
        bincount must degrade (clip) rather than crash the engine step."""
        out = eng_on.generate([[200, 3, 7, 2, 6]], SamplingParams(max_new_tokens=2))
        assert len(out[0]) == 2

    def test_stats_surface_and_disable_flag(self, eng_on, eng_off):
        st = eng_on.stats()["prefix_cache"]  # warmed by the tests above
        assert st["enabled"] and st["hits"] >= 3
        assert st["cached_tokens"] >= 16 and st["cached_blocks"] >= 4
        st_off = eng_off.stats()["prefix_cache"]
        # the host-tier sub-dict is ALWAYS present (zeros when no tier is
        # attached) so the metrics plane reads one shape
        host_off = st_off.pop("host")
        assert host_off["enabled"] is False and host_off["blocks"] == 0
        assert st_off == {"enabled": False, "hits": 0, "cached_tokens": 0,
                          "evictions": 0, "cached_blocks": 0}

    def test_mixed_finish_abort_preempt_churn_no_leak(self, model):
        """(c) engine-level: finish + abort + forced preemption + eviction,
        then free + cached == total and no tables remain."""
        eng = _engine(model, cache=True, max_batch_size=2, num_blocks=14)
        samp = SamplingParams(max_new_tokens=8)
        # round 1: two shared-prefix requests under block pressure
        eng.generate([PREFIX[:8] + [60], PREFIX[:8] + [70]], samp)
        # round 2: abort one mid-flight
        rid = eng.add_request(PREFIX[:8] + [80], samp)
        eng.add_request(PREFIX[:8] + [90], samp)
        eng.step()
        eng.abort(rid)
        while eng.has_work():
            eng.step()
        # round 3: force eviction of the cached prefix with a long request
        eng.generate([[40 + i for i in range(44)]], SamplingParams(max_new_tokens=4))
        mgr = eng.mgr
        assert not mgr.tables
        assert len(mgr.free) + len(mgr._lru) == mgr.total_usable_blocks
        mgr_conserved(mgr)

    def test_eviction_pressure_output_parity(self, model):
        """(d) under a pool small enough to force eviction + preemption, the
        cached engine completes the same work with identical tokens."""
        samp = SamplingParams(max_new_tokens=8)
        rounds = [[PREFIX[:8] + [60], PREFIX[:8] + [61]],
                  [PREFIX[:8] + [62], [33, 34, 35, 36, 37, 38, 39, 40, 41]]]
        on = _engine(model, cache=True, max_batch_size=2, num_blocks=12)
        off = _engine(model, cache=False, max_batch_size=2, num_blocks=12)
        for prompts in rounds:
            got = on.generate(prompts, samp)
            want = off.generate(prompts, samp)
            for g, w in zip(got, want):
                np.testing.assert_array_equal(g, w)
        assert len(on.mgr.free) + len(on.mgr._lru) == on.mgr.total_usable_blocks
