"""Hierarchical KV cache: the host-RAM spill tier under the BlockManager.

The invariants pinned here (see ``kv_host_tier.py``'s module docstring):

(a) **token identity**: a prompt whose prefix was LRU-evicted to the host
    tier and promoted back streams bitwise-identical tokens to a never-
    evicted run — greedy and seeded sampling, monolithic and chunked
    prefill, tp=1 and tp=2;
(b) **resident-XOR + conservation**: under mixed finish/abort/churn a chain
    hash lives in the device index XOR the host tier, the BlockManager's
    free/cached/owned partition stays exact, and the tier's batch refcounts
    match its entry count — no leak in either tier, in either direction;
(c) **chaos degrades to the pre-tier behavior**: a fault on the spill path
    drops the batch (cold re-prefill later, nothing lost); a fault on the
    promote path falls back to cold prefill token-exactly with zero stream
    loss and no tier/device leak;
(d) **conversation lifetime**: a finished request's GENERATED blocks are
    registered alongside its prompt blocks, so a turn-2 prompt that threads
    turn 1's completion back re-prefills only the new suffix;
(e) **epoch invalidation**: ``clear_prefix_cache()`` empties the host tier
    with the device index (the weight-swap HTTP path is covered in
    tests/serving/test_weight_swap.py).
"""

import numpy as np
import pytest

from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams
from paddlenlp_tpu.experimental.kv_host_tier import HostKVTier
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM
from paddlenlp_tpu.utils.faults import FAULTS, InjectedFault

BS = 4
PREFIX = list(range(5, 21))  # 4 full blocks
GREEDY = SamplingParams(max_new_tokens=8)
SAMPLED = SamplingParams(max_new_tokens=8, do_sample=True, top_p=0.9, seed=7)


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=256,
                      eos_token_id=None, pad_token_id=0, use_scan_layers=True)
    return LlamaForCausalLM.from_config(cfg, seed=0)


def _engine(model, **kw):
    """A SMALL device pool (so churn forces LRU eviction) over a roomy host
    tier — the configuration every spill/promote test needs."""
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("block_size", BS)
    kw.setdefault("num_blocks", 15)
    kw.setdefault("max_blocks_per_seq", 16)
    kw.setdefault("enable_prefix_cache", True)
    kw.setdefault("host_kv_blocks", 64)
    return InferenceEngine(model, **kw)


@pytest.fixture(scope="module")
def eng_host(model):
    return _engine(model)


@pytest.fixture(scope="module")
def eng_host_chunked(model):
    return _engine(model, prefill_chunk_tokens=8)


@pytest.fixture(scope="module")
def eng_host_tp2(model):
    return _engine(model, mesh_shape=(1, 2))


@pytest.fixture(scope="module")
def eng_off(model):
    """Ground truth: no cache, pool big enough that nothing is ever evicted."""
    return InferenceEngine(model, max_batch_size=2, block_size=BS,
                           num_blocks=64, max_blocks_per_seq=16,
                           enable_prefix_cache=False)


CHURN = [22 + i for i in range(44)]  # 11 blocks + decode: floods the pool


def tier_conserved(eng):
    """(b) resident-XOR between tiers + tier-internal batch refcounts +
    device-side block conservation."""
    mgr, tier = eng.mgr, eng._host_tier
    dev = set(mgr._index)
    host = set(tier._entries)
    assert not (dev & host), "chain hash resident in BOTH tiers"
    assert tier.num_blocks <= tier.max_blocks
    batches = {id(b): b for b, _row in tier._entries.values()}
    assert sum(b.live for b in batches.values()) == len(tier._entries)
    owned = {b for blocks in mgr.tables.values() for b in blocks}
    assert len(mgr.free) + len(mgr._lru) + len(owned) == mgr.total_usable_blocks


def spill_then_promote(eng, samp, warm_tail, target_tail):
    """Warm PREFIX into the device cache, churn it out to the host tier,
    then run a PREFIX-sharing prompt that must promote. Returns the target
    output and asserts the tier actually did the work."""
    eng.generate([PREFIX + warm_tail], samp)
    spills0 = eng._host_tier.stats["spills"]
    eng.generate([CHURN], SamplingParams(max_new_tokens=4))
    assert eng._host_tier.stats["spills"] > spills0, "churn never spilled"
    promotes0 = eng._host_tier.stats["promoted_blocks"]
    out = eng.generate([PREFIX + target_tail], samp)[0]
    assert eng._host_tier.stats["promoted_blocks"] >= promotes0 + 4, \
        "target prompt did not promote its evicted prefix"
    tier_conserved(eng)
    return out


class TestPromotedTokenIdentity:
    """(a) across engine geometries. Each case uses disjoint tail tokens so
    the shared module-scoped reference engine stays collision-free; the
    content-addressed caches make prefix overlap across cases harmless."""

    def test_greedy_and_sampled_monolithic(self, eng_host, eng_off):
        got = spill_then_promote(eng_host, GREEDY, [60, 61], [62, 63])
        eng_off.generate([PREFIX + [60, 61]], GREEDY)
        want = eng_off.generate([PREFIX + [62, 63]], GREEDY)[0]
        np.testing.assert_array_equal(got, want)
        got_s = spill_then_promote(eng_host, SAMPLED, [64, 65], [66, 67])
        eng_off.generate([PREFIX + [64, 65]], SAMPLED)
        want_s = eng_off.generate([PREFIX + [66, 67]], SAMPLED)[0]
        np.testing.assert_array_equal(got_s, want_s)

    def test_chunked_prefill(self, eng_host_chunked, eng_off):
        got = spill_then_promote(eng_host_chunked, GREEDY, [68, 69], [70, 71])
        eng_off.generate([PREFIX + [68, 69]], GREEDY)
        want = eng_off.generate([PREFIX + [70, 71]], GREEDY)[0]
        np.testing.assert_array_equal(got, want)

    def test_tp2(self, eng_host_tp2, eng_off):
        got = spill_then_promote(eng_host_tp2, GREEDY, [72, 73], [74, 75])
        eng_off.generate([PREFIX + [72, 73]], GREEDY)
        want = eng_off.generate([PREFIX + [74, 75]], GREEDY)[0]
        np.testing.assert_array_equal(got, want)

    def test_chunked_tp2(self, model, eng_off):
        eng = _engine(model, mesh_shape=(1, 2), prefill_chunk_tokens=8)
        got = spill_then_promote(eng, GREEDY, [88, 89], [90, 91])
        eng_off.generate([PREFIX + [88, 89]], GREEDY)
        want = eng_off.generate([PREFIX + [90, 91]], GREEDY)[0]
        np.testing.assert_array_equal(got, want)


class TestConversationLifetime:
    def test_generated_blocks_registered_and_reused(self, model, eng_off):
        """(d) turn 2 = turn 1's prompt + completion + new user tokens: the
        cached span covers the COMPLETION, not just the prompt."""
        eng = _engine(model, num_blocks=64)  # no eviction: isolates (d)
        p1 = [3] + PREFIX + [4]  # 18 tokens
        out1 = list(eng.generate([p1], GREEDY)[0])
        turn2 = p1 + out1 + [76, 77]
        cached0 = eng.mgr.cached_tokens_total
        out2 = eng.generate([turn2], GREEDY)[0]
        # prompt+completion = 26 tokens = 6 full blocks all served from cache
        assert eng.mgr.cached_tokens_total - cached0 >= \
            (len(p1) + len(out1)) // BS * BS
        eng_off.generate([p1], GREEDY)
        want = eng_off.generate([turn2], GREEDY)[0]
        np.testing.assert_array_equal(out2, want)

    def test_turn2_survives_eviction_via_host_tier(self, model, eng_off):
        """(a)+(d): the whole turn-1 history (prompt AND completion) comes
        back from the HOST tier after churn evicted it from the device."""
        eng = _engine(model)
        p1 = [3] + PREFIX + [4]
        out1 = list(eng.generate([p1], GREEDY)[0])
        # 52 + 4 tokens = ALL 14 usable blocks: every history block evicts
        eng.generate([[22 + i % 60 for i in range(52)]],
                     SamplingParams(max_new_tokens=4))
        promotes0 = eng._host_tier.stats["promoted_blocks"]
        turn2 = p1 + out1 + [78, 79]
        out2 = eng.generate([turn2], GREEDY)[0]
        assert eng._host_tier.stats["promoted_blocks"] >= promotes0 + 6
        eng_off.generate([p1], GREEDY)
        want = eng_off.generate([turn2], GREEDY)[0]
        np.testing.assert_array_equal(out2, want)
        tier_conserved(eng)


class TestChaos:
    """(c) both fault points from utils/faults.py CATALOG."""

    def test_spill_fault_drops_batch_no_leak(self, eng_host, eng_off):
        eng_host.generate([PREFIX + [80, 81]], GREEDY)
        FAULTS.arm("engine.kv_spill", times=1)
        blocks0 = eng_host._host_tier.num_blocks
        got = eng_host.generate([CHURN], SamplingParams(max_new_tokens=4))[0]
        assert FAULTS.fired("engine.kv_spill") == 1
        # the faulted batch is GONE (pre-tier behavior), nothing half-resident
        assert eng_host._host_tier.num_blocks <= blocks0 + len(CHURN) // BS
        want = eng_off.generate([CHURN], SamplingParams(max_new_tokens=4))[0]
        np.testing.assert_array_equal(got, want)
        tier_conserved(eng_host)

    def test_promote_fault_cold_prefill_token_exact(self, eng_host, eng_off):
        eng_host.generate([PREFIX + [82, 83]], GREEDY)
        eng_host.generate([CHURN], SamplingParams(max_new_tokens=4))
        assert eng_host._host_tier.num_blocks >= 4
        FAULTS.arm("engine.kv_promote", times=1)
        promotes0 = eng_host._host_tier.stats["promotes"]
        got = eng_host.generate([PREFIX + [84, 85]], GREEDY)[0]
        assert FAULTS.fired("engine.kv_promote") == 1
        # fallback recomputed the span cold: no promote happened, the fault
        # fired BEFORE take() so the entries stay tier-resident
        assert eng_host._host_tier.stats["promotes"] == promotes0
        eng_off.generate([PREFIX + [82, 83]], GREEDY)
        want = eng_off.generate([PREFIX + [84, 85]], GREEDY)[0]
        np.testing.assert_array_equal(got, want)
        tier_conserved(eng_host)


class TestEpochAndSurface:
    def test_clear_prefix_cache_empties_host_tier(self, model):
        """(e) the engine-level half of the weight-swap invalidation."""
        eng = _engine(model)
        eng.generate([PREFIX + [86, 87]], GREEDY)
        eng.generate([CHURN], SamplingParams(max_new_tokens=4))
        assert eng._host_tier.num_blocks > 0
        eng.clear_prefix_cache()
        assert eng._host_tier.num_blocks == 0
        assert eng.mgr.num_cached_blocks == 0
        # a post-clear repeat must not promote (nothing resident anywhere)
        promotes0 = eng._host_tier.stats["promotes"]
        eng.generate([PREFIX + [86, 87]], GREEDY)
        assert eng._host_tier.stats["promotes"] == promotes0
        tier_conserved(eng)

    def test_stats_surface(self, eng_host, model):
        host = eng_host.stats()["prefix_cache"]["host"]
        assert host["enabled"] and host["capacity"] == 64
        for k in ("blocks", "spills", "spill_batches", "promotes",
                  "promoted_blocks", "promote_bytes", "evictions",
                  "promotes_inflight"):
            assert k in host, k
        # tier off: same shape, zeros + enabled False
        off = InferenceEngine(model, max_batch_size=2, block_size=BS,
                              num_blocks=15, max_blocks_per_seq=16,
                              enable_prefix_cache=True)
        host_off = off.stats()["prefix_cache"]["host"]
        assert host_off["enabled"] is False and host_off["blocks"] == 0

    def test_host_tier_requires_prefix_cache(self, model):
        with pytest.raises(ValueError, match="enable_prefix_cache"):
            InferenceEngine(model, max_batch_size=2, block_size=BS,
                            num_blocks=15, max_blocks_per_seq=16,
                            enable_prefix_cache=False, host_kv_blocks=8)


class TestHostTierUnit:
    """Pure HostKVTier semantics, no engine: LRU under capacity pressure,
    re-spill dedup, take pops (resident-XOR half), clear, byte fidelity."""

    def _batch(self, n, seed):
        rng = np.random.default_rng(seed)
        return rng.standard_normal((2, 2, n, BS, 2 * 8)).astype(np.float32)

    def test_put_take_roundtrip_bitwise(self):
        tier = HostKVTier(8, block_bytes=2 * 2 * 2 * BS * 8 * 4)
        kv = self._batch(3, 0)
        tier.put([b"a", b"b", b"c"], kv)
        got, scale, nbytes = tier.take([b"b", b"c"])
        np.testing.assert_array_equal(got, kv[:, :, 1:3])
        assert scale is None and nbytes == 2 * tier.block_bytes
        assert tier.num_blocks == 1 and not tier.contains(b"b")
        assert tier.stats["promotes"] == 1
        assert tier.stats["promoted_blocks"] == 2

    def test_lru_eviction_and_respill(self):
        tier = HostKVTier(3)
        tier.put([b"a", b"b"], self._batch(2, 1))
        tier.put([b"c", b"a"], self._batch(2, 2))  # re-spill of a: newest wins
        assert tier.num_blocks == 3 and tier.stats["evictions"] == 0
        tier.put([b"d"], self._batch(1, 3))  # capacity 3: oldest (b) evicted
        assert tier.stats["evictions"] == 1
        assert not tier.contains(b"b") and tier.contains(b"a")
        tier.clear()
        assert tier.num_blocks == 0

    def test_disabled_tier_accepts_nothing(self):
        tier = HostKVTier(0)
        assert not tier.accepting
        tier.put([b"a"], self._batch(1, 4))
        assert tier.num_blocks == 0
