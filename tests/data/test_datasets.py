"""Data subsystem tests: mmap indexed dataset, GPT pretraining dataset (native +
numpy index builders), blending, collators, zero-padding packing."""

import os

import numpy as np
import pytest

from paddlenlp_tpu.data import (
    BlendableDataset,
    GPTDataset,
    MMapIndexedDataset,
    MMapIndexedDatasetBuilder,
    build_train_valid_test_datasets,
)
from paddlenlp_tpu.data.native import _build_sample_idx_np, build_sample_idx, native_available
from paddlenlp_tpu.datasets import ZeroPaddingMapDataset, greedy_pack


@pytest.fixture()
def corpus(tmp_path):
    """20 docs of varying lengths, token value == doc id (provenance-checkable)."""
    prefix = str(tmp_path / "corpus")
    builder = MMapIndexedDatasetBuilder(prefix, dtype=np.uint16)
    rng = np.random.default_rng(0)
    for d in range(20):
        builder.add_document(np.full(int(rng.integers(5, 40)), d, dtype=np.uint16))
    builder.finalize()
    return prefix


class TestIndexedDataset:
    def test_roundtrip(self, corpus):
        ds = MMapIndexedDataset(corpus)
        assert len(ds) == 20 and ds.n_docs == 20
        np.testing.assert_array_equal(np.unique(ds[3]), [3])

    def test_partial_get(self, corpus):
        ds = MMapIndexedDataset(corpus)
        full = ds[5]
        np.testing.assert_array_equal(ds.get(5, 2, 3), full[2:5])

    def test_bad_magic(self, tmp_path):
        p = tmp_path / "x.idx"
        p.write_bytes(b"NOTMAGIC" + b"\0" * 64)
        with pytest.raises(ValueError, match="bad magic"):
            MMapIndexedDataset(str(tmp_path / "x"))


class TestSampleIdx:
    def test_native_matches_numpy(self, corpus):
        ds = MMapIndexedDataset(corpus)
        doc_idx = np.concatenate([np.random.default_rng(1).permutation(20) for _ in range(4)]).astype(np.int64)
        got = build_sample_idx(np.asarray(ds.sizes), doc_idx, seq_length=16, n_samples=30)
        want = _build_sample_idx_np(np.asarray(ds.sizes), doc_idx, 16, 30)
        np.testing.assert_array_equal(got, want)

    def test_native_compiled(self):
        assert native_available(), "g++ helper should compile on this image"

    def test_stale_binary_in_a_copied_tree_is_not_used(self, tmp_path, monkeypatch):
        """git ignores csrc/*.so, a tree copy carries them along and resets
        mtimes: the binary is named by its source's hash, so one built from
        another source is never loaded, and one that does not load (here a
        truncated copy) is rebuilt."""
        import hashlib
        import shutil

        from paddlenlp_tpu.data import native

        csrc = tmp_path / "csrc"
        csrc.mkdir()
        shutil.copy(os.path.join(native._CSRC, "sample_idx.cpp"), csrc)
        with open(csrc / "sample_idx.cpp", "a") as f:
            f.write("\n// edited after the stale binary was built\n")
        stale = csrc / "libpdnlp_data.so"  # the old fixed name, newer than the source
        stale.write_bytes(b"built from another source")
        digest = hashlib.sha256((csrc / "sample_idx.cpp").read_bytes()).hexdigest()[:16]
        current = csrc / f"libpdnlp_data.{digest}.so"
        current.write_bytes(b"truncated by the copy")
        monkeypatch.setattr(native, "_CSRC", str(csrc))
        monkeypatch.setattr(native, "_tried", False)
        monkeypatch.setattr(native, "_lib", None)
        assert native.native_available()
        assert native._lib._name == str(current) and current.stat().st_size > 1000
        assert stale.read_bytes() == b"built from another source"

    def test_exhaustion_raises(self, corpus):
        ds = MMapIndexedDataset(corpus)
        doc_idx = np.arange(20, dtype=np.int64)
        with pytest.raises(ValueError, match="exhausted"):
            build_sample_idx(np.asarray(ds.sizes), doc_idx, seq_length=64, n_samples=10**4)


class TestGPTDataset:
    def test_samples_fixed_length_and_shifted(self, corpus):
        ds = MMapIndexedDataset(corpus)
        g = GPTDataset(ds, np.arange(20), seq_length=32, n_samples=50, seed=0)
        assert len(g) == 50
        s = g[7]
        assert s["input_ids"].shape == (32,) and s["labels"].shape == (32,)
        # labels are inputs shifted by one within the sample window
        np.testing.assert_array_equal(s["input_ids"][1:], s["labels"][:-1])

    def test_deterministic_and_cached(self, corpus):
        ds = MMapIndexedDataset(corpus)
        a = GPTDataset(ds, np.arange(20), 32, 50, seed=3)
        b = GPTDataset(ds, np.arange(20), 32, 50, seed=3)  # second build hits the cache
        for i in (0, 13, 49):
            np.testing.assert_array_equal(a[i]["input_ids"], b[i]["input_ids"])

    def test_split_builder(self, corpus):
        train, valid, test = build_train_valid_test_datasets(
            corpus, seq_length=16, train_valid_test_num_samples=(40, 8, 0), splits_string="80,20,0"
        )
        assert len(train) == 40 and len(valid) == 8 and test is None
        # valid draws only from the last 20% of documents (ids 16..19)
        v = valid[0]
        assert set(np.unique(v["input_ids"])) <= set(range(16, 20))

    def test_blendable_mixture(self, corpus, tmp_path):
        ds = MMapIndexedDataset(corpus)
        g1 = GPTDataset(ds, np.arange(10), 16, 40, seed=0, name="a")
        g2 = GPTDataset(ds, np.arange(10, 20), 16, 40, seed=0, name="b")
        blend = BlendableDataset([g1, g2], [0.75, 0.25], n_samples=40)
        counts = np.bincount(blend.dataset_index, minlength=2)
        assert counts[0] == 30 and counts[1] == 10


class TestCollators:
    def _tok(self):
        class Tok:
            pad_token_id = 0
            cls_token_id = 1
            sep_token_id = 2
            mask_token_id = 3
            vocab_size = 50
            padding_side = "right"

        return Tok()

    def test_padding_collator(self):
        from paddlenlp_tpu.data import DataCollatorWithPadding

        coll = DataCollatorWithPadding(self._tok())
        out = coll([{"input_ids": [5, 6, 7]}, {"input_ids": [8, 9]}])
        np.testing.assert_array_equal(out["input_ids"], [[5, 6, 7], [8, 9, 0]])
        np.testing.assert_array_equal(out["attention_mask"], [[1, 1, 1], [1, 1, 0]])

    def test_label_padding_uses_ignore(self):
        from paddlenlp_tpu.data import DataCollatorForSeq2Seq

        coll = DataCollatorForSeq2Seq(self._tok())
        out = coll([{"input_ids": [5, 6, 7], "labels": [5, 6, 7]}, {"input_ids": [8], "labels": [8]}])
        np.testing.assert_array_equal(out["labels"][1], [8, -100, -100])

    def test_mlm_collator(self):
        from paddlenlp_tpu.data import DataCollatorForLanguageModeling

        coll = DataCollatorForLanguageModeling(self._tok(), mlm_probability=0.5, seed=0)
        feats = [{"input_ids": np.arange(4, 30)} for _ in range(4)]
        out = coll(feats)
        masked = out["labels"] != -100
        assert masked.any()
        # masked positions mostly replaced with mask_token (3)
        assert (out["input_ids"][masked] == 3).sum() > 0
        # non-masked labels are ignored
        assert (out["labels"][~masked] == -100).all()


class TestZeroPadding:
    def test_greedy_pack(self):
        examples = [{"input_ids": np.arange(5) + 1}, {"input_ids": np.arange(6) + 1},
                    {"input_ids": np.arange(10) + 1}, {"input_ids": np.arange(3) + 1}]
        packs = greedy_pack(examples, max_length=12)
        assert len(packs) == 3  # first-fit-in-order: [5,6] | [10] | [3]
        p = packs[0]
        assert p["input_ids"].shape == (12,)
        np.testing.assert_array_equal(p["segment_ids"][:11], [0] * 5 + [1] * 6)
        np.testing.assert_array_equal(p["position_ids"][:11], list(range(5)) + list(range(6)))
        assert p["labels"][11] == -100  # padding ignored in loss

    def test_map_dataset(self):
        class DS:
            def __len__(self):
                return 6

            def __getitem__(self, i):
                return {"input_ids": np.arange(4 + i) + 1}

        z = ZeroPaddingMapDataset(DS(), max_length=16)
        assert len(z) >= 2
        total = sum((p["labels"] != -100).sum() for p in [z[i] for i in range(len(z))])
        assert total == sum(4 + i for i in range(6))

    def test_packed_training_correctness(self):
        """Packed rows train like separate rows (segment mask + positions)."""
        import jax.numpy as jnp

        from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM

        cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=64, num_hidden_layers=1,
                          num_attention_heads=2, num_key_value_heads=2, max_position_embeddings=32)
        model = LlamaForCausalLM.from_config(cfg, seed=0)
        a = {"input_ids": np.asarray([5, 6, 7, 8])}
        b = {"input_ids": np.asarray([9, 10, 11])}
        pack = greedy_pack([a, b], max_length=8)[0]
        out = model(
            input_ids=jnp.asarray(pack["input_ids"][None]),
            segment_ids=jnp.asarray(pack["segment_ids"][None]),
            position_ids=jnp.asarray(pack["position_ids"][None]),
        ).logits
        sep_a = model(input_ids=jnp.asarray(a["input_ids"][None])).logits
        np.testing.assert_allclose(np.asarray(out[0, :4]), np.asarray(sep_a[0]), atol=2e-5)


class TestLoadDataset:
    def test_local_files_and_splits(self, tmp_path):
        import json

        from paddlenlp_tpu.datasets import load_dataset

        d = tmp_path / "corpus"
        d.mkdir()
        (d / "train.jsonl").write_text("\n".join(json.dumps({"text": f"t{i}"}) for i in range(4)))
        (d / "dev.json").write_text(json.dumps([{"text": "v0"}, {"text": "v1"}]))
        (d / "test.tsv").write_text("text\tlabel\na\t1\nb\t0\n")
        train, dev, test = load_dataset(str(d), splits=("train", "dev", "test"))
        assert len(train) == 4 and train[0]["text"] == "t0"
        assert len(dev) == 2 and dev[1]["text"] == "v1"
        assert len(test) == 2 and test[0] == {"text": "a", "label": "1"}

    def test_map_filter_shuffle(self, tmp_path):
        from paddlenlp_tpu.datasets import MapDataset

        ds = MapDataset([{"x": i} for i in range(10)])
        ds.map(lambda r: {"x": r["x"] * 2}).filter(lambda r: r["x"] >= 8)
        assert sorted(r["x"] for r in ds) == [8, 10, 12, 14, 16, 18]
        lazy = ds.map(lambda r: {"x": r["x"] + 1}, lazy=True)
        assert lazy[0]["x"] == ds[0]["x"] + 1

    def test_lazy_map_chains_eager_transforms(self):
        from paddlenlp_tpu.datasets import MapDataset

        base = MapDataset([{"x": i} for i in range(6)])
        lazy = base.map(lambda r: {"x": r["x"] * 2}, lazy=True)
        got = lazy.filter(lambda r: r["x"] >= 4)
        assert sorted(r["x"] for r in got) == [4, 6, 8, 10]
        shuffled = base.map(lambda r: {"x": r["x"]}, lazy=True).shuffle(seed=3)
        assert sorted(r["x"] for r in shuffled) == list(range(6))
        double_lazy = base.map(lambda r: {"x": r["x"] + 1}, lazy=True).map(
            lambda r: {"x": r["x"] * 10}, lazy=True
        )
        assert double_lazy[1]["x"] == (1 + 1) * 10
        eager_after = base.map(lambda r: {"x": r["x"]}, lazy=True).map(lambda r: {"x": -r["x"]})
        assert [r["x"] for r in eager_after] == [0, -1, -2, -3, -4, -5]

    def test_multihost_sampler_marks_filler_rows(self):
        import numpy as np

        from paddlenlp_tpu.data.dataloader import DataLoader

        ds = [{"labels": np.full((4,), i, np.int64)} for i in range(10)]
        # 10 rows, global batch 8, 2 shards: batch 2 is partial (2 real rows)
        loaders = [
            DataLoader(ds, batch_size=8, shuffle=False, drop_last=False,
                       num_shards=2, shard_id=s, shard_span=1)
            for s in (0, 1)
        ]
        b0 = list(loaders[0])
        b1 = list(loaders[1])
        assert len(b0) == len(b1) == 2
        # final batch: global rows 8..9 real, 10..15 wrap-filler
        # shard 0 holds rows 8,9,(10,11 filler); shard 1 all filler
        assert (b0[1]["labels"][:2] >= 0).all()
        assert (b0[1]["labels"][2:] == -100).all()
        assert (b1[1]["labels"] == -100).all()

    def test_registry_builder(self):
        from paddlenlp_tpu.datasets import load_dataset, register_dataset

        @register_dataset("unit_test_corpus")
        def build(split, name=None, **kw):
            return [{"split": split, "i": i} for i in range(3)]

        ds = load_dataset("unit_test_corpus", splits="dev")
        assert len(ds) == 3 and ds[0]["split"] == "dev"

    def test_missing_named_dataset_errors(self):
        import pytest

        from paddlenlp_tpu.datasets import load_dataset

        with pytest.raises(FileNotFoundError, match="register_dataset"):
            load_dataset("no_such_dataset_xyz")

    def test_iter_dataset_streaming(self):
        from paddlenlp_tpu.datasets import IterDataset

        ds = IterDataset(lambda: ({"x": i} for i in range(6)))
        ds.map(lambda r: {"x": r["x"] * 10}).filter(lambda r: r["x"] >= 30)
        assert [r["x"] for r in ds] == [30, 40, 50]
        assert [r["x"] for r in ds] == [30, 40, 50]  # re-iterable
