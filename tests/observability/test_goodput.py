"""GoodputLedger unit contract: exact conservation, compile attribution,
FLOPs/MFU model, and the /debug/efficiency doc shape. Pure stdlib — no jax,
no engine (the engine-level parity lives in
tests/experimental/test_goodput_ledger.py)."""

import json
import math
import threading

import pytest

from paddlenlp_tpu.observability.goodput import (
    GoodputLedger,
    _on_duration,
    compile_attribution,
    device_peak_flops,
    efficiency_doc,
    estimate_model_flops_per_token,
)


class TestConservation:
    def test_record_accumulates(self):
        led = GoodputLedger()
        led.record("prefill", 32, 20, padding=10, rework=2)
        led.record("decode", 16, 4, padding=12)
        led.record("verify", 10, 3, padding=5, spec_rejected=2)
        assert led.totals == {"fed": 58, "useful": 27, "padding": 27,
                              "spec_rejected": 2, "rework": 2}
        assert led.verify_conservation()
        assert led.ratio() == pytest.approx(27 / 58)
        assert {k: led.by_kind["prefill"][k] for k in ("steps", "fed", "useful")} == \
            {"steps": 1, "fed": 32, "useful": 20}
        assert led.padding_by["decode"] == 12

    def test_violation_raises(self):
        led = GoodputLedger()
        with pytest.raises(ValueError, match="conservation violated"):
            led.record("prefill", 10, 9, padding=2)  # 9 + 2 != 10
        with pytest.raises(ValueError, match="conservation violated"):
            led.record("decode", 10, 12, padding=-2)  # negative component
        with pytest.raises(ValueError, match="unknown step kind"):
            led.record("nope", 1, 1)
        # a failed record must not have mutated the totals
        assert led.totals["fed"] == 0 and led.verify_conservation()

    def test_rework_attribution_sums_or_raises(self):
        led = GoodputLedger()
        led.record("reseed", 7, 0, rework=7, rework_by={"migration_reseed": 7})
        assert led.rework_by["migration_reseed"] == 7
        with pytest.raises(ValueError, match="does not sum"):
            led.record("prefill", 5, 2, padding=1, rework=2,
                       rework_by={"cow_token": 1})
        # unattributed rework defaults to the preemption bucket
        led.record("prefill", 4, 1, padding=1, rework=2)
        assert led.rework_by["preempt_refill"] == 2
        assert led.verify_conservation()

    def test_idle_ledger_reads_clean(self):
        led = GoodputLedger()
        assert led.ratio() == 1.0
        assert math.isnan(led.mfu())
        assert led.verify_conservation()
        snap = led.snapshot()
        assert snap["totals"]["fed"] == 0
        assert snap["by_kind"] == {} and snap["padding_by"] == {}


class TestCompileTelemetry:
    def test_attribution_is_per_thread(self):
        mine, other = GoodputLedger(), GoodputLedger()
        with compile_attribution(mine, "prefill"):
            _on_duration("jax/backend_compile", 1.5)
            # another thread compiling concurrently attributes to ITS ledger
            def other_thread():
                with compile_attribution(other, "decode"):
                    _on_duration("jax/backend_compile", 0.5)
            t = threading.Thread(target=other_thread)
            t.start()
            t.join()
        _on_duration("jax/backend_compile", 9.0)  # outside any block: dropped
        assert mine.compiles == {"prefill": 1}
        assert mine.compile_seconds == {"prefill": 1.5}
        assert other.compiles == {"decode": 1}

    def test_non_compile_events_ignored_and_nesting_restores(self):
        led = GoodputLedger()
        with compile_attribution(led, "mixed"):
            _on_duration("jax/some_other_event", 1.0)
            with compile_attribution(led, "verify"):
                _on_duration("x/backend_compile", 0.25)
            _on_duration("x/backend_compile", 0.25)
        assert led.compiles == {"verify": 1, "mixed": 1}

    def test_none_ledger_noop(self):
        with compile_attribution(None, "prefill"):
            _on_duration("x/backend_compile", 1.0)  # must not raise

    def test_shape_bucket_cardinality(self):
        led = GoodputLedger()
        led.note_shape(("prefill", 2, 16))
        led.note_shape(("prefill", 2, 16))
        led.note_shape(("decode", 4, 8))
        assert led.snapshot()["shape_buckets"] == 2


class TestFlopsModel:
    def test_estimate_from_config(self):
        class Cfg:
            hidden_size = 64
            num_hidden_layers = 2
            vocab_size = 96
            intermediate_size = 112
            num_attention_heads = 8
            num_key_value_heads = 4
        # embed+head + layers * (attn(q,o full + k,v at GQA ratio) + 3 mlp)
        attn = 64 * 64 * (2 + 2 * 4 / 8)
        expect = 2.0 * (96 * 64 * 2 + 2 * (attn + 3 * 64 * 112))
        assert estimate_model_flops_per_token(Cfg()) == pytest.approx(expect)

    def test_estimate_nan_on_junk(self):
        class Junk:
            hidden_size = "nope"
        assert math.isnan(estimate_model_flops_per_token(Junk()))
        assert math.isnan(estimate_model_flops_per_token(object()))

    def test_peak_flops_table(self):
        # keyed by device_kind exactly as jax reports it ("TPU v5 lite" = v5e)
        assert device_peak_flops("TPU v5 lite") == pytest.approx(197e12)
        assert device_peak_flops("TPU v4") == pytest.approx(275e12)
        assert math.isnan(device_peak_flops("cpu"))
        with pytest.raises(ValueError):  # an unlisted accelerator is an error,
            device_peak_flops("NVIDIA H100")  # never a default or a NaN

    def test_mfu_real_and_nan(self):
        led = GoodputLedger(flops_per_token=2.0, peak_flops=float("nan"))
        led.record("decode", 10, 10)
        assert math.isnan(led.mfu())  # unknown peak -> NaN, never fake
        led2 = GoodputLedger(flops_per_token=100.0, peak_flops=1000.0)
        led2.record("decode", 10, 5, padding=5)
        led2._first_record_t = 0.0
        led2._last_record_t = 1.0
        assert led2.mfu() == pytest.approx(5 * 100.0 / (1.0 * 1000.0))


class TestEfficiencyDoc:
    def test_doc_shape_and_json_safe(self):
        led = GoodputLedger()
        led.record("mixed", 8, 5, padding=3)
        led.note_step(0.001, 0.05, 0.002)
        doc = efficiency_doc(led, [(1, 0.001, 0.05, 0.002), (2, -1.0, 0.04, 0.001)],
                             extra={"kv_fragmentation": 0.25})
        assert doc["tier"] == "serving"
        assert doc["ledger"]["totals"]["fed"] == 8
        assert doc["mfu"] is None  # NaN serialized as null
        assert doc["kv_fragmentation"] == 0.25
        anatomy = doc["step_anatomy"]
        assert anatomy["window_steps"] == 2
        assert anatomy["device_p99_ms"] == pytest.approx(50.0)
        json.dumps(doc)  # strictly serializable

    def test_unmeasured_gaps_excluded_from_percentiles(self):
        # gap < 0 marks first/post-idle steps: they must not drag the p50 down
        times = [(1, -1.0, 0.01, 0.0), (2, 0.5, 0.01, 0.0), (3, 0.5, 0.01, 0.0)]
        doc = efficiency_doc(None, times)
        assert doc["step_anatomy"]["gap_p50_ms"] == pytest.approx(500.0)
        # an ALL-unmeasured window reports null, never a fake perfect 0.0
        doc = efficiency_doc(None, [(1, -1.0, 0.01, 0.0)])
        assert doc["step_anatomy"]["gap_p50_ms"] is None
        assert doc["step_anatomy"]["gap_p99_ms"] is None
