"""What a prefilling request's time was spent on (ISSUE 38): at the end of every
launch the engine books the launch span's duration to each admitted request that
has no first token yet, as its own (the launch carried its prompt tokens) or as
another's (``prefill_behind_s``), and the launch span says whose prompt it carried
(``carried``) and how many admitted requests still waited for a chunk row
(``prefill_waiting``)."""

import pytest

from paddlenlp_tpu.experimental import InferenceEngine, SamplingParams
from paddlenlp_tpu.experimental.engine import TRACER
from paddlenlp_tpu.transformers import LlamaConfig, LlamaForCausalLM

LAUNCHES = ("prefill", "decode", "mixed_step", "spec_verify")


@pytest.fixture(scope="module")
def model():
    cfg = LlamaConfig(vocab_size=96, hidden_size=64, intermediate_size=112,
                      num_hidden_layers=2, num_attention_heads=8, num_key_value_heads=8,
                      max_position_embeddings=256, eos_token_id=None, pad_token_id=0,
                      use_scan_layers=True)
    return LlamaForCausalLM.from_config(cfg, seed=0)


def make_engine(model, **kw):
    kw = {"max_batch_size": 4, "block_size": 4, "num_blocks": 128, "max_blocks_per_seq": 32,
          "decode_steps": 4, **kw}
    return InferenceEngine(model, **kw)


def run(eng):
    done = {}
    while eng.has_work():
        for req in eng.step():
            done[req.req_id] = req
    return done


def launch_spans():
    return [s for s in TRACER.snapshot() if s.cat == "engine" and s.name in LAUNCHES]


class TestChunkedBooking:
    @pytest.fixture(scope="class")
    def two_long_prompts(self, model):
        """Two prompts of 3 and 2 chunks admitted together: one chunk row's
        budget a step goes to the older one first, so the younger one waits
        out the older one's chunks in its slot."""
        eng = make_engine(model, prefill_chunk_tokens=8)
        eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=2))  # compile first
        TRACER.clear()
        old = eng.add_request(list(range(10, 34)), SamplingParams(max_new_tokens=3))
        young = eng.add_request(list(range(40, 56)), SamplingParams(max_new_tokens=3))
        done = run(eng)
        return done[old], done[young], launch_spans()

    def test_the_younger_prompt_is_booked_the_older_one_s_chunks(self, two_long_prompts):
        old, young, spans = two_long_prompts
        older_chunks = [s for s in spans if s.args["carried"] == [old.req_id]]
        assert len(older_chunks) == 3 and {s.name for s in older_chunks} == {"mixed_step"}
        assert young.prefill_behind_s == pytest.approx(sum(s.dur for s in older_chunks), rel=1e-9)
        assert young.prefill_steps == 2 and old.prefill_steps == 3
        assert old.prefill_behind_s == 0.0
        own = [s for s in spans if young.req_id in s.args["carried"]]
        assert young.prefill_own_s == pytest.approx(sum(s.dur for s in own), rel=1e-9)

    def test_own_and_behind_fit_inside_admission_to_first_token(self, two_long_prompts):
        for req in two_long_prompts[:2]:
            assert req.prefill_own_s > 0.0
            assert req.prefill_own_s + req.prefill_behind_s <= req.first_token_t - req.sched_t + 1e-4

    def test_launch_spans_say_whose_prompt_they_carried(self, two_long_prompts):
        old, young, spans = two_long_prompts
        assert spans and all("carried" in s.args and "prefill_waiting" in s.args for s in spans)
        assert all(s.args["carried"] == [] and s.args["prefill_waiting"] == 0
                   for s in spans if s.name == "decode")
        assert any(s.name == "decode" for s in spans)
        # while the older prompt's chunks ran, one admitted request waited for a row
        assert [s.args["prefill_waiting"] for s in spans if s.args["carried"] == [old.req_id]] == [1, 1, 1]
        assert all(s.args["prefill_waiting"] == 0 for s in spans if young.req_id in s.args["carried"])

    def test_one_chunk_row_shared_by_two_prompts_carries_both(self, model):
        eng = make_engine(model, prefill_chunk_tokens=16)
        eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=2))
        TRACER.clear()
        a = eng.add_request([5, 6, 7, 8], SamplingParams(max_new_tokens=2))
        b = eng.add_request([9, 6, 7, 8, 9], SamplingParams(max_new_tokens=2))
        done = run(eng)
        first = launch_spans()[0]
        assert first.name == "mixed_step" and first.args["carried"] == [a, b]
        assert first.args["prefill_waiting"] == 0
        for rid in (a, b):
            assert done[rid].prefill_steps == 1 and done[rid].prefill_behind_s == 0.0
            assert done[rid].prefill_own_s == pytest.approx(first.dur, rel=1e-9)


class TestMonolithicBooking:
    def test_one_prefill_launch_and_nothing_behind(self, model):
        eng = make_engine(model)
        eng.generate([[5, 6, 7]], SamplingParams(max_new_tokens=2))
        TRACER.clear()
        rid = eng.add_request([5, 6, 7, 8, 9], SamplingParams(max_new_tokens=6))
        req = run(eng)[rid]
        (prefill,) = [s for s in launch_spans() if s.name == "prefill"]
        assert prefill.args["carried"] == [rid] and prefill.args["prefill_waiting"] == 0
        assert req.prefill_steps == 1 and req.prefill_behind_s == 0.0
        assert req.prefill_own_s == pytest.approx(prefill.dur, rel=1e-9)
        assert req.prefill_own_s <= req.first_token_t - req.sched_t + 1e-4

    def test_a_later_bucket_waits_out_the_earlier_one(self, model):
        """Two prompts admitted in one step whose padded lengths differ launch
        one after the other: the second sat behind the first's launch."""
        eng = make_engine(model)
        eng.generate([[5, 6, 7], list(range(10, 50))], SamplingParams(max_new_tokens=2))
        TRACER.clear()
        short = eng.add_request([9, 6, 7, 8], SamplingParams(max_new_tokens=2))
        long = eng.add_request(list(range(50, 90)), SamplingParams(max_new_tokens=2))  # no cached prefix
        done = run(eng)
        first, second = [s for s in launch_spans() if s.name == "prefill"]
        assert first.args["carried"] == [short] and first.args["prefill_waiting"] == 1
        assert second.args["carried"] == [long] and second.args["prefill_waiting"] == 0
        assert done[long].prefill_behind_s == pytest.approx(first.dur, rel=1e-9)
        assert done[short].prefill_behind_s == 0.0
        assert done[short].prefill_steps == done[long].prefill_steps == 1


class TestPreemption:
    def test_nothing_is_booked_after_the_first_token(self, model):
        """A request preempted while decoding is prefilled again, and carried
        again, but its time to first token is over."""
        eng = make_engine(model, prefill_chunk_tokens=8)
        rid = eng.add_request(list(range(10, 22)), SamplingParams(max_new_tokens=12))
        while not any(r is not None and r.output_ids for r in eng.slots):
            eng.step()
        req = next(r for r in eng.slots if r is not None)
        booked = (req.prefill_steps, req.prefill_own_s, req.prefill_behind_s)
        assert booked[0] == 2 and req.first_token_t is not None
        TRACER.clear()
        eng._preempt(eng.slots.index(req))
        done = run(eng)[rid]
        assert done is req and len(done.prompt_ids) > 12  # the generated tokens joined the prompt
        again = [s for s in launch_spans() if rid in s.args["carried"]]
        assert len(again) >= 2  # carried again, chunk by chunk
        assert (req.prefill_steps, req.prefill_own_s, req.prefill_behind_s) == booked
