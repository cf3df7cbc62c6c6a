"""Engine-loop supervisor unit tests on a scripted stub engine (no jax
compute): degrade → triage → rebuild → requeue, bounded retries,
engine_error fast-clear, the 503 circuit breaker, stop() join reporting,
and the deadline/completion race.

The stub emits position-keyed tokens (token at absolute generated position p
is ``p % 50``), mirroring the real engine's (seed, absolute position)
sampling contract — so a requeued request whose streamed tokens were folded
into the prompt continues with identical tokens, and the tests can assert
exact end-to-end streams across a rebuild."""

import dataclasses
import threading
import time
from collections import Counter, deque

import pytest

from paddlenlp_tpu.serving import (
    DegradedError,
    EngineLoop,
    MetricsRegistry,
    Scheduler,
    SchedulerConfig,
    ServingMetrics,
    SupervisorPolicy,
)
from paddlenlp_tpu.utils.faults import FAULTS


@pytest.fixture(autouse=True)
def _clean_faults():
    FAULTS.reset()
    yield
    FAULTS.reset()


# a dataclass so dataclasses.replace works on the supervisor's requeue path
@dataclasses.dataclass
class Sampling:
    max_new_tokens: int = 4
    eos_after: int = 0  # stub-only: emit done=True (an "EOS") after N tokens


class StubMgr:
    def __init__(self, total=64):
        self.block_size = 4
        self.max_blocks_per_seq = 16
        self.total_usable_blocks = total
        self.num_free = total
        self.lengths = {}
        self.free_calls = Counter()

    def free_seq(self, req_id):
        self.free_calls[req_id] += 1
        self.lengths.pop(req_id, None)


class StubRequest:
    def __init__(self, req_id, prompt_ids, sampling, stream_cb, trace, arrival_t=None):
        self.req_id = req_id
        self.prompt_ids = list(prompt_ids)
        self.sampling = sampling or Sampling()
        self.stream_cb = stream_cb
        self.trace = trace
        self.output_ids = []
        self.done = False
        self.aborted = False
        self.finish_reason = None
        self.enqueued_t = time.time()
        self.arrival_t = self.enqueued_t if arrival_t is None else arrival_t
        self.sched_t = None
        self.first_token_t = None
        self.finish_t = None
        self.queue_wait = None
        self.ttft = None
        self.decode_time = None


class StubEngine:
    """One token per active request per step; position-keyed token values."""

    def __init__(self, max_batch_size=4, fail_on_step=(), step_hook=None,
                 fail_after_stream_on_step=None):
        self.mgr = StubMgr()
        self.max_batch_size = max_batch_size
        self.waiting = deque()
        self.slots = [None] * max_batch_size
        self.spec_stats = {"drafted": 0, "accepted": 0}
        self.num_preemptions = 0
        self.step_count = 0
        self.fail_on_step = set(fail_on_step)
        # emit that step's tokens (incl. a possible done=True), THEN raise —
        # the stream-closed-but-crash-ate-the-finish race
        self.fail_after_stream_on_step = fail_after_stream_on_step
        self.step_hook = step_hook  # called at step start (blocking tests)
        self.abort_calls = []
        self._ids = iter(range(10_000))

    # ----------------------------------------------------------- engine api
    cur_step = property(lambda self: self.step_count)
    last_step_device_s = 1e-3  # every stub step launches

    def add_request(self, prompt_ids, sampling=None, stream_cb=None, trace=None, arrival_t=None):
        req = StubRequest(next(self._ids), prompt_ids, sampling, stream_cb, trace, arrival_t)
        self.mgr.lengths[req.req_id] = len(req.prompt_ids)
        self.waiting.append(req)
        return req.req_id

    def has_work(self):
        return bool(self.waiting) or any(r is not None for r in self.slots)

    def abort(self, req_id):
        self.abort_calls.append(req_id)
        for i, req in enumerate(self.waiting):
            if req.req_id == req_id:
                del self.waiting[i]
                return self._finish_abort(req)
        for slot, req in enumerate(self.slots):
            if req is not None and req.req_id == req_id:
                self.slots[slot] = None
                return self._finish_abort(req)
        return None

    def _finish_abort(self, req):
        self.mgr.free_seq(req.req_id)
        req.done = True
        req.aborted = True
        req.finish_reason = "abort"
        req.finish_t = time.time()
        return req

    def release_request(self, req_id):
        """Slot-quarantine support, mirroring the real engine: drop the
        request + free its KV without touching finish fields."""
        for i, req in enumerate(self.waiting):
            if req.req_id == req_id:
                del self.waiting[i]
                return True
        for slot, req in enumerate(self.slots):
            if req is not None and req.req_id == req_id:
                self.mgr.free_seq(req_id)
                self.slots[slot] = None
                return True
        return False

    def stats(self):
        return {"queue_depth": len(self.waiting),
                "running": sum(1 for r in self.slots if r is not None),
                "free_blocks": self.mgr.num_free,
                "num_preemptions": self.num_preemptions}

    def reset(self):
        self.waiting.clear()
        self.slots = [None] * self.max_batch_size
        self.mgr = StubMgr()

    def step(self):
        self.step_count += 1
        if self.step_hook is not None:
            self.step_hook(self)
        if self.step_count in self.fail_on_step:
            raise RuntimeError(f"stub engine exploded at step {self.step_count}")
        finished = []
        for i in range(self.max_batch_size):
            if self.slots[i] is None and self.waiting:
                req = self.waiting.popleft()
                req.sched_t = time.time()
                self.slots[i] = req
        for i, req in enumerate(self.slots):
            if req is None:
                continue
            pos = len(req.prompt_ids) + len(req.output_ids)  # absolute position
            tok = pos % 50
            if req.first_token_t is None:
                req.first_token_t = time.time()
            req.output_ids.append(tok)
            eos_after = getattr(req.sampling, "eos_after", 0)
            req.done = (len(req.output_ids) >= req.sampling.max_new_tokens
                        or (eos_after and len(req.output_ids) >= eos_after))
            if req.stream_cb is not None:
                try:
                    req.stream_cb(tok, req.done)
                except Exception as e:
                    # per-request attribution, mirroring the real engine's
                    # _emit: a poisoned callback names its request
                    if getattr(e, "req_id", None) is None:
                        e.req_id = req.req_id
                    raise
            if req.done:
                req.finish_reason = "length"
                req.finish_t = time.time()
                self.mgr.free_seq(req.req_id)
                self.slots[i] = None
                finished.append(req)
        if self.step_count == self.fail_after_stream_on_step:
            raise RuntimeError(f"stub engine exploded AFTER streaming at step {self.step_count}")
        return finished


def expected_tokens(prompt, n):
    return [(len(prompt) + i) % 50 for i in range(n)]


def make_loop(fail_on_step=(), factory_fails=0, policy=None, **kw):
    """Loop + factory that counts engines; engine #1 fails at the given steps."""
    made = []

    def factory():
        eng = StubEngine(fail_on_step=fail_on_step if not made else ())
        made.append(eng)
        return eng

    engine = factory()
    loop = EngineLoop(engine, metrics=ServingMetrics(engine, MetricsRegistry()),
                      engine_factory=factory,
                      policy=policy or SupervisorPolicy(backoff_base_s=0.02, backoff_max_s=0.1),
                      idle_wait_s=0.01, **kw)
    return loop, made


class TestSupervisor:
    def test_retry_across_rebuild_streams_identical_tokens(self):
        loop, made = make_loop(fail_on_step=(3,))
        loop.start()
        try:
            prompt = [7, 8, 9]
            h = loop.submit(prompt, Sampling(max_new_tokens=6))
            req = h.result(timeout=10)
            # 2 tokens streamed pre-crash + 4 post-rebuild == uninterrupted run
            assert req.output_ids == expected_tokens(prompt, 6)
            assert list(h._streamed) == expected_tokens(prompt, 6)
            assert req.finish_reason == "length"
            assert req.prompt_ids == prompt  # retry suffix unfolded
            assert h.retries == 1
            assert len(made) == 2  # original + rebuild
            assert loop.metrics.engine_restarts.value() == 1
            assert loop.metrics.request_retries.value() == 1
            assert loop.state == "running"
        finally:
            assert loop.stop(drain=False) is True

    def test_retry_budget_exhausted_fails_engine_error(self):
        # both the first AND second engines fail -> a max_retries=1 request
        # rides one rebuild then fast-clears on the second failure
        made = []

        def factory():
            eng = StubEngine(fail_on_step=(2,) if len(made) < 2 else ())
            made.append(eng)
            return eng

        engine = factory()
        registry = MetricsRegistry()
        loop = EngineLoop(engine, metrics=ServingMetrics(engine, registry),
                          engine_factory=factory,
                          policy=SupervisorPolicy(max_retries=1, backoff_base_s=0.02),
                          idle_wait_s=0.01)
        loop.start()
        try:
            h = loop.submit([1, 2], Sampling(max_new_tokens=8))
            req = h.result(timeout=10)
            assert req.finish_reason == "engine_error"
            assert h.retries == 1
            # whatever streamed before the final failure is preserved
            assert req.output_ids == list(h._streamed)
            assert registry.get("paddlenlp_serving_requests_total").value(status="engine_error", priority="interactive", tenant="default") == 1
        finally:
            loop.stop(drain=False)

    def test_max_retries_zero_fast_clears(self):
        loop, _made = make_loop(fail_on_step=(2,))
        loop.start()
        try:
            h_keep = loop.submit([1, 2, 3], Sampling(max_new_tokens=5))
            h_fail = loop.submit([4, 5, 6], Sampling(max_new_tokens=5), max_retries=0)
            req_fail = h_fail.result(timeout=10)
            req_keep = h_keep.result(timeout=10)
            assert req_fail.finish_reason == "engine_error"
            assert req_keep.finish_reason == "length"
            assert req_keep.output_ids == expected_tokens([1, 2, 3], 5)
        finally:
            loop.stop(drain=False)

    def test_degraded_circuit_breaker_503(self):
        FAULTS.arm("engine.rebuild", nth=1)  # first rebuild attempt fails
        loop, _ = make_loop(fail_on_step=(2,),
                            policy=SupervisorPolicy(backoff_base_s=0.3, backoff_max_s=1.0))
        sched = Scheduler(loop, SchedulerConfig(max_inflight=8))
        loop.start()
        try:
            h = sched.submit([1, 2], Sampling(max_new_tokens=8))
            deadline = time.time() + 5
            while not loop.degraded and time.time() < deadline:
                time.sleep(0.005)
            assert loop.degraded
            with pytest.raises(DegradedError) as ei:
                sched.submit([3, 4], Sampling(max_new_tokens=2))
            assert ei.value.retry_after_s > 0
            assert sched.stats()["rejected_degraded"] >= 1
            assert sched.stats()["engine_state"] == "degraded"
            # recovery completes the original request despite the failed rebuild
            req = h.result(timeout=10)
            assert req.finish_reason == "length"
            assert loop.state == "running"
            # and admission works again
            h2 = sched.submit([9], Sampling(max_new_tokens=2))
            assert h2.result(timeout=10).finish_reason == "length"
        finally:
            loop.stop(drain=False)

    def test_stream_closed_request_not_requeued_past_eos(self):
        """A request whose done=True (EOS) token streamed in the crashing step
        must resolve as finished — requeueing it would generate past the end
        of a completed sequence."""
        made = []

        def factory():
            eng = StubEngine(fail_after_stream_on_step=2 if not made else None)
            made.append(eng)
            return eng

        engine = factory()
        loop = EngineLoop(engine, metrics=ServingMetrics(engine, MetricsRegistry()),
                          engine_factory=factory,
                          policy=SupervisorPolicy(backoff_base_s=0.02), idle_wait_s=0.01)
        loop.start()
        try:
            # EOS after 2 tokens (mid-budget): the done token lands on exactly
            # the step that then explodes
            h = loop.submit([1, 2, 3], Sampling(max_new_tokens=10, eos_after=2))
            req = h.result(timeout=10)
            assert req.finish_reason == "stop"
            assert req.output_ids == expected_tokens([1, 2, 3], 2)  # nothing past EOS
            assert h.retries == 0
            # budget-exhausted variant of the same race resolves as "length"
            h2 = loop.submit([4, 5], Sampling(max_new_tokens=3))
            assert h2.result(timeout=10).finish_reason == "length"
        finally:
            loop.stop(drain=False)

    def test_cancel_racing_crash_resolves_as_abort(self):
        release = threading.Event()

        def hook(eng):
            if eng.step_count == 2:
                release.wait(timeout=5)  # hold step 2 open while we cancel
                raise RuntimeError("boom during the held step")

        engine = StubEngine(step_hook=hook)
        registry = MetricsRegistry()
        loop = EngineLoop(engine, metrics=ServingMetrics(engine, registry),
                          policy=SupervisorPolicy(backoff_base_s=0.02), idle_wait_s=0.01)
        loop.start()
        try:
            h = loop.submit([1, 2], Sampling(max_new_tokens=10))
            while not h._streamed:  # one token delivered
                time.sleep(0.005)
            loop.cancel(h)  # sets _cancelled synchronously; cmd never drains
            release.set()  # now the engine explodes with the cancel pending
            req = h.result(timeout=10)
            assert req.finish_reason == "abort" and req.aborted
            assert registry.get("paddlenlp_serving_requests_total").value(status="abort", priority="interactive", tenant="default") == 1
            assert registry.get("paddlenlp_serving_requests_total").value(status="engine_error", priority="interactive", tenant="default") == 0
        finally:
            loop.stop(drain=False)

    def test_retry_timing_spans_degraded_window(self):
        loop, _made = make_loop(fail_on_step=(3,),
                                policy=SupervisorPolicy(backoff_base_s=0.2, backoff_max_s=0.5))
        loop.start()
        try:
            h = loop.submit([7, 8, 9], Sampling(max_new_tokens=6))
            req = h.result(timeout=10)
            # timing anchors rebased to the ORIGINAL submission, so e2e/TTFT
            # include the pre-crash stint and the degraded window
            assert req.arrival_t == h.submitted_t
            assert req.first_token_t == h._first_token_t
            assert req.finish_t - req.arrival_t >= 0.2  # covers >= one backoff
        finally:
            loop.stop(drain=False)

    def test_stop_reports_failed_join_with_phase(self):
        release = threading.Event()

        def hook(_eng):
            release.wait(timeout=30)

        engine = StubEngine(step_hook=hook)
        loop = EngineLoop(engine, metrics=ServingMetrics(engine, MetricsRegistry()),
                          idle_wait_s=0.01)
        loop.start()
        h = loop.submit([1], Sampling(max_new_tokens=1))
        time.sleep(0.1)  # loop is now blocked inside engine.step
        assert loop.stop(drain=False, join_timeout_s=0.2) is False
        assert loop._phase == "step"  # last-known phase of the wedged thread
        release.set()
        h.result(timeout=10)
        assert loop.stop(drain=False, join_timeout_s=10.0) is True

    def test_stop_while_degraded_resolves_stash(self):
        # rebuild never succeeds -> requests sit in the requeue stash; stop()
        # must resolve them (result() returns None) instead of stranding clients
        made = []

        def bad_factory():
            made.append(1)
            raise RuntimeError("no engine for you")

        engine = StubEngine(fail_on_step=(2,))
        loop = EngineLoop(engine, metrics=ServingMetrics(engine, MetricsRegistry()),
                          engine_factory=bad_factory,
                          policy=SupervisorPolicy(backoff_base_s=0.02, backoff_max_s=0.05),
                          idle_wait_s=0.01)
        loop.start()
        h = loop.submit([1, 2], Sampling(max_new_tokens=8))
        deadline = time.time() + 5
        while not loop.degraded and time.time() < deadline:
            time.sleep(0.005)
        assert loop.stop(drain=False, join_timeout_s=10.0) is True
        assert h.result(timeout=1) is None


class TestDeadlineCompletionRace:
    def test_finish_and_deadline_same_iteration(self):
        """A request that finishes in the same loop iteration its deadline
        expires must resolve exactly once as finished — never double-finished,
        never a double KV free, never a post-finish abort."""
        def hook(_eng):
            time.sleep(0.15)  # deadline (0.06s) expires INSIDE this step

        engine = StubEngine(step_hook=hook)
        loop = EngineLoop(engine, metrics=ServingMetrics(engine, MetricsRegistry()),
                          idle_wait_s=0.01)
        loop.start()
        try:
            h = loop.submit([1, 2, 3], Sampling(max_new_tokens=1), deadline_s=0.06)
            req = h.result(timeout=10)
            # completion won the race: not clawed back by deadline enforcement
            assert req.finish_reason == "length"
            assert h.timed_out is False
            assert engine.mgr.free_calls[req.req_id] == 1  # KV freed exactly once
            assert engine.abort_calls == []  # no abort issued for a done request
            # a late cancel on the finished handle is also a no-op
            loop.cancel(h)
            time.sleep(0.1)
            assert engine.abort_calls == []
            assert engine.mgr.free_calls[req.req_id] == 1
        finally:
            loop.stop(drain=False)

    def test_deadline_wins_when_request_not_done(self):
        release = threading.Event()

        def hook(eng):
            # park the loop long enough for the deadline to expire before the
            # FIRST token is produced, then let it continue
            if eng.step_count == 1:
                release.wait(timeout=5)

        engine = StubEngine(step_hook=hook)
        loop = EngineLoop(engine, metrics=ServingMetrics(engine, MetricsRegistry()),
                          idle_wait_s=0.01)
        loop.start()
        try:
            h = loop.submit([1, 2, 3], Sampling(max_new_tokens=50), deadline_s=0.05)
            time.sleep(0.1)
            release.set()
            req = h.result(timeout=10)
            assert h.timed_out and req.aborted and req.finish_reason == "abort"
            assert engine.mgr.free_calls[req.req_id] <= 1
        finally:
            loop.stop(drain=False)


class TestSlotQuarantine:
    """Slot-level partial recovery (ISSUE 11): a failure the engine attributed
    to ONE request quarantines only that slot — unaffected streams never
    pause, the engine is never rebuilt, the 503 breaker never trips — with a
    bounded escalation ladder back to the full rebuild path."""

    @staticmethod
    def _poison(handle, after=0):
        """Make the handle's stream callback raise once ``after`` tokens have
        been delivered (the engine attributes the failure to this request)."""
        orig = handle._on_token
        seen = {"n": 0}

        def boom(tok, done):
            if seen["n"] >= after:
                raise RuntimeError("poisoned stream callback")
            seen["n"] += 1
            orig(tok, done)

        handle._on_token = boom

    def test_poisoned_request_quarantined_not_rebuilt(self):
        loop, made = make_loop()
        loop.start()
        try:
            healthy = loop.submit([1, 2], Sampling(max_new_tokens=6))
            bad = loop.submit([9], Sampling(max_new_tokens=6))
            self._poison(bad)
            # the poisoned request fails alone, in-band
            bad_req = bad.result(timeout=30)
            assert bad_req.finish_reason == "engine_error"
            # the healthy stream never paused: full token-exact output, no
            # requeue, no rebuild, loop still running
            req = healthy.result(timeout=30)
            assert req.finish_reason == "length"
            assert list(healthy.output_ids) == expected_tokens([1, 2], 6)
            assert healthy.retries == 0
            assert len(made) == 1  # the factory never ran again
            assert loop.state == "running"
            assert loop.slot_quarantines == 1
            reg = loop.metrics.registry
            assert reg.get("paddlenlp_serving_slot_quarantines_total").value() == 1
            assert reg.get("paddlenlp_serving_engine_restarts_total").value() == 0
            # engine-side: the poisoned slot + its KV were released
            eng = made[0]
            assert all(r is None for r in eng.slots)
            assert eng.mgr.lengths == {}
        finally:
            loop.stop(drain=False)

    def test_finished_request_swept_not_blamed(self):
        """A request that finished in the SAME step the poison killed must
        resolve as its completion (the crash only ate the bookkeeping)."""
        loop, made = make_loop()
        loop.start()
        try:
            done_h = loop.submit([1, 2, 3], Sampling(max_new_tokens=1))
            bad = loop.submit([9], Sampling(max_new_tokens=6))
            self._poison(bad)
            assert bad.result(timeout=30).finish_reason == "engine_error"
            req = done_h.result(timeout=30)
            assert req.finish_reason == "length"
            assert list(done_h.output_ids) == expected_tokens([1, 2, 3], 1)
            assert len(made) == 1 and loop.state == "running"
        finally:
            loop.stop(drain=False)

    def test_quarantine_budget_escalates_to_full_rebuild(self):
        loop, made = make_loop(policy=SupervisorPolicy(
            max_slot_quarantines=1, max_retries=0,
            backoff_base_s=0.02, backoff_max_s=0.1))
        loop.start()
        try:
            h1 = loop.submit([1], Sampling(max_new_tokens=4))
            self._poison(h1)
            assert h1.result(timeout=30).finish_reason == "engine_error"
            assert len(made) == 1 and loop.slot_quarantines == 1
            # second poison inside the window: budget spent -> full rebuild
            h2 = loop.submit([2], Sampling(max_new_tokens=4))
            self._poison(h2)
            assert h2.result(timeout=30).finish_reason == "engine_error"
            deadline = time.time() + 10
            while time.time() < deadline and not (len(made) == 2
                                                  and loop.state == "running"):
                time.sleep(0.01)
            assert len(made) == 2  # escalation really rebuilt the engine
            assert loop.slot_quarantines == 1  # no second quarantine
            reg = loop.metrics.registry
            assert reg.get("paddlenlp_serving_engine_restarts_total").value() == 1
        finally:
            loop.stop(drain=False)

    def test_slot_rebuild_fault_escalates(self):
        """engine.slot_rebuild armed: the quarantine itself fails (before KV
        release) and the supervisor falls back to the full rebuild path."""
        FAULTS.arm("engine.slot_rebuild", nth=1)
        loop, made = make_loop(policy=SupervisorPolicy(
            max_retries=0, backoff_base_s=0.02, backoff_max_s=0.1))
        loop.start()
        try:
            h = loop.submit([1], Sampling(max_new_tokens=4))
            self._poison(h)
            assert h.result(timeout=30).finish_reason == "engine_error"
            assert FAULTS.fired("engine.slot_rebuild") == 1
            deadline = time.time() + 10
            while time.time() < deadline and len(made) < 2:
                time.sleep(0.01)
            assert len(made) == 2  # escalated: engine rebuilt
            assert loop.slot_quarantines == 0  # the quarantine never landed
        finally:
            loop.stop(drain=False)

    def test_unaffected_stream_tokens_flow_during_quarantine(self):
        """Stream continuity: the healthy handle's token queue keeps draining
        while the poisoned slot is quarantined (no degraded pause, no 503)."""
        loop, made = make_loop()
        loop.start()
        scheduler = Scheduler(loop, SchedulerConfig(max_inflight=8))
        try:
            healthy = scheduler.submit([1, 2], Sampling(max_new_tokens=8))
            bad = scheduler.submit([9], Sampling(max_new_tokens=8))
            self._poison(bad, after=1)
            toks = list(healthy.tokens(timeout=30))
            assert toks == expected_tokens([1, 2], 8)
            assert bad.result(timeout=30).finish_reason == "engine_error"
            # the breaker never tripped: a new admission sails through
            extra = scheduler.submit([3], Sampling(max_new_tokens=2))
            assert extra.result(timeout=30).finish_reason == "length"
            assert scheduler.stats()["slot_quarantines"] == 1
            assert loop.state == "running"
        finally:
            loop.stop(drain=False)
