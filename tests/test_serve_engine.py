"""Continuous-batching engine edge cases + telemetry (repro.serve.engine)."""
import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.control.telemetry import TickSample
from repro.models.model import Model
from repro.serve.engine import Engine, Request


@pytest.fixture(scope="module")
def dense():
    cfg = registry.get("llama3.2-1b").reduced()
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def _eng(model, params, **kw):
    kw.setdefault("batch_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("eos_id", -1)
    kw.setdefault("warmup", False)
    return Engine(model, params, **kw)


class TestMidFlightAdmission:
    def test_admission_mid_decode_matches_solo(self, dense):
        """A request admitted while another is mid-decode must produce the
        same greedy tokens as running alone (the ragged pos/n_valid
        contract: no global position barrier)."""
        cfg, model, params = dense
        pa = np.arange(5) % cfg.vocab_size
        pb = (np.arange(7) * 2 + 1) % cfg.vocab_size

        solo = _eng(model, params)
        solo.submit(Request(0, pb, max_new=6))
        ref = solo.run()[0].out

        eng = _eng(model, params)
        eng.submit(Request(0, pa, max_new=12))
        for _ in range(4):  # A is now several tokens into decode
            eng.step()
        assert any(r is not None for r in eng.slot_req)
        eng.submit(Request(1, pb, max_new=6))
        done = {r.rid: r for r in eng.run()}
        assert done[1].out == ref

    def test_staggered_prompts_all_match_solo(self, dense):
        cfg, model, params = dense
        prompts = [(np.arange(3 + 4 * i) * (i + 1)) % cfg.vocab_size
                   for i in range(3)]
        refs = []
        for i, p in enumerate(prompts):
            e = _eng(model, params)
            e.submit(Request(i, p, max_new=5))
            refs.append(e.run()[0].out)

        eng = _eng(model, params, batch_slots=2)  # 3 reqs through 2 slots
        for i, p in enumerate(prompts):
            eng.submit(Request(i, p, max_new=5))
        done = {r.rid: r.out for r in eng.run()}
        assert [done[i] for i in range(3)] == refs


class TestSlotRecycling:
    def test_many_requests_reuse_slots_without_growth(self, dense):
        cfg, model, params = dense
        eng = _eng(model, params, batch_slots=2)
        shapes0 = [x.shape for x in jax.tree_util.tree_leaves(eng.cache)]
        for rid in range(6):
            eng.submit(Request(rid, np.arange(4 + rid % 3) % cfg.vocab_size,
                               max_new=4))
        done = eng.run()
        assert len(done) == 6
        shapes1 = [x.shape for x in jax.tree_util.tree_leaves(eng.cache)]
        assert shapes0 == shapes1  # recycling, not reallocation
        assert eng.mgr.pages_in_use == 0
        assert 0 < eng.mgr.peak_pages <= eng.mgr.total_pages


class TestEdgeCases:
    def test_admit_cap_zero_starves_then_recovers(self, dense):
        cfg, model, params = dense
        eng = _eng(model, params, admit_cap=0)
        eng.submit(Request(0, np.arange(4) % cfg.vocab_size, max_new=3))
        samples = []
        eng.on_tick.append(samples.append)
        for _ in range(3):
            assert eng.step()  # work exists, none admitted
        assert not eng.finished and len(eng.queue) == 1
        # starvation is VISIBLE: every throttled step emitted telemetry
        assert len(samples) == 3
        assert all(s.queued == 1 and s.admitted == 0 and s.tokens == 0
                   for s in samples)
        assert samples[-1].oldest_wait == 2.0
        eng.admit_cap = None  # Throttle(None) lifts the cap
        done = eng.run()
        assert len(done) == 1 and len(done[0].out) == 3

    def test_prompt_at_or_over_max_len_rejected(self, dense):
        cfg, model, params = dense
        eng = _eng(model, params, max_len=16)
        eng.submit(Request(0, np.arange(16) % cfg.vocab_size, max_new=2))
        eng.submit(Request(1, np.arange(40) % cfg.vocab_size, max_new=2))
        eng.submit(Request(2, np.arange(4) % cfg.vocab_size, max_new=2))
        done = {r.rid: r for r in eng.run()}
        assert done[0].error == "prompt_too_long" and done[0].out == []
        assert done[1].error == "prompt_too_long"
        assert done[2].error is None and len(done[2].out) == 2
        assert len(eng.mgr.free_slots) == eng.B  # nothing leaked

    def test_eos_on_first_decode_tick(self, dense):
        cfg, model, params = dense
        prompt = np.arange(5) % cfg.vocab_size
        probe = _eng(model, params)
        probe.submit(Request(0, prompt, max_new=4))
        first = probe.run()[0].out[0]

        eng = _eng(model, params, eos_id=first)
        eng.submit(Request(0, prompt, max_new=4))
        done = eng.run()
        assert done[0].out == [first] and done[0].done
        assert len(eng.mgr.free_slots) == eng.B  # slot freed immediately

    def test_run_on_empty_queue_is_a_noop(self, dense):
        _, model, params = dense
        eng = _eng(model, params)
        samples = []
        eng.on_tick.append(samples.append)
        assert eng.run() == []
        assert eng.step() is False
        assert samples == [] and eng.ticks == 0


class TestPreemption:
    """§9 thermal-emergency preemption: evict -> host page pool -> resume
    bitwise-identical."""

    def _refs(self, model, params, prompts, max_new=8):
        refs = []
        for i, p in enumerate(prompts):
            e = _eng(model, params)
            e.submit(Request(i, p, max_new=max_new))
            refs.append(e.run()[0].out)
        return refs

    def test_preempt_resume_is_bitwise_identical(self, dense):
        cfg, model, params = dense
        prompts = [np.arange(5) % cfg.vocab_size,
                   (np.arange(7) * 2 + 1) % cfg.vocab_size]
        refs = self._refs(model, params, prompts)

        eng = _eng(model, params)
        for i, p in enumerate(prompts):
            eng.submit(Request(i, p, max_new=8))
        for _ in range(3):  # both mid-decode
            eng.step()
        assert eng.preempt_to(0) == 2  # full eviction to the page pool
        assert len(eng.pool) == 2 and sorted(eng.mgr.free_slots) == [0, 1]
        assert all(r is None for r in eng.slot_req)
        done = {r.rid: r for r in eng.run()}
        assert [done[i].out for i in range(2)] == refs
        assert all(done[i].preempts == 1 for i in range(2))
        assert eng.preempts == 2 and len(eng.pool) == 0

    def test_low_priority_newest_evicted_first(self, dense):
        cfg, model, params = dense
        eng = _eng(model, params)
        eng.submit(Request(0, np.arange(4) % cfg.vocab_size, max_new=8,
                           priority=1))  # premium
        eng.step()
        eng.submit(Request(1, np.arange(6) % cfg.vocab_size, max_new=8))
        eng.step()
        assert eng.preempt_to(1) == 1
        kept = [r for r in eng.slot_req if r is not None]
        assert len(kept) == 1 and kept[0].rid == 0  # premium survives
        assert eng.queue[0].rid == 1 and 1 in eng.pool

    def test_preempt_mid_prefill_resumes_the_stream(self, dense):
        cfg, model, params = dense
        prompt = np.arange(11) % cfg.vocab_size
        ref = self._refs(model, params, [prompt], max_new=6)[0]
        eng = _eng(model, params, prefill_chunk=4)
        eng.submit(Request(0, prompt, max_new=6))
        eng.step()  # one 4-token chunk fed: mid-prefill
        req = next(r for r in eng.slot_req if r is not None)
        assert 0 < req.fed < len(prompt)
        eng.preempt_to(0)
        assert eng.run()[0].out == ref

    def test_preempt_to_is_a_noop_when_under_cap(self, dense):
        cfg, model, params = dense
        eng = _eng(model, params)
        eng.submit(Request(0, np.arange(4) % cfg.vocab_size, max_new=4))
        eng.step()
        assert eng.preempt_to(2) == 0
        assert eng.preempts == 0 and len(eng.pool) == 0


class TestTelemetry:
    def test_every_step_emits_one_sample(self, dense):
        cfg, model, params = dense
        eng = _eng(model, params)
        samples = []
        eng.on_tick.append(samples.append)
        eng.submit(Request(0, np.arange(4) % cfg.vocab_size, max_new=3))
        eng.submit(Request(1, np.arange(6) % cfg.vocab_size, max_new=3))
        steps = 0
        while True:  # count CALLS: the final productive step returns False
            steps += 1
            if not eng.step():
                break
        assert len(samples) == steps
        assert all(isinstance(s, TickSample) for s in samples)
        assert samples[0].admitted == 2  # both fit the 2 slots at once
        assert sum(s.tokens for s in samples) == 6
        assert samples[-1].finished == 2 and samples[-1].active == 0
        assert [s.tick for s in samples] == list(range(steps))
        assert all(s.slots == eng.B for s in samples)
