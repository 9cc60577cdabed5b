"""Continuous-batching serving subsystem tests (serving/).

Host-side pieces (SlotAllocator, ContinuousBatchScheduler) run at CPU
speed with an injected fake clock; the ServingEngine integration tests
compile a deliberately tiny GPT so the quick tier stays quick. The
throughput comparison against sequential ``generate`` needs a model wide
enough that compute dominates dispatch, so it lives in the slow tier.
"""

import os

import numpy as np
import pytest

from deepspeed_tpu.serving import (REJECT_DEADLINE_EXPIRED,
                                   REJECT_PROMPT_TOO_LONG,
                                   REJECT_QUEUE_FULL,
                                   ContinuousBatchScheduler, Request,
                                   Reservoir, ServingEngine,
                                   ServingMetrics, SlotAllocator,
                                   csv_monitor_master)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# --------------------------------------------------------------- allocator
class TestSlotAllocator:
    def test_alloc_lowest_first_and_exhaustion(self):
        a = SlotAllocator(max_batch=3, max_seq_len=16)
        assert [a.alloc(), a.alloc(), a.alloc()] == [0, 1, 2]
        assert a.alloc() is None                    # pool exhausted
        assert a.n_active == 3 and a.n_free == 0
        assert a.occupancy == 1.0

    def test_free_reissues_lowest_slot(self):
        a = SlotAllocator(max_batch=3, max_seq_len=16)
        for _ in range(3):
            a.alloc()
        a.free(1)
        a.free(0)
        assert a.alloc() == 0                       # lowest free wins
        assert a.alloc() == 1

    def test_fill_tracking_and_advance(self):
        a = SlotAllocator(max_batch=2, max_seq_len=8)
        s = a.alloc(fill_len=5)
        assert a.fill[s] == 5 and a.remaining(s) == 3
        a.advance([s])
        assert a.fill[s] == 6
        a.free(s)
        assert a.fill[s] == 0 and not a.active[s]

    def test_errors(self):
        a = SlotAllocator(max_batch=1, max_seq_len=4)
        with pytest.raises(ValueError):
            a.alloc(fill_len=5)                     # beyond the cache row
        with pytest.raises(ValueError):
            a.free(0)                               # never leased
        with pytest.raises(ValueError):
            SlotAllocator(max_batch=0, max_seq_len=4)


# --------------------------------------------------------------- scheduler
def _sched(max_batch=2, max_seq=32, **kw):
    clock = kw.pop("clock", FakeClock())
    alloc = SlotAllocator(max_batch, max_seq)
    return ContinuousBatchScheduler(alloc, clock=clock, **kw), alloc, clock


class TestScheduler:
    def test_fifo_admission_order(self):
        sched, _, _ = _sched(max_batch=2)
        reqs = [Request(prompt=[1, 2], max_new_tokens=4) for _ in range(4)]
        for r in reqs:
            assert sched.submit(r)
        admitted = sched.admit()
        # first two submitted get the two slots, in order, lowest slot first
        assert [r.uid for r in admitted] == [reqs[0].uid, reqs[1].uid]
        assert [r.slot for r in admitted] == [0, 1]
        assert sched.queue_depth == 2
        assert all(r.status == "running" for r in admitted)

    def test_admit_stamps_when_the_lane_was_leased(self):
        sched, _, clock = _sched(max_batch=1)
        a, b = (Request(prompt=[1, 2], max_new_tokens=1) for _ in range(2))
        clock.advance(1.0)
        sched.submit(a)
        sched.submit(b)
        clock.advance(2.0)
        assert sched.admit() == [a]
        assert (a.submit_t, a.admit_t) == (1.0, 3.0)
        assert b.admit_t is None                 # still queued: no lane
        clock.advance(4.0)
        sched.record_first_token(a, 5)           # a retires, its lane frees
        assert sched.admit() == [b] and b.admit_t == 7.0

    def test_queue_full_rejection(self):
        sched, _, _ = _sched(max_batch=1, max_queue=2)
        accepted = [sched.submit(Request(prompt=[1], max_new_tokens=4))
                    for _ in range(3)]
        assert accepted == [True, True, False]
        assert sched.n_rejected == 1
        extra = Request(prompt=[1], max_new_tokens=4)
        assert not sched.submit(extra)
        assert extra.status == "rejected"
        assert extra.reject_reason == REJECT_QUEUE_FULL

    def test_prompt_too_long_rejection(self):
        sched, _, _ = _sched(max_batch=1, max_seq=16, max_prompt_len=8)
        r = Request(prompt=list(range(9)), max_new_tokens=1)
        assert not sched.submit(r)
        assert r.reject_reason == REJECT_PROMPT_TOO_LONG
        # fits the prefill bucket but prompt + budget overflows the row
        r2 = Request(prompt=list(range(8)), max_new_tokens=16)
        assert not sched.submit(r2)
        assert r2.reject_reason == REJECT_PROMPT_TOO_LONG

    def test_max_new_tokens_termination(self):
        sched, alloc, _ = _sched(max_batch=1)
        r = Request(prompt=[1, 2], max_new_tokens=3)
        sched.submit(r)
        (req,) = sched.admit()
        sched.record_first_token(req, 10)
        assert sched.step_tokens_chunk({req.slot: [11]}) == []
        done = sched.step_tokens_chunk({0: [12]})
        assert done == [r] and r.status == "done"
        assert r.tokens == [10, 11, 12]
        assert list(r.output_ids) == [1, 2, 10, 11, 12]
        assert alloc.n_free == 1                    # slot released

    def test_eos_termination(self):
        sched, _, _ = _sched(max_batch=1)
        r = Request(prompt=[1], max_new_tokens=20, eos_token_id=7)
        sched.submit(r)
        sched.admit()
        sched.record_first_token(r, 3)
        done = sched.step_tokens_chunk({r.slot: [7]})
        assert done == [r] and r.status == "done"
        assert r.tokens == [3, 7]                   # EOS included

    def test_immediate_finish_on_first_token(self):
        sched, alloc, _ = _sched(max_batch=1)
        r = Request(prompt=[1], max_new_tokens=1)
        sched.submit(r)
        sched.admit()
        sched.record_first_token(r, 5)
        assert r.status == "done" and alloc.n_free == 1
        assert not sched.has_work()

    def test_deadline_sheds_queued_request(self):
        clock = FakeClock()
        sched, _, _ = _sched(max_batch=1, clock=clock)
        keep = Request(prompt=[1], max_new_tokens=2)
        late = Request(prompt=[2], max_new_tokens=2, deadline_s=5.0)
        sched.submit(keep)
        sched.submit(late)
        sched.admit()                               # keep takes the slot
        clock.advance(10.0)                         # late expires in queue
        sched.record_first_token(keep, 1)
        sched.step_tokens_chunk({keep.slot: [2]})    # frees the slot
        assert sched.admit() == []                  # late shed, not admitted
        assert late.status == "expired" and sched.n_expired == 1
        assert not sched.has_work()

    def test_deadline_expires_running_request(self):
        clock = FakeClock()
        sched, alloc, _ = _sched(max_batch=1, clock=clock)
        r = Request(prompt=[1], max_new_tokens=20, deadline_s=5.0)
        sched.submit(r)
        sched.admit()
        sched.record_first_token(r, 1)
        clock.advance(10.0)
        done = sched.step_tokens_chunk({r.slot: [2]})
        assert done == [r] and r.status == "expired"
        assert alloc.n_free == 1

    def test_already_expired_deadline_rejected_at_submit(self):
        """A deadline in the past can never be met: submit must reject
        with a reason instead of queueing work that would prefill and die
        at the first chunk boundary."""
        clock = FakeClock(10.0)
        sched, _, _ = _sched(max_batch=1, clock=clock)
        r = Request(prompt=[1], max_new_tokens=4, deadline_s=9.0)
        assert not sched.submit(r)
        assert r.status == "rejected"
        assert r.reject_reason == REJECT_DEADLINE_EXPIRED
        assert sched.n_rejected == 1 and sched.queue_depth == 0
        # a deadline exactly at now is equally unmeetable
        r2 = Request(prompt=[1], max_new_tokens=4, deadline_s=10.0)
        assert not sched.submit(r2)
        assert r2.reject_reason == REJECT_DEADLINE_EXPIRED

    def test_cancel_queued_and_running(self):
        sched, alloc, _ = _sched(max_batch=1)
        a = Request(prompt=[1], max_new_tokens=8)
        b = Request(prompt=[2], max_new_tokens=8)
        sched.submit(a)
        sched.submit(b)
        sched.admit()
        sched.record_first_token(a, 1)
        assert sched.cancel(b) is True              # still queued
        assert b.status == "cancelled" and sched.queue_depth == 0
        assert sched.cancel(a) is True              # running: frees slot
        assert a.status == "cancelled" and alloc.n_free == 1
        assert sched.n_cancelled == 2
        assert sched.cancel(a) is False             # already terminal
        assert not sched.has_work()
        assert sched.finished == [b, a]

    def test_slot_reuse_admits_next_queued(self):
        sched, _, _ = _sched(max_batch=1)
        a = Request(prompt=[1], max_new_tokens=1)
        b = Request(prompt=[2], max_new_tokens=1)
        sched.submit(a)
        sched.submit(b)
        (first,) = sched.admit()
        assert first is a and b.status == "queued"
        sched.record_first_token(a, 9)              # retires a, frees slot 0
        (second,) = sched.admit()
        assert second is b and b.slot == 0          # reuses the same row

    def test_ttft_uses_clock(self):
        clock = FakeClock()
        sched, _, _ = _sched(max_batch=1, clock=clock)
        r = Request(prompt=[1], max_new_tokens=2)
        sched.submit(r)
        clock.advance(0.25)
        sched.admit()
        sched.record_first_token(r, 1)
        assert r.ttft_s == pytest.approx(0.25)

    def test_step_tokens_chunk_matches_per_token_calls(self):
        """A chunk's token list must behave exactly like as many calls
        of width one: per-token allocator advance, termination mid-list,
        trailing speculative tokens dropped."""
        lists = {"a": [9, 9, 7, 8, 8], "b": [6, 6, 6, 6]}

        def admit():
            sched, alloc, _ = _sched(max_batch=2, max_seq=32)
            a = Request(prompt=[1, 2], max_new_tokens=10, eos_token_id=7)
            b = Request(prompt=[3], max_new_tokens=3)
            sched.submit(a)
            sched.submit(b)
            sched.admit()
            sched.record_first_token(a, 4)
            sched.record_first_token(b, 5)
            return sched, alloc, a, b

        # a hits EOS at its 3rd chunk token; b exhausts max_new_tokens at
        # its 2nd — trailing tokens in both lists are speculative junk
        sched, alloc, a, b = admit()
        done = sched.step_tokens_chunk({a.slot: lists["a"],
                                        b.slot: lists["b"]})
        assert sorted(r.uid for r in done) == sorted([a.uid, b.uid])
        assert a.status == "done" and a.tokens == [4, 9, 9, 7]
        assert b.status == "done" and b.tokens == [5, 6, 6]
        # fill advanced once per CONSUMED token, then reset by free()
        assert alloc.n_free == 2
        # unknown slot still raises
        with pytest.raises(KeyError):
            sched.step_tokens_chunk({1: [1]})

        # the reference: one token a call, to lanes still running
        ref, ref_alloc, ra, rb = admit()
        fills = []
        for j in range(5):
            step = {r.slot: [lists[k][j]]
                    for k, r in (("a", ra), ("b", rb))
                    if r.status == "running" and j < len(lists[k])}
            fills.append({s: int(ref_alloc.fill[s]) for s in step})
            ref.step_tokens_chunk(step)
        assert (ra.tokens, rb.tokens) == (a.tokens, b.tokens)
        assert (ra.status, rb.status) == (a.status, b.status)
        assert fills[:3] == [{0: 2, 1: 1}, {0: 3, 1: 2}, {0: 4}]
        assert fills[3:] == [{}, {}] and ref_alloc.n_free == 2

    def test_step_tokens_chunk_advances_fill_per_token(self):
        """The cache-row safety net must see the same remaining count
        calls of one token each would — fill advances inside the chunk,
        not once at the end."""
        sched, alloc, _ = _sched(max_batch=1, max_seq=8)
        r = Request(prompt=[1, 2, 3], max_new_tokens=5)
        sched.submit(r)
        sched.admit()
        r.max_new_tokens = 99      # white-box: leave only the row limit
        sched.record_first_token(r, 4)
        assert int(alloc.fill[r.slot]) == 3
        sched.step_tokens_chunk({r.slot: [5, 6]})
        assert r.status == "running"
        assert int(alloc.fill[r.slot]) == 5
        # three more writable rows -> the third consumed token drives
        # remaining() to 0 and the safety net retires the request; the
        # trailing speculative token is dropped
        done = sched.step_tokens_chunk({r.slot: [7, 8, 9, 9]})
        assert done == [r] and r.status == "done"
        assert r.tokens == [4, 5, 6, 7, 8, 9]


# ----------------------------------------------------- metrics reservoir
class TestReservoir:
    def test_exact_percentiles_under_capacity(self):
        res = Reservoir(capacity=1024)
        for x in range(1, 101):                     # 1..100
            res.add(float(x))
        assert res.percentile(50) == pytest.approx(50.5)
        assert res.percentile(0) == 1.0
        assert res.percentile(100) == 100.0
        assert res.percentile(99) == pytest.approx(99.01)

    def test_empty_and_singleton(self):
        res = Reservoir(capacity=4)
        assert res.percentile(99) == 0.0            # matches mean default
        res.add(3.5)
        assert res.percentiles((50, 95, 99)) == {50: 3.5, 95: 3.5, 99: 3.5}

    def test_memory_bounded_and_unbiased_range(self):
        res = Reservoir(capacity=16, seed=0)
        for x in range(10_000):
            res.add(float(x))
        assert len(res.values) == 16 and res.n_seen == 10_000
        # the sample is drawn from the whole stream, not just the head
        assert max(res.values) > 1000

    def test_deterministic_under_seed(self):
        def fill(seed):
            r = Reservoir(capacity=8, seed=seed)
            for x in range(1000):
                r.add(float(x))
            return r.values
        assert fill(0) == fill(0)
        assert fill(0) != fill(1)

    def test_metrics_snapshot_has_percentile_keys(self):
        """snapshot() gains reservoir-backed TTFT percentiles WITHOUT
        breaking any pre-existing key serving_bench.py reads."""
        m = ServingMetrics()
        for ttft in (0.1, 0.2, 0.3):
            req = Request(prompt=[1], max_new_tokens=1)
            req.submit_t, req.first_token_t = 0.0, ttft
            m.on_finished([req])
        snap = m.snapshot(queue_depth=0, occupancy=0.0)
        assert snap["serving/ttft_p50_s"] == pytest.approx(0.2)
        assert snap["serving/ttft_p95_s"] == pytest.approx(0.29)
        assert snap["serving/ttft_p99_s"] == pytest.approx(0.298)
        for legacy in ("serving/tokens_per_s", "serving/ttft_s",
                       "serving/queue_depth", "serving/slot_occupancy",
                       "serving/requests_done", "serving/rejected_total",
                       "serving/prefill_padding_waste",
                       "serving/prefill_programs"):
            assert legacy in snap


# --------------------------------------------------- engine (integration)
def _tiny(vocab=64, max_seq=48):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    cfg = GPTConfig(vocab_size=vocab, max_seq_len=max_seq, num_layers=2,
                    num_heads=2, d_model=32, d_ff=64, dtype=jnp.float32,
                    param_dtype=jnp.float32, remat=False)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def tiny_engine():
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    model, params = _tiny()
    return ds.init_inference(model, model_parameters=params,
                             dtype=jnp.float32)


FAMILIES = {
    "dense": {},
    "paged": dict(paged=True, kv_block_size=8),
    "int8": dict(kv_dtype="int8"),
    "speculative": dict(speculative=True, spec_k=3),
    "fused": dict(fused_prefill=True, prefill_chunk=4),
}


class TestServingEngine:
    @pytest.mark.parametrize("decode_chunk", [1, 3, 8])
    @pytest.mark.parametrize("family", list(FAMILIES))
    def test_greedy_parity_with_generate(self, tiny_engine, family,
                                         decode_chunk):
        """Mixed-length prompts, more requests than slots: every request's
        output must match a dedicated InferenceEngine.generate run — the
        continuous batch changes throughput, never tokens. In every
        program family, at every chunk length: K = 1 is a chunk of one
        step through the same scan, and 3 divides neither the answer
        nor the other lengths."""
        rng = np.random.default_rng(0)
        vocab = tiny_engine.module.cfg.vocab_size
        lens = [3, 7, 5, 9, 4, 6]
        prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
                   for n in lens]
        serving = ServingEngine(engine=tiny_engine, max_batch=3,
                                max_prompt_len=16, max_queue=8,
                                decode_chunk=decode_chunk,
                                **FAMILIES[family])
        results = serving.run(prompts, max_new_tokens=6)
        assert all(r.status == "done" for r in results)
        for p, r in zip(prompts, results):
            ref = np.asarray(tiny_engine.generate(
                p[None], max_new_tokens=6, temperature=0.0))[0]
            np.testing.assert_array_equal(r.output_ids, ref)

    def test_chunk_of_one_matches_chunk_of_eight(self, tiny_engine):
        """K is an execution strategy, not a model change: greedy outputs
        must be BIT-identical between a chunk of one step and a chunk of
        eight for mixed-length prompts, mid-chunk EOS, and EOS on the
        very first (prefill-sampled) token."""
        rng = np.random.default_rng(1)
        vocab = tiny_engine.module.cfg.vocab_size
        prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
                   for n in [3, 7, 5, 9, 4, 6]]
        pt = ServingEngine(engine=tiny_engine, max_batch=3,
                           max_prompt_len=16, max_queue=8, decode_chunk=1)
        ck = ServingEngine(engine=tiny_engine, max_batch=3,
                           max_prompt_len=16, max_queue=8, decode_chunk=8)

        def both(**kw):
            a = pt.run(list(prompts), **kw)
            b = ck.run(list(prompts), **kw)
            for x, y in zip(a, b):
                assert x.status == y.status == "done"
                np.testing.assert_array_equal(x.output_ids, y.output_ids)
            return a

        base = both(max_new_tokens=11)       # K does not divide 11
        # mid-chunk EOS: a token observed mid-stream becomes the EOS id,
        # so lanes retire at different in-chunk offsets
        mid_eos = base[0].tokens[2]
        both(max_new_tokens=11, eos_token_id=int(mid_eos))
        # instant EOS: some request's FIRST sampled token is the EOS id —
        # it retires during admission, before any decode chunk
        first_eos = base[1].tokens[0]
        res = both(max_new_tokens=11, eos_token_id=int(first_eos))
        assert any(len(r.tokens) == 1 for r in res)

    def test_the_constructor_gains_no_option_unnoticed(self):
        """33 keyword options are what the benchmark's cells and the
        tests' families need today. The next one is a deliberate edit of
        this number, made with the case for it."""
        import inspect
        params = inspect.signature(ServingEngine.__init__).parameters
        options = [n for n, p in params.items()
                   if p.kind is inspect.Parameter.KEYWORD_ONLY]
        assert "tuned_config" not in params
        assert len(options) <= 33, options

    def test_engine_rejections_surface(self, tiny_engine):
        serving = ServingEngine(engine=tiny_engine, max_batch=2,
                                max_prompt_len=8, max_queue=8)
        r = serving.submit(np.arange(12, dtype=np.int32), max_new_tokens=2)
        assert r.status == "rejected"
        assert r.reject_reason == REJECT_PROMPT_TOO_LONG

    def test_metrics_csv_written(self, tiny_engine, tmp_path):
        monitor = csv_monitor_master(str(tmp_path), "t")
        serving = ServingEngine(engine=tiny_engine, max_batch=2,
                                max_prompt_len=8, monitor=monitor,
                                emit_every_steps=2)
        prompts = [np.array([1, 2, 3], np.int32),
                   np.array([4, 5], np.int32)]
        results = serving.run(prompts, max_new_tokens=5)
        monitor.close()
        assert all(r.status == "done" for r in results)
        out = tmp_path / "t"
        files = {f.name for f in out.iterdir()}
        for label in ("serving_tokens_per_s", "serving_ttft_s",
                      "serving_queue_depth", "serving_slot_occupancy"):
            assert f"{label}.csv" in files
        rows = (out / "serving_tokens_per_s.csv").read_text().strip()
        assert len(rows.splitlines()) >= 2            # header + >=1 sample


def _spans(rt, *names):
    """(name, start_us, end_us) of the ring's spans called ``names``."""
    return [(e[1], e[2], e[2] + e[3]) for e in rt.events()
            if e[0] == "X" and e[1] in names]


def _inside(inner, outers):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


STARVED = ("serve/starved_after_prefill", "serve/starved_after_chunk")


class TestServeLoopSpans:
    """The serve loop's boundaries as telemetry spans (and, through them,
    as annotations in the profiler's trace)."""

    def _run(self, tiny_engine, decode_chunk, n_requests=5):
        return self._drive(
            ServingEngine(engine=tiny_engine, max_batch=2,
                          max_prompt_len=16, max_queue=8,
                          decode_chunk=decode_chunk), n_requests)

    @staticmethod
    def _drive(serving, n_requests=5):
        rng = np.random.default_rng(3)
        vocab = serving.module.cfg.vocab_size
        prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
                   for n in [3, 7, 5, 9, 4][:n_requests]]
        # answers of different lengths: lanes retire and are refilled
        # while the other lane's chunk is in flight (the patched path)
        results = [serving.submit(p, max_new_tokens=n)
                   for p, n in zip(prompts, [9, 21, 6, 14, 11])]
        serving.run()
        assert all(r.status == "done" for r in results)
        return serving

    @pytest.mark.parametrize("decode_chunk", [4, 1])
    def test_starved_spans_never_overlap_a_device_wait(
            self, tiny_engine, telemetry_on, decode_chunk):
        waits = ("serve/chunk_host_wait", "serve/prefill_wait")
        serving = self._run(tiny_engine, decode_chunk)
        starved = _spans(telemetry_on, *STARVED)
        blocked = _spans(telemetry_on, *waits)
        # a chunk of one step is launched ahead like any other, so its
        # sync leaves the chip idle only where every lane was on its
        # last token: this traffic has that at K = 4 and not at K = 1
        names = {n for n, _, _ in starved}
        assert names == set(STARVED if decode_chunk > 1 else STARVED[:1])
        assert {n for n, _, _ in blocked} == set(waits)
        for _, s0, s1 in starved:
            assert s1 >= s0
            for name, w0, w1 in blocked:
                assert s1 <= w0 or w1 <= s0, (name, (s0, s1), (w0, w1))
        # drained: the one left open after the last sync was dropped, not
        # recorded as the host starving the chip
        assert serving._starved is None

    def test_none_is_left_open_at_close(self, tiny_engine, telemetry_on):
        serving = ServingEngine(engine=tiny_engine, max_batch=2,
                                max_prompt_len=16, max_queue=8,
                                decode_chunk=4)
        serving.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=30)
        serving.step()           # prefill, one chunk, its sync: work left
        assert serving.scheduler.has_work()
        assert serving._starved is not None
        recorded = len(_spans(telemetry_on, *STARVED))
        serving.close()
        assert serving._starved is None
        assert len(_spans(telemetry_on, *STARVED)) == recorded

    def test_spans_nest_pump_admit_prefill_and_its_two_halves(
            self, tiny_engine, telemetry_on):
        self._run(tiny_engine, 4)
        pumps = _spans(telemetry_on, "serve/pump")
        admits = _spans(telemetry_on, "serve/admit")
        prefills = _spans(telemetry_on, "serve/prefill")
        halves = _spans(telemetry_on, "serve/prefill_dispatch",
                        "serve/prefill_wait")
        assert pumps and prefills and len(halves) == 2 * len(prefills)
        assert all(_inside(a, pumps) for a in admits)
        assert all(_inside(p, admits) for p in prefills)
        assert all(_inside(h, prefills) for h in halves)
        # the prefill span names the requests it serves
        attrs = [e[5] for e in telemetry_on.events()
                 if e[0] == "X" and e[1] == "serve/prefill"]
        assert all(a["uids"].startswith("[") for a in attrs)
        # lane patches: a span only where there was something to patch,
        # with the two instants that were there before inside it
        patches = _spans(telemetry_on, "serve/lane_patch")
        marks = [(e[1], e[2], e[2]) for e in telemetry_on.events()
                 if e[0] == "i" and e[1] in ("serve/admit_patch",
                                             "serve/deact_patch")]
        assert patches and marks
        assert all(_inside(m, patches) for m in marks)

    @pytest.mark.parametrize("decode_chunk", [4, 1])
    def test_telemetry_off_opens_nothing(self, tiny_engine, decode_chunk):
        from deepspeed_tpu.telemetry import core as tel
        rt = tel.get_runtime()
        assert not rt.enabled
        before = (len(rt.events()), rt.span_stats(), rt.counter_totals())
        serving = self._run(tiny_engine, decode_chunk, n_requests=3)
        assert serving._starved is None
        assert (len(rt.events()), rt.span_stats(),
                rt.counter_totals()) == before

    @pytest.mark.parametrize("decode_chunk", [8, 1])
    def test_chunk_spans_count_the_chunks(self, tiny_engine, telemetry_on,
                                          decode_chunk):
        """One loop at every K: each chunk is one launch, one host wait
        and one retire on the spans, as many as ``ServingMetrics``
        counted, and at K = 1 too they are the chunk's spans."""
        calls = []
        serving = ServingEngine(engine=tiny_engine, max_batch=2,
                                max_prompt_len=16, max_queue=8,
                                decode_chunk=decode_chunk)
        assert not hasattr(serving, "_jit_decode")
        jitted = serving._jit_decode_chunk
        serving._jit_decode_chunk = \
            lambda *a: calls.append(1) or jitted(*a)
        results = serving.run([np.arange(1, n, dtype=np.int32)
                               for n in (4, 8, 6)], max_new_tokens=11)
        assert all(r.status == "done" for r in results)
        stats = telemetry_on.span_stats()
        chunks = serving.metrics.decode_steps
        # 30 tokens after the three that prefill sampled, two lanes
        assert chunks == len(calls) >= 30 / (2 * decode_chunk)
        for name in ("serve/chunk_launch", "serve/chunk_host_wait",
                     "serve/chunk_retire"):
            assert stats[name]["count"] == chunks, name
        assert "serve/decode_step" not in stats
        assert telemetry_on.counter_totals()["serve/decode_tokens"] == 30

    def test_one_prefill_wait_for_each_prefill_call(self, tiny_engine,
                                                    telemetry_on):
        """What the host waited on prefill programs while lanes were
        decoding is the sum of these spans: one for each call."""
        calls = []
        serving = ServingEngine(engine=tiny_engine, max_batch=2,
                                max_prompt_len=16, max_queue=8,
                                decode_chunk=4)
        jitted = serving._jit_prefill
        serving._jit_prefill = lambda *a: calls.append(1) or jitted(*a)
        self._drive(serving)
        stats = telemetry_on.span_stats()
        # two lanes, five requests of different lengths: refills arrive
        # one by one, so there are more calls than compiled shapes
        assert len(calls) > serving.metrics.prefill_programs
        assert stats["serve/prefill_wait"]["count"] == len(calls)
        assert stats["serve/prefill"]["count"] == len(calls)
        totals = telemetry_on.counter_totals()
        assert totals["serve/prefill_tokens"] == 3 + 7 + 5 + 9 + 4
        assert "serve/prefill_inline_tokens" not in totals

    def test_a_fused_run_waits_on_no_prefill(self, tiny_engine,
                                             telemetry_on):
        """Fused prefill has no prefill program to wait on: the prompt
        is consumed inside the scan, and the counter says how much."""
        serving = ServingEngine(engine=tiny_engine, max_batch=2,
                                max_prompt_len=16, max_queue=8,
                                decode_chunk=4, fused_prefill=True,
                                prefill_chunk=4)
        self._drive(serving)
        stats = telemetry_on.span_stats()
        assert "serve/prefill_wait" not in stats
        assert "serve/prefill" not in stats
        assert stats["serve/chunk_host_wait"]["count"] == \
            serving.metrics.decode_steps
        totals = telemetry_on.counter_totals()
        assert totals["serve/prefill_inline_tokens"] == 3 + 7 + 5 + 9 + 4
        assert serving.inline_prefill_tokens == 3 + 7 + 5 + 9 + 4

    def test_program_names_the_benchmark_reads_by(self, tiny_engine):
        """chipbench finds the serving programs in a device trace by their
        XLA module names (readers.py::decode_step_ms by ``decode_chunk``,
        prefill_ms_per_ktok.chat.py by ``jit_prefill``, PERF.md section 5
        by ``_insert_batch``): a rename has to fail here, not a reader on
        the chip."""
        import re
        serving = ServingEngine(engine=tiny_engine, max_batch=2,
                                max_prompt_len=16, max_queue=8,
                                decode_chunk=4)
        modules = set()

        def spy(owner, attr):
            jitted = getattr(owner, attr)

            def call(*args):
                text = jitted.lower(*args).as_text()
                modules.add(re.search(r"module @(\S+)", text).group(1))
                return jitted(*args)
            setattr(owner, attr, call)

        spy(serving, "_jit_prefill")
        spy(serving, "_jit_decode_chunk")
        spy(serving.kv, "_insert_batch")
        serving.run([np.arange(1, 6, dtype=np.int32)], max_new_tokens=6)
        assert len(modules) == 3, modules
        for needle in ("decode_chunk", "jit_prefill", "_insert_batch"):
            assert any(needle in name for name in modules), (needle,
                                                             modules)


class TestBucketedPrefill:
    def test_bucket_selection(self, tiny_engine):
        serving = ServingEngine(engine=tiny_engine, max_batch=2,
                                max_prompt_len=40)
        assert serving._buckets == [16, 32, 40]
        assert serving._bucket_for(3) == 16
        assert serving._bucket_for(16) == 16
        assert serving._bucket_for(17) == 32
        assert serving._bucket_for(40) == 40
        # a max_prompt_len at/below the smallest bucket collapses to one
        small = ServingEngine(engine=tiny_engine, max_batch=2,
                              max_prompt_len=12)
        assert small._buckets == [12]

    def test_short_prompts_use_small_bucket(self, tiny_engine):
        """A short prompt must prefill through its own bucket, not
        max_prompt_len — the compiled shape set and the padding-waste
        metric both show it."""
        serving = ServingEngine(engine=tiny_engine, max_batch=2,
                                max_prompt_len=40, decode_chunk=4)
        res = serving.run([np.arange(1, 4, dtype=np.int32),      # len 3
                           np.arange(1, 21, dtype=np.int32)],    # len 20
                          max_new_tokens=4)
        assert all(r.status == "done" for r in res)
        # one (1, 16) and one (1, 32) prefill — never a 40-wide program
        assert serving._prefill_shapes == {(1, 16), (1, 32)}
        assert serving.metrics.prefill_programs == 2
        # 23 true prompt tokens over 48 padded positions
        assert serving.metrics.padding_waste == pytest.approx(1 - 23 / 48)

    def test_mixed_lengths_same_bucket_batch(self, tiny_engine):
        """Same-bucket admissions share ONE batched prefill call."""
        serving = ServingEngine(engine=tiny_engine, max_batch=3,
                                max_prompt_len=16, decode_chunk=4)
        res = serving.run([np.arange(1, 4, dtype=np.int32),
                           np.arange(1, 9, dtype=np.int32),
                           np.arange(1, 14, dtype=np.int32)],
                          max_new_tokens=3)
        assert all(r.status == "done" for r in res)
        assert serving._prefill_shapes == {(3, 16)}


class TestSampling:
    def test_sample_tokens_top_k_and_greedy(self):
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.serving.engine import sample_tokens
        logits = jnp.asarray(
            np.random.default_rng(0).normal(size=(4, 32)), jnp.float32)
        # temperature 0 is argmax regardless of key
        greedy = np.asarray(sample_tokens(logits, jax.random.PRNGKey(0),
                                          0.0, None))
        np.testing.assert_array_equal(greedy,
                                      np.argmax(np.asarray(logits), -1))
        # top-k draws stay inside each row's top-k set
        topk = set()
        for k in range(16):
            out = np.asarray(sample_tokens(logits, jax.random.PRNGKey(k),
                                           1.0, 3))
            ranked = np.argsort(np.asarray(logits), -1)[:, -3:]
            for row, tok in enumerate(out):
                assert tok in ranked[row]
                topk.add((row, int(tok)))
        assert len(topk) > 4          # actually stochastic, not argmax

    def test_sample_tokens_top_p_nucleus(self):
        """top-p keeps the minimal token set whose cumulative mass
        reaches p: every draw must land inside the nucleus computed
        independently in numpy, and a tiny p over peaked logits
        degenerates to argmax."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.serving.engine import sample_tokens
        logits_np = np.random.default_rng(5).normal(
            size=(4, 32)).astype(np.float32) * 2.0
        logits = jnp.asarray(logits_np)
        top_p = 0.7
        order = np.argsort(-logits_np, axis=-1)
        srt = np.take_along_axis(logits_np, order, axis=-1)
        probs = np.exp(srt) / np.exp(srt).sum(-1, keepdims=True)
        keep = (np.cumsum(probs, -1) - probs) < top_p
        nucleus = [set(order[r][keep[r]]) for r in range(4)]
        assert all(0 < len(n) < 32 for n in nucleus)   # actually filters
        seen = set()
        for k in range(24):
            out = np.asarray(sample_tokens(logits, jax.random.PRNGKey(k),
                                           1.0, None, top_p))
            for row, tok in enumerate(out):
                assert int(tok) in nucleus[row]
                seen.add((row, int(tok)))
        assert len(seen) > 4                           # still stochastic
        # a nucleus smaller than any probability gap keeps only argmax
        peaked = np.asarray(sample_tokens(logits * 8.0,
                                          jax.random.PRNGKey(0),
                                          1.0, None, 0.01))
        np.testing.assert_array_equal(peaked,
                                      np.argmax(logits_np, -1))

    def test_filter_logits_temperature_one_single_path(self):
        """temperature=1.0 takes the same scaling branch as every other
        nonzero temperature (x / 1.0 is the bitwise identity — the old
        ``not in (0.0, 1.0)`` guard forked the path for no numeric
        effect): output is bit-equal to the f32 input."""
        import jax.numpy as jnp
        from deepspeed_tpu.serving.engine import filter_logits
        logits = jnp.asarray(np.random.default_rng(6).normal(
            size=(3, 16)).astype(np.float32))
        out = filter_logits(logits, 1.0, None, None)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(logits))
        # and temperature scaling itself is the plain division
        out2 = filter_logits(logits, 0.5, None, None)
        np.testing.assert_array_equal(np.asarray(out2),
                                      np.asarray(logits) / 0.5)

    def test_sampled_serving_is_deterministic_under_seed(self, tiny_engine):
        """temperature/top-k sampling through the chunked loop: same
        engine seed -> identical streams; different seed -> different."""
        rng = np.random.default_rng(2)
        vocab = tiny_engine.module.cfg.vocab_size
        prompts = [rng.integers(0, vocab, (5,)).astype(np.int32)
                   for _ in range(3)]

        def run(seed):
            serving = ServingEngine(engine=tiny_engine, max_batch=3,
                                    max_prompt_len=8, decode_chunk=4,
                                    temperature=1.0, top_k=8, seed=seed)
            return [r.tokens for r in
                    serving.run(list(prompts), max_new_tokens=8)]

        assert run(seed=0) == run(seed=0)
        assert run(seed=0) != run(seed=1)


def test_serving_bench_smoke(tmp_path):
    """Fast end-to-end smoke over the real benchmark path (the
    bin/serving_smoke.sh entry point): chunks of one step ("per-token")
    vs chunks of K on the tiny model, greedy parity asserted inside
    run_bench, JSON-ready result dict with tokens/s for both."""
    from deepspeed_tpu.benchmarks.serving_bench import run_bench
    result = run_bench(n_requests=4, max_new_tokens=10, max_batch=4,
                       prompt_len=16, decode_chunk=4,
                       out_dir=str(tmp_path / "csv"),
                       with_sequential=False)
    assert result["greedy_parity"] is True
    assert result["per_token_tokens_per_s"] > 0
    assert result["chunked_tokens_per_s"] > 0
    assert result["prefill_programs"] >= 1
    assert 0.0 <= result["prefill_padding_waste"] < 1.0
    assert result["csv_files"], "serving metrics CSVs missing"


@pytest.mark.slow
def test_continuous_batching_beats_sequential(tmp_path):
    """Acceptance: for N >= 8 concurrent requests, the slotted continuous
    batch outruns N sequential generate calls (same model, same params,
    both warmed). Needs a compute-dominated model, hence slow tier."""
    from deepspeed_tpu.benchmarks.serving_bench import run_bench
    result = run_bench(n_requests=8, max_new_tokens=32, max_batch=8,
                       prompt_len=16, out_dir=str(tmp_path / "csv"))
    assert result["speedup"] > 1.0, result
    assert result["csv_files"], "serving metrics CSVs missing"
    assert os.path.isdir(str(tmp_path / "csv"))


class TestShardedServing:
    """Tensor-parallel and disaggregated-prefill serving are PLACEMENT
    changes, never math changes: greedy token streams must be
    bit-identical to the unsharded engine (replication/sharding moves
    data; the row-parallel psum's f32 reassociation never flips a greedy
    argmax on these magnitudes), and each mode compiles under its own
    ``decode_chunk*_fn`` variant name so the pinned dense/paged budgets
    stay exact."""

    def _engine(self, model, params, **kw):
        import jax.numpy as jnp
        kw.setdefault("max_batch", 2)
        kw.setdefault("decode_chunk", 4)
        return ServingEngine(model, model_parameters=params,
                             dtype=jnp.float32, **kw)

    def _prompts(self, n=4):
        rng = np.random.default_rng(3)
        return [rng.integers(1, 64, int(rng.integers(3, 9)))
                .astype(np.int32) for _ in range(n)]

    def test_tp2_bit_identical_with_own_variant(self):
        from deepspeed_tpu.analysis.auditor import TraceAuditor
        model, params = _tiny()
        prompts = self._prompts()
        base = self._engine(model, params).run(prompts, max_new_tokens=6)
        with TraceAuditor(audit_jaxprs=False) as aud:
            tp_eng = self._engine(model, params, tp=2)
            got = tp_eng.run(prompts, max_new_tokens=6)
        assert tp_eng.tp == 2
        # its own program family — zero compiles against the dense name
        assert aud.compiles("decode_chunk_tp2_fn") >= 1
        assert aud.compiles("decode_chunk_fn") == 0
        for b, g in zip(base, got):
            assert g.status == "done"
            np.testing.assert_array_equal(b.output_ids, g.output_ids)

    def test_disaggregated_prefill_bit_identical(self):
        from deepspeed_tpu.analysis.auditor import TraceAuditor
        from deepspeed_tpu.telemetry import core as telemetry
        model, params = _tiny()
        prompts = self._prompts()
        base = self._engine(model, params, paged=True).run(
            prompts, max_new_tokens=6)
        telemetry.enable()
        try:
            with TraceAuditor(audit_jaxprs=False) as aud:
                dis = self._engine(model, params, paged=True,
                                   disaggregate_prefill=True)
                got = dis.run(prompts, max_new_tokens=6)
            assert dis.disaggregated
            assert aud.compiles("decode_chunk_paged_disagg_fn") >= 1
            assert aud.compiles("decode_chunk_paged_fn") == 0
            # every prefill handed its KV to the decode slice
            counters = telemetry.get_runtime().counter_totals()
            assert counters.get("serve/disagg_handoffs", 0) >= len(prompts)
            assert counters.get("serve/disagg_handoff_bytes", 0) > 0
        finally:
            telemetry.disable()
            telemetry.get_runtime().clear()
        for b, g in zip(base, got):
            assert g.status == "done"
            np.testing.assert_array_equal(b.output_ids, g.output_ids)

    def test_tp_mismatch_raises(self):
        import jax.numpy as jnp
        import deepspeed_tpu as ds
        model, params = _tiny()
        eng = ds.init_inference(model, model_parameters=params,
                                dtype=jnp.float32)          # tp=1 mesh
        with pytest.raises(ValueError):
            ServingEngine(engine=eng, tp=2)
