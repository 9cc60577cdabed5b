"""Serving frontend tests (serving/frontend/).

The control-plane pieces (TokenBucket, AdmissionController, TraceLog)
are host-side Python with injectable clocks and run at CPU speed. The
ServingFrontend integration tests share one tiny compiled GPT through a
module fixture; each test builds its own ServingEngine + frontend (the
frontend owns its engine's execution) and closes the frontend so no
driver thread outlives its test.
"""

import threading
import time

import numpy as np
import pytest

from deepspeed_tpu.serving import REJECT_DEADLINE_EXPIRED
from deepspeed_tpu.serving.frontend import (AdmissionConfig,
                                            AdmissionController,
                                            ChunkThroughputEstimator,
                                            PRIORITY_HIGH, PRIORITY_LOW,
                                            PRIORITY_NORMAL,
                                            REJECT_DEADLINE_INFEASIBLE,
                                            REJECT_FRONTEND_CLOSED,
                                            REJECT_FRONTEND_QUEUE_FULL,
                                            REJECT_RATE_LIMITED,
                                            ServingFrontend, Ticket,
                                            TokenBucket, TraceLog)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ------------------------------------------------------------ token bucket
class TestTokenBucket:
    def test_burst_then_throttle_then_refill(self):
        clock = FakeClock()
        b = TokenBucket(rate=2.0, burst=3.0, clock=clock)
        assert [b.try_acquire() for _ in range(3)] == [True] * 3
        assert b.try_acquire() is False            # burst exhausted
        clock.advance(0.5)                         # refills 1 token
        assert b.try_acquire() is True
        assert b.try_acquire() is False

    def test_refill_caps_at_burst(self):
        clock = FakeClock()
        b = TokenBucket(rate=100.0, burst=2.0, clock=clock)
        clock.advance(60.0)
        assert [b.try_acquire() for _ in range(3)] == [True, True, False]

    def test_invalid_args(self):
        with pytest.raises(ValueError):
            TokenBucket(rate=0, burst=1)
        with pytest.raises(ValueError):
            TokenBucket(rate=1, burst=0)


class TestThroughputEstimator:
    def test_cold_start_is_none(self):
        est = ChunkThroughputEstimator()
        assert est.rate() is None
        est.record(0, 1.0)                         # degenerate: ignored
        est.record(10, 0.0)
        assert est.rate() is None

    def test_ewma_converges(self):
        est = ChunkThroughputEstimator(alpha=0.5)
        est.record(100, 1.0)
        assert est.rate() == pytest.approx(100.0)
        est.record(200, 1.0)
        assert est.rate() == pytest.approx(150.0)  # 0.5*200 + 0.5*100


# -------------------------------------------------------------- admission
def _ticket(prio=PRIORITY_NORMAL, deadline=None, tenant="default",
            prompt_len=4, max_new=8):
    return Ticket(prompt_len=prompt_len, max_new_tokens=max_new,
                  priority=prio, tenant=tenant, deadline_s=deadline)


class TestAdmissionController:
    def test_priority_order_fifo_within_class(self):
        c = AdmissionController(clock=FakeClock())
        low1, high, low2 = (_ticket(PRIORITY_LOW), _ticket(PRIORITY_HIGH),
                            _ticket(PRIORITY_LOW))
        for t in (low1, high, low2):
            assert c.offer(t) is None
        admits, sheds = c.pop(room=3, rate=None, backlog_tokens=0)
        assert admits == [high, low1, low2] and sheds == []
        assert c.pending == 0

    def test_room_bounds_pop(self):
        c = AdmissionController(clock=FakeClock())
        tickets = [_ticket() for _ in range(4)]
        for t in tickets:
            c.offer(t)
        admits, _ = c.pop(room=2, rate=None, backlog_tokens=0)
        assert admits == tickets[:2] and c.pending == 2

    def test_offer_rejects_expired_deadline(self):
        clock = FakeClock(10.0)
        c = AdmissionController(clock=clock)
        assert c.offer(_ticket(deadline=9.0)) == REJECT_DEADLINE_EXPIRED
        assert c.pending == 0

    def test_offer_rejects_when_full(self):
        c = AdmissionController(AdmissionConfig(max_pending=1),
                                clock=FakeClock())
        assert c.offer(_ticket()) is None
        assert c.offer(_ticket()) == REJECT_FRONTEND_QUEUE_FULL

    def test_per_tenant_rate_limit(self):
        clock = FakeClock()
        c = AdmissionController(
            AdmissionConfig(rate_per_tenant=1.0, burst_per_tenant=1.0),
            clock=clock)
        assert c.offer(_ticket(tenant="a")) is None
        assert c.offer(_ticket(tenant="a")) == REJECT_RATE_LIMITED
        # tenants have independent buckets
        assert c.offer(_ticket(tenant="b")) is None
        clock.advance(1.0)                          # tenant a refills
        assert c.offer(_ticket(tenant="a")) is None
        assert c.n_rate_limited == 1

    def test_pop_sheds_expired_and_infeasible(self):
        clock = FakeClock()
        c = AdmissionController(clock=clock)
        expired = _ticket(deadline=1.0)
        # 100 tok/s measured; backlog 50 + cost ~8.6 -> eta ~ 2.59s
        infeasible = _ticket(deadline=2.5)
        feasible = _ticket(deadline=5.0)
        no_deadline = _ticket()
        for t in (expired, infeasible, feasible, no_deadline):
            assert c.offer(t) is None
        clock.advance(2.0)                          # expired's deadline past
        admits, sheds = c.pop(room=4, rate=100.0, backlog_tokens=50.0)
        reasons = dict((t.seq, r) for t, r in sheds)
        assert reasons[expired.seq] == REJECT_DEADLINE_EXPIRED
        assert reasons[infeasible.seq] == REJECT_DEADLINE_INFEASIBLE
        assert admits == [feasible, no_deadline]
        assert c.n_shed == 2

    def test_cold_start_admits_optimistically(self):
        """No measured rate -> no feasibility shedding (an unmeasured
        system never rejects on a guess)."""
        clock = FakeClock()
        c = AdmissionController(clock=clock)
        tight = _ticket(deadline=0.001)
        c.offer(tight)
        admits, sheds = c.pop(room=1, rate=None, backlog_tokens=1e9)
        assert admits == [tight] and sheds == []

    def test_admitted_cost_feeds_backlog(self):
        """Each admit's own cost counts against the next ticket's ETA
        within the same pop."""
        clock = FakeClock()
        c = AdmissionController(clock=clock)
        first = _ticket(deadline=10.0, max_new=80)
        second = _ticket(deadline=0.5, max_new=8)   # feasible only if
        c.offer(first)                              # first's cost ignored
        c.offer(second)
        admits, sheds = c.pop(room=2, rate=100.0, backlog_tokens=0.0)
        assert admits == [first]
        assert sheds[0][0] is second
        assert sheds[0][1] == REJECT_DEADLINE_INFEASIBLE

    def test_remove_tombstones_and_drain(self):
        c = AdmissionController(clock=FakeClock())
        a, b = _ticket(), _ticket()
        c.offer(a)
        c.offer(b)
        assert c.remove(a) is True
        assert c.remove(a) is False                 # idempotent
        assert c.pending == 1
        assert c.drain() == [b]
        assert c.pending == 0
        admits, sheds = c.pop(room=4, rate=None, backlog_tokens=0)
        assert admits == [] and sheds == []


# ---------------------------------------------------------------- tracing
class TestTraceLog:
    def test_span_lifecycle_and_derived_latencies(self):
        clock = FakeClock()
        log = TraceLog(clock=clock)
        log.start(1, tenant="t", priority=0, prompt_len=4,
                  max_new_tokens=8, slo_ttft_s=2.0)
        log.mark(1, "submitted")
        clock.advance(0.5)
        log.mark(1, "admitted")
        clock.advance(0.5)
        log.mark(1, "prefill")
        clock.advance(0.5)
        log.chunk(1, 4)                    # stamps first_token at 1.5
        clock.advance(1.0)
        log.chunk(1, 4)
        trace = log.finish(1, "done")
        assert trace.n_tokens == 8
        assert trace.ttft_s == pytest.approx(1.5)
        assert trace.queue_wait_s == pytest.approx(1.0)
        assert trace.tpot_s == pytest.approx(1.0 / 7)
        assert trace.slo_ttft_met is True
        assert log.counters == {"done": 1, "slo_ttft_met": 1}
        assert log.histograms["ttft_s"].n_seen == 1
        snap = log.snapshot()
        assert snap["frontend/ttft_p50_s"] == pytest.approx(1.5)
        assert snap["frontend/done"] == 1.0

    def test_mark_is_first_write_wins(self):
        clock = FakeClock()
        log = TraceLog(clock=clock)
        log.start(1)
        log.mark(1, "submitted", t=1.0)
        log.mark(1, "submitted", t=99.0)
        assert log.finish(1, "done").events["submitted"] == 1.0

    def test_record_rejected_counts_reason(self):
        log = TraceLog(clock=FakeClock())
        log.record_rejected(7, "rate_limited", tenant="x")
        assert log.counters["rejected"] == 1
        assert log.counters["rejected:rate_limited"] == 1
        assert log.to_json()["requests"][0]["status"] == "rejected"

    def test_queue_wait_ends_at_the_lane_and_prefill_runs_from_it(self):
        """submit -> lane is the wait for a lane; lane -> first token is
        the prefill and what it queued behind; together they are TTFT."""
        clock = FakeClock()
        log = TraceLog(clock=clock)
        log.start(1, prompt_len=4, max_new_tokens=8)
        log.mark(1, "submitted")
        clock.advance(0.5)
        log.mark(1, "admitted")
        clock.advance(2.0)
        log.mark(1, "lane")                # the scheduler leased a slot
        clock.advance(0.25)
        log.mark(1, "prefill")             # first token sampled
        clock.advance(0.05)
        log.chunk(1, 1)                    # ... and delivered: first_token
        clock.advance(1.0)
        log.chunk(1, 7)
        trace = log.finish(1, "done")
        assert trace.queue_wait_s == pytest.approx(2.5)
        assert trace.prefill_s == pytest.approx(0.3)
        assert trace.ttft_s == pytest.approx(
            trace.queue_wait_s + trace.prefill_s)
        assert trace.to_dict()["prefill_s"] == pytest.approx(0.3)
        assert log.histograms["queue_wait_s"].percentile(50) == \
            pytest.approx(2.5)

    def test_a_trace_without_a_lane_mark_reads_as_before(self):
        clock = FakeClock()
        log = TraceLog(clock=clock)
        log.start(1)
        log.mark(1, "submitted")
        clock.advance(1.0)
        log.mark(1, "prefill")
        log.chunk(1, 2)
        trace = log.finish(1, "done")
        assert trace.queue_wait_s == pytest.approx(1.0)
        assert trace.prefill_s is None

    def test_finish_records_the_requests_phases_as_telemetry_spans(
            self, telemetry_on):
        clock = FakeClock(100.0)
        log = TraceLog(clock=clock)
        log.start(7, trace_id="abc")
        log.mark(7, "submitted")
        clock.advance(2.0)
        log.mark(7, "lane")
        clock.advance(0.5)
        log.chunk(7, 1)
        clock.advance(3.0)
        log.finish(7, "done")
        log.record_rejected(8, "rate_limited")   # no lane, no token
        spans = {e[1]: e for e in telemetry_on.events() if e[0] == "X"}
        assert set(spans) == {"request/queued", "request/prefill",
                              "request/decode"}
        durs = {n: e[3] / 1e6 for n, e in spans.items()}
        assert durs == pytest.approx({"request/queued": 2.0,
                                      "request/prefill": 0.5,
                                      "request/decode": 3.0})
        assert spans["request/queued"][2] == pytest.approx(100.0e6)
        for e in spans.values():
            assert e[5] == {"uid": 7, "trace_id": "abc"}

    def test_keep_last_bounds_records_not_counters(self):
        log = TraceLog(clock=FakeClock(), keep_last=2)
        for uid in range(5):
            log.start(uid)
            log.finish(uid, "done")
        assert log.counters["done"] == 5
        assert [t["uid"] for t in log.to_json()["requests"]] == [3, 4]

    def test_emit_through_monitor_and_dump(self, tmp_path):
        events = []

        class FakeMonitor:
            def write_events(self, evs):
                events.extend(evs)

        log = TraceLog(FakeMonitor(), clock=FakeClock())
        log.start(1)
        log.finish(1, "done")
        snap = log.emit()
        labels = {label for label, _, _ in events}
        assert set(snap) == labels and "frontend/done" in labels
        path = tmp_path / "traces.json"
        log.dump(str(path))
        assert path.exists() and path.read_text().startswith("{")


# ------------------------------------------------- monitor thread-safety
def test_monitor_concurrent_writes(tmp_path):
    """CsvWriter/MonitorMaster hold a lock around write/flush: concurrent
    emitters from many threads must neither crash nor interleave partial
    rows (the frontend driver emits while callers may flush)."""
    from deepspeed_tpu.serving import csv_monitor_master
    monitor = csv_monitor_master(str(tmp_path), "mt")
    n_threads, n_each = 8, 50
    errors = []

    def emit(k):
        try:
            for i in range(n_each):
                monitor.write_events([("x", float(k * n_each + i), i)])
                if i % 10 == 0:
                    monitor.flush()
        except Exception as e:  # pragma: no cover - the assertion target
            errors.append(e)

    threads = [threading.Thread(target=emit, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    monitor.close()
    assert not errors
    rows = (tmp_path / "mt" / "x.csv").read_text().strip().splitlines()
    assert len(rows) == 1 + n_threads * n_each      # header + every event
    assert all(len(r.split(",")) == 2 for r in rows[1:])  # no torn rows


# ------------------------------------------------- frontend (integration)
def _tiny(vocab=64, max_seq=64):
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


def _serving(tiny_engine, **kw):
    from deepspeed_tpu.serving import ServingEngine
    kw.setdefault("max_batch", 2)
    kw.setdefault("max_prompt_len", 16)
    kw.setdefault("max_queue", 16)
    kw.setdefault("decode_chunk", 4)
    return ServingEngine(engine=tiny_engine, **kw)


class TestEngineCancelAndPump:
    """Engine-level cancellation via the external pump() driver — fully
    deterministic (no threads): the mid-chunk patch path must free the
    slot for the next queued request within one chunk and never corrupt
    the surviving lane's stream."""

    def test_cancel_running_frees_slot_within_one_chunk(self, tiny_engine):
        serving = _serving(tiny_engine, max_batch=1)
        solo = serving.run([np.arange(1, 6, dtype=np.int32)],
                           max_new_tokens=6)[0]

        a = serving.submit(np.arange(1, 5, dtype=np.int32),
                           max_new_tokens=40)
        b = serving.submit(np.arange(1, 6, dtype=np.int32),
                           max_new_tokens=6)
        while not a.tokens:                      # a running, b queued
            serving.pump()
        assert a.status == "running" and b.status == "queued"
        assert serving.cancel(a) is True
        assert a.status == "cancelled"
        assert serving.scheduler.allocator.n_free == 1   # slot free NOW
        n_before = len(a.tokens)
        serving.pump()                           # admits b into a's slot
        assert b.status == "running" or b.status == "done"
        while b.status != "done":
            serving.pump()
        # the cancelled lane stopped producing; b's stream is b's own
        assert len(a.tokens) == n_before
        np.testing.assert_array_equal(b.output_ids, solo.output_ids)
        assert serving.cancel(a) is False        # already terminal

    def test_cancel_queued_never_prefills(self, tiny_engine):
        serving = _serving(tiny_engine, max_batch=1)
        a = serving.submit(np.arange(1, 5, dtype=np.int32),
                           max_new_tokens=8)
        b = serving.submit(np.arange(1, 5, dtype=np.int32),
                           max_new_tokens=8)
        assert serving.cancel(b) is True
        assert b.status == "cancelled" and b.tokens == []
        while a.status != "done":
            serving.pump()
        assert serving.scheduler.n_cancelled == 1


def _xspans(rt, *names):
    return [e for e in rt.events() if e[0] == "X" and e[1] in names]


class TestDriverSpans:
    """The driver loop, the serve loop under it and each request's phases
    as telemetry spans."""

    def _serve(self, tiny_engine, n=5):
        rng = np.random.default_rng(5)
        vocab = tiny_engine.module.cfg.vocab_size
        fe = ServingFrontend(_serving(tiny_engine))
        try:
            handles = [fe.submit(rng.integers(0, vocab, (k,)), max_new_tokens=m)
                       for k, m in zip([3, 7, 5, 9, 4][:n],
                                       [9, 21, 6, 14, 11])]
            for h in handles:
                assert h.result(timeout=60) == "done"
            time.sleep(0.05)               # the driver goes idle
        finally:
            fe.close()
        return fe, handles

    def test_drive_minus_its_device_waits_is_the_drivers_own_time(
            self, tiny_engine, telemetry_on):
        self._serve(tiny_engine)
        drives = _xspans(telemetry_on, "frontend/drive")
        waits = _xspans(telemetry_on, "serve/chunk_host_wait",
                        "serve/prefill_wait")
        assert drives and waits
        # every device wait happens inside an iteration of the driver
        for w in waits:
            assert any(d[2] <= w[2] and w[2] + w[3] <= d[2] + d[3]
                       for d in drives), w[1]
        own = sum(d[3] for d in drives) - sum(w[3] for w in waits)
        assert own >= 0.0
        for name in ("frontend/feed", "frontend/deliver", "serve/pump"):
            for e in _xspans(telemetry_on, name):
                assert any(d[2] <= e[2] and e[2] + e[3] <= d[2] + d[3]
                           for d in drives), name
        names = telemetry_on.thread_names()
        assert {names[d[4]] for d in drives} == {"serving-frontend-driver"}

    def test_an_idle_server_is_not_starved_by_its_host(
            self, tiny_engine, telemetry_on):
        fe = ServingFrontend(_serving(tiny_engine))
        try:
            h = fe.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=6)
            assert h.result(timeout=60) == "done"
            time.sleep(0.05)
            starved = len(_xspans(telemetry_on,
                                  "serve/starved_after_prefill",
                                  "serve/starved_after_chunk"))
            drives = len(_xspans(telemetry_on, "frontend/drive"))
            idles = len(_xspans(telemetry_on, "frontend/idle_wait"))
            time.sleep(0.1)                # idle: nothing to serve
            assert fe._engine._starved is None
            assert len(_xspans(telemetry_on,
                               "serve/starved_after_prefill",
                               "serve/starved_after_chunk")) == starved
            assert len(_xspans(telemetry_on, "frontend/drive")) == drives
            assert len(_xspans(telemetry_on,
                               "frontend/idle_wait")) > idles
        finally:
            fe.close()

    def test_request_phases_tile_submit_to_finish_under_its_uid(
            self, tiny_engine, telemetry_on):
        fe, handles = self._serve(tiny_engine)
        records = {r["uid"]: r for r in fe.tracing.to_json()["requests"]}
        phases = _xspans(telemetry_on, "request/queued", "request/prefill",
                         "request/decode")
        for h in handles:
            ev = records[h.uid]["events"]
            mine = sorted((e for e in phases if e[5]["uid"] == h.uid),
                          key=lambda e: e[2])
            assert [e[1] for e in mine] == [
                "request/queued", "request/prefill", "request/decode"]
            assert {e[5]["trace_id"] for e in mine} == {h.trace_id}
            assert sum(e[3] for e in mine) / 1e6 == pytest.approx(
                ev["finish"] - ev["submitted"], abs=1e-6)
            for a, b in zip(mine, mine[1:]):       # no gap, no overlap
                assert a[2] + a[3] == pytest.approx(b[2], abs=1e-3)

    def test_the_lane_mark_is_the_schedulers_admit_stamp(self, tiny_engine):
        fe, handles = self._serve(tiny_engine)
        records = {r["uid"]: r for r in fe.tracing.to_json()["requests"]}
        waited = 0
        for h in handles:
            rec, req = records[h.uid], h._request
            ev = rec["events"]
            assert ev["lane"] == req.admit_t
            assert ev["prefill"] == req.first_token_t
            assert (ev["submitted"] <= ev["admitted"] <= ev["lane"]
                    <= ev["prefill"] <= ev["first_token"] <= ev["finish"])
            assert rec["queue_wait_s"] == pytest.approx(
                ev["lane"] - ev["submitted"])
            assert rec["prefill_s"] == pytest.approx(
                ev["first_token"] - ev["lane"])
            waited += rec["queue_wait_s"] > rec["prefill_s"]
        # five requests on two lanes: the later ones wait for a lane far
        # longer than their prefill takes — the wait is no longer the TTFT
        assert waited >= 1

    def test_the_driver_thread_is_named_for_the_profilers_trace(
            self, tiny_engine):
        """The profiler labels a host thread's line by its OS name; Linux
        keeps 15 characters of it."""
        fe = ServingFrontend(_serving(tiny_engine))
        try:
            path = f"/proc/self/task/{fe._thread.native_id}/comm"
            want = "serving-frontend-driver"[:15]
            # the thread names itself as its first act: give it a moment
            deadline = time.monotonic() + 5.0
            while True:
                try:
                    with open(path) as f:
                        name = f.read().strip()
                except OSError:
                    pytest.skip("no /proc thread names here")
                if name == want or time.monotonic() > deadline:
                    break
                time.sleep(0.01)
            assert name == want
        finally:
            fe.close()


class TestServingFrontend:
    def test_streaming_parity_with_engine_run(self, tiny_engine):
        """Streamed greedy tokens — blocking iterator AND non-blocking
        poll — must be bit-identical to a plain ServingEngine.run of the
        same prompts."""
        rng = np.random.default_rng(0)
        vocab = tiny_engine.module.cfg.vocab_size
        prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
                   for n in [3, 7, 5, 9]]
        ref = _serving(tiny_engine).run(list(prompts), max_new_tokens=6)
        fe = ServingFrontend(_serving(tiny_engine))
        try:
            handles = [fe.submit(p, max_new_tokens=6) for p in prompts]
            streamed = [list(h) for h in handles]    # blocking iterators
            for h, toks, r in zip(handles, streamed, ref):
                assert h.status == "done"
                assert toks == h.tokens
                np.testing.assert_array_equal(h.output_ids, r.output_ids)
                assert h.poll() == []    # iterator consumed the cursor
            # poll() path: fresh handle, drain via polling
            h = fe.submit(prompts[0], max_new_tokens=6)
            got = []
            while not h.done or len(got) < len(h.tokens):
                got.extend(h.poll())
                time.sleep(0.001)
            assert h.result(timeout=10) == "done" and got == ref[0].tokens
        finally:
            fe.close()

    def test_cancel_resolves_cancelled(self, tiny_engine):
        fe = ServingFrontend(_serving(tiny_engine))
        try:
            h = fe.submit(np.arange(1, 5, dtype=np.int32),
                          max_new_tokens=48)
            h.cancel()
            assert h.result(timeout=30) == "cancelled"
            assert len(h.tokens) < 48
            # the engine survives: the next request completes normally
            h2 = fe.submit(np.arange(1, 5, dtype=np.int32),
                           max_new_tokens=4)
            assert h2.result(timeout=30) == "done"
            assert len(h2.tokens) == 4
        finally:
            fe.close()

    def test_submit_rejections_carry_reasons(self, tiny_engine):
        fe = ServingFrontend(
            _serving(tiny_engine),
            admission=AdmissionConfig(rate_per_tenant=0.001,
                                      burst_per_tenant=1.0))
        try:
            p = np.arange(1, 5, dtype=np.int32)
            dead = fe.submit(p, deadline_s=0.0, max_new_tokens=4)
            assert dead.status == "rejected"
            assert dead.reject_reason == REJECT_DEADLINE_EXPIRED
            ok = fe.submit(p, tenant="spammy", max_new_tokens=4)
            limited = fe.submit(p, tenant="spammy", max_new_tokens=4)
            assert limited.status == "rejected"
            assert limited.reject_reason == REJECT_RATE_LIMITED
            assert ok.result(timeout=30) == "done"
            counters = fe.tracing.counters
            assert counters["rejected:deadline_expired"] == 1
            assert counters["rejected:rate_limited"] == 1
        finally:
            fe.close()

    def test_engine_crash_resolves_all_handles_with_error(self, tiny_engine):
        """An injected decode fault must convert every outstanding
        request into a structured error result — no hung callers — and
        poison later submits."""
        serving = _serving(tiny_engine, max_batch=2)

        def boom(*a, **k):
            raise RuntimeError("injected decode fault")

        serving._jit_decode_chunk = boom
        fe = ServingFrontend(serving)
        try:
            handles = [fe.submit(np.arange(1, 5, dtype=np.int32),
                                 max_new_tokens=8) for _ in range(5)]
            for h in handles:
                assert h.result(timeout=30) == "error"
                assert "injected decode fault" in h.error
            assert fe.crashed
            late = fe.submit(np.arange(1, 3, dtype=np.int32))
            assert late.status == "rejected"
            assert late.reject_reason == REJECT_FRONTEND_CLOSED
        finally:
            fe.close(timeout=5)

    def test_close_drains_inflight_work(self, tiny_engine):
        fe = ServingFrontend(_serving(tiny_engine))
        handles = [fe.submit(np.arange(1, 5, dtype=np.int32),
                             max_new_tokens=6) for _ in range(4)]
        fe.close()                     # returns only after the drain
        for h in handles:
            assert h.status == "done" and len(h.tokens) == 6
        rejected = fe.submit(np.arange(1, 3, dtype=np.int32))
        assert rejected.status == "rejected"
        assert rejected.reject_reason == REJECT_FRONTEND_CLOSED
        fe.close()                     # idempotent

    def test_priority_admission_under_contention(self, tiny_engine):
        """With one slot and a deep pending queue, high-priority arrivals
        submitted AFTER low-priority ones must still admit first (the
        frontend heap rules the backlog, not arrival order)."""
        fe = ServingFrontend(_serving(tiny_engine, max_batch=1),
                             feed_depth=1)
        try:
            p = np.arange(1, 5, dtype=np.int32)
            first = fe.submit(p, max_new_tokens=24)   # occupies the slot
            lows = [fe.submit(p, priority=PRIORITY_LOW, max_new_tokens=2)
                    for _ in range(3)]
            high = fe.submit(p, priority=PRIORITY_HIGH, max_new_tokens=2)
            for h in [first, high] + lows:
                assert h.result(timeout=60) == "done"
            traces = {t["uid"]: t
                      for t in fe.tracing.to_json()["requests"]}
            high_admit = traces[high.uid]["events"]["admitted"]
            low_admits = [traces[h.uid]["events"]["admitted"]
                          for h in lows]
            # at most one low can have been fed (feed_depth=1) before the
            # high-priority arrival; every other low must admit after it
            assert sum(t > high_admit for t in low_admits) >= 2
        finally:
            fe.close()
