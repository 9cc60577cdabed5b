"""The device timeline of the serve loop (serving/device_timeline.py and its
sites in serving/engine.py), on the CPU and against no wall clock: the
telemetry runtime reads a clock the test owns, and the engine's programs are
handed to a fake device, one queue that runs what it is handed in order on
that clock, whose arrays answer ``is_ready()`` from it."""

import types

import numpy as np
import pytest

from deepspeed_tpu.serving.device_timeline import DISAGGREGATED, DeviceTimeline
from deepspeed_tpu.serving.engine import ServingEngine
from deepspeed_tpu.telemetry import core as tel
from tests.test_serving import _tiny
from tests.test_telemetry import FakeClock as Clock

pytestmark = pytest.mark.telemetry

DEVICE = ("serve/device_decode_chunk", "serve/device_prefill")
LATE = "serve/device_stamp_late"


@pytest.fixture
def clocked(telemetry_on):
    """The process-wide runtime, on, reading the test's clock."""
    real, clock = telemetry_on.clock, Clock()
    telemetry_on.clock = clock
    yield telemetry_on, clock
    telemetry_on.clock = real


def _events(rt, *names):
    """(name, start_s, seconds, attrs) of the ring's spans called ``names``."""
    return [(e[1], e[2] / 1e6, e[3] / 1e6, e[5]) for e in rt.events()
            if e[0] == "X" and e[1] in names]


# ------------------------------------------------------- the timeline alone
def _end(t):
    return types.SimpleNamespace(t1=t)      # a live span that ended at t


class TestTimelineAlone:
    def test_two_exact_stamps_record_the_interval_between_them(self, clocked):
        rt, _ = clocked
        tl = DeviceTimeline()
        first = tl.dispatched("decode_chunk", k=8, lanes=3)
        tl.dispatched("lane_patch")
        second = tl.dispatched("decode_chunk", k=8, lanes=4)
        tl.stamp(first, _end(1.0), exact=True)      # starts the timeline
        assert not _events(rt, *DEVICE)
        tl.dispatched("insert_batch")               # runs after `second`
        tl.stamp(second, _end(1.25), exact=True)
        (name, start, seconds, attrs), = _events(rt, *DEVICE)
        assert name == "serve/device_decode_chunk"
        assert (start, seconds) == pytest.approx((1.0, 0.25))
        assert attrs == {"k": 8, "lanes": 4, "with": "lane_patch"}
        assert tl._open == [("insert_batch", {})]   # rides with the next

    def test_a_prefill_names_its_interval_and_what_rode_with_it(self,
                                                                 clocked):
        rt, _ = clocked
        tl = DeviceTimeline()
        chunk = tl.dispatched("decode_chunk", k=4, lanes=2)
        tl.stamp(chunk, _end(2.0), exact=True)
        tl.dispatched("insert_batch")       # the group before's insert
        pre = tl.dispatched("prefill", n=2, bucket=16, sp=False,
                            prompt_tokens=20, padded_tokens=32)
        tl.stamp(pre, _end(2.5), exact=True)
        (name, _, seconds, attrs), = _events(rt, *DEVICE)
        assert name == "serve/device_prefill" and seconds == 0.5
        assert attrs == {"n": 2, "bucket": 16, "sp": False,
                         "prompt_tokens": 20, "padded_tokens": 32,
                         "with": "insert_batch"}

    def test_a_late_stamp_records_nothing_and_the_next_exact_one_starts_anew(
            self, clocked):
        rt, _ = clocked
        tl = DeviceTimeline()
        a, b, c, d = (tl.dispatched("decode_chunk", k=4, lanes=1)
                      for _ in range(4))
        tl.stamp(a, _end(1.0), exact=True)
        tl.stamp(b, _end(9.0), exact=False)     # ended some time before 9
        assert rt.counter_totals()[LATE] == 1
        tl.stamp(c, _end(9.5), exact=True)      # from when? nobody knows
        assert not _events(rt, *DEVICE)
        tl.stamp(d, _end(10.0), exact=True)
        (_, start, seconds, _), = _events(rt, *DEVICE)
        assert (start, seconds) == (9.5, 0.5)
        assert rt.counter_totals()[LATE] == 1

    def test_starved_seconds_inside_an_interval_are_taken_off_it(self,
                                                                 clocked):
        rt, _ = clocked
        tl = DeviceTimeline()
        a = tl.dispatched("prefill", n=1, bucket=16)
        tl.stamp(a, _end(1.0), exact=True)
        tl.starved(0.25)                # the chip ran dry until 1.25
        b = tl.dispatched("decode_chunk", k=4, lanes=1)
        tl.stamp(b, _end(2.0), exact=True)
        (_, start, seconds, _), = _events(rt, *DEVICE)
        assert (start, seconds) == (1.25, 0.75)
        # and only off that one
        c = tl.dispatched("decode_chunk", k=4, lanes=1)
        tl.stamp(c, _end(3.0), exact=True)
        assert _events(rt, *DEVICE)[-1][1:3] == (2.0, 1.0)

    def test_a_sync_on_a_program_already_closed_is_no_stamp(self, clocked):
        rt, _ = clocked
        tl = DeviceTimeline()
        a = tl.dispatched("decode_chunk", k=4, lanes=1)
        b = tl.dispatched("prefill", n=1, bucket=16)
        tl.stamp(a, _end(1.0), exact=True)
        tl.stamp(b, _end(1.5), exact=True)
        assert not tl.is_open(a) and not tl.is_open(None)
        tl.stamp(a, _end(7.0), exact=False)     # consumed a pump later
        assert LATE not in rt.counter_totals()
        c = tl.dispatched("decode_chunk", k=4, lanes=1)
        tl.stamp(c, _end(2.5), exact=True)
        assert [e[1:3] for e in _events(rt, *DEVICE)] == [(1.0, 0.5),
                                                          (1.5, 1.0)]

    def test_an_idle_server_and_programs_never_synced_record_nothing(
            self, clocked):
        rt, _ = clocked
        tl = DeviceTimeline()
        a = tl.dispatched("decode_chunk", k=4, lanes=1)
        tl.stamp(a, _end(1.0), exact=True)
        tl.reset()                      # out of requests for a minute
        b = tl.dispatched("prefill", n=1, bucket=16)
        tl.stamp(b, _end(61.0), exact=True)
        # two heavy programs under one stamp (the first was never synced:
        # telemetry went off and on again between them)
        tl.dispatched("decode_chunk", k=4, lanes=1)
        c = tl.dispatched("decode_chunk", k=4, lanes=1)
        tl.stamp(c, _end(62.0), exact=True)
        assert not _events(rt, *DEVICE)
        # telemetry went off under a sync: its span is the no-op one
        d = tl.dispatched("decode_chunk", k=4, lanes=1)
        tl.stamp(d, tel.NOOP_SPAN, exact=True)
        assert tl.is_open(d)

    def test_a_refused_timeline_says_so_once_by_name(self, clocked):
        rt, _ = clocked
        assert DeviceTimeline().on() is not None
        tl = DeviceTimeline(DISAGGREGATED)
        for _ in range(3):
            assert tl.on() is None
        marks = [e for e in rt.events() if e[0] == "i"]
        assert [(m[1], m[4]) for m in marks] == [
            ("serve/device_timeline_off", {"reason": DISAGGREGATED})]


# ------------------------------------------------- the engine's own sites
SECONDS = {"decode_chunk": 0.080, "prefill": 0.030, "insert_batch": 0.002,
           "lane_patch": 0.0001}


class _Array:
    """What a program handed the fake device returns where the engine
    will sync: ready once the clock has passed its program's end; a sync
    on it waits (moves the clock) until then."""

    def __init__(self, device, real, done_at):
        self._device, self._real, self._done_at = device, real, done_at

    def is_ready(self):
        self._device.calls["is_ready"] += 1
        return (not self._device.never_ready
                and self._device.clock.t >= self._done_at)

    def _wait(self):
        clock = self._device.clock
        clock.t = max(clock.t, self._done_at)

    def block_until_ready(self):
        self._device.calls["block_until_ready"] += 1
        self._wait()
        return self

    def __array__(self, dtype=None, copy=None):
        self._device.calls["asarray"] += 1
        self._wait()
        return np.asarray(self._real, dtype=dtype)


class FakeDevice:
    """One queue on the test's clock: a program starts when it is handed
    over or when the one before ends, whichever is later, and takes
    SECONDS[name]. The real program runs too (its values are the
    engine's), only its time is the fake's. ``never_ready``: every array
    reports not ready whatever the clock says (a real CPU run, where the
    test's clock does not move with the work)."""

    def __init__(self, clock, never_ready=False):
        self.clock, self.free_at = clock, 0.0
        self.never_ready = never_ready
        self.ran = []       # (name, start, end) in the order handed over
        self.calls = {"is_ready": 0, "block_until_ready": 0, "asarray": 0}

    def _hand(self, name):
        start = max(self.clock.t, self.free_at)
        self.free_at = start + SECONDS[name]
        self.ran.append((name, start, self.free_at))
        return self.free_at

    def attach(self, serving):
        def wrap(owner, attr, name, synced):
            real = getattr(owner, attr)

            def program(*args):
                done_at = self._hand(name)
                out = real(*args)
                if not synced:
                    return out
                return (_Array(self, out[0], done_at),) + tuple(out[1:])
            setattr(owner, attr, program)

        wrap(serving, "_jit_decode_chunk", "decode_chunk", True)
        wrap(serving, "_jit_prefill", "prefill", True)
        wrap(serving.kv, "_insert_batch", "insert_batch", False)
        wrap(serving, "_jit_lane_patch", "lane_patch", False)
        return serving


@pytest.fixture(scope="module")
def tiny_engine():
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    model, params = _tiny()
    return ds.init_inference(model, model_parameters=params,
                             dtype=jnp.float32)


def _serving(tiny_engine, device):
    return device.attach(ServingEngine(
        engine=tiny_engine, max_batch=2, max_prompt_len=16, max_queue=8,
        decode_chunk=4))


def _traffic(serving):
    """Five requests over two lanes, answers of different lengths: lanes
    retire and are refilled behind a chunk launched ahead."""
    rng = np.random.default_rng(3)
    vocab = serving.module.cfg.vocab_size
    return [serving.submit(rng.integers(0, vocab, (n,)).astype(np.int32),
                           max_new_tokens=m)
            for n, m in zip([3, 7, 5, 9, 4], [9, 21, 6, 14, 11])]


def _device_seconds(attrs, heavy):
    return SECONDS[heavy] + sum(SECONDS[w]
                                for w in attrs["with"].split(",") if w)


class TestEngineTimeline:
    def test_every_interval_is_its_programs_device_time(self, tiny_engine,
                                                        clocked):
        """The host is never late here (it costs no time on the test's
        clock), so every stamp is exact and every interval holds exactly
        the device seconds of the programs it names."""
        rt, clock = clocked
        device = FakeDevice(clock)
        serving = _serving(tiny_engine, device)
        requests = _traffic(serving)
        serving.run()
        assert all(r.status == "done" for r in requests)
        assert LATE not in rt.counter_totals()
        chunks = _events(rt, "serve/device_decode_chunk")
        prefills = _events(rt, "serve/device_prefill")
        n_chunks = sum(1 for r in device.ran if r[0] == "decode_chunk")
        n_prefills = sum(1 for r in device.ran if r[0] == "prefill")
        # all but the very first stamp, which only starts the timeline
        assert len(chunks) + len(prefills) == n_chunks + n_prefills - 1
        assert len(prefills) == n_prefills - 1 >= 2 and len(chunks) >= 8
        for _, _, seconds, attrs in chunks:
            assert attrs["k"] == 4 and 1 <= attrs["lanes"] <= 2
            assert seconds == pytest.approx(
                _device_seconds(attrs, "decode_chunk"))
        for _, _, seconds, attrs in prefills:
            assert attrs["bucket"] == 16 and attrs["sp"] is False
            assert attrs["padded_tokens"] == 16 * attrs["n"]
            assert 0 < attrs["prompt_tokens"] <= attrs["padded_tokens"]
            assert seconds == pytest.approx(
                _device_seconds(attrs, "prefill"))
        rode = ",".join(a["with"] for *_, a in chunks + prefills)
        assert "insert_batch" in rode and "lane_patch" in rode
        # device time + starved time is the whole run, first stamp to last
        starved = sum(e[2] for e in _events(
            rt, "serve/starved_after_prefill", "serve/starved_after_chunk"))
        first = min(e[1] for e in chunks + prefills)
        assert sum(e[2] for e in chunks + prefills) + starved == \
            pytest.approx(clock.t - first)
        # every prompt but the first call's two (its stamp is the first)
        assert serving.metrics.prefill_prompt_tokens == 3 + 7 + 5 + 9 + 4
        assert sum(a["prompt_tokens"] for *_, a in prefills) == 5 + 9 + 4

    def test_a_prefill_behind_a_chunk_launched_ahead(self, tiny_engine,
                                                     clocked):
        """``serve/prefill_wait`` keeps the total it had (the host waited
        through the chunk and the prefill either way), and its two
        children split it into one ``serve/device_decode_chunk`` and one
        ``serve/device_prefill``."""
        rt, clock = clocked
        serving = _serving(tiny_engine, FakeDevice(clock))
        _traffic(serving)
        serving.run()
        behind = 0
        for _, w0, wait_s, _ in _events(rt, "serve/prefill_wait"):
            inside = [e for e in _events(
                rt, "serve/prefill_wait_chunk_ahead", "serve/prefill_wait_own")
                if w0 <= e[1] and e[1] + e[2] <= w0 + wait_s]
            assert sum(e[2] for e in inside) == pytest.approx(wait_s)
            if len(inside) < 2:
                continue
            behind += 1
            ahead, own = inside
            assert ahead[0] == "serve/prefill_wait_chunk_ahead"
            chunk, = [e for e in _events(rt, "serve/device_decode_chunk")
                      if e[1] + e[2] == pytest.approx(ahead[1] + ahead[2])]
            prefill, = [e for e in _events(rt, "serve/device_prefill")
                        if e[1] + e[2] == pytest.approx(own[1] + own[2])]
            # the host reached the wait as the chunk before ended: the
            # chunk ahead and the prefill are all it waited for
            assert chunk[2] + prefill[2] == pytest.approx(wait_s)
            assert prefill[1] == pytest.approx(chunk[1] + chunk[2])
        assert behind >= 1
        stats = rt.span_stats()
        assert stats["serve/prefill_wait_own"]["count"] == \
            stats["serve/prefill_wait"]["count"]
        # the chunk stamped inside the wait is consumed a pump later, ready
        # by then, and is no stamp: nothing was late
        assert LATE not in rt.counter_totals()
        # host_ms_per_chunk.batch subtracts serve/prefill_wait BY NAME and
        # knows nothing of its children: it reads what it read before
        from chipbench import spec
        read = spec.load_module(spec.find_reader(
            spec.load_benchmark(), "host_ms_per_chunk.batch")).read
        spans = {k: {"count": v["count"], "total_s": v["total_s"]}
                 for k, v in stats.items()}
        spans["frontend/drive"] = spans["serve/pump"]
        parents = {k: v for k, v in spans.items()
                   if "prefill_wait_" not in k and "serve/device_" not in k}
        assert read(None, spans, {}, {}) == read(None, parents, {}, {})

    def test_a_late_stamp_on_a_host_that_fell_behind(self, tiny_engine,
                                                     clocked):
        rt, clock = clocked
        device = FakeDevice(clock)
        serving = _serving(tiny_engine, device)
        serving.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=40)
        for _ in range(4):
            serving.pump()
        before = len(_events(rt, *DEVICE))
        assert before >= 2 and LATE not in rt.counter_totals()
        clock.t += 1.0        # the host slept through the chunk in flight
        serving.pump()          # finds it ready: late
        assert rt.counter_totals()[LATE] == 1
        assert len(_events(rt, *DEVICE)) == before
        serving.pump()          # exact again: starts anew, no interval yet
        assert len(_events(rt, *DEVICE)) == before
        serving.pump()
        assert len(_events(rt, *DEVICE)) == before + 1
        assert _events(rt, *DEVICE)[-1][2] == pytest.approx(
            SECONDS["decode_chunk"])

    def test_starved_time_is_not_device_time(self, tiny_engine, clocked):
        """After a prefill's sync the chip runs dry until the next chunk
        is handed over; a slow host there is ``serve/starved_after_prefill``
        and comes off the interval that follows."""
        rt, clock = clocked
        serving = _serving(tiny_engine, FakeDevice(clock))
        serving.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=20)
        real_launch = serving._launch_chunk

        def slow_launch(state):
            clock.t += 0.5        # half a second before any dispatch
            return real_launch(state)
        serving._launch_chunk = slow_launch
        serving.pump()              # prefill, its sync, then the slow launch
        serving._launch_chunk = real_launch
        serving.pump()
        starved, = _events(rt, "serve/starved_after_prefill")
        assert starved[2] == pytest.approx(0.5)
        chunk, = _events(rt, "serve/device_decode_chunk")
        assert chunk[1] == pytest.approx(starved[1] + starved[2])
        # the insert ran while the host was slow; the chunk alone is left
        assert chunk[2] == pytest.approx(SECONDS["decode_chunk"])
        assert chunk[3]["with"] == "insert_batch"

    def test_off_means_off(self, tiny_engine):
        """Telemetry off: the engine asks no array whether it is ready,
        makes no sync it did not make before, calls nothing of the
        timeline's, and hands the device the same programs in the same
        order as with it on."""
        rt = tel.get_runtime()
        assert not rt.enabled
        device = FakeDevice(Clock())
        serving = _serving(tiny_engine, device)

        del serving._timeline       # touching it raises
        assert serving._tl() is None
        before = (len(rt.events()), rt.span_stats(), rt.counter_totals())
        requests = _traffic(serving)
        serving.run()
        assert all(r.status == "done" for r in requests)
        assert device.calls["is_ready"] == 0
        assert device.calls["block_until_ready"] == 0
        assert (len(rt.events()), rt.span_stats(),
                rt.counter_totals()) == before
        syncs_off = device.calls["asarray"]

        clock = Clock()
        real, rt.clock = rt.clock, clock
        rt.clear()
        rt.enable()
        try:
            traced = FakeDevice(clock)
            serving_on = _serving(tiny_engine, traced)
            on = _traffic(serving_on)
            serving_on.run()
        finally:
            rt.clock = real
            rt.disable()
            rt.clear()
        assert [r[0] for r in traced.ran] == [r[0] for r in device.ran]
        assert [r.tokens for r in on] == [r.tokens for r in requests]
        # one sync a chunk and one a prefill on both sides; the only wait
        # added is the one on a chunk launched ahead of a prefill
        assert traced.calls["asarray"] == syncs_off
        assert 0 < traced.calls["block_until_ready"] <= sum(
            1 for r in traced.ran if r[0] == "prefill")
        assert traced.calls["is_ready"] > 0

    def test_prefill_on_other_chips_refuses_by_name(self, clocked):
        """Disaggregated: the prefill programs run on a queue of their
        own, so two syncs in a row bracket nothing in particular."""
        import jax
        import jax.numpy as jnp
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        rt, _ = clocked
        model, params = _tiny()
        serving = ServingEngine(model, model_parameters=params,
                                dtype=jnp.float32, max_batch=2,
                                decode_chunk=4, paged=True,
                                disaggregate_prefill=True)
        assert serving._handoff_sharding is not None
        assert serving._timeline.refusal == DISAGGREGATED
        requests = _traffic(serving)
        serving.run()
        assert all(r.status == "done" for r in requests)
        assert rt.instant_counts()["serve/device_timeline_off"] == 1
        stats = rt.span_stats()
        assert not set(DEVICE) & set(stats)
        assert LATE not in rt.counter_totals()
        assert stats["serve/prefill_wait"]["count"] >= 3
        assert "serve/prefill_wait_own" not in stats


def never_ready(serving):
    """For a test that runs real programs on the CPU and wants every sync
    to be a stamp: every array the engine syncs on reports not ready."""
    return FakeDevice(Clock(), never_ready=True).attach(serving)


def test_the_fused_and_speculative_loops_are_chunks_like_any_other(
        tiny_engine, telemetry_on):
    for kw in (dict(fused_prefill=True, prefill_chunk=4),
               dict(speculative=True, spec_k=3)):
        telemetry_on.clear()
        serving = never_ready(ServingEngine(
            engine=tiny_engine, max_batch=2, max_prompt_len=16, max_queue=8,
            decode_chunk=4, **kw))
        requests = _traffic(serving)
        while serving.scheduler.has_work() or serving.chunk_in_flight:
            serving.step()          # the synchronous loop too
        assert all(r.status == "done" for r in requests)
        stats = telemetry_on.span_stats()
        chunks = stats["serve/chunk_host_wait"]["count"]
        stamped = stats["serve/device_decode_chunk"]["count"] + \
            stats.get("serve/device_prefill", {"count": 0})["count"]
        # every sync but the first closes an interval
        prefills = stats.get("serve/prefill_wait", {"count": 0})["count"]
        assert stamped == chunks + prefills - 1, kw
        assert LATE not in telemetry_on.counter_totals()
