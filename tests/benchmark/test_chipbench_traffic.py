"""The traffic generator and the client loops: seeds, distributions, and what
an open and a closed loop charge a stalled server."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from chipbench import spec, traffic  # noqa: E402
from chipbench.client import ClosedLoop, run_open_loop  # noqa: E402
from chipbench.harness import percentile  # noqa: E402

BENCH = spec.load_benchmark()
CHAT = spec.load_json(spec.find_mix(BENCH, "chat-steady"))
BATCH = spec.load_json(spec.find_mix(BENCH, "batch-closed"))


def _shape(plan):
    return [(len(r.prompt), r.max_new_tokens, round(r.due_s, 9)) for r in plan]


@pytest.mark.parametrize("seed", [0, 7, 3_000_000_011])
def test_same_seed_same_schedule_and_lengths(seed):
    a = traffic.open_loop_plan(CHAT, seed, 45.0, 50432)
    b = traffic.open_loop_plan(CHAT, seed, 45.0, 50432)
    assert _shape(a) == _shape(b)
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_another_seed_the_same_work_in_the_same_order():
    """Sizes, gaps and their order are the mix's; the seed draws the ids."""
    a = traffic.open_loop_plan(CHAT, 1, 45.0, 50432)
    b = traffic.open_loop_plan(CHAT, 2, 45.0, 50432)
    assert _shape(a) == _shape(b)
    assert len(a) == round(CHAT["arrivals"]["rate_per_s"] * 45.0)
    assert not all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c1 = traffic.closed_loop_plan(BATCH, 1, 50432)
    c2 = traffic.closed_loop_plan(BATCH, 3_000_000_011, 50432)
    assert _shape(c1) == _shape(c2) and len(c1) == BATCH["population"]
    assert not np.array_equal(c1[0].prompt, c2[0].prompt)


def test_the_mixes_order_is_a_shuffle_of_the_quantiles():
    """Not sorted (neighbours are mixed sizes), and every quantile once."""
    plan = traffic.closed_loop_plan(BATCH, 0, 50432)
    lens = [len(r.prompt) for r in plan]
    assert lens != sorted(lens) and lens != sorted(lens, reverse=True)
    assert sorted(lens) == traffic.length_quantiles(
        BATCH["prompt_len"], len(plan)).tolist()
    outs = [r.max_new_tokens for r in plan]
    assert sorted(outs) == traffic.length_quantiles(
        BATCH["output_len"], len(plan)).tolist()
    # prompt and answer lengths are shuffled apart: not rank-matched
    assert np.corrcoef(lens, outs)[0, 1] < 0.5


@pytest.mark.parametrize("key,median,lo,hi", [("prompt_len", 256, 16, 1536),
                                              ("output_len", 64, 8, 256)])
def test_length_distributions_median_and_clips(key, median, lo, hi):
    dist = BATCH[key]
    assert (dist["median"], dist["min"], dist["max"]) == (median, lo, hi)
    xs = traffic.length_quantiles(dist, 2048)
    assert xs.min() == lo and xs.max() == hi          # both tails clipped
    assert abs(float(np.median(xs)) - median) <= 0.02 * median
    # lognormal: the log of the unclipped middle is symmetric about the median
    mid = xs[(xs > lo) & (xs < hi)]
    q25, q75 = np.percentile(mid, [25, 75])
    assert abs(np.log(q75 / median) - np.log(median / q25)) < 0.15


def test_token_ids_are_uniform_over_the_vocabulary_and_seeded():
    plan = traffic.closed_loop_plan(BATCH, 5, 50432)
    ids = np.concatenate([r.prompt for r in plan[:200]])
    assert ids.dtype == np.int32 and ids.min() >= 0 and ids.max() < 50432
    assert ids.max() > 50000 and abs(ids.mean() - 25216) < 500


def test_arrival_gaps_rate_and_burstiness():
    gaps = traffic.gap_quantiles({"process": "poisson", "rate_per_s": 4.0},
                                 4000)
    assert abs(gaps.sum() - 1000.0) < 1e-6            # exactly n / rate
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1  # exponential: cv 1


def test_an_arrival_process_no_cell_uses_is_refused():
    with pytest.raises(ValueError):
        traffic.gap_quantiles({"process": "gamma", "rate_per_s": 4.0,
                               "cv": 2.5}, 100)


def test_train_dataset_is_a_pure_function_of_seed_and_index():
    a = traffic.train_dataset(11, 50304, 2048)
    b = traffic.train_dataset(11, 50304, 2048)
    c = traffic.train_dataset(12, 50304, 2048)
    assert np.array_equal(a[5]["input_ids"], b[5]["input_ids"])
    assert not np.array_equal(a[5]["input_ids"], a[6]["input_ids"])
    assert not np.array_equal(a[5]["input_ids"], c[5]["input_ids"])
    assert a[0]["input_ids"].shape == (2048,)


# ------------------------------------------------------------ fake server
class SimClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def sleep(self, dt):
        self.t += max(dt, 1e-4)


class FakeHandle:
    def __init__(self, server, n):
        self.server, self.n = server, n
        self.first_at = self.done_at = None
        self._read = 0

    def _have(self):
        if self.first_at is None or self.server.clock() < self.first_at:
            return 0
        if self.server.clock() >= self.done_at:
            return self.n
        return 1

    def poll(self):
        have = self._have()
        new = list(range(self._read, have))
        self._read = have
        return new

    @property
    def done(self):
        return self.done_at is not None and self.server.clock() >= self.done_at

    @property
    def status(self):
        return "done" if self.done else "pending"

    def cancel(self):
        self.done_at = self.first_at = self.server.clock()


class FakeServer:
    """One request at a time, ``service_s`` each; between ``stall`` [a, b)
    it serves nothing (a compile, a long prefill, a GC pause)."""

    def __init__(self, clock, service_s, stall=(0.0, 0.0)):
        self.clock, self.service_s, self.stall = clock, service_s, stall
        self.free_at = 0.0

    def submit(self, prompt, max_new_tokens):
        h = FakeHandle(self, max_new_tokens)
        start = max(self.clock(), self.free_at)
        if self.stall[0] <= start < self.stall[1]:
            start = self.stall[1]
        h.first_at = start + self.service_s / 2
        h.done_at = start + self.service_s
        self.free_at = h.done_at
        return h


def _plan(n, gap):
    return [traffic.PlannedRequest(i, i * gap, np.zeros(4, np.int32), 4)
            for i in range(n)]


def test_open_loop_charges_a_stall_to_the_requests_behind_it():
    clock = SimClock()
    server = FakeServer(clock, service_s=0.01, stall=(1.0, 2.0))
    client, late, t0 = run_open_loop(server, _plan(300, 0.02), drain_s=5.0,
                                     clock=clock, sleep=clock.sleep)
    assert not client.active and len(client.finished) == 300
    ttft = {t.plan.index: t.ttft_s() for t in client.finished}
    # due before the stall: served at once; due DURING it: waits for its end,
    # and the queue that built up is charged to those behind it too
    assert ttft[10] < 0.02
    assert ttft[50] > 0.9                       # due at 1.00, served at ~2.0
    assert ttft[99] > 0.4                       # due at 1.98: still queued
    assert ttft[299] < 0.05                     # the backlog has drained
    assert max(late) < 0.005                    # the generator kept its times
    # every request was sent on schedule, whatever had finished
    sent = sorted(t.submit_t - t0 for t in client.finished)
    assert abs(sent[60] - 60 * 0.02) < 0.005


def test_ttft_is_timed_from_the_due_time_not_the_send_time():
    clock = SimClock()
    server = FakeServer(clock, service_s=0.01)

    def slow_sleep(dt):             # a starved generator: wakes 50 ms late
        clock.sleep(dt + 0.05)

    client, late, _ = run_open_loop(server, _plan(20, 0.1), drain_s=5.0,
                                    clock=clock, sleep=slow_sleep)
    tr = client.finished[5]
    assert tr.submit_t > tr.due_t                       # sent late ...
    assert tr.ttft_s() == pytest.approx(tr.first_t - tr.due_t)
    assert tr.ttft_s() > tr.first_t - tr.submit_t       # ... and charged so
    assert percentile(late, 95) > 0.03                  # and it is reported


def test_closed_loop_does_not_charge_a_stall_to_later_requests():
    clock = SimClock()
    server = FakeServer(clock, service_s=0.01, stall=(1.0, 2.0))
    loop = ClosedLoop(server, _plan(64, 0.0), clients=1, clock=clock,
                      sleep=clock.sleep)
    loop.run_until(lambda: clock() >= 3.0)
    done = loop.client.finished
    ttft = [t.ttft_s() for t in done]
    # one request waited out the stall; every other saw an idle server,
    # because a closed loop sends less while the server is slow
    assert sum(1 for x in ttft if x > 0.5) == 1
    assert sorted(ttft)[len(ttft) // 2] < 0.02
    assert len(done) < 3.0 / 0.01 * 0.75        # the stall cost throughput
    assert all(t.ok for t in done)


def test_tpot_is_per_request_not_per_gap():
    clock = SimClock()
    server = FakeServer(clock, service_s=0.08)
    client, _, _ = run_open_loop(server, _plan(3, 1.0), drain_s=5.0,
                                 clock=clock, sleep=clock.sleep)
    for t in client.finished:
        # 4 tokens: first at +40 ms, the other three together at +80 ms
        assert t.n_tokens == 4
        assert t.tpot_s() == pytest.approx(0.04 / 3, abs=2e-3)
