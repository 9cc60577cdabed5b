"""The load generator's side of serving: what a client sees, on its own clock.

Pure Python over a front end's ``submit(prompt, max_new_tokens=...)`` and the
handle's ``poll()`` / ``done`` / ``status`` — no JAX — so the loops can be
tested against a fake server with a simulated clock. One thread: the same
loop submits what is due and collects what has arrived, every ``tick_s``.

Token times are the times the client RECEIVED them (``poll`` found them),
not the server's own marks.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List, Optional, Sequence

from .traffic import PlannedRequest


@dataclasses.dataclass
class Tracked:
    plan: PlannedRequest
    due_t: Optional[float]          # open loop: when it was DUE; else None
    submit_t: float
    handle: Any
    first_t: Optional[float] = None
    last_t: Optional[float] = None
    n_tokens: int = 0
    status: Optional[str] = None    # terminal status, once seen
    done_t: Optional[float] = None

    @property
    def ok(self) -> bool:
        """Finished ``done`` with exactly the tokens it asked for."""
        return (self.status == "done"
                and self.n_tokens == self.plan.max_new_tokens)

    def ttft_s(self) -> Optional[float]:
        """First token received minus the time the request was DUE (open
        loop: a stall is charged to the requests behind it) or, without a
        due time, minus the time it was sent."""
        if self.first_t is None:
            return None
        return self.first_t - (self.due_t if self.due_t is not None
                               else self.submit_t)

    def tpot_s(self) -> Optional[float]:
        """(last token time - first token time) / (tokens - 1): per request,
        not per gap — the engine delivers a chunk of tokens at once."""
        if self.n_tokens < 2 or self.first_t is None:
            return None
        return (self.last_t - self.first_t) / (self.n_tokens - 1)


class Client:
    def __init__(self, frontend, clock: Callable[[], float] = time.perf_counter):
        self.fe = frontend
        self.clock = clock
        self.active: List[Tracked] = []
        self.finished: List[Tracked] = []
        self.token_log: List[tuple] = []      # (time received, n tokens)
        self.kv_samples: List[tuple] = []     # (time, live KV positions)

    def submit(self, plan: PlannedRequest,
               due_t: Optional[float] = None) -> Tracked:
        now = self.clock()
        handle = self.fe.submit(plan.prompt,
                                max_new_tokens=plan.max_new_tokens)
        tr = Tracked(plan=plan, due_t=due_t, submit_t=now, handle=handle)
        self.active.append(tr)
        return tr

    def sweep(self) -> List[Tracked]:
        """Collect what arrived since the last sweep; returns the requests
        that reached a terminal status."""
        now = self.clock()
        ended, arrived = [], 0
        for tr in self.active:
            done = tr.handle.done       # read BEFORE poll: nothing is lost
            new = tr.handle.poll()
            if new:
                if tr.first_t is None:
                    tr.first_t = now
                tr.last_t = now
                tr.n_tokens += len(new)
                arrived += len(new)
            if done:
                tr.status = tr.handle.status
                tr.done_t = now
                ended.append(tr)
        if arrived:
            self.token_log.append((now, arrived))
            # live KV positions, from what the client itself holds: prompt
            # plus tokens received of every request that has its first token
            live = sum(len(t.plan.prompt) + t.n_tokens for t in self.active
                       if t.first_t is not None and t.status is None)
            self.kv_samples.append((now, live))
        if ended:
            self.active = [t for t in self.active if t.status is None]
            self.finished.extend(ended)
        return ended

    def tokens_between(self, t0: float, t1: float) -> int:
        """Tokens received in ``(t0, t1]``."""
        return sum(n for t, n in self.token_log if t0 < t <= t1)

    def all(self) -> List[Tracked]:
        return self.finished + self.active


class ClosedLoop:
    """``clients`` callers that each send their next request when their last
    one ends: a slow server receives less load, and nothing is charged to
    the requests behind a stall."""

    def __init__(self, frontend, plan: Sequence[PlannedRequest], clients: int,
                 *, clock=time.perf_counter, sleep=time.sleep,
                 tick_s: float = 0.001):
        self.client = Client(frontend, clock)
        self.plan, self.clients = list(plan), int(clients)
        self.clock, self.sleep, self.tick_s = clock, sleep, tick_s
        self.taken = 0

    def tick(self) -> None:
        self.client.sweep()
        while len(self.client.active) < self.clients:
            self.client.submit(self.plan[self.taken % len(self.plan)])
            self.taken += 1

    def run_until(self, pred: Callable[[], bool]) -> None:
        while not pred():
            self.tick()
            self.sleep(self.tick_s)

    def run_for(self, seconds: float) -> None:
        end = self.clock() + seconds
        self.run_until(lambda: self.clock() >= end)

    def stop(self, timeout_s: float = 30.0) -> None:
        """Cancel what is in flight and wait for it to resolve."""
        for tr in self.client.active:
            tr.handle.cancel()
        end = self.clock() + timeout_s
        while self.client.active and self.clock() < end:
            self.client.sweep()
            self.sleep(self.tick_s)


def run_open_loop(frontend, plan: Sequence[PlannedRequest], *,
                  drain_s: float, clock=time.perf_counter, sleep=time.sleep,
                  tick_s: float = 0.001,
                  hooks: Sequence[Callable[[float], None]] = ()):
    """Send request i at ``t0 + plan[i].due_s`` whether or not earlier ones
    finished, until the plan is spent; then wait for what is in flight, at
    most ``drain_s`` past the last due time. Returns (client, lateness, t0):
    ``lateness[i]`` is how long after its due time request i was sent — a
    starved generator must not be read as a fast server."""
    client = Client(frontend, clock)
    plan = list(plan)
    t0 = clock()
    lateness: List[float] = []
    i = 0
    give_up = t0 + (plan[-1].due_s if plan else 0.0) + drain_s
    while True:
        now = clock()
        while i < len(plan) and t0 + plan[i].due_s <= now:
            due = t0 + plan[i].due_s
            tr = client.submit(plan[i], due_t=due)
            lateness.append(tr.submit_t - due)
            i += 1
        client.sweep()
        for hook in hooks:
            hook(now - t0)
        now = clock()
        if (i >= len(plan) and not client.active) or now >= give_up:
            break
        wait = tick_s
        if i < len(plan):
            wait = min(wait, max(0.0, t0 + plan[i].due_s - now))
        sleep(wait)
    return client, lateness, t0
