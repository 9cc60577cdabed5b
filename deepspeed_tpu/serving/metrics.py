"""Live serving metrics, emitted through the monitor fan-out.

Events ride the existing ``(label, value, sample)`` contract of
``deepspeed_tpu/monitor/monitor.py`` (reference monitor/monitor.py:45), so
any configured writer — CSV, TensorBoard, W&B — picks them up unchanged.
``sample`` is the decode-iteration counter: serving dashboards line up
against the same x-axis the training monitor uses for steps.

Labels:
  serving/tokens_per_s      aggregate decode throughput since start
  serving/ttft_s            mean time-to-first-token over finished requests
  serving/ttft_p50_s        reservoir-sampled TTFT percentiles (p50/p95/
  serving/ttft_p95_s        p99) — tail latency, the number SLOs are
  serving/ttft_p99_s        written against; the mean stays for dashboards
  serving/queue_depth       requests waiting for a slot
  serving/slot_occupancy    fraction of KV slots leased [0, 1]
  serving/requests_done     completed requests (cumulative)
  serving/rejected_total    backpressure rejections (cumulative)
  serving/prefill_padding_waste
                            fraction of prefill compute spent on bucket
                            padding: 1 - true_prompt_tokens/padded_tokens
                            (0 when every prompt exactly fills its bucket)
  serving/prefill_programs  distinct compiled (batch, bucket) prefill
                            program shapes so far (the compile-cache cost
                            of bucketed prefill, watched so it stays
                            bounded)
  serving/prefix_cache_hits admissions served from the paged prefix cache
                            (prefill skipped; blocks shared COW)
  serving/prefix_cache_misses
                            paged admissions that ran a real prefill
                            (0 for both in dense mode)
  serving/prefix_hit_rate   hits / (hits + misses), 0.0 before the first
                            paged admission
  serving/cow_forks         copy-on-write block forks (a shared partial
                            block privatized for one request)
"""

from __future__ import annotations

import random
import time
from types import SimpleNamespace
from typing import Dict, List, Optional, Sequence


class Reservoir:
    """Fixed-size uniform reservoir (Vitter's algorithm R) for streaming
    percentile estimates. Under ``capacity`` observations the percentiles
    are EXACT; past it each seen value has equal probability of being in
    the sample, so long-running servers keep an unbiased tail estimate in
    O(capacity) memory. Host-side only; seeded so runs are
    reproducible."""

    def __init__(self, capacity: int = 1024, seed: int = 0):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.capacity = int(capacity)
        self._rng = random.Random(seed)
        self.values: List[float] = []
        self.n_seen = 0
        self.total = 0.0        # running sum over ALL seen (not the sample)

    def add(self, x: float) -> None:
        self.n_seen += 1
        self.total += float(x)
        if len(self.values) < self.capacity:
            self.values.append(float(x))
        else:
            j = self._rng.randrange(self.n_seen)
            if j < self.capacity:
                self.values[j] = float(x)

    def percentile(self, q: float) -> float:
        """Linear-interpolated percentile over the sample, q in [0, 100]
        (out-of-range q is clamped, never an index error); 0.0 when
        empty (matches the mean-TTFT zero default)."""
        if not self.values:
            return 0.0
        xs = sorted(self.values)
        if len(xs) == 1:
            return xs[0]
        q = min(100.0, max(0.0, float(q)))
        pos = (q / 100.0) * (len(xs) - 1)
        lo = int(pos)
        hi = min(lo + 1, len(xs) - 1)
        return xs[lo] + (pos - lo) * (xs[hi] - xs[lo])

    def percentiles(self, qs: Sequence[float] = (50, 95, 99)
                    ) -> Dict[float, float]:
        return {q: self.percentile(q) for q in qs}


def csv_monitor_master(output_path: str, job_name: str = "serving"):
    """A CSV-only MonitorMaster for serving/benchmark runs that have no
    DeepSpeedConfig — same writer class, same on-disk format."""
    from ..monitor.monitor import MonitorMaster
    cfg = SimpleNamespace(
        tensorboard=SimpleNamespace(enabled=False),
        wandb=SimpleNamespace(enabled=False),
        csv_monitor=SimpleNamespace(enabled=True, output_path=output_path,
                                    job_name=job_name))
    return MonitorMaster(cfg)


class ServingMetrics:
    """Aggregates serving counters and periodically flushes them as monitor
    events. ``clock`` is injectable for deterministic tests."""

    def __init__(self, monitor=None, *, emit_every_steps: int = 16,
                 clock=time.perf_counter):
        self.monitor = monitor
        self.emit_every_steps = max(1, int(emit_every_steps))
        self.clock = clock
        self.t0: Optional[float] = None
        self.tokens_out = 0
        self.decode_steps = 0
        self.requests_done = 0
        self.rejected = 0
        # what the expert layers routed, "<prefill|decode>_<counter>" ->
        # the sum over the programs' steps (moe/grouped.py COUNTERS)
        self.routing: Dict[str, float] = {}
        # what the model's own step counters summed (on_state_rows)
        self.state_rows: Dict[str, float] = {}
        self._ttft_sum = 0.0
        self._ttft_n = 0
        self.ttft_reservoir = Reservoir()
        self.prefill_prompt_tokens = 0
        self.prefill_padded_tokens = 0
        self.prefill_programs = 0
        self.n_prefix_hits = 0
        self.n_prefix_misses = 0
        self.n_cow_forks = 0
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.kv_blocks_read = 0
        self.kv_blocks_arena = 0

    # ----------------------------------------------------------- recording
    def start(self) -> None:
        if self.t0 is None:
            self.t0 = self.clock()

    def on_tokens(self, n: int) -> None:
        self.tokens_out += int(n)

    def on_decode_step(self) -> None:
        self.decode_steps += 1

    def on_finished(self, requests) -> None:
        for req in requests:
            self.requests_done += 1
            if req.ttft_s is not None:
                self._ttft_sum += req.ttft_s
                self._ttft_n += 1
                self.ttft_reservoir.add(req.ttft_s)

    def on_rejected(self, n: int = 1) -> None:
        self.rejected += int(n)

    def on_prefill(self, n_prompts: int, bucket_len: int,
                   prompt_tokens: int, n_programs: int) -> None:
        """One batched bucketed prefill: ``n_prompts`` prompts padded to
        ``bucket_len`` (``prompt_tokens`` true tokens among them);
        ``n_programs`` is the engine's running count of distinct compiled
        (batch, bucket) prefill shapes."""
        self.prefill_prompt_tokens += int(prompt_tokens)
        self.prefill_padded_tokens += int(n_prompts) * int(bucket_len)
        self.prefill_programs = int(n_programs)

    def on_routing(self, kind: str, name: str, value: float) -> None:
        """A counter of what the expert layers routed in one prefill or
        decode program (``kind``), summed on the device over its steps."""
        key = f"{kind}_{name}"
        self.routing[key] = self.routing.get(key, 0.0) + value

    def on_state_rows(self, name: str, value: float) -> None:
        """A counter of what the lanes' state held or a decode chunk's steps
        read of it, under the name the model gave it (a model whose lane
        state is not a row a position: ``GPT.step_counters``)."""
        self.state_rows[name] = self.state_rows.get(name, 0.0) + value

    def on_prefix(self, hit: bool) -> None:
        """One paged admission resolved against the prefix cache."""
        if hit:
            self.n_prefix_hits += 1
        else:
            self.n_prefix_misses += 1

    def on_cow(self) -> None:
        """One copy-on-write block fork (shared tail privatized)."""
        self.n_cow_forks += 1

    def on_spec(self, proposed: int, accepted: int) -> None:
        """One speculative chunk consumed: ``proposed`` draft tokens
        offered to verification, ``accepted`` of them kept."""
        self.spec_proposed += int(proposed)
        self.spec_accepted += int(accepted)

    def on_kv_read(self, read: float, arena: int) -> None:
        """One decode chunk consumed: blocks of a layer's KV rows its steps
        read, and the blocks those steps would read of the whole arena
        (``read`` may be fractional: a mean over a block's layers)."""
        self.kv_blocks_read += read
        self.kv_blocks_arena += int(arena)

    # ------------------------------------------------------------ reading
    @property
    def kv_read_share(self) -> float:
        """Share of the arena's rows a decode step read, in blocks (1.0
        where the step reads every row; 0.0 before the first chunk)."""
        return (self.kv_blocks_read / self.kv_blocks_arena
                if self.kv_blocks_arena else 0.0)

    @property
    def padding_waste(self) -> float:
        """Fraction of padded prefill positions that carried no prompt
        token (0.0 before the first prefill)."""
        if not self.prefill_padded_tokens:
            return 0.0
        return 1.0 - self.prefill_prompt_tokens / self.prefill_padded_tokens

    @property
    def mean_ttft_s(self) -> float:
        return self._ttft_sum / self._ttft_n if self._ttft_n else 0.0

    def tokens_per_s(self) -> float:
        if self.t0 is None:
            return 0.0
        dt = self.clock() - self.t0
        return self.tokens_out / dt if dt > 0 else 0.0

    @property
    def prefix_hit_rate(self) -> float:
        n = self.n_prefix_hits + self.n_prefix_misses
        return self.n_prefix_hits / n if n else 0.0

    @property
    def spec_acceptance_rate(self) -> float:
        """Accepted / proposed draft tokens (0.0 before any speculative
        chunk ran) — the lever behind speculative speedup: per-step
        emitted tokens average 1 + rate * k."""
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed else 0.0)

    def snapshot(self, queue_depth: int, occupancy: float) -> Dict[str, float]:
        pct = self.ttft_reservoir.percentiles((50, 95, 99))
        return {
            "serving/tokens_per_s": self.tokens_per_s(),
            "serving/ttft_s": self.mean_ttft_s,
            "serving/ttft_p50_s": pct[50],
            "serving/ttft_p95_s": pct[95],
            "serving/ttft_p99_s": pct[99],
            "serving/queue_depth": float(queue_depth),
            "serving/slot_occupancy": float(occupancy),
            "serving/requests_done": float(self.requests_done),
            "serving/rejected_total": float(self.rejected),
            "serving/prefill_padding_waste": float(self.padding_waste),
            "serving/prefill_programs": float(self.prefill_programs),
            "serving/prefix_cache_hits": float(self.n_prefix_hits),
            "serving/prefix_cache_misses": float(self.n_prefix_misses),
            "serving/prefix_hit_rate": float(self.prefix_hit_rate),
            "serving/cow_forks": float(self.n_cow_forks),
            "serving/spec_acceptance_rate": float(self.spec_acceptance_rate),
            "serving/kv_read_share": float(self.kv_read_share),
        }

    # ------------------------------------------------------------ emitting
    def maybe_emit(self, queue_depth: int, occupancy: float,
                   force: bool = False) -> Optional[Dict[str, float]]:
        """Write a snapshot through the monitor every ``emit_every_steps``
        decode iterations (always on ``force`` — the drain path, so short
        benchmark runs still land their last rows)."""
        if not force and self.decode_steps % self.emit_every_steps != 0:
            return None
        snap = self.snapshot(queue_depth, occupancy)
        if self.monitor is not None:
            events = [(label, value, self.decode_steps)
                      for label, value in snap.items()]
            self.monitor.write_events(events)
            if force:
                self.monitor.flush()
        return snap
