"""Per-request tracing for the serving frontend.

``ServingMetrics`` (serving/metrics.py) aggregates engine-side counters;
this module records the *per-request* control-plane story the frontend
owns: a span record per request

    submitted -> admitted -> lane -> prefill -> first_token -> chunk[i]
    -> finish

(``admitted``: the admission winner entered the scheduler's FIFO;
``lane``: the scheduler leased it a slot; ``prefill``: the scheduler
stamped its first sampled token; ``first_token``: that token reached the
caller's handle) with derived latency stats (TTFT, TPOT, queue wait)
folded into reservoir-backed p50/p95/p99 histograms (the same ``Reservoir`` the
engine metrics use). Snapshots emit through the existing monitor fan-out
(``(label, value, sample)`` events — CSV/TensorBoard/W&B pick them up
unchanged) and the whole log dumps as JSON for offline analysis
(``frontend_bench.py`` embeds it in ``BENCH_frontend.json``).

Latency fields (all seconds):
  ttft_s        submit -> first streamed token (the user-visible TTFT —
                measured from ``ServingFrontend.submit``, so it includes
                admission queueing, unlike the engine's scheduler-side
                TTFT)
  queue_wait_s  submit -> lane granted (time spent waiting for
                admission + a slot; nothing of the prefill)
  prefill_s     lane granted -> first streamed token: the prefill and
                whatever it queued behind on the device (with the
                bucketed prefill, the decode chunk already in flight).
                ``queue_wait_s + prefill_s == ttft_s``
  tpot_s        mean time per output token after the first
                (first_token -> finish over n_tokens - 1)

Thread safety: one lock around all mutation — marks arrive from the
frontend driver thread while ``snapshot``/``to_json`` may be read from
callers.
"""

from __future__ import annotations

import json
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Deque, Dict, List, Optional

from ...analysis import locks
from ..metrics import Reservoir
from ...telemetry.core import count as _telemetry_count
from ...telemetry.core import gauge as _telemetry_gauge
from ...telemetry.core import record_span as _telemetry_record_span

#: canonical span event names, in lifecycle order
EVENTS = ("submitted", "admitted", "lane", "prefill", "first_token",
          "finish")

#: a finished request's phases as telemetry spans: name, from, to. They
#: tile submit -> finish; a phase whose ends were not both stamped (no
#: lane ever granted, no token ever delivered) is left out
REQUEST_SPANS = (("request/queued", "submitted", "lane"),
                 ("request/prefill", "lane", "first_token"),
                 ("request/decode", "first_token", "finish"))

#: /tenants payload schema
TENANTS_SCHEMA = "dstpu-tenants-v1"


class _TenantStats:
    """Per-tenant terminal aggregates. Goodput counts the tokens of
    requests that finished ``done`` without missing their TTFT SLO —
    requests with no SLO set count as good (delivered tokens with no
    target are not a miss), so untargeted traffic never reads as zero
    goodput."""

    __slots__ = ("counts", "total_tokens", "goodput_tokens",
                 "n_slo_scored", "n_slo_met", "ttft", "tpot")

    def __init__(self, reservoir_capacity: int):
        self.counts: Dict[str, int] = {}
        self.total_tokens = 0
        self.goodput_tokens = 0
        self.n_slo_scored = 0
        self.n_slo_met = 0
        self.ttft = Reservoir(reservoir_capacity)
        self.tpot = Reservoir(reservoir_capacity)

    def fold(self, trace: "RequestTrace") -> None:
        status = trace.status or "unknown"
        self.counts[status] = self.counts.get(status, 0) + 1
        self.total_tokens += trace.n_tokens
        met = trace.slo_ttft_met
        if met is not None:
            self.n_slo_scored += 1
            self.n_slo_met += int(met)
        if status == "done" and met is not False:
            self.goodput_tokens += trace.n_tokens
        if trace.ttft_s is not None:
            self.ttft.add(trace.ttft_s)
        if trace.tpot_s is not None:
            self.tpot.add(trace.tpot_s)

    @property
    def goodput_fraction(self) -> float:
        if self.total_tokens <= 0:
            return 1.0
        return self.goodput_tokens / self.total_tokens

    def to_dict(self) -> Dict[str, Any]:
        return {
            "requests": dict(self.counts),
            "n_requests": sum(self.counts.values()),
            "total_tokens": self.total_tokens,
            "goodput_tokens": self.goodput_tokens,
            "goodput_fraction": self.goodput_fraction,
            "slo": {"scored": self.n_slo_scored,
                    "met": self.n_slo_met},
            "ttft_s": {"p50": self.ttft.percentile(50),
                       "p95": self.ttft.percentile(95),
                       "n": self.ttft.n_seen},
            "tpot_s": {"p50": self.tpot.percentile(50),
                       "p95": self.tpot.percentile(95),
                       "n": self.tpot.n_seen},
        }


class RequestTrace:
    """One request's span record. ``events`` maps event name -> absolute
    clock time; chunk deliveries append to ``chunks`` as (t, n_tokens)
    pairs rather than one event each (a 512-token stream stays a compact
    record)."""

    __slots__ = ("uid", "tenant", "priority", "prompt_len",
                 "max_new_tokens", "slo_ttft_s", "deadline_s", "events",
                 "chunks", "status", "reject_reason", "error", "n_tokens",
                 "trace_id", "replica", "rerouted_from", "replayed_tokens",
                 "migrated_from", "resumed_tokens")

    def __init__(self, uid: int, *, tenant: str = "default",
                 priority: int = 1, prompt_len: int = 0,
                 max_new_tokens: int = 0,
                 slo_ttft_s: Optional[float] = None,
                 deadline_s: Optional[float] = None,
                 trace_id: Optional[str] = None,
                 replica: Optional[str] = None,
                 rerouted_from: Optional[str] = None,
                 replayed_tokens: int = 0,
                 migrated_from: Optional[str] = None,
                 resumed_tokens: int = 0):
        self.uid = uid
        self.tenant = tenant
        self.priority = priority
        self.prompt_len = prompt_len
        self.max_new_tokens = max_new_tokens
        self.slo_ttft_s = slo_ttft_s
        self.deadline_s = deadline_s
        # fleet journey identity: the distributed trace id this request
        # rides under, which replica recorded this segment, and — for a
        # segment re-homed after a crash — the replica it came from
        self.trace_id = trace_id
        self.replica = replica
        self.rerouted_from = rerouted_from
        # tokens the caller had ALREADY received when this segment
        # opened: >0 marks an in-flight replay after a crash (the
        # survivor re-prefilled prompt + this many emitted tokens)
        self.replayed_tokens = replayed_tokens
        # live KV-block migration hop: the replica this segment's KV
        # arrived from, and the decode cursor it resumed at (no
        # re-prefill — the blocks moved, unlike a crash replay)
        self.migrated_from = migrated_from
        self.resumed_tokens = resumed_tokens
        self.events: Dict[str, float] = {}
        self.chunks: List[List[float]] = []      # [t, n_tokens] pairs
        self.status: Optional[str] = None        # terminal status
        self.reject_reason: Optional[str] = None
        self.error: Optional[str] = None
        self.n_tokens = 0

    # ------------------------------------------------------- derived
    def _delta(self, a: str, b: str) -> Optional[float]:
        if a in self.events and b in self.events:
            return self.events[b] - self.events[a]
        return None

    @property
    def ttft_s(self) -> Optional[float]:
        return self._delta("submitted", "first_token")

    @property
    def queue_wait_s(self) -> Optional[float]:
        # a record without a ``lane`` mark (built by hand, or from a
        # driver that stamps none) reads as it always did
        return self._delta("submitted",
                           "lane" if "lane" in self.events else "prefill")

    @property
    def prefill_s(self) -> Optional[float]:
        return self._delta("lane", "first_token")

    @property
    def tpot_s(self) -> Optional[float]:
        dt = self._delta("first_token", "finish")
        if dt is None or self.n_tokens < 2:
            return None
        return dt / (self.n_tokens - 1)

    @property
    def slo_ttft_met(self) -> Optional[bool]:
        """Whether the measured TTFT met the request's SLO target; None
        when no target was set or no token was produced."""
        if self.slo_ttft_s is None or self.ttft_s is None:
            return None
        return self.ttft_s <= self.slo_ttft_s

    def to_dict(self) -> Dict[str, Any]:
        return {
            "uid": self.uid,
            "trace_id": self.trace_id,
            "replica": self.replica,
            "rerouted_from": self.rerouted_from,
            "replayed_tokens": self.replayed_tokens,
            "migrated_from": self.migrated_from,
            "resumed_tokens": self.resumed_tokens,
            "tenant": self.tenant,
            "priority": self.priority,
            "prompt_len": self.prompt_len,
            "max_new_tokens": self.max_new_tokens,
            "status": self.status,
            "reject_reason": self.reject_reason,
            "error": self.error,
            "n_tokens": self.n_tokens,
            "slo_ttft_s": self.slo_ttft_s,
            "deadline_s": self.deadline_s,
            "events": dict(self.events),
            "chunks": [list(c) for c in self.chunks],
            "ttft_s": self.ttft_s,
            "queue_wait_s": self.queue_wait_s,
            "prefill_s": self.prefill_s,
            "tpot_s": self.tpot_s,
            "slo_ttft_met": self.slo_ttft_met,
        }


class TraceLog:
    """Bounded per-request span store + latency histograms + terminal
    counters, with monitor fan-out emission.

    ``keep_last`` bounds the retained *finished* span records (the
    histograms and counters keep aggregating past it — a long-running
    server never grows unboundedly)."""

    #: histogram name -> RequestTrace property feeding it
    _HISTOGRAMS = ("ttft_s", "tpot_s", "queue_wait_s")

    def __init__(self, monitor=None, *, keep_last: int = 256,
                 reservoir_capacity: int = 1024,
                 clock: Callable[[], float] = time.monotonic):
        self.monitor = monitor
        self.clock = clock
        self.keep_last = int(keep_last)
        self._lock = locks.make_lock("frontend.tracelog")
        self._live: "OrderedDict[int, RequestTrace]" = OrderedDict()
        self._done: Deque[RequestTrace] = deque(maxlen=self.keep_last)
        self.histograms: Dict[str, Reservoir] = {
            name: Reservoir(reservoir_capacity)
            for name in self._HISTOGRAMS}
        self.counters: Dict[str, int] = {}
        # per-tenant goodput/latency aggregates, keyed by the tenant
        # label each trace carries (untagged records fold under
        # "default" — aggregation never silently drops them)
        self._reservoir_capacity = int(reservoir_capacity)
        self._tenants: Dict[str, _TenantStats] = {}
        self._emit_seq = 0
        # terminal-record fan-out (SLO engine): called OUTSIDE the lock
        self._listeners: List[Callable[[RequestTrace], None]] = []

    def add_listener(self,
                     fn: Callable[["RequestTrace"], None]) -> None:
        """Subscribe to every terminal record (``finish`` /
        ``record_rejected``). Listeners run on the finishing thread
        after the log's lock is released — they may read the trace but
        must not call back into this log."""
        self._listeners.append(fn)

    # ---------------------------------------------------------- recording
    def start(self, uid: int, **meta) -> RequestTrace:
        """Open a span (event ``submitted`` stamped now unless an
        explicit time is threaded via ``mark`` later)."""
        trace = RequestTrace(uid, **meta)
        with self._lock:
            self._live[uid] = trace
        return trace

    def mark(self, uid: int, event: str,
             t: Optional[float] = None) -> None:
        with self._lock:
            trace = self._live.get(uid)
            if trace is not None and event not in trace.events:
                trace.events[event] = self.clock() if t is None else t

    def chunk(self, uid: int, n_tokens: int,
              t: Optional[float] = None) -> None:
        """One delivery of ``n_tokens`` streamed tokens (one decode chunk
        retiring). The first delivery also stamps ``first_token``."""
        with self._lock:
            trace = self._live.get(uid)
            if trace is None or n_tokens <= 0:
                return
            now = self.clock() if t is None else t
            if "first_token" not in trace.events:
                trace.events["first_token"] = now
            trace.chunks.append([now, int(n_tokens)])
            trace.n_tokens += int(n_tokens)

    def finish(self, uid: int, status: str, *,
               reject_reason: Optional[str] = None,
               error: Optional[str] = None,
               t: Optional[float] = None) -> Optional[RequestTrace]:
        """Close a span with its terminal status; folds its latencies
        into the histograms and bumps the terminal counters. Terminal
        listeners (``add_listener``) fire after the lock is released."""
        with self._lock:
            trace = self._live.pop(uid, None)
            if trace is None:
                return None
            trace.events["finish"] = self.clock() if t is None else t
            trace.status = status
            trace.reject_reason = reject_reason
            trace.error = error
            self.counters[status] = self.counters.get(status, 0) + 1
            if reject_reason:
                key = f"rejected:{reject_reason}"
                self.counters[key] = self.counters.get(key, 0) + 1
            met = trace.slo_ttft_met
            if met is not None:
                key = "slo_ttft_met" if met else "slo_ttft_missed"
                self.counters[key] = self.counters.get(key, 0) + 1
            for name in self._HISTOGRAMS:
                v = getattr(trace, name)
                if v is not None:
                    self.histograms[name].add(v)
            tenant = getattr(trace, "tenant", None) or "default"
            stats = self._tenants.get(tenant)
            if stats is None:
                stats = self._tenants[tenant] = _TenantStats(
                    self._reservoir_capacity)
            stats.fold(trace)
            goodput = stats.goodput_fraction
            self._done.append(trace)
        # tenant-labelled series on /metrics: the embedded-label names
        # ride the same split_embedded_labels mechanism replica labels
        # use (and compose with them — name|tenant=a|replica=0)
        _telemetry_gauge(f"frontend/goodput_fraction|tenant={tenant}",
                         float(goodput))
        if trace.n_tokens:
            _telemetry_count(f"frontend/tenant_tokens|tenant={tenant}",
                             float(trace.n_tokens))
        for name, a, b in REQUEST_SPANS:
            if a in trace.events and b in trace.events:
                _telemetry_record_span(name, trace.events[a],
                                       trace.events[b], uid=trace.uid,
                                       trace_id=trace.trace_id)
        for fn in self._listeners:
            try:
                fn(trace)
            except Exception:  # noqa: BLE001 — observers never break us
                pass
        return trace

    def record_rejected(self, uid: int, reason: str, **meta) -> None:
        """Shorthand for a request rejected before it ever opened a live
        span (submit-side gate rejections)."""
        self.start(uid, **meta)
        self.mark(uid, "submitted")
        self.finish(uid, "rejected", reject_reason=reason)

    # ------------------------------------------------------------ reading
    def snapshot(self) -> Dict[str, float]:
        """Flat label -> value map (the monitor event payload)."""
        with self._lock:
            out: Dict[str, float] = {}
            for name, res in self.histograms.items():
                pct = res.percentiles((50, 95, 99))
                base = name[:-2] if name.endswith("_s") else name
                out[f"frontend/{base}_p50_s"] = pct[50]
                out[f"frontend/{base}_p95_s"] = pct[95]
                out[f"frontend/{base}_p99_s"] = pct[99]
            for status, n in self.counters.items():
                out[f"frontend/{status.replace(':', '_')}"] = float(n)
            return out

    def histogram_stats(self, qs=(0.5, 0.95, 0.99)) -> Dict[str, Any]:
        """Locked snapshot of the latency histograms for exposition:
        name -> {quantiles: {q: value}, count, sum}. Computed entirely
        under the lock so a concurrent ``finish`` never mutates a
        reservoir mid-serialization."""
        with self._lock:
            return {name: {"quantiles": {q: res.percentile(q * 100)
                                         for q in qs},
                           "count": res.n_seen,
                           "sum": res.total}
                    for name, res in self.histograms.items()}

    def counter_totals(self) -> Dict[str, int]:
        """Locked copy of the terminal-status counters."""
        with self._lock:
            return dict(self.counters)

    def tenants_report(self) -> Dict[str, Any]:
        """Per-tenant goodput accounting (the ``/tenants`` endpoint
        payload): terminal counts, tokens delivered within SLO vs
        total, and TTFT/TPOT reservoir percentiles per tenant."""
        # per-tenant stats keep mutating under finish(): rendering
        # INSIDE the lock is what makes each tenant row self-consistent
        # (lockcheck-audited; the row count is small and bounded)
        with self._lock:
            tenants = {t: s.to_dict()
                       for t, s in sorted(self._tenants.items())}
        return {
            "schema": TENANTS_SCHEMA,
            "n_tenants": len(tenants),
            "tenants": tenants,
        }

    def emit(self, sample: Optional[int] = None) -> Dict[str, float]:
        """Write the snapshot through the monitor fan-out (no-op without
        a monitor; still returns the snapshot)."""
        snap = self.snapshot()
        if self.monitor is not None:
            self._emit_seq = self._emit_seq + 1 if sample is None \
                else int(sample)
            self.monitor.write_events(
                [(label, value, self._emit_seq)
                 for label, value in snap.items()])
        return snap

    def to_json(self) -> Dict[str, Any]:
        # copy-out under the lock, render outside it: ``_done`` traces
        # are terminal (finish() moved them here and nothing mutates
        # them again), so their to_dict() — the bulk of this payload —
        # must not hold up every concurrent finish()/start(). Only the
        # still-mutating pieces (histograms, counters, _live) serialize
        # under the lock, where rendering IS the consistency guarantee.
        with self._lock:
            done = list(self._done)
            histograms = {
                name: {
                    "p50": res.percentile(50),
                    "p95": res.percentile(95),
                    "p99": res.percentile(99),
                    "n": res.n_seen,
                } for name, res in self.histograms.items()}
            counters = dict(self.counters)
            live = [t.to_dict() for t in self._live.values()]
        return {
            "histograms": histograms,
            "counters": counters,
            "requests": [t.to_dict() for t in done],
            "live": live,
        }

    def dump(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2)

    def export_chrome(self, path: Optional[str] = None,
                      runtime=None) -> Dict[str, Any]:
        """One Perfetto file for the whole story: this log's per-request
        lanes (with submit->finish flow arrows) merged with the
        process-wide telemetry runtime's engine/driver timeline — no
        second trace format to maintain. On Linux the two clocks
        (``time.monotonic`` here, ``time.perf_counter`` in telemetry)
        are both CLOCK_MONOTONIC, so the lanes line up without
        translation. Writes to ``path`` when given; always returns the
        trace object."""
        from ...telemetry import (chrome_trace, request_trace_events,
                                  write_chrome_trace)
        from ...telemetry import core as _tcore
        rt = runtime if runtime is not None else _tcore.get_runtime()
        extra = request_trace_events(self.to_json())
        if path is None:
            return chrome_trace(rt, extra_events=extra)
        return write_chrome_trace(path, rt, extra_events=extra)
