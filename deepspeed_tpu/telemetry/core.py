"""Telemetry runtime: spans, instants, counters in a bounded ring.

Design constraints, in order:

1. **Disabled must be ~free.** Every hot path (train step, decode chunk,
   frontend driver) is instrumented permanently; the disabled cost is one
   module-level function call + one attribute check returning a shared
   no-op context manager — no allocation, no clock read, no lock. The
   self-overhead gate in tests/test_telemetry.py measures this against a
   dispatch-bound loop.
2. **Enabled must be lock-light.** The timing window (enter -> exit)
   never holds a lock; one short critical section per COMPLETED event
   covers the ring append + aggregate fold (~a few hundred ns,
   uncontended). Nothing is ever flushed from the emitting thread.
3. **Bounded.** The ring is a ``deque(maxlen=capacity)`` — a long
   serving run evicts the oldest timeline events but the aggregates
   (count/total/Reservoir per span name, counter totals) keep folding,
   so summaries stay correct past eviction.

Event wire format (ring entries are plain tuples, cheap to create and
GIL-friendly to copy):

    ("X", name, ts_us, dur_us, tid, attrs)    completed span
    ("i", name, ts_us, tid, attrs)            instant event
    ("C", name, ts_us, value)                 counter/gauge sample

``ts_us`` is ``time.perf_counter()`` in microseconds — on Linux the same
CLOCK_MONOTONIC timebase as ``time.monotonic()``, which is what lets the
frontend's ``TraceLog`` request events merge into the same Perfetto file
(export.py) without clock surgery.

The ``sync=`` span argument carries the honesty contract of
``utils/timer.py``: JAX dispatch returns before the device finishes, so
a span closing right after a jitted call measures dispatch only;
``sync=result`` blocks on the result first and the span covers real
work.

**One clock with the device.** A live span also enters a
``jax.profiler.TraceAnnotation`` of the same name and attributes, so
while a ``jax.profiler`` trace runs every span lies on its thread's line
of the trace's host plane, on the clock the device's ops are on — an
idle gap on the device can be read against what the host was doing. No
switch of its own: the bridge is on exactly when telemetry is, and the
annotation costs a flag check unless a trace is being taken.

JAX is imported lazily and ONLY by a live span — this module stays
importable by the stdlib-only ``bin/tputrace``.
"""

from __future__ import annotations

import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Tuple

from ..analysis import locks

_US = 1e6

# ---------------------------------------------------------------------------
# Replica labeling. A fleet runs N serving replicas in ONE process against
# one default runtime; without a discriminator their identically-named
# gauges/counters would fold together (and the last gauge write would win).
# The label is thread-local — each replica's driver thread tags everything
# it records — and rides INSIDE the metric name as a ``|replica=<id>``
# suffix, so the runtime's flat string-keyed dicts need no schema change.
# The Prometheus exposition layer (exposition.py) splits the suffix back
# out into a real ``{replica="<id>"}`` label before sanitizing the name.
# ---------------------------------------------------------------------------

_replica_ctx = threading.local()


class _ReplicaLabel:
    __slots__ = ("label", "_prev")

    def __init__(self, replica):
        self.label = None if replica is None else str(replica)

    def __enter__(self):
        self._prev = getattr(_replica_ctx, "label", None)
        _replica_ctx.label = self.label
        return self

    def __exit__(self, *exc):
        _replica_ctx.label = self._prev
        return False


def replica_label(replica) -> _ReplicaLabel:
    """Context manager tagging every metric recorded on THIS thread with
    ``|replica=<id>`` while active (nestable; ``None`` clears). Cheap
    enough to wrap a whole driver loop iteration."""
    return _ReplicaLabel(replica)


def current_replica() -> Optional[str]:
    """The replica label active on the calling thread, or None."""
    return getattr(_replica_ctx, "label", None)


def _labeled(name: str) -> str:
    lbl = getattr(_replica_ctx, "label", None)
    return name if lbl is None else f"{name}|replica={lbl}"


class _NoopSpan:
    """Shared do-nothing context manager returned while disabled."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


NOOP_SPAN = _NoopSpan()


class _Span:
    """One live span; created by :meth:`TelemetryRuntime.span` only when
    the runtime is enabled. The clock starts in ``__enter__`` and stops
    in ``__exit__`` (after the optional ``sync`` block), so attribute
    setup and lock acquisition never pollute the measured window. The
    profiler's annotation of the same region is entered before the clock
    starts and left after it stops. ``t0`` and ``t1`` are the two clock
    readings, for a caller that chains what it records to a span's ends
    (``ServingEngine``'s device timeline)."""

    __slots__ = ("_rt", "name", "attrs", "_sync", "t0", "t1", "_annotation")

    def __init__(self, rt: "TelemetryRuntime", name: str, sync,
                 attrs: Optional[Dict[str, Any]]):
        self._rt = rt
        self.name = name
        self.attrs = attrs
        self._sync = sync
        self.t0 = self.t1 = 0.0
        self._annotation = None

    def __enter__(self):
        import jax
        self._annotation = jax.profiler.TraceAnnotation(
            self.name, **(self.attrs or {}))
        self._annotation.__enter__()
        self.t0 = self._rt.clock()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._sync is not None:
            import jax
            jax.block_until_ready(self._sync)
        self.t1 = self._rt.clock()
        self._annotation.__exit__(exc_type, exc, tb)
        self._rt._record_span(self.name, self.t0, self.t1, self.attrs)
        return False

    def drop(self) -> None:
        """Leave an entered span without recording it (what it was timing
        turned out not to be that: ``ServingEngine``'s starved span when
        the loop ran out of requests). The profiler cannot take an
        annotation back: in a trace it ends here."""
        self._annotation.__exit__(None, None, None)


class _SpanAgg:
    """Cumulative per-span-name statistics (survive ring eviction)."""

    __slots__ = ("count", "total_s", "reservoir")

    def __init__(self, reservoir):
        self.count = 0
        self.total_s = 0.0
        self.reservoir = reservoir


def _make_reservoir(capacity: int = 1024):
    # the serving Reservoir (Vitter's algorithm R) — imported lazily so
    # this module never drags in the jax-heavy serving package at import
    # time (bin/tputrace must stay stdlib-only)
    from ..serving.metrics import Reservoir
    return Reservoir(capacity)


class TelemetryRuntime:
    """Process-wide telemetry recorder. All methods are safe from any
    thread; see the module docstring for the locking discipline."""

    def __init__(self, capacity: int = 65536, *,
                 enabled: bool = False,
                 clock: Callable[[], float] = time.perf_counter,
                 reservoir_capacity: int = 1024):
        self.clock = clock
        self.capacity = int(capacity)
        self.enabled = bool(enabled)
        self._reservoir_capacity = int(reservoir_capacity)
        self._lock = locks.make_lock("telemetry.runtime")
        self._events: deque = deque(maxlen=self.capacity)
        self._span_aggs: Dict[str, _SpanAgg] = {}
        self._counters: Dict[str, float] = {}
        self._gauges: Dict[str, float] = {}
        self._instants: Dict[str, int] = {}
        self._thread_names: Dict[int, str] = {}
        self.n_dropped = 0          # events evicted from the ring

    # ------------------------------------------------------------ control
    def enable(self) -> "TelemetryRuntime":
        self.enabled = True
        return self

    def disable(self) -> "TelemetryRuntime":
        self.enabled = False
        return self

    def clear(self) -> None:
        with self._lock:
            self._events.clear()
            self._span_aggs.clear()
            self._counters.clear()
            self._gauges.clear()
            self._instants.clear()
            self.n_dropped = 0

    def __enter__(self) -> "TelemetryRuntime":
        self.enable()
        return self

    def __exit__(self, *exc) -> None:
        self.disable()

    # ---------------------------------------------------------- recording
    def span(self, name: str, *, sync=None, **attrs):
        """Context manager timing one named region. ``sync=x`` blocks on
        ``x`` (``jax.block_until_ready``) before the clock stops — the
        honest wall-clock for device work. No-op while disabled."""
        if not self.enabled:
            return NOOP_SPAN
        return _Span(self, name, sync, attrs or None)

    def record_span(self, name: str, t0: float, t1: float,
                    **attrs) -> None:
        """A span whose ends were stamped elsewhere, in seconds on the
        runtime clock's timebase (a request's phases start on the
        caller's thread and end on the driver's: no context manager, and
        no profiler annotation, can bracket them). No-op while
        disabled."""
        if self.enabled:
            self._record_span(name, t0, t1, attrs or None)

    def instant(self, name: str, **attrs) -> None:
        """A zero-duration timeline marker (Perfetto instant event)."""
        if not self.enabled:
            return
        name = _labeled(name)
        ts = self.clock() * _US
        tid = threading.get_ident()
        with self._lock:
            self._note_thread(tid)
            self._append(("i", name, ts, tid, attrs or None))
            self._instants[name] = self._instants.get(name, 0) + 1

    def count(self, name: str, delta: float = 1.0) -> None:
        """Monotonic counter: accumulates ``delta`` and records the new
        cumulative value as a counter-track sample."""
        if not self.enabled:
            return
        name = _labeled(name)
        ts = self.clock() * _US
        with self._lock:
            val = self._counters.get(name, 0.0) + float(delta)
            self._counters[name] = val
            self._append(("C", name, ts, val))

    def gauge(self, name: str, value: float) -> None:
        """Point-in-time level (queue depth, occupancy): records the
        value as-is on the counter track."""
        if not self.enabled:
            return
        name = _labeled(name)
        ts = self.clock() * _US
        with self._lock:
            self._gauges[name] = float(value)
            self._append(("C", name, ts, float(value)))

    # --------------------------------------------------- internal helpers
    def _record_span(self, name: str, t0: float, t1: float,
                     attrs: Optional[Dict[str, Any]]) -> None:
        name = _labeled(name)
        tid = threading.get_ident()
        dur_s = t1 - t0
        with self._lock:
            self._note_thread(tid)
            self._append(("X", name, t0 * _US, dur_s * _US, tid, attrs))
            agg = self._span_aggs.get(name)
            if agg is None:
                agg = self._span_aggs[name] = _SpanAgg(
                    _make_reservoir(self._reservoir_capacity))
            agg.count += 1
            agg.total_s += dur_s
            agg.reservoir.add(dur_s)

    def _append(self, event: Tuple) -> None:
        if len(self._events) == self.capacity:
            self.n_dropped += 1
        self._events.append(event)

    def _note_thread(self, tid: int) -> None:
        if tid not in self._thread_names:
            self._thread_names[tid] = threading.current_thread().name

    # ------------------------------------------------------------ reading
    def events(self) -> List[Tuple]:
        """Snapshot of the ring (oldest first)."""
        with self._lock:
            return list(self._events)

    def thread_names(self) -> Dict[int, str]:
        with self._lock:
            return dict(self._thread_names)

    def span_stats(self) -> Dict[str, Dict[str, float]]:
        """Cumulative per-span statistics: count, total/mean seconds and
        reservoir p50/p95/p99 — correct even past ring eviction."""
        with self._lock:
            out = {}
            for name, agg in self._span_aggs.items():
                pct = agg.reservoir.percentiles((50, 95, 99))
                out[name] = {
                    "count": agg.count,
                    "total_s": agg.total_s,
                    "mean_s": agg.total_s / agg.count if agg.count else 0.0,
                    "p50_s": pct[50], "p95_s": pct[95], "p99_s": pct[99],
                }
            return out

    def counter_totals(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._counters)

    def gauge_values(self) -> Dict[str, float]:
        with self._lock:
            return dict(self._gauges)

    def instant_counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._instants)


# ---------------------------------------------------------------------------
# Process-wide default runtime + module-level helpers. Instrumentation
# sites call these directly (no handle threading); disabled cost is the
# function call + one attribute check.
# ---------------------------------------------------------------------------

_default = TelemetryRuntime()


def get_runtime() -> TelemetryRuntime:
    return _default


def configure(capacity: Optional[int] = None, *,
              enabled: Optional[bool] = None) -> TelemetryRuntime:
    """Reconfigure the default runtime (resizing clears the ring)."""
    rt = _default
    if capacity is not None and int(capacity) != rt.capacity:
        with rt._lock:
            rt.capacity = int(capacity)
            rt._events = deque(rt._events, maxlen=rt.capacity)
    if enabled is not None:
        rt.enabled = bool(enabled)
    return rt


def enable() -> TelemetryRuntime:
    return _default.enable()


def disable() -> TelemetryRuntime:
    return _default.disable()


def span(name: str, *, sync=None, **attrs):
    if not _default.enabled:
        return NOOP_SPAN
    return _Span(_default, name, sync, attrs or None)


def record_span(name: str, t0: float, t1: float, **attrs) -> None:
    if _default.enabled:
        _default._record_span(name, t0, t1, attrs or None)


def instant(name: str, **attrs) -> None:
    if _default.enabled:
        _default.instant(name, **attrs)


def count(name: str, delta: float = 1.0) -> None:
    if _default.enabled:
        _default.count(name, delta)


def gauge(name: str, value: float) -> None:
    if _default.enabled:
        _default.gauge(name, value)
