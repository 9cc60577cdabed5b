"""Online drift detection over serving vitals (EWMA + z-score).

``bin/benchdiff`` catches regressions offline, between runs; nothing
watches *live* traffic for the slow drifts that precede an incident —
TPOT creeping up, speculative acceptance sagging, the prefix cache
going cold.
:class:`AnomalyDetector` closes that gap with the classic streaming
recipe:

* per metric, an exponentially-weighted mean and variance form the
  baseline; each new sample is scored ``z = (x - mean) / std``
  *before* being folded in;
* a sample is an *excursion* when its direction-aware z exceeds
  ``z_threshold``; ``trip_consecutive`` consecutive excursions trip
  the metric (debounce — one noisy sample never pages);
* while a metric is excursing or tripped the baseline is frozen, so a
  sustained drift cannot launder itself into the mean and recovery is
  judged against the *pre-drift* baseline;
* ``rearm_consecutive`` consecutive in-band samples re-arm it.

The detector-level healthy→tripped transition fires a one-shot
``FlightRecorder`` postmortem (trigger kind ``anomaly``) — exactly
once per flip, mirroring the watchdog's unhealthy-flip debounce — and
``HealthMonitor`` can opt in so a trip degrades ``/readyz`` until the
metric re-arms. Feed it from a ``TraceLog`` (:meth:`attach` folds TPOT
per finished request) and poll :meth:`observe` for engine-side vitals
(spec acceptance from ``ServingMetrics.spec_acceptance_rate``,
prefix-cache hit rate).

Stdlib-only; safe to import without JAX.
"""

from __future__ import annotations

import math
import threading
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Iterable, List, Optional

from ..analysis import locks
from .core import gauge as _telemetry_gauge

SCHEMA = "dstpu-anomaly-v1"

#: directions a metric can drift in before it is anomalous
DIRECTIONS = ("higher_is_bad", "lower_is_bad")


@dataclass
class AnomalySpec:
    """One watched metric. ``min_samples`` gates scoring until the
    baseline has enough evidence; the variance floor
    ``rel_std_floor * |mean|`` keeps a perfectly quiet baseline from
    producing infinite z-scores."""
    metric: str
    direction: str = "higher_is_bad"
    z_threshold: float = 4.0
    min_samples: int = 16
    trip_consecutive: int = 3
    rearm_consecutive: int = 8
    rel_std_floor: float = 1e-3

    def __post_init__(self):
        if self.direction not in DIRECTIONS:
            raise ValueError(
                f"unknown direction: {self.direction!r}")
        if self.trip_consecutive < 1 or self.rearm_consecutive < 1:
            raise ValueError("trip/rearm_consecutive must be >= 1")


def default_specs() -> List[AnomalySpec]:
    """The serving tier's stock watchlist: the three vitals whose drift
    most reliably precedes an SLO breach."""
    return [
        AnomalySpec("tpot_s", direction="higher_is_bad"),
        AnomalySpec("spec_acceptance", direction="lower_is_bad"),
        AnomalySpec("prefix_hit_rate", direction="lower_is_bad"),
    ]


class _MetricState:
    __slots__ = ("mean", "var", "n", "consec_bad", "consec_good",
                 "tripped", "last_z", "last_value", "n_excursions")

    def __init__(self):
        self.mean = 0.0
        self.var = 0.0
        self.n = 0
        self.consec_bad = 0
        self.consec_good = 0
        self.tripped = False
        self.last_z = 0.0
        self.last_value: Optional[float] = None
        self.n_excursions = 0


class AnomalyDetector:
    """Streaming drift detector over a fixed watchlist of metrics.

    ``alpha`` is the EWMA smoothing factor (small = long memory).
    ``flight`` (a ``FlightRecorder``) receives a one-shot postmortem
    per healthy→tripped flip; assign it any time."""

    def __init__(self, specs: Optional[Iterable[AnomalySpec]] = None, *,
                 alpha: float = 0.05,
                 clock: Callable[[], float] = time.monotonic,
                 gauge_fn: Optional[Callable[[str, float], None]] = None,
                 flight: Any = None,
                 export_gauges: bool = True):
        specs = list(specs) if specs is not None else default_specs()
        self.specs: Dict[str, AnomalySpec] = {s.metric: s for s in specs}
        if not self.specs:
            raise ValueError("need at least one AnomalySpec")
        self.alpha = float(alpha)
        self.clock = clock
        self._gauge = gauge_fn if gauge_fn is not None \
            else _telemetry_gauge
        self.flight = flight
        self.export_gauges = export_gauges
        self._states: Dict[str, _MetricState] = {
            m: _MetricState() for m in self.specs}
        self._lock = locks.make_lock("telemetry.anomaly")
        self._tripped = False
        self.n_trips = 0
        self.n_observed = 0
        self.last_trip_t: Optional[float] = None

    # ---------------------------------------------------------- ingestion
    def observe(self, metric: str, value: Optional[float],
                t: Optional[float] = None) -> bool:
        """Fold one sample; returns the detector-level tripped state.
        Unknown metrics and ``None`` values are ignored."""
        if value is None:
            return self.tripped
        value = float(value)
        flipped = False
        trip_payload: Dict[str, Any] = {}
        with self._lock:
            # specs can grow concurrently via ensure_spec — resolve
            # under the same lock (self._tripped: lock already held)
            spec = self.specs.get(metric)
            if spec is None:
                return self._tripped
            st = self._states[metric]
            self.n_observed += 1
            scored = st.n >= spec.min_samples
            if scored:
                std = math.sqrt(max(st.var, 0.0))
                floor = max(abs(st.mean) * spec.rel_std_floor, 1e-12)
                std = max(std, floor)
                z = (value - st.mean) / std
            else:
                z = 0.0
            st.last_z = z
            st.last_value = value
            if spec.direction == "higher_is_bad":
                excursion = scored and z > spec.z_threshold
            else:
                excursion = scored and z < -spec.z_threshold
            if excursion:
                st.consec_bad += 1
                st.consec_good = 0
                st.n_excursions += 1
            else:
                st.consec_good += 1
                st.consec_bad = 0
            if not st.tripped \
                    and st.consec_bad >= spec.trip_consecutive:
                st.tripped = True
            elif st.tripped \
                    and st.consec_good >= spec.rearm_consecutive:
                st.tripped = False
            # freeze the baseline during excursions and while tripped
            # so drift cannot launder itself into the mean
            if not excursion and not st.tripped:
                if st.n == 0:
                    st.mean = value
                    st.var = 0.0
                else:
                    d = value - st.mean
                    st.mean += self.alpha * d
                    st.var = (1.0 - self.alpha) \
                        * (st.var + self.alpha * d * d)
                st.n += 1
            now_tripped = any(s.tripped
                              for s in self._states.values())
            flipped = now_tripped and not self._tripped
            self._tripped = now_tripped
            if flipped:
                self.n_trips += 1
                self.last_trip_t = self.clock()
                trip_payload = {
                    "metric": metric, "value": value, "z": z,
                    "mean": st.mean,
                    "reasons": [m for m, s in self._states.items()
                                if s.tripped],
                }
            tripped = self._tripped
        if self.export_gauges:
            self._gauge(f"anomaly/{metric}/z", float(z))
            self._gauge("anomaly/tripped", 1.0 if tripped else 0.0)
        if flipped and self.flight is not None:
            # one-shot postmortem per healthy->tripped flip, same
            # debounce contract as the watchdog unhealthy flip; never
            # let recorder errors poison the hot path
            try:
                self.flight.record("anomaly", **trip_payload)
                self.flight.dump(reason="anomaly",
                                 extra={"anomaly": trip_payload})
            except Exception:
                pass
        return tripped

    def ensure_spec(self, spec: AnomalySpec) -> bool:
        """Register one more watched metric after construction (no-op
        when the metric is already watched — existing baselines are
        never reset). The fleet plane uses this to grow per-pod specs
        as pods join the hierarchy. Returns True when the spec was
        newly added."""
        with self._lock:
            if spec.metric in self.specs:
                return False
            self.specs[spec.metric] = spec
            self._states[spec.metric] = _MetricState()
            return True

    def observe_trace(self, trace: Any) -> None:
        """TraceLog finish-listener: fold TPOT from each finished
        ``done`` request."""
        if getattr(trace, "status", None) != "done":
            return
        self.observe("tpot_s", getattr(trace, "tpot_s", None))

    def attach(self, tracelog: Any) -> "AnomalyDetector":
        """Subscribe to a ``TraceLog``'s finish fan-out; returns self
        so ``AnomalyDetector().attach(log)`` chains."""
        tracelog.add_listener(self.observe_trace)
        return self

    # --------------------------------------------------------- inspection
    @property
    def tripped(self) -> bool:
        with self._lock:
            return self._tripped

    def trip_reasons(self) -> List[str]:
        """Metrics currently tripped (empty when healthy)."""
        with self._lock:
            return [m for m, s in self._states.items() if s.tripped]

    def report(self) -> Dict[str, Any]:
        with self._lock:
            metrics = {}
            for m, spec in self.specs.items():
                st = self._states[m]
                metrics[m] = {
                    "direction": spec.direction,
                    "z_threshold": spec.z_threshold,
                    "n": st.n,
                    "mean": st.mean,
                    "std": math.sqrt(max(st.var, 0.0)),
                    "last_value": st.last_value,
                    "last_z": st.last_z,
                    "tripped": st.tripped,
                    "consec_bad": st.consec_bad,
                    "n_excursions": st.n_excursions,
                }
            return {
                "schema": SCHEMA,
                "tripped": self._tripped,
                "reasons": [m for m, s in self._states.items()
                            if s.tripped],
                "n_trips": self.n_trips,
                "n_observed": self.n_observed,
                "last_trip_t": self.last_trip_t,
                "metrics": metrics,
            }

    def clear(self) -> None:
        with self._lock:
            self._states = {m: _MetricState() for m in self.specs}
            self._tripped = False
            self.n_trips = 0
            self.n_observed = 0
            self.last_trip_t = None
