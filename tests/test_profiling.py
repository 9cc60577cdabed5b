"""Per-tenant goodput and anomaly detection.

Two layers under test, both host-side:

* per-tenant goodput accounting in ``TraceLog`` (untagged submits fold
  under ``"default"``) with the ``/tenants`` endpoint and
  ``tenant=``-labelled ``/metrics`` series scraped live;
* ``AnomalyDetector`` trip/debounce/re-arm mechanics, the one-shot
  postmortem per healthy→tripped flip, and the full injected-drift →
  ``/readyz`` degraded → recovery loop.
"""

import json
import urllib.error
import urllib.request

import numpy as np
import pytest

import deepspeed_tpu.telemetry as tel
from deepspeed_tpu.serving.frontend import HealthMonitor, TraceLog
from deepspeed_tpu.serving.scheduler import Request
from deepspeed_tpu.telemetry import (AnomalyDetector, AnomalySpec,
                                     FlightRecorder, default_specs)
from deepspeed_tpu.telemetry.exposition import (MetricsServer,
                                                parse_prometheus_text)

pytestmark = pytest.mark.observability


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


# ----------------------------------------------------- tenant goodput
class TestTenantAccounting:
    def test_untagged_request_defaults_to_default_tenant(self):
        req = Request(prompt=np.array([1, 2], np.int32))
        assert req.tenant == "default"
        # the frontend submit surface carries the same default
        import inspect
        from deepspeed_tpu.serving.frontend.frontend import ServingFrontend
        sig = inspect.signature(ServingFrontend.submit)
        assert sig.parameters["tenant"].default == "default"

    def test_untagged_trace_folds_under_default(self):
        clock = FakeClock(0.0)
        log = TraceLog(clock=clock)
        log.start(1)                      # no tenant meta at all
        log.mark(1, "submitted")
        log.chunk(1, 4)
        log.finish(1, "done")
        rep = log.tenants_report()
        assert rep["schema"] == "dstpu-tenants-v1"
        assert rep["n_tenants"] == 1
        assert rep["tenants"]["default"]["n_requests"] == 1
        assert rep["tenants"]["default"]["total_tokens"] == 4

    def test_goodput_counts_slo_misses_against_tenant(self):
        clock = FakeClock(0.0)
        log = TraceLog(clock=clock)
        # within SLO: 8 good tokens
        log.start(1, tenant="acme", slo_ttft_s=1.0)
        log.mark(1, "submitted", t=0.0)
        log.chunk(1, 8, t=0.5)
        log.finish(1, "done", t=1.0)
        # missed TTFT SLO: 8 tokens delivered but none count as goodput
        log.start(2, tenant="acme", slo_ttft_s=0.1)
        log.mark(2, "submitted", t=0.0)
        log.chunk(2, 8, t=0.5)
        log.finish(2, "done", t=1.0)
        # no SLO set: delivered tokens are good by definition
        log.start(3, tenant="acme")
        log.mark(3, "submitted", t=0.0)
        log.chunk(3, 4, t=0.5)
        log.finish(3, "done", t=1.0)
        t = log.tenants_report()["tenants"]["acme"]
        assert t["total_tokens"] == 20
        assert t["goodput_tokens"] == 12
        assert t["goodput_fraction"] == pytest.approx(12 / 20)
        assert t["slo"] == {"scored": 2, "met": 1}
        assert t["ttft_s"]["n"] == 3 and t["tpot_s"]["n"] == 3

    def test_tenants_endpoint_and_labelled_metrics_live_scrape(self):
        rt = tel.get_runtime()
        was_enabled = rt.enabled
        tel.enable()
        try:
            clock = FakeClock(0.0)
            log = TraceLog(clock=clock)
            server = MetricsServer(runtime=rt, tracelog=log)
            try:
                # the tenant-token counter is process-global: earlier
                # tests may have folded tokens into it, so assert the
                # DELTA this test produces, not an absolute total
                with urllib.request.urlopen(f"{server.url}/metrics",
                                            timeout=5) as resp:
                    before = parse_prometheus_text(
                        resp.read().decode())["samples"]
                base = dict((lab["tenant"], v) for lab, v in
                            before.get("dstpu_frontend_tenant_tokens_total",
                                       []))
                log.start(1, tenant="acme")
                log.mark(1, "submitted", t=0.0)
                log.chunk(1, 6, t=0.5)
                log.finish(1, "done", t=1.0)
                log.start(2)                       # untagged
                log.mark(2, "submitted", t=0.0)
                log.chunk(2, 2, t=0.5)
                log.finish(2, "done", t=1.0)
                with urllib.request.urlopen(f"{server.url}/tenants",
                                            timeout=5) as resp:
                    assert resp.status == 200
                    rep = json.load(resp)
                assert rep["schema"] == "dstpu-tenants-v1"
                assert set(rep["tenants"]) == {"acme", "default"}
                assert rep["tenants"]["acme"]["goodput_fraction"] == 1.0
                with urllib.request.urlopen(f"{server.url}/metrics",
                                            timeout=5) as resp:
                    samples = parse_prometheus_text(
                        resp.read().decode())["samples"]
                good = samples["dstpu_frontend_goodput_fraction"]
                tenants = {lab["tenant"] for lab, _ in good}
                assert {"acme", "default"} <= tenants
                toks = dict((lab["tenant"], v) for lab, v in
                            samples["dstpu_frontend_tenant_tokens_total"])
                assert toks["acme"] - base.get("acme", 0.0) == 6.0
                assert toks["default"] - base.get("default", 0.0) == 2.0
            finally:
                server.stop()
        finally:
            if not was_enabled:
                tel.disable()

    def test_tenants_endpoint_404_when_not_wired(self):
        server = MetricsServer()
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(f"{server.url}/tenants", timeout=5)
            assert exc.value.code == 404
        finally:
            server.stop()


# ------------------------------------------------------------ anomaly
def _spec(**over):
    kw = dict(metric="tpot_s", direction="higher_is_bad",
              z_threshold=4.0, min_samples=4, trip_consecutive=3,
              rearm_consecutive=4)
    kw.update(over)
    return AnomalySpec(**kw)


def _baseline(det, n=10, base=0.010):
    for i in range(n):
        det.observe("tpot_s", base + (0.0002 if i % 2 else -0.0002))


class TestAnomalyDetector:
    def test_default_specs_cover_the_vitals(self):
        names = {s.metric for s in default_specs()}
        assert names == {"tpot_s", "spec_acceptance", "prefix_hit_rate"}
        with pytest.raises(ValueError):
            AnomalySpec("x", direction="sideways_is_bad")

    def test_trip_needs_consecutive_excursions(self):
        det = AnomalyDetector([_spec()], gauge_fn=lambda *_: None)
        _baseline(det)
        assert not det.observe("tpot_s", 0.05)
        assert not det.observe("tpot_s", 0.05)
        # an in-band sample resets the debounce counter
        assert not det.observe("tpot_s", 0.010)
        assert not det.observe("tpot_s", 0.05)
        assert not det.observe("tpot_s", 0.05)
        assert det.observe("tpot_s", 0.05)       # third consecutive
        assert det.tripped and det.trip_reasons() == ["tpot_s"]
        assert det.n_trips == 1

    def test_min_samples_gates_scoring(self):
        det = AnomalyDetector([_spec(min_samples=8)],
                              gauge_fn=lambda *_: None)
        for _ in range(6):
            assert not det.observe("tpot_s", 5.0)   # wild but unscored
        assert not det.tripped

    def test_unknown_metric_and_none_are_ignored(self):
        det = AnomalyDetector([_spec()], gauge_fn=lambda *_: None)
        det.observe("nope", 1e9)
        det.observe("tpot_s", None)
        assert det.n_observed == 0 and not det.tripped

    def test_baseline_frozen_while_tripped_and_rearms(self):
        det = AnomalyDetector([_spec()], gauge_fn=lambda *_: None)
        _baseline(det)
        mean_before = det.report()["metrics"]["tpot_s"]["mean"]
        for _ in range(10):
            det.observe("tpot_s", 0.05)
        assert det.tripped
        # sustained drift must not launder itself into the mean
        assert det.report()["metrics"]["tpot_s"]["mean"] == \
            pytest.approx(mean_before)
        for _ in range(4):
            det.observe("tpot_s", 0.010)
        assert not det.tripped and det.trip_reasons() == []
        assert det.n_trips == 1

    def test_postmortem_dumped_once_per_flip(self, tmp_path):
        fr = FlightRecorder(label="anomtest", out_dir=str(tmp_path))
        det = AnomalyDetector([_spec()], gauge_fn=lambda *_: None,
                              flight=fr)
        _baseline(det)
        for _ in range(8):                  # trip, then keep drifting
            det.observe("tpot_s", 0.05)
        assert det.tripped and fr.n_dumps == 1
        post = json.loads(open(fr.last_postmortem_path).read())
        assert post["reason"] == "anomaly"
        assert post["extra"]["anomaly"]["metric"] == "tpot_s"
        assert post["extra"]["anomaly"]["reasons"] == ["tpot_s"]
        # recovery re-arms; a second drift is a NEW flip -> second dump
        for _ in range(4):
            det.observe("tpot_s", 0.010)
        assert not det.tripped
        for _ in range(3):
            det.observe("tpot_s", 0.05)
        assert det.tripped
        assert det.n_trips == 2 and fr.n_dumps == 2

    def test_observe_trace_filters_status(self):
        det = AnomalyDetector([_spec()], gauge_fn=lambda *_: None)

        class T:
            status = "rejected"
            tpot_s = 99.0
        det.observe_trace(T())
        assert det.n_observed == 0
        T.status = "done"
        det.observe_trace(T())
        assert det.n_observed == 1

    def test_report_shape(self):
        det = AnomalyDetector([_spec()], gauge_fn=lambda *_: None)
        _baseline(det, n=6)
        rep = det.report()
        assert rep["schema"] == "dstpu-anomaly-v1"
        assert rep["tripped"] is False and rep["n_observed"] == 6
        m = rep["metrics"]["tpot_s"]
        assert m["direction"] == "higher_is_bad" and m["n"] == 6


class TestAnomalyReadiness:
    def test_injected_drift_degrades_readyz_and_dumps_once(self,
                                                           tmp_path):
        clock = FakeClock(0.0)
        log = TraceLog(clock=clock)
        fr = FlightRecorder(label="readyz", out_dir=str(tmp_path))
        det = AnomalyDetector([_spec()], gauge_fn=lambda *_: None,
                              flight=fr, clock=clock).attach(log)
        monitor = HealthMonitor(anomaly=det)
        server = MetricsServer(health=monitor)

        uid = [0]

        def finish_one(tpot):
            uid[0] += 1
            u = uid[0]
            log.start(u, tenant="acme")
            log.mark(u, "submitted", t=0.0)
            log.chunk(u, 1, t=0.1)              # first_token at 0.1
            log.chunk(u, 4, t=0.2)
            # finish so that tpot = (finish - first_token) / (n - 1)
            log.finish(u, "done", t=0.1 + 4 * tpot)

        try:
            for i in range(10):
                finish_one(0.010 + (0.0002 if i % 2 else -0.0002))
            with urllib.request.urlopen(f"{server.url}/readyz",
                                        timeout=5) as resp:
                assert resp.status == 200
            for _ in range(5):                  # inject sustained drift
                finish_one(0.05)
            assert det.tripped
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(f"{server.url}/readyz", timeout=5)
            assert exc.value.code == 503
            body = json.loads(exc.value.read())
            assert "anomaly" in body["reasons"]
            assert body["details"]["anomaly"] == ["tpot_s"]
            assert fr.n_dumps == 1              # once per flip, debounced
            for _ in range(4):                  # recovery re-arms
                finish_one(0.010)
            assert not det.tripped
            with urllib.request.urlopen(f"{server.url}/readyz",
                                        timeout=5) as resp:
                assert resp.status == 200
            assert fr.n_dumps == 1
        finally:
            server.stop()
