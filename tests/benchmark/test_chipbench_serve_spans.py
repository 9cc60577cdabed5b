"""The per-layer metrics that read the serve loop's telemetry spans: each
reader on a hand-built ``spans`` dict, their BENCHMARK.json entries, and the
``serve-batch`` rehearsal, traced, printing all four."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import spec  # noqa: E402
from chipbench.harness import SUFFIX_REHEARSAL  # noqa: E402

BENCH = spec.load_benchmark()
SEED = 3_000_000_029


def _span(count, total_s):
    return {"count": count, "total_s": total_s}


# a 40 s window of 80 chunks in which 50 requests finished
SPANS = {
    "serve/starved_after_prefill": _span(50, 1.5),
    "serve/starved_after_chunk": _span(5, 0.5),
    "frontend/drive": _span(85, 39.0),
    "serve/chunk_host_wait": _span(80, 30.0),
    "serve/prefill_wait": _span(50, 7.0),
    "serve/chunk_retire": _span(80, 0.2),
    "request/queued": _span(50, 200.0),
    "request/prefill": _span(50, 25.0),
    "request/decode": _span(50, 400.0),
}
COUNTERS = {"window_s": 40.0}
WANT = {
    "device_starved.batch": 100.0 * (1.5 + 0.5) / 40.0,
    "host_ms_per_chunk.batch": 1e3 * (39.0 - 30.0 - 7.0) / 80,
    "queue_wait_ms.mean.batch": 1e3 * 200.0 / 50,
    "lane_to_first_token_ms.mean.batch": 1e3 * 25.0 / 50,
}


def _reader(name):
    return spec.load_module(spec.find_reader(BENCH, name))


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_hand_built_spans(name):
    got = _reader(name).read(None, SPANS, COUNTERS, {})
    assert got == pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_in_a_program_without_the_spans(name):
    """As at the parent commit, or with telemetry off: nothing to read is
    None, never an error, and the line leaves the metric out."""
    read = _reader(name).read
    assert read(None, {}, COUNTERS, {}) is None
    # the parent's spans alone (what PR 23's program recorded)
    old = {k: SPANS[k] for k in ("serve/chunk_host_wait",
                                 "serve/chunk_retire")}
    assert read(None, old, COUNTERS, {}) is None


def test_starved_share_reads_either_span_alone():
    read = _reader("device_starved.batch").read
    one = {"serve/starved_after_prefill": _span(3, 2.0)}
    assert read(None, one, COUNTERS, {}) == pytest.approx(5.0)
    assert read(None, one, {}, {}) is None      # no window to divide by


def test_the_four_entries_are_appended_for_serve_batch_alone():
    spec.validate(BENCH)
    tail = BENCH["per_layer"][-4:]
    assert [m["name"] for m in tail] == [
        "device_starved.batch", "host_ms_per_chunk.batch",
        "queue_wait_ms.mean.batch", "lane_to_first_token_ms.mean.batch"]
    layers = {m["layer"] for m in BENCH["per_layer"][:-4]}
    for m in tail:
        assert m["workloads"] == ["serve-batch"]
        assert m["source"] == "program_span" and m["better"] == "lower"
        assert m["moves"] == "serve_tokens_per_s"
    assert tail[0]["layer"] in layers           # a layer already named
    assert {m["name"] for m in spec.metrics_of_cell(
        BENCH, "serve-batch", "per_layer")} >= set(WANT)


def test_traced_rehearsal_prints_the_four_span_metrics():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", "serve-batch", "--seed", str(SEED), "--seconds", "2",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:] + r.stdout[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    metrics = {n[:-len(SUFFIX_REHEARSAL)]: m["value"]
               for n, m in last["metrics"].items()
               if n.endswith(SUFFIX_REHEARSAL)}
    assert len(metrics) == len(last["metrics"])     # all renamed
    assert set(WANT) <= set(metrics)
    assert 0.0 < metrics["device_starved.batch"] < 100.0
    assert metrics["host_ms_per_chunk.batch"] > 0.0
    assert metrics["queue_wait_ms.mean.batch"] > 0.0
    assert metrics["lane_to_first_token_ms.mean.batch"] > 0.0
