"""The five per-layer metrics that read the serve loop's device timeline
(spans serve/device_decode_chunk and serve/device_prefill, PR 34) and
ServingMetrics' prompt-token counts: each reader on hand-built spans and on
none, their BENCHMARK.json entries found BY NAME (so a later appended entry
leaves this file green), and the traced rehearsals of ``serve-batch`` and of
one ``arch`` cell printing all five."""

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
SEED = 3_000_000_034
SERVING = ["serve-batch", "serve-reason", "serve-longdoc", "serve-agent"]


def _span(count, total_s):
    return {"count": count, "total_s": total_s}


# a 40 s window: 300 chunks of 8 steps, 296 of them between two exact
# stamps, 100 prefill calls of which 98 were; 0.4 s starved
SPANS = {
    "serve/device_decode_chunk": _span(296, 29.6),
    "serve/device_prefill": _span(98, 9.5),
    "serve/starved_after_prefill": _span(100, 0.4),
    "serve/chunk_host_wait": _span(300, 30.0),
    "serve/prefill_wait": _span(100, 7.0),
    "serve/prefill_wait_chunk_ahead": _span(90, 5.0),
    "serve/prefill_wait_own": _span(100, 2.0),
}
COUNTERS = {"window_s": 40.0, "decode_chunk": 8,
            "window": {"prefill_prompt_tokens": 100_000,
                       "prefill_padded_tokens": 125_000, "chunks": 300}}
WANT = {
    "decode_step_ms.window.batch": 1e3 * 29.6 / (296 * 8),
    "prefill_share.window.batch": 100.0 * 9.5 / 40.0,
    "prefill_ms_per_ktok.window.batch": 1e3 * 9.5 / 125.0,
    "device_observed.window.batch": 100.0 * (29.6 + 9.5) / 40.0,
    "prefill_pad_ratio.batch": 1.25,
}
# what the parent's program hands a reader: its spans (no timeline) and
# the same counters
PARENTS = {k: v for k, v in SPANS.items()
           if not k.startswith("serve/device_")
           and not k.startswith("serve/prefill_wait_")}


def _reader(name):
    return spec.load_module(spec.find_reader(BENCH, name)).read


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_on_hand_built_spans(name):
    assert _reader(name)(None, SPANS, COUNTERS, {}) == \
        pytest.approx(WANT[name])


@pytest.mark.parametrize("name", sorted(WANT))
def test_reader_finds_nothing_in_a_program_without_its_source(name):
    """At the parent commit (no timeline spans), with telemetry off (no
    spans at all) or from a driver that hands no such counter: nothing to
    read is None, never an error, and the line leaves the metric out."""
    read = _reader(name)
    assert read(None, {}, {}, {}) is None
    assert read(None, {}, {"window_s": 40.0, "decode_chunk": 8,
                           "window": {}}, {}) is None
    if name == "prefill_pad_ratio.batch":     # a counter: the parent has it
        assert read(None, PARENTS, COUNTERS, {}) == pytest.approx(1.25)
    else:
        assert read(None, PARENTS, COUNTERS, {}) is None
    zero = dict(COUNTERS, window_s=0.0, decode_chunk=0,
                window={"prefill_prompt_tokens": 0,
                        "prefill_padded_tokens": 0})
    assert read(None, SPANS, zero, {}) is None      # nothing to divide by


def test_observed_reads_either_span_alone():
    read = _reader("device_observed.window.batch")
    one = {"serve/device_decode_chunk": _span(10, 8.0)}
    assert read(None, one, COUNTERS, {}) == pytest.approx(20.0)


def test_observed_and_starved_close_on_the_window():
    starved = _reader("device_starved.batch")(None, SPANS, COUNTERS, {})
    observed = _reader("device_observed.window.batch")(None, SPANS,
                                                       COUNTERS, {})
    # 4 chunks and 2 prefills of the hand-built window went to late stamps
    assert 97.0 < observed + starved <= 100.0


@pytest.mark.parametrize("name", sorted(WANT))
def test_the_entry_is_there_for_the_four_serving_cells(name):
    spec.validate(BENCH)
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert entry["workloads"] == SERVING
    assert entry["moves"] == "serve_tokens_per_s"
    assert entry["source"] == ("program_counter"
                               if name == "prefill_pad_ratio.batch"
                               else "program_span")
    assert entry["better"] == ("higher" if name.startswith("device_observed")
                               else "lower")
    older = {m["layer"] for m in BENCH["per_layer"] if m["name"] not in WANT}
    assert entry["layer"] in older              # a layer already named
    for cell in SERVING:
        assert name in {m["name"] for m in spec.metrics_of_cell(
            BENCH, cell, "per_layer")}
    assert os.path.dirname(spec.find_reader(BENCH, name)).endswith(
        os.path.join("chipbench", "layer_metrics"))


@pytest.mark.parametrize("cell", ["serve-batch", "serve-reason"])
def test_traced_rehearsal_prints_the_five(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=1",
               PYTHONPATH=ROOT)
    env.pop("BENCH_RUN", None)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
         "--workload", cell, "--seed", str(SEED), "--seconds", "2",
         "--trace", "1", "--rehearsal"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-3000:] + r.stdout[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    metrics = {n[:-len(SUFFIX_REHEARSAL)]: m["value"]
               for n, m in last["metrics"].items()
               if n.endswith(SUFFIX_REHEARSAL)}
    assert len(metrics) == len(last["metrics"])     # all renamed
    assert set(WANT) <= set(metrics)
    assert metrics["decode_step_ms.window.batch"] > 0.0
    assert 0.0 < metrics["prefill_share.window.batch"] < 100.0
    assert metrics["prefill_ms_per_ktok.window.batch"] > 0.0
    assert 0.0 < metrics["device_observed.window.batch"] <= 100.5
    assert metrics["prefill_pad_ratio.batch"] >= 1.0
