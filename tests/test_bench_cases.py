"""bench.py case machinery rehearsal (BENCH_TINY=1): every case must
construct its engine and produce a line on the CPU backend under a
``_TINY_SMOKE`` name, so the run on the chip can't die to plumbing bit-rot
— and a real-size run without a chip must fail, not carry on."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "bench.py")


def _bench(args, timeout=420, **env):
    full = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="")
    full.pop("BENCH_CASES", None)
    full.pop("BENCH_TINY", None)
    full.update(env)
    return subprocess.run([sys.executable, BENCH] + args, cwd=REPO,
                          env=full, capture_output=True, text=True,
                          timeout=timeout)


@pytest.mark.parametrize("name,metric_prefix", [
    ("gpt2_125m_zero1", "gpt2_125m_train_mfu"),
    ("ladder_zero3", "ladder_"),
    ("ladder_zero3_offload", "ladder_"),
    ("capacity_streamed", "capacity_streamed_params_B"),
    ("long_context", "long_context_"),
    ("max_params", "max_params_per_chip_B"),
    ("nvme_overlap", "nvme_swap_overlap_ratio"),
    ("long_context_sparse", "long_context_sparse_"),
])
def test_bench_case_rehearses_under_a_tiny_smoke_name(name, metric_prefix,
                                                      tmp_path):
    p = _bench(["--case", name], BENCH_TINY="1",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert p.returncode == 0, f"{name}: {p.stderr[-2000:]}"
    lines = [json.loads(l) for l in p.stdout.splitlines()
             if l.startswith("{")]
    case, summary = lines[0], lines[-1]
    assert case["metric"].startswith(metric_prefix), case
    assert case["metric"].endswith("_TINY_SMOKE"), case
    # every line names the device it ran on
    assert case["device"] == {"platform": "cpu", "kind": "cpu", "count": 1}
    assert summary["device"] == case["device"]
    assert summary["cases"][name]["metric"] == case["metric"]
    assert "failed_cases" not in summary


def test_real_size_without_a_chip_fails():
    p = _bench(["--case", "max_params"])
    assert p.returncode != 0
    assert not [l for l in p.stdout.splitlines() if l.startswith("{")]
    assert "measures on a TPU" in p.stderr


def test_an_unknown_case_is_refused(tmp_path):
    p = _bench([], BENCH_TINY="1", BENCH_CASES="max_params,no_such_case",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    assert p.returncode == 2 and "unknown cases" in p.stderr


def test_a_failed_case_makes_the_run_exit_nonzero(monkeypatch, capsys):
    """Whatever else landed — the flagship included — one failed case is a
    non-zero exit, and the summary says which."""
    import jax
    sys.path.insert(0, REPO)
    import bench
    monkeypatch.setattr(bench, "TINY", True)
    monkeypatch.setattr(bench, "_persist", lambda state: None)
    monkeypatch.setitem(bench.CASE_FNS, bench.FLAGSHIP, lambda: {
        "metric": "flagship_TINY_SMOKE", "value": None, "unit": "",
        "vs_baseline": None})
    monkeypatch.setitem(bench.CASE_FNS, "max_params", lambda: 1 / 0)
    monkeypatch.setenv("BENCH_CASES", f"{bench.FLAGSHIP},max_params")
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    before = jax.config.jax_compilation_cache_dir
    try:
        rc = bench.main()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    assert rc == 1
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert summary["metric"] == "flagship_TINY_SMOKE"
    assert "ZeroDivisionError" in summary["failed_cases"]["max_params"]
