"""Test env: force an 8-device virtual CPU mesh BEFORE jax import.

Mirrors the reference's distributed-test strategy (tests/unit/common.py:67 —
N forked processes stand in for a cluster): here N virtual CPU devices in one
process stand in for a TPU slice.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"    # inherited by every child a test spawns
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = flags + " --xla_force_host_platform_device_count=8"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

# a pytest plugin may have imported jax before this file ran, in which case
# it read JAX_PLATFORMS from the outer env; the config wins either way
jax.config.update("jax_platforms", "cpu")
assert jax.devices()[0].platform == "cpu", (
    "tests must run on the virtual CPU mesh; backend was initialized too early")
assert len(jax.devices()) == 8

import pytest  # noqa: E402

# Modules dominated by multi-second jit compiles / process forks / NVMe
# swaps; `pytest -m "not slow"` is the quick tier (reference CI's
# `-m 'sequential'`-style split, nv-torch-latest-v100.yml:63).
_SLOW_MODULES = {
    "test_pipe_engine", "test_multiprocess", "test_offload",
    "test_autotuning", "test_onebit", "test_sharded_checkpoint",
    "test_sequence_parallel", "test_inference", "test_config_knobs",
    "test_moe", "test_bert_and_autotp", "test_bert_sparse",
    "test_features", "test_zero_init", "test_engine", "test_gpt_model",
    "test_zero", "test_launcher", "test_175b_plan", "test_pipe_overlap",
    "test_layer_stream", "test_bench_cases", "test_multiprocess_pipe",
}


def pytest_collection_modifyitems(items):
    for item in items:
        if item.module.__name__ in _SLOW_MODULES:
            item.add_marker(pytest.mark.slow)


# ---- per-module wall-clock budget (the slow tier grows every round; a
# module that quietly balloons past the budget starts failing its TAIL
# tests with an explicit budget message instead of making the whole tier
# unrunnable unnoticed). Override with DS_TEST_MODULE_BUDGET_S; 0 disables.
_MODULE_BUDGET_S = float(os.environ.get("DS_TEST_MODULE_BUDGET_S", "600"))
_module_spent: dict = {}


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    import time
    t0 = time.perf_counter()
    try:
        return (yield)
    finally:
        mod = item.module.__name__
        _module_spent[mod] = (_module_spent.get(mod, 0.0)
                              + time.perf_counter() - t0)


def pytest_runtest_setup(item):
    mod = item.module.__name__
    spent = _module_spent.get(mod, 0.0)
    if _MODULE_BUDGET_S and spent > _MODULE_BUDGET_S:
        pytest.fail(
            f"test module {mod} has spent {spent:.0f}s, over its "
            f"{_MODULE_BUDGET_S:.0f}s wall-clock budget — split the "
            f"module, shrink its cases, or raise "
            f"DS_TEST_MODULE_BUDGET_S (0 disables)", pytrace=False)


def pytest_terminal_summary(terminalreporter):
    rows = sorted(_module_spent.items(), key=lambda kv: -kv[1])[:8]
    if rows and rows[0][1] > 30:
        terminalreporter.write_line("")
        terminalreporter.write_line(
            f"slowest modules (budget {_MODULE_BUDGET_S:.0f}s each): "
            + ", ".join(f"{m}={t:.0f}s" for m, t in rows if t > 10))


@pytest.fixture(autouse=True)
def _reset_mesh():
    from deepspeed_tpu.parallel import mesh as mesh_lib
    yield
    mesh_lib.reset_global_mesh()


@pytest.fixture
def telemetry_on():
    """The process-wide telemetry runtime (the one the serve loop's and
    the driver's spans go to), enabled and clean; restored after."""
    from deepspeed_tpu.telemetry import core as tel
    rt = tel.get_runtime()
    was_enabled = rt.enabled
    rt.clear()
    rt.enable()
    yield rt
    rt.clear()
    rt.enabled = was_enabled


@pytest.fixture
def past_auto_path(monkeypatch):
    """``"auto"`` resolved as on the chip: the kernel where its gate
    accepts (on the CPU it takes the XLA path whatever the gate says, and
    the Pallas kernel then runs in the interpreter). What was asked and
    answered is handed to the test."""
    from deepspeed_tpu.ops.pallas import _utils as kernels
    asked = []

    def auto_path(kernel, refusal):
        asked.append((kernel, refusal))
        return refusal is None
    monkeypatch.setattr(kernels, "auto_path", auto_path)
    return asked
