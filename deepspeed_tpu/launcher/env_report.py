"""Environment / op-compatibility report (reference: ``deepspeed/env_report.py``
driving the ``ds_report`` bin script — version matrix + op build status)."""

from __future__ import annotations

import importlib
import json
import os
import platform
import shutil
import subprocess
import sys

GREEN_OK = "\033[92m[OKAY]\033[0m"
RED_NO = "\033[91m[NO]\033[0m"


def _try_version(mod: str):
    try:
        m = importlib.import_module(mod)
        return getattr(m, "__version__", "unknown")
    except Exception:
        return None


def probe_devices(timeout: float = 30.0) -> dict:
    """Device probe in a child process with a hard timeout. The child takes
    the chip, reports and exits, so the CALLER never initialises a backend:
    a chip belongs to one process at a time, and a parent that held it
    could start no child that needs it (the autotuner's process isolation
    relies on this). A backend init that hangs costs the timeout, not the
    report."""
    code = (
        "import json, jax\n"
        "devs = jax.devices()\n"
        "try:\n"
        "    hbm = devs[0].memory_stats()['bytes_limit']\n"
        "except Exception:\n"
        "    hbm = None\n"
        "print(json.dumps({'backend': jax.default_backend(),"
        " 'devices': [str(d) for d in devs], 'hbm': hbm}))\n")
    try:
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            timeout=timeout)
    except subprocess.TimeoutExpired:
        return {"error": f"backend init timed out after {timeout:.0f}s"}
    if out.returncode != 0:
        tail = (out.stderr or "").strip().splitlines()
        return {"error": tail[-1] if tail else f"probe rc={out.returncode}"}
    try:
        return json.loads(out.stdout.strip().splitlines()[-1])
    except Exception:
        return {"error": "unparseable probe output"}


def op_report() -> list:
    """Build/compat status of the native + pallas ops (reference
    op_builder ``is_compatible`` matrix)."""
    rows = []
    from ..ops.op_builder import available_builders
    for name, builder in available_builders().items():
        try:
            compatible = builder.is_compatible()
        except Exception:
            compatible = False
        loaded = False
        if compatible:
            try:
                builder.load()
                loaded = True
            except Exception:
                loaded = False
        rows.append((name, compatible, loaded))
    return rows


def main() -> int:
    print("-" * 64)
    print("deepspeed_tpu environment report")
    print("-" * 64)
    from .. import version
    print(f"deepspeed_tpu .......... {version.__version__}")
    print(f"python ................. {platform.python_version()}")
    print(f"platform ............... {platform.platform()}")
    for mod in ("jax", "jaxlib", "flax", "optax", "numpy"):
        v = _try_version(mod)
        print(f"{mod:<22} {'.' * 1} {v if v else RED_NO}")
    for tool in ("g++", "cmake", "ninja"):
        path = shutil.which(tool)
        print(f"{tool:<22} . {path or RED_NO}")

    print("-" * 64)
    print("devices")
    print("-" * 64)
    probe = probe_devices(timeout=float(os.environ.get(
        "DS_REPORT_DEVICE_TIMEOUT", "30")))
    if "error" in probe:
        print(f"jax devices unavailable: {probe['error']}")
    else:
        devs = probe["devices"]
        print(f"backend ................ {probe['backend']}")
        print(f"device count ........... {len(devs)}")
        for d in devs[:8]:
            print(f"  {d}")
        if len(devs) > 8:
            print(f"  ... and {len(devs) - 8} more")

    print("-" * 64)
    print("op compatibility")
    print("-" * 64)
    print(f"{'op name':<24}{'compatible':<16}{'built'}")
    for name, compatible, loaded in op_report():
        print(f"{name:<24}"
              f"{GREEN_OK if compatible else RED_NO:<25}"
              f"{GREEN_OK if loaded else RED_NO}")

    # capacity estimates (reference: the estimate_zero*_mem_needs helpers
    # users run to size a job, runtime/zero/utils)
    print("-" * 64)
    print("capacity (this host, max trainable params per chip)")
    print("-" * 64)
    try:
        from ..autotuning.memory import capacity_tiers, host_resources
        hbm = probe.get("hbm")
        if not hbm:
            raise RuntimeError("the device reported no memory size "
                               "(no chip reachable?)")
        res = host_resources()
        tiers = capacity_tiers(float(hbm), res["host_dram"],
                               res["nvme_free"])
        rows = [
            ("pure HBM (ZeRO-1/2/3, dp=1)", tiers["hbm_only"]),
            ("+ offload_optimizer=cpu", tiers["host_offload"]),
            ("+ optimizer state on NVMe", tiers["nvme_offload"]),
            ("+ layer_streaming (DRAM-bound)", tiers["streamed_host"]),
            ("+ layer_streaming + NVMe state", tiers["streamed_nvme"]),
        ]
        for name, n in rows:
            print(f"{name:<36} ~{n / 1e9:5.2f}B params")
        print("(bytes-per-param model: autotuning/memory.py "
              "capacity_tiers)")
    except Exception as e:
        print(f"capacity estimate unavailable: {e}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
