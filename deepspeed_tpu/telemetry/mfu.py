"""Compile-time FLOPs and model-FLOPs-utilization (MFU) estimation.

Methodology: instead of an analytic ``6 * params * tokens`` guess, we
ask XLA what the compiled program actually does —
``jitted.lower(*abstract_args).compile().cost_analysis()`` — and divide
the achieved FLOPs/s (program flops x calls / measured wall) by the
accelerator's published peak. Abstract lowering uses
``jax.ShapeDtypeStruct`` trees, so no device buffers are touched.

Caveats (also in docs/observability.md):

* **One extra compile.** Lowering for cost analysis compiles the
  program once more than the serving/training path needs. Callers that
  sit under a :class:`~deepspeed_tpu.analysis.auditor.TraceAuditor`
  retrace budget MUST run estimation *after* the audited/timed region
  (the benches do) — the pinned decode/train compile counts stay exact.
* **Scan undercount.** XLA cost analysis counts a ``lax.scan`` body
  once, not trip-count times (see ``profiling/flops_profiler.py``);
  for scanned-layer models the report marks flops a lower bound.
* **CPU peak is unknown.** On the XLA CPU backend ``cost_analysis``
  still reports flops (the estimator is testable in CI), but there is
  no meaningful peak, so ``mfu`` is ``None`` unless
  ``DSTPU_PEAK_FLOPS`` overrides it.

JAX is imported lazily — this module (pulled in by the package
``__init__``) stays importable by the stdlib-only ``bin/tputrace``.
"""

from __future__ import annotations

import os
from typing import Any, Dict, Optional

#: Published dense peak FLOPs/s per TPU *chip* (bf16), keyed by a
#: lowercase substring of ``device.device_kind``. Most-specific first.
#: The ONE peaks table: bench.py reads it through :func:`table_peak_flops`
#: and treats a device it does not list as an error. Source: Google Cloud
#: TPU documentation, per-generation system-architecture pages ("TPU v5e":
#: 197 TFLOP/s bf16 per chip; JAX reports that chip as ``TPU v5 lite``).
_TPU_PEAK_BF16 = (
    ("v6", 918e12),      # Trillium
    ("v5p", 459e12),
    ("v5 lite", 197e12),
    ("v5e", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 45e12),
)

PEAK_FLOPS_ENV = "DSTPU_PEAK_FLOPS"


def table_peak_flops(device_kind: str) -> Optional[float]:
    """The table's bf16 peak for a ``device_kind``, or ``None`` when the
    table does not know it. No environment override, no default."""
    kind = device_kind.lower()
    for sub, peak in _TPU_PEAK_BF16:
        if sub in kind:
            return peak
    return None


def peak_flops_per_device(device=None) -> Optional[float]:
    """Peak bf16 FLOPs/s of one device, or ``None`` when unknown (CPU,
    unrecognized kind). ``DSTPU_PEAK_FLOPS`` (float, FLOPs/s) overrides
    the table — the knob for GPU backends or future chips."""
    env = os.environ.get(PEAK_FLOPS_ENV)
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if device is None:
        import jax
        device = jax.devices()[0]
    kind = getattr(device, "device_kind", "").lower()
    if "tpu" not in kind and getattr(device, "platform", "") != "tpu":
        return None
    return table_peak_flops(kind)


def compiled_cost_analysis(fn, *args, **kwargs) -> Optional[Dict[str, Any]]:
    """XLA cost analysis of ``fn(*args, **kwargs)``: ``{"flops": float,
    "bytes_accessed": float|None}``. ``fn`` may be a plain callable
    (jitted here) or an existing ``jax.jit`` wrapper — passing the
    engine's own jitted program guarantees the analyzed computation IS
    the one being timed. Args may be real arrays or
    ``jax.ShapeDtypeStruct`` (abstract lowering; no device work).
    Returns ``None`` when the backend does not report."""
    import jax
    try:
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        ca = jitted.lower(*args, **kwargs).compile().cost_analysis()
        if isinstance(ca, (list, tuple)):
            ca = ca[0] if ca else {}
        flops = float(ca.get("flops", 0.0))
        if flops <= 0.0:
            return None
        ba = ca.get("bytes accessed")
        return {"flops": flops,
                "bytes_accessed": float(ba) if ba is not None else None}
    except Exception:
        return None


def mfu_report(*, flops_per_call: Optional[float], calls: int,
               wall_s: float, n_devices: int = 1,
               peak_flops: Optional[float] = None,
               label: str = "") -> Dict[str, Any]:
    """Assemble the MFU block embedded in bench JSON and printed by the
    flops profiler. ``flops_per_call`` is the whole-program flops of one
    call (already spanning all devices for a pmapped/sharded program);
    ``mfu`` is achieved / (peak x n_devices), ``None`` when either side
    is unknown."""
    achieved = None
    if flops_per_call and wall_s > 0 and calls > 0:
        achieved = flops_per_call * calls / wall_s
    mfu = None
    if achieved is not None and peak_flops:
        mfu = achieved / (peak_flops * max(n_devices, 1))
    return {
        "label": label,
        "flops_per_call": flops_per_call,
        "calls": calls,
        "wall_s": wall_s,
        "achieved_flops_per_s": achieved,
        "achieved_tflops_per_s":
            achieved / 1e12 if achieved is not None else None,
        "n_devices": n_devices,
        "peak_flops_per_device": peak_flops,
        "mfu": mfu,
    }
