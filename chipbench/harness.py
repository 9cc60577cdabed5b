"""What every driver shares: the run's context, the traced stretch, device
facts and percentiles."""

from __future__ import annotations

import dataclasses
import os
import shutil
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Sequence

from . import trace_reduce
from .compile_watch import CompileWatch

SUFFIX_REHEARSAL = ".REHEARSAL_NOT_A_DEVICE_NUMBER"


@dataclasses.dataclass
class Ctx:
    cell: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    rehearsal: bool
    devices: list
    peaks: Optional[Dict[str, float]]     # None in rehearsal: no chip
    watch: CompileWatch
    t_start: float                        # perf_counter at process start
    trace_dir: str

    def say(self, msg: str) -> None:
        print(f"[{self.cell['name']} +{time.perf_counter() - self.t_start:6.1f}s]"
              f" {msg}", flush=True)


def annotate(name: str):
    """A host span in the profiler's own trace (``chipbench/<name>``): idle
    gaps on the device are labelled by the span that covers them."""
    import jax
    return jax.profiler.TraceAnnotation(trace_reduce.HOST_PREFIX + name)


class TracedStretch:
    """``with TracedStretch(ctx) as t: ...`` traces the body with
    ``jax.profiler`` into a fixed directory inside the checkout; afterwards
    ``t.summary`` is the reduced trace (None if nothing could be read)."""

    def __init__(self, ctx: Ctx):
        self.ctx = ctx
        self.summary: Optional[trace_reduce.TraceSummary] = None
        self.outline: List[str] = []
        self._span = None

    def start(self) -> None:
        import jax
        shutil.rmtree(self.ctx.trace_dir, ignore_errors=True)
        os.makedirs(self.ctx.trace_dir, exist_ok=True)
        # the host's Python tracer off: it slows the threads under test
        # and the benchmark's own TraceAnnotation spans do not need it
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.ctx.trace_dir, profiler_options=opts)
        self._span = annotate("traced")
        self._span.__enter__()

    def stop(self) -> None:
        import jax
        self._span.__exit__(None, None, None)
        jax.profiler.stop_trace()
        t0 = time.perf_counter()
        self.summary = trace_reduce.reduce_trace_dir(self.ctx.trace_dir,
                                                     self.outline)
        self.ctx.say(f"trace reduced in {time.perf_counter() - t0:.1f}s: "
                     + (f"window {self.summary.window_s:.3f}s busy "
                        f"{self.summary.busy_s:.3f}s over "
                        f"{self.summary.n_devices} device(s)"
                        if self.summary else "no device op found"))
        shutil.rmtree(self.ctx.trace_dir, ignore_errors=True)

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()


def in_thread(fn: Callable[[], None]) -> threading.Thread:
    """Run ``fn`` beside a client loop that must not stall (starting and
    stopping the profiler takes seconds)."""
    t = threading.Thread(target=fn, name="chipbench-profiler", daemon=True)
    t.start()
    return t


def percentile(values: Sequence[float], q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order
    statistics; the tail of ALL values given."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def device_facts(devices) -> Dict[str, Any]:
    peak = 0
    limit = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        limit = max(limit, int(stats.get("bytes_limit", 0)))
    dev = devices[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices), "memory_peak_bytes": peak,
            "memory_limit_bytes": limit}


def memory_line(devices) -> str:
    """In use now / peak so far on the fullest device, for the log."""
    stats = [d.memory_stats() or {} for d in devices]
    return ("device memory in use %.2f GB, peak so far %.2f GB" % (
        max(s.get("bytes_in_use", 0) for s in stats) / 1e9,
        max(s.get("peak_bytes_in_use", 0) for s in stats) / 1e9))


def device_shares(tree, devices) -> List[float]:
    """Fraction of a pytree's bytes held on each device (after
    chip_smoke.py::device_shares, PR 21)."""
    import jax
    held = {d.id: 0 for d in devices}
    total = 0
    for leaf in jax.tree.leaves(tree):
        if not hasattr(leaf, "addressable_shards"):
            continue
        total += leaf.nbytes
        for shard in leaf.addressable_shards:
            held[shard.device.id] += shard.data.nbytes
    return [held[d.id] / max(total, 1) for d in devices]
