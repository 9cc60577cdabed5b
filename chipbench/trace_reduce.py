"""From a profiler trace to busy/idle, op time by name, collective time and
labelled idle gaps.

The reduction works on plain tuples so that it can be checked on a small
hand-built trace (tests/benchmark/test_chipbench_reduce.py):

  device_events  {device_id: [(name, start_ns, duration_ns), ...]}   ops
  module_events  {device_id: [(name, start_ns, duration_ns), ...]}   programs
  host_events    [(name, start_ns, duration_ns), ...]   the benchmark's own
                 ``jax.profiler.TraceAnnotation`` spans (``chipbench/...``)

``load_xplane`` fills them from the ``.xplane.pb`` file JAX's profiler
writes, with nothing but ``jax.profiler.ProfileData``.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]

COLLECTIVE_MARKS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute", "collective-broadcast")
HOST_PREFIX = "chipbench/"
# a TPU device plane's lines, as libtpu names them
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ASYNC_LINE = "Async XLA Ops"
DEVICE_PLANE_RE = re.compile(r"^/device:TPU:(\d+)$")


def union_ns(intervals: Sequence[Tuple[int, int]]) -> int:
    """Total length covered by ``[(start, end), ...]``."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals: Sequence[Tuple[int, int]], lo: int, hi: int
            ) -> List[Tuple[int, int]]:
    """The uncovered stretches of ``[lo, hi]``."""
    out, at = [], lo
    for s, e in sorted(intervals):
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def base_name(name: str) -> str:
    """The op or program without its instance number or its operands. A TPU
    trace names an op by its whole HLO line (``%fusion.123 = bf16[..]
    fusion(..)``) and a program as ``jit_f(4711)``: -> ``fusion``,
    ``jit_f``."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    name = re.sub(r"\(\d+\)$", "", name)
    return re.sub(r"[.\-]\d+$", "", name)


def self_times(events: Sequence[Tuple[str, int, int]]
               ) -> List[Tuple[str, int]]:
    """``[(name, start, end)]`` -> ``[(name, self_ns)]``: an op's time less
    the ops nested inside it. The ops line nests: a ``while`` spans its
    body's ops, and counting both would count the body twice."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    child = [0] * len(events)
    stack: List[int] = []
    for i in order:
        _, s, e = events[i]
        while stack and events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            child[stack[-1]] += e - s
        stack.append(i)
    return [(events[i][0], max(0, events[i][2] - events[i][1] - child[i]))
            for i in range(len(events))]


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    n_devices: int
    busy_s: float                         # mean over devices
    op_seconds: Dict[str, float]          # by base name, mean over devices
    op_counts: Dict[str, int]             # by base name, device 0
    module_seconds: Dict[str, float]      # by program name, mean over devices
    module_counts: Dict[str, int]         # by program name, device 0
    collective_s: float                   # mean over devices
    idle_gaps: List[Tuple[str, float]]    # (host label, seconds), device 0

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s if self.window_s > 0 else 0.0

    def op_time(self, *needles: str) -> float:
        """Seconds (mean over devices) of ops whose name holds a needle."""
        return sum(s for n, s in self.op_seconds.items()
                   if any(k in n for k in needles))

    def module_time(self, needle: str) -> Tuple[float, int]:
        secs = sum(s for n, s in self.module_seconds.items() if needle in n)
        count = sum(c for n, c in self.module_counts.items() if needle in n)
        return secs, count

    def top_ops(self, k: int = 10) -> List[List]:
        top = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:k]
        return [[n, s] for n, s in top]

    def top_gaps(self, k: int = 10) -> List[List]:
        return [[n, s] for n, s in self.idle_gaps[:k]]


def reduce_events(device_events: Dict[int, List[Event]],
                  module_events: Dict[int, List[Event]],
                  host_events: List[Event],
                  window_ns: Optional[Tuple[int, int]] = None,
                  min_gap_ns: int = 20_000,
                  async_events: Optional[Dict[int, List[Event]]] = None
                  ) -> Optional[TraceSummary]:
    """None when no op ran on any device (nothing to read). ``async_events``
    are the device's asynchronous ops (DMAs and collectives that run beside
    the ops line): they count towards collective time, never towards busy."""
    devices = sorted(d for d, ev in device_events.items() if ev)
    if not devices:
        return None
    if window_ns is None:
        lo = min(s for d in devices for _, s, _ in device_events[d])
        hi = max(s + n for d in devices for _, s, n in device_events[d])
    else:
        lo, hi = window_ns
    n = len(devices)

    def clip(ev):
        return [(nm, max(s, lo), min(s + d, hi)) for nm, s, d in ev
                if s + d > lo and s < hi]

    busy = 0
    op_s: Dict[str, float] = {}
    op_c: Dict[str, int] = {}
    mod_s: Dict[str, float] = {}
    mod_c: Dict[str, int] = {}
    coll = 0
    for d in devices:
        ops = clip(device_events[d])
        busy += union_ns([(s, e) for _, s, e in ops])
        for nm, own in self_times(ops):
            key = base_name(nm)
            op_s[key] = op_s.get(key, 0.0) + own / 1e9 / n
            if d == devices[0]:
                op_c[key] = op_c.get(key, 0) + 1
        coll += union_ns([(s, e) for nm, s, e in
                          ops + clip((async_events or {}).get(d, []))
                          if any(m in base_name(nm)
                                 for m in COLLECTIVE_MARKS)])
        for nm, s, e in clip(module_events.get(d, [])):
            key = base_name(nm)
            mod_s[key] = mod_s.get(key, 0.0) + (e - s) / 1e9 / n
            if d == devices[0]:
                mod_c[key] = mod_c.get(key, 0) + 1

    # idle gaps of the first device, each labelled by the benchmark's own
    # host span that covers most of it
    first = clip(device_events[devices[0]])
    hosts = [(nm, s, s + d) for nm, s, d in host_events]
    by_label: Dict[str, float] = {}
    for gs, ge in gaps_ns([(s, e) for _, s, e in first], lo, hi):
        if ge - gs < min_gap_ns:
            label = "gaps under %d us" % (min_gap_ns // 1000)
        else:
            label, best = "unlabelled", 0
            for nm, hs, he in hosts:
                cover = min(ge, he) - max(gs, hs)
                if cover > best:
                    label, best = nm, cover
        by_label[label] = by_label.get(label, 0.0) + (ge - gs) / 1e9
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])
    return TraceSummary(window_s=(hi - lo) / 1e9, n_devices=n,
                        busy_s=busy / 1e9 / n, op_seconds=op_s,
                        op_counts=op_c, module_seconds=mod_s,
                        module_counts=mod_c, collective_s=coll / 1e9 / n,
                        idle_gaps=gaps)


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load_xplane(path: str, outline: Optional[list] = None):
    """-> (device_events, module_events, host_events, async_events) from an
    xplane file.
    ``outline``, if a list, receives one line per plane/line with its event
    count and a few names: what to look at before trusting the reduction."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device_events: Dict[int, List[Event]] = {}
    module_events: Dict[int, List[Event]] = {}
    async_events: Dict[int, List[Event]] = {}
    host_events: List[Event] = []
    lines = {OPS_LINE: device_events, MODULES_LINE: module_events,
             ASYNC_LINE: async_events}
    for plane in data.planes:
        m = DEVICE_PLANE_RE.match(plane.name)
        for line in plane.lines:
            events = list(line.events)
            if outline is not None:
                names = sorted({e.name[:80] for e in events[:2000]})[:6]
                outline.append(f"{plane.name} | {line.name} | "
                               f"{len(events)} events | {names}")
            if m:
                if line.name in lines:
                    lines[line.name].setdefault(int(m.group(1)), []).extend(
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in events)
            elif plane.name.startswith("/host:"):
                host_events.extend(
                    (e.name, int(e.start_ns), int(e.duration_ns))
                    for e in events if e.name.startswith(HOST_PREFIX))
    return device_events, module_events, host_events, async_events


def reduce_trace_dir(trace_dir: str, outline: Optional[list] = None
                     ) -> Optional[TraceSummary]:
    """The newest trace under ``trace_dir`` reduced; None if there is none
    or no op ran on a device. The window is the stretch the benchmark's own
    ``chipbench/traced`` host span covers, when it is there."""
    path = find_xplane(trace_dir)
    if path is None:
        return None
    dev, mod, host, asy = load_xplane(path, outline)
    window = None
    for nm, s, d in host:
        if nm == HOST_PREFIX + "traced":
            window = (s, s + d)
    host = [h for h in host if h[0] != HOST_PREFIX + "traced"]
    return reduce_events(dev, mod, host, window, async_events=asy)
