"""Chrome-trace / Perfetto JSON export.

One trace file for everything: engine/driver spans on per-thread lanes
(pid 1), serving-frontend request lifecycles on per-request lanes
(pid 2) with flow arrows, ``TraceAuditor`` retrace markers as instant
events, and counters as Perfetto counter tracks. Open the file at
https://ui.perfetto.dev or chrome://tracing.

Format notes (Trace Event Format, the JSON Perfetto ingests):

* ``ph: "X"`` complete events carry ``ts`` + ``dur`` (microseconds);
* ``ph: "i"`` instants (scope ``"t"`` = thread-local tick);
* ``ph: "C"`` counter samples — Perfetto draws one track per name;
* ``ph: "M"`` metadata names processes and threads;
* ``ph: "s"`` / ``"f"`` flow start/finish arrows tie a request's
  submit to its finish across the timeline.

Timebase: the runtime stamps ``time.perf_counter``; the frontend
``TraceLog`` stamps ``time.monotonic``. On Linux both read
CLOCK_MONOTONIC, so the lanes line up in one file without translation;
``request_trace_events`` takes ``clock_offset_s`` for platforms where
they differ.

Stdlib-only — ``bin/tputrace`` imports this without JAX.
"""

from __future__ import annotations

import json
from typing import Any, Dict, Iterable, List, Optional

_US = 1e6

#: pid lanes in the merged file (3 = journeys, see ``journey.py``)
PID_RUNTIME = 1
PID_REQUESTS = 2


def _args_of(attrs: Optional[Dict[str, Any]]) -> Dict[str, Any]:
    if not attrs:
        return {}
    out = {}
    for k, v in attrs.items():
        out[k] = v if isinstance(v, (int, float, str, bool, type(None))) \
            else str(v)
    return out


def runtime_events(runtime, *, pid: int = PID_RUNTIME,
                   process_name: str = "deepspeed_tpu") -> List[dict]:
    """Render a :class:`TelemetryRuntime`'s ring as trace events."""
    events: List[dict] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": process_name},
    }]
    for tid, tname in sorted(runtime.thread_names().items()):
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": tname}})
    for ev in runtime.events():
        kind = ev[0]
        if kind == "X":
            _, name, ts, dur, tid, attrs = ev
            events.append({"name": name, "ph": "X", "ts": ts,
                           "dur": max(dur, 0.0), "pid": pid, "tid": tid,
                           "args": _args_of(attrs)})
        elif kind == "i":
            _, name, ts, tid, attrs = ev
            events.append({"name": name, "ph": "i", "s": "t", "ts": ts,
                           "pid": pid, "tid": tid,
                           "args": _args_of(attrs)})
        elif kind == "C":
            _, name, ts, value = ev
            events.append({"name": name, "ph": "C", "ts": ts, "pid": pid,
                           "tid": 0, "args": {name: value}})
    return events


def request_trace_events(trace_json: Dict[str, Any], *,
                         pid: int = PID_REQUESTS,
                         clock_offset_s: float = 0.0) -> List[dict]:
    """Render ``TraceLog.to_json()`` request records as trace events —
    the frontend's per-request story in the SAME file as the engine
    timeline (satellite: no second trace format to maintain).

    Each request gets its own lane (``tid`` = uid): a whole-lifetime
    span, child spans for the queue-wait and streaming phases, one
    instant per delivered chunk, and an ``s``/``f`` flow pair keyed by
    uid tying submit to finish."""
    events: List[dict] = [{
        "name": "process_name", "ph": "M", "pid": pid, "tid": 0,
        "args": {"name": "frontend requests"},
    }]

    def us(t: float) -> float:
        return (t + clock_offset_s) * _US

    for rec in list(trace_json.get("requests", ())) + \
            list(trace_json.get("live", ())):
        uid = rec["uid"]
        ev = rec.get("events", {})
        sub, fin = ev.get("submitted"), ev.get("finish")
        label = f"req {uid} [{rec.get('tenant', '?')}]"
        events.append({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": uid, "args": {"name": label}})
        args = {k: rec.get(k) for k in
                ("status", "reject_reason", "error", "priority",
                 "prompt_len", "n_tokens", "ttft_s", "tpot_s")
                if rec.get(k) is not None}
        if sub is not None and fin is not None:
            events.append({"name": f"request:{rec.get('status')}",
                           "ph": "X", "ts": us(sub),
                           "dur": max((fin - sub) * _US, 0.0),
                           "pid": pid, "tid": uid, "args": args})
            # flow arrow submit -> finish (id must be unique per flow)
            events.append({"name": "request", "ph": "s", "cat": "request",
                           "id": uid, "ts": us(sub), "pid": pid,
                           "tid": uid})
            events.append({"name": "request", "ph": "f", "bp": "e",
                           "cat": "request", "id": uid, "ts": us(fin),
                           "pid": pid, "tid": uid})
        # queue_wait ends where the request got its lane (records from
        # before the ``lane`` mark: at the first sampled token)
        lane = "lane" if "lane" in ev else "prefill"
        phases = (("queue_wait", "submitted", lane),
                  ("prefill", "lane", "prefill"),
                  ("prefill_to_first_token", "prefill", "first_token"),
                  ("stream", "first_token", "finish"))
        for pname, a, b in phases:
            if a in ev and b in ev:
                events.append({"name": pname, "ph": "X", "ts": us(ev[a]),
                               "dur": max((ev[b] - ev[a]) * _US, 0.0),
                               "pid": pid, "tid": uid, "args": {}})
        for t, n in rec.get("chunks", ()):
            events.append({"name": f"chunk({int(n)})", "ph": "i",
                           "s": "t", "ts": us(t), "pid": pid, "tid": uid,
                           "args": {"n_tokens": int(n)}})
    return events


def chrome_trace(runtime=None, *, extra_events: Iterable[dict] = (),
                 metadata: Optional[Dict[str, Any]] = None) -> dict:
    """Assemble the final trace object. Events are sorted by ``ts``
    (metadata first) so per-lane timestamps are monotone — the shape
    ``bin/tputrace validate`` and the golden-shape test check."""
    events: List[dict] = []
    if runtime is not None:
        events.extend(runtime_events(runtime))
    events.extend(extra_events)
    meta = [e for e in events if e.get("ph") == "M"]
    rest = sorted((e for e in events if e.get("ph") != "M"),
                  key=lambda e: e.get("ts", 0.0))
    return {
        "traceEvents": meta + rest,
        "displayTimeUnit": "ms",
        "otherData": dict(metadata or {}),
    }


def write_chrome_trace(path: str, runtime=None, *,
                       extra_events: Iterable[dict] = (),
                       metadata: Optional[Dict[str, Any]] = None) -> dict:
    """Write the merged trace JSON to ``path``; returns the object."""
    obj = chrome_trace(runtime, extra_events=extra_events,
                       metadata=metadata)
    with open(path, "w") as f:
        json.dump(obj, f)
    return obj
