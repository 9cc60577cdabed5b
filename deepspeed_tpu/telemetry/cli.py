"""``bin/tputrace`` — inspect and validate captured Chrome traces.

Subcommands::

    tputrace summary <trace.json> [--top N]   top-N spans, counters,
                                              retrace table
    tputrace validate <trace.json>            golden-shape check
                                              (exit 0 ok / 1 malformed)
    tputrace convert <tracelog.json> -o OUT   render a frontend
                                              ``TraceLog.dump`` file as
                                              a Perfetto-loadable trace
    tputrace journey <trace.json> [TRACE_ID]  fleet journeys in a trace:
                                              table of all, or one
                                              journey's events in full;
                                              --validate gates each
                                              journey's connectedness
                                              (exit 1 on problems)

Stdlib-only on purpose: like ``bin/tracelint``, the launcher installs a
synthetic parent package so this file imports in milliseconds without
executing the JAX-heavy ``deepspeed_tpu/__init__``.
"""

from __future__ import annotations

import argparse
import json
import sys
from typing import Any, Dict, List, Tuple

from .export import chrome_trace, request_trace_events
from .journey import PID_JOURNEYS, summarize_journeys, validate_journeys
from .memory import format_bytes

_NUMBER = (int, float)

# counter-track names that are byte-valued memory gauges (HBM arena,
# headroom, live-buffer census) — summarized in their own section
_MEMORYISH = ("bytes", "hbm", "headroom")


def _memoryish(name: str) -> bool:
    low = name.lower()
    return any(k in low for k in _MEMORYISH)


def _load(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


# --------------------------------------------------------------- validate

def validate_trace(obj: Any) -> List[str]:
    """Structural checks mirroring what Perfetto needs: returns a list
    of problems (empty = valid). Checked: top-level shape, per-phase
    required keys, numeric non-negative ts/dur, and monotone event
    order per (pid, tid) lane (file order — the exporter sorts)."""
    problems: List[str] = []
    if not isinstance(obj, dict) or not isinstance(
            obj.get("traceEvents"), list):
        return ["top level must be an object with a 'traceEvents' list"]
    last_ts: Dict[Tuple[Any, Any], float] = {}
    for i, ev in enumerate(obj["traceEvents"]):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            problems.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if not ph:
            problems.append(f"{where}: missing 'ph'")
            continue
        if "name" not in ev:
            problems.append(f"{where}: missing 'name'")
        if ph == "M":
            continue
        for key in ("ts", "pid", "tid"):
            if not isinstance(ev.get(key), _NUMBER):
                problems.append(f"{where} (ph={ph}): missing/non-numeric "
                                f"'{key}'")
        ts = ev.get("ts")
        if isinstance(ts, _NUMBER):
            if ts < 0:
                problems.append(f"{where}: negative ts")
            lane = (ev.get("pid"), ev.get("tid"))
            if ts < last_ts.get(lane, float("-inf")):
                problems.append(f"{where}: ts not monotone within "
                                f"pid/tid lane {lane}")
            last_ts[lane] = ts
        if ph == "X" and not (isinstance(ev.get("dur"), _NUMBER)
                              and ev["dur"] >= 0):
            problems.append(f"{where}: 'X' event needs numeric dur >= 0")
        if ph == "i" and ev.get("s") not in ("t", "p", "g", None):
            problems.append(f"{where}: instant scope 's' must be t/p/g")
    return problems


def cmd_validate(args) -> int:
    try:
        obj = _load(args.trace)
    except (OSError, ValueError) as exc:
        print(f"tputrace: cannot read {args.trace}: {exc}",
              file=sys.stderr)
        return 1
    problems = validate_trace(obj)
    if problems:
        for p in problems[:50]:
            print(f"INVALID  {p}", file=sys.stderr)
        if len(problems) > 50:
            print(f"... and {len(problems) - 50} more", file=sys.stderr)
        return 1
    n = len(obj["traceEvents"])
    print(f"OK  {args.trace}: {n} events, Perfetto-loadable shape")
    return 0


# ---------------------------------------------------------------- summary

def summarize_trace(obj: Dict[str, Any]) -> Dict[str, Any]:
    """Aggregate a trace file back into tables: per-span-name totals,
    final counter values, instant counts, and the retrace table (instant
    events carrying a compile/retrace marker, with their args)."""
    spans: Dict[str, Dict[str, float]] = {}
    counters: Dict[str, float] = {}
    peaks: Dict[str, float] = {}
    instants: Dict[str, int] = {}
    retraces: List[Dict[str, Any]] = []
    t_min, t_max = float("inf"), float("-inf")
    for ev in obj.get("traceEvents", ()):
        ph = ev.get("ph")
        ts = ev.get("ts")
        if isinstance(ts, _NUMBER):
            t_min = min(t_min, ts)
            t_max = max(t_max, ts + (ev.get("dur") or 0.0))
        if ph == "X":
            st = spans.setdefault(ev.get("name", "?"), {
                "count": 0, "total_us": 0.0, "max_us": 0.0})
            dur = float(ev.get("dur") or 0.0)
            st["count"] += 1
            st["total_us"] += dur
            st["max_us"] = max(st["max_us"], dur)
        elif ph == "C":
            for k, v in (ev.get("args") or {}).items():
                if isinstance(v, _NUMBER):
                    counters[k] = float(v)
                    peaks[k] = max(peaks.get(k, float("-inf")), float(v))
        elif ph == "i":
            name = ev.get("name", "?")
            instants[name] = instants.get(name, 0) + 1
            if "retrace" in name or "compile" in name:
                retraces.append({"name": name, "ts_us": ts,
                                 "args": ev.get("args") or {}})
    wall_us = (t_max - t_min) if t_max >= t_min else 0.0
    return {"spans": spans, "counters": counters,
            "counter_peaks": peaks, "instants": instants,
            "retraces": retraces, "wall_us": wall_us,
            "n_events": len(obj.get("traceEvents", ()))}


def cmd_summary(args) -> int:
    try:
        obj = _load(args.trace)
    except (OSError, ValueError) as exc:
        print(f"tputrace: cannot read {args.trace}: {exc}",
              file=sys.stderr)
        return 1
    s = summarize_trace(obj)
    print(f"{args.trace}: {s['n_events']} events over "
          f"{s['wall_us'] / 1e3:.1f} ms")
    ranked = sorted(s["spans"].items(),
                    key=lambda kv: -kv[1]["total_us"])[:args.top]
    if ranked:
        print(f"\ntop {len(ranked)} spans by total time:")
        print(f"  {'span':<32} {'count':>7} {'total ms':>10} "
              f"{'mean us':>9} {'max us':>9}")
        for name, st in ranked:
            mean = st["total_us"] / st["count"] if st["count"] else 0.0
            print(f"  {name:<32} {st['count']:>7} "
                  f"{st['total_us'] / 1e3:>10.2f} {mean:>9.1f} "
                  f"{st['max_us']:>9.1f}")
    if s["counters"]:
        print("\ncounters (final value):")
        for name in sorted(s["counters"]):
            print(f"  {name:<40} {s['counters'][name]:>14g}")
    mem = sorted(n for n in s["counters"] if _memoryish(n))
    if mem:
        print("\nmemory gauge tracks (final / peak):")
        for name in mem:
            final = s["counters"][name]
            peak = s["counter_peaks"].get(name, final)
            print(f"  {name:<40} {format_bytes(final):>12} / "
                  f"{format_bytes(peak):>12}")
    if s["retraces"]:
        print(f"\nretrace/compile events ({len(s['retraces'])}):")
        for r in s["retraces"][:args.top]:
            extra = " ".join(f"{k}={v}" for k, v in r["args"].items())
            print(f"  @{(r['ts_us'] or 0.0) / 1e3:>10.2f} ms  "
                  f"{r['name']}  {extra}")
        if len(s["retraces"]) > args.top:
            print(f"  ... and {len(s['retraces']) - args.top} more")
    elif s["instants"]:
        print("\nno retrace/compile instants recorded")
    return 0


# ---------------------------------------------------------------- journey

def cmd_journey(args) -> int:
    try:
        obj = _load(args.trace)
    except (OSError, ValueError) as exc:
        print(f"tputrace: cannot read {args.trace}: {exc}",
              file=sys.stderr)
        return 1
    rc = 0
    if args.validate:
        problems = validate_journeys(obj, pid=args.pid)
        for p in problems[:50]:
            print(f"FAIL: {p}", file=sys.stderr)
        if len(problems) > 50:
            print(f"... and {len(problems) - 50} more", file=sys.stderr)
        if problems:
            return 1
    journeys = summarize_journeys(obj, pid=args.pid)
    if args.trace_id:
        wanted = [j for j in journeys
                  if str(j["trace_id"]).startswith(args.trace_id)]
        if not wanted:
            print(f"tputrace: no journey matching '{args.trace_id}' in "
                  f"{args.trace}", file=sys.stderr)
            return 1
        for j in wanted:
            print(f"journey {j['trace_id']}  uid={j['uid']}  "
                  f"status={j['status']}  reroutes={j['n_reroutes']}")
            print(f"  replicas: {' -> '.join(j['replicas']) or '-'}")
            print(f"  chunks: {j['n_chunks']}  tokens: {j['n_tokens']}")
            evs = [e for e in obj.get("traceEvents", ())
                   if (e.get("args") or {}).get("trace_id")
                   == j["trace_id"] and e.get("pid") == args.pid]
            for e in sorted(evs, key=lambda e: e.get("ts", 0.0)):
                extra = " ".join(
                    f"{k}={v}" for k, v in (e.get("args") or {}).items()
                    if k != "trace_id" and v is not None)
                print(f"  @{e.get('ts', 0.0) / 1e3:>10.2f} ms  "
                      f"[{e.get('ph')}] {e.get('name')}  {extra}")
        return rc
    if not journeys:
        print(f"{args.trace}: no journey events (pid {args.pid})")
        return rc
    print(f"{args.trace}: {len(journeys)} journeys")
    print(f"  {'trace_id':<18} {'uid':>5} {'status':<9} {'chunks':>6} "
          f"{'tokens':>6} {'rr':>3}  replicas")
    for j in journeys:
        print(f"  {j['trace_id']:<18} {str(j['uid']):>5} "
              f"{str(j['status']):<9} {j['n_chunks']:>6} "
              f"{j['n_tokens']:>6} {j['n_reroutes']:>3}  "
              f"{' -> '.join(j['replicas']) or '-'}")
    if args.validate:
        print("journeys OK: every journey connected under one trace_id")
    return rc


# ---------------------------------------------------------------- convert

def cmd_convert(args) -> int:
    try:
        obj = _load(args.tracelog)
    except (OSError, ValueError) as exc:
        print(f"tputrace: cannot read {args.tracelog}: {exc}",
              file=sys.stderr)
        return 1
    trace = chrome_trace(None, extra_events=request_trace_events(obj),
                         metadata={"source": args.tracelog})
    with open(args.out, "w") as f:
        json.dump(trace, f)
    print(f"wrote {args.out}: {len(trace['traceEvents'])} events "
          f"(open at https://ui.perfetto.dev)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="tputrace",
        description="Summarize, validate, and convert telemetry traces.")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("summary", help="top-N spans, counters, retraces")
    p.add_argument("trace")
    p.add_argument("--top", type=int, default=15)
    p.set_defaults(fn=cmd_summary)
    p = sub.add_parser("validate", help="check Perfetto-loadable shape")
    p.add_argument("trace")
    p.set_defaults(fn=cmd_validate)
    p = sub.add_parser("convert",
                       help="TraceLog dump -> Chrome trace JSON")
    p.add_argument("tracelog")
    p.add_argument("-o", "--out", required=True)
    p.set_defaults(fn=cmd_convert)
    p = sub.add_parser("journey",
                       help="list/inspect/validate fleet journeys")
    p.add_argument("trace")
    p.add_argument("trace_id", nargs="?", default=None,
                   help="show one journey (prefix match) in full")
    p.add_argument("--validate", action="store_true",
                   help="gate journey connectedness, incl. pod-hop "
                        "links on hierarchy traces (exit 1 on problems)")
    p.add_argument("--pid", type=int, default=PID_JOURNEYS)
    p.set_defaults(fn=cmd_journey)
    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
