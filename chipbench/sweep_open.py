#!/usr/bin/env python3
"""chipbench/sweep_open.py — find the knee behind an open-loop mix's rate.

    python3 chipbench/sweep_open.py --config <config> --mix <mix> --rates 0.6,0.9,1.2 [--seed n] [--seconds s]

Not a run of a cell and no measurement of one: it prints no result line. One
process brings the server up as the ``serve_open`` driver does, then runs one
open-loop window of the mix at each rate (the lanes emptied between them) and
prints the share of requests that met the mix's ``limits``. The knee is the
highest rate at which 90 % met them with nothing left in flight at the drain
limit; four fifths of it is written into the mix's file by hand, once, and
the table goes into PERF.md. ``--rehearsal`` runs it on the CPU at toy size.
"""

from __future__ import annotations

import argparse
import os
import sys
import time
from statistics import median

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import spec  # noqa: E402
from chipbench.harness import percentile  # noqa: E402
from chipbench.run import open_ctx  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", required=True)
    ap.add_argument("--rates", required=True,
                    help="requests per second, comma-separated")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    # a cell that is in no BENCHMARK.json: this config under this mix
    cell = spec.load_cell(
        dict(bench, workloads=[{"name": "sweep", "config": args.config,
                                "traffic": args.mix, "chips": 1,
                                "why": "sweep"}]),
        "sweep", rehearsal=args.rehearsal)
    mix = cell["mix"]
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])
    ctx = open_ctx(cell, seed=args.seed, seconds=seconds, trace=False,
                   rehearsal=args.rehearsal)
    if isinstance(ctx, int):
        return ctx
    driver = spec.load_module(spec.find_driver(bench, mix["kind"]))
    server, fe = driver.bring_up(ctx)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            at = dict(mix, arrivals=dict(mix["arrivals"], rate_per_s=rate))
            w = driver._window(ctx, server, fe, at, ctx.seed, seconds, False)
            ttft, tpot, met = driver._tails(at, w["in_window"])
            client = w["client"]
            backlog = len(client.active)
            end = time.perf_counter() + 180.0     # empty before the next
            while client.active and time.perf_counter() < end:
                client.sweep()
                time.sleep(0.01)
            ctx.say(f"SWEEP rate {rate}/s: {len(ttft)} due, "
                    f"{100.0 * met / len(ttft):.1f}% met {mix['limits']}, "
                    f"unfinished or failed "
                    f"{sum(1 for t in w['in_window'] if not t.ok)}, in "
                    f"flight {mix['drain_s']}s after the last due time "
                    f"{backlog}; ttft p50 {median(ttft):.0f} p95 "
                    f"{percentile(ttft, 95):.0f} ms, tpot p50 "
                    f"{median(tpot):.1f} p95 {percentile(tpot, 95):.1f} ms; "
                    f"{w['counters']['tokens_out'] / seconds:.0f} tokens/s; "
                    f"programs built inside it: {w['built']}; correct so "
                    f"far: {server.correct}")
    finally:
        fe.close(timeout=60.0)
    return 0


if __name__ == "__main__":
    sys.exit(main())
