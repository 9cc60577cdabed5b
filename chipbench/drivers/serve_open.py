"""Mix kind ``serve_open``: requests sent at their due times whatever has
finished (independent users), at the fixed rate in the mix. The numbers are
the tails of time to first token — from the time a request was DUE — and of
time per output token, over every request due in the window.

The rate in the mix is found once, by ``chipbench/sweep_open.py``.
"""

from __future__ import annotations

import time
from statistics import median

from chipbench import serving, traffic
from chipbench.client import run_open_loop
from chipbench.harness import TracedStretch, in_thread, percentile


def _window(ctx, server, fe, mix, seed, seconds, trace: bool):
    """One open-loop window (plus, traced, a stretch behind it)."""
    plan = traffic.open_loop_plan(mix, seed, seconds, server.vocab)
    n_window = len(plan)
    hooks, state, trace_s = [], {}, float(mix["trace_s"])
    if trace:
        # the same arrival process goes on behind the window; the profiler
        # starts a second after the window's last due time
        tail = traffic.open_loop_plan(mix, seed + 1, trace_s + 3.0,
                                      server.vocab)
        for r in tail:
            r.due_s += seconds
        plan = plan + tail
        stretch = TracedStretch(ctx)

        def hook(at: float) -> None:
            if "start" not in state and at >= seconds + 1.0:
                state["start"] = in_thread(stretch.start)
                state["c0"] = server.counters()
            elif "stop" not in state and at >= seconds + 1.0 + trace_s \
                    and "start" in state and not state["start"].is_alive():
                state["traced"] = serving.delta(server.counters(),
                                                state["c0"])
                state["stop"] = in_thread(stretch.stop)
        hooks.append(hook)

    built0 = ctx.watch.programs()
    c0, s0 = server.counters(), server.span_totals()
    client, lateness, t0 = run_open_loop(fe, plan, hooks=hooks,
                                         drain_s=float(mix["drain_s"]))
    if trace:
        if "start" in state and "stop" not in state:   # the plan ran out first
            state["start"].join()
            state["traced"] = serving.delta(server.counters(), state["c0"])
            state["stop"] = in_thread(stretch.stop)
        for key in ("start", "stop"):
            if key in state:
                state[key].join()
    # programs built while the window's requests were in flight
    built = ctx.watch.names_since(built0)
    counters = serving.delta(server.counters(), c0)
    spans = serving.span_delta(server.span_totals(), s0)
    in_window = [t for t in client.all() if t.due_t - t0 < seconds]
    return dict(client=client, lateness=lateness[:n_window],
                in_window=in_window, built=built, counters=counters,
                spans=spans, traced=state.get("traced", {}),
                stretch=stretch if trace else None)


def _tails(mix, in_window):
    """TTFT and TPOT of every request due in the window, in ms; a request
    that was rejected, failed or did not finish inside the drain limit
    enters at the drain limit."""
    penalty = float(mix["drain_s"]) * 1e3
    ttft, tpot, met = [], [], 0
    lim = mix["limits"]
    for t in in_window:
        a = t.ttft_s() * 1e3 if t.ok else penalty
        b = (t.tpot_s() or 0.0) * 1e3 if t.ok else penalty
        ttft.append(a)
        if t.plan.max_new_tokens > 1:
            tpot.append(b)
        met += a <= lim["ttft_ms"] and b <= lim["tpot_ms"]
    return ttft, tpot, met


def bring_up(ctx):
    """The server warmed over every shape, the reference prompts through
    the real front end, warm-up traffic, then the lanes drained."""
    mix = ctx.cell["mix"]
    server = serving.Server(ctx)
    server.warm_prefill_family()
    fe = server.frontend()
    try:
        server.check_emitted_tokens(fe)
        warm_plan = traffic.closed_loop_plan(
            dict(mix, population=256, output_len=mix["warm_output_len"]),
            ctx.seed + 7, server.vocab)
        server.warm_traffic(fe, warm_plan, int(mix["warm_clients"]),
                            seconds=float(mix["warm_s"])).stop()
    except BaseException:
        fe.close(timeout=60.0)
        raise
    return server, fe


def run(ctx):
    mix = ctx.cell["mix"]
    server, fe = bring_up(ctx)
    try:
        setup_s = time.perf_counter() - ctx.t_start
        w = _window(ctx, server, fe, mix, ctx.seed, ctx.seconds, ctx.trace)
    finally:
        fe.close(timeout=60.0)

    in_window = w["in_window"]
    server.check_window(in_window, w["built"])
    ttft, tpot, met = _tails(mix, in_window)
    late_ms = [x * 1e3 for x in w["lateness"]]
    ctx.say(f"window: {len(in_window)} requests due at "
            f"{mix['arrivals']['rate_per_s']}/s; generator lateness p50 "
            f"{median(late_ms):.3f} ms p95 {percentile(late_ms, 95):.3f} ms "
            f"max {max(late_ms):.3f} ms; met both limits: {met}")
    ctx.say("percentiles (ms) " + ", ".join(
        f"p{q}: ttft {percentile(ttft, q):.1f} tpot {percentile(tpot, q):.2f}"
        for q in (50, 75, 90, 95, 99)))
    waits = [server.queue_waits[t.handle.uid] * 1e3 for t in in_window
             if t.handle.uid in server.queue_waits]
    stretch = w["stretch"]
    return {
        "correct": server.correct, "attempted": len(in_window),
        "failed": sum(1 for t in in_window if not t.ok),
        "setup_s": setup_s,
        "end_to_end": {"ttft_ms.p95": percentile(ttft, 95),
                       "tpot_ms.p95": percentile(tpot, 95)},
        "trace": stretch.summary if stretch else None,
        "outline": stretch.outline if stretch else [],
        "spans": w["spans"],
        "counters": dict(
            server.facts(), window=w["counters"], traced=w["traced"],
            window_s=ctx.seconds,
            ttft_ms_p50=median(ttft), tpot_ms_p50=median(tpot),
            slo_share=100.0 * met / len(in_window),
            queue_wait_ms=waits,
            generator_late_ms_p95=percentile(late_ms, 95)),
    }
