"""Mix kind ``serve_closed``: N clients that each send their next request when
their last one ends. The lanes are full when the window opens and stay full;
the number is output tokens delivered to clients per second of the window.
"""

from __future__ import annotations

import time
from statistics import median

from chipbench import serving, traffic
from chipbench.harness import TracedStretch, in_thread, percentile


def run(ctx):
    mix = ctx.cell["mix"]
    server = serving.Server(ctx)
    server.warm_prefill_family()
    plan = traffic.closed_loop_plan(mix, ctx.seed, server.vocab)
    fe = server.frontend()
    try:
        server.check_emitted_tokens(fe)
        # warm-up traffic: the mix's own requests, from the far end of its
        # cycle, for a fixed time (the same set-up from every seed); the
        # lanes are full when it ends and stay so
        loop = server.warm_traffic(fe, plan[::-1], int(mix["clients"]),
                                   seconds=float(mix["warm_s"]))
        loop.plan, loop.taken = plan, 0
        client = loop.client

        # ---- the measured window: lanes left full, nothing drained. It
        # opens and closes ON a delivery of tokens (the engine delivers a
        # chunk for every lane at once, ~1% of a window's tokens): whole
        # chunks over the time they took, as the trainer counts whole steps
        def next_delivery():
            seen = len(client.token_log)
            loop.run_until(lambda: len(client.token_log) > seen)
            return client.token_log[-1][0]

        next_delivery()
        setup_s = time.perf_counter() - ctx.t_start
        built0 = ctx.watch.programs()
        c0, s0 = server.counters(), server.span_totals()
        t0 = client.token_log[-1][0]
        loop.run_until(lambda: time.perf_counter() >= t0 + ctx.seconds)
        t1 = next_delivery()
        built = ctx.watch.names_since(built0)
        counters = serving.delta(server.counters(), c0)
        spans = serving.span_delta(server.span_totals(), s0)

        # ---- the traced stretch: the same load, a few seconds more
        summary, outline, traced, ts0, ts1 = None, [], {}, 0.0, 0.0
        if ctx.trace:
            stretch = TracedStretch(ctx)
            starter = in_thread(stretch.start)    # the load keeps going
            loop.run_until(lambda: not starter.is_alive())
            tc0, ts0 = server.counters(), time.perf_counter()
            loop.run_for(float(mix["trace_s"]))
            traced, ts1 = (serving.delta(server.counters(), tc0),
                           time.perf_counter())
            stopper = in_thread(stretch.stop)
            loop.run_until(lambda: not stopper.is_alive())
            summary, outline = stretch.summary, stretch.outline
        loop.stop()
    finally:
        fe.close(timeout=60.0)

    ended = [t for t in client.finished if t0 < t.done_t <= t1]
    server.check_window(ended, built)
    tokens = client.tokens_between(t0, t1)      # received in (t0, t1]
    window_s = t1 - t0
    tokens_per_s = tokens / window_s
    ttfts = [t.ttft_s() for t in ended if t.ttft_s() is not None]
    def kv_live_mean(a, b):
        kv = [live for at, live in client.kv_samples if a < at <= b]
        return sum(kv) / len(kv) if kv else None

    ctx.say(f"window {window_s:.3f}s: {tokens} tokens to clients, "
            f"{len(ended)} requests ended, {counters['chunks']} chunks, "
            f"server rejected {counters['rejected']}")
    first_tokens = sum(1 for t in client.all()
                       if t.first_t is not None and t0 < t.first_t <= t1)
    return {
        "correct": server.correct, "attempted": len(ended),
        "failed": sum(1 for t in ended if not t.ok),
        "setup_s": setup_s,
        "end_to_end": {"serve_tokens_per_s": tokens_per_s},
        "trace": summary, "outline": outline, "spans": spans,
        "counters": dict(
            server.facts(), window=counters, traced=traced,
            window_s=window_s, client_tokens=tokens,
            first_tokens=first_tokens,
            kv_live_mean=kv_live_mean(t0, t1),
            kv_live_mean_traced=kv_live_mean(ts0, ts1),
            ttft_ms_p50=median(ttfts) * 1e3 if ttfts else None,
            ttft_ms_p95=percentile(ttfts, 95) * 1e3 if ttfts else None,
            requests_per_s=len(ended) / window_s),
    }
