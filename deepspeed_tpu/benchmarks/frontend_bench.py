"""Frontend benchmark: open-loop overload with mixed priorities.

``serving_bench`` measures the data plane (decode throughput of the
chunked loop); this benchmark measures the control plane built on top of
it — :class:`~deepspeed_tpu.serving.frontend.ServingFrontend` under an
arrival process it cannot fully serve. Three phases over one tiny model:

  1. **calibrate** — a plain ``ServingEngine.run`` measures decode
     capacity (tokens/s -> requests/s at the benchmark's token budget);
  2. **parity** — the same prompts go through the frontend's streaming
     path; every streamed greedy output must be BIT-identical to the
     ``ServingEngine.run`` result (the frontend is a delivery mechanism,
     not a model change);
  3. **overload** — an OPEN-LOOP arrival process (submission times fixed
     in advance, never waiting on completions — the honest overload
     model; closed loops self-throttle) offers
     ``overload_factor``x the measured capacity, mixed priorities:
     high-priority interactive traffic without deadlines, low-priority
     traffic with deadlines that cannot all be met.

Assertions (the bench FAILS, not just reports):
  * every admitted high-priority request finishes ``done``;
  * p99 TTFT over finished high-priority requests stays under
    ``ttft_bound_s`` — shedding low-priority work is what buys this;
  * low-priority work IS shed, every shed carrying a machine-readable
    reason (``deadline_infeasible`` / ``deadline_expired`` / ...);
  * streamed greedy parity (phase 2).

Run:  python -m deepspeed_tpu.benchmarks.frontend_bench
(or the repo-root wrapper ``benchmarks/frontend_bench.py``). The tier-1
smoke wrapper is ``bin/frontend_smoke.sh`` (writes BENCH_frontend.json).
"""

from __future__ import annotations

import argparse
import json
import time

import numpy as np

from .serving_bench import _round_tree, _tiny_model


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs, np.float64), q)) if xs \
        else None


def _fused_mixed_case(tpot_gate: float = 2.0, ttft_hold_s: float = 0.25,
                      seed: int = 0) -> dict:
    """Mixed long-prompt/short-decode A/B: bucketed prefill vs fused.

    The ROADMAP item-4 acceptance workload. A handful of interactive
    short-prompt requests decode steadily while bursts of long prompts
    (prompt >> prefill chunk) arrive mid-stream. With bucketed prefill
    every long-prompt admission launches a separate wide prefill program
    that preempts the next decode chunk — the in-flight decoders' inter-
    token gaps spike (``serve/prefill_wait`` > 0, p99 TPOT blows up). With
    ``fused_prefill=True`` the same prompts are consumed as in-scan
    chunks under the chunk token budget, so decode lanes keep emitting
    every scan step and the stall never exists.

    Gates (the bench FAILS, not just reports):
      * greedy token streams bit-identical between the two modes;
      * fused p99 TPOT over the short (interactive) class is at least
        ``tpot_gate``x better than bucketed;
      * the fused drive records no ``serve/prefill_wait`` span while the
        bucketed reference waits a strictly positive time in them;
      * fused short-class TTFT p99 stays under ``ttft_hold_s`` — the
        chunked prompt path must not starve time-to-first-token.
    """
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from .. import telemetry
    from ..serving import ServingEngine
    from ..serving.scheduler import Request
    from ..telemetry.summary import phase_breakdown

    # Geometry locked by CPU A/B prototyping: the fused chunk cost is
    # invariant to prefill load while the bucketed stall scales with the
    # burst size, so long prompts must dominate (448 tokens vs chunk 8)
    # and the decode cadence must be tight (decode_chunk 1) for the p99
    # gap to be attributable to prefill preemption rather than noise.
    short_len, long_len = 8, 448
    n_short, n_long = 2, 8
    burst, inject_every = 4, 2
    max_new_short, max_new_long = 64, 2
    max_batch, decode_chunk, prefill_chunk = 6, 1, 8

    model, params = _tiny_model(max_seq_len=512)
    vocab = model.cfg.vocab_size
    engine = ds.init_inference(model, model_parameters=params,
                               dtype=jnp.float32)
    rng = np.random.default_rng(seed)
    short_prompts = [rng.integers(0, vocab, (short_len,)).astype(np.int32)
                     for _ in range(n_short)]
    long_prompts = [rng.integers(0, vocab, (long_len,)).astype(np.int32)
                    for _ in range(n_long)]

    def drive(serving):
        # shorts at t0 (the interactive class under observation), longs
        # injected in bursts while the shorts are mid-decode
        reqs = []
        for p in short_prompts:
            r = Request(prompt=p.copy(), max_new_tokens=max_new_short)
            serving.submit(r)
            reqs.append((r, "short"))
        pending = [p.copy() for p in long_prompts]
        deliveries = {}
        pumps = 0
        while serving.scheduler.has_work() or serving.chunk_in_flight \
                or pending:
            if pending and pumps % inject_every == 0:
                for _ in range(min(burst, len(pending))):
                    r = Request(prompt=pending.pop(0),
                                max_new_tokens=max_new_long)
                    serving.submit(r)
                    reqs.append((r, "long"))
            serving.pump()
            t = time.perf_counter()
            for r, _kind in reqs:
                dl = deliveries.setdefault(r.uid, [])
                n = len(r.tokens)
                if not dl or n > dl[-1][1]:
                    dl.append((t, n))
            pumps += 1
        return reqs, deliveries

    def run_side(fused: bool):
        kw = dict(fused_prefill=True, prefill_chunk=prefill_chunk) \
            if fused else {}
        serving = ServingEngine(engine=engine, max_batch=max_batch,
                                max_prompt_len=long_len, max_queue=32,
                                decode_chunk=decode_chunk, **kw)
        # warm every (n, bucket) prefill width the drive loop can hit —
        # a cold wide-prompt compile mid-drive would masquerade as a
        # multi-second stall
        for k in range(1, max_batch + 1):
            serving.run([short_prompts[i % n_short].copy()
                         for i in range(k)], max_new_tokens=4)
            serving.run([long_prompts[i % n_long].copy()
                         for i in range(k)], max_new_tokens=4)
            serving.run([short_prompts[0].copy()]
                        + [long_prompts[i % n_long].copy()
                           for i in range(k - 1)], max_new_tokens=4)
        warm = [p.copy() for p in short_prompts] \
            + [p.copy() for p in long_prompts]
        serving.run(warm, max_new_tokens=4)
        serving.run(warm, max_new_tokens=4)
        drive(serving)        # absorb the drive-pattern arena retraces
        rt = telemetry.get_runtime()
        stats_before = rt.span_stats()
        inline_before = serving.inline_prefill_tokens
        reqs, deliveries = drive(serving)
        # the measured drive's host wait on prefill programs, and the
        # prompt tokens it consumed in-scan
        stall_s = phase_breakdown(stats_before, rt.span_stats()).get(
            "serve/prefill_wait", {}).get("total_s", 0.0)
        inline = serving.inline_prefill_tokens - inline_before
        # TPOT over the interactive class: gaps between consecutive
        # token deliveries of each short request
        gaps = []
        for r, kind in reqs:
            if kind != "short":
                continue
            dl = deliveries[r.uid]
            for (t0, n0), (t1, n1) in zip(dl, dl[1:]):
                gaps.append((t1 - t0) / max(1, n1 - n0))
        ttft = {kind: [r.ttft_s for r, k in reqs if k == kind]
                for kind in ("short", "long")}
        return reqs, gaps, stall_s, inline, ttft

    b_reqs, b_gaps, bucketed_stall, _, b_ttft = run_side(fused=False)
    f_reqs, f_gaps, fused_stall, f_inline, f_ttft = run_side(fused=True)

    for (rb, _), (rf, _) in zip(b_reqs, f_reqs):
        if not np.array_equal(rb.output_ids, rf.output_ids):
            raise RuntimeError(
                "fused greedy output diverged from bucketed under the "
                f"mixed workload (uids {rb.uid}/{rf.uid})")
    p99_b, p99_f = _percentile(b_gaps, 99), _percentile(f_gaps, 99)
    improvement = p99_b / p99_f
    if improvement < tpot_gate:
        raise RuntimeError(
            f"fused p99 TPOT improvement {improvement:.2f}x under the "
            f"mixed long-prompt workload is below the {tpot_gate}x gate "
            f"(bucketed {p99_b * 1e3:.2f}ms, fused {p99_f * 1e3:.2f}ms)")
    if fused_stall > 1e-6:
        raise RuntimeError(
            f"fused drive waited {fused_stall:.4f}s on prefill programs "
            "— in-scan prompt chunks must never preempt decode launches")
    if bucketed_stall <= 0.0:
        raise RuntimeError(
            "bucketed reference never waited on a prefill — the mixed "
            "workload lost the contrast this case exists to measure")
    if f_inline <= 0:
        raise RuntimeError("fused run consumed no in-scan prompt tokens")
    f_short_ttft = _percentile(f_ttft["short"], 99)
    b_short_ttft = _percentile(b_ttft["short"], 99)
    if f_short_ttft > ttft_hold_s:
        raise RuntimeError(
            f"fused short-class TTFT p99 {f_short_ttft:.3f}s exceeds the "
            f"{ttft_hold_s}s hold")
    return {
        "geometry": {
            "short_len": short_len, "long_len": long_len,
            "n_short": n_short, "n_long": n_long,
            "long_burst": burst, "inject_every_pumps": inject_every,
            "max_new_short": max_new_short, "max_new_long": max_new_long,
            "max_batch": max_batch, "decode_chunk": decode_chunk,
            "prefill_chunk": prefill_chunk,
        },
        "greedy_parity": True,
        "tpot_gate": tpot_gate,
        "tpot_p99_improvement": round(improvement, 3),
        "tpot_p50_ms": {
            "bucketed": round(_percentile(b_gaps, 50) * 1e3, 3),
            "fused": round(_percentile(f_gaps, 50) * 1e3, 3)},
        "tpot_p99_ms": {"bucketed": round(p99_b * 1e3, 3),
                        "fused": round(p99_f * 1e3, 3)},
        "short_ttft_p99_s": {"bucketed": round(b_short_ttft, 4),
                             "fused": round(f_short_ttft, 4)},
        "long_ttft_p99_s": {
            "bucketed": round(_percentile(b_ttft["long"], 99), 4),
            "fused": round(_percentile(f_ttft["long"], 99), 4)},
        "ttft_p99_ratio": round(f_short_ttft / b_short_ttft, 3),
        "ttft_hold_s": ttft_hold_s,
        "inline_prefill_tokens": int(f_inline),
        "bucketed_stall_s": round(bucketed_stall, 4),
    }


def run_bench(n_requests: int = 48, overload_factor: float = 4.0,
              max_new_tokens: int = 16, max_batch: int = 4,
              prompt_len: int = 16, decode_chunk: int = 4,
              high_fraction: float = 0.25, ttft_bound_s: float = 10.0,
              seed: int = 0, model=None, params=None,
              timeout_s: float = 300.0, trace_out: str = None,
              metrics_port: int = 0, slo: bool = True,
              fused_mixed: bool = True) -> dict:
    import urllib.request

    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from .. import telemetry
    from ..telemetry.exposition import MetricsServer, parse_prometheus_text
    from ..telemetry.mfu import mfu_report
    from ..telemetry.slo import SLOEngine, default_slos
    from ..telemetry.summary import phase_breakdown
    from ..serving import ServingEngine
    from ..serving.frontend import (AdmissionConfig, BackendWatchdog,
                                    HealthMonitor, PRIORITY_HIGH,
                                    PRIORITY_LOW, ServingFrontend)

    telemetry.enable()

    if model is None:
        model, params = _tiny_model()
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(seed)
    lens = rng.integers(min(4, prompt_len), prompt_len + 1, max_batch * 2)
    prompts = [rng.integers(0, vocab, (int(n),)).astype(np.int32)
               for n in lens]
    engine = ds.init_inference(model, model_parameters=params,
                               dtype=jnp.float32)

    # ---- phase 1: calibrate capacity on the plain engine loop ----------
    reference = ServingEngine(engine=engine, max_batch=max_batch,
                              max_prompt_len=prompt_len,
                              decode_chunk=decode_chunk,
                              max_queue=max(len(prompts), 8))
    reference.run(list(prompts), max_new_tokens=max_new_tokens)  # warm
    reference.run(list(prompts), max_new_tokens=max_new_tokens)
    t0 = time.perf_counter()
    ref_results = reference.run(list(prompts),
                                max_new_tokens=max_new_tokens)
    cal_dt = time.perf_counter() - t0
    cal_tokens = sum(len(r.tokens) for r in ref_results)
    capacity_tps = cal_tokens / cal_dt
    capacity_rps = capacity_tps / max_new_tokens
    offered_rps = overload_factor * capacity_rps

    # ---- phase 2: streaming parity through the frontend ----------------
    fe_engine = ServingEngine(engine=engine, max_batch=max_batch,
                              max_prompt_len=prompt_len,
                              decode_chunk=decode_chunk,
                              max_queue=max(n_requests, 8))
    # warm every program the frontend can hit before it owns the engine:
    # batched prefill compiles per (n, bucket), and which n the driver
    # sees depends on arrival timing — a cold (2, 16) prefill mid-overload
    # would charge ~1 s of XLA compile to some request's TTFT. The k-sized
    # runs compile every prefill width; the extra full runs absorb the
    # decode-chunk program's arena-metadata retraces (serving_bench's
    # double-warm).
    for k in range(1, max_batch + 1):
        fe_engine.run(list(prompts[:k]), max_new_tokens=max_new_tokens)
    fe_engine.run(list(prompts), max_new_tokens=max_new_tokens)
    frontend = ServingFrontend(
        fe_engine,
        admission=AdmissionConfig(max_pending=n_requests + 8),
        trace_keep_last=n_requests + len(prompts) + 8)
    # /metrics + /healthz + /readyz for the whole serving window: the
    # acceptance check is a LIVE scrape while the bench is serving, not a
    # post-hoc render. Watchdog heartbeats are tiny jitted ops on the
    # same backend the engine uses.
    watchdog = BackendWatchdog(interval_s=2.0, timeout_s=60.0)
    watchdog.start()
    # SLO burn-rate engine fed by every terminal trace; served live at
    # /slo and exported as slo/* gauges on the next /metrics render
    slo_engine = None
    if slo:
        slo_engine = SLOEngine(
            default_slos(ttft_threshold_s=ttft_bound_s),
            windows_s=(10.0, 60.0)).attach(frontend.tracing)
    health = HealthMonitor(frontend=frontend, watchdog=watchdog)
    metrics_server = MetricsServer(
        runtime=telemetry.get_runtime(), tracelog=frontend.tracing,
        gauges_fn=lambda: fe_engine.metrics.snapshot(
            fe_engine.scheduler.queue_depth, fe_engine.kv.occupancy),
        health=health, slo=slo_engine, port=metrics_port)
    handles = [frontend.submit(p, max_new_tokens=max_new_tokens)
               for p in prompts]
    for h, ref in zip(handles, ref_results):
        streamed = list(h)                       # the blocking iterator
        if h.status != "done":
            raise RuntimeError(
                f"parity request uid={h.uid} ended {h.status}, not done")
        if streamed != h.tokens or not np.array_equal(
                h.output_ids, ref.output_ids):
            raise RuntimeError(
                "streamed greedy output diverged from ServingEngine.run "
                f"for uid={h.uid} — the frontend must be bit-identical")
    parity = True
    # the parity pass also warmed the frontend's throughput estimator, so
    # the overload phase sheds against a measured rate from step one

    # ---- phase 3: open-loop overload with mixed priorities -------------
    # low-priority deadline: roughly the unloaded service time of a few
    # requests — generous when idle, infeasible at overload_factor x
    low_deadline_s = 4.0 / capacity_rps
    interval = 1.0 / offered_rps
    n_high = 0
    load_handles = []
    stats_before = telemetry.get_runtime().span_stats()
    t_start = time.perf_counter()
    for i in range(n_requests):
        # open loop: the i-th arrival is scheduled at t_start + i*interval
        # regardless of how far behind the server is
        target = t_start + i * interval
        delay = target - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        high = (i % max(1, round(1 / high_fraction))) == 0
        n_high += int(high)
        n = int(rng.integers(min(4, prompt_len), prompt_len + 1))
        prompt = rng.integers(0, vocab, (n,)).astype(np.int32)
        h = frontend.submit(
            prompt, max_new_tokens=max_new_tokens,
            priority=PRIORITY_HIGH if high else PRIORITY_LOW,
            tenant="interactive" if high else "bulk",
            slo_ttft_s=ttft_bound_s if high else None,
            deadline_s=None if high else low_deadline_s)
        load_handles.append((h, high))
    deadline = time.monotonic() + timeout_s
    for h, _ in load_handles:
        h.result(timeout=max(0.1, deadline - time.monotonic()))
    wall_s = time.perf_counter() - t_start

    # ---- live self-scrape: a real HTTP GET against the running server,
    # parsed by the same golden-format parser the tests use. Must happen
    # BEFORE frontend.close() — /readyz flips 503 once the driver stops.
    with urllib.request.urlopen(f"{metrics_server.url}/metrics",
                                timeout=10) as resp:
        scrape_text = resp.read().decode("utf-8")
    parsed = parse_prometheus_text(scrape_text)
    ttft_family = "dstpu_frontend_ttft_seconds"
    arena_gauge = "dstpu_serve_arena_headroom_bytes"
    for required in (ttft_family, arena_gauge):
        if required not in parsed["samples"]:
            raise RuntimeError(
                f"/metrics scrape is missing {required} — the exposition "
                "wiring regressed")
    ttft_quantiles = {
        labels.get("quantile"): v
        for labels, v in parsed["samples"][ttft_family]
        if "quantile" in labels}
    with urllib.request.urlopen(f"{metrics_server.url}/readyz",
                                timeout=10) as resp:
        readyz_code = resp.status
    if readyz_code != 200:
        raise RuntimeError(f"/readyz answered {readyz_code} while serving")
    # live /tenants fetch + tenant-labelled series in the same scrape:
    # parity traffic lands under "default", overload traffic under
    # "interactive"/"bulk" — all three must round-trip through HTTP
    with urllib.request.urlopen(f"{metrics_server.url}/tenants",
                                timeout=10) as resp:
        tenants_payload = json.loads(resp.read().decode("utf-8"))
    if tenants_payload.get("schema") != "dstpu-tenants-v1":
        raise RuntimeError(
            f"/tenants schema {tenants_payload.get('schema')!r} != "
            "dstpu-tenants-v1")
    seen_tenants = set(tenants_payload.get("tenants", {}))
    if not {"interactive", "bulk", "default"} <= seen_tenants:
        raise RuntimeError(
            f"/tenants is missing expected tenants: saw {sorted(seen_tenants)}")
    goodput_family = "dstpu_frontend_goodput_fraction"
    labelled = {labels.get("tenant")
                for labels, _ in parsed["samples"].get(goodput_family, [])
                if "tenant" in labels}
    if not {"interactive", "bulk", "default"} <= labelled:
        raise RuntimeError(
            f"/metrics carries no per-tenant {goodput_family} series "
            f"(saw tenant labels {sorted(labelled)})")
    # live /slo fetch: the endpoint evaluates the rolling windows on GET
    # and exports slo/* gauges — verified by a second /metrics scrape
    slo_block = None
    if slo_engine is not None:
        with urllib.request.urlopen(f"{metrics_server.url}/slo",
                                    timeout=10) as resp:
            slo_payload = json.loads(resp.read().decode("utf-8"))
        for key in ("schema", "slos", "max_burn_rate", "windows_s",
                    "n_samples"):
            if key not in slo_payload:
                raise RuntimeError(f"/slo payload is missing '{key}'")
        if not slo_payload["slos"]:
            raise RuntimeError("/slo reported no SLOs")
        with urllib.request.urlopen(f"{metrics_server.url}/metrics",
                                    timeout=10) as resp:
            rescrape = parse_prometheus_text(
                resp.read().decode("utf-8"))
        if not any(fam.startswith("dstpu_slo_")
                   for fam in rescrape["samples"]):
            raise RuntimeError(
                "/metrics carries no slo/* gauges after a /slo "
                "evaluation — the burn-rate export regressed")
        worst = max(slo_payload["slos"],
                    key=lambda s: s["worst_burn_rate"])
        slo_block = {
            "endpoint_ok": 1.0,
            "n_slos": len(slo_payload["slos"]),
            "n_samples": slo_payload["n_samples"],
            "worst_burn_rate": round(worst["worst_burn_rate"], 4),
            "worst_slo": worst["name"],
            "worst_window_s": worst["worst_window_s"],
            "budget_remaining_min": round(min(
                w["budget_remaining"] for s in slo_payload["slos"]
                for w in s["windows"].values()), 4),
            "windows_s": slo_payload["windows_s"],
        }
    metrics_scrape = {
        "url": metrics_server.url,
        "n_families": len(parsed["samples"]),
        "n_samples": sum(len(v) for v in parsed["samples"].values()),
        "ttft_quantiles_s": {q: round(v, 4)
                             for q, v in sorted(ttft_quantiles.items())},
        "arena_headroom_bytes": parsed["samples"][arena_gauge][0][1],
        "readyz": readyz_code,
        "watchdog": watchdog.state(),
    }
    frontend.close()
    watchdog.stop()
    metrics_server.stop()
    # overload-phase-only span breakdown (telemetry aggregate deltas;
    # the engine-driver thread's serve/* spans land in their own lane)
    overload_phases = phase_breakdown(
        stats_before, telemetry.get_runtime().span_stats(), wall_s=wall_s)
    # MFU for the decode-chunk program over the overload window. Costed
    # AFTER all serving work — cost analysis pays one extra XLA compile
    # (see ServingEngine.estimate_chunk_cost)
    mfu = None
    cost = fe_engine.estimate_chunk_cost()
    if cost is not None:
        n_chunks = int(overload_phases.get("serve/chunk_launch",
                                           {}).get("count", 0))
        mfu = mfu_report(flops_per_call=cost["flops_per_chunk"],
                         calls=n_chunks, wall_s=wall_s,
                         peak_flops=cost["peak_flops_per_device"],
                         label="decode_chunk@overload")
        mfu["flops_per_token"] = cost["flops_per_token"]
        mfu["scan_body_counted_once"] = cost["scan_body_counted_once"]
    # HBM accounting: same after-the-audit placement as cost analysis
    hbm = fe_engine.estimate_hbm()
    if trace_out:
        # one Perfetto file: engine/driver thread lanes + per-request
        # frontend lanes with submit->finish flow arrows
        frontend.tracing.export_chrome(trace_out)

    # ---- fused chunked-prefill A/B under the mixed long-prompt
    # workload (own tiny model with a 512-token context; independent of
    # the overload phase above)
    fused_block = _fused_mixed_case(seed=seed) if fused_mixed else None

    traces = {t["uid"]: t
              for t in frontend.tracing.to_json()["requests"]}
    high_statuses = [h.status for h, hi in load_handles if hi]
    low_statuses = [h.status for h, hi in load_handles if not hi]
    shed_reasons = sorted({
        h.reject_reason for h, hi in load_handles
        if not hi and h.status == "rejected"})
    n_shed = sum(s == "rejected" for s in low_statuses)
    ttfts_high = [traces[h.uid]["ttft_s"] for h, hi in load_handles
                  if hi and h.status == "done"
                  and traces.get(h.uid, {}).get("ttft_s") is not None]
    p50_high = _percentile(ttfts_high, 50)
    p99_high = _percentile(ttfts_high, 99)

    if not all(s == "done" for s in high_statuses):
        raise RuntimeError(
            "admitted high-priority requests did not all finish: "
            f"{sorted(set(high_statuses))}")
    if n_shed == 0:
        raise RuntimeError(
            f"no low-priority request was shed at {overload_factor}x "
            "offered load — admission control is not shedding")
    if any(r is None for r in shed_reasons):
        raise RuntimeError("a shed request carried no rejection reason")
    if p99_high is None or p99_high > ttft_bound_s:
        raise RuntimeError(
            f"high-priority p99 TTFT {p99_high}s exceeds the "
            f"{ttft_bound_s}s bound under overload")

    return {
        "n_requests": n_requests,
        "n_high": n_high,
        "n_low": n_requests - n_high,
        "overload_factor": overload_factor,
        "max_new_tokens": max_new_tokens,
        "max_batch": max_batch,
        "decode_chunk": decode_chunk,
        "greedy_streaming_parity": parity,
        "capacity_tokens_per_s": round(capacity_tps, 2),
        "capacity_requests_per_s": round(capacity_rps, 3),
        "offered_requests_per_s": round(offered_rps, 3),
        "low_deadline_s": round(low_deadline_s, 4),
        "overload_wall_s": round(wall_s, 4),
        "high_statuses": {s: int(n) for s, n in
                          zip(*np.unique(high_statuses,
                                         return_counts=True))},
        "low_statuses": {s: int(n) for s, n in
                         zip(*np.unique(low_statuses, return_counts=True))},
        "low_shed": n_shed,
        "shed_reasons": shed_reasons,
        "ttft_bound_s": ttft_bound_s,
        "high_ttft_p50_s": round(p50_high, 4) if p50_high else None,
        "high_ttft_p99_s": round(p99_high, 4) if p99_high else None,
        "frontend_snapshot": frontend.tracing.snapshot(),
        "frontend_stats": frontend.stats(),
        # overload-phase-only span breakdown + decode-chunk MFU estimate
        "phase_breakdown": _round_tree(overload_phases),
        "mfu": _round_tree(mfu) if mfu else None,
        "hbm": _round_tree(hbm) if hbm else None,
        "metrics_scrape": metrics_scrape,
        "slo": slo_block,
        # fused chunked prefill vs bucketed under mixed long prompts
        # (ROADMAP item 4 acceptance: p99 TPOT >= 2x, stall ~ 0)
        "fused_mixed": fused_block,
        "tenant_goodput": {
            "endpoint_ok": 1.0,
            "labelled_series_ok": 1.0,
            "n_tenants": tenants_payload["n_tenants"],
            "tenants": _round_tree(tenants_payload["tenants"]),
        },
        "trace_file": trace_out,
    }


def main(argv=None):
    from ..utils.platform import enable_compile_cache
    enable_compile_cache()       # before any compile
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-requests", type=int, default=48)
    ap.add_argument("--overload-factor", type=float, default=4.0)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--decode-chunk", type=int, default=4)
    ap.add_argument("--high-fraction", type=float, default=0.25)
    ap.add_argument("--ttft-bound-s", type=float, default=10.0)
    ap.add_argument("--fused-mixed", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the fused-vs-bucketed chunked-prefill A/B "
                    "under the mixed long-prompt workload "
                    "(--no-fused-mixed skips)")
    ap.add_argument("--slo", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="wire an SLO burn-rate engine to the frontend "
                    "tracelog and self-fetch /slo live (--no-slo skips)")
    ap.add_argument("--metrics-port", type=int, default=0,
                    help="bind /metrics + health endpoints to this port "
                    "for the duration of the bench (0 = ephemeral; the "
                    "bench self-scrapes either way)")
    ap.add_argument("--json-out", type=str, default=None,
                    help="also write the result dict to this JSON file")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write a Perfetto-loadable Chrome trace "
                    "(engine lanes + per-request flow lanes) to this "
                    "path (inspect with bin/tputrace)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    # the whole bench runs under a strict LockAuditor: every lock the
    # serving stack constructs during the window is order-graphed, an
    # inversion raises LockOrderError mid-bench, and the report lands in
    # the JSON as `lock_audit` (obs_smoke gates enabled + zero
    # violations; deliberately NOT a watched benchdiff metric)
    from ..analysis import locks
    auditor = locks.install_auditor(locks.LockAuditor(strict=True))
    try:
        result = run_bench(n_requests=args.n_requests,
                           overload_factor=args.overload_factor,
                           max_new_tokens=args.max_new_tokens,
                           max_batch=args.max_batch,
                           prompt_len=args.prompt_len,
                           decode_chunk=args.decode_chunk,
                           high_fraction=args.high_fraction,
                           ttft_bound_s=args.ttft_bound_s,
                           seed=args.seed, trace_out=args.trace_out,
                           metrics_port=args.metrics_port, slo=args.slo,
                           fused_mixed=args.fused_mixed)
    finally:
        locks.uninstall_auditor()
    auditor.export_gauges()
    result["lock_audit"] = auditor.report()
    print(json.dumps(result, indent=2))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(result, f, indent=2)
    return result


if __name__ == "__main__":
    main()
