"""Fleet serving benchmark: replica routing, tp=2, disaggregation,
cross-host transport + live migration, the fleet observability plane,
crash observability, and elastic recovery.

Eight cases over one tiny model (CPU-runnable, smoke-sized):

  * router scaling — a 2-replica :class:`FleetRouter` against a
    1-replica router on SIMULATED-compute replicas: engines that honor
    the full ``ServingEngine`` frontend surface (real scheduler, real
    slot accounting, real admission/throughput telemetry) but whose
    decode chunk is a GIL-releasing sleep standing in for device
    compute. This isolates what the router itself adds or costs.

    Measured fact that forces the simulation: one XLA CPU engine
    already saturates every host core through its intra-op thread
    pool, so two REAL replicas on one shared-memory CPU scale at
    ~1.0x no matter what the router does (measured 0.9-1.1x across
    model sizes) — data parallelism needs a second chip's worth of
    compute, which this host does not have. With compute that actually
    parallelizes (the sleep), the >= 1.6x acceptance floor asserts the
    router adds no serialization: placement, admission, and stream
    delivery all stay off the critical path.

  * router streaming parity — REAL engines: every stream routed
    through a 2-replica fleet must be bit-identical to
    ``ServingEngine.run`` on the same prompts (greedy). The pinned
    workload must not shed or re-route (those counters are asserted
    zero here; the crash-drain path is exercised in tests/test_fleet.py).

  * tp=2 — a tensor-parallel engine on the 8-virtual-device CPU mesh:
    greedy parity against the unsharded engine, and the tp chunk
    program's pinned compile count under its own variant name.

  * disaggregated prefill — paged prefill slice + decode slice:
    greedy parity against the co-located paged engine, pinned compile
    count, and exactly one D2D handoff per prefilled request.

  * cross-host transport + live migration — the same fleet surface over
    the ``dstpu-fleet-v1`` streaming HTTP transport: two REAL paged
    engines behind :class:`ReplicaServer`/:class:`RemoteReplica`
    loopback pairs, routed streams greedy bit-identical to the
    in-process paged engine; one running request is then live-migrated
    mid-decode (KV blocks + block table + cursor over the wire) and
    must finish bit-identical with zero lost or duplicated tokens.
    A second leg runs a 3-replica SIMULATED fleet under a skewed
    arrival (everything lands on one replica), with periodic
    ``FleetRouter.rebalance`` passes: the post-rebalance occupancy
    spread must stay below the unbalanced control run's, again with
    zero lost/duplicated tokens, and the merged journey export must
    validate with its migration hops connected.

  * fleet observability plane — a 3-pod mixed local+remote hierarchy
    behind ``RootRouter.serve_metrics``: the merged ``/fleet/metrics``
    exposition shows every replica up with ``pod=``/``replica=``
    labels and one TYPE header per family, killing a remote replica
    flips exactly its ``up`` series to 0 within one TTL, and a forced
    cross-pod failover's merged journey export validates with the pod
    hop connected on the pod lane (pid 5).

  * crash observability — an injected mid-decode-chunk replica crash
    over a 2-replica fleet: ZERO requests resolve error (the wedged
    mid-chunk request REPLAYS its prompt + emitted prefix on the
    survivor, finishing bit-identical), the flight-recorder
    postmortem's in-flight set must exactly match the rerouted handles
    with every record ``salvageable``, every request must render as
    ONE connected journey under one trace id in the merged Perfetto
    export (``validate_journeys``), and the TTFT SLO burn rate —
    replayed journeys keep their original submit time — must move
    during the crash window and recover after it, while availability
    stays clean (``--slo`` / ``--trace-out``).

  * elastic recovery — kill a replica mid-stream at 2x load with an
    :class:`ElasticController` holding the fleet at target size: zero
    lost requests, replayed streams bit-identical with no duplicate
    tokens, bounded recovery TTFT p99, the below-target fleet restored
    immediately from the replica factory (EWMA warm-started from a
    peer), a surge replica retired gracefully (drain -> idle -> close)
    once burn calms, and the fleet finishing at exactly target size
    with a clean fast window.

Run:  python -m deepspeed_tpu.benchmarks.fleet_bench --json-out BENCH_fleet.json
(needs XLA_FLAGS=--xla_force_host_platform_device_count=8 for the tp
case; ``bin/fleet_smoke.sh`` sets it). Compare runs with bin/benchdiff
(kind ``fleet``).
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Optional

import numpy as np

#: pinned compile count for the tp=2 dense chunk program
#: (``decode_chunk_tp2_fn``) across three full runs: the initial trace
#: plus ONE carry retrace — the tp chunk consumes the donated arena
#: whose NamedSharding metadata is identical between the insert-built
#: and chunk-output forms, so the dense budget's third compile never
#: happens (same physics as the paged layout). Measured; the bench
#: fails at the offending call beyond it.
TP2_DECODE_PROGRAM_BUDGET = 2

#: pinned compile count for the disaggregated paged chunk program
#: (``decode_chunk_paged_disagg_fn``) across three full runs: identical
#: to the co-located paged budget (2) plus one more — the first decode
#: chunk after a D2D handoff sees the replicated-transfer pool's buffer
#: metadata once before steady state. Measured; the bench fails at the
#: offending call beyond it.
DISAGG_PAGED_DECODE_PROGRAM_BUDGET = 3

#: acceptance floor for 2-replica router scaling over simulated-compute
#: replicas (ISSUE: fleet throughput >= 1.6x a single replica).
ROUTER_SCALING_FLOOR = 1.6


# --------------------------------------------------------------------------
# simulated-compute replica (router-scaling case only)
# --------------------------------------------------------------------------
class _SimMetrics:
    """The one engine-metrics field the frontend driver reads."""

    def __init__(self):
        self.tokens_out = 0


class SimulatedEngine:
    """``ServingEngine``'s frontend-facing surface with the device
    replaced by ``time.sleep`` (which drops the GIL, exactly like a
    blocking device sync). Scheduling, slot accounting, admission
    feedback, and stream delivery are all REAL — only the math is
    simulated — so a router throughput ratio over these replicas
    measures the routing/driver stack, not XLA's CPU thread pool."""

    def __init__(self, *, max_batch: int = 4, max_seq_len: int = 4096,
                 decode_chunk: int = 8, chunk_time_s: float = 0.005,
                 max_queue: int = 256):
        from ..serving.kv_cache import SlotAllocator
        from ..serving.scheduler import ContinuousBatchScheduler
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self.decode_chunk = decode_chunk
        self.chunk_time_s = chunk_time_s
        self.scheduler = ContinuousBatchScheduler(
            SlotAllocator(max_batch, max_seq_len), max_queue=max_queue)
        self.chunk_in_flight = False
        self.metrics = _SimMetrics()

    def submit(self, req):
        self.scheduler.submit(req)
        return req

    def cancel(self, req):
        return self.scheduler.cancel(req)

    def pump(self):
        before = len(self.scheduler.finished)
        admitted = self.scheduler.admit()
        if not self.scheduler.running:
            return self.scheduler.finished[before:]
        time.sleep(self.chunk_time_s)          # the "device" chunk
        for req in admitted:                   # prefill samples token #1
            self.scheduler.record_first_token(req, int(req.prompt[-1]))
            self.metrics.tokens_out += 1
        chunk = {}
        for slot, req in list(self.scheduler.running.items()):
            k = min(self.decode_chunk, req.max_new_tokens - len(req.tokens))
            if k > 0:
                base = len(req.tokens)
                chunk[slot] = [int(req.prompt[(base + i) % req.prompt_len])
                               for i in range(k)]
        if chunk:
            n = sum(len(v) for v in chunk.values())
            self.scheduler.step_tokens_chunk(chunk)
            self.metrics.tokens_out += n
        return self.scheduler.finished[before:]

    # ---- live-migration surface (the ServingEngine contract with the
    # device state reduced to the decode cursor: a simulated request's
    # "KV" is fully determined by prompt + emitted tokens, so the
    # bundle ships an empty leaf dict and the importer just re-seats
    # the cursor) ----
    def can_migrate(self, req) -> bool:
        if req.status != "running" or not req.tokens:
            return False
        slot = req.slot
        return slot is not None and self.scheduler.running.get(slot) is req

    def export_request(self, req):
        from ..serving.engine import MIGRATE_SCHEMA, MigrationError
        if not self.can_migrate(req):
            raise MigrationError(
                f"request uid={req.uid} is not migratable "
                f"(status={req.status!r})")
        fill = req.prompt_len + len(req.tokens) - 1
        return {
            "schema": MIGRATE_SCHEMA,
            "prompt": [int(t) for t in np.asarray(req.prompt)],
            "tokens": [int(t) for t in req.tokens],
            "max_new_tokens": int(req.max_new_tokens),
            "eos_token_id": req.eos_token_id,
            "deadline_s": req.deadline_s,
            "tenant": req.tenant,
            "trace_id": req.trace_id,
            "fill": int(fill),
            "block_size": 1,
            "n_blocks": int(fill),
            "kv_bytes": 0,
            "kv": {},
        }

    def import_request(self, bundle):
        from ..serving.engine import MIGRATE_SCHEMA, MigrationError
        from ..serving.scheduler import Request
        if bundle.get("schema") != MIGRATE_SCHEMA:
            raise MigrationError(
                f"unknown migration schema {bundle.get('schema')!r}")
        prompt = np.asarray(bundle["prompt"], np.int32)
        tokens = [int(t) for t in bundle["tokens"]]
        fill = int(bundle["fill"])
        if fill != prompt.shape[0] + len(tokens) - 1:
            raise MigrationError(
                f"bundle cursor fill={fill} inconsistent with "
                f"prompt_len={prompt.shape[0]} + {len(tokens)} tokens")
        if fill + 1 > self.max_seq_len:
            raise MigrationError(
                f"sequence length {fill + 1} exceeds this replica's "
                f"max_seq_len {self.max_seq_len}")
        slot = self.scheduler.allocator.alloc(fill)
        if slot is None:
            raise MigrationError(
                "no free slot for the incoming request")
        req = Request(prompt=prompt,
                      max_new_tokens=int(bundle["max_new_tokens"]),
                      eos_token_id=bundle.get("eos_token_id"),
                      deadline_s=bundle.get("deadline_s"),
                      trace_id=bundle.get("trace_id"),
                      tenant=bundle.get("tenant") or "default")
        now = self.scheduler.clock()
        req.submit_t = now
        req.first_token_t = now
        req.status = "running"
        req.slot = slot
        req.tokens = tokens
        self.scheduler.running[slot] = req
        return req


def _sim_router_pass(n_replicas: int, prompts, max_new_tokens: int,
                     max_batch: int, decode_chunk: int,
                     chunk_time_s: float) -> float:
    """One full routed run over fresh simulated replicas; returns
    aggregate tokens/s (submit of the first request to the last
    terminal stream)."""
    from ..serving import FleetRouter
    engines = [SimulatedEngine(max_batch=max_batch,
                               decode_chunk=decode_chunk,
                               chunk_time_s=chunk_time_s)
               for _ in range(n_replicas)]
    router = FleetRouter(engines)
    try:
        t0 = time.perf_counter()
        handles = [router.submit(p, max_new_tokens=max_new_tokens)
                   for p in prompts]
        for h in handles:
            status = h.result(timeout=120)
            if status != "done":
                raise RuntimeError(
                    f"simulated replica run shed work: uid={h.uid} "
                    f"status={status} reason={h.reject_reason}")
        dt = time.perf_counter() - t0
        tokens = sum(len(h.tokens) for h in handles)
    finally:
        router.close(timeout=30)
    return tokens / dt


def _warm_widths(eng, prompts, max_new_tokens: int) -> None:
    """Charge every prefill width this replica can see: batched prefill
    compiles per (n, bucket) and arrival timing decides n, so a cold
    width inside a measured window reads as multi-second TTFT burn on a
    slow-compiling host (same physics as frontend_bench's k-sized warm
    runs)."""
    for k in range(1, len(prompts) + 1):
        eng.run(list(prompts[:k]), max_new_tokens=max_new_tokens)


def _round_tree(obj, nd=6):
    if isinstance(obj, dict):
        return {k: _round_tree(v, nd) for k, v in obj.items()}
    if isinstance(obj, float):
        return round(obj, nd)
    return obj


def run_bench(n_requests: int = 8, max_new_tokens: int = 32,
              max_batch: int = 8, prompt_len: int = 16,
              decode_chunk: int = 8, seed: int = 0,
              sim_requests: int = 16,
              sim_chunk_time_s: float = 0.005,
              slo: bool = True, transport: bool = True,
              fleetobs: bool = True,
              trace_out: Optional[str] = None) -> dict:
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    from .. import telemetry
    from ..analysis import TraceAuditor
    from ..serving import FleetRouter, ServingEngine
    from .serving_bench import _timed_serving_run, _tiny_model

    telemetry.enable()
    model, params = _tiny_model()
    vocab = model.cfg.vocab_size
    rng = np.random.default_rng(seed)
    lens = rng.integers(min(4, prompt_len), prompt_len + 1, n_requests)
    lens[0] = prompt_len
    prompts = [rng.integers(0, vocab, (int(n),)).astype(np.int32)
               for n in lens]

    result: dict = {
        "bench": "fleet",
        "n_requests": n_requests, "max_new_tokens": max_new_tokens,
        "max_batch": max_batch, "decode_chunk": decode_chunk,
    }

    # ---- router scaling over simulated-compute replicas ----------------
    sim_prompts = [rng.integers(0, vocab, (int(prompt_len),))
                   .astype(np.int32) for _ in range(sim_requests)]
    sim_kw = dict(max_new_tokens=max_new_tokens, max_batch=max_batch // 2,
                  decode_chunk=decode_chunk, chunk_time_s=sim_chunk_time_s)
    _sim_router_pass(1, sim_prompts, **sim_kw)          # warm (threads, jit
    _sim_router_pass(2, sim_prompts, **sim_kw)          # of nothing — pure
    single_tps = _sim_router_pass(1, sim_prompts, **sim_kw)   # host paths)
    fleet_tps = _sim_router_pass(2, sim_prompts, **sim_kw)
    scaling = fleet_tps / single_tps
    result["single_tokens_per_s"] = single_tps
    result["fleet_tokens_per_s"] = fleet_tps
    result["replica_scaling"] = scaling
    result["sim"] = {"n_requests": sim_requests,
                     "chunk_time_s": sim_chunk_time_s,
                     "replica_max_batch": max_batch // 2}
    if scaling < ROUTER_SCALING_FLOOR:
        raise RuntimeError(
            f"2-replica router scaling {scaling:.2f}x is below the "
            f"{ROUTER_SCALING_FLOOR}x acceptance floor — the router is "
            f"serializing work that should overlap")

    # ---- router streaming parity over REAL engines ---------------------
    inf = ds.init_inference(model, model_parameters=params,
                            dtype=jnp.float32)
    eng_kw = dict(max_batch=max_batch, max_prompt_len=prompt_len,
                  decode_chunk=decode_chunk, max_queue=max(n_requests, 8))
    oracle = ServingEngine(engine=inf, **eng_kw)
    oracle_out = [r.output_ids
                  for r in oracle.run(list(prompts),
                                      max_new_tokens=max_new_tokens)]
    replicas = [ServingEngine(engine=inf, **eng_kw) for _ in range(2)]
    for eng in replicas:                 # charge compiles before the
        eng.run(list(prompts),          # frontend takes ownership
                max_new_tokens=max_new_tokens)
    router = FleetRouter(replicas)
    try:
        handles = [router.submit(p, max_new_tokens=max_new_tokens,
                                 tenant="tenant-a" if i % 2 == 0
                                 else "tenant-b")
                   for i, p in enumerate(prompts)]
        for h in handles:
            h.result(timeout=300)
        parity = all(
            h.status == "done"
            and np.array_equal(h.output_ids, oracle_out[i])
            for i, h in enumerate(handles))
        shed = sum(1 for h in handles if h.status == "rejected")
        stats = router.stats()
        tenants = router.tenants_report()
    finally:
        router.close(timeout=60)
    merged = tenants["tenants"]
    if not {"tenant-a", "tenant-b"} <= set(merged):
        raise RuntimeError(
            f"fleet tenants report is missing tagged tenants: "
            f"saw {sorted(merged)}")
    result["tenant_goodput"] = {
        "n_tenants": tenants["n_tenants"],
        "tenants": merged,
    }
    result["router_streaming_parity"] = float(parity)
    result["router"] = {
        "routed": stats["routed"], "shed": shed,
        "rerouted": stats["rerouted"],
        "affinity_hits": stats["affinity_hits"],
        "replica_crashes": stats["replica_crashes"],
    }
    if not parity:
        raise RuntimeError("routed streams diverged from ServingEngine.run")
    if shed or stats["rerouted"] or stats["replica_crashes"]:
        raise RuntimeError(
            f"pinned fleet workload shed or re-routed: shed={shed} "
            f"rerouted={stats['rerouted']} "
            f"crashes={stats['replica_crashes']}")

    # ---- tensor-parallel serving (tp=2) --------------------------------
    auditor = TraceAuditor(
        budgets={"decode_chunk_tp2_fn": TP2_DECODE_PROGRAM_BUDGET},
        audit_jaxprs=False)
    with auditor:
        tp_eng = ServingEngine(model, model_parameters=params,
                               dtype=jnp.float32, tp=2, max_batch=max_batch,
                               max_prompt_len=prompt_len,
                               decode_chunk=decode_chunk,
                               max_queue=max(n_requests, 8))
        tp_res, tp_dt, tp_tokens, _ = _timed_serving_run(
            tp_eng, prompts, max_new_tokens)
    tp_parity = all(
        r.status == "done" and np.array_equal(r.output_ids, oracle_out[i])
        for i, r in enumerate(tp_res))
    result["tp"] = {
        "tp": 2,
        "greedy_parity": float(tp_parity),
        "decode_chunk_compiles": auditor.compiles("decode_chunk_tp2_fn"),
        "tokens_per_s": tp_tokens / tp_dt,
    }
    if not tp_parity:
        raise RuntimeError("tp=2 greedy streams diverged from tp=1")

    # ---- prefill/decode disaggregation ---------------------------------
    paged_oracle = ServingEngine(engine=inf, paged=True, **eng_kw)
    paged_out = [r.output_ids
                 for r in paged_oracle.run(list(prompts),
                                           max_new_tokens=max_new_tokens)]
    counters0 = telemetry.get_runtime().counter_totals()
    auditor = TraceAuditor(
        budgets={"decode_chunk_paged_disagg_fn":
                 DISAGG_PAGED_DECODE_PROGRAM_BUDGET},
        audit_jaxprs=False)
    with auditor:
        dis_eng = ServingEngine(engine=inf, paged=True,
                                disaggregate_prefill=True, **eng_kw)
        dis_res, dis_dt, dis_tokens, _ = _timed_serving_run(
            dis_eng, prompts, max_new_tokens)
    counters1 = telemetry.get_runtime().counter_totals()
    handoffs = int(counters1.get("serve/disagg_handoffs", 0)
                   - counters0.get("serve/disagg_handoffs", 0))
    dis_parity = all(
        r.status == "done" and np.array_equal(r.output_ids, paged_out[i])
        for i, r in enumerate(dis_res))
    result["disagg"] = {
        "greedy_parity": float(dis_parity),
        "decode_chunk_compiles":
            auditor.compiles("decode_chunk_paged_disagg_fn"),
        "handoffs": handoffs,
        "tokens_per_s": dis_tokens / dis_dt,
    }
    if not dis_parity:
        raise RuntimeError(
            "disaggregated greedy streams diverged from co-located paged")
    # one handoff per prefill EXECUTED: the paged prefix cache absorbs
    # the warm passes' repeats (same prompts all three runs), so across
    # 3 runs each request prefills — and hands off — exactly once
    if handoffs != n_requests:
        raise RuntimeError(
            f"expected {n_requests} D2D handoffs (one per executed "
            f"prefill; prefix cache covers the warm repeats), "
            f"saw {handoffs}")

    # ---- cross-host transport + live KV-block migration ----------------
    # before the crash cases: this case's parity asserts need a fleet
    # whose crash/reroute counters stay zero
    if transport:
        result.update(_transport_case(
            inf, eng_kw, prompts, paged_out, max_new_tokens))

    # ---- fleet observability plane (--fleetobs) ------------------------
    if fleetobs:
        result.update(_fleetobs_case())

    # ---- crash journeys + SLO burn + flight recorder -------------------
    # LAST on purpose: these cases inject mid-stream replica crashes,
    # and the parity cases above assert their crash counters are zero.
    # Replayed requests re-prefill prompt + emitted prefix, so the
    # crash-path engines need prompt headroom for the whole stream.
    crash_kw = dict(eng_kw, max_prompt_len=prompt_len + max_new_tokens)
    result.update(_crash_case(
        inf, crash_kw, prompts, oracle_out, max_new_tokens,
        slo=slo, trace_out=trace_out))

    # ---- elastic fleet: kill a replica mid-stream at 2x load -----------
    result.update(_elastic_case(
        inf, crash_kw, prompts, oracle_out, max_new_tokens))

    return _round_tree(result)


def _crash_case(inf, eng_kw, prompts, oracle_out, max_new_tokens, *,
                slo=True, trace_out=None,
                slo_windows_s=(2.0, 20.0),
                ttft_threshold_s=2.0, wedge_hold_s=3.0) -> dict:
    """Injected mid-stream replica crash over a 2-replica fleet:

    * phase A (healthy) — a routed batch lands on the survivor; every
      SLO burn rate must be 0;
    * phase B (crash) — one request is wedged mid-decode-chunk on the
      crashy replica, the rest queue behind it, the wedge holds past
      the TTFT threshold, then the chunk raises. NOTHING resolves
      ``error``: the queued requests re-route and the wedged one
      REPLAYS (prompt + emitted prefix) on the survivor, every stream
      finishing with greedy parity. The crashed frontend's flight
      recorder must dump a postmortem whose in-flight set EXACTLY
      matches the rerouted handles (all ``salvageable``), and the TTFT
      burn rate must move — with full replay the availability budget
      never burns, so the crash's cost shows up as latency: ``adopt``
      keeps the ORIGINAL submit time, putting the recovery delay inside
      the survivor segment's TTFT;
    * phase C (recovered) — after the fast window drains, a healthy
      batch brings the fast burn rate back to 0.

    The router's merged Perfetto export must pass
    ``validate_journeys``: every request — including the rerouted ones —
    one connected journey under one trace id, with the reroute flow
    link carrying ``rerouted_from``.
    """
    import threading

    import deepspeed_tpu as ds  # noqa: F401 — keeps import side effects
    from ..serving import FleetRouter, ServingEngine
    from ..telemetry.journey import validate_journeys
    from ..telemetry.slo import SLOEngine, default_slos

    out: dict = {}
    engines = [ServingEngine(engine=inf, **eng_kw) for _ in range(2)]
    for eng in engines:                     # charge compiles up front
        _warm_widths(eng, prompts, max_new_tokens)
    router = FleetRouter(engines)
    crashy, survivor = router.replicas[0], router.replicas[1]

    slo_engine = None
    if slo:
        # tpot is parked at 30s (CPU chunk timing is noise); TTFT at
        # ``ttft_threshold_s`` is the signal the crash moves — the
        # wedge holds longer than the threshold, and replayed journeys
        # keep their original submit time, so the recovery delay lands
        # inside TTFT while availability stays clean (zero errors)
        slo_engine = SLOEngine(
            default_slos(ttft_threshold_s=ttft_threshold_s,
                         tpot_threshold_s=30.0),
            windows_s=slo_windows_s)
        for rep in router.replicas:
            slo_engine.attach(rep.frontend.tracing)

    def serve_batch():
        handles = [router.submit(p, max_new_tokens=max_new_tokens)
                   for p in prompts]
        for h in handles:
            if h.result(timeout=120) != "done":
                raise RuntimeError(
                    f"healthy fleet batch failed: uid={h.uid} "
                    f"status={h.status}")
        return handles

    try:
        # phase A: healthy traffic (survivor only — deterministic lane)
        crashy.dead = True
        serve_batch()
        burn_pre = (slo_engine.evaluate(export_gauges=False)
                    ["max_burn_rate"] if slo_engine else 0.0)

        # phase B: wedge one request mid-chunk on the crashy replica,
        # queue the rest behind it, hold past the TTFT threshold, then
        # let the chunk raise
        crashy.dead = False
        survivor.dead = True
        entered, release = threading.Event(), threading.Event()

        def boom(*a, **k):
            entered.set()
            release.wait(30)
            raise RuntimeError("injected decode fault")

        engines[0]._jit_decode_chunk = boom
        first = router.submit(prompts[0], max_new_tokens=max_new_tokens)
        if not entered.wait(30):
            raise RuntimeError("injected fault never reached the chunk")
        rest = [router.submit(p, max_new_tokens=max_new_tokens)
                for p in prompts[1:]]
        time.sleep(wedge_hold_s)    # outage longer than the threshold
        survivor.dead = False       # revive BEFORE the crash fires
        release.set()
        all_handles = [first] + rest
        statuses = [h.result(timeout=120) for h in all_handles]
        n_errors = sum(1 for s in statuses if s == "error")
        if any(s != "done" for s in statuses):
            raise RuntimeError(
                f"crash must lose nothing — the wedged request replays "
                f"and the queued ones re-route: {statuses}")
        rerouted_parity = all(
            np.array_equal(h.output_ids, oracle_out[i])
            for i, h in enumerate(all_handles))
        if not rerouted_parity:
            raise RuntimeError(
                "rerouted greedy streams diverged from ServingEngine.run")
        if any(len(h.tokens) != max_new_tokens for h in all_handles):
            raise RuntimeError("replayed stream dropped or duplicated "
                               "tokens")
        burn_crash = (slo_engine.evaluate(export_gauges=False)
                      ["max_burn_rate"] if slo_engine else 0.0)

        # postmortem: the in-flight set must be EXACTLY the handles the
        # caller saw re-route, every one of them salvageable (v2: the
        # record is a replay manifest, not a casualty list)
        pm_path = crashy.frontend.postmortem_path
        if not pm_path:
            raise RuntimeError("crashed frontend dumped no postmortem")
        with open(pm_path) as f:
            pm = json.load(f)
        pm_uids = {e["uid"] for e in pm["in_flight"]}
        expect = {h.uid for h in all_handles}
        pm_match = (pm_uids == expect and all(
            e["disposition"] == "salvageable" for e in pm["in_flight"]))
        if not pm_match:
            raise RuntimeError(
                f"postmortem in-flight set {sorted(pm_uids)} != "
                f"rerouted handles {sorted(expect)}, or a prefilled "
                f"request was not marked salvageable")

        # phase C: drain the fast window, then healthy traffic again
        if slo_engine:
            time.sleep(slo_windows_s[0] + 0.5)
            serve_batch()
            burn_recovered = slo_engine.fast_burn_rate()
        else:
            burn_recovered = 0.0

        stats = router.stats()
        trace_obj = router.export_chrome(trace_out or None)
        problems = validate_journeys(trace_obj)
        if problems:
            raise RuntimeError(
                "journey validation failed: " + "; ".join(problems[:5]))
        n_traces = sum(
            1 for e in trace_obj["traceEvents"]
            if e.get("name") == "route")
    finally:
        router.close(timeout=60)

    out["crash"] = {
        "errors": n_errors,
        "rerouted": stats["rerouted"],
        "replayed": stats["replayed"],
        "journey_complete": 1.0,
        "rerouted_parity": float(rerouted_parity),
        "postmortem_inflight_match": float(pm_match),
        "postmortem_events": len(pm["events"]),
        "postmortem": pm_path,
    }
    out["journey"] = {
        "n_traces": n_traces,
        "complete": 1.0,
        "rerouted_links": stats["rerouted"],
        "trace_file": trace_out or "",
    }
    if slo_engine:
        rep = slo_engine.evaluate(export_gauges=False)
        ttft = next(s for s in rep["slos"] if s["name"] == "ttft")
        avail = next(s for s in rep["slos"]
                     if s["kind"] == "availability")
        out["slo"] = {
            "burn_pre": burn_pre,
            "burn_crash": burn_crash,
            "burn_recovered": burn_recovered,
            "burn_moved": float(burn_crash > burn_pre),
            "burn_recovered_flag": float(
                burn_recovered < min(1.0, burn_crash)),
            "windows_s": list(slo_windows_s),
            "ttft_threshold_s": ttft_threshold_s,
            "ttft_worst_window_s": ttft["worst_window_s"],
            # with full replay the availability budget must NOT burn —
            # the whole crash cost moved into latency
            "availability_burn": avail["worst_burn_rate"],
            "budget_remaining": min(
                w["budget_remaining"]
                for s in rep["slos"] for w in s["windows"].values()),
        }
        if burn_crash <= burn_pre:
            raise RuntimeError(
                f"ttft burn rate did not move during the crash "
                f"window: pre={burn_pre} crash={burn_crash}")
        if burn_recovered > 0.0:
            raise RuntimeError(
                f"fast burn rate did not recover after the crash "
                f"window drained: {burn_recovered}")
        if avail["worst_burn_rate"] > 0.0:
            raise RuntimeError(
                f"availability burned during a zero-loss crash: "
                f"{avail['worst_burn_rate']}")
    return out


def _elastic_case(inf, eng_kw, prompts, oracle_out, max_new_tokens, *,
                  slo_windows_s=(2.0, 20.0), ttft_threshold_s=2.0,
                  wedge_hold_s=3.0, recovery_p99_bound_s=30.0) -> dict:
    """Elastic fleet under failure: kill a replica mid-stream at 2x
    load, then watch the :class:`ElasticController` put the fleet back.

    One scripted incident over a 2-replica fleet with a checkpoint-
    backed replica factory (fresh engines share the committed params
    and are warmed before joining):

    * 2x the pinned workload is aimed at one replica (the other is
      briefly unroutable — a deterministic lane), the first request
      wedges mid-decode-chunk, the outage holds past the TTFT
      threshold, then the chunk raises;
    * ZERO requests are lost: the 2N streams re-route — the prefilled
      one REPLAYS — and every one finishes greedy bit-identical with
      no duplicate or dropped tokens;
    * the controller restores the below-target fleet immediately (no
      cooldown) via the factory, with the newcomer's EWMA warm-started
      from the survivor;
    * a manual surge replica is then retired gracefully once the burn
      calms: ``draining`` excludes it from placement, ``poll_draining``
      closes it idle, and the fleet ends at exactly ``target`` size;
    * the TTFT burn rate moves during the incident (replayed journeys
      keep their ORIGINAL submit time) and the fast window is clean
      after recovery; recovery-window TTFT p99 stays bounded.
    """
    import threading

    from ..serving import FleetRouter, ServingEngine
    from ..serving.fleet import ElasticConfig, ElasticController
    from ..telemetry.slo import default_slos

    def factory():
        eng = ServingEngine(engine=inf, **eng_kw)
        # checkpoint-backed warm start: committed params, compiles
        # charged on the pinned workload before the replica takes
        # traffic (a cold compile inside the recovery window would
        # read as burn)
        _warm_widths(eng, prompts, max_new_tokens)
        return eng

    load_prompts = list(prompts) + list(prompts)        # 2x load
    load_out = list(oracle_out) + list(oracle_out)
    engines = [ServingEngine(engine=inf, **eng_kw) for _ in range(2)]
    for eng in engines:
        _warm_widths(eng, prompts, max_new_tokens)
    router = FleetRouter(engines, replica_factory=factory)
    ctrl = ElasticController(
        router,
        ElasticConfig(min_replicas=1, max_replicas=4, cooldown_s=0.5),
        slos=default_slos(ttft_threshold_s=ttft_threshold_s,
                          tpot_threshold_s=30.0),
        windows_s=slo_windows_s)
    crashy, survivor = router.replicas[0], router.replicas[1]

    def max_fast_burn():
        burns = ctrl.burn_rates()
        return max(burns.values(), default=0.0)

    try:
        rec0 = ctrl.step()                  # sensors + inferred target
        if ctrl.target != 2 or rec0["action"] != "none":
            raise RuntimeError(f"controller mis-read the fleet: {rec0}")

        # healthy 1x traffic, burn baseline
        for h in [router.submit(p, max_new_tokens=max_new_tokens)
                  for p in prompts]:
            if h.result(timeout=120) != "done":
                raise RuntimeError("healthy elastic batch failed")
        burn_pre = max_fast_burn()

        # the incident: 2x load onto the crashy replica, wedge, hold,
        # crash
        survivor.dead = True                # deterministic lane
        entered, release = threading.Event(), threading.Event()

        def boom(*a, **k):
            entered.set()
            release.wait(30)
            raise RuntimeError("injected decode fault")

        engines[0]._jit_decode_chunk = boom
        first = router.submit(load_prompts[0],
                              max_new_tokens=max_new_tokens)
        if not entered.wait(30):
            raise RuntimeError("injected fault never reached the chunk")
        rest = [router.submit(p, max_new_tokens=max_new_tokens)
                for p in load_prompts[1:]]
        time.sleep(wedge_hold_s)
        survivor.dead = False               # revive BEFORE the crash
        release.set()
        all_handles = [first] + rest
        statuses = [h.result(timeout=180) for h in all_handles]
        n_errors = sum(1 for s in statuses if s == "error")
        n_lost = sum(1 for s in statuses if s != "done")
        if n_lost:
            raise RuntimeError(
                f"elastic crash lost {n_lost} requests: {statuses}")
        replay_parity = all(
            np.array_equal(h.output_ids, load_out[i])
            for i, h in enumerate(all_handles))
        if not replay_parity:
            raise RuntimeError(
                "replayed/rerouted streams diverged from the oracle")
        n_dup = sum(1 for h in all_handles
                    if len(h.tokens) != max_new_tokens)
        if n_dup:
            raise RuntimeError(
                f"{n_dup} streams dropped or duplicated tokens")

        # recovery TTFT (original submit time -> survivor first token)
        crash_uids = {h.uid for h in all_handles}
        recs = survivor.frontend.tracing.to_json()["requests"]
        ttfts = [t["ttft_s"] for t in recs
                 if t["uid"] in crash_uids and t["status"] == "done"
                 and t["ttft_s"] is not None]
        if len(ttfts) != len(all_handles):
            raise RuntimeError(
                f"survivor adopted {len(ttfts)} of "
                f"{len(all_handles)} crashed streams")
        recovery_p99 = float(np.percentile(ttfts, 99))
        if recovery_p99 > recovery_p99_bound_s:
            raise RuntimeError(
                f"recovery TTFT p99 {recovery_p99:.2f}s above the "
                f"{recovery_p99_bound_s}s bound")
        burn_crash = max_fast_burn()
        if burn_crash <= burn_pre:
            raise RuntimeError(
                f"ttft burn did not move during the incident: "
                f"pre={burn_pre} crash={burn_crash}")

        # autoscale: restore the below-target fleet (no cooldown wait)
        rec1 = ctrl.step()
        if rec1["action"] != "scale_up" or rec1["reason"] != "below_target":
            raise RuntimeError(
                f"controller did not restore the crashed fleet: {rec1}")
        restored = router.replicas[-1]
        seeded = restored.frontend._estimator.snapshot()
        if seeded["tokens_per_s"] is None or seeded["n_samples"] != 0:
            raise RuntimeError(
                f"restored replica's EWMA was not warm-started from a "
                f"peer: {seeded}")

        # surge + graceful scale-down back to target once burn calms
        router.add_replica()
        time.sleep(slo_windows_s[0] + 0.5)  # drain the fast window
        deadline = time.monotonic() + 30.0
        while (router.n_drained < 1 or router.n_routable != ctrl.target) \
                and time.monotonic() < deadline:
            ctrl.step()
            time.sleep(0.1)
        if router.n_drained < 1 or router.n_routable != ctrl.target:
            raise RuntimeError(
                f"fleet did not return to target: "
                f"routable={router.n_routable} target={ctrl.target} "
                f"drained={router.n_drained}")

        # recovered: healthy traffic on the final fleet, clean fast burn
        for h in [router.submit(p, max_new_tokens=max_new_tokens)
                  for p in prompts]:
            if h.result(timeout=120) != "done":
                raise RuntimeError("post-recovery batch failed")
        burn_recovered = max_fast_burn()
        if burn_recovered > 0.0:
            raise RuntimeError(
                f"fast burn did not recover: {burn_recovered}")
        stats = router.stats()
    finally:
        ctrl.stop()
        router.close(timeout=60)

    return {"elastic": {
        "n_requests": len(load_prompts),
        "load_factor": 2,
        "errors": n_errors,
        "lost": n_lost,
        "rerouted": stats["rerouted"],
        "replayed": stats["replayed"],
        "replay_parity": float(replay_parity),
        "duplicate_tokens": n_dup,
        "scale_up": stats["scale_up"],
        "scale_down": stats["scale_down"],
        "drained": stats["drained"],
        "target": ctrl.target,
        "final_routable": stats["routable"],
        "returned_to_target": float(stats["routable"] == ctrl.target),
        "recovery_ttft_p99_s": recovery_p99,
        "burn_pre": burn_pre,
        "burn_crash": burn_crash,
        "burn_recovered": burn_recovered,
        "burn_moved": float(burn_crash > burn_pre),
        "burn_recovered_flag": float(burn_recovered == 0.0),
    }}


def _sim_expected(prompt, max_new: int):
    """The SimulatedEngine's deterministic greedy stream: token #1 is
    ``prompt[-1]`` (sampled at prefill), token k >= 1 is
    ``prompt[k % prompt_len]`` — position-keyed, so a migrated
    continuation is bit-identical iff the cursor moved intact."""
    plen = len(prompt)
    return [int(prompt[-1])] + [int(prompt[k % plen])
                                for k in range(1, max_new)]


def _transport_sim_fleet(*, rebalance: bool, n_replicas: int = 3,
                         n_requests: int = 12, prompt_len: int = 16,
                         max_new: int = 48, chunk_time_s: float = 0.02,
                         seed: int = 1) -> dict:
    """One skewed routed run over REMOTE simulated replicas: every
    request is aimed at replica 0 (the others are briefly unroutable),
    then the fleet either rebalances periodically (``rebalance=True``)
    or serves the skew as-is (the control). Occupancy spread is
    sampled right after each rebalance pass — the bounded quantity the
    ISSUE gates — over the window where every pending stream still has
    at least 16 tokens to go (so a picked candidate can never finish
    under the migration's feet)."""
    from ..serving import FleetRouter
    from ..serving.fleet import RemoteReplica, ReplicaServer
    from ..serving.frontend.frontend import ServingFrontend
    from ..telemetry.journey import validate_journeys

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 512, (prompt_len,)).astype(np.int32)
               for _ in range(n_requests)]
    engines = [SimulatedEngine(max_batch=4, decode_chunk=4,
                               chunk_time_s=chunk_time_s)
               for _ in range(n_replicas)]
    fronts = [ServingFrontend(eng, telemetry_label=f"sim{i}")
              for i, eng in enumerate(engines)]
    servers = [ReplicaServer(fe) for fe in fronts]
    remotes = [RemoteReplica("127.0.0.1", srv.port, label=f"sim{i}")
               for i, srv in enumerate(servers)]
    router = FleetRouter([], remotes=remotes)
    spreads: list = []
    n_moves = 0
    try:
        for rep in router.replicas[1:]:
            rep.dead = True        # the skew: everything lands on sim0
        handles = [router.submit(p, max_new_tokens=max_new)
                   for p in prompts]
        # wait for every accepted frame so migrate_out always finds its
        # client-side handle (otherwise an early rebalance pass reads
        # as a spurious failure)
        t_acc = time.monotonic() + 30.0
        while any(h._remote_uid is None and not h.done for h in handles) \
                and time.monotonic() < t_acc:
            time.sleep(0.002)
        for rep in router.replicas[1:]:
            rep.dead = False
        deadline = time.monotonic() + 120.0
        while not all(h.done for h in handles):
            if time.monotonic() > deadline:
                raise RuntimeError(
                    "transport sim fleet wedged: "
                    f"{[h.status for h in handles]}")
            pending = [h for h in handles if not h.done]
            in_window = pending and max(
                len(h.tokens) for h in pending) <= max_new - 16
            if in_window:
                if rebalance:
                    n_moves += len(router.rebalance(
                        spread_threshold=2, max_moves=2))
                occ = [int(r.frontend.load_snapshot()
                           .get("engine_running", 0))
                       for r in router.replicas]
                spreads.append(max(occ) - min(occ))
            time.sleep(0.01)
        errors = sum(1 for h in handles if h.status != "done")
        lost = dup = 0
        parity = True
        for h, p in zip(handles, prompts):
            exp = _sim_expected(p, max_new)
            got = [int(t) for t in h.tokens]
            lost += max(0, len(exp) - len(got))
            dup += max(0, len(got) - len(exp))
            if got != exp:
                parity = False
        stats = router.stats()
        if rebalance:
            problems = validate_journeys(router.export_chrome(None))
            if problems:
                raise RuntimeError(
                    "transport journey validation failed: "
                    + "; ".join(problems[:5]))
    finally:
        router.close(timeout=30)
        for srv in servers:
            srv.close()
        for fe in fronts:
            fe.close(timeout=10)
    return {
        "parity": parity, "errors": errors, "lost": lost, "dup": dup,
        "n_migrated": int(stats["migrated"]),
        "n_migrate_failed": int(stats["migrate_failed"]),
        "n_moves": n_moves,
        "mean_spread": float(np.mean(spreads)) if spreads else 0.0,
        "n_requests": n_requests,
    }


def _transport_case(inf, eng_kw, prompts, paged_out,
                    max_new_tokens: int) -> dict:
    """Cross-host transport + live migration, two legs:

    * REAL engines over loopback HTTP — a fleet built entirely from
      :class:`RemoteReplica` clients (``engines=[]``) must stream
      greedy bit-identical to the in-process paged engine, and one
      running request live-migrates mid-decode (KV blocks + cursor
      over the wire) finishing bit-identical with zero lost or
      duplicated tokens;
    * SIMULATED 3-replica fleet under skew — periodic ``rebalance``
      passes keep the sampled post-rebalance occupancy spread below
      the unbalanced control run's mean, with zero lost/duplicated
      tokens and a validating journey export (migration hops
      connected).

    The source replica's decode chunk is throttled (a plain sleep
    wrapper — the driver thread must keep reaching iteration
    boundaries, where migration verbs execute) so the stream is
    reliably mid-flight when the migration lands.
    """
    from ..serving import FleetRouter, ServingEngine
    from ..serving.fleet import RemoteReplica, ReplicaServer
    from ..serving.frontend.frontend import ServingFrontend
    from ..telemetry.journey import validate_journeys

    engines = [ServingEngine(engine=inf, paged=True, **eng_kw)
               for _ in range(2)]
    for eng in engines:                     # charge compiles up front
        eng.run(list(prompts), max_new_tokens=max_new_tokens)
    # the migration leg's oracle: computed in-process BEFORE the
    # frontends take the engines over; sized to fit the tiny model's
    # max_seq_len with the full prompt
    mig_prompt = prompts[0]
    mig_new = int(engines[0].max_seq_len) - len(mig_prompt) - 8
    if mig_new < 16:
        raise RuntimeError(
            f"model too small for the migration leg: mig_new={mig_new}")
    mig_oracle = engines[0].run(
        [mig_prompt], max_new_tokens=mig_new)[0].output_ids

    fronts = [ServingFrontend(eng, telemetry_label=str(i))
              for i, eng in enumerate(engines)]
    servers = [ReplicaServer(fe) for fe in fronts]
    remotes = [RemoteReplica("127.0.0.1", srv.port, label=f"loop{i}")
               for i, srv in enumerate(servers)]
    router = FleetRouter([], remotes=remotes)
    real_chunk = engines[0]._jit_decode_chunk
    try:
        # ---- leg 1a: loopback streaming parity -------------------------
        handles = [router.submit(p, max_new_tokens=max_new_tokens)
                   for p in prompts]
        statuses = [h.result(timeout=300) for h in handles]
        real_errors = sum(1 for s in statuses if s != "done")
        loop_parity = (real_errors == 0 and all(
            np.array_equal(h.output_ids, paged_out[i])
            for i, h in enumerate(handles)))
        if not loop_parity:
            raise RuntimeError(
                "loopback-transport routed streams diverged from the "
                f"in-process paged engine: statuses={statuses}")

        # ---- leg 1b: live KV-block migration mid-decode ----------------
        def slow_chunk(*a, **k):
            time.sleep(0.05)                # widen the mid-flight window
            return real_chunk(*a, **k)

        engines[0]._jit_decode_chunk = slow_chunk
        rep0, rep1 = router.replicas
        rep1.dead = True                    # deterministic placement
        mig_h = router.submit(mig_prompt, max_new_tokens=mig_new)
        t_mig = time.monotonic() + 60.0
        while (mig_h._remote_uid is None or len(mig_h.tokens) < 4) \
                and not mig_h.done and time.monotonic() < t_mig:
            time.sleep(0.005)
        rep1.dead = False
        if mig_h.done or mig_h._remote_uid is None:
            raise RuntimeError(
                "migration target stream was not mid-flight: "
                f"status={mig_h.status} tokens={len(mig_h.tokens)}")
        if not router.migrate(int(mig_h._remote_uid), rep0, rep1):
            raise RuntimeError("live migration of the throttled stream "
                               "failed")
        engines[0]._jit_decode_chunk = real_chunk
        if mig_h.result(timeout=120) != "done":
            raise RuntimeError(
                f"migrated stream did not finish: {mig_h.status}")
        mig_parity = bool(np.array_equal(mig_h.output_ids, mig_oracle))
        if not mig_parity:
            raise RuntimeError(
                "migrated stream diverged from the never-moved oracle")
        if len(mig_h.tokens) != mig_new:
            raise RuntimeError(
                f"migrated stream lost or duplicated tokens: "
                f"{len(mig_h.tokens)} != {mig_new}")
        real_stats = router.stats()
        if (real_stats["migrated"] != 1 or real_stats["migrate_failed"]
                or real_stats["migrate_bytes"] <= 0):
            raise RuntimeError(
                f"migration counters off: migrated="
                f"{real_stats['migrated']} "
                f"failed={real_stats['migrate_failed']} "
                f"bytes={real_stats['migrate_bytes']}")
        problems = validate_journeys(router.export_chrome(None))
        if problems:
            raise RuntimeError(
                "transport journey validation failed: "
                + "; ".join(problems[:5]))
        real_lost = max(0, mig_new - len(mig_h.tokens))
        real_dup = max(0, len(mig_h.tokens) - mig_new)
    finally:
        engines[0]._jit_decode_chunk = real_chunk
        router.close(timeout=60)
        for srv in servers:
            srv.close()
        for fe in fronts:
            fe.close(timeout=10)

    # ---- leg 2: skewed simulated fleet, rebalance vs control -----------
    rebal = _transport_sim_fleet(rebalance=True)
    control = _transport_sim_fleet(rebalance=False)
    if not (rebal["parity"] and control["parity"]):
        raise RuntimeError(
            f"simulated transport streams diverged: rebal={rebal} "
            f"control={control}")
    if rebal["n_migrated"] < 1:
        raise RuntimeError(
            f"skewed workload triggered no live migrations: {rebal}")
    if rebal["mean_spread"] >= control["mean_spread"]:
        raise RuntimeError(
            f"rebalancing did not bound the occupancy spread: "
            f"rebalanced {rebal['mean_spread']:.2f} vs control "
            f"{control['mean_spread']:.2f}")

    total_errors = real_errors + rebal["errors"] + control["errors"]
    total_lost = real_lost + rebal["lost"] + control["lost"]
    total_dup = real_dup + rebal["dup"] + control["dup"]
    n_failed = real_stats["migrate_failed"] + rebal["n_migrate_failed"]
    return {"transport": {
        "loopback_parity": float(loop_parity),
        "migration_parity": float(mig_parity),
        # binary indicators (the raw counts below are timing-shaped):
        # at least one live migration on each leg...
        "migrated": float(real_stats["migrated"] == 1
                          and rebal["n_migrated"] >= 1),
        # ...and a failed migration must never lose a stream (failure
        # degrades to a load-balancing miss by design)
        "migrate_failed": float(
            n_failed > 0 and bool(total_errors or total_lost
                                  or total_dup)),
        "errors": total_errors,
        "lost_tokens": total_lost,
        "duplicate_tokens": total_dup,
        "occupancy_spread": rebal["mean_spread"],
        "control_spread": control["mean_spread"],
        "n_migrated": real_stats["migrated"] + rebal["n_migrated"],
        "n_migrate_failed": n_failed,
        "n_moves": rebal["n_moves"],
        "migrate_bytes": real_stats["migrate_bytes"],
        "sim_requests": rebal["n_requests"],
    }}


def _fleetobs_case(*, n_requests: int = 12, prompt_len: int = 8,
                   max_new: int = 16, ttl_s: float = 0.75,
                   seed: int = 3) -> dict:
    """Fleet observability plane, two legs:

    * LIVE — a 3-pod mixed local+remote hierarchy (two pods of
      in-process simulated replicas, one pod of loopback-HTTP
      :class:`RemoteReplica` clients) behind
      ``RootRouter.serve_metrics``: after a routed batch, one GET of
      ``/fleet/metrics`` must show every replica ``up 1`` with
      ``pod=``/``replica=`` labels, exactly one ``# TYPE`` header per
      family, and every ``dstpu_fleet_pod_*`` rollup family; killing
      the remote pod's second replica (its :class:`ReplicaServer`
      closes under it) must flip EXACTLY that series to ``up 0``
      within one TTL — the dark replica renders, it never vanishes;
    * JOURNEY — a deterministic sim-world fleet loses a whole pod
      mid-stream (the test_hierarchy failover scenario): zero lost
      streams, and the merged hierarchy Perfetto export must pass
      ``validate_journeys`` with the cross-pod hop CONNECTED on the
      pod lane (pid 5) — the regression gate for the trace-context
      drop this PR fixed in the failover/re-submit paths.
    """
    import urllib.request

    from ..serving.fleet import (RemoteReplica, ReplicaServer,
                                 RootConfig, RootRouter,
                                 SimReplicaConfig, SimWorld,
                                 build_sim_fleet, sim_expected)
    from ..serving.frontend.frontend import ServingFrontend
    from ..telemetry.fleetobs import POD_FAMILIES
    from ..telemetry.journey import validate_journeys

    def _get(url: str) -> str:
        with urllib.request.urlopen(url, timeout=10) as r:
            return r.read().decode("utf-8")

    def _up_lines(text: str) -> dict:
        out = {}
        for ln in text.splitlines():
            if ln.startswith("dstpu_fleet_replica_up{"):
                out[ln.rsplit(" ", 1)[0]] = float(ln.rsplit(" ", 1)[1])
        return out

    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 512, (prompt_len,)).astype(np.int32)
               for _ in range(n_requests)]

    # ---- leg 1: live mixed local+remote plane --------------------------
    root = RootRouter(config=RootConfig())
    rem_engines = [SimulatedEngine(max_batch=4, decode_chunk=4,
                                   chunk_time_s=0.002) for _ in range(2)]
    fronts = [ServingFrontend(eng, telemetry_label=f"obs{i}")
              for i, eng in enumerate(rem_engines)]
    servers = [ReplicaServer(fe) for fe in fronts]
    try:
        for pod in ("p0", "p1"):
            root.add_pod(pod, engines=[
                SimulatedEngine(max_batch=4, decode_chunk=4,
                                chunk_time_s=0.002) for _ in range(2)])
        root.add_pod("p2", remotes=[
            RemoteReplica("127.0.0.1", srv.port, label=f"obs{i}")
            for i, srv in enumerate(servers)])
        handles = [root.submit(p, max_new_tokens=max_new)
                   for p in prompts]
        statuses = [h.result(timeout=120) for h in handles]
        if any(s != "done" for s in statuses):
            raise RuntimeError(
                f"fleetobs routed batch failed: {statuses}")
        parity = all([int(t) for t in h.tokens]
                     == _sim_expected(p, max_new)
                     for h, p in zip(handles, prompts))
        if not parity:
            raise RuntimeError(
                "fleetobs routed streams diverged from the simulated "
                "oracle")

        srv = root.serve_metrics(ttl_s=ttl_s)
        t0 = time.perf_counter()
        text = _get(srv.url + "/fleet/metrics")
        scrape_s = time.perf_counter() - t0
        pods_doc = json.loads(_get(srv.url + "/fleet/pods"))
        ups = _up_lines(text)
        n_up_initial = sum(1 for v in ups.values() if v == 1.0)
        if len(ups) != 6 or n_up_initial != 6:
            raise RuntimeError(
                f"expected 6/6 replicas up at steady state, saw "
                f"{n_up_initial}/{len(ups)}")
        type_names = [ln.split()[2] for ln in text.splitlines()
                      if ln.startswith("# TYPE ")]
        types_unique = len(type_names) == len(set(type_names))
        if not types_unique:
            dupes = sorted({n for n in type_names
                            if type_names.count(n) > 1})
            raise RuntimeError(
                f"duplicate TYPE headers in the merged exposition: "
                f"{dupes}")
        fams_present = all(f"dstpu_{fam}" in text
                           for fam in POD_FAMILIES)
        if not fams_present:
            missing = [f for f in POD_FAMILIES
                       if f"dstpu_{f}" not in text]
            raise RuntimeError(
                f"pod rollup families missing from the exposition: "
                f"{missing}")
        if pods_doc["n_pods"] != 3 or pods_doc["n_replicas"] != 6:
            raise RuntimeError(
                f"/fleet/pods topology off: {pods_doc['n_pods']} pods, "
                f"{pods_doc['n_replicas']} replicas")

        # kill the remote pod's second replica: its server closes under
        # it, the next refresh past the TTL must flip up -> 0
        servers[1].close()
        time.sleep(ttl_s + 0.5)
        text2 = _get(srv.url + "/fleet/metrics")
        ups2 = _up_lines(text2)
        n_up_after = sum(1 for v in ups2.values() if v == 1.0)
        dark = [k for k, v in ups2.items() if v == 0.0]
        dark_ok = (len(ups2) == 6 and len(dark) == 1
                   and 'pod="p2"' in dark[0])
        if n_up_after != 5 or not dark_ok:
            raise RuntimeError(
                f"killed replica did not flip to up 0 within one TTL: "
                f"up={n_up_after}/6 dark={dark}")
    finally:
        root.close(timeout=30)
        for s in servers:
            s.close()
        for fe in fronts:
            fe.close(timeout=10)

    # ---- leg 2: cross-pod failover journey validates -------------------
    world = SimWorld(seed=seed)
    sim_root = RootRouter(config=RootConfig(), clock=world.clock)
    build_sim_fleet(world, sim_root, n_pods=3, pod_size=2,
                    config=SimReplicaConfig(decode_tokens_per_s=8.0))
    try:
        sim_handles = [sim_root.submit([3, i + 1], max_new_tokens=16)
                       for i in range(12)]
        world.clock.run_for(0.5)             # mid-stream everywhere
        victim = sim_root._placements[-1]["pod"]
        sim_root.mark_pod_lost(victim)
        for rep in list(sim_root.pods[victim].replicas):
            rep.frontend.fail(RuntimeError("rack power"))
        world.clock.run_for(60.0)
        for i, h in enumerate(sim_handles):
            if h.status != "done" \
                    or h.tokens != sim_expected([3, i + 1], 16):
                raise RuntimeError(
                    f"failover lost or corrupted stream {i}: "
                    f"{h.status}")
        n_failover = sim_root.stats()["pod_failover"]
        if n_failover < 1:
            raise RuntimeError("pod loss triggered no cross-pod "
                               "failover")
        trace_obj = sim_root.export_chrome(None)
        problems = validate_journeys(trace_obj)
        if problems:
            raise RuntimeError(
                "failover journey validation failed: "
                + "; ".join(problems[:5]))
        n_pod_events = sum(
            1 for e in trace_obj["traceEvents"] if e.get("pid") == 5
            and e.get("ph") in ("X", "i", "s", "f"))
        if n_pod_events < 1:
            raise RuntimeError("hierarchy trace has no pod-lane events")
    finally:
        sim_root.close()

    return {"fleetobs": {
        "n_pods": 3,
        "n_replicas": 6,
        "n_up_initial": n_up_initial,
        "n_up_after_kill": n_up_after,
        "dark_replica_up_zero": float(dark_ok),
        "type_headers_unique": float(types_unique),
        "pod_families_present": float(fams_present),
        "parity": float(parity),
        "scrape_s": scrape_s,
        "ttl_s": ttl_s,
        "journey_validate_ok": 1.0,
        "pod_failover": n_failover,
        "pod_lane_events": n_pod_events,
    }}


def _ensure_virtual_devices(n: int = 8) -> None:
    """The tp=2 case needs a multi-device mesh; on CPU that is the XLA
    host-platform device-count flag, which must be set before jax
    initializes. No-op when jax is already imported (the caller — e.g.
    pytest's conftest — owns the flag then)."""
    import sys
    if "jax" in sys.modules:
        return
    flag = f"--xla_force_host_platform_device_count={n}"
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (flags + " " + flag).strip()


def main(argv=None):
    from ..utils.platform import enable_compile_cache
    enable_compile_cache()       # before any compile
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=32)
    ap.add_argument("--max-batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--decode-chunk", type=int, default=8)
    ap.add_argument("--sim-requests", type=int, default=16,
                    help="requests in the simulated-replica scaling case")
    ap.add_argument("--sim-chunk-time-ms", type=float, default=5.0,
                    help="simulated device time per decode chunk")
    ap.add_argument("--json-out", type=str, default=None,
                    help="also write the result dict to this JSON file")
    ap.add_argument("--slo", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="evaluate SLO burn rates across the crash case "
                         "(--no-slo skips the slo block)")
    ap.add_argument("--transport", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the cross-host transport + live-migration "
                         "case (--no-transport skips it)")
    ap.add_argument("--fleetobs", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the fleet observability plane case: live "
                         "mixed local+remote /fleet/metrics + failover "
                         "journey validation (--no-fleetobs skips it)")
    ap.add_argument("--trace-out", type=str, default=None,
                    help="write the merged fleet journey Perfetto trace "
                         "(validated either way)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    _ensure_virtual_devices(8)
    result = run_bench(n_requests=args.n_requests,
                       max_new_tokens=args.max_new_tokens,
                       max_batch=args.max_batch,
                       prompt_len=args.prompt_len,
                       decode_chunk=args.decode_chunk,
                       seed=args.seed,
                       sim_requests=args.sim_requests,
                       sim_chunk_time_s=args.sim_chunk_time_ms / 1e3,
                       slo=args.slo, transport=args.transport,
                       fleetobs=args.fleetobs,
                       trace_out=args.trace_out)
    print(json.dumps(result, indent=2))
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(result, f, indent=2)
    return result


if __name__ == "__main__":
    main()
