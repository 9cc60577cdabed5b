"""Bench regression sentry: diff ``BENCH_*.json`` rounds against
tolerance bands.

ROADMAP Open item 5's second failure mode: bench rounds landed numbers
nobody compared, so a regression (throughput, phase share creep, HBM
growth) only surfaced when someone eyeballed two JSON files. This module
is the machine that does the comparing: named metric paths into the
bench document, each with a direction and a tolerance band, diffed
baseline-vs-current into a machine-readable ``regressions`` block.
``bin/benchdiff`` is the CLI; ``bin/obs_smoke.sh`` gates CI on it
(committed baseline vs a fresh run must pass, a seeded synthetic
regression must fail).

Stdlib-only — never imports JAX (the sentry must run on a machine with
no accelerator stack at all).

Tolerance philosophy: timing metrics (tokens/s, TTFT) get wide bands
(30-50%) because CI machines are shared and noisy; structural metrics
(compile counts, parity flags, phase *shares*) get exact or tight
bands because they are deterministic — a compile-count bump is a real
retrace regression no matter how noisy the wall clock was.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

_MISSING = object()

#: directions: how ``current`` may move relative to ``baseline`` before
#: the check regresses.
HIGHER = "higher"        # throughput-like: regression when it DROPS
LOWER = "lower"          # latency/bytes-like: regression when it GROWS
SHIFT = "shift"          # two-sided: |current - baseline| > abs_tol


@dataclasses.dataclass
class MetricSpec:
    """One watched metric. ``path`` is a tuple of keys into the bench
    dict (tuples, not '/'-joined strings — span names like
    ``serve/chunk_host_wait`` contain '/'). ``rel_tol`` is the
    fractional band for higher/lower; ``abs_tol`` (when set) is an
    absolute band OR'd with it — the check regresses only when both
    bands are exceeded, so near-zero baselines don't flag on noise."""
    path: Tuple[str, ...]
    direction: str = HIGHER
    rel_tol: float = 0.3
    abs_tol: Optional[float] = None
    note: str = ""

    @property
    def name(self) -> str:
        return ".".join(self.path)


def lookup(doc: Any, path: Sequence[str]) -> Any:
    for key in path:
        if not isinstance(doc, dict) or key not in doc:
            return _MISSING
        doc = doc[key]
    return doc


SERVING_SPECS: List[MetricSpec] = [
    MetricSpec(("chunked_tokens_per_s",), HIGHER, 0.30),
    MetricSpec(("per_token_tokens_per_s",), HIGHER, 0.30),
    MetricSpec(("chunk_speedup",), HIGHER, 0.25),
    MetricSpec(("greedy_parity",), SHIFT, abs_tol=0.0,
               note="bit-exactness is binary"),
    MetricSpec(("decode_chunk_compiles",), SHIFT, abs_tol=0.0,
               note="pinned retrace budget"),
    MetricSpec(("prefill_programs",), SHIFT, abs_tol=0.0),
    MetricSpec(("phase_breakdown", "chunked", "serve/chunk_host_wait",
                "share_of_wall"), SHIFT, abs_tol=0.15),
    MetricSpec(("phase_breakdown", "chunked", "serve/prefill",
                "share_of_wall"), SHIFT, abs_tol=0.15),
    MetricSpec(("mfu", "flops_per_token"), LOWER, 0.25,
               note="compiled flops per token growing = model program "
                    "got heavier"),
    MetricSpec(("hbm", "decode_chunk", "temp_bytes"), LOWER, 0.25),
    MetricSpec(("hbm", "decode_chunk", "argument_bytes"), LOWER, 0.25),
    MetricSpec(("hbm", "arena", "arena_bytes"), LOWER, 0.10,
               note="KV arena footprint is deterministic"),
    # ---- paged block-pool KV (--paged A/B + shared-prefix workload) ----
    MetricSpec(("paged", "greedy_parity"), SHIFT, abs_tol=0.0,
               note="paged vs dense bit-exactness is binary"),
    MetricSpec(("paged", "decode_chunk_compiles"), SHIFT, abs_tol=0.0,
               note="pinned paged retrace budget"),
    MetricSpec(("paged", "block_pool", "bytes_per_block"), SHIFT,
               abs_tol=0.0, note="pool geometry is deterministic"),
    MetricSpec(("paged", "block_pool", "blocks_total"), SHIFT,
               abs_tol=0.0),
    MetricSpec(("paged", "shared_prefix", "prefix_cache_hits"), SHIFT,
               abs_tol=0.0,
               note="N-1 hits or the shared prefill ran more than once"),
    MetricSpec(("paged", "shared_prefix", "effective_seq_multiplier"),
               HIGHER, 0.25,
               note="sequences held per unit of KV HBM vs dense slots"),
    MetricSpec(("paged", "shared_prefix", "prefix_hit_rate"), HIGHER,
               0.10, abs_tol=0.05),
    # ---- speculative decoding (--speculative A/B, repetitive workload) ----
    MetricSpec(("speculative", "greedy_parity"), SHIFT, abs_tol=0.0,
               note="spec vs sequential bit-exactness is binary"),
    MetricSpec(("speculative", "decode_chunk_compiles"), SHIFT,
               abs_tol=0.0, note="pinned spec retrace budget"),
    MetricSpec(("speculative", "acceptance_rate"), SHIFT, abs_tol=0.25,
               note="drafter quality band on the pinned workload"),
    MetricSpec(("speculative", "spec_speedup"), HIGHER, 0.30,
               note="accepted drafts must keep buying wall-clock"),
    # ---- int8 KV (--kv-dtype int8 A/B) ----
    MetricSpec(("int8_kv", "greedy_parity_paged"), SHIFT, abs_tol=0.0,
               note="int8 dense vs int8 paged bit-exactness is binary"),
    MetricSpec(("int8_kv", "kv_bytes_ratio"), SHIFT, abs_tol=0.0,
               note="quantized/fp arena byte ratio is deterministic"),
    MetricSpec(("int8_kv", "kv_bytes_saved"), SHIFT, abs_tol=0.0),
    MetricSpec(("int8_kv", "decode_chunk_compiles"), SHIFT, abs_tol=0.0,
               note="pinned int8 retrace budget"),
    # ---- fused chunked prefill (--fused A/B vs the bucketed reference) ----
    MetricSpec(("fused", "greedy_parity"), SHIFT, abs_tol=0.0,
               note="fused chunked prefill vs bucketed bit-exactness "
                    "is binary"),
    MetricSpec(("fused", "decode_chunk_compiles"), SHIFT, abs_tol=0.0,
               note="pinned fused retrace budget"),
    MetricSpec(("fused", "inline_prefill_tokens"), SHIFT, abs_tol=0.0,
               note="every prompt token of the pinned workload appends "
                    "in-scan — deterministic count"),
    MetricSpec(("fused", "prefill_stall_s"), LOWER, 0.50, abs_tol=0.05,
               note="fused mode must keep decode launches free of "
                    "prefill preemption (ROADMAP item 4: ~0)"),
    # ---- tiered KV cache (--tiered: 10x-over-HBM workload) ----
    MetricSpec(("tiered", "greedy_parity"), SHIFT, abs_tol=0.0,
               note="tiered vs all-HBM bit-exactness is binary — the "
                    "demote/promote round trip is storage movement"),
    MetricSpec(("tiered", "oversubscription"), SHIFT, abs_tol=0.0,
               note="workload geometry (aggregate context over HBM "
                    "pool) is deterministic"),
    MetricSpec(("tiered", "tiered_vs_all_hbm"), HIGHER, 0.25,
               note="tiered throughput over the all-HBM reference; the "
                    ">= 0.8 floor is asserted inside the bench"),
    MetricSpec(("tiered", "tiered_tokens_per_s"), HIGHER, 0.30),
    MetricSpec(("tiered", "decode_chunk_compiles"), SHIFT, abs_tol=1.0,
               note="pinned relative to the untiered run inside the "
                    "bench (+1 allowance for the first promotion-built "
                    "pool); one count of cross-round slack here"),
    MetricSpec(("tiered", "promote_failures"), SHIFT, abs_tol=0.0,
               note="a failed promotion degrades that request to a "
                    "re-prefill — zero on the pinned workload"),
    # ---- fused decode megakernel (--megakernel A/B vs composed) ----
    MetricSpec(("megakernel", "greedy_parity"), SHIFT, abs_tol=0.0,
               note="megakernel vs composed greedy bit-exactness is "
                    "binary — the fused epilogue must not move a ulp"),
    MetricSpec(("megakernel", "variant_isolation"), SHIFT, abs_tol=0.0,
               note="the _megakernel variant must never compile under "
                    "the composed variant's name (cache isolation)"),
    MetricSpec(("megakernel", "decode_chunk_compiles"), SHIFT,
               abs_tol=0.0, note="pinned megakernel retrace budget"),
    MetricSpec(("megakernel", "paged", "greedy_parity"), SHIFT,
               abs_tol=0.0),
    MetricSpec(("megakernel", "paged", "decode_chunk_compiles"), SHIFT,
               abs_tol=0.0, note="pinned paged megakernel retrace "
                                 "budget"),
]

FRONTEND_SPECS: List[MetricSpec] = [
    MetricSpec(("capacity_tokens_per_s",), HIGHER, 0.30),
    MetricSpec(("greedy_streaming_parity",), SHIFT, abs_tol=0.0),
    MetricSpec(("high_ttft_p99_s",), LOWER, 0.50, abs_tol=0.25),
    MetricSpec(("frontend_snapshot", "frontend/ttft_p99_s"),
               LOWER, 0.50, abs_tol=0.25),
    MetricSpec(("phase_breakdown", "serve/chunk_host_wait",
                "share_of_wall"), SHIFT, abs_tol=0.20),
    MetricSpec(("mfu", "flops_per_token"), LOWER, 0.25),
    MetricSpec(("hbm", "decode_chunk", "temp_bytes"), LOWER, 0.25),
    MetricSpec(("hbm", "arena", "arena_bytes"), LOWER, 0.10),
    # ---- SLO burn-rate engine (live /slo self-fetch) ----
    MetricSpec(("slo", "endpoint_ok"), SHIFT, abs_tol=0.0,
               note="the bench GETs /slo live and checks its schema"),
    MetricSpec(("slo", "n_slos"), SHIFT, abs_tol=0.0,
               note="stock objective count is deterministic"),
    # ---- per-tenant goodput accounting (live /tenants self-fetch) ----
    MetricSpec(("tenant_goodput", "endpoint_ok"), SHIFT, abs_tol=0.0,
               note="the bench GETs /tenants live and checks its schema"),
    MetricSpec(("tenant_goodput", "labelled_series_ok"), SHIFT,
               abs_tol=0.0,
               note="tenant-labelled goodput gauges round-trip through "
                    "the /metrics scrape"),
    MetricSpec(("tenant_goodput", "n_tenants"), SHIFT, abs_tol=0.0,
               note="default + interactive + bulk on the pinned "
                    "workload"),
    MetricSpec(("tenant_goodput", "tenants", "default",
                "goodput_fraction"), SHIFT, abs_tol=0.0,
               note="parity traffic has no SLO and all finishes done — "
                    "goodput is exactly 1.0"),
    # ---- fused chunked prefill under the mixed long-prompt/short-decode
    # overload (the ROADMAP item-4 gate) ----
    MetricSpec(("fused_mixed", "greedy_parity"), SHIFT, abs_tol=0.0,
               note="fused vs bucketed token streams under the mixed "
                    "workload, binary"),
    MetricSpec(("fused_mixed", "tpot_p99_improvement"), HIGHER, 0.40,
               abs_tol=2.0,
               note="fused p99 TPOT speedup over bucketed; the >= 2x "
                    "acceptance floor is asserted inside the bench"),
    MetricSpec(("fused_mixed", "ttft_p99_ratio"), LOWER, 0.60,
               abs_tol=0.5,
               note="fused TTFT p99 / bucketed TTFT p99 — chunking the "
                    "prompt must not blow up time-to-first-token"),
]

FLEET_SPECS: List[MetricSpec] = [
    # ---- data-parallel router (2 replicas vs 1, open-loop burst) ----
    MetricSpec(("replica_scaling",), HIGHER, 0.20,
               note="2-replica router throughput over single-replica; "
                    "the acceptance floor (>= 1.6x) is asserted inside "
                    "the bench itself"),
    MetricSpec(("fleet_tokens_per_s",), HIGHER, 0.30),
    MetricSpec(("single_tokens_per_s",), HIGHER, 0.30),
    MetricSpec(("router_streaming_parity",), SHIFT, abs_tol=0.0,
               note="routed streams vs ServingEngine.run is binary"),
    MetricSpec(("router", "shed",), SHIFT, abs_tol=0.0,
               note="the pinned workload must not shed"),
    MetricSpec(("router", "rerouted",), SHIFT, abs_tol=0.0,
               note="no crashes injected in the bench workload"),
    # ---- tensor-parallel serving (tp=2 on the 8-device CPU mesh) ----
    MetricSpec(("tp", "greedy_parity"), SHIFT, abs_tol=0.0,
               note="tp=2 vs tp=1 bit-exactness is binary"),
    MetricSpec(("tp", "decode_chunk_compiles"), SHIFT, abs_tol=0.0,
               note="pinned tp retrace budget"),
    # ---- prefill/decode disaggregation ----
    MetricSpec(("disagg", "greedy_parity"), SHIFT, abs_tol=0.0,
               note="disaggregated handoff bit-exactness is binary"),
    MetricSpec(("disagg", "decode_chunk_compiles"), SHIFT, abs_tol=0.0,
               note="pinned disagg retrace budget"),
    MetricSpec(("disagg", "handoffs"), SHIFT, abs_tol=0.0,
               note="one D2D handoff per prefilled request"),
    # ---- crash observability (injected mid-stream replica crash) ----
    MetricSpec(("crash", "journey_complete"), SHIFT, abs_tol=0.0,
               note="every request one connected journey, binary"),
    MetricSpec(("crash", "postmortem_inflight_match"), SHIFT,
               abs_tol=0.0,
               note="postmortem in-flight set == rerouted handles, "
                    "all salvageable, binary"),
    MetricSpec(("crash", "rerouted_parity"), SHIFT, abs_tol=0.0,
               note="rerouted greedy streams stay bit-identical"),
    MetricSpec(("crash", "errors"), SHIFT, abs_tol=0.0,
               note="zero: the wedged mid-chunk request replays on the "
                    "survivor instead of erroring"),
    MetricSpec(("crash", "rerouted"), SHIFT, abs_tol=0.0,
               note="every in-flight request re-homes on the survivor"),
    MetricSpec(("crash", "replayed"), SHIFT, abs_tol=0.0,
               note="exactly the prefilled request replays its emitted "
                    "prefix"),
    MetricSpec(("journey", "complete"), SHIFT, abs_tol=0.0,
               note="validate_journeys over the merged export, binary"),
    MetricSpec(("journey", "rerouted_links"), SHIFT, abs_tol=0.0,
               note="one reroute flow link per adopted handle"),
    MetricSpec(("slo", "burn_moved"), SHIFT, abs_tol=0.0,
               note="ttft burn must rise in the crash window (replay "
                    "keeps the original submit time)"),
    MetricSpec(("slo", "burn_recovered_flag"), SHIFT, abs_tol=0.0,
               note="fast burn must fall back after the window drains"),
    MetricSpec(("slo", "availability_burn"), SHIFT, abs_tol=0.0,
               note="zero-loss crash: the availability budget never "
                    "burns"),
    # ---- elastic fleet (kill a replica mid-stream at 2x load) ----
    MetricSpec(("elastic", "errors"), SHIFT, abs_tol=0.0,
               note="zero requests resolve error across the incident"),
    MetricSpec(("elastic", "lost"), SHIFT, abs_tol=0.0,
               note="zero requests lost (every status is done)"),
    MetricSpec(("elastic", "replay_parity"), SHIFT, abs_tol=0.0,
               note="replayed/rerouted streams bit-identical, binary"),
    MetricSpec(("elastic", "duplicate_tokens"), SHIFT, abs_tol=0.0,
               note="dedup at the chunk boundary: no stream drops or "
                    "repeats a token"),
    MetricSpec(("elastic", "replayed"), SHIFT, abs_tol=0.0,
               note="the prefilled stream replays, deterministic count"),
    MetricSpec(("elastic", "rerouted"), SHIFT, abs_tol=0.0,
               note="all 2x-load requests re-home, deterministic count"),
    MetricSpec(("elastic", "returned_to_target"), SHIFT, abs_tol=0.0,
               note="the controller ends the incident at target size"),
    MetricSpec(("elastic", "scale_up"), SHIFT, abs_tol=0.0,
               note="below-target restore + surge, deterministic"),
    MetricSpec(("elastic", "scale_down"), SHIFT, abs_tol=0.0,
               note="the surge retires gracefully once burn calms"),
    MetricSpec(("elastic", "drained"), SHIFT, abs_tol=0.0,
               note="poll_draining finalizes the retirement"),
    MetricSpec(("elastic", "burn_moved"), SHIFT, abs_tol=0.0,
               note="ttft burn must rise during the incident"),
    MetricSpec(("elastic", "burn_recovered_flag"), SHIFT, abs_tol=0.0,
               note="the fast window is clean after recovery"),
    MetricSpec(("elastic", "recovery_ttft_p99_s"), LOWER, 1.00,
               abs_tol=2.0,
               note="recovery-window TTFT stays bounded (wedge hold + "
                    "survivor backlog; CPU timing is noisy)"),
    # ---- fleet-wide per-tenant goodput (router merge) ----
    MetricSpec(("tenant_goodput", "n_tenants"), SHIFT, abs_tol=0.0,
               note="tenant-a + tenant-b on the pinned parity workload"),
    MetricSpec(("tenant_goodput", "tenants", "tenant-a",
                "goodput_fraction"), SHIFT, abs_tol=0.0,
               note="no SLO, all done — exactly 1.0"),
    MetricSpec(("tenant_goodput", "tenants", "tenant-b",
                "goodput_fraction"), SHIFT, abs_tol=0.0),
    # ---- cross-host transport + live KV-block migration (--transport) ----
    MetricSpec(("transport", "loopback_parity"), SHIFT, abs_tol=0.0,
               note="loopback-HTTP routed streams vs ServingEngine.run "
                    "bit-exactness is binary"),
    MetricSpec(("transport", "migration_parity"), SHIFT, abs_tol=0.0,
               note="real-KV migration mid-decode stays greedy "
                    "bit-identical — zero lost/dup tokens, binary"),
    MetricSpec(("transport", "migrated"), SHIFT, abs_tol=0.0,
               note="binary: at least one live migration on each leg "
                    "(raw counts are timing-shaped and unwatched)"),
    MetricSpec(("transport", "migrate_failed"), SHIFT, abs_tol=0.0,
               note="binary: a failed migration must never lose a "
                    "stream — failure degrades to a load-balancing "
                    "miss"),
    MetricSpec(("transport", "lost_tokens"), SHIFT, abs_tol=0.0,
               note="zero tokens lost across migrations"),
    MetricSpec(("transport", "duplicate_tokens"), SHIFT, abs_tol=0.0,
               note="zero tokens duplicated across migrations"),
    MetricSpec(("transport", "errors"), SHIFT, abs_tol=0.0,
               note="no stream resolves error on the pinned workload"),
    MetricSpec(("transport", "occupancy_spread"), LOWER, 0.50,
               abs_tol=1.0,
               note="max-min per-replica running count after rebalance; "
                    "the hard bound is asserted inside the bench"),
    # ---- fleet observability plane (--fleetobs, telemetry/fleetobs.py) ----
    MetricSpec(("fleetobs", "n_replicas"), SHIFT, abs_tol=0.0,
               note="3-pod mixed local+remote topology is pinned"),
    MetricSpec(("fleetobs", "n_up_initial"), SHIFT, abs_tol=0.0,
               note="every replica scrapes up=1 at steady state"),
    MetricSpec(("fleetobs", "n_up_after_kill"), SHIFT, abs_tol=0.0,
               note="killing the remote replica flips exactly its "
                    "up series to 0 within one TTL"),
    MetricSpec(("fleetobs", "dark_replica_up_zero"), SHIFT, abs_tol=0.0,
               note="the dead replica renders up 0, never vanishes"),
    MetricSpec(("fleetobs", "type_headers_unique"), SHIFT, abs_tol=0.0,
               note="one TYPE header per family in the merged "
                    "exposition, binary"),
    MetricSpec(("fleetobs", "pod_families_present"), SHIFT, abs_tol=0.0,
               note="all dstpu_fleet_pod_* rollup families render"),
    MetricSpec(("fleetobs", "journey_validate_ok"), SHIFT, abs_tol=0.0,
               note="forced cross-pod failover journey passes "
                    "tputrace-style validation incl. pod-hop links, "
                    "binary"),
    MetricSpec(("fleetobs", "scrape_s"), LOWER, 1.00, abs_tol=1.0,
               note="full-fleet scrape wall time (loopback HTTP; CPU "
                    "timing is noisy)"),
]

KERNELS_SPECS: List[MetricSpec] = [
    # ---- BENCH_kernels.json (benchmarks/kernels_bench.py) ----
    MetricSpec(("megakernel", "greedy_parity"), SHIFT, abs_tol=0.0,
               note="composed-vs-fused spec int8 paged decode "
                    "bit-exactness is binary"),
    MetricSpec(("megakernel", "filter_bitwise"), SHIFT, abs_tol=0.0,
               note="sort-free filter output is bitwise vs the sorted "
                    "reference"),
    MetricSpec(("megakernel", "greedy_token_bitwise"), SHIFT,
               abs_tol=0.0),
    MetricSpec(("megakernel", "speedup_spec_int8_paged"), HIGHER, 0.25,
               note="fused over composed; the >= 1.5x floor is asserted "
                    "inside the bench (roofline proxy on CPU, measured "
                    "on TPU)"),
    MetricSpec(("megakernel", "traffic_ratio"), HIGHER, 0.10,
               note="HBM bytes composed/fused is deterministic "
                    "geometry"),
    MetricSpec(("tp_overlap", "tp2_overlapped_vs_tp1_unhidden"), LOWER,
               0.10, note="overlapped tp=2 step over tp=1; the <= 0.6 "
                          "ceiling is asserted inside the bench "
                          "(analytic step model)"),
    MetricSpec(("tp_overlap", "tp2_overlap_gain"), HIGHER, 0.10,
               note="unhidden over overlapped tp=2 step"),
    MetricSpec(("decode_microbench", "value"), HIGHER, 0.30,
               note="op-level Pallas-vs-XLA decode speedup (bench.py "
                    "case); null (skipped) on CPU hosts"),
]

FLEETSIM_SPECS: List[MetricSpec] = [
    # The simulator is deterministic (seeded virtual time), so nearly
    # everything here is a binary gate or an exact count — only the
    # wall-clock placement latencies are timing-shaped, and those are
    # gated by the in-bench 2x ratio bound, not diffed here.
    MetricSpec(("fleetsim_replicas",), SHIFT, abs_tol=0.0,
               note="the gated fleet size (1000) is part of the "
                    "bench's contract"),
    MetricSpec(("placement", "scaling_ok"), SHIFT, abs_tol=0.0,
               note="root placement p99 at 1000 replicas within 2x "
                    "the p99 at 10, binary"),
    MetricSpec(("prefix", "within_tol"), SHIFT, abs_tol=0.0,
               note="hierarchical prefix hit rate within 10% of the "
                    "flat-router oracle, binary"),
    MetricSpec(("prefix", "root_hit_rate"), HIGHER, 0.10,
               note="deterministic given the seed; drift means the "
                    "ring or the leaf affinity probe changed"),
    MetricSpec(("prefix", "lost"), SHIFT, abs_tol=0.0,
               note="no chaos in the affinity case: zero lost"),
    MetricSpec(("prefix", "duplicated"), SHIFT, abs_tol=0.0),
    MetricSpec(("prefix", "rejected"), SHIFT, abs_tol=0.0,
               note="the storm must not trip edge admission"),
    MetricSpec(("chaos", "lost"), SHIFT, abs_tol=0.0,
               note="zero lost streams through pod loss + zombie + "
                    "partition chaos, exact token-oracle audit"),
    MetricSpec(("chaos", "duplicated"), SHIFT, abs_tol=0.0,
               note="zero duplicated/diverged streams, exact audit"),
    MetricSpec(("chaos", "pending"), SHIFT, abs_tol=0.0,
               note="every stream reaches a terminal state"),
    MetricSpec(("chaos", "digest_match"), SHIFT, abs_tol=0.0,
               note="same seed reproduces the event log byte-for-byte "
                    "(sha256 over two full runs), binary"),
    MetricSpec(("chaos", "seed_sensitivity"), SHIFT, abs_tol=0.0,
               note="a different seed must diverge — the log actually "
                    "records the run"),
    MetricSpec(("chaos", "watchdog_kills"), SHIFT, abs_tol=0.0,
               note="exactly the zombie and the unhealed partition; "
                    "a skewed-but-healthy replica false-killed shows "
                    "up here"),
    MetricSpec(("chaos", "pod_failover"), SHIFT, abs_tol=0.0,
               note="pod loss salvages in-flight streams cross-pod, "
                    "deterministic count"),
    # ---- sim-time timeline export (sim_trace_events, --trace-out) ----
    MetricSpec(("chaos", "trace", "valid"), SHIFT, abs_tol=0.0,
               note="exported sim-time Chrome trace passes "
                    "validate_trace, binary"),
    MetricSpec(("chaos", "trace", "n_lanes"), SHIFT, abs_tol=0.0,
               note="one lane per sim replica plus the world lane — "
                    "deterministic topology"),
    MetricSpec(("chaos", "trace", "n_kill_arrows"), SHIFT, abs_tol=0.0,
               note="one flow arrow per watchdog kill, exact"),
    MetricSpec(("chaos", "trace", "n_chaos_instants"), SHIFT,
               abs_tol=0.0,
               note="pod-loss chaos renders as global-scope instants, "
                    "exact count"),
]

SPEC_SETS: Dict[str, List[MetricSpec]] = {
    "serving": SERVING_SPECS,
    "frontend": FRONTEND_SPECS,
    "fleet": FLEET_SPECS,
    "fleetsim": FLEETSIM_SPECS,
    "kernels": KERNELS_SPECS,
}


def detect_kind(doc: Dict[str, Any]) -> Optional[str]:
    if "chunked_tokens_per_s" in doc:
        return "serving"
    if "capacity_tokens_per_s" in doc:
        return "frontend"
    if "replica_scaling" in doc:
        return "fleet"
    if "fleetsim_replicas" in doc:
        return "fleetsim"
    if "decode_microbench" in doc:
        return "kernels"
    return None


def _check_one(spec: MetricSpec, base: Any, cur: Any) -> Dict[str, Any]:
    rec: Dict[str, Any] = {"metric": spec.name, "path": list(spec.path),
                           "direction": spec.direction,
                           "rel_tol": spec.rel_tol,
                           "abs_tol": spec.abs_tol}
    if spec.note:
        rec["note"] = spec.note
    if base is _MISSING or cur is _MISSING:
        rec["status"] = "missing"
        rec["missing_in"] = ("baseline" if base is _MISSING else "") + \
            ("+" if base is _MISSING and cur is _MISSING else "") + \
            ("current" if cur is _MISSING else "")
        return rec
    if base is None or cur is None:
        # a legitimately-unavailable metric (mfu on CPU) — not a
        # regression, not missing structure
        rec["status"] = "skipped"
        rec["baseline"], rec["current"] = base, cur
        return rec
    base_f, cur_f = float(base), float(cur)
    rec["baseline"], rec["current"] = base_f, cur_f
    delta = cur_f - base_f
    rec["delta"] = delta
    rec["rel_delta"] = delta / abs(base_f) if base_f else None
    if spec.direction == SHIFT:
        tol = spec.abs_tol if spec.abs_tol is not None else 0.0
        bad = abs(delta) > tol
    else:
        drift = -delta if spec.direction == HIGHER else delta
        bad = drift > spec.rel_tol * abs(base_f)
        if bad and spec.abs_tol is not None:
            bad = drift > spec.abs_tol     # both bands must be exceeded
    rec["status"] = "regression" if bad else "ok"
    return rec


def diff_benchmarks(baseline: Dict[str, Any], current: Dict[str, Any],
                    specs: Sequence[MetricSpec]) -> Dict[str, Any]:
    """Diff two bench documents over ``specs``. Returns the
    machine-readable block: ``checks`` (every spec's record),
    ``regressions`` / ``missing`` (the subsets), ``ok``."""
    checks = [_check_one(s, lookup(baseline, s.path),
                         lookup(current, s.path)) for s in specs]
    regressions = [c for c in checks if c["status"] == "regression"]
    missing = [c for c in checks if c["status"] == "missing"]
    return {"checks": checks, "regressions": regressions,
            "missing": missing,
            "n_ok": sum(c["status"] == "ok" for c in checks),
            "ok": not regressions}


def _fmt(v: Any) -> str:
    if isinstance(v, float):
        return f"{v:.6g}"
    return str(v)


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(
        prog="benchdiff",
        description="Diff two BENCH_*.json rounds against tolerance "
                    "bands; exit 1 on regression.")
    p.add_argument("baseline", help="baseline BENCH_*.json")
    p.add_argument("current", help="current BENCH_*.json")
    p.add_argument("--kind",
                   choices=["auto", "serving", "frontend", "fleet",
                            "fleetsim", "kernels"],
                   default="auto")
    p.add_argument("--fail-on-missing", action="store_true",
                   help="exit 1 when a watched metric is absent from "
                        "either document")
    p.add_argument("--json-out", default=None,
                   help="write the machine-readable regressions block")
    p.add_argument("--quiet", action="store_true")
    args = p.parse_args(argv)

    try:
        with open(args.baseline) as f:
            baseline = json.load(f)
        with open(args.current) as f:
            current = json.load(f)
    except (OSError, ValueError) as e:
        print(f"benchdiff: cannot load inputs: {e}", file=sys.stderr)
        return 2

    kind = args.kind
    if kind == "auto":
        kind = detect_kind(current) or detect_kind(baseline)
        if kind is None:
            print("benchdiff: cannot auto-detect bench kind "
                  "(pass --kind)", file=sys.stderr)
            return 2
    result = diff_benchmarks(baseline, current, SPEC_SETS[kind])
    result["kind"] = kind
    result["baseline_file"] = args.baseline
    result["current_file"] = args.current

    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(result, f, indent=2)

    if not args.quiet:
        for c in result["checks"]:
            status = c["status"]
            if status == "ok":
                mark = "ok        "
            elif status == "regression":
                mark = "REGRESSION"
            elif status == "missing":
                mark = "missing   "
            else:
                mark = "skipped   "
            detail = ""
            if "baseline" in c and c.get("baseline") is not None:
                detail = (f" {_fmt(c['baseline'])} -> "
                          f"{_fmt(c.get('current'))}")
                if c.get("rel_delta") is not None:
                    detail += f" ({c['rel_delta']:+.1%})"
            print(f"  {mark} [{kind}] {c['metric']}{detail}")
        n_reg = len(result["regressions"])
        n_miss = len(result["missing"])
        print(f"benchdiff: {result['n_ok']} ok, {n_reg} regression(s), "
              f"{n_miss} missing")
    if result["regressions"]:
        return 1
    if args.fail_on_missing and result["missing"]:
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
