#!/usr/bin/env bash
# Observability smoke: the CI gate for the memory/fleet-health stack.
#
#   1. benchdiff self-diff — each committed BENCH_*.json baseline diffed
#      against itself must pass (exit 0): proves the sentry parses the
#      real documents and every watched path resolves;
#   2. seeded synthetic regression — a baseline with the headline
#      throughput cut in half MUST make benchdiff exit nonzero: proves
#      the gate actually fires (a sentry that can't fail is decoration);
#   3. live /metrics scrape — a short frontend_bench run self-scrapes
#      its own metrics server (TTFT quantiles + arena-headroom gauge
#      parsed out of real Prometheus text), asserts /readyz answers
#      200 while serving, and live-GETs /slo (schema + dstpu_slo_*
#      gauges on /metrics). frontend_bench raises on a failed scrape,
#      so this doubles as the exposition integration test;
#   4. fleet journey trace — a fleet_bench run with its injected
#      mid-stream replica crash emits a merged journey trace;
#      `tputrace journey --validate` must pass (every request one
#      connected journey under one trace id, rerouted requests carry
#      the reroute link), the crash postmortem's in-flight set must
#      exactly match the handles reported error/rerouted, and the SLO
#      burn-rate gauges must move during the crash window and recover;
#   5. fleet observability plane — the same fleet_bench run stands up a
#      3-pod mixed local+remote hierarchy behind
#      RootRouter.serve_metrics and live-GETs /fleet/metrics +
#      /fleet/pods: every replica up with pod=/replica= labels, one
#      TYPE header per family, every dstpu_fleet_pod_* rollup family,
#      the killed remote replica flipped to up 0 within one TTL, and
#      the forced cross-pod failover journey validating with its pod
#      hop connected on the pod lane (pid 5).
#
# Usage: bin/obs_smoke.sh    (from the repo root, or anywhere)

set -u
cd "$(dirname "$0")/.." || exit 1

fail=0

# ---- 1. committed baselines must self-diff clean -----------------------
for bench in BENCH_serving.json BENCH_frontend.json BENCH_fleet.json; do
    if [ ! -f "$bench" ]; then
        echo "obs_smoke: MISSING baseline $bench" >&2
        fail=1
        continue
    fi
    if python bin/benchdiff "$bench" "$bench" --fail-on-missing --quiet;
    then
        echo "obs_smoke: benchdiff self-diff ok: $bench"
    else
        echo "obs_smoke: FAIL benchdiff self-diff: $bench" >&2
        fail=1
    fi
done

# ---- 2. a seeded regression must trip the gate -------------------------
seeded="$(mktemp /tmp/obs_smoke_seeded.XXXXXX.json)"
trap 'rm -f "$seeded"' EXIT
python - "$seeded" <<'EOF'
import json, sys
doc = json.load(open("BENCH_serving.json"))
doc["chunked_tokens_per_s"] = doc["chunked_tokens_per_s"] / 2.0
json.dump(doc, open(sys.argv[1], "w"))
EOF
if python bin/benchdiff BENCH_serving.json "$seeded" --quiet; then
    echo "obs_smoke: FAIL seeded regression was NOT detected" >&2
    fail=1
else
    echo "obs_smoke: seeded regression correctly detected (exit 1)"
fi

# ---- 3. live scrape during a real (short) frontend bench ---------------
if timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m deepspeed_tpu.benchmarks.frontend_bench \
    --n-requests 16 --overload-factor 4.0 --max-new-tokens 8 \
    --max-batch 2 --decode-chunk 4 \
    --json-out /tmp/obs_smoke_frontend.json > /dev/null; then
    python - <<'EOF'
import json
d = json.load(open("/tmp/obs_smoke_frontend.json"))
s = d["metrics_scrape"]
assert s["readyz"] == 200, s
assert s["ttft_quantiles_s"], s
assert s["arena_headroom_bytes"] >= 0, s
assert d["hbm"] and d["hbm"]["decode_chunk"]["temp_bytes"] > 0, d["hbm"]
slo = d["slo"]
assert slo["endpoint_ok"] == 1.0, slo      # live GET /slo parsed clean
assert slo["n_slos"] >= 4 and slo["n_samples"] > 0, slo
tg = d["tenant_goodput"]
assert tg["endpoint_ok"] == 1.0 and tg["labelled_series_ok"] == 1.0, tg
assert {"interactive", "bulk", "default"} <= set(tg["tenants"]), tg
# fused chunked prefill under the mixed long-prompt workload: parity,
# >= 2x p99 TPOT, and no wait on a prefill program in the fused drive
# (the in-bench gates raise on violation; these asserts pin the
# committed shape)
fm = d["fused_mixed"]
assert fm["greedy_parity"] is True, fm
assert fm["tpot_p99_improvement"] >= 2.0, fm
assert fm["bucketed_stall_s"] > 0.0, fm
# the bench must have run under the LockAuditor (runtime half of
# lockcheck) and observed ZERO lock-order violations across the
# serving window — a deadlockable ordering in frontend/fleet/telemetry
# locks fails the smoke even if no thread happened to interleave
la = d["lock_audit"]
assert la["enabled"] is True and la["strict"] is True, la
assert la["order_violations"] == 0, la
assert la["n_locks"] >= 5 and la["n_acquisitions"] > 0, la
print("obs_smoke: live /metrics scrape ok "
      f"({s['n_families']} families, ttft p99="
      f"{s['ttft_quantiles_s'].get('0.99')}s, /slo "
      f"{slo['n_slos']} objectives over {slo['n_samples']} samples, "
      f"{tg['n_tenants']} tenants, fused p99 TPOT "
      f"{fm['tpot_p99_improvement']}x, lock audit "
      f"{la['n_locks']} locks/{la['n_acquisitions']} acquisitions, "
      "0 order violations)")
EOF
    [ $? -ne 0 ] && fail=1
else
    echo "obs_smoke: FAIL frontend_bench live-scrape run" >&2
    fail=1
fi

# ---- 4. fleet journeys: crash-connected trace + postmortem + SLO burn --
if timeout -k 10 600 env JAX_PLATFORMS=cpu \
    XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m deepspeed_tpu.benchmarks.fleet_bench \
    --n-requests 8 --max-new-tokens 24 --prompt-len 16 \
    --decode-chunk 8 --json-out /tmp/obs_smoke_fleet.json \
    --trace-out /tmp/obs_smoke_fleet_trace.json > /dev/null; then
    if python bin/tputrace journey /tmp/obs_smoke_fleet_trace.json \
        --validate > /dev/null; then
        echo "obs_smoke: fleet journey trace validates"
    else
        echo "obs_smoke: FAIL tputrace journey --validate" >&2
        fail=1
    fi
    python - <<'EOF'
import json
d = json.load(open("/tmp/obs_smoke_fleet.json"))
c, j, s = d["crash"], d["journey"], d["slo"]
# every in-flight handle at crash time is in the postmortem, and only them
assert c["postmortem_inflight_match"] == 1.0, c
assert c["journey_complete"] == 1.0 and c["rerouted_parity"] == 1.0, c
assert c["rerouted"] > 0 and c["errors"] == 0, c  # full replay: no loss
assert j["complete"] == 1.0 and j["rerouted_links"] == c["rerouted"], j
# burn rate moved during the crash window and recovered after it
assert s["burn_crash"] > s["burn_pre"], s
assert s["burn_recovered"] == 0.0, s
print("obs_smoke: fleet crash observability ok "
      f"({j['n_traces']} journeys, {c['rerouted']} rerouted, "
      f"burn {s['burn_pre']} -> {s['burn_crash']} -> "
      f"{s['burn_recovered']})")
EOF
    [ $? -ne 0 ] && fail=1
    # ---- 5. fleet observability plane (same run's fleetobs block) ------
    python - <<'EOF'
import json
d = json.load(open("/tmp/obs_smoke_fleet.json"))
fo = d["fleetobs"]
assert fo["n_replicas"] == 6 and fo["n_up_initial"] == 6, fo
# killing the remote replica flipped exactly its up series to 0
# within one TTL — the dark replica renders, it never vanishes
assert fo["n_up_after_kill"] == 5, fo
assert fo["dark_replica_up_zero"] == 1.0, fo
assert fo["type_headers_unique"] == 1.0, fo
assert fo["pod_families_present"] == 1.0, fo
assert fo["parity"] == 1.0, fo
# forced cross-pod failover: connected journeys incl. the pod hop
assert fo["journey_validate_ok"] == 1.0, fo
assert fo["pod_failover"] >= 1 and fo["pod_lane_events"] >= 1, fo
print("obs_smoke: fleet observability plane ok "
      f"({fo['n_up_initial']} -> {fo['n_up_after_kill']} up after "
      f"kill, scrape {fo['scrape_s']}s, "
      f"{fo['pod_failover']} pod failovers, "
      f"{fo['pod_lane_events']} pod-lane events)")
EOF
    [ $? -ne 0 ] && fail=1
else
    echo "obs_smoke: FAIL fleet_bench crash-observability run" >&2
    fail=1
fi

if [ "$fail" -ne 0 ]; then
    echo "obs_smoke: FAILED" >&2
    exit 1
fi
echo "obs_smoke: all gates passed"
