#!/usr/bin/env bash
# Serving smoke: tiny-model serving benchmark comparing the serve loop
# at a chunk of one decode step (decode_chunk=1, one host sync per
# token) against chunks of eight (decode_chunk=8), asserting
# bit-identical greedy outputs between them,
# plus the --paged A/B (block-pool KV vs dense arena, bit-identical
# greedy asserted; pinned paged retrace budget), the shared-prefix
# workload (N requests, one common prompt: prefill executed exactly
# once, effective-concurrency multiplier >= 2 at equal KV HBM), the
# --kv-dtype int8 A/B (quantized arena at <= half the fp bytes,
# dense-int8 vs paged-int8 bit-identical), and the COMBINED
# --speculative case over the int8 arena (self-drafted greedy outputs
# bit-identical to the sequential loops, dense AND paged; >= 1.3x
# tokens/s on the repetitive workload; acceptance rate reported), and
# the default-on fused chunked-prefill A/B (prompts consumed in-scan:
# bit-identical greedy dense AND paged, pinned fused retrace budgets,
# no wait on a prefill program), and the --tiered case (a workload
# whose aggregate context is 10x the HBM block pool: cold prefixes
# demote to host DRAM/NVMe and promote back on re-serve — bit-identical
# greedy vs an all-HBM reference, >= 0.8x its throughput, demote/promote
# counters nonzero, paged compile count within one retrace of the
# untiered run, spill files cleaned on close), and the --megakernel A/B
# (fused decode megakernel engine vs the composed baseline:
# bit-identical greedy dense AND paged, pinned megakernel retrace
# budgets, jit-cache variant-name isolation).
# Writes BENCH_serving.json (tokens/s for both loops, chunk_speedup,
# prefill padding waste, the paged/speculative/int8_kv/fused/tiered/
# megakernel blocks) at the repo root, then runs the kernel-level bench
# (composed-vs-fused megakernel speedup — roofline proxy on CPU hosts —
# plus the tp collective/MLP overlap step model; the TPU-only
# decode_microbench case skips itself on CPU) into BENCH_kernels.json.
# Exits nonzero on parity failure, a missed gate, or any crash — fast
# enough for tier-1.
#
# Usage: bin/serving_smoke.sh        (from the repo root, or anywhere)

cd "$(dirname "$0")/.." || exit 1

timeout -k 10 600 env JAX_PLATFORMS=cpu \
    python -m deepspeed_tpu.benchmarks.serving_bench \
    --n-requests 8 --max-new-tokens 24 --prompt-len 16 \
    --decode-chunk 8 --skip-sequential --paged \
    --speculative --kv-dtype int8 --tiered --megakernel \
    --out-dir /tmp/serving_smoke_csv --json-out BENCH_serving.json \
    || exit $?

exec timeout -k 10 300 env JAX_PLATFORMS=cpu \
    python -m deepspeed_tpu.benchmarks.kernels_bench \
    --json-out BENCH_kernels.json
