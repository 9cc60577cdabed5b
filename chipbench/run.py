#!/usr/bin/env python3
"""chipbench/run.py — one run of one cell of BENCHMARK.json.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process. It fails (non-zero, no result line) unless JAX finds exactly the
cell's number of TPU chips of a ``device_kind`` listed in
``chipbench/peaks.json``. ``--rehearsal`` is the only mode that runs on the
CPU: toy sizes from the ``rehearsal`` groups of the cell's files, every metric
renamed so that it cannot be read as a device number. The last line of
standard output is the one JSON object the benchmark's contract fixes;
everything else goes on earlier lines.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench import spec  # noqa: E402
from chipbench.harness import (SUFFIX_REHEARSAL, Ctx,  # noqa: E402
                               device_facts)

TRACE_DIR = os.path.join(spec.REPO_ROOT, ".chipbench_trace")
# with --trace 1: the trace's planes, lines, top ops and gaps, for a human
OUTLINE_DIR = os.path.join(spec.REPO_ROOT, ".chipbench_outline")


def fail(msg: str, code: int = 1) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return code


def open_ctx(cell, *, seed: int, seconds: float, trace: bool,
             rehearsal: bool):
    """The run's context, or an exit code: fails unless JAX finds exactly
    the cell's number of TPU chips of a kind in peaks.json (the CPU only in
    rehearsal); turns the persistent compilation cache on."""
    import jax
    devices = jax.devices()
    dev = devices[0]
    peaks = None
    if rehearsal:
        if dev.platform != "cpu":
            return fail("--rehearsal is the CPU rehearsal; on a chip run "
                        "without it", 2)
    else:
        if dev.platform != "tpu":
            return fail(f"needs a TPU and JAX found platform "
                        f"{dev.platform!r} ({len(devices)} device(s)); it "
                        f"does not carry on without one (CPU rehearsal: "
                        f"--rehearsal)")
        table = spec.load_json(os.path.join(
            os.path.dirname(os.path.abspath(__file__)), "peaks.json"))
        peaks = table.get(dev.device_kind)
        if not isinstance(peaks, dict):
            return fail(f"device_kind {dev.device_kind!r} is not in "
                        f"chipbench/peaks.json; a device without published "
                        f"peaks is an error, not a default")
    if len(devices) != cell["chips"]:
        return fail(f"cell {cell['name']} asks for {cell['chips']} chip(s) "
                    f"and JAX found {len(devices)}")

    # the persistent compilation cache: at the program's fixed path inside
    # the checkout (or where JAX_COMPILATION_CACHE_DIR says), and storing
    # every program, not only those that took over a second to compile
    # (the CPU rehearsal keeps none: nothing it compiles is worth keeping)
    cache_dir = "off"
    if not rehearsal:
        from deepspeed_tpu.utils.platform import enable_compile_cache
        cache_dir = enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from chipbench.compile_watch import CompileWatch
    ctx = Ctx(cell=cell, seed=int(seed), seconds=seconds, trace=bool(trace),
              rehearsal=rehearsal, devices=devices, peaks=peaks,
              watch=CompileWatch(), t_start=T_START, trace_dir=TRACE_DIR)
    ctx.say(f"{'REHEARSAL (CPU, toy size, not a measurement) ' if rehearsal else ''}"
            f"platform={dev.platform} device_kind={dev.device_kind!r} "
            f"count={len(devices)} seed={seed} seconds={seconds} "
            f"trace={int(trace)} compile cache {cache_dir}")
    return ctx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU rehearsal at toy size; not a measurement")
    args = ap.parse_args(argv)

    bench = spec.load_benchmark()
    spec.validate(bench)
    cell = spec.load_cell(bench, args.workload, rehearsal=args.rehearsal)
    seconds = float(args.seconds if args.seconds is not None
                    else bench["run_seconds"])
    ctx = open_ctx(cell, seed=args.seed, seconds=seconds, trace=args.trace,
                   rehearsal=args.rehearsal)
    if isinstance(ctx, int):
        return ctx
    devices = ctx.devices
    peaks = ctx.peaks

    kind = cell["mix"]["kind"]
    driver = spec.load_module(spec.find_driver(bench, kind))
    out = driver.run(ctx)
    ctx.say(f"set-up {out['setup_s']:.2f}s; programs built over the run: "
            f"{ctx.watch.snapshot()}")

    metrics = {"setup_s": out["setup_s"], **out["end_to_end"]}
    units = {m["name"]: m["unit"] for m in cell["end_to_end"]}
    device = device_facts(devices)
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"]}
    if args.trace:
        summary = out.get("trace")
        if out.get("outline"):
            os.makedirs(OUTLINE_DIR, exist_ok=True)
            with open(os.path.join(OUTLINE_DIR, cell["name"] + ".txt"),
                      "w") as f:
                f.write("\n".join(out["outline"]) + "\n")
                if summary is not None:
                    f.write(json.dumps({
                        "ops": sorted(summary.op_seconds.items(),
                                      key=lambda kv: -kv[1])[:80],
                        "modules": summary.module_seconds,
                        "module_counts": summary.module_counts,
                        "gaps": summary.idle_gaps}, indent=1) + "\n")
        counters = dict(out["counters"], device=device, peaks=peaks)
        metrics, units = {}, {}
        for m in cell["per_layer"]:
            reader = spec.load_module(spec.find_reader(bench, m["name"]))
            value = reader.read(summary, out["spans"], counters, cell)
            if value is not None:
                metrics[m["name"]] = float(value)
                units[m["name"]] = m["unit"]
        if summary is not None:
            device["busy_s"] = summary.busy_s
            device["window_s"] = summary.window_s
            result["breakdown"] = {"device_ops": summary.top_ops(10),
                                   "idle_gaps": summary.top_gaps(10)}
    suffix = SUFFIX_REHEARSAL if args.rehearsal else ""
    result["metrics"] = {name + suffix: {"value": value, "unit": units[name]}
                         for name, value in metrics.items()}
    result["device"] = device
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
