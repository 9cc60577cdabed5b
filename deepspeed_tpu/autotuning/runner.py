"""Isolated autotuning experiment runner.

Reference analogue: ``deepspeed/autotuning/scheduler.py`` — every
experiment runs as its own launched job so compile caches, HBM
fragmentation, and hard runtime crashes cannot leak between experiments
or kill the tuner. This is the child-process entry point: it imports the
user's factory by dotted path, builds the engine from the experiment
config, measures, and prints ONE JSON line that the parent harvests.

Factory contract (``--factory pkg.mod:fn``):
    fn(config: dict) -> (engine, make_iter)
where ``engine.train_batch(make_iter())`` runs one global batch.

Usage (normally built by ``Autotuner._run_subprocess``):
    python -m deepspeed_tpu.autotuning.runner --factory tests.x:build \
        --config exp.json [--warmup 2] [--steps 3] [--metric throughput]
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time


def _resolve(path: str):
    mod, _, attr = path.partition(":")
    if not attr:
        raise ValueError(f"--factory must be 'module:callable', got {path!r}")
    return getattr(importlib.import_module(mod), attr)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="autotuning.runner")
    ap.add_argument("--factory", required=True)
    ap.add_argument("--config", required=True, help="experiment config JSON")
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--metric", default="throughput",
                    choices=("throughput", "latency"))
    args = ap.parse_args(argv)

    with open(args.config) as fh:
        config = json.load(fh)
    factory = _resolve(args.factory)

    import jax  # after argparse: a wedged backend should not mask CLI errors
    engine, make_iter = factory(config)
    loss = None
    for _ in range(args.warmup):
        loss = engine.train_batch(make_iter())
    if loss is not None:
        jax.block_until_ready(loss)            # sync before timing
    t0 = time.perf_counter()
    for _ in range(args.steps):
        loss = engine.train_batch(make_iter())
    jax.block_until_ready(loss)
    dt = (time.perf_counter() - t0) / args.steps
    val = dt if args.metric == "latency" else engine.train_batch_size() / dt
    print(json.dumps({"metric_val": val, "step_s": dt}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
