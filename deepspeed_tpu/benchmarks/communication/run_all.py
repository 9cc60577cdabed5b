"""Collective micro-benchmarks over the device mesh.

Reference: ``benchmarks/communication/run_all.py`` + the per-collective
modules and ``bin/ds_bench`` — size sweeps reporting latency and the
standard algorithmic bandwidth ("busbw": volume scaled by the collective's
(n-1)/n ring factor so numbers compare across world sizes).

TPU shape: collectives are jitted shard_map programs over the global mesh
(one program per size, cached), each timed window ending in
``jax.block_until_ready``. The same sweep serves
ICI (single host, multi-chip) and DCN (multi-host) by just launching on
more hosts; bandwidth is per-chip wire bandwidth.
"""

from __future__ import annotations

import argparse
import json
import time
from typing import Callable, Dict

import numpy as np


def _bw_factors(name: str, world: int) -> float:
    """busbw scaling: fraction of the payload that crosses each link in an
    optimal ring implementation (NCCL-tests convention, which the
    reference's utils.py mirrors)."""
    if world <= 1:
        return 0.0
    if name == "all_reduce":
        return 2.0 * (world - 1) / world
    if name in ("all_gather", "reduce_scatter"):
        return (world - 1) / world
    if name == "all_to_all":
        return (world - 1) / world
    if name == "pt2pt":
        return 1.0
    raise ValueError(name)


def _build(name: str, group):
    import jax
    import jax.numpy as jnp
    from ...comm import comm as dist

    G = group.size
    if name == "all_reduce":
        return lambda x: dist.all_reduce(x, group=group)
    if name == "all_gather":
        return lambda x: dist.all_gather_base(x, group=group)
    if name == "reduce_scatter":
        return lambda x: dist.reduce_scatter_base(x, group=group)
    if name == "all_to_all":
        def a2a(x):
            n = x.shape[1]
            return dist.all_to_all_single(
                x.reshape(G, G, n // G), group=group)
        return a2a
    if name == "pt2pt":
        return lambda x: dist.ppermute(
            x, [(i, (i + 1) % G) for i in range(G)], group=group)
    raise ValueError(name)


COLLECTIVES = ("all_reduce", "all_gather", "reduce_scatter", "all_to_all",
               "pt2pt")


def run_collective(name: str, *, sizes_mb=(1, 4, 16, 64), trials: int = 20,
                   warmups: int = 3, dtype="float32", group=None,
                   quiet: bool = False):
    """Sweep one collective; returns a list of result dicts."""
    import jax
    import jax.numpy as jnp
    from ...comm import comm as dist

    dist.init_distributed()
    group = group if group is not None else dist.new_group("dp")
    G = group.size
    fn = _build(name, group)
    jdt = jnp.dtype(dtype)
    results = []
    if not quiet:
        print(f"---- {name} (world={G}, dtype={jdt.name}) ----")
        print(f"{'size/rank':>12} {'latency':>12} {'alg bw':>12} "
              f"{'bus bw':>12}")
    for mb in sizes_mb:
        n = int(mb * 2 ** 20 / jdt.itemsize)
        n = -(-n // (G * G)) * G * G      # divisible for every collective
        x = jnp.ones((G, n), jdt)
        jit_fn = jax.jit(fn)
        out = jit_fn(x)
        for _ in range(warmups):
            out = jit_fn(x)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(trials):
            out = jit_fn(x)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / trials
        size_bytes = n * jdt.itemsize          # per-rank payload
        alg_bw = size_bytes / dt / 1e9
        bus_bw = alg_bw * _bw_factors(name, G)
        results.append({"collective": name, "world": G,
                        "size_per_rank_bytes": size_bytes,
                        "latency_us": dt * 1e6, "alg_bw_gbps": alg_bw,
                        "bus_bw_gbps": bus_bw})
        if not quiet:
            print(f"{size_bytes / 2**20:>10.1f}MB {dt * 1e6:>10.1f}us "
                  f"{alg_bw:>10.2f}GB/s {bus_bw:>10.2f}GB/s")
    return results


def main(argv=None):
    from ...utils.platform import enable_compile_cache
    enable_compile_cache()       # before any compile
    parser = argparse.ArgumentParser(
        prog="ds_bench", description="collective bw/latency sweeps")
    parser.add_argument("--collective", choices=COLLECTIVES + ("all",),
                        default="all")
    parser.add_argument("--sizes-mb", type=float, nargs="+",
                        default=[1, 4, 16, 64])
    parser.add_argument("--trials", type=int, default=20)
    parser.add_argument("--warmups", type=int, default=3)
    parser.add_argument("--dtype", default="float32")
    parser.add_argument("--json", action="store_true",
                        help="emit one JSON line per measurement")
    args = parser.parse_args(argv)
    names = COLLECTIVES if args.collective == "all" else (args.collective,)
    all_results = []
    for name in names:
        all_results += run_collective(
            name, sizes_mb=args.sizes_mb, trials=args.trials,
            warmups=args.warmups, dtype=args.dtype, quiet=args.json)
    if args.json:
        for r in all_results:
            print(json.dumps(r))
    return all_results


if __name__ == "__main__":
    main()
