"""Overlapping the post-attention tp collective with the MLP gemm.

At decode batch sizes the tensor-parallel all-reduce after the attention
output projection is pure exposed latency: the tokens-per-step tensor is
tiny, so the collective is latency-bound, and in the sequential-residual
block nothing can run until it lands. The NeoX parallel-residual block
(``x + attn(ln1 x) + ffn(ln2 x)``) breaks that dependence — the MLP gemm
reads ``ln2(x)`` and is completely independent of the attention branch,
so its compute can hide the collective's wire time.

Rather than hand-scheduling, we decompose the all-reduce so XLA's
latency-hiding scheduler can do the overlap itself:

  * ``defer_attn_allreduce`` pins the attention-branch output to a
    hidden-sharded layout ``P(None, None, "tp")``. Under GSPMD the
    psum that would have followed the output projection becomes a
    REDUCE-SCATTER into that layout, and the later residual add against
    replicated operands forces the matching ALL-GATHER. Between the two
    halves sits the (independent) MLP gemm — an async-start/async-done
    pair the scheduler slots compute into, instead of one blocking
    all-reduce. The decomposition is a relayout of the same sum: at
    tp=2 the reduction is a single two-term add either way, so greedy
    decode stays bit-identical (gated by test_serving's tp=2 parity
    test); at higher degrees ring reassociation applies, same as any
    psum implementation choice.

  * ``ring_allreduce`` is the explicit latency-optimized form for when
    GSPMD must not be trusted with the decomposition: a shard_map
    reduce-scatter + all-gather ring built from ``ppermute`` (the same
    collective idiom as ops/ring_attention.py). 2(n-1) hops of 1/n-sized
    messages — the bandwidth-optimal schedule — with each hop's partial
    add available for overlap.

  * ``decode_step_overlap_model`` is the CPU proxy for the acceptance
    gate: on hosts without ICI the overlap cannot be timed for real, so
    the bench reports the analytic step model
    ``attn + max(collective, mlp)`` vs ``attn + collective + mlp``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from jax import shard_map


def overlap_supported(y, mesh: Optional[Mesh], axis_name: str = "tp") -> bool:
    """The RS/AG decomposition needs a real tp axis and a hidden dim it
    divides; anything else keeps the plain psum (constraint would fail or
    be the identity)."""
    if mesh is None or y.ndim != 3:
        return False
    tp = dict(mesh.shape).get(axis_name, 1)
    return tp > 1 and y.shape[-1] % tp == 0


def defer_attn_allreduce(y, axis_name: str = "tp",
                         mesh: Optional[Mesh] = None):
    """Constrain the attention-branch output [B, S, D] to hidden-sharded
    ``P(None, None, tp)`` so GSPMD splits its pending psum into
    reduce-scatter (here) + all-gather (at the residual add), leaving the
    MLP gemm free to run between them. No-op when the mesh has no tp
    axis or D doesn't divide — the caller's math is unchanged either
    way (the constraint is a layout statement, not an op)."""
    if mesh is None:
        from ..parallel.mesh import get_constraint_mesh
        mesh = get_constraint_mesh()
    if not overlap_supported(y, mesh, axis_name):
        return y
    return jax.lax.with_sharding_constraint(
        y, NamedSharding(mesh, P(None, None, axis_name)))


def _ring_local(x, *, axis_name: str, n: int):
    """Per-shard reduce-scatter + all-gather ring over leading-dim chunks.
    x arrives REPLICATED per shard holding that shard's partial sum; the
    return is the full sum, replicated again."""
    r = jax.lax.axis_index(axis_name)
    chunks = jnp.stack(jnp.split(x, n, axis=0))        # [n, rows/n, ...]
    perm = [(i, (i + 1) % n) for i in range(n)]

    def rs_step(carry, t):
        acc, chunks = carry
        # the acc arriving from shard r-1 carries chunk (r - t - 1) % n;
        # add our own contribution to the same chunk. After n-1 hops
        # shard r holds the COMPLETE sum of chunk r.
        idx = (r - t - 1) % n
        acc = jax.lax.ppermute(acc, axis_name, perm) + chunks[idx]
        return (acc, chunks), None

    acc0 = chunks[(r - 1) % n]                          # t=0 seed, no hop
    (acc, _), _ = jax.lax.scan(rs_step, (acc0, chunks),
                               jnp.arange(1, n))

    def ag_step(carry, t):
        blk, out = carry
        blk = jax.lax.ppermute(blk, axis_name, perm)
        src = (r - t) % n                               # origin of blk now
        out = jax.lax.dynamic_update_index_in_dim(out, blk, src, 0)
        return (blk, out), None

    out = jnp.zeros_like(chunks)
    out = jax.lax.dynamic_update_index_in_dim(out, acc, r, 0)
    (_, out), _ = jax.lax.scan(ag_step, (acc, out), jnp.arange(1, n))
    return out.reshape(x.shape)


def ring_allreduce(x, mesh: Mesh, axis_name: str = "tp"):
    """Explicit ring all-reduce of per-shard partial sums: x [rows, ...]
    is one partial per tp shard (replicated layout in, replicated out);
    rows must divide by the ring size. Bitwise == psum at n=2 (one add
    per element either way); at n>2 the ring's reassociation applies."""
    n = dict(mesh.shape).get(axis_name, 1)
    if n == 1:
        return x
    if x.shape[0] % n != 0:
        raise ValueError(
            f"ring_allreduce needs rows % ring == 0, got {x.shape[0]} "
            f"rows on a {n}-wide {axis_name!r} axis")
    fn = partial(_ring_local, axis_name=axis_name, n=n)
    spec = P(*([None] * x.ndim))
    return shard_map(fn, mesh=mesh, in_specs=(spec,), out_specs=spec,
                     check_vma=False)(x)


def decode_step_overlap_model(t_attn: float, t_collective: float,
                              t_mlp: float) -> Dict[str, float]:
    """Analytic decode-step model for the overlap win, used as the CPU
    proxy (no ICI to time): the unhidden baseline serializes
    attn -> collective -> mlp; the overlapped step runs the collective
    under the MLP gemm. Returns both step times and their ratio."""
    unhidden = t_attn + t_collective + t_mlp
    overlapped = t_attn + max(t_collective, t_mlp)
    return {
        "t_attn_s": float(t_attn),
        "t_collective_s": float(t_collective),
        "t_mlp_s": float(t_mlp),
        "step_unhidden_s": float(unhidden),
        "step_overlapped_s": float(overlapped),
        "overlap_ratio": float(overlapped / unhidden) if unhidden else 1.0,
        "hidden_s": float(unhidden - overlapped),
    }
