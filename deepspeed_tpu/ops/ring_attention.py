"""Ring attention: context parallelism by rotating KV blocks around the
``sp`` ring.

The second long-context strategy next to Ulysses (models/gpt.py
``sequence_parallel``): Ulysses all-to-alls sequence<->head shards, so its
parallel degree is capped by (and must divide) the head count; ring
attention keeps q sequence-sharded and passes the K/V shard around the
ring with ``ppermute``, accumulating blockwise-softmax partials — any ring
size works, per-chip memory is O(S/sp), and each hop's compute hides the
next hop's ICI transfer (the blockwise-parallel-transformer/ring-attention
construction; reference v0.6.6 has no context parallelism at all, SURVEY
§2.10).

Everything lives in one ``shard_map`` region differentiated through a
``lax.scan`` over ring steps — collectives (ppermute) transpose cleanly, so
the backward pass is the reverse rotation, no custom VJP needed.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30


def _block_attend(q, k, v, q_pos, k_pos, scale, causal):
    """One blockwise attention partial: returns (scores_max [B,H,Sq],
    exp-sum [B,H,Sq], weighted values [B,Sq,H,D]) in fp32."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        mask = q_pos[:, None] >= k_pos[None, :]
        s = jnp.where(mask[None, None], s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    acc = jnp.einsum("bhqk,bkhd->bqhd", p.astype(q.dtype), v)
    return m, l, acc.astype(jnp.float32)


def _ring_local(q, k, v, *, axis_name, ring_size, scale, causal):
    """Per-shard body: q/k/v [B, S/sp, H, D] local chunks."""
    r = jax.lax.axis_index(axis_name)
    chunk = q.shape[1]
    base = jnp.arange(chunk)
    q_pos = r * chunk + base
    perm = [(i, (i + 1) % ring_size) for i in range(ring_size)]

    def combine(state, t, k_t, v_t):
        m, l, acc = state
        src = (r - t) % ring_size          # origin rank of the current kv
        k_pos = src * chunk + base
        bm, bl, bacc = _block_attend(q, k_t, v_t, q_pos, k_pos, scale,
                                     causal)
        m_new = jnp.maximum(m, bm)
        c_old = jnp.exp(m - m_new)
        c_new = jnp.exp(bm - m_new)
        l = l * c_old + bl * c_new
        acc = acc * jnp.moveaxis(c_old, 1, -1)[..., None] \
            + bacc * jnp.moveaxis(c_new, 1, -1)[..., None]
        return m_new, l, acc

    def step(carry, t):
        # rotate FIRST (steps 1..ring-1): the local block was consumed
        # before the scan, and this layout never pays for a final rotation
        # whose result would be discarded
        kv, state = carry
        kv = jax.tree.map(lambda x: jax.lax.ppermute(x, axis_name, perm),
                          kv)
        state = combine(state, t, *kv)
        return (kv, state), None

    b, sq, h, d = q.shape
    state0 = (jnp.full((b, h, sq), NEG_INF, jnp.float32),
              jnp.zeros((b, h, sq), jnp.float32),
              jnp.zeros((b, sq, h, d), jnp.float32))
    state0 = combine(state0, 0, k, v)      # local block, no transfer
    (_, (m, l, acc)), _ = jax.lax.scan(
        step, ((k, v), state0), jnp.arange(1, ring_size))
    l_safe = jnp.where(l == 0, 1.0, l)
    out = acc / jnp.moveaxis(l_safe, 1, -1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, *, axis_name: str = "sp",
                   scale: Optional[float] = None, causal: bool = True,
                   batch_axis: str = "dp"):
    """q, k, v: [B, S, H, D] global arrays (S sharded over `axis_name`,
    B over `batch_axis`) -> [B, S, H, D] attention output, same sharding."""
    ring = dict(mesh.shape).get(axis_name, 1)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if ring == 1:
        m, l, acc = _block_attend(
            q, k, v, jnp.arange(q.shape[1]), jnp.arange(k.shape[1]),
            scale, causal)
        l_safe = jnp.where(l == 0, 1.0, l)
        return (acc / jnp.moveaxis(l_safe, 1, -1)[..., None]).astype(q.dtype)
    dp = dict(mesh.shape).get(batch_axis, 1)
    b_axis = batch_axis if q.shape[0] % max(dp, 1) == 0 else None
    spec = P(b_axis, axis_name, None, None)
    fn = partial(_ring_local, axis_name=axis_name, ring_size=ring,
                 scale=scale, causal=causal)
    return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)
