"""A chip's share of a sigmoid-routed expert layer, without dropped tokens.

``sharded_moe.py`` is the 2021 gate: softmax top-1/top-2 with a capacity,
every expert padded to the same number of rows. The layer here is what the
large sparse models of 2024-25 run and what expert parallelism asks of a
chip: the router scores ALL ``n_routed_experts`` (the published width), each
token takes its ``k`` largest, and this chip computes the part of the result
that the experts it HOLDS give — ``experts_held`` of them starting at
``expert_offset``. What the absent experts would add is left out; on one
chip the layer runs without its exchange and nothing stands in for it.

No capacity and no padding to it: the token-expert pairs that fall on held
experts are sorted by expert, cut into row tiles of one expert each, and a
loop with a DYNAMIC trip count multiplies tile after tile. Work and bytes
follow the pairs that arrive — an expert that received no token is never
read, and a step in which every token picks the same experts only runs more
tiles. Pure functions over arrays; ``models/mla.py`` declares the weights.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

f32 = jnp.float32


def sigmoid_topk(x, router, k: int, scaling: float, normalize: bool = True,
                 bias=None):
    """The router, in float32 whatever ``x`` is computed in: scores
    ``sigmoid(x @ router)`` over the published experts ``[T, E]``, the ``k``
    largest, and their weights ``scaling * s_i / (sum of the k + 1e-20)``
    (normalised over all ``k`` chosen, held here or not). ``bias [E]``: a
    per-expert selection bias; the ``k`` largest of ``s + bias`` are chosen
    and the weights are made of ``s`` alone. Returns
    ``(choice [T, k] int32, weights [T, k] float32)``."""
    logits = jnp.dot(x.astype(f32), router.astype(f32),
                     precision=jax.lax.Precision.HIGHEST)
    if bias is not None:
        scores = jax.nn.sigmoid(logits)
        _, choice = jax.lax.top_k(scores + bias.astype(f32), k)
        top_s = jnp.take_along_axis(scores, choice, axis=-1)
    else:
        top_s, choice = jax.lax.top_k(jax.nn.sigmoid(logits), k)
    if normalize:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    return choice.astype(jnp.int32), top_s * scaling


def gated_mlp(x, gate, up, down):
    """``(silu(x W_g) * (x W_u)) W_d``; products accumulate in float32 and
    the hidden is rounded to ``x``'s dtype before the last one."""
    g = jnp.dot(x, gate, preferred_element_type=f32)
    u = jnp.dot(x, up, preferred_element_type=f32)
    return jnp.dot((jax.nn.silu(g) * u).astype(x.dtype), down,
                   preferred_element_type=f32)


def grouped_experts(x, choice, weights, gate, up, down, *, lead=(),
                    expert_offset: int = 0, tile: int):
    """``sum over the chosen experts HELD HERE of w_i E_i(x)``, float32.

    ``x [T, d]``; ``choice``/``weights [T, k]`` from :func:`sigmoid_topk`;
    ``gate``/``up [*lead_dims, H, d, f]`` and ``down [*lead_dims, H, f, d]``
    the held experts' banks, indexed at ``lead`` (the layer of a
    layer-stacked bank: the loop reads one expert's matrices where they lie
    instead of being handed a copy of the layer's bank).

    Sort-and-group: a pair (token, expert) on a held expert gets the key
    ``expert - offset``, every other pair the key ``H``; a stable sort puts
    the held pairs first, expert by expert. Expert ``e``'s rows are cut into
    tiles of ``tile`` rows and the loop runs the tiles that exist. A token
    names an expert at most once, so a tile's scatter has unique rows."""
    T, d = x.shape
    k = choice.shape[1]
    H = gate.shape[len(lead)]
    local = choice - expert_offset
    key = jnp.where((local >= 0) & (local < H), local, H).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    # a tile may reach past the last pair: pad so that the slice never clamps
    pair_token = jnp.concatenate([order // k, jnp.full((tile,), T, jnp.int32)])
    pair_weight = jnp.concatenate([weights.reshape(-1)[order].astype(f32),
                                   jnp.zeros((tile,), f32)])
    sizes = jnp.sum(key[:, None] == jnp.arange(H, dtype=key.dtype)[None],
                    axis=0, dtype=jnp.int32)
    ends = jnp.cumsum(sizes)
    tiles = (sizes + tile - 1) // tile
    tile_ends = jnp.cumsum(tiles)

    def bank(w, e):
        start = tuple(lead) + (e,) + (0, 0)
        return jax.lax.dynamic_slice(
            w, start, (1,) * (len(lead) + 1) + w.shape[-2:]
        ).reshape(w.shape[-2:])

    def one_tile(state):
        t, out = state
        e = jnp.sum(tile_ends <= t, dtype=jnp.int32)
        first = ends[e] - sizes[e] + (t - (tile_ends[e] - tiles[e])) * tile
        live = first + jnp.arange(tile, dtype=jnp.int32) < ends[e]
        rows = jnp.where(
            live, jax.lax.dynamic_slice(pair_token, (first,), (tile,)), T)
        w = jnp.where(
            live, jax.lax.dynamic_slice(pair_weight, (first,), (tile,)), 0.0)
        xe = x.at[rows].get(mode="fill", fill_value=0)
        y = gated_mlp(xe, bank(gate, e), bank(up, e), bank(down, e))
        return t + 1, out.at[rows].add(y * w[:, None], mode="drop",
                                       unique_indices=True)

    _, out = jax.lax.while_loop(lambda s: s[0] < tile_ends[-1], one_tile,
                                (jnp.int32(0), jnp.zeros((T, d), f32)))
    return out


COUNTERS = ("pairs_held", "pairs_absent", "experts_touched", "load_max",
            "load_mean", "steps")


def routing_counters(choice, live, *, expert_offset: int, experts_held: int
                     ) -> Dict[str, jnp.ndarray]:
    """What the serving programs sum on the device and fetch with their
    tokens. ``choice [layers, b, s, k]`` the experts chosen, ``live [b, s]``
    which tokens count (an idle lane and a prompt's padding route too, and
    are nobody's traffic). One (layer, call) is a "step": the counters are
    sums over steps of the pairs that fell on held experts and on absent
    ones, the held experts that received a token, the largest and the mean
    load of a held expert; ``steps`` counts the steps that had a live token,
    so a reader divides by it."""
    local = choice - expert_offset
    held = (local >= 0) & (local < experts_held) & live[None, :, :, None]
    load = jnp.sum(
        held[..., None] & (local[..., None] == jnp.arange(experts_held)),
        axis=(1, 2, 3), dtype=f32)                      # [layers, held]
    n_live = jnp.sum(live, dtype=f32)
    n_pairs = n_live * choice.shape[0] * choice.shape[-1]
    pairs_held = jnp.sum(load)
    return {"pairs_held": pairs_held,
            "pairs_absent": n_pairs - pairs_held,
            "experts_touched": jnp.sum(load > 0, dtype=f32),
            "load_max": jnp.sum(jnp.max(load, axis=1)),
            "load_mean": pairs_held / experts_held,
            "steps": choice.shape[0] * (n_live > 0).astype(f32)}
