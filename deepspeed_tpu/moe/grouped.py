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
experts are sorted by expert and cut into row tiles of one expert each. Work
and bytes follow the pairs that arrive — an expert that received no token is
never read, and a step in which every token picks the same experts only runs
more tiles. Pure functions over arrays; ``models/mla.py`` and
``models/afmoe.py`` declare the weights.

WHERE THE TILES ARE A KERNEL AND WHERE A LOOP. On a TPU, a call of at most
256 tokens over bfloat16 banks (a decode step of either serving model, and
the prefills of at most 256 tokens) runs its tiles as ONE Pallas call
(``ops/pallas/grouped_mlp.py``, ``grouped_mlp`` in a trace): the grid walks
the tile schedule, the index maps read each expert's matrices where they lie
in the bank, and tile ``t + 1``'s weights are in flight while tile ``t``
multiplies. Everywhere else — a longer prefill (``x`` and the float32 result
no longer stay in fast memory for the whole grid), another dtype, a mesh of
several devices, the CPU — a ``while_loop`` with a DYNAMIC trip count
multiplies tile after tile, one iteration finding its expert, cutting three
matrices out of the bank, gathering rows and scatter-adding its result. The
gate (``grouped_mlp_refusal``) reads static shapes alone; the loop is also
the reference the kernel is held to. No backward pass runs through either.
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

    if takes_kernel(T, d, gate.shape[-1], tile, gate.dtype, len(lead),
                    x.dtype):
        from ..ops.pallas.grouped_mlp import grouped_mlp
        # every tile at once: tile t's expert, its first sorted pair, and
        # the one-hot of its rows' tokens (a slot past the expert's pairs
        # names token T, which no column is)
        expert, first, n_live = tile_schedule(
            sizes, tile, min(T * k, H) + T * k // tile)
        at = first[:, None] + jnp.arange(tile, dtype=jnp.int32)
        live = at < ends[expert][:, None]
        token = jnp.where(live, pair_token[at], T)
        sel = token[..., None] == jnp.arange(T, dtype=jnp.int32)
        return grouped_mlp(x, expert, n_live, sel.astype(x.dtype),
                           jnp.where(live, pair_weight[at], 0.0), gate, up,
                           down, *lead)

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


def tile_schedule(sizes, tile: int, n_tiles: int):
    """The tiles of ``sizes [H]`` pairs an expert, as tables over a static
    grid of ``n_tiles`` (at least ``min(pairs, H) + pairs // tile``, the most
    that can exist): ``expert [n_tiles]`` the expert of tile ``t``, experts
    in rising order and an expert's tiles next to each other, so a touched
    expert is named in one run of consecutive tiles; ``first [n_tiles]`` the
    tile's first sorted pair; ``n_live`` the tiles that exist. Past them the
    tables repeat the LAST live tile (the last held expert's empty one when
    there is none), so a grid step there names blocks already fetched."""
    sizes = sizes.astype(jnp.int32)
    tiles = (sizes + tile - 1) // tile
    tile_ends = jnp.cumsum(tiles)
    n_live = tile_ends[-1]
    t = jnp.minimum(jnp.arange(n_tiles, dtype=jnp.int32),
                    jnp.maximum(n_live - 1, 0))
    expert = jnp.minimum(
        jnp.sum(tile_ends[None, :] <= t[:, None], axis=1, dtype=jnp.int32),
        sizes.shape[0] - 1)
    first = (jnp.cumsum(sizes)[expert] - sizes[expert]
             + (t - (tile_ends[expert] - tiles[expert])) * tile)
    return expert, first, n_live


def takes_kernel(tokens: int, d: int, f: int, tile: int, dtype,
                 lead_dims: int = 1, x_dtype=None) -> bool:
    """Whether a call of these static shapes runs its tiles through the
    kernel: what :func:`grouped_experts` asks, and what a model asks for its
    ``kernel_tiles`` counter. ``"auto"`` alone chooses: the kernel on a TPU
    where its gate accepts the shapes and the mesh is one device (GSPMD
    cannot partition the custom call), the loop otherwise, logged once."""
    from ..models.gpt import _decode_mesh_refusal
    from ..ops.pallas import _utils as kernels
    from ..ops.pallas.grouped_mlp import grouped_mlp_refusal
    refusal = grouped_mlp_refusal(tokens, d, f, tile, dtype, lead_dims,
                                  x_dtype) or _decode_mesh_refusal()
    return kernels.auto_path("grouped_mlp", refusal)


COUNTERS = ("pairs_held", "pairs_absent", "experts_touched", "load_max",
            "load_mean", "steps", "tiles", "kernel_tiles")


def routing_counters(choice, live, *, expert_offset: int, experts_held: int,
                     tile: int, kernel: bool = False
                     ) -> Dict[str, jnp.ndarray]:
    """What the serving programs sum on the device and fetch with their
    tokens. ``choice [layers, b, s, k]`` the experts chosen, ``live [b, s]``
    which tokens count (an idle lane and a prompt's padding route too, and
    are nobody's traffic). One (layer, call) is a "step": the counters are
    sums over steps of the pairs that fell on held experts and on absent
    ones, the held experts that received a token, the largest and the mean
    load of a held expert; ``steps`` counts the steps that had a live token,
    so a reader divides by it.

    ``tiles`` is what the loop or the kernel RAN, at ``tile`` rows a tile
    (the caller's ``_tile_rows`` of the call's tokens): NOT masked by
    ``live``, because an idle lane's and a prompt padding's pairs route too
    and cost tiles. ``kernel_tiles`` is those of them that went through
    ``ops/pallas/grouped_mlp.py`` (all or none of a call: ``kernel`` is
    :func:`takes_kernel` of its shapes). ``kernel_tiles / tiles`` is the
    share the kernel took; ``tiles / experts_touched`` is weight reads a
    touched expert, 1.0 at a decode step whose lanes are all live."""
    local = choice - expert_offset
    on = local[..., None] == jnp.arange(experts_held)
    held = (local >= 0) & (local < experts_held) & live[None, :, :, None]
    load = jnp.sum(held[..., None] & on, axis=(1, 2, 3),
                   dtype=f32)                           # [layers, held]
    ran = jnp.sum(-(-jnp.sum(on, axis=(1, 2, 3), dtype=jnp.int32) // tile),
                  dtype=f32)
    n_live = jnp.sum(live, dtype=f32)
    n_pairs = n_live * choice.shape[0] * choice.shape[-1]
    pairs_held = jnp.sum(load)
    return {"pairs_held": pairs_held,
            "pairs_absent": n_pairs - pairs_held,
            "experts_touched": jnp.sum(load > 0, dtype=f32),
            "load_max": jnp.sum(jnp.max(load, axis=1)),
            "load_mean": pairs_held / experts_held,
            "steps": choice.shape[0] * (n_live > 0).astype(f32),
            "tiles": ran,
            "kernel_tiles": ran if kernel else jnp.zeros((), f32)}
