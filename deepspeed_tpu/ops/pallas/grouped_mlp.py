"""The expert loop of ``moe/grouped.py`` as ONE pipelined kernel.

``grouped_experts`` sorts the (token, expert) pairs by expert and cuts them
into row tiles of one expert each; its ``while_loop`` then runs ten small
programs a tile (find the expert, cut three matrices out of the bank, gather
the rows, three skinny matmuls that each start their weight stream cold,
scatter-add), and nothing of tile ``t + 1`` is in flight while tile ``t``
computes. Here the same tiles are the grid of one ``pallas_call``:

* the schedule arrives as scalar-prefetched tables (tile -> expert, the count
  of live tiles, the layer); the grid is static at the schedule's upper bound
  and a step past the last live tile does nothing and points at the blocks
  the last live tile left in fast memory, so it fetches nothing;
* the three banks stay where they lie (``[L, H, d, f]``, ``[L, H, d, f]``,
  ``[L, H, f, d]``): the index maps pick ``(layer, expert[t], ...)``, so the
  pipeline has tile ``t + 1``'s weight blocks in flight while tile ``t``
  multiplies. An expert whose three matrices fit the budget twice over is
  one grid step; a wider one is blocked along ``f`` (:func:`block_of_f`)
  under a float32 ``[tile, d]`` accumulator. A popular expert's second tile
  finds its unblocked matrices still there and reads nothing;
* ``x [T, d]`` and the float32 result ``[T, d]`` stay in fast memory for the
  whole grid (a decode step's 64 tokens, a short prefill's 256). A tile's
  rows are gathered by a one-hot product, exact in bfloat16, and its result
  is combined by the transposed one: a token names an expert once, so a
  result row receives ONE product a tile, and the float32 ``w * y`` crosses
  the matrix unit as three bfloat16 pieces that add back to all its 24 bits.

The numbers are the loop's (``moe/grouped.py::gated_mlp``): bfloat16
operands, every product accumulated in float32, ``silu(g) * u`` rounded to
bfloat16 before the last product, the pair's weight applied in float32, the
routed sum float32. The loop stays for every shape the gate refuses and on
the CPU, and is the reference this kernel is held to
(``tests/test_grouped_mlp_kernel.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import interpret_mode, refuse

f32 = jnp.float32

# Tokens whose x and float32 result stay in fast memory for the whole grid,
# in whole 128-lane tiles of the one-hot's columns: a decode step (64 lanes)
# and the prefills of at most 256 tokens. Longer prefills keep the loop.
_ROWS_RESIDENT_MAX = 256
# What the kernel may take of the chip's 128 MiB of fast memory, and what of
# that BOTH pipeline buffers of the three weight blocks may: an expert of
# 3 x 2048 x 1024 (12.6 MB) whole, one of 3 x 7680 x 2048 (94.4 MB) in four
# blocks of 512 columns (23.6 MB a grid step).
_VMEM_LIMIT = 100 * 1024 * 1024
_WEIGHT_BUDGET = 48 * 1024 * 1024


def block_of_f(d: int, f: int, tile: int, itemsize: int) -> Optional[int]:
    """Columns of ``f`` one grid step takes of an expert's three matrices:
    the largest divisor of ``f`` in whole 128-lane tiles whose gate, up and
    down blocks, twice (the block in use and the one in flight), with the
    float32 ``[tile, block]`` gate and up products beside them, fit
    ``_WEIGHT_BUDGET``. None where not even 128 columns do."""
    for n in range(1, f // 128 + 1):
        bf = f // n
        if f % n or bf % 128:
            continue
        if 2 * 3 * d * bf * itemsize + 2 * tile * bf * 4 <= _WEIGHT_BUDGET:
            return bf
    return None


def _resident_rows(tokens: int) -> int:
    return -(-tokens // 128) * 128


def grouped_mlp_refusal(tokens: int, d: int, f: int, tile: int, dtype,
                        lead_dims: int = 1, x_dtype=None) -> Optional[str]:
    """Why :func:`grouped_mlp` cannot run this shape; None when it can.
    Static shapes alone: the tokens of the call, an expert's ``[d, f]``, the
    rows of a tile, the banks' dtype (and ``x``'s, where it may differ) and
    how many leading dimensions the banks are indexed at. (The mesh is the
    caller's to ask.)"""
    dt = jnp.dtype(dtype)
    if dt != jnp.bfloat16:
        return (f"banks of {dt.name}: the one-hot gather of a tile's rows "
                f"and the three-piece combine are exact for bfloat16 "
                f"operands alone")
    if x_dtype is not None and jnp.dtype(x_dtype) != dt:
        return f"x of {jnp.dtype(x_dtype).name} against banks of {dt.name}"
    if lead_dims > 1:
        return (f"banks indexed at {lead_dims} leading dimensions: the "
                f"index maps take the layer alone")
    if tokens > _ROWS_RESIDENT_MAX:
        return (f"{tokens} tokens: x and the float32 result stay in fast "
                f"memory for the whole grid up to {_ROWS_RESIDENT_MAX} rows "
                f"(longer prefills keep the loop)")
    if tile % 16 != 0:
        return f"tiles of {tile} rows are not whole 16-row bfloat16 tiles"
    if d % 128 != 0 or f % 128 != 0:
        return (f"an expert of [{d}, {f}] is not whole 128-lane tiles "
                f"either way")
    bf = block_of_f(d, f, tile, dt.itemsize)
    if bf is None:
        return (f"no block of f={f} in whole 128-lane tiles holds three "
                f"[{d}, block] matrices twice in {_WEIGHT_BUDGET >> 20} MiB")
    rows = _resident_rows(tokens)
    held = (2 * 3 * d * bf * dt.itemsize            # weight blocks, twice
            + 2 * rows * d * (dt.itemsize + 4)      # x and the result
            + tile * d * (dt.itemsize + 4 + 4)      # gathered rows, acc, w*y
            + 3 * tile * bf * 4)                    # gate, up, hidden
    if held > _VMEM_LIMIT - (8 << 20):
        return (f"{held >> 20} MiB of blocks at tile={tile}, [{d}, {bf}]: "
                f"over the {_VMEM_LIMIT >> 20} MiB the kernel may take")
    return None


def live_step(t, j, n_live, n_f: int):
    """The (tile, f block) whose blocks grid step ``(t, j)`` names: itself
    while ``t`` is a live tile, the LAST live tile's last block past it, so
    that a dead step's blocks are the ones already in fast memory."""
    live = t < n_live
    return (jnp.where(live, t, jnp.maximum(n_live - 1, 0)),
            jnp.where(live, j, n_f - 1))


def _grouped_mlp_kernel(expert_ref, n_ref, layer_ref, sel_ref, w_ref, x_ref,
                        gate_ref, up_ref, down_ref, out_ref, xe_ref, acc_ref,
                        *, n_f):
    """Grid step ``(t, j)``: tile ``t``'s rows through block ``j`` of its
    expert's ``f``. ``sel_ref [tile, rows]`` one-hot (a dead slot's row all
    zeros), ``w_ref [tile, 128]`` the pairs' weights along every lane,
    ``x_ref [rows, d]`` and ``out_ref [rows, d]`` float32 resident."""
    del expert_ref, layer_ref           # the index maps read them
    t, j = pl.program_id(0), pl.program_id(1)

    @pl.when((t == 0) & (j == 0))
    def _first_step():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(t < n_ref[0])
    def _live_tile():
        @pl.when(j == 0)
        def _gather_rows():
            xe_ref[...] = jnp.dot(
                sel_ref[...], x_ref[...],
                preferred_element_type=f32).astype(xe_ref.dtype)
        xe = xe_ref[...]
        g = jnp.dot(xe, gate_ref[...], preferred_element_type=f32)
        u = jnp.dot(xe, up_ref[...], preferred_element_type=f32)
        part = jnp.dot((jax.nn.silu(g) * u).astype(xe.dtype), down_ref[...],
                       preferred_element_type=f32)

        def combine(y):
            yw = y * w_ref[:, 0:1]
            sel = sel_ref[...]
            total = None
            for _ in range(3):      # 3 x 8 bits: all of a float32
                piece = yw.astype(sel.dtype)
                yw = yw - piece.astype(f32)
                back = jax.lax.dot_general(
                    sel, piece, (((0,), (0,)), ((), ())),
                    preferred_element_type=f32)
                total = back if total is None else total + back
            out_ref[...] += total

        if n_f == 1:
            combine(part)
        else:
            @pl.when(j == 0)
            def _first_block():
                acc_ref[...] = part

            @pl.when(j > 0)
            def _later_block():
                acc_ref[...] += part

            @pl.when(j == n_f - 1)
            def _last_block():
                combine(acc_ref[...])


def grouped_mlp(x, tile_expert, n_live, sel, w, gate, up, down, layer=0):
    """``out[token] = sum over the tiles of w * E_expert(x[token])``, float32
    ``[T, d]``, over the tile schedule of ``moe/grouped.py``.

    ``x [T, d]``; ``tile_expert [n_tiles]`` int32 the held expert of each
    tile of the static grid (any held expert past the live ones);
    ``n_live`` how many tiles exist; ``sel [n_tiles, tile, T]`` one-hot in
    ``x``'s dtype, row ``i`` of tile ``t`` naming the token of its ``i``-th
    pair (all zeros for a slot past the expert's pairs); ``w [n_tiles,
    tile]`` float32 the pairs' weights; ``gate``/``up [L, H, d, f]`` and
    ``down [L, H, f, d]`` the banks where they lie (or ``[H, ...]`` with no
    layer), read at ``layer`` (a traced scalar under the layer scan). Raises
    ``KernelUnsupported`` outside :func:`grouped_mlp_refusal`."""
    T, d = x.shape
    n_tiles, tile, _ = sel.shape
    lead_dims = gate.ndim - 3
    f = gate.shape[-1]
    reason = grouped_mlp_refusal(T, d, f, tile, gate.dtype, lead_dims,
                                 x.dtype)
    if reason is not None:
        refuse("grouped_mlp", f"x={x.shape} tile={tile} gate={gate.shape}",
               reason)
    if lead_dims == 0:
        gate, up, down = gate[None], up[None], down[None]
    bf = block_of_f(d, f, tile, gate.dtype.itemsize)
    n_f = f // bf
    rows = _resident_rows(T)
    x = jnp.pad(x, ((0, rows - T), (0, 0)))
    sel = jnp.pad(sel, ((0, 0), (0, 0), (0, rows - T)))
    w = jnp.broadcast_to(w.astype(f32)[..., None], (n_tiles, tile, 128))

    def tile_block(t, j, expert_ref, n_ref, layer_ref):
        at, _ = live_step(t, j, n_ref[0], n_f)
        return at, 0, 0

    def resident(t, j, expert_ref, n_ref, layer_ref):
        return 0, 0

    def column_block(t, j, expert_ref, n_ref, layer_ref):
        at, blk = live_step(t, j, n_ref[0], n_f)
        return layer_ref[0], expert_ref[at], 0, blk

    def row_block(t, j, expert_ref, n_ref, layer_ref):
        at, blk = live_step(t, j, n_ref[0], n_f)
        return layer_ref[0], expert_ref[at], blk, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,          # tile -> expert, live tiles, layer
        grid=(n_tiles, n_f),
        in_specs=[
            pl.BlockSpec((None, tile, rows), tile_block),           # sel
            pl.BlockSpec((None, tile, 128), tile_block),            # w
            pl.BlockSpec((rows, d), resident),                      # x
            pl.BlockSpec((None, None, d, bf), column_block),        # gate
            pl.BlockSpec((None, None, d, bf), column_block),        # up
            pl.BlockSpec((None, None, bf, d), row_block),           # down
        ],
        out_specs=pl.BlockSpec((rows, d), resident),
        scratch_shapes=[pltpu.VMEM((tile, d), x.dtype),             # xe
                        pltpu.VMEM((tile, d), f32)],                # acc
    )
    out = pl.pallas_call(
        functools.partial(_grouped_mlp_kernel, n_f=n_f),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((rows, d), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="grouped_mlp",
        interpret=interpret_mode(),
    )(jnp.asarray(tile_expert, jnp.int32),
      jnp.asarray(n_live, jnp.int32).reshape(1),
      jnp.asarray(layer, jnp.int32).reshape(1),
      sel, w, x, gate, up, down)
    return out[:T]
