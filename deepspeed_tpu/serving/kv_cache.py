"""Slotted KV-cache management for continuous-batching serving.

Reference analogue: the inference kernel's per-request KV arena
(``csrc/transformer/inference/includes/context.h`` allocates one workspace
sized ``[max_out_tokens, ...]`` per layer and hands each request a region).
Here the arena is the model's own flax ``cache`` collection, widened to a
fixed ``[max_batch]`` slot axis with a PER-SLOT fill index — the vLLM/
PagedAttention idea specialized to TPU constraints: rather than paging
variable-sized blocks (dynamic shapes XLA would recompile on), every
request leases one fixed ``[max_seq, ...]`` slot row, and slot reuse is a
single fused ``dynamic_update_slice`` per cache leaf.

The leaves are layer-stacked (``blocks/attn/cached_key|cached_value
[L, B, S, h, d]``, ``cache_index [L, B]`` under ``scan_layers``). The decode
programs donate this tree and get the same buffers back: the model's layer
loop carries a cache that is passed in and writes one token per lane at
``(layer, lane, pos)`` in place (``models/gpt.py::_kv_write``), so a step
never slices, restacks or copies the arena. Only ``insert``/``insert_batch``
below move a row's worth of KV, once per admission.

Two layers, deliberately separable:
  * :class:`SlotAllocator` — pure host-side accounting (free list, per-slot
    fill lengths, occupancy). No JAX. Unit-testable at CPU speed.
  * :class:`SlotKVCacheManager` — owns the device arena pytree and the
    jitted slot-insert program; composes a SlotAllocator for the
    bookkeeping.
"""

from __future__ import annotations

import heapq
from functools import partial
from typing import Any, List, Optional

import numpy as np


def declared_head_dim(cache_shapes, seq_axis: int,
                      num_heads: Optional[int]) -> Optional[int]:
    """The head size of the keys and values a model's ``cache`` collection
    declares (its ``eval_shape``; ``seq_axis`` = where the positions lie):
    the last dim of a ``[.., S, h, d]`` leaf, or a flat ``[.., S, h*d]``
    ``cached_key``'s width over ``num_heads``. None where no leaf has heads
    (a latent leaf ``[.., S, r]`` serves every head with one row): what
    shards or tiles by heads then has nothing to go by, and says so."""
    import jax
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache_shapes)[0]:
        tail = leaf.shape[seq_axis + 1:]
        if len(tail) == 2:
            return int(tail[1])
        if (len(tail) == 1 and num_heads
                and "cached_key" in jax.tree_util.keystr(path)):
            return int(tail[0]) // int(num_heads)
    return None


class SlotAllocator:
    """Host-side slot accounting: a fixed pool of ``max_batch`` cache rows,
    each leased to at most one in-flight request, with per-slot fill
    lengths (number of valid KV positions). Lowest-index-first allocation
    keeps runs deterministic."""

    def __init__(self, max_batch: int, max_seq_len: int):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self._free: List[int] = list(range(max_batch))
        heapq.heapify(self._free)
        self.fill = np.zeros(max_batch, np.int32)
        self.active = np.zeros(max_batch, bool)

    # ------------------------------------------------------------- leases
    def alloc(self, fill_len: int = 0) -> Optional[int]:
        """Lease the lowest free slot at ``fill_len`` valid positions;
        None when every slot is busy (caller applies backpressure)."""
        if not self._free:
            return None
        if fill_len > self.max_seq_len:
            raise ValueError(
                f"fill_len {fill_len} exceeds max_seq_len {self.max_seq_len}")
        slot = heapq.heappop(self._free)
        self.active[slot] = True
        self.fill[slot] = fill_len
        return slot

    def free(self, slot: int) -> None:
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        self.active[slot] = False
        self.fill[slot] = 0
        heapq.heappush(self._free, slot)

    def advance(self, slots) -> None:
        """One decode step wrote one token into each of ``slots``."""
        self.fill[np.asarray(slots, np.int64)] += 1

    # ------------------------------------------------------------ queries
    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def occupancy(self) -> float:
        return self.n_active / self.max_batch

    def remaining(self, slot: int) -> int:
        """Cache positions still writable in this slot's row."""
        return self.max_seq_len - int(self.fill[slot])


class SlotKVCacheManager:
    """The device arena: the model's flax ``cache`` pytree widened to
    ``[..., max_batch, max_seq, ...]`` with per-slot ``cache_index``
    vectors, plus the jitted insert that moves one prefilled request's KV
    into its leased slot row.

    ``slot_axis``: position of the batch/slot axis in the cached k/v
    leaves — 1 when the model scans its layers (leaves are stacked
    ``[L, B, S, ...]``), 0 otherwise.
    """

    def __init__(self, model, params, max_batch: int, *,
                 slot_axis: Optional[int] = None):
        import jax
        import jax.numpy as jnp

        cfg = getattr(model, "cfg", None)
        self.max_seq_len = int(getattr(cfg, "max_seq_len"))
        # fp itemsize the arena WOULD use without int8 KV — the baseline
        # for arena_report's kv_bytes_saved accounting
        self._fp_itemsize = int(jnp.dtype(
            getattr(cfg, "dtype", jnp.float32)).itemsize)
        self.allocator = SlotAllocator(max_batch, self.max_seq_len)
        # rows a lane's state holds in a layer: a row a position, unless the
        # model keeps another count (a window beside chunk summaries)
        lane_rows = getattr(model, "lane_rows", None)
        self.rows_per_slot = int(lane_rows()) if lane_rows \
            else self.max_seq_len
        if slot_axis is None:
            slot_axis = 1 if getattr(cfg, "scan_layers", False) else 0
        self._slot_axis = slot_axis

        # Arena construction via eval_shape: no compute, no compile — just
        # the cache pytree the decode path would allocate for a [B, 1]
        # step, with every leaf zeroed and the scalar-per-layer
        # ``cache_index`` widened to a per-slot [..., B] vector (the shape
        # models/gpt.py's _decode_attention dispatches per-slot mode on).
        ids = jnp.zeros((max_batch, 1), jnp.int32)
        pos = jnp.zeros((max_batch, 1), jnp.int32)
        shapes = jax.eval_shape(
            partial(model.apply, mutable=["cache"]),
            {"params": params}, ids, positions=pos)
        cache_shapes = shapes[1]["cache"]
        self.head_dim = partial(declared_head_dim, cache_shapes,
                                slot_axis + 1)

        def build(path, leaf):
            if "cache_index" in jax.tree_util.keystr(path):
                return jnp.zeros(leaf.shape + (max_batch,), jnp.int32)
            return jnp.zeros(leaf.shape, leaf.dtype)

        self.cache = jax.tree_util.tree_map_with_path(build, cache_shapes)

        ax = self._slot_axis

        @partial(jax.jit, donate_argnums=(0,))
        def _insert(arena, one, slot, fill):
            def leaf(a, o):
                if a.ndim == o.ndim:        # cached_key / cached_value rows
                    start = tuple(slot if i == ax else 0
                                  for i in range(a.ndim))
                    return jax.lax.dynamic_update_slice(
                        a, o.astype(a.dtype), start)
                # per-slot fill vector: the TRUE prompt length, not the
                # prefill program's padded index
                return a.at[..., slot].set(fill)
            return jax.tree.map(leaf, arena, one)

        self._insert = _insert

        @partial(jax.jit, donate_argnums=(0,))
        def _insert_batch(arena, batched, slots, fills):
            """Move a batch-n bucketed prefill cache into n leased slot
            rows. The prefill leaves are [.., n, P_bucket, ..] with
            P_bucket <= max_seq — only the bucket's prefix of each row is
            overwritten; stale tail rows from a previous occupant stay
            masked until the new request's own decode writes them, so they
            are never attended. WHICH rows a lane at its fill sees is the
            model's: a row a position sees the rows below the fill; a
            window beside chunk summaries (models/eva.py, whose prefill
            hands out both leaves whole) masks by ``fill mod w`` and by
            ``fill // w``. Leaves of any meaning pass here by shape."""
            def leaf(a, o):
                if a.ndim == o.ndim:        # cached_key / cached_value rows
                    for i in range(o.shape[ax]):    # n <= max_batch: unroll
                        row = jax.lax.dynamic_slice_in_dim(o, i, 1, axis=ax)
                        start = tuple(slots[i] if j == ax else 0
                                      for j in range(a.ndim))
                        a = jax.lax.dynamic_update_slice(
                            a, row.astype(a.dtype), start)
                    return a
                # per-slot fill vector: scatter the TRUE prompt lengths
                return a.at[..., slots].set(fills)
            return jax.tree.map(leaf, arena, batched)

        self._insert_batch = _insert_batch

    # ----------------------------------------------------------- mutation
    def insert(self, prefill_cache: Any, slot: int, fill_len: int) -> None:
        """Move a batch-1 prefilled cache into slot ``slot`` and pin its
        fill at ``fill_len`` (the unpadded prompt length). Donates and
        replaces the arena — one fused copy per cache leaf."""
        self.cache = self._insert(self.cache, prefill_cache,
                                  np.int32(slot), np.int32(fill_len))

    def insert_batch(self, prefill_cache: Any, slots, fills) -> None:
        """Move a batch-n bucketed prefill cache (leaves [.., n, P, ..])
        into the n slot rows ``slots``, pinning each slot's fill at its
        TRUE prompt length. Donates and replaces the arena. Compiles one
        program per (n, P_bucket) pair — the same lazy shape family as the
        bucketed prefill itself."""
        import jax.numpy as jnp
        self.cache = self._insert_batch(
            self.cache, prefill_cache,
            jnp.asarray(np.asarray(slots, np.int32)),
            jnp.asarray(np.asarray(fills, np.int32)))

    def update(self, new_cache: Any) -> None:
        """Adopt the cache returned by a (donating) decode step."""
        self.cache = new_cache

    # ---------------------------------------------------------- accounting
    def arena_report(self) -> dict:
        """HBM accounting of the arena pytree: total/kv/index bytes plus
        the derived per-slot, per-row and per-token costs and the current
        headroom (bytes of KV the free slots could still hold). A ROW is
        what a lane's state is made of (``rows_per_slot`` of them a layer,
        by the model's count) and a TOKEN a position of context: they are
        the same where the cache has a row a position; where it has not,
        ``bytes_per_token`` is a full lane's cost over its positions. This is
        the ground truth the admission cost model and the bench ``hbm``
        block read — computed from the live leaves, so dtype changes
        (e.g. a future int8 KV) are reflected automatically."""
        import jax
        import numpy as _np
        kv_bytes = 0
        index_bytes = 0
        int8_payload = 0            # quantized payload bytes
        scale_bytes = 0             # per-token f32 dequant multipliers
        # by what a leaf IS, whatever the model calls it: a leaf without a
        # sequence axis is a cursor; every other leaf is paid per position
        # (keys and values, a latent row, ...); beside an int8 payload a
        # float leaf of one value a position is its scale
        leaves = [x for x in jax.tree.leaves(self.cache)
                  if getattr(x, "nbytes", None) is not None]
        quantized = any(x.dtype == _np.int8 for x in leaves)
        for leaf in leaves:
            if leaf.ndim <= self._slot_axis + 1:
                index_bytes += int(leaf.nbytes)
                continue
            kv_bytes += int(leaf.nbytes)
            if leaf.dtype == _np.int8:
                int8_payload += int(leaf.nbytes)
            elif quantized and int(_np.prod(
                    leaf.shape[self._slot_axis + 2:])) == 1:
                scale_bytes += int(leaf.nbytes)
        # what the SAME payload would cost in the model's fp dtype (scale
        # leaves don't exist in fp mode): saved = fp-equivalent - actual
        kv_bytes_fp = (kv_bytes - int8_payload - scale_bytes
                       + int8_payload * self._fp_itemsize)
        alloc = self.allocator
        per_slot = kv_bytes // alloc.max_batch if alloc.max_batch else 0
        per_token = per_slot // self.max_seq_len if self.max_seq_len else 0
        return {
            "arena_bytes": kv_bytes + index_bytes,
            "kv_bytes": kv_bytes,
            "index_bytes": index_bytes,
            "int8_payload_bytes": int8_payload,
            "scale_bytes": scale_bytes,
            "kv_bytes_fp_equiv": kv_bytes_fp,
            "kv_bytes_saved": kv_bytes_fp - kv_bytes,
            "max_batch": alloc.max_batch,
            "max_seq_len": self.max_seq_len,
            "bytes_per_slot": per_slot,
            "rows_per_slot": self.rows_per_slot,
            "bytes_per_row": per_slot // self.rows_per_slot,
            "bytes_per_token": per_token,
            "n_active": alloc.n_active,
            "n_free": alloc.n_free,
            "active_bytes": alloc.n_active * per_slot,
            "headroom_bytes": alloc.n_free * per_slot,
        }

    # ---------------------------------------------- allocator passthrough
    def alloc(self, fill_len: int = 0) -> Optional[int]:
        return self.allocator.alloc(fill_len)

    def free(self, slot: int) -> None:
        self.allocator.free(slot)

    @property
    def fill(self) -> np.ndarray:
        return self.allocator.fill

    @property
    def occupancy(self) -> float:
        return self.allocator.occupancy
