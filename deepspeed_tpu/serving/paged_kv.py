"""Paged KV cache: block tables, copy-on-write forking, prefix sharing.

The slotted arena (serving/kv_cache.py) pins ``max_seq_len`` KV positions
per slot for every request: a short request strands the tail of its lane,
and identical prefixes (system prompts, few-shot templates) are prefilled
and stored once PER REQUEST. This module is the vLLM-PagedAttention /
SGLang-RadixAttention shape specialized to this engine's constraints:

  * the KV arena becomes a pool of fixed-size blocks
    ``[num_blocks, block_size, h*d]`` per layer, and each slot holds a
    BLOCK TABLE (``[T]`` int32 per slot, ``T = max_seq_len//block_size``)
    threaded through the decode program as a device array — the model's
    ``_kv_write_paged`` scatters through it, the paged attention op
    gathers through it;
  * blocks are refcounted: a prefix-cache entry and any number of live
    requests may reference the same block read-only; the first writer
    copies (COW) — one jitted block-copy program per fork;
  * a prefix cache keyed on the prompt token bytes makes a repeated
    prompt skip prefill entirely: its full blocks are shared by
    refcount-bump, its partial tail block is COW-forked, and the stored
    first sampled token (greedy-deterministic) seeds decode.

Allocation policy is UPFRONT RESERVATION: a request leases
``ceil((prompt_len + max_new_tokens)/block_size)`` blocks at admission or
is not admitted (FIFO head-of-line wait; ``REJECT_KV_OOM`` at submit for
requests no empty pool could ever hold). No preemption, no swapping —
a leased request always runs to termination, which keeps the scheduler's
fill/remaining arithmetic identical to the dense arena's.

Safety invariants (the reasoning the tests pin down):
  * blocks referenced by the prefix cache (refcount >= 1) are never on
    the free list, so a planned COW source cannot be re-leased between
    planning and the device copy — hit plans additionally hold a
    temporary refcount on the COW source across same-batch evictions;
  * device dispatch order is the write order on one JAX stream: hit
    forks are dispatched BEFORE miss inserts in an admission round, and
    stale speculative writes from retired lanes land before the block's
    next owner overwrites them (the same discipline the dense arena
    relies on);
  * bit-exact parity with the dense oracle needs
    ``block_size | max_seq_len`` and per-sequence capacity
    ``T*block_size == max_seq_len`` — both enforced at construction.

Host classes (:class:`BlockAllocator`, :class:`PrefixCache`,
:class:`PagedSlotAllocator`) import no JAX and unit-test at CPU speed;
:class:`PagedKVCacheManager` owns the device pool and the two jitted
programs (scatter-insert, COW-fork)."""

from __future__ import annotations

import dataclasses
import heapq
import re
from collections import OrderedDict, deque
from functools import partial
from typing import Any, Deque, Dict, List, Optional, Tuple

import numpy as np

from .kv_cache import declared_head_dim


class BlockAllocator:
    """Refcounted fixed-size block pool with an LRU free list.

    ``alloc`` returns the least-recently-freed block (FIFO recycle order
    keeps just-freed blocks cold longest — their stale speculative
    writes are the furthest back in dispatch order) or None when the
    pool is exhausted; OOM is a value, never an exception."""

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 1:
            raise ValueError(f"num_blocks must be >= 1, got {num_blocks}")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        self._free: Deque[int] = deque(range(num_blocks))
        self.refcount = np.zeros(num_blocks, np.int32)
        self.peak_used = 0

    def alloc(self) -> Optional[int]:
        if not self._free:
            return None
        block = self._free.popleft()
        self.refcount[block] = 1
        self.peak_used = max(self.peak_used, self.n_used)
        return block

    def incref(self, block: int) -> None:
        if self.refcount[block] < 1:
            raise ValueError(f"block {block} is not allocated")
        self.refcount[block] += 1

    def decref(self, block: int) -> None:
        if self.refcount[block] < 1:
            raise ValueError(f"block {block} is not allocated")
        self.refcount[block] -= 1
        if self.refcount[block] == 0:
            self._free.append(block)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return self.num_blocks - len(self._free)


@dataclasses.dataclass
class _PrefixEntry:
    blocks: Tuple[int, ...]      # every prompt block, in position order
    prompt_len: int
    first_token: int             # greedy-deterministic token #1


class PrefixCache:
    """LRU map from prompt token bytes -> cached prompt blocks.

    Keyed on the EXACT token sequence (``prompt.tobytes()`` — a
    dict-hashed prompt-token key), so a hit shares the whole prompt:
    full blocks by refcount, the partial tail by COW. Entries hold their
    own refcount on every block, so cached prefixes survive the request
    that created them; eviction (capacity or allocator pressure) drops
    those refs and frees whatever no live request still shares."""

    def __init__(self, capacity: int = 64):
        self.capacity = capacity
        self._entries: "OrderedDict[bytes, _PrefixEntry]" = OrderedDict()
        self.hits = 0            # successful hit-plan admissions
        self.misses = 0          # successful miss-plan admissions
        self.evictions = 0
        # demotion hook (serving/kv_tiers.py): called with (key, entry)
        # BEFORE the entry's block refs drop, while the blocks still
        # hold their device payload — eviction becomes demotion
        self.on_evict = None

    @staticmethod
    def key_for(prompt) -> bytes:
        return np.asarray(prompt, np.int32).tobytes()

    def lookup(self, key: bytes) -> Optional[_PrefixEntry]:
        """Peek without touching hit/miss counters (the allocator counts
        only on a SUCCESSFUL lease — a deferred or OOM-blocked attempt
        retried every pump must not inflate the rates)."""
        entry = self._entries.get(key)
        if entry is not None:
            self._entries.move_to_end(key)
        return entry

    def put(self, key: bytes, blocks: Tuple[int, ...], prompt_len: int,
            first_token: int, block_allocator: BlockAllocator) -> bool:
        if self.capacity <= 0 or key in self._entries:
            return False
        for b in blocks:
            block_allocator.incref(b)
        self._entries[key] = _PrefixEntry(tuple(blocks), prompt_len,
                                          first_token)
        while len(self._entries) > self.capacity:
            self.evict_lru(block_allocator)
        return True

    def pop(self, key: bytes, block_allocator: BlockAllocator) -> None:
        entry = self._entries.pop(key, None)
        if entry is not None:
            for b in entry.blocks:
                block_allocator.decref(b)

    def evict_lru(self, block_allocator: BlockAllocator) -> bool:
        if not self._entries:
            return False
        key, entry = self._entries.popitem(last=False)
        self._drop(key, entry, block_allocator)
        return True

    def demote(self, key: bytes, block_allocator: BlockAllocator) -> bool:
        """Evict ONE entry by key through the demotion hook — the
        explicit 'push this prefix down a tier' verb (tests, and the
        fleet's make-fetchable path)."""
        entry = self._entries.pop(key, None)
        if entry is None:
            return False
        self._drop(key, entry, block_allocator)
        return True

    def _drop(self, key: bytes, entry: _PrefixEntry,
              block_allocator: BlockAllocator) -> None:
        if self.on_evict is not None:
            self.on_evict(key, entry)
        for b in entry.blocks:
            block_allocator.decref(b)
        self.evictions += 1

    def __contains__(self, key: bytes) -> bool:
        """Pure membership peek — no LRU reordering, no counter touch.
        The fleet router probes every replica's cache per placement
        decision; a probe must not refresh entries the replica itself
        never re-used."""
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    @property
    def blocks_held(self) -> int:
        return sum(len(e.blocks) for e in self._entries.values())


@dataclasses.dataclass
class PagedAdmitPlan:
    """What ``alloc_request`` decided for one admitted request; the
    engine pops it (``take_plan``) and turns it into device work: a
    ``_fork`` dispatch for hits, prefill + scatter-insert (+
    ``commit_prefix``) for misses."""
    slot: int
    hit: bool
    key: Optional[bytes]         # None: prefix caching off for this req
    fill: int                    # prompt_len (the slot's starting fill)
    first_token: Optional[int]   # hits only: cached greedy token #1
    cow: Optional[Tuple[int, int]]   # (src, dst) tail fork; hits only
    n_shared: int                # full blocks shared by refcount


class PagedSlotAllocator:
    """Slot accounting over a block pool: the dense
    :class:`~deepspeed_tpu.serving.kv_cache.SlotAllocator` interface
    (``fill``/``active``/``advance``/``remaining``/``free``/occupancy —
    the scheduler and engine drive both identically) plus block tables,
    request-shaped allocation (``alloc_request``) and prefix-cache
    commit. Host-side only — no JAX."""

    def __init__(self, max_batch: int, max_seq_len: int, *,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefix_cache: Optional[PrefixCache] = None,
                 prefix_caching: bool = True):
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        if max_seq_len % block_size != 0:
            raise ValueError(
                f"block_size {block_size} must divide max_seq_len "
                f"{max_seq_len} (bit-parity needs T*block_size == max_seq)")
        self.max_batch = max_batch
        self.max_seq_len = max_seq_len
        self.block_size = block_size
        self.blocks_per_seq = max_seq_len // block_size
        if num_blocks is None:
            # pool bytes == dense arena bytes: the equal-HBM comparison
            num_blocks = max_batch * self.blocks_per_seq
        self.blocks = BlockAllocator(num_blocks, block_size)
        self.prefix = prefix_cache if prefix_cache is not None \
            else PrefixCache()
        self.prefix_enabled = prefix_caching
        self._free_slots: List[int] = list(range(max_batch))
        heapq.heapify(self._free_slots)
        self.fill = np.zeros(max_batch, np.int32)
        self.active = np.zeros(max_batch, bool)
        self.tables: List[List[int]] = [[] for _ in range(max_batch)]
        self.plans: Dict[int, PagedAdmitPlan] = {}
        self._pending: set = set()   # prompt keys mid-prefill (defer dups)
        # KVTierManager when tiering is on (PagedKVCacheManager wires
        # it): tier-held prompts defer admission while promoting
        self.tier = None
        self.peak_active = 0
        self.cow_forks = 0

    # ------------------------------------------------------------- leases
    def alloc_request(self, req) -> Optional[int]:
        """Plan one request's admission: lease a slot plus its FULL block
        reservation (prompt + max_new budget), sharing/forking through
        the prefix cache when the prompt is cached. None = not admissible
        yet (no slot, not enough blocks even after cache eviction, or an
        identical prompt is mid-prefill — admitting it next pump turns a
        duplicate prefill into a hit). The decision is recorded in
        ``self.plans[slot]`` for the engine."""
        if not self._free_slots:
            return None
        bs = self.block_size
        pl_ = int(req.prompt_len)
        n_total = -(-(pl_ + int(req.max_new_tokens)) // bs)
        if n_total > self.blocks_per_seq:
            n_total = self.blocks_per_seq    # submit() caps at max_seq_len
        key = PrefixCache.key_for(req.prompt) if self.prefix_enabled \
            else None
        entry = None
        if key is not None:
            if key in self._pending:
                return None
            entry = self.prefix.lookup(key)
            if (entry is None and self.tier is not None
                    and self.tier.holds(key)):
                # tier hit: DEFER (the async promotion is overlapped
                # against running chunks; the engine installs it at a
                # later admission pass and this retry becomes a plain
                # HBM hit) — same retry-next-pump contract as the
                # duplicate-prompt deferral above
                self.tier.request_promotion(key)
                return None
        if entry is not None:
            return self._lease_hit(req, key, entry, n_total)
        return self._lease_miss(req, key, pl_, n_total)

    def _lease_hit(self, req, key, entry, n_total) -> Optional[int]:
        bs = self.block_size
        pl_ = int(req.prompt_len)
        n_full = pl_ // bs                   # shareable read-only
        has_tail = pl_ % bs != 0
        n_new = n_total - n_full             # COW dst (if tail) + fresh
        if not self._ensure_free(n_new):
            return None
        shared = list(entry.blocks[:n_full])
        for b in shared:
            self.blocks.incref(b)
        new_blocks = [self.blocks.alloc() for _ in range(n_new)]
        cow = None
        if has_tail:
            src = entry.blocks[n_full]
            # temporary hold: a later same-round eviction must not free
            # the COW source before the device copy is dispatched
            # (released by PagedKVCacheManager.apply_fork)
            self.blocks.incref(src)
            cow = (src, new_blocks[0])
            self.cow_forks += 1
        slot = self._take_slot(pl_, shared + new_blocks)
        self.plans[slot] = PagedAdmitPlan(
            slot=slot, hit=True, key=key, fill=pl_,
            first_token=entry.first_token, cow=cow, n_shared=n_full)
        self.prefix.hits += 1
        return slot

    def _lease_miss(self, req, key, pl_, n_total) -> Optional[int]:
        if not self._ensure_free(n_total):
            return None
        table = [self.blocks.alloc() for _ in range(n_total)]
        slot = self._take_slot(pl_, table)
        if key is not None:
            self._pending.add(key)
            self.prefix.misses += 1
        self.plans[slot] = PagedAdmitPlan(
            slot=slot, hit=False, key=key, fill=pl_,
            first_token=None, cow=None, n_shared=0)
        return slot

    def _take_slot(self, fill_len: int, table: List[int]) -> int:
        slot = heapq.heappop(self._free_slots)
        self.active[slot] = True
        self.fill[slot] = fill_len
        self.tables[slot] = table
        self.peak_active = max(self.peak_active, self.n_active)
        return slot

    def _ensure_free(self, n: int) -> bool:
        """Evict cold prefix-cache entries until ``n`` blocks are free.
        Entries shared with live requests may free nothing — each
        eviction still retires one entry, so the loop terminates."""
        while self.blocks.n_free < n:
            if not self.prefix.evict_lru(self.blocks):
                return False
        return True

    def alloc_span(self, fill_len: int,
                   n_blocks: int) -> Optional[int]:
        """Lease a slot with EXACTLY ``n_blocks`` fresh blocks at fill
        ``fill_len`` — the migration-import lease: the incoming request
        already has its KV (the bundle carries the block payload), so no
        prefix planning, no admit plan, just a slot whose table can
        receive the scattered blocks. None = no slot or not enough
        blocks even after cache eviction (OOM is a value)."""
        if n_blocks < 1 or n_blocks > self.blocks_per_seq:
            raise ValueError(
                f"n_blocks {n_blocks} out of range [1, "
                f"{self.blocks_per_seq}]")
        if not self._free_slots:
            return None
        if not self._ensure_free(n_blocks):
            return None
        table = [self.blocks.alloc() for _ in range(n_blocks)]
        return self._take_slot(fill_len, table)

    def alloc(self, fill_len: int = 0) -> Optional[int]:
        """Dense-compatible lease (no Request in hand): reserves the full
        per-sequence block budget, skipping the prefix cache. The
        scheduler prefers ``alloc_request``; this exists for drivers and
        tests written against the SlotAllocator interface."""
        if fill_len > self.max_seq_len:
            raise ValueError(
                f"fill_len {fill_len} exceeds max_seq_len {self.max_seq_len}")
        if not self._free_slots:
            return None
        if not self._ensure_free(self.blocks_per_seq):
            return None
        table = [self.blocks.alloc() for _ in range(self.blocks_per_seq)]
        return self._take_slot(fill_len, table)

    def free(self, slot: int) -> None:
        if not self.active[slot]:
            raise ValueError(f"slot {slot} is not active")
        for b in self.tables[slot]:
            self.blocks.decref(b)
        self.tables[slot] = []
        self.active[slot] = False
        self.fill[slot] = 0
        self.plans.pop(slot, None)
        heapq.heappush(self._free_slots, slot)

    def advance(self, slots) -> None:
        self.fill[np.asarray(slots, np.int64)] += 1

    # ------------------------------------------------------ prefix commit
    def commit_prefix(self, slot: int, key: Optional[bytes],
                      first_token: int) -> Optional[Tuple[int, int]]:
        """After a MISS's prefill lands: cache the prompt blocks under
        ``key``. If the prompt ends mid-block the request's tail block is
        now shared with the cache, so the request COWs it — a fresh block
        replaces it in the table (cache keeps the original). Returns the
        (src, dst) pair the caller must copy on device, or None."""
        if key is None:
            return None
        self._pending.discard(key)
        if not self.active[slot]:
            return None                      # request already retired
        bs = self.block_size
        pl_ = int(self.fill[slot])
        n_prompt = -(-pl_ // bs)
        prompt_blocks = tuple(self.tables[slot][:n_prompt])
        if not self.prefix.put(key, prompt_blocks, pl_, int(first_token),
                               self.blocks):
            return None
        if pl_ % bs == 0:
            return None                      # tail is block-aligned
        src = self.tables[slot][n_prompt - 1]
        dst = self.blocks.alloc()
        if dst is None:
            # cannot privatize the tail: un-cache instead of sharing a
            # block the request is about to write into
            self.prefix.pop(key, self.blocks)
            return None
        self.tables[slot][n_prompt - 1] = dst
        self.blocks.decref(src)              # slot's ref; cache keeps one
        self.cow_forks += 1
        return (src, dst)

    def release_cow_hold(self, block: int) -> None:
        """Drop the temporary refcount a hit plan held on its COW source
        (call strictly AFTER the device copy is dispatched)."""
        self.blocks.decref(block)

    def padded_table(self, slot: int) -> np.ndarray:
        # pad with the num_blocks SENTINEL, not 0: entries past the
        # slot's reservation must never name a real block — a
        # speculative-verify write past the reservation routes through
        # the padding and must hit the kernel's drop guard, while a 0
        # pad would silently corrupt block 0 (likely leased elsewhere)
        out = np.full(self.blocks_per_seq, self.blocks.num_blocks,
                      np.int32)
        table = self.tables[slot]
        out[:len(table)] = table
        return out

    # ------------------------------------------------------------ queries
    def remaining(self, slot: int) -> int:
        """Cache positions still writable: bounded by the slot's OWN
        block reservation, not the arena row extent."""
        return len(self.tables[slot]) * self.block_size \
            - int(self.fill[slot])

    @property
    def pool_capacity_tokens(self) -> int:
        return self.blocks.num_blocks * self.block_size

    @property
    def n_active(self) -> int:
        return int(self.active.sum())

    @property
    def n_free(self) -> int:
        return len(self._free_slots)

    @property
    def occupancy(self) -> float:
        return self.n_active / self.max_batch


_WORD = re.compile(r"[^A-Za-z0-9_]+")


def _norm_key(keystr: str) -> str:
    """Normalize a tree_util keystr across container types (dict vs
    FrozenDict render paths differently) for leaf pairing."""
    return _WORD.sub("/", keystr).strip("/")


class PagedKVCacheManager:
    """The device block pool: the model's flax ``cache`` pytree rebuilt
    with every ``cached_key``/``cached_value`` leaf as a flat block pool
    ``[..., num_blocks, block_size, h*d]``, per-slot ``cache_index``
    vectors (as in the dense arena) plus injected ``block_tables``
    leaves ``[..., max_batch, T]`` the decode program reads/writes
    through. Drop-in for
    :class:`~deepspeed_tpu.serving.kv_cache.SlotKVCacheManager` on the
    engine side: same ``insert_batch``/``update``/``arena_report``
    surface, plus ``apply_fork``/``commit_prefix``/``take_plan`` for the
    paged admission flow."""

    def __init__(self, model, params, max_batch: int, *,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 prefix_cache_capacity: int = 64,
                 prefix_caching: bool = True,
                 slot_axis: Optional[int] = None):
        import jax
        import jax.numpy as jnp

        cfg = getattr(model, "cfg", None)
        self.max_seq_len = int(getattr(cfg, "max_seq_len"))
        # fp itemsize the pool WOULD use without int8 KV (arena_report's
        # kv_bytes_saved baseline)
        self._fp_itemsize = int(jnp.dtype(
            getattr(cfg, "dtype", jnp.float32)).itemsize)
        self.block_size = int(block_size)
        T = self.max_seq_len // self.block_size
        self.allocator = PagedSlotAllocator(
            max_batch, self.max_seq_len, block_size=self.block_size,
            num_blocks=num_blocks,
            prefix_cache=PrefixCache(prefix_cache_capacity),
            prefix_caching=prefix_caching)
        self.num_blocks = self.allocator.blocks.num_blocks
        self.tier = None                     # KVTierManager (attach_tier)
        if slot_axis is None:
            slot_axis = 1 if getattr(cfg, "scan_layers", False) else 0
        self._slot_axis = slot_axis

        # Pool construction from the same eval_shape the dense arena
        # uses: no compute, no compile. kv leaves [.., B, S, h, d] (or
        # already-flat [.., B, S, h*d]) become [.., nb, bs, h*d]; the
        # per-slot cache_index widening matches the dense arena; every
        # attention scope gains a sibling block_tables leaf (stacked
        # [L, B, T] under scan_layers so nn.scan slices it per layer).
        ids = jnp.zeros((max_batch, 1), jnp.int32)
        pos = jnp.zeros((max_batch, 1), jnp.int32)
        shapes = jax.eval_shape(
            partial(model.apply, mutable=["cache"]),
            {"params": params}, ids, positions=pos)
        cache_shapes = shapes[1]["cache"]
        self.head_dim = partial(declared_head_dim, cache_shapes,
                                slot_axis + 1)

        nb, bs, ax = self.num_blocks, self.block_size, self._slot_axis

        def build(node):
            out: Dict[str, Any] = {}
            for name, v in node.items():
                if hasattr(v, "items"):
                    out[name] = build(v)
                elif "cache_index" in name:
                    out[name] = jnp.zeros(v.shape + (max_batch,), jnp.int32)
                else:
                    tail = v.shape[ax + 2:]
                    hd = int(np.prod(tail)) if tail else 1
                    out[name] = jnp.zeros(
                        v.shape[:ax] + (nb, bs, hd), v.dtype)
            if "cached_key" in node:
                idx_shape = node["cache_index"].shape
                out["block_tables"] = jnp.zeros(
                    idx_shape + (max_batch, T), jnp.int32)
            return out

        self.cache = build(cache_shapes)

        keystr = jax.tree_util.keystr
        flatten = jax.tree_util.tree_flatten_with_path

        @partial(jax.jit, donate_argnums=(0,))
        def _insert_paged(pool, pre, tables, slots, fills):
            """Scatter a batch-n prefill cache (leaves [.., n, S, ..],
            S == max_seq_len) into each request's reserved blocks.
            Position p of row i lands at flat pool index
            ``tables[i, p//bs]*bs + p%bs``; positions past the true
            prompt length route to the out-of-range sentinel and drop —
            a fresh block's tail stays whatever it held until the
            request's own decode writes it (masked until then, exactly
            like the dense arena's stale rows)."""
            pre_by_norm = {_norm_key(keystr(p)): leaf
                           for p, leaf in flatten(pre)[0]}

            def leaf(path, a):
                ks = keystr(path)
                if "block_tables" in ks:
                    return a.at[..., slots, :].set(tables)
                if "cache_index" in ks:
                    return a.at[..., slots].set(fills)
                o = pre_by_norm[_norm_key(ks)]
                lead = a.ndim - 3
                hd = a.shape[-1]
                n = o.shape[lead]
                S = o.shape[lead + 1]
                of = o.astype(a.dtype).reshape(
                    o.shape[:lead] + (n, S, hd))
                p = jnp.arange(S)
                blk = jnp.take(tables, p // bs, axis=1)          # [n, S]
                flat = blk * bs + (p % bs)[None, :]
                flat = jnp.where(p[None, :] < fills[:, None], flat,
                                 nb * bs)                        # sentinel
                flat = flat.reshape(n * S)

                def scat(pf, off):
                    return pf.reshape(nb * bs, hd).at[flat].set(
                        off.reshape(n * S, hd),
                        mode="drop").reshape(nb, bs, hd)

                f = scat
                for _ in range(lead):
                    f = jax.vmap(f)
                return f(a, of)

            return jax.tree_util.tree_map_with_path(leaf, pool)

        self._insert_paged = _insert_paged

        @partial(jax.jit, donate_argnums=(0,))
        def _fork(pool, slot, table_row, fill, src, dst):
            """Install one slot's lane state (block-table row + fill) and
            copy block src -> dst in every kv pool leaf — the COW fork.
            src == dst is the no-COW case (self-copy, a no-op write);
            one compiled program serves every hit admission."""
            def leaf(path, a):
                ks = keystr(path)
                if "block_tables" in ks:
                    return a.at[..., slot, :].set(table_row)
                if "cache_index" in ks:
                    return a.at[..., slot].set(fill)
                lead = a.ndim - 3
                blk = jnp.take(a, src, axis=lead)
                idx = (slice(None),) * lead + (dst,)
                return a.at[idx].set(blk)
            return jax.tree_util.tree_map_with_path(leaf, pool)

        self._fork = _fork

    # ----------------------------------------------------------- mutation
    def insert_batch(self, prefill_cache: Any, slots, fills) -> None:
        """Move a batch-n prefill cache into the n slots' reserved
        blocks. Donates and replaces the pool; compiles one program per
        batch size n (the prefill cache's S extent is always the model's
        full max_seq_len, so only n varies)."""
        import jax.numpy as jnp
        tables = np.stack([self.allocator.padded_table(int(s))
                           for s in slots])
        self.cache = self._insert_paged(
            self.cache, prefill_cache, jnp.asarray(tables),
            jnp.asarray(np.asarray(slots, np.int32)),
            jnp.asarray(np.asarray(fills, np.int32)))

    def apply_fork(self, plan: PagedAdmitPlan) -> None:
        """Realize a prefix-cache HIT on device: install the slot's
        block table + fill and COW-copy the partial tail block (self-copy
        when the prompt is block-aligned). Releases the plan's temporary
        hold on the COW source once the copy is in the dispatch queue."""
        import jax.numpy as jnp
        if plan.cow is not None:
            src, dst = plan.cow
        else:
            src = dst = self.allocator.tables[plan.slot][0]
        self.cache = self._fork(
            self.cache, jnp.int32(plan.slot),
            jnp.asarray(self.allocator.padded_table(plan.slot)),
            jnp.int32(plan.fill), jnp.int32(src), jnp.int32(dst))
        if plan.cow is not None:
            self.allocator.release_cow_hold(plan.cow[0])

    def commit_prefix(self, plan: PagedAdmitPlan,
                      first_token: int) -> Optional[Tuple[int, int]]:
        """After a MISS's prefill + insert: publish the prompt blocks to
        the prefix cache and, when the prompt ends mid-block, dispatch
        the request-side COW copy so the cached tail stays immutable."""
        import jax.numpy as jnp
        cow = self.allocator.commit_prefix(plan.slot, plan.key,
                                           first_token)
        if cow is not None:
            src, dst = cow
            self.cache = self._fork(
                self.cache, jnp.int32(plan.slot),
                jnp.asarray(self.allocator.padded_table(plan.slot)),
                jnp.int32(int(self.allocator.fill[plan.slot])),
                jnp.int32(src), jnp.int32(dst))
        return cow

    def take_plan(self, slot: int) -> PagedAdmitPlan:
        return self.allocator.plans.pop(slot)

    def install_table(self, slot: int) -> None:
        """Install a MISS lane's block table + fill on device WITHOUT a
        prefill insert — the fused-prefill admission path: the decode
        scan itself writes the prompt's KV block-granularly, it only
        needs the lane's table row and write index live first. Reuses
        the hit-fork program with a self-copy (src == dst, a no-op
        block write)."""
        import jax.numpy as jnp
        t0 = int(self.allocator.tables[slot][0])
        self.cache = self._fork(
            self.cache, jnp.int32(slot),
            jnp.asarray(self.allocator.padded_table(slot)),
            jnp.int32(int(self.allocator.fill[slot])),
            jnp.int32(t0), jnp.int32(t0))

    def abandon_plan(self, plan: PagedAdmitPlan) -> None:
        """Walk back a MISS plan whose lane retired before its first
        token (fused-prefill cancel / expiry mid-prompt): drop the
        pending-prompt key so duplicate prompts stop deferring on a
        commit that will never come. The lane's blocks free through the
        normal slot release."""
        if plan.key is not None:
            self.allocator._pending.discard(plan.key)

    # ------------------------------------------------- block portability
    def export_blocks(self, slot: int,
                      n_blocks: Optional[int] = None) -> Dict[str, Any]:
        """Gather one slot's leased KV blocks off-device: the payload a
        live migration ships. Returns ``{normalized leaf key ->
        np.ndarray [..., n, block_size, h*d]}`` in block-TABLE order
        (position order), for every kv pool leaf — index leaves
        (cache_index / block_tables) are reconstructed at import, never
        shipped. One eager gather per leaf; migration is a rare
        host-paced op, so nothing here is jitted (no retrace-budget
        surface)."""
        table = self.allocator.tables[slot]
        if n_blocks is None:
            n_blocks = len(table)
        return self.export_block_ids(table[:n_blocks])

    def export_block_ids(self, blocks) -> Dict[str, Any]:
        """``export_blocks`` by explicit block-id list (position order)
        instead of a slot's table — the tier-demotion gather reads a
        prefix-cache entry's blocks, which belong to no slot. Same
        eager no-jit rationale: demotion is host-paced, and the gather
        is dispatched before the caller's decrefs can recycle the
        blocks, so the payload is the pre-overwrite bytes."""
        import jax
        import jax.numpy as jnp
        idx = jnp.asarray(np.asarray(list(blocks), np.int32))
        gathered: Dict[str, Any] = {}
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.cache)[0]:
            ks = jax.tree_util.keystr(path)
            if "cache_index" in ks or "block_tables" in ks:
                continue
            lead = leaf.ndim - 3
            gathered[_norm_key(ks)] = jnp.take(leaf, idx, axis=lead)
        # one transfer for the whole tree — per-leaf np.asarray would
        # block on a device sync per leaf, which shows up directly in
        # the demotion path's host time
        return jax.device_get(gathered)

    def import_blocks(self, slot: int, leaves: Dict[str, Any]) -> None:
        """Scatter exported block payloads into ``slot``'s freshly
        leased blocks (``alloc_span``) and install the slot's table row +
        write cursor on device — the receiving half of a live migration.
        ``leaves`` maps normalized leaf keys (``export_blocks`` output)
        to ``[..., n, block_size, h*d]`` arrays; ``n`` may be smaller
        than the lease (only written blocks ship). Eager per-leaf
        scatter, same rare-op rationale as ``export_blocks``."""
        import jax
        import jax.numpy as jnp
        table = self.allocator.tables[slot]
        fill = int(self.allocator.fill[slot])

        def leaf(path, a):
            ks = jax.tree_util.keystr(path)
            if "block_tables" in ks:
                return a.at[..., slot, :].set(
                    jnp.asarray(self.allocator.padded_table(slot)))
            if "cache_index" in ks:
                return a.at[..., slot].set(jnp.int32(fill))
            payload = leaves.get(_norm_key(ks))
            if payload is None:
                raise KeyError(
                    f"migration bundle is missing kv leaf {ks!r}")
            lead = a.ndim - 3
            n = payload.shape[lead]
            idx = jnp.asarray(np.asarray(table[:n], np.int32))
            sel = (slice(None),) * lead + (idx,)
            return a.at[sel].set(jnp.asarray(payload).astype(a.dtype))

        self.cache = jax.tree_util.tree_map_with_path(leaf, self.cache)

    def update(self, new_cache: Any) -> None:
        self.cache = new_cache

    # ------------------------------------------------------------ tiering
    def attach_tier(self, tier) -> None:
        """Wire a :class:`~deepspeed_tpu.serving.kv_tiers.KVTierManager`
        behind the allocator: prefix-cache eviction becomes DEMOTION
        (gather + DRAM admit), and tier-held prompts defer admission
        while their async promotion runs."""
        self.tier = tier
        self.allocator.tier = tier
        self.allocator.prefix.on_evict = self._demote_entry

    def _demote_entry(self, key: bytes, entry) -> None:
        """Eviction hook (engine thread — eviction fires inside
        allocator calls the engine drives): gather the entry's blocks
        off-device and admit them to the DRAM tier."""
        if self.tier is None or key is None:
            return
        leaves = self.export_block_ids(entry.blocks)
        self.tier.admit(key, entry.prompt_len, entry.first_token, leaves)

    def demote_prefix(self, key: bytes) -> bool:
        """Explicitly push one cached prefix down to the tier (tests and
        the fleet's make-fetchable path). Engine thread only."""
        return self.allocator.prefix.demote(key, self.allocator.blocks)

    def readmit_prefix(self, key: bytes, prompt_len: int,
                       first_token: int, leaves: Dict[str, Any]) -> bool:
        """Install a completed promotion back into HBM: lease blocks,
        eagerly scatter the payload into them (the import_blocks pattern
        — no slot, no table row), and republish the prefix-cache entry.
        The next ``alloc_request`` for this prompt is then a plain HBM
        hit. Returns False when the pool cannot free enough blocks —
        the caller returns the payload to the tier and retries later.
        Engine thread only; eager, zero jit variants."""
        installed, _rejected = self.readmit_prefix_many(
            [(key, prompt_len, first_token, leaves)])
        return bool(installed)

    def readmit_prefix_many(self, entries):
        """Batched :meth:`readmit_prefix`: every promotion that drained
        ready in the same admission pass installs through ONE scatter
        per pool leaf (indices and payloads concatenated on the block
        axis). Eager-op dispatch dominates the install cost, so k
        simultaneous promotions cost one entry's dispatch, not k.
        ``entries`` is ``[(key, prompt_len, first_token, leaves), ...]``;
        returns ``(installed_keys, rejected_entries)`` where rejected
        entries did not fit the pool (caller returns them to the tier).
        Engine thread only; eager, zero jit variants."""
        import jax
        import jax.numpy as jnp
        al = self.allocator
        bs = self.block_size
        installed: list = []
        rejected: list = []
        plan: list = []           # (key, plen, ftok, leaves, blocks)
        for key, plen, ftok, leaves in entries:
            if al.prefix.lookup(key) is not None:
                installed.append(key)        # re-prefilled meanwhile
                continue
            n = -(-int(plen) // bs)
            if not al._ensure_free(n):
                rejected.append((key, plen, ftok, leaves))
                continue
            plan.append((key, plen, ftok, leaves,
                         [al.blocks.alloc() for _ in range(n)]))
        if not plan:
            return installed, rejected
        idx = jnp.asarray(np.asarray(
            [b for *_, blks in plan for b in blks], np.int32))

        def leaf(path, a):
            ks = jax.tree_util.keystr(path)
            if "cache_index" in ks or "block_tables" in ks:
                return a
            lead = a.ndim - 3
            parts = []
            for _key, _plen, _ftok, leaves, _blks in plan:
                payload = leaves.get(_norm_key(ks))
                if payload is None:
                    raise KeyError(
                        f"promotion payload is missing kv leaf {ks!r}")
                parts.append(np.asarray(payload))
            payload = parts[0] if len(parts) == 1 else \
                np.concatenate(parts, axis=lead)
            sel = (slice(None),) * lead + (idx,)
            return a.at[sel].set(jnp.asarray(payload).astype(a.dtype))

        self.cache = jax.tree_util.tree_map_with_path(leaf, self.cache)
        for key, plen, ftok, _leaves, blks in plan:
            al.prefix.put(key, tuple(blks), int(plen), int(ftok),
                          al.blocks)
            for b in blks:
                al.blocks.decref(b)          # cache holds the sole ref
            installed.append(key)
        return installed, rejected

    # ---------------------------------------------------------- accounting
    def arena_report(self) -> dict:
        """Block-pool HBM accounting: the paged analogue of the dense
        ``arena_report``. Keeps the dense report's load-bearing keys
        (``arena_bytes``/``kv_bytes``/``index_bytes``/``bytes_per_slot``/
        ``headroom_bytes``/``n_active``/``n_free``) so the engine gauges
        and bench specs read both layouts, and adds the block-pool view:
        bytes per block, blocks total/used/free/peak, and the prefix
        cache's share of the pool."""
        import jax
        kv_bytes = 0
        index_bytes = 0
        int8_payload = 0
        scale_bytes = 0
        for path, leaf in jax.tree_util.tree_flatten_with_path(
                self.cache)[0]:
            nbytes = getattr(leaf, "nbytes", None)
            if nbytes is None:
                continue
            ks = jax.tree_util.keystr(path)
            if "cache_index" in ks or "block_tables" in ks:
                index_bytes += int(nbytes)
            else:
                kv_bytes += int(nbytes)
                if "scale" in ks:
                    scale_bytes += int(nbytes)
                elif leaf.dtype == np.int8:
                    int8_payload += int(nbytes)
        kv_bytes_fp = (kv_bytes - int8_payload - scale_bytes
                       + int8_payload * self._fp_itemsize)
        al = self.allocator
        bytes_per_block = kv_bytes // self.num_blocks
        bytes_per_token = bytes_per_block // self.block_size \
            if self.block_size else 0
        per_slot = bytes_per_token * self.max_seq_len
        used = al.blocks.n_used
        free_ = al.blocks.n_free
        held = al.prefix.blocks_held
        rep = {
            "layout": "paged",
            "arena_bytes": kv_bytes + index_bytes,
            "kv_bytes": kv_bytes,
            "index_bytes": index_bytes,
            "int8_payload_bytes": int8_payload,
            "scale_bytes": scale_bytes,
            "kv_bytes_fp_equiv": kv_bytes_fp,
            "kv_bytes_saved": kv_bytes_fp - kv_bytes,
            "max_batch": al.max_batch,
            "max_seq_len": self.max_seq_len,
            "block_size": self.block_size,
            "blocks_total": self.num_blocks,
            "blocks_used": used,
            "blocks_free": free_,
            "blocks_peak_used": al.blocks.peak_used,
            "blocks_per_seq": al.blocks_per_seq,
            "bytes_per_block": bytes_per_block,
            "bytes_per_token": bytes_per_token,
            "bytes_per_slot": per_slot,
            "n_active": al.n_active,
            "n_free": al.n_free,
            "active_bytes": used * bytes_per_block,
            "headroom_bytes": free_ * bytes_per_block,
            "prefix_cache_entries": len(al.prefix),
            "prefix_cache_blocks": held,
            "prefix_cache_share": held / self.num_blocks,
        }
        if self.tier is not None:
            # per-tier accounting rides along under its own versioned
            # schema (dstpu-tiers-v1): hbm_* mirrors the pool numbers so
            # the tiers block reads standalone on dashboards
            tiers = self.tier.report()
            tiers["hbm_bytes"] = rep["active_bytes"]
            tiers["hbm_capacity_bytes"] = kv_bytes
            tiers["hbm_blocks"] = used
            rep["tiers"] = tiers
        return rep

    # ---------------------------------------------- allocator passthrough
    @property
    def prefix_enabled(self) -> bool:
        return self.allocator.prefix_enabled

    @property
    def prefix_cache(self) -> PrefixCache:
        return self.allocator.prefix

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
