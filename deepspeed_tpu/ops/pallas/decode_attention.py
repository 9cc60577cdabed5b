"""Decode attention over a KV cache: three reads of it, and their gates.

Reference analogue: the ``softmax_context`` inference kernel
(``csrc/transformer/inference/csrc/softmax.cu``) — single-token attention
over the KV cache.

* :func:`masked_cache_attention` — the ONE masked einsum, every model's
  reference and what ``decode_impl="xla"``, every prefill, speculative,
  fused-prefill, window and int8 call runs: it reads all S rows of every
  lane whatever their fill.
* :func:`live_decode_attention` — what ``decode_impl="auto"`` (the
  default) runs for a one-token step on a TPU: ONE kernel call a layer is
  handed the layer-stacked arena leaves ``[L, b, S, h, d]`` whole, in HBM,
  as they lie (rank-4 rows at d % 128 == 0 have no padding, so nothing is
  sliced, reshaped or copied on the way in), and DMAs
  ``ceil(fill / 128)`` blocks of each lane's rows and nothing of a masked
  lane. On ``serve-batch`` (8 lanes x 2048, a fifth of them live) the
  16 layers' attention went from 6.18 ms (the einsum) to 1.33 ms
  (my chip run, PR 29); a prefix-bounded einsum took 3.21 ms. It takes a
  LIST of (k leaf, v leaf, fills) pairs under one softmax a lane: one for
  a NeoX block, two for a block that keeps a window beside chunk summaries
  (models/eva.py: each lane's live window blocks, then its live summary
  blocks, walked by the same loop). Leaves of one rank less are flat rows
  of grouped heads (models/afmoe.py: a ring pair or a global pair a
  layer), read by the same loop in blocks sized by the row's bytes.
* :func:`decode_attention` / :func:`paged_decode_attention` — the older
  kernels, asked for BY NAME (``decode_impl="pallas"``,
  ``megakernel=True``): all lanes ride one DMA window sized by the
  DEEPEST lane over a FLAT ``[b, S, h*d]`` cache (int8 dequant and the
  speculative width in the window). Their layout notes:

  * The flat layout is a free reshape of ``[b, S, h, d]`` only when the
    cache is STORED flat (models/gpt.py does under ``"pallas"``): rank-4
    rows tile (h, d) and lane-pad d = 64 to 128, which doubles the DMA
    bytes and makes dynamic sub-slices unaligned; ``(S, h*d)`` tiles
    exactly, so a ``[bk, h*d]`` block is one contiguous DMA.
  * Per-head dots become ONE MXU matmul against a block-diagonal query
    matrix qmat [h*d, hp] (qmat[g*d + j, g] = q[g, j]):
    s = k_flat @ qmat. The combine p^T @ v_flat yields [hp, h*d] whose
    row g holds every head's segment weighted by head g's probabilities;
    the wrapper slices the block diagonal — 16x more output elements than
    needed, but the arrays are tiny and it keeps the hot loop on the MXU.
  * Under a cache the layer loop carries they are handed a
    ``dynamic_index_in_dim`` of the stacked leaf, which a custom call's
    operand makes a COPY of the layer's rows every layer, every step; no
    cell has timed them since the loop carries the cache (PR 25).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import interpret_mode, refuse

NEG_INF = float(np.finfo(np.float32).min)

# Widest speculative-verify query width (k+1 draft positions) the kernels
# take in-kernel. The qmat lane dim is s*hp, so wider shapes would start
# eating MXU lanes for masked-out work; past this the gates refuse and the
# model takes the masked einsum (prefill always does — s there is
# prompt-len).
MAX_SPEC_S = 8


def _spec_live_mask(pos, fill, s, hp, shape):
    """[bk, s*hp] causal liveness: query column-group i (lanes i*hp ..
    (i+1)*hp) sits at absolute position ``fill - s + i``, so key position
    ``pos`` is visible iff ``pos < fill - (s-1) + i``. For s == 1 this is
    the plain filled-prefix mask (kept on its scalar form so the
    single-token hot path's codegen is untouched)."""
    if s == 1:
        return pos < fill
    qidx = jax.lax.broadcasted_iota(jnp.int32, shape, 1) // hp
    return pos < fill - (s - 1) + qidx


def _decode_kernel(meta_ref, qmat_ref, *refs, scale, block_k, b, hp, hd,
                   quantized=False, s=1):
    """Single program. k_hbm/v_hbm: full [b, S, h*d] refs in HBM;
    k_buf/v_buf: [2, b, block_k, h*d] VMEM slots — ALL batch rows ride one
    (strided) DMA per block, so the DMA count is O(live blocks), not
    O(b * live blocks). Online softmax state rides the loop carry; the
    per-batch dots unroll statically (b is small at decode time).

    meta_ref: [1 + b] scalars — [0] is the live block count (max over
    rows), [1 + bi] row bi's filled prefix length. Per-row lengths are what
    continuous-batching serving needs: every slot sits at its own fill, so
    the mask is per-row while the DMA window is sized by the deepest slot.

    ``quantized``: the cache rides int8 with per-position f32 dequant
    multipliers ks_hbm/vs_hbm [b, S] — int8 blocks are DMA-streamed
    (half/quarter the HBM bytes) and the scale-multiply happens here in
    VMEM right before the MXU dot.

    ``s``: static query positions per lane (the k+1 speculative-verify
    shape). The block-diagonal qmat widens to [h*d, s*hp] — column group
    i is query position i's block-diagonal matrix — so the s-position
    scores still come out of ONE MXU matmul; the causal mask staggers per
    column group (:func:`_spec_live_mask`) and the online-softmax carries
    widen to [b, s*hp]. The DMA window is unchanged: int8 dequant stays
    fused in VMEM, so the spec path never materializes an f32 cache."""
    if quantized:
        (k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf, ks_buf, vs_buf,
         k_sem, v_sem, ks_sem, vs_sem) = refs
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, k_sem, v_sem = refs
    nb = meta_ref[0]       # live kv blocks (max over batch rows)

    def block_copies(i, slot):
        win = pl.ds(i * block_k, block_k)
        out = [
            pltpu.make_async_copy(k_hbm.at[:, win], k_buf.at[slot],
                                  k_sem.at[slot]),
            pltpu.make_async_copy(v_hbm.at[:, win], v_buf.at[slot],
                                  v_sem.at[slot]),
        ]
        if quantized:
            out.append(pltpu.make_async_copy(
                ks_hbm.at[:, win], ks_buf.at[slot], ks_sem.at[slot]))
            out.append(pltpu.make_async_copy(
                vs_hbm.at[:, win], vs_buf.at[slot], vs_sem.at[slot]))
        return out

    # prologue: stage block 0 into slot 0
    for c in block_copies(0, 0):
        c.start()

    def body(i, carry):
        m_prev, l_prev, acc = carry                # [b,hp] [b,hp] [b,hp,hd]
        slot = jax.lax.rem(i, 2)
        nxt = i + 1

        @pl.when(nxt < nb)
        def _prefetch():
            ns = jax.lax.rem(nxt, 2)
            for c in block_copies(nxt, ns):
                c.start()

        for c in block_copies(i, slot):
            c.wait()
        pos = i * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, s * hp), 0)
        ms, ls, accs = [], [], []
        for bi in range(b):                        # static unroll
            live = _spec_live_mask(pos, meta_ref[1 + bi], s, hp,
                                   (block_k, s * hp))
            kbk = k_buf[slot, bi].astype(jnp.float32)   # [bk, h*d]
            vbk = v_buf[slot, bi].astype(jnp.float32)
            if quantized:
                kbk = kbk * ks_buf[slot, bi][:, None]
                vbk = vbk * vs_buf[slot, bi][:, None]
            qmat = qmat_ref[bi].astype(jnp.float32)     # [h*d, s*hp]
            sc = jax.lax.dot(kbk, qmat,
                             preferred_element_type=jnp.float32) * scale
            sc = jnp.where(live, sc, NEG_INF)
            m_new = jnp.maximum(m_prev[bi], jnp.max(sc, axis=0))
            p = jnp.exp(sc - m_new[None, :])
            corr = jnp.exp(m_prev[bi] - m_new)
            l_new = l_prev[bi] * corr + jnp.sum(p, axis=0)
            # p^T @ v: [hp, h*d]; row g = every segment under head-g weights
            pv = jax.lax.dot_general(p, vbk, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ms.append(m_new)
            ls.append(l_new)
            accs.append(acc[bi] * corr[:, None] + pv)
        return (jnp.stack(ms), jnp.stack(ls), jnp.stack(accs))

    m0 = jnp.full((b, s * hp), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s * hp), jnp.float32)
    a0 = jnp.zeros((b, s * hp, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, a0))
    l_safe = jnp.where(l == 0, 1.0, l)
    o_ref[...] = (acc / l_safe[:, :, None]).astype(o_ref.dtype)


def _pick_block(s: int, want: int = 256) -> Optional[int]:
    cand = want
    while cand >= 128:
        if s % cand == 0:
            return cand
        cand //= 2
    return s if s <= 128 and s % 8 == 0 else None


# Staging window budget of the all-lanes kernels (2 slots x k+v x b lanes).
# At GPT-2 125M (b 8, h*d 768) the 256-row bf16 window is 6 MiB and compiles
# on a v5e inside Mosaic's scoped VMEM (chip_smoke kernel leg, PR 21). What
# else has been through the compiler: the live-rows read's ONE-lane window
# at h*d 4096, 4 MiB at 128 rows (5.7 MB of scoped VMEM with its scores) and
# 8 MiB at 256 (PR 29, compiled for v5e and run); an all-lanes window there
# would be 32 MiB, which this budget refuses.
_VMEM_BUDGET = 8 * 1024 * 1024


def _choose_block(b: int, S: int, h: int, d: int, itemsize: int,
                  block_k: Optional[int] = None) -> Optional[int]:
    """kv block size for the DMA window, or None when the kernel can't run
    (S not block-decomposable, h*d lane-unaligned handled by caller, or the
    window would blow the VMEM arena even at the smallest block). Every
    candidate must divide S — a non-divisor would silently drop the cache
    tail (nb is clipped to S // bk)."""
    if block_k is not None:
        if S % block_k != 0:
            raise ValueError(
                f"block_k={block_k} must divide the cache length S={S}")
        bk = block_k
    else:
        bk = _pick_block(S)
    if bk is None:
        return None
    while bk > 128 and 4 * b * bk * h * d * itemsize > _VMEM_BUDGET \
            and S % (bk // 2) == 0:
        bk //= 2
    if S % bk != 0 or 4 * b * bk * h * d * itemsize > _VMEM_BUDGET:
        return None
    return bk


def decode_refusal(b: int, S: int, h: int, d: int, dtype, s: int = 1,
                   block_k: Optional[int] = None) -> Optional[str]:
    """Why the all-lanes FLAT-cache kernel (:func:`decode_attention`, asked
    for by name) cannot run this shape; None when it can. Callers choosing
    a cache LAYOUT (models/gpt.py flat cache) ask the same gate the kernel
    does. ``s``: query positions per lane (1 = plain decode, 2..MAX_SPEC_S
    = the speculative-verify shape). The default read's gate is
    :func:`live_decode_refusal`: one lane's window, so no budget on b."""
    if not 1 <= s <= MAX_SPEC_S:
        return (f"query width s={s} outside 1..{MAX_SPEC_S} (wider calls "
                f"are prefill and take the masked einsum)")
    if (h * d) % 128 != 0:
        return f"h*d={h * d} is not a multiple of the 128-lane tile"
    itemsize = jnp.dtype(dtype).itemsize
    if _choose_block(b, S, h, d, itemsize, block_k) is None:
        return (f"no kv block: cache length {S} must split into blocks of "
                f">= 128 rows (or be <= 128 and a multiple of 8) whose "
                f"double-buffered k+v window 4*b*bk*h*d*{itemsize} B stays "
                f"within the {_VMEM_BUDGET >> 20} MiB staging budget "
                f"(b={b}, h*d={h * d}: the 128-row window is "
                f"{4 * b * 128 * h * d * itemsize / 2 ** 20:.1f} MiB)")
    return None


def pallas_decode_supported(b: int, S: int, h: int, d: int, dtype,
                            s: int = 1) -> bool:
    return decode_refusal(b, S, h, d, dtype, s) is None


def _spec_qmat(q: jnp.ndarray, hp: int) -> jnp.ndarray:
    """Block-diagonal query matrix for s query positions:
    qmat[b, g*d + j, i*hp + g] = q[b, i, g, j] — column group i holds
    position i's block-diagonal so all s*h per-head dots are one MXU
    matmul against the flat [bk, h*d] cache block."""
    b, s, h, d = q.shape
    eye = jnp.eye(h, hp, dtype=q.dtype)                     # [h, hp]
    return jnp.einsum("bshd,hg->bhdsg", q, eye).reshape(b, h * d, s * hp)


def _slice_block_diagonal(out: jnp.ndarray, s: int, h: int,
                          d: int) -> jnp.ndarray:
    """Invert the block-diagonal packing: kernel output row i*hp + g holds
    every head's segment weighted under (query i, head g); the real output
    is segment g of that row -> [b, s, h, d]."""
    b, sp, hd = out.shape
    hp = sp // s
    out = out.reshape(b, s, hp, hd)[:, :, :h].reshape(b, s, h, h, d)
    out = jnp.diagonal(out, axis1=2, axis2=3)               # [b, s, d, h]
    return out.transpose(0, 1, 3, 2)                        # [b, s, h, d]


def decode_attention(q: jnp.ndarray, cached_key: jnp.ndarray,
                     cached_value: jnp.ndarray, cache_len,
                     scale: Optional[float] = None,
                     block_k: Optional[int] = None,
                     k_scale: Optional[jnp.ndarray] = None,
                     v_scale: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: [b, 1, h, d]. cached_key/value: PREFERABLY the flat [b, S, h*d]
    cache layout — rank-4 [b, S, h, d] caches are accepted but XLA
    lane-pads their d dim (64 -> 128), so every call pays a full-cache
    relayout copy; keep the cache flat (models/gpt.py does when decode_impl
    resolves to pallas). cache_len: count of valid cache positions
    (including this token, already written) — a scalar when every row sits
    at the same fill (single-stream generate), or a [b] int32 vector of
    per-row fills (slotted continuous-batching decode, serving/engine.py).
    Masked-lane entries may sit past the cache extent (the serving
    engine's retired-lane sentinel is ``max_seq_len``); they are clamped
    to S here so the DMA window / mask math stays in range — the lane's
    output is garbage the caller discards, never an OOB access.
    ``k_scale``/``v_scale`` [b, S] f32 mark an int8 cache
    (kv_cache_dtype="int8"): per-position dequant multipliers, applied in
    VMEM.
    ``s_q`` in 2..MAX_SPEC_S is the speculative-verify shape and stays on
    the kernel (s-position qmat). A shape the gate refuses
    (:func:`decode_refusal`) raises ``KernelUnsupported``; callers that
    may take the masked einsum instead ask the gate first.
    Returns [b, s_q, h, d] (so [b, 1, h, d] for plain decode)."""
    b, s_q, h, d = q.shape
    S = cached_key.shape[1]
    cache_len = jnp.minimum(jnp.asarray(cache_len, jnp.int32), S)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    reason = decode_refusal(b, S, h, d, cached_key.dtype, s_q, block_k)
    if reason is not None:
        refuse("decode_attention",
               f"q={q.shape} cache={cached_key.shape}", reason)
    bk = _choose_block(b, S, h, d, jnp.dtype(cached_key.dtype).itemsize,
                       block_k)
    flat = cached_key.ndim == 3
    quantized = k_scale is not None

    hp = -(-h // 8) * 8
    hd = h * d
    # block-diagonal query: qmat[g*d + j, i*hp + g] = q[i, g, j]
    qmat = _spec_qmat(q, hp)                                # [b, hd, s*hp]

    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,))
    # DMA window sized by the deepest row; shallower rows mask in-kernel
    nb = jnp.clip((jnp.max(clen) + bk - 1) // bk, 1, S // bk)
    meta = jnp.concatenate([nb[None], clen])

    if flat:
        kf, vf = cached_key, cached_value
    else:
        kf = cached_key.reshape(b, S, hd)
        vf = cached_value.reshape(b, S, hd)

    kernel = functools.partial(_decode_kernel, scale=scale, block_k=bk,
                               b=b, hp=hp, hd=hd, quantized=quantized,
                               s=s_q)
    in_specs = [
        pl.BlockSpec((b, hd, s_q * hp), lambda g, meta: (0, 0, 0)),
        # the cache never enters VMEM wholesale: the kernel DMAs only
        # live blocks out of HBM
        pl.BlockSpec(memory_space=pltpu.HBM),
        pl.BlockSpec(memory_space=pltpu.HBM),
    ]
    scratch = [
        pltpu.VMEM((2, b, bk, hd), cached_key.dtype),
        pltpu.VMEM((2, b, bk, hd), cached_value.dtype),
    ]
    sems = [pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,))]
    operands = [meta, qmat, kf, vf]
    if quantized:
        in_specs += [pl.BlockSpec(memory_space=pltpu.HBM),
                     pl.BlockSpec(memory_space=pltpu.HBM)]
        scratch += [pltpu.VMEM((2, b, bk), jnp.float32),
                    pltpu.VMEM((2, b, bk), jnp.float32)]
        sems += [pltpu.SemaphoreType.DMA((2,)),
                 pltpu.SemaphoreType.DMA((2,))]
        operands += [k_scale.astype(jnp.float32),
                     v_scale.astype(jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(1,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((b, s_q * hp, hd), lambda g, meta: (0, 0, 0)),
        scratch_shapes=scratch + sems,
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s_q * hp, hd), q.dtype),
        name="decode_attention",
        interpret=interpret_mode(),
    )(*operands)
    # block diagonal: (query i, head g)'s output is row i*hp+g, segment g
    return _slice_block_diagonal(out, s_q, h, d)


# --------------------------------------------------------------------------
# Dense decode over each lane's LIVE rows of the layer-stacked arena
# --------------------------------------------------------------------------

def _live_kernel(layer_ref, *refs, scale, block_k, b, rows, h, d,
                 kv_heads=None):
    """One program for all lanes, over ``len(rows)`` (k leaf, v leaf, fills)
    pairs under ONE softmax a lane. ``refs``: the pairs' fills (scalar
    prefetch), the queries, then each pair's arena leaves
    [L, b, rows[p], h, d] WHOLE in HBM (``kv_heads`` given: FLAT rows
    [L, b, rows[p], d] of that many key heads that the h queries share);
    k_buf/v_buf: VMEM slots of [2, block_k, h, d] ([2, block_k, d]) that
    every pair's blocks share.
    The scalar core first writes the step's schedule into SMEM — one entry
    (lane, block) per LIVE block, lane after lane, and within a lane pair
    after pair: a fill of f has ceil(f / block_k) of them, a masked lane (a
    fill past its leaf's rows, the engine's retired-lane sentinel) none in
    any pair — and ONE
    double-buffered loop then walks it, so the DMA of a lane's first block
    is in flight while the lane before it computes its last, whichever leaf
    either lies in. Online-softmax state rides the loop carry and starts
    anew at a lane's first entry; a lane's output is written at its last, a
    masked lane's stays zero. With more than one pair a third SMEM array
    says which pair's leaves an entry's copy starts from; a single pair
    emits no such branch.

    Per block: the [block_k, h, d] rows are [block_k*h, d] to the MXU (a
    free reshape, h being whole sublane tiles), the h queries meet all of
    them in one dot on bf16 operands with float32 accumulation, and the
    mask keeps of column (k, g) the row g alone (and k < fill), so the
    rest of the body is flash attention with one query row a head. Over
    flat rows the queries come widened to the row's d columns (zeros
    outside their own key head's) and meet the [block_k, d] block as it
    lies: scores [h, block_k], masked by k < fill alone; p @ v gives every
    head's context in its own key head's columns of [h, d], and a lane's
    result keeps those columns alone, [h, d / kv_heads]."""
    P = len(rows)
    fill_refs, q_ref, leaves = refs[:P], refs[P], refs[P + 1:3 * P + 1]
    o_ref, k_buf, v_buf, k_sem, v_sem, lane_of, blk_of, *pair_of = \
        refs[3 * P + 1:]
    layer = layer_ref[0]

    def lane_blocks(lane, n):
        fills = [f[lane] for f in fill_refs]
        masked = functools.reduce(
            jnp.logical_or, [f > S for f, S in zip(fills, rows)])
        for p, f in enumerate(fills):
            def put(j, n, p=p):
                # stores to the kernel's SMEM scratch refs, not host state
                lane_of[n] = lane   # tracelint: disable=mutation-in-trace
                blk_of[n] = j       # tracelint: disable=mutation-in-trace
                if pair_of:
                    pair_of[0][n] = p  # tracelint: disable=mutation-in-trace
                return n + 1
            n = jax.lax.fori_loop(
                0, jnp.where(masked, 0, (f + block_k - 1) // block_k), put, n)
        return n

    total = jax.lax.fori_loop(0, b, lane_blocks, jnp.int32(0))
    lane_of[total] = b      # no lane's: the last entry ends its lane

    def copies(i, slot, p):
        at = (layer, lane_of[i], pl.ds(blk_of[i] * block_k, block_k))
        return (pltpu.make_async_copy(leaves[2 * p].at[at], k_buf.at[slot],
                                      k_sem.at[slot]),
                pltpu.make_async_copy(leaves[2 * p + 1].at[at],
                                      v_buf.at[slot], v_sem.at[slot]))

    def start(i, slot):
        if not pair_of:
            for c in copies(i, slot, 0):
                c.start()
            return
        for p in range(P):
            @pl.when(pair_of[0][i] == p)
            def _from_pair(p=p):
                for c in copies(i, slot, p):
                    c.start()

    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(total > 0)
    def _prologue():
        start(0, 0)

    flat = kv_heads is not None
    if flat:
        # column k of a block's [h, block_k] scores is key k for every head
        n = block_k
        own_key = jax.lax.broadcasted_iota(jnp.int32, (h, n), 1)
    else:
        # column (k, g) of a block's [h, block_k*h] scores is key k under
        # head g's rows: row g keeps it while k is under the lane's fill, no
        # other row ever does
        n = block_k * h
        col = jax.lax.broadcasted_iota(jnp.int32, (h, n), 1)
        row = jax.lax.broadcasted_iota(jnp.int32, (h, n), 0)
        never = max(rows)                              # under no fill
        own_key = jnp.where(col % h == row, col // h, never)

    def body(i, carry):
        m_prev, l_prev, acc = carry                # [h,1] [h,1] [h,d]
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < total)
        def _prefetch():
            start(i + 1, 1 - slot)

        # a wait reads its semaphore and its window's size alone, which
        # every pair's blocks share
        for c in copies(i, slot, 0):
            c.wait()
        lane, blk = lane_of[i], blk_of[i]
        fill = fill_refs[0][lane]
        for pair in range(1, P):
            fill = jnp.where(pair_of[0][i] == pair, fill_refs[pair][lane],
                             fill)
        first = jnp.logical_or(i == 0,
                               lane_of[jnp.maximum(i - 1, 0)] != lane)
        m_prev = jnp.where(first, NEG_INF, m_prev)
        l_prev = jnp.where(first, 0.0, l_prev)
        acc = jnp.where(first, 0.0, acc)
        q = q_ref[lane]                                         # [h, d]
        sc = jax.lax.dot_general(
            q, k_buf[slot].reshape(n, d), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale         # [h, n]
        sc = jnp.where(own_key < fill - blk * block_k, sc, NEG_INF)
        # a scheduled block holds a live key, so every head's max is finite
        # and a masked column's exp is an exact zero
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = jax.lax.dot(p.astype(v_buf.dtype), v_buf[slot].reshape(n, d),
                         preferred_element_type=jnp.float32)    # [h, d]
        acc = acc * corr + pv

        @pl.when(lane_of[i + 1] != lane)
        def _lane_done():
            out = acc / l_new
            if flat:
                # head n's context lies in key head n // (h / kv_heads)'s
                # columns; the others hold what its zeros met
                dh, group = d // kv_heads, h // kv_heads
                head = jax.lax.broadcasted_iota(jnp.int32, (h, 1), 0)
                out = functools.reduce(jnp.add, [
                    jnp.where(head // group == g, out[:, g * dh:(g + 1) * dh],
                              0.0) for g in range(kv_heads)])
            o_ref[lane] = out.astype(o_ref.dtype)
        return m_new, l_new, acc

    jax.lax.fori_loop(
        0, total, body,
        (jnp.full((h, 1), NEG_INF, jnp.float32),
         jnp.zeros((h, 1), jnp.float32), jnp.zeros((h, d), jnp.float32)))


# Rows a DMA of the live-rows read carries. One lane's k+v window of 128
# rows, double-buffered, is 4 MiB at h*d 4096 in bf16, and the [h, 128*h]
# float32 scores and probabilities beside it 1 MiB each: inside Mosaic's
# scoped VMEM on a v5e. 256 rows read more dead rows a lane and were slower
# at every fill tried (1.43 against 1.33 ms for serve-batch's 16 layers; my
# chip run, PR 29).
_LIVE_BLOCK = 128
# Bytes of a leaf a block of FLAT rows carries at the least: a row of
# grouped heads is a few key heads wide (512 values in serve-agent, 1 KiB),
# so 128 of them would be a 128 KiB DMA that the loop's own cost a block
# outweighs. serve-agent's five layers' read at its cell's shapes took 4.38
# ms in blocks of 128 rows, 3.34 at 256, 3.05 at 512, 3.16 at 1,024 and
# 3.23 at 2,048 (the masked einsum 6.65; one TPU v5e chip): past 512 KiB
# a block rounds a fill up by more than it saves.
_LIVE_BLOCK_BYTES = 1 << 19


def live_block(S: int, row_bytes: Optional[int] = None) -> int:
    """Rows a DMA of the live-rows read carries over a cache of S rows:
    ``_LIVE_BLOCK`` of a head a query head; of flat rows of ``row_bytes``
    (grouped heads) the largest power of two that carries
    ``_LIVE_BLOCK_BYTES``, and never fewer than ``_LIVE_BLOCK``."""
    rows = _LIVE_BLOCK
    if row_bytes is not None:
        rows = max(rows, 1 << ((_LIVE_BLOCK_BYTES // row_bytes).bit_length()
                               - 1))
    return min(rows, S)


def live_decode_refusal(b: int, S, h: int, d: int, dtype, s: int = 1,
                        block_k: Optional[int] = None,
                        row: Optional[int] = None) -> Optional[str]:
    """Why :func:`live_decode_attention` cannot run this shape; None when
    it can. ``S``: the rows of a lane's leaf, or of each pair's leaves (one
    block size serves them all). ``row``: the values of a FLAT row of
    grouped heads (``hk * d``), None for rows of a head a query head. It
    reads the rows as they lie, so it takes what lies without padding: a
    head (a flat row) of whole 128-lane rows, heads in whole sublane
    tiles, a plain floating cache, one query a lane."""
    if s != 1:
        return ("more than one query a lane: the live-rows read takes the "
                "decode width alone (prefill, speculative and fused-prefill "
                "widths take the masked einsum)")
    dt = jnp.dtype(dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        return (f"cache dtype {dt.name}: the live-rows read has no dequant "
                f"in its window")
    sublane = 32 // dt.itemsize
    if row is None and d % 128 != 0:
        return (f"head size d={d} is not whole 128-lane rows: a rank-4 "
                f"[b, S, h, d] leaf is lane-padded in HBM")
    if row is not None and row % 128 != 0:
        return (f"a flat row of {row} values is not whole 128-lane rows: "
                f"its blocks would be lane-padded in VMEM")
    if row is not None and (row % d != 0 or h % (row // d) != 0):
        return (f"a flat row of {row} values is not key heads of d={d} "
                f"that h={h} query heads share evenly")
    if h % sublane != 0:
        return (f"h={h} heads are not whole {sublane}-row sublane tiles of "
                f"{dt.name}")
    rows = (S,) if isinstance(S, int) else tuple(S)
    bk = block_k or live_block(min(rows),
                               None if row is None else row * dt.itemsize)
    for n in rows:
        if n % bk != 0:
            return (f"cache length {n} is not a multiple of the {bk}-row "
                    f"block")
    return None


def live_decode_attention(q: jnp.ndarray, pairs, layer=None,
                          scale: Optional[float] = None,
                          block_k: Optional[int] = None) -> jnp.ndarray:
    """q: [b, 1, h, d]. ``pairs``: one ``(k_leaf, v_leaf, fills)`` a kind of
    cache rows the lanes hold, attended under ONE softmax a lane (a NeoX
    block's keys and values: one pair; a window beside chunk summaries,
    models/eva.py: two). The leaves as the layer loop carries them,
    [L, b, S, h, d] with ``layer`` a (traced) index, or one layer's
    [b, S, h, d] with ``layer`` None; S may differ from pair to pair.
    Leaves of one rank less are FLAT rows of grouped heads, [L, b, S,
    hk * d] (models/afmoe.py): query head n reads key head n // (h / hk),
    so the queries are widened to the row's columns with zeros outside
    their own key head's, and each head's context is read back out of its
    own columns. ``fills``: the pair's valid rows a lane (this token's
    included, already written), scalar or [b]; a lane with a fill past its
    leaf's S is MASKED (the serving engine's retired-lane sentinel writes
    at ``max_seq_len``): nothing of it is read in any pair and its output
    is zeros the caller discards. Reads ceil(fill / block) blocks of each
    lane's rows in each pair and nothing else of the leaves; no slice or
    reshape of a leaf is made on the way in. Returns [b, 1, h, d]."""
    b, s_q, h, d = q.shape
    if layer is None:
        pairs, layer = [(k[None], v[None], f) for k, v, f in pairs], 0
    rows = tuple(k.shape[2] for k, _, _ in pairs)
    dtype = pairs[0][0].dtype
    row = pairs[0][0].shape[3] if pairs[0][0].ndim == 4 else None
    bk = block_k or live_block(
        min(rows), None if row is None else row * jnp.dtype(dtype).itemsize)
    reason = live_decode_refusal(b, rows, h, d, dtype, s_q, bk, row=row)
    if reason is not None:
        refuse("live_decode_attention",
               f"q={q.shape} cache={[k.shape for k, _, _ in pairs]}", reason)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    fills = [jnp.broadcast_to(jnp.asarray(f, jnp.int32), (b,))
             for _, _, f in pairs]
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    queries, width, block, kv_heads = q.reshape(b, h, d), d, (h, d), None
    if row is not None:
        # [h, hk]: 1 where query head n reads key head n // (h / hk)
        kv_heads = row // d
        own = (jnp.arange(h)[:, None] // (h // kv_heads)
               == jnp.arange(kv_heads)[None, :]).astype(q.dtype)
        queries = (queries[:, :, None, :] * own[None, :, :, None]
                   ).reshape(b, h, row)
        width, block = row, (row,)
    kernel = functools.partial(_live_kernel, scale=scale, block_k=bk, b=b,
                               rows=rows, h=h, d=width, kv_heads=kv_heads)
    # the schedule: every block of every lane, and the entry that ends it
    n_max = b * sum(S // bk for S in rows) + 1

    def whole(n):
        return pl.BlockSpec((b, h, n), lambda g, *prefetched: (0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1 + len(pairs),   # layer index + per-lane fills
        grid=(1,),
        in_specs=[whole(width)] + [pl.BlockSpec(memory_space=pltpu.HBM)]
        * (2 * len(pairs)),
        out_specs=whole(d),
        scratch_shapes=[
            pltpu.VMEM((2, bk) + block, dtype),
            pltpu.VMEM((2, bk) + block, dtype),
            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
        ] + [pltpu.SMEM((n_max,), jnp.int32)]
        * (2 if len(pairs) == 1 else 3),
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, d), q.dtype),
        name="decode_attention_live",
        interpret=interpret_mode(),
    )(layer, *fills, queries,
      *(leaf for k, v, _ in pairs for leaf in (k, v)))
    return out[:, None]


# --------------------------------------------------------------------------
# Paged decode attention: gather K/V through a per-row block table
# --------------------------------------------------------------------------

def _paged_decode_kernel(meta_ref, bt_ref, qmat_ref, *refs, scale, b, hp,
                         hd, bs, nb_total, quantized=False, s=1):
    """Paged variant of :func:`_decode_kernel`. k_hbm/v_hbm are the FULL
    block pools [nb_total, bs, h*d] in HBM; each fori step DMAs one
    block PER ROW (rows no longer share a contiguous window — that is
    the price of paging, paid as b strided copies per step instead of
    one), double-buffered through [2, b, bs, h*d] VMEM with a (2, b)
    semaphore grid. meta_ref: [1 + b] — [0] the live block count (max
    over rows), [1 + bi] row bi's filled prefix. bt_ref: [b, T] block
    tables (scalar-prefetch, so the DMA source indices are host-known
    ints at issue time); entries past a row's reservation are clamped
    into the pool and masked dead by the fill. ``quantized``: int8 pools;
    the per-position f32 dequant multipliers arrive ALREADY gathered
    through the table as VMEM blocks ks_ref/vs_ref [b, T, bs] (4 bytes a
    position against h*d payload bytes — see the wrapper for why they are
    not DMA'd per block) and are applied in VMEM.
    ``s``: static query positions per lane (the
    speculative-verify shape — same widened qmat / staggered mask as
    :func:`_decode_kernel`)."""
    if quantized:
        (k_hbm, v_hbm, ks_ref, vs_ref, o_ref, k_buf, v_buf, k_sem,
         v_sem) = refs
    else:
        k_hbm, v_hbm, o_ref, k_buf, v_buf, k_sem, v_sem = refs
    nb = meta_ref[0]

    def row_copies(i, slot, bi):
        blk = jnp.minimum(bt_ref[bi, i], nb_total - 1)
        return [
            pltpu.make_async_copy(k_hbm.at[blk], k_buf.at[slot, bi],
                                  k_sem.at[slot, bi]),
            pltpu.make_async_copy(v_hbm.at[blk], v_buf.at[slot, bi],
                                  v_sem.at[slot, bi]),
        ]

    for bi in range(b):                    # prologue: stage block 0
        for c in row_copies(0, 0, bi):
            c.start()

    def body(i, carry):
        m_prev, l_prev, acc = carry            # [b,hp] [b,hp] [b,hp,hd]
        slot = jax.lax.rem(i, 2)
        nxt = i + 1

        @pl.when(nxt < nb)
        def _prefetch():
            ns = jax.lax.rem(nxt, 2)
            for bi in range(b):
                for c in row_copies(nxt, ns, bi):
                    c.start()

        pos = i * bs + jax.lax.broadcasted_iota(jnp.int32, (bs, s * hp), 0)
        ms, ls, accs = [], [], []
        for bi in range(b):                    # static unroll
            for c in row_copies(i, slot, bi):
                c.wait()
            live = _spec_live_mask(pos, meta_ref[1 + bi], s, hp,
                                   (bs, s * hp))
            kbk = k_buf[slot, bi].astype(jnp.float32)     # [bs, h*d]
            vbk = v_buf[slot, bi].astype(jnp.float32)
            if quantized:
                kbk = kbk * ks_ref[bi, i][:, None]
                vbk = vbk * vs_ref[bi, i][:, None]
            qmat = qmat_ref[bi].astype(jnp.float32)       # [h*d, s*hp]
            sc = jax.lax.dot(kbk, qmat,
                             preferred_element_type=jnp.float32) * scale
            sc = jnp.where(live, sc, NEG_INF)
            m_new = jnp.maximum(m_prev[bi], jnp.max(sc, axis=0))
            p = jnp.exp(sc - m_new[None, :])
            corr = jnp.exp(m_prev[bi] - m_new)
            l_new = l_prev[bi] * corr + jnp.sum(p, axis=0)
            pv = jax.lax.dot_general(p, vbk, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
            ms.append(m_new)
            ls.append(l_new)
            accs.append(acc[bi] * corr[:, None] + pv)
        return (jnp.stack(ms), jnp.stack(ls), jnp.stack(accs))

    m0 = jnp.full((b, s * hp), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, s * hp), jnp.float32)
    a0 = jnp.zeros((b, s * hp, hd), jnp.float32)
    _, l, acc = jax.lax.fori_loop(0, nb, body, (m0, l0, a0))
    l_safe = jnp.where(l == 0, 1.0, l)
    o_ref[...] = (acc / l_safe[:, :, None]).astype(o_ref.dtype)


def paged_decode_refusal(b: int, block_size: int, h: int, d: int,
                         dtype, s: int = 1) -> Optional[str]:
    """Why the paged kernel cannot run this shape; None when it can:
    lane-aligned h*d, sublane-aligned block_size (the DMA unit), the
    double-buffered staging window within the VMEM budget, and the query
    width s within the in-kernel speculative-verify range."""
    if not 1 <= s <= MAX_SPEC_S:
        return f"query width s={s} outside 1..{MAX_SPEC_S}"
    if (h * d) % 128 != 0:
        return f"h*d={h * d} is not a multiple of the 128-lane tile"
    itemsize = jnp.dtype(dtype).itemsize
    sublane = max(8, 32 // itemsize)
    if block_size % sublane != 0:
        return (f"kv block_size={block_size} is not a multiple of the "
                f"{sublane}-row sublane tile of {jnp.dtype(dtype).name} "
                f"(the per-block DMA unit)")
    window = 4 * b * block_size * h * d * itemsize
    if window > _VMEM_BUDGET:
        return (f"double-buffered k+v window {window / 2 ** 20:.1f} MiB "
                f"exceeds the {_VMEM_BUDGET >> 20} MiB staging budget")
    return None


def paged_decode_supported(b: int, block_size: int, h: int, d: int,
                           dtype, s: int = 1) -> bool:
    return paged_decode_refusal(b, block_size, h, d, dtype, s) is None


def paged_gather_kv(pool: jnp.ndarray, block_tables: jnp.ndarray,
                    layer=None) -> jnp.ndarray:
    """Reference gather: pool [nb, bs, h*d] through block_tables [b, T]
    -> [b, T*bs, h*d]. Position p of row i reads flat pool index
    ``block_tables[i, p//bs]*bs + p%bs``; table entries past a row's
    reservation point at whatever block they name (zeros-padded tables
    read block 0) — those positions sit past the row's fill and are
    masked by the caller, so garbage is gathered but never attended.
    With ``layer`` the pool is layer-stacked [L, nb, bs, h*d] and the
    gather reads that layer's blocks where they lie (no slice of the
    layer's pool is made)."""
    nb, bs, hd = pool.shape[-3:]
    b, T = block_tables.shape
    p = jnp.arange(T * bs)
    blk = jnp.take(block_tables, p // bs, axis=1)            # [b, S]
    # a sentinel-padded entry is clipped INSIDE the layer, as the unstacked
    # gather clips it, so stacked and unstacked read the same rows
    flat = jnp.minimum(blk * bs + (p % bs)[None, :], nb * bs - 1)
    if layer is not None:
        flat = layer * (nb * bs) + flat
    return jnp.take(pool.reshape(-1, hd), flat, axis=0, mode="clip")


def paged_decode_attention(q: jnp.ndarray, k_pool: jnp.ndarray,
                           v_pool: jnp.ndarray, block_tables: jnp.ndarray,
                           cache_len, scale: Optional[float] = None,
                           impl: str = "xla",
                           k_scale: Optional[jnp.ndarray] = None,
                           v_scale: Optional[jnp.ndarray] = None,
                           layer=None) -> jnp.ndarray:
    """Decode attention over a PAGED cache. q: [b, s_q, h, d] (s_q > 1 is
    the speculative-verify shape); k_pool/v_pool: [nb, bs, h*d] block
    pools; block_tables: [b, T]; cache_len: valid positions per row
    (including this call's tokens, already written) — scalar or [b],
    sentinel entries past T*bs are clamped. ``k_scale``/``v_scale``
    [nb, bs] f32 mark int8 pools (per-position dequant multipliers).
    ``layer``: the pools (and scales) are layer-stacked [L, nb, bs, ...]
    and this call attends over that layer's blocks — the reference path
    gathers them out of the stack directly, the kernel takes its layer's
    slice.

    ``impl="xla"`` (the reference) gathers the pool through the table
    and calls the SAME masked einsum as the dense decode path — gathered
    values are bit-identical to the dense arena's rows, masked positions
    underflow to exact zeros, so greedy outputs are bit-identical to the
    dense oracle (the tier-1 parity gate). ``impl="pallas"`` DMAs
    per-(row, block) through the table — compute and HBM traffic stay
    O(cache_len) per token — and raises ``KernelUnsupported`` at a shape
    :func:`paged_decode_refusal` refuses."""
    b, s_q, h, d = q.shape
    if layer is not None and impl == "pallas":
        k_pool, v_pool, k_scale, v_scale = (
            None if a is None else
            jax.lax.dynamic_index_in_dim(a, layer, 0, keepdims=False)
            for a in (k_pool, v_pool, k_scale, v_scale))
        layer = None
    nb, bs, hd = k_pool.shape[-3:]
    T = block_tables.shape[1]
    S = T * bs
    clen = jnp.minimum(
        jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32), (b,)), S)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    quantized = k_scale is not None
    if impl == "pallas":
        reason = paged_decode_refusal(b, bs, h, d, k_pool.dtype, s_q)
        if reason is not None:
            refuse("paged_decode_attention",
                   f"q={q.shape} pool={k_pool.shape}", reason)
        hp = -(-h // 8) * 8
        qmat = _spec_qmat(q, hp)                        # [b, hd, s*hp]
        nb_live = jnp.clip((jnp.max(clen) + bs - 1) // bs, 1, T)
        meta = jnp.concatenate([nb_live[None], clen])
        kernel = functools.partial(
            _paged_decode_kernel, scale=scale, b=b, hp=hp, hd=hd,
            bs=bs, nb_total=nb, quantized=quantized, s=s_q)
        in_specs = [
            pl.BlockSpec((b, hd, s_q * hp), lambda g, meta, bt: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.HBM),
            pl.BlockSpec(memory_space=pltpu.HBM),
        ]
        scratch = [
            pltpu.VMEM((2, b, bs, hd), k_pool.dtype),
            pltpu.VMEM((2, b, bs, hd), v_pool.dtype),
        ]
        sems = [pltpu.SemaphoreType.DMA((2, b)),
                pltpu.SemaphoreType.DMA((2, b))]
        operands = [meta, block_tables.astype(jnp.int32), qmat,
                    k_pool, v_pool]
        if quantized:
            # The scales are gathered through the table HERE, by XLA, and
            # ride into VMEM whole as [b, T, bs] blocks. A per-block DMA
            # out of the [nb, bs] scale pool is refused by Mosaic on a v5e
            # — a block's bs=32 f32 scales are a quarter of a 128-lane
            # tile: "Slice shape along dimension 1 must be aligned to
            # tiling (128), but is 32" (PR 21), the same with the pool
            # reshaped [nb, 1, bs] — and they are 4 bytes a position next
            # to h*d payload bytes, so the O(S) gather is noise.
            def gathered(scale):
                return paged_gather_kv(
                    scale.astype(jnp.float32)[..., None],
                    block_tables).reshape(b, T, bs)
            spec = pl.BlockSpec((b, T, bs), lambda g, meta, bt: (0, 0, 0))
            in_specs += [spec, spec]
            operands += [gathered(k_scale), gathered(v_scale)]
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # meta + block tables
            grid=(1,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((b, s_q * hp, hd),
                                   lambda g, meta, bt: (0, 0, 0)),
            scratch_shapes=scratch + sems,
        )
        out = pl.pallas_call(
            kernel, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, s_q * hp, hd), q.dtype),
            name="paged_decode_attention",
            interpret=interpret_mode(),
        )(*operands)
        return _slice_block_diagonal(out, s_q, h, d)
    kflat = paged_gather_kv(k_pool, block_tables, layer)
    vflat = paged_gather_kv(v_pool, block_tables, layer)
    if quantized:
        from ..quantizer import dequantize_kv
        ks = paged_gather_kv(k_scale[..., None].astype(jnp.float32),
                             block_tables, layer)
        vs = paged_gather_kv(v_scale[..., None].astype(jnp.float32),
                             block_tables, layer)
        kflat = dequantize_kv(kflat, ks, q.dtype)
        vflat = dequantize_kv(vflat, vs, q.dtype)
    kf = kflat.reshape(b, S, h, d)
    vf = vflat.reshape(b, S, h, d)
    return masked_cache_attention(q, kf, vf, clen - s_q, scale)


def masked_cache_attention(q, ck, cv, first_q_pos, scale, window=None):
    """The ONE masked-einsum cache attention (the kernels' reference and the
    model's xla/prefill/window paths, so the two can't drift):
    q [b, s, h, d] with query i at absolute position ``first_q_pos + i``,
    ck/cv [b, S, h, d]; each query sees keys at positions <= its own
    (within the trailing local ``window`` if given). ``first_q_pos``:
    scalar, or a [b] vector when each row decodes at its own fill (slotted
    serving)."""
    S = ck.shape[1]
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, ck).astype(jnp.float32) * scale
    key_pos = jnp.arange(S)[None, None, None, :]
    fq = jnp.asarray(first_q_pos)
    if fq.ndim == 1:                               # per-row fills: [b,1,s,1]
        q_pos = (fq[:, None] + jnp.arange(q.shape[1]))[:, None, :, None]
    else:
        q_pos = (fq + jnp.arange(q.shape[1]))[None, None, :, None]
    visible = key_pos <= q_pos
    if window is not None:
        visible = jnp.logical_and(visible, key_pos > q_pos - window)
    logits = jnp.where(visible, logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, cv)


# --------------------------------------------------------------------------
# Absorbed decode over each lane's LIVE rows of the layer-stacked latent leaf
# (appended below every older call site: a compiled kernel body carries its
# callers' line numbers, and the cells that run them keep their programs)
# --------------------------------------------------------------------------

def _live_latent_kernel(layer_ref, fill_ref, q_hbm, leaf_hbm, o_hbm, kv_buf,
                        q_buf, o_buf, zero_buf, m_ref, l_ref, acc_ref, kv_sem,
                        q_sem, o_sem, zero_sem, lane_of, blk_of, *, scale,
                        block_k, b, S, vw):
    """One program for all lanes over the ONE latent leaf [L, b, S, row],
    whole in HBM, whose rows are key and value at once and shared by every
    head. The schedule is :func:`_live_kernel`'s: the scalar core writes one
    (lane, block) entry a LIVE block into SMEM, lane after lane (a fill of f
    has ceil(f / block_k); a masked lane, its fill past S, has none and is
    sent zeros), and ONE double-buffered loop walks it, the next block's DMA
    in flight under this block's dots. The queries [b, h, row] and the
    results [b, h, vw] stay in HBM too (64 lanes of 128 heads are 10.5 and
    8.4 MB): a lane's [h, row] queries arrive in one of two slots, asked for
    at the first block of the lane BEFORE it, and its result leaves from one
    of two slots while the next lane computes.

    Per block the ONE buffer [block_k, row] is both operands: scores
    ``q [h, row] x block^T`` on the leaf's dtype with float32 accumulation,
    masked by ``k < fill`` alone, and ``p x block[:, :vw]`` (the value is the
    row's first columns, a lane-aligned slice of the buffer). Online-softmax
    state lies in VMEM (the [h, vw] float32 accumulator is the vector
    registers' whole file), reset at a lane's first block."""
    layer = layer_ref[0]

    def n_blocks(lane):
        f = fill_ref[lane]
        return jnp.where(f > S, 0, (f + block_k - 1) // block_k)

    def kv_copy(i, slot):
        at = (layer, lane_of[i], pl.ds(blk_of[i] * block_k, block_k))
        return pltpu.make_async_copy(leaf_hbm.at[at], kv_buf.at[slot],
                                     kv_sem.at[slot])

    def q_copy(lane, slot):
        return pltpu.make_async_copy(q_hbm.at[lane], q_buf.at[slot],
                                     q_sem.at[slot])

    def o_copy(lane, slot):
        return pltpu.make_async_copy(o_buf.at[slot], o_hbm.at[lane],
                                     o_sem.at[slot])

    def zero_copy(lane):
        return pltpu.make_async_copy(zero_buf, o_hbm.at[lane], zero_sem)

    zero_buf[...] = jnp.zeros_like(zero_buf)

    def lane_blocks(lane, carry):
        n, dead = carry
        nb = n_blocks(lane)

        def put(j, n):
            # stores to the kernel's SMEM scratch refs, not host state
            lane_of[n] = lane   # tracelint: disable=mutation-in-trace
            blk_of[n] = j       # tracelint: disable=mutation-in-trace
            return n + 1

        @pl.when(nb == 0)
        def _no_rows():
            zero_copy(lane).start()
        return (jax.lax.fori_loop(0, nb, put, n),
                dead + (nb == 0).astype(jnp.int32))

    total, dead = jax.lax.fori_loop(0, b, lane_blocks,
                                    (jnp.int32(0), jnp.int32(0)))
    lane_of[total] = b      # no lane's: the last entry ends its lane

    @pl.when(total > 0)
    def _prologue():
        kv_copy(0, 0).start()
        q_copy(lane_of[0], 0).start()

    col = jax.lax.broadcasted_iota(jnp.int32, (q_buf.shape[1], block_k), 1)

    def body(i, begun):
        """``begun``: the live lanes whose first block lies before entry i;
        the lane of entry i uses query and result slot (begun - 1) % 2."""
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < total)
        def _prefetch():
            kv_copy(i + 1, 1 - slot).start()

        lane, blk = lane_of[i], blk_of[i]
        first = blk == 0
        begun = begun + first.astype(jnp.int32)
        mine = jax.lax.rem(begun - 1, 2)

        @pl.when(first)
        def _lane_begins():
            q_copy(lane, mine).wait()
            after = lane_of[i + n_blocks(lane)]

            @pl.when(after < b)
            def _next_lanes_queries():
                q_copy(after, 1 - mine).start()
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        kv_copy(i, slot).wait()
        sc = jax.lax.dot_general(
            q_buf[mine], kv_buf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale        # [h, block_k]
        sc = jnp.where(col < fill_ref[lane] - blk * block_k, sc, NEG_INF)
        # a scheduled block holds a live key, so every head's max is finite
        # and a masked column's exp is an exact zero
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(sc, axis=1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m_prev - m_new)
        pv = jax.lax.dot(p.astype(kv_buf.dtype), kv_buf[slot, :, :vw],
                         preferred_element_type=jnp.float32)    # [h, vw]
        # stores to the kernel's VMEM scratch refs, not host state
        l_ref[...] = (      # tracelint: disable=mutation-in-trace
            l_ref[...] * corr + jnp.sum(p, axis=1, keepdims=True))
        acc_ref[...] = (    # tracelint: disable=mutation-in-trace
            acc_ref[...] * corr + pv)
        m_ref[...] = m_new  # tracelint: disable=mutation-in-trace

        @pl.when(lane_of[i + 1] != lane)
        def _lane_done():
            @pl.when(begun > 2)
            def _slots_earlier_result_has_left():
                o_copy(lane, mine).wait()
            o_buf[mine] = (acc_ref[...] / l_ref[...]).astype(o_buf.dtype)
            o_copy(lane, mine).start()
        return begun

    begun = jax.lax.fori_loop(0, total, body, jnp.int32(0))
    for last in (1, 2):         # the results still on their way out
        @pl.when(begun >= last)
        def _drain(last=last):
            o_copy(0, jax.lax.rem(begun - last, 2)).wait()

    def zeros_landed(_, c):
        zero_copy(0).wait()
        return c
    jax.lax.fori_loop(0, dead, zeros_landed, 0)


# Rows a DMA of the latent live-rows read carries: one [rows, 640] buffer
# that is key and value, 655 KB in bf16. A block costs ~0.67 us whatever its
# rows (the [128, 512] float32 accumulator rescaled, the copy issued and
# waited for, the loop's branches) and ~0.22 us more every 128 rows, so
# larger blocks win until rounding a fill up outweighs them: five layers at
# serve-reason's shapes and fills (64 lanes x 4096, 1,187 live rows a lane,
# two lanes masked) took 2.70 ms at 128 rows, 1.68 at 256 and 1.37 at 512,
# where the einsum over the whole leaf took 7.66; with every row live 8.63,
# 4.96 and 3.60 (466 GB/s); under 300 rows a lane 0.67, 0.52 and 0.62 (my
# chip run, PR 33). The reverse of _LIVE_BLOCK's finding: that kernel's
# block is bound by its DMA, this one's by what surrounds its dots.
_LIVE_LATENT_BLOCK = 512


def live_latent_block(S: int) -> int:
    """Rows a DMA of the latent live-rows read carries over S positions."""
    return min(_LIVE_LATENT_BLOCK, S)


def live_latent_refusal(b: int, S: int, h: int, row: int, dtype, s: int = 1,
                        block_k: Optional[int] = None) -> Optional[str]:
    """Why :func:`live_latent_attention` cannot run this shape; None when it
    can: one query a lane, a plain floating leaf whose rows are whole
    128-lane tiles, heads in whole sublane tiles, S in whole blocks. (The
    mesh is the caller's to ask: ``gpt._decode_mesh_refusal``.)"""
    if s != 1:
        return ("more than one query a lane: the latent live-rows read "
                "takes the decode width alone (speculative and fused-prefill "
                "widths take the absorbed einsum over the whole leaf)")
    dt = jnp.dtype(dtype)
    if not jnp.issubdtype(dt, jnp.floating):
        return (f"cache dtype {dt.name}: the latent live-rows read has no "
                f"dequant in its window")
    if row % 128 != 0:
        return (f"a latent row of {row} values is not whole 128-lane tiles "
                f"(models/mla.py pads 576 to 640)")
    sublane = 32 // dt.itemsize
    if h % sublane != 0:
        return (f"h={h} heads are not whole {sublane}-row sublane tiles of "
                f"{dt.name}")
    bk = block_k or live_latent_block(S)
    if S % bk != 0 or bk % sublane != 0:
        return (f"cache length {S} is not a multiple of the {bk}-row block "
                f"(itself whole {sublane}-row tiles)")
    return None


def live_latent_attention(q_row: jnp.ndarray, latent: jnp.ndarray, fills,
                          layer, scale: float, v_width: int,
                          block_k: Optional[int] = None) -> jnp.ndarray:
    """Absorbed attention of one query a lane over the LIVE rows of a latent
    cache (models/mla.py): ``softmax(q_row . row * scale) row[:v_width]``.

    ``q_row`` [b, h, row]: a lane's queries in the cache row's own space
    (``q_nope W_uk^T | rotated q_rope | zeros``). ``latent``: the leaf as the
    layer loop carries it, [L, b, S, row], with ``layer`` a (traced) index;
    every row is key and value of ALL h heads. ``fills``: valid rows a lane
    (this token's among them), scalar or [b]; a lane whose fill is past S
    is MASKED (the serving engine's retired-lane sentinel writes at
    ``max_seq_len``): nothing of it is read and its output is zeros the
    caller discards. Reads ceil(fill / block) blocks of each lane's rows and
    nothing else of the leaf; no slice or reshape of the leaf is made on the
    way in. Operands in the leaf's dtype, every accumulation float32, the
    result rounded once. Returns [b, h, v_width] (the kernel computes whole
    128-lane tiles of columns; a narrower value is cut out of them here)."""
    b, h, row = q_row.shape
    S = latent.shape[2]
    bk = block_k or live_latent_block(S)
    reason = live_latent_refusal(b, S, h, row, latent.dtype, 1, bk)
    if reason is None and latent.shape[3] != row:
        reason = f"queries of {row} values against rows of {latent.shape[3]}"
    if reason is not None:
        refuse("live_latent_attention",
               f"q_row={q_row.shape} latent={latent.shape}", reason)
    vw = -(-v_width // 128) * 128
    dtype = latent.dtype
    kernel = functools.partial(_live_latent_kernel, scale=scale, block_k=bk,
                               b=b, S=S, vw=vw)
    n_max = b * (S // bk) + 1       # every block of every lane, and the end
    in_hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                # layer index, per-lane fills
        grid=(1,),
        in_specs=[in_hbm, in_hbm],
        out_specs=in_hbm,
        scratch_shapes=[
            pltpu.VMEM((2, bk, row), dtype),              # kv_buf
            pltpu.VMEM((2, h, row), dtype),               # q_buf
            pltpu.VMEM((2, h, vw), dtype),                # o_buf
            pltpu.VMEM((h, vw), dtype),                   # zero_buf
            pltpu.VMEM((h, 1), jnp.float32),              # m
            pltpu.VMEM((h, 1), jnp.float32),              # l
            pltpu.VMEM((h, vw), jnp.float32),             # acc
            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)), pltpu.SemaphoreType.DMA(()),
            pltpu.SMEM((n_max,), jnp.int32), pltpu.SMEM((n_max,), jnp.int32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, h, vw), dtype),
        name="mla_decode_attention_live",
        interpret=interpret_mode(),
    )(jnp.asarray(layer, jnp.int32).reshape(1),
      jnp.broadcast_to(jnp.asarray(fills, jnp.int32), (b,)),
      q_row.astype(dtype), latent)
    return out[..., :v_width]
