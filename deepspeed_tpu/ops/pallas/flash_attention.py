"""Flash attention, Pallas/TPU.

This is the TPU-native replacement for the reference's fused attention
kernels — the training-side softmax/attention CUDA kernels
(``csrc/transformer/softmax_kernels.cu``, ``ds_transformer_cuda.cpp``) and
the inference ``softmax_context`` kernel family
(``csrc/transformer/inference/csrc/softmax.cu``). Instead of materializing
the [S, S] score matrix in HBM, K/V stream through VMEM one [block_k, D]
tile at a time with an online-softmax accumulator (Flash Attention,
arXiv:2205.14135), so HBM traffic is O(S·D) and VMEM residency is
O(block²) regardless of sequence length — the k loop is the innermost
*grid* dimension with accumulators in VMEM scratch, so long sequences never
blow the ~16 MB VMEM budget.

Layout: q, k, v are [B, S, H, D] (model layout); kernels run per (batch,
head). The backward pass recomputes attention per tile from the saved
per-row logsumexp — the rematerialization trade the reference makes with
activation checkpointing, here at kernel granularity.

On the CPU backend the kernels run in Pallas interpret mode, which is how
the CPU test mesh exercises them (tests/test_pallas_ops.py).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import interpret_mode, refuse

NEG_INF = -1e30


def _causal_mask(s, qi, ki, block_q, block_k):
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    return jnp.where(rows >= cols, s, NEG_INF)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, scale, block_q, block_k, causal):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip fully-masked tiles (strictly above the diagonal)
    live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        kb = k_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)
        acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot(
            p, vb, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        l_safe = jnp.where(l == 0, 1.0, l)
        o_ref[0, 0] = (acc_scr[...] / l_safe[:, None]).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_scr[...] + jnp.log(l_safe))[:, None]


def _flash_fwd(q, k, v, causal, scale, block_q, block_k):
    b, s, h, d = q.shape
    dv = v.shape[-1]    # may differ from q's and k's (latent attention)
    # kernel layout [B, H, S, D]
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    nq, nk = s // block_q, s // block_k

    kernel = functools.partial(_fwd_kernel, scale=scale, block_q=block_q,
                               block_k=block_k, causal=causal)
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, dv),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_q, dv),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            # stats carry a trailing singleton lane dim: TPU lowering needs
            # the last two block dims divisible by (8, 128) or equal to the
            # array dims — (block_q, 1) qualifies, (1, block_q) does not
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, dv), q.dtype),
            jax.ShapeDtypeStruct((b, h, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, dv), jnp.float32),
        ],
        name="flash_attention_fwd",
        interpret=interpret_mode(),
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3), (qt, kt, vt, out, lse)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------

def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, scale, block_q, block_k, causal):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    live = (not causal) or (ki * block_k <= qi * block_q + block_q - 1)

    @pl.when(live)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)
        do = do_ref[0, 0].astype(jnp.float32)
        lse = lse_ref[0, 0, :, 0]
        delta = delta_ref[0, 0, :, 0]
        kb = k_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(do, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[...] = dq_scr[...] + jax.lax.dot(
            ds, kb, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scr[...].astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, scale, block_q,
                    block_k, causal):
    ki, qi = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    live = (not causal) or (qi * block_q + block_q - 1 >= ki * block_k)

    @pl.when(live)
    def _compute():
        kb = k_ref[0, 0].astype(jnp.float32)
        vb = v_ref[0, 0].astype(jnp.float32)
        qb = q_ref[0, 0].astype(jnp.float32)
        dob = do_ref[0, 0].astype(jnp.float32)
        lseb = lse_ref[0, 0, :, 0]
        deltab = delta_ref[0, 0, :, 0]
        s = jax.lax.dot_general(qb, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s, qi, ki, block_q, block_k)
        p = jnp.exp(s - lseb[:, None])                     # [bq, bk]
        dv_scr[...] = dv_scr[...] + jax.lax.dot_general(
            p, dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(dob, vb, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - deltab[:, None]) * scale
        dk_scr[...] = dk_scr[...] + jax.lax.dot_general(
            ds, qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scr[...].astype(dv_ref.dtype)


def _flash_bwd(causal, scale, block_q, block_k, res, g):
    qt, kt, vt, out, lse = res
    b, h, s, d = qt.shape
    if vt.shape[-1] != d:
        refuse("flash_attention backward", qt.shape,
               f"v's head size {vt.shape[-1]} differs from q's {d}: only "
               f"the forward kernel takes that")
    dot = g.transpose(0, 2, 1, 3)                          # [B,H,S,D]
    delta = jnp.sum(dot.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1, keepdims=True)                # [B,H,S,1]
    nq, nk = s // block_q, s // block_k

    dq_kernel = functools.partial(_bwd_dq_kernel, scale=scale,
                                  block_q=block_q, block_k=block_k,
                                  causal=causal)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, qi, ki: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), qt.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        name="flash_attention_bwd_dq",
        interpret=interpret_mode(),
    )(qt, kt, vt, dot, lse, delta)

    dkv_kernel = functools.partial(_bwd_dkv_kernel, scale=scale,
                                   block_q=block_q, block_k=block_k,
                                   causal=causal)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(b, h, nk, nq),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_q, d),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, block_q, 1),
                         lambda bi, hi, ki, qi: (bi, hi, qi, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
            pl.BlockSpec((1, 1, block_k, d),
                         lambda bi, hi, ki, qi: (bi, hi, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, h, s, d), kt.dtype),
            jax.ShapeDtypeStruct((b, h, s, d), vt.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        name="flash_attention_bwd_dkv",
        interpret=interpret_mode(),
    )(qt, kt, vt, dot, lse, delta)

    tr = lambda x: x.transpose(0, 2, 1, 3)
    return tr(dq), tr(dk), tr(dv)


# ---------------------------------------------------------------------------
# Public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, causal, scale, block_q, block_k):
    out, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k)
    return out


def _flash_attention_fwd(q, k, v, causal, scale, block_q, block_k):
    return _flash_fwd(q, k, v, causal, scale, block_q, block_k)


_flash_attention.defvjp(_flash_attention_fwd, _flash_bwd)


def reference_attention(q, k, v, causal, scale):
    """The XLA einsum the kernel is checked against (tests, chip_smoke)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s = q.shape[1]
        mask = jnp.tril(jnp.ones((s, s), dtype=bool))
        logits = jnp.where(mask[None, None], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _pick_block(s: int, prefer: int) -> Optional[int]:
    """Largest power-of-two tile <= prefer that divides s (or s itself when
    the whole sequence fits in one tile)."""
    if s <= prefer:
        return s
    for b in (prefer, 512, 256, 128):
        if s % b == 0:
            return b
    return None


def flash_refusal(s: int, block_q: int = 1024,
                  block_k: int = 1024) -> Optional[str]:
    """Why the kernel cannot run a sequence of length ``s`` (None when it
    can): the sequence must fit one tile or split into tiles of >= 128."""
    if _pick_block(s, block_q) is None or _pick_block(s, block_k) is None:
        return (f"sequence length {s} exceeds one tile and no tile in "
                f"(1024, 512, 256, 128) divides it")
    return None


def flash_attention(q, k, v, causal: bool = True,
                    sm_scale: Optional[float] = None,
                    block_q: int = 1024, block_k: int = 1024):
    """Fused attention. q, k, v: [B, S, H, D] -> [B, S, H, D]. The forward
    also takes a ``v`` of another head size ``[B, S, H, Dv]`` (latent
    attention: q.k over 192, v 128) and returns ``[B, S, H, Dv]``; the
    backward kernels do not, and a gradient through such a call refuses.

    Default 1024-wide tiles: at GPT-2 125M shapes (b 8, s 1024, 12 heads of
    64, bf16) every tile from 128 to 1024 compiles on a v5e and the 1024
    tile read fastest (chip_smoke kernel leg, PR 21 — a smoke reading, not
    a benchmark; see PERF.md). Sequences that don't tile at the preferred
    size degrade to the largest power-of-two tile that divides S; a shape
    no tile >= 128 divides raises :class:`KernelUnsupported` — callers
    that may take the einsum instead ask :func:`flash_refusal` first.
    """
    b, s, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    reason = flash_refusal(s, block_q, block_k)
    if reason is not None:
        refuse("flash_attention", q.shape, reason)
    return _flash_attention(q, k, v, causal, scale,
                            _pick_block(s, block_q), _pick_block(s, block_k))


# ---------------------------------------------------------------------------
# Forward over grouped heads and a band (appended below everything the other
# programs call: a Mosaic kernel's body carries the line numbers of its call
# sites, so a line moved above them compiles every such program cold)
# ---------------------------------------------------------------------------

def _band_blocks(win_ref, qi, block_q, block_k):
    """First and last key block a query block meets: keys ``j`` of query
    ``i`` with ``0 <= i - j < window``."""
    lo = jnp.maximum(qi * block_q - (win_ref[0] - 1), 0) // block_k
    return lo, (qi * block_q + block_q - 1) // block_k


def _fwd_band_kernel(win_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                     acc_scr, *, scale, block_q, block_k):
    qi, ki = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)
    window = win_ref[0]

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # skip the tiles above the diagonal and those wholly behind the band
    lo, hi = _band_blocks(win_ref, qi, block_q, block_k)

    @pl.when(jnp.logical_and(ki >= lo, ki <= hi))
    def _compute():
        q, kb, vb = q_ref[0], k_ref[0], v_ref[0]
        s = jax.lax.dot_general(q, kb, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0)
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        s = jnp.where(jnp.logical_and(rows >= cols, rows - cols < window),
                      s, NEG_INF)
        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        # a row whose keys in this tile are all outside the band keeps
        # m = NEG_INF: exp(NEG_INF - NEG_INF) would count them
        p = jnp.where(s > NEG_INF, jnp.exp(s - m_new), 0.0)
        corr = jnp.exp(m_prev - m_new)
        m_scr[...] = m_new
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot(
            p.astype(vb.dtype), vb, preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0, 1.0, l)
                    ).astype(o_ref.dtype)


def flash_band_refusal(s: int, d: int, h: int, kv_heads: int,
                       block_q: int = 512,
                       block_k: int = 512) -> Optional[str]:
    """Why :func:`flash_attention_band` cannot run this shape; None when it
    can. It reads the heads as 128-lane column blocks of flat ``[B, S, H*D]``
    rows (no transposed copy of q, k, v or the output), so a head is whole
    128-lane rows."""
    if d % 128 != 0:
        return (f"head size d={d} is not whole 128-lane rows: a head is no "
                f"column block of the flat [B, S, H*D] rows")
    if h % kv_heads != 0:
        return f"{h} query heads do not divide over {kv_heads} key heads"
    return flash_refusal(s, block_q, block_k)


def flash_attention_band(q, k, v, window, sm_scale: Optional[float] = None,
                         block_q: int = 512, block_k: int = 512):
    """Causal attention, forward only, over grouped heads and a band.
    ``q [B, S, H, D]``; ``k, v [B, S, Hk, D]`` with ``H`` a multiple of
    ``Hk``: query head ``n`` reads key head ``n // (H / Hk)`` where it lies
    (the key block's index, not a repeated copy). ``window``: an int32
    scalar, traced or not; query ``i`` sees keys ``j`` with ``0 <= i - j <
    window`` (``window >= S`` is plain causal attention, so ONE program
    serves a stack whose layers differ in it). Key tiles wholly outside the
    band are neither computed nor fetched (their block index is clamped
    into the band, and a repeated index is no new DMA), as the tiles above
    the diagonal. Operands meet the MXU in their own dtype with float32
    accumulation; the probabilities are rounded to ``v``'s dtype before
    they meet the values. Returns ``[B, S, H, D]``."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    reason = flash_band_refusal(s, d, h, hk, block_q, block_k)
    if reason is not None:
        refuse("flash_attention_band", q.shape, reason)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    bq, bk = _pick_block(s, block_q), _pick_block(s, block_k)
    group = h // hk

    def q_at(bi, hi, qi, ki, win):
        return bi, qi, hi

    def kv_at(bi, hi, qi, ki, win):
        lo, hi_blk = _band_blocks(win, qi, bq, bk)
        return bi, jnp.clip(ki, lo, hi_blk), hi // group

    kernel = functools.partial(_fwd_band_kernel, scale=scale, block_q=bq,
                               block_k=bk)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, s // bq, s // bk),
        in_specs=[pl.BlockSpec((1, bq, d), q_at),
                  pl.BlockSpec((1, bk, d), kv_at),
                  pl.BlockSpec((1, bk, d), kv_at)],
        out_specs=pl.BlockSpec((1, bq, d), q_at),
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, d), jnp.float32)],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), q.dtype),
        name="flash_attention_fwd_band",
        interpret=interpret_mode(),
    )(jnp.asarray(window, jnp.int32).reshape(1), q.reshape(b, s, h * d),
      k.reshape(b, s, hk * d), v.reshape(b, s, hk * d))
    return out.reshape(b, s, h, d)
