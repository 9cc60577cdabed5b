"""Flagship GPT model family (GPT-2 / GPT-NeoX style), TPU-first.

This is the model zoo counterpart of the reference's test/model fixtures
(tests/unit/simple_model.py, Megatron GPT-2 in tests/model/) and the target of
the engine milestones (BASELINE.json configs: GPT-2 125M -> GPT-NeoX 20B ->
175B). Design notes:

  * Plain flax.linen with einsum attention; the hot ops (attention, layernorm)
    route through ``deepspeed_tpu.ops`` so Pallas kernels can slot in.
  * ``scan_layers=True`` stacks the transformer blocks into one scanned
    layer with stacked params [L, ...]. Under ZeRO-3 ``dp`` shards a dim
    INSIDE the layer (never L: runtime/sharding.py) and the scan body
    gathers its own layer's slice where it reads it (``_layer``), so one
    layer is live at a time; remat per scan step is the checkpointing analogue
    (reference runtime/activation_checkpointing/checkpointing.py:493).
    The KV ``cache`` collection is stacked the same way ([L, B, S, h, d]
    leaves). A call that CREATES the cache (prefill) scans over it like the
    params; a call that is HANDED one (every decode step) carries the
    stacked leaves through the loop instead and each layer writes its
    tokens at its own index in place — scanning over a cache that came in
    costs three passes over the whole arena a step (``GPT.__call__``).
  * Tensor parallelism comes from sharding rules on param paths (see
    runtime/sharding.py), not from model surgery: q/k/v and up-projection
    kernels shard their output dim over ``tp``; out/down projections shard
    their input dim; XLA inserts the psum (the reference does this manually
    with ``LinearAllreduce``, module_inject/replace_module.py:13).
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..utils.logging import logger


_sp_drop_warned = set()


def _layer_rows(stacked, layer):
    """One layer's view of a cache leaf. ``layer`` None: the leaf IS the
    layer's (the cache is being created by this call, or the model does not
    scan its layers). Otherwise the leaf is the layer-stacked ``[L, ...]``
    array that the layer loop carries, and the layer's rows are read from it
    where they lie."""
    if layer is None:
        return stacked
    return jax.lax.dynamic_index_in_dim(stacked, layer, 0, keepdims=False)


def _set_layer_rows(stacked, layer, rows):
    """Counterpart of :func:`_layer_rows` for the small per-layer leaves
    (``cache_index``): the new value of the leaf with this layer's rows
    replaced."""
    if layer is None:
        return rows
    return jax.lax.dynamic_update_index_in_dim(stacked, rows, layer, 0)


def _kv_write(cache, kv, cur, layer=None):
    """Write this step's k/v ``[b, s, ...]`` into the cache at sequence
    offset ``cur``. ``layer`` None: ``cache`` is one layer's ``[b, S, ...]``.
    ``layer`` a (traced) index: ``cache`` is the layer-stacked
    ``[L, b, S, ...]`` leaf carried by the layer loop and the write lands at
    ``(layer, row, pos)`` IN PLACE — the update touches ``b*s`` positions,
    never a layer's worth of rows, so a donated arena stays where it lies.

    ``cur`` scalar: the whole batch sits at one fill (single-stream
    generate) — one dynamic_update_slice. ``cur`` [b]: every row has its
    own fill (slotted continuous-batching decode, serving/engine.py) — a
    per-position scatter. A per-row offset >= the cache extent is the
    MASKED-LANE sentinel: that row's write is dropped entirely, in every
    layer (the fused multi-step serving decode pins retired lanes at
    ``max_seq_len`` so a dead lane never dirties KV rows a later occupant
    of the slot could attend before overwriting them)."""
    lead = () if layer is None else (layer,)
    if jnp.ndim(cur) == 0:
        if layer is not None:
            kv = kv[None]
        start = lead + (0, cur) + (0,) * (cache.ndim - len(lead) - 2)
        return jax.lax.dynamic_update_slice(cache, kv, start)
    # per-position scatter, NOT dynamic_update_slice: dus CLAMPS its start
    # index, so a multi-token write near the row end (or at the sentinel)
    # would silently land on the last s positions instead of dropping —
    # mode="drop" discards exactly the out-of-range positions and is
    # bit-identical to dus for in-range writes
    b, s = kv.shape[:2]
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    pos = cur[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
    return cache.at[lead + (rows, pos)].set(kv, mode="drop")


def _kv_write_paged(pool, kv, block_tables, cur, layer=None):
    """Paged counterpart of :func:`_kv_write`: scatter ``s`` tokens' k/v
    through each row's block table. ``pool`` [nb, bs, h*d] is the shared
    block pool — or, with ``layer`` given, the layer-stacked
    [L, nb, bs, h*d] pool the layer loop carries, written in place at that
    layer's blocks. ``kv`` [b, s, h*d] this step's flattened k or v,
    ``block_tables`` [b, T], ``cur`` [b] per-row write positions. The
    masked-lane sentinel (``cur >= T*bs == max_seq_len``) routes to an
    out-of-range flat index and drops — same contract as the dense path.
    Table entries past a row's reservation are padded with the
    ``num_blocks`` sentinel (paged_kv.padded_table), so a speculative
    position beyond the leased blocks also drops instead of dirtying
    block 0 (or, stacked, the next layer's)."""
    nb, bs, hd = pool.shape[-3:]
    b, T = block_tables.shape
    s = kv.shape[1]
    cur = jnp.broadcast_to(jnp.asarray(cur, jnp.int32), (b,))
    pos = cur[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]   # [b, s]
    blk = jnp.take_along_axis(
        block_tables, jnp.clip(pos // bs, 0, T - 1), axis=1)       # [b, s]
    base = 0 if layer is None else layer * (nb * bs)
    flat = jnp.where((pos < T * bs) & (blk < nb),
                     base + blk * bs + pos % bs, pool.size // hd)
    return pool.reshape(-1, hd).at[flat.reshape(-1)].set(
        kv.reshape(b * s, hd), mode="drop").reshape(pool.shape)


def _sp_constraint(x, spec_parts):
    """Ulysses sharding constraint against the global mesh (no-op when the
    mesh's sp axis is 1). Axes the shape doesn't divide are dropped —
    silently for the size-1 sample batch used at init (the sp axis on dim 0),
    with a warning otherwise, because a dropped sp axis means attention
    quietly degrades to seq-sharded GSPMD (no all-to-all — a different
    comm/memory profile than true Ulysses)."""
    from ..parallel import mesh as mesh_lib
    mesh = mesh_lib.get_constraint_mesh()
    shape = dict(mesh.shape)
    if shape.get("sp", 1) == 1:
        return x
    parts = []
    for i, a in enumerate(spec_parts):
        if a is not None and x.shape[i] % shape.get(a, 1) != 0:
            key = (i, a, x.shape[i], shape.get(a, 1))
            if a == "sp" and x.shape[0] > 1 and key not in _sp_drop_warned:
                _sp_drop_warned.add(key)
                logger.warning(
                    f"sequence-parallel sharding dropped: dim {i} of a "
                    f"{x.shape} tensor is not divisible by sp="
                    f"{shape.get(a, 1)} — Ulysses needs num_heads % sp == 0 "
                    f"(and seq % sp == 0); falling back to a replicated "
                    f"axis for this tensor")
            parts.append(None)
        else:
            parts.append(a)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*parts)))


def sp_shard_sequence(x):
    """[B, S, D] activations sequence-sharded over sp."""
    return _sp_constraint(x, ("dp", "sp", None))


def sp_shard_heads(x):
    """[B, S, H, d] attention tensors head-sharded over sp (full sequence
    per chip for its head subset — the all-to-all happens here)."""
    return _sp_constraint(x, ("dp", None, "sp", None))


_pa_drop_warned = set()


def tp_shard_sequence(x):
    """Megatron-style partitioned activations: the residual stream is
    sequence-sharded over ``tp`` (in addition to dp/sp) at block boundaries,
    so remat-saved activations cost 1/tp the HBM per chip and LN/residual
    math runs sequence-parallel — GSPMD turns the out-projection's psum into
    a reduce-scatter and inserts the all-gather before qkv (the declarative
    form of reference activation partitioning,
    runtime/activation_checkpointing/checkpointing.py:493). No-op when the
    mesh has no tp axis (nothing to partition across, as in the reference
    with mp=1)."""
    from ..parallel import mesh as mesh_lib
    mesh = mesh_lib.get_constraint_mesh()
    shape = dict(mesh.shape)
    tp = shape.get("tp", 1)
    if tp <= 1 or x.ndim < 3:
        return x
    sp = shape.get("sp", 1)
    seq_axes = ("sp", "tp") if sp > 1 else ("tp",)
    div = tp * sp
    if x.shape[1] % div != 0:
        key = (x.shape, div)
        if x.shape[1] > 1 and key not in _pa_drop_warned:
            _pa_drop_warned.add(key)
            logger.warning(
                f"partition_activations dropped: seq dim {x.shape[1]} of a "
                f"{x.shape} tensor is not divisible by tp*sp={div}; "
                f"activations stay replicated over tp for this shape")
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, P("dp", seq_axes, None)))


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304          # pad to a multiple of 128 for the MXU
    max_seq_len: int = 1024
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    rotary: bool = False             # False: learned positions (GPT-2)
    rotary_pct: float = 1.0
    rotary_base: float = 10000.0
    block: Any = None   # None: this file's block; else a kind's config (_kind)
    parallel_residual: bool = False  # True for NeoX
    # Decode-time tp collective/MLP overlap (ops/tp_overlap.py): the attention
    # branch's output is pinned hidden-sharded so that GSPMD splits its
    # all-reduce into reduce-scatter + all-gather around the independent
    # parallel-residual MLP gemm. Parallel-residual only; inert without a tp
    # axis. The serving engine's megakernel mode flips it on when tp > 1.
    tp_overlap: bool = False
    tie_embeddings: bool = True
    dtype: Any = jnp.bfloat16        # compute dtype
    param_dtype: Any = jnp.float32
    dropout: float = 0.0
    scan_layers: bool = True
    # layers inlined per scan step: 1 = pure while-loop (smallest program,
    # per-step loop overhead); num_layers = fully inlined (XLA schedules
    # across layer boundaries). Param layout is unchanged either way.
    scan_unroll: int = 1
    remat: bool = True
    # what remat may keep: "nothing" recomputes the whole block (max memory
    # savings, ~+33% compute); "dots_no_batch" keeps non-batch matmul outputs
    # (skips recomputing GEMMs — the XLA analogue of the reference's
    # checkpointing trade, runtime/activation_checkpointing/checkpointing.py)
    remat_policy: str = "dots_no_batch"   # nothing | dots | dots_no_batch
    # Partitioned activations (reference activation_checkpointing config
    # "partition_activations", checkpointing.py:493): shard the residual
    # stream's sequence dim over tp at block boundaries, cutting remat-saved
    # activation HBM per chip by 1/tp. See tp_shard_sequence.
    partition_activations: bool = False
    # CPU checkpointing (reference checkpointing.py:122): remat saves only
    # the per-layer block inputs and offloads them to host memory
    # (pinned_host); everything else recomputes in backward. Activation HBM
    # becomes O(one layer) regardless of depth. Requires remat=True.
    cpu_checkpointing: bool = False
    # "auto" resolves to the Pallas flash kernel on a TPU when its gate
    # accepts the shape and to the XLA einsum on the CPU test mesh, logging
    # the choice once; "pallas" asks for the kernel by name and raises where
    # "auto" would take the einsum. Kernel against einsum as a train step:
    # not measured (PERF.md, PR 21 has the kernel-level smoke readings).
    attention_impl: str = "auto"     # auto | xla | pallas | sparse
    sparse_attention: Any = None     # SparsityConfig when attention_impl=sparse
    # "auto" keeps the cache rank-4 [b, S, h, d] and, on a TPU, reads each
    # lane's LIVE blocks of it where live_read_block's gate accepts the call
    # (one device, plain bf16/f32 rows, one query a lane, d % 128 == 0), the
    # masked einsum over all S rows everywhere else, logging the choice once
    # (serve-batch, PR 29: the step 14.7 -> 10.1 ms). "pallas" asks BY NAME
    # for the older all-lanes kernel (int8 and speculative widths in its DMA
    # window) over a FLAT [b, S, h*d] cache and raises where its gate
    # refuses; "xla" is the einsum alone. The knob is ROADMAP D1's.
    decode_impl: str = "auto"        # auto | xla | pallas
    # KV-cache storage dtype: "auto" stores at the compute dtype; "int8"
    # stores symmetric per-token-group int8 (ops/quantizer.quantize_kv —
    # one scale per position's concatenated heads, kept in f32
    # ``key_scale``/``value_scale`` cache leaves) and dequantizes inside
    # the attention jit, halving KV HBM and bandwidth vs bf16 (KIVI/
    # LLM.int8-style cache compression). Decode-path only: prefill always
    # computes at full precision and quantizes on the cache write.
    kv_cache_dtype: str = "auto"     # auto | int8
    # Ulysses-style sequence parallelism over the mesh's `sp` axis (the
    # long-context strategy beyond the reference's block-sparse attention;
    # DeepSpeed-Ulysses all-to-all design, here expressed as sharding
    # constraints): activations ride sequence-sharded [B, S/sp, D] through
    # embeddings/LN/MLP, and attention constrains q/k/v to HEAD-sharded
    # [B, S, H/sp, d] — GSPMD inserts the two all-to-alls per layer. Each
    # chip's attention sees the FULL sequence for its head subset, so
    # context length scales with the sp degree at O(S/sp) activation
    # memory per chip. Requires num_heads % sp == 0.
    sequence_parallel: bool = False
    # context-parallel attention flavor when sequence_parallel is on:
    # "ulysses" (head-sharded all-to-all) or "ring" (KV shards rotate via
    # ppermute — no head-count constraint; ops/ring_attention.py)
    cp_impl: str = "ulysses"
    layer_norm_eps: float = 1e-5
    # attention-score scale; None -> 1/sqrt(head_dim). GPT-Neo uses 1.0.
    qk_scale: Any = None
    # per-layer local-attention windows (None entry = global); requires
    # scan_layers=False since layers become heterogeneous (GPT-Neo
    # alternates global/local-256)
    attn_windows: Any = None
    # --- MoE (reference: deepspeed/moe/; MoE-NLG model family) ------------
    moe: bool = False
    num_experts: int = 1
    moe_top_k: int = 1
    moe_capacity_factor: float = 1.25
    moe_eval_capacity_factor: float = 2.0
    moe_min_capacity: int = 4
    moe_aux_loss_coef: float = 0.01
    moe_use_residual: bool = False   # PR-MoE residual experts

    def __post_init__(self):
        if self.cpu_checkpointing and not self.remat:
            raise ValueError(
                "cpu_checkpointing offloads remat-saved block inputs to "
                "host memory, so it requires remat=True")
        if self.cp_impl not in ("ulysses", "ring"):
            raise ValueError(
                f"cp_impl must be 'ulysses' or 'ring', got {self.cp_impl!r}")
        if self.attention_impl not in ("auto", "xla", "pallas", "sparse"):
            raise ValueError(f"unknown attention_impl {self.attention_impl!r}")
        if self.decode_impl not in ("auto", "xla", "pallas"):
            raise ValueError(f"unknown decode_impl {self.decode_impl!r}")
        if self.kv_cache_dtype not in ("auto", "int8"):
            raise ValueError(
                f"unknown kv_cache_dtype {self.kv_cache_dtype!r}: "
                f"use 'auto' or 'int8'")
        if self.tp_overlap and not self.parallel_residual:
            raise ValueError(
                "tp_overlap hides the attention all-reduce behind the "
                "parallel-residual MLP gemm; it requires "
                "parallel_residual=True")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


def gpt2_125m(**kw):
    return GPTConfig(num_layers=12, num_heads=12, d_model=768, d_ff=3072, **kw)


def gpt2_1_3b(**kw):
    return GPTConfig(num_layers=24, num_heads=32, d_model=2048, d_ff=8192, **kw)


def gpt_neox_6_7b(**kw):
    return GPTConfig(num_layers=32, num_heads=32, d_model=4096, d_ff=16384,
                     rotary=True, parallel_residual=True, **kw)


def gpt_neox_20b(**kw):
    return GPTConfig(num_layers=44, num_heads=64, d_model=6144, d_ff=24576,
                     rotary=True, parallel_residual=True, tie_embeddings=False, **kw)


def gpt3_175b(**kw):
    return GPTConfig(num_layers=96, num_heads=96, d_model=12288, d_ff=49152, **kw)


def gpt_moe_1_3b(num_experts=128, **kw):
    """1.3B + MoE-128: matches 6.7B dense quality at ~5x lower compute
    (reference docs/_posts/2021-12-09-deepspeed-moe-nlg.md:123-133)."""
    return GPTConfig(num_layers=24, num_heads=16, d_model=2048, d_ff=8192,
                     moe=True, num_experts=num_experts, **kw)


# --------------------------------------------------------------------------
# Building blocks
# --------------------------------------------------------------------------

def rotary_embedding(x, positions, rotary_dim: int, base: float = 10000.0):
    """Apply rotary position embedding to [..., S, H, D] over first rotary_dim."""
    d = rotary_dim
    x_rot, x_pass = x[..., :d], x[..., d:]
    freqs = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [.., S, d/2]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x_rot[..., 0::2], x_rot[..., 1::2]
    r1 = x1 * cos - x2 * sin
    r2 = x2 * cos + x1 * sin
    rot = jnp.stack([r1, r2], axis=-1).reshape(x_rot.shape)
    return jnp.concatenate([rot.astype(x.dtype), x_pass], axis=-1)


# GSPMD cannot partition a Mosaic custom call: a Pallas kernel inside a jit
# over several devices fails at lowering with "Mosaic kernels cannot be
# automatically partitioned. Please wrap the call in a shard_map" (v5e x4,
# PR 21 — the first time any of this met more than one real chip).
_NO_AUTO_PARTITION = ("GSPMD cannot partition a Mosaic custom call and this "
                      "kernel call is not wrapped in shard_map")


def _mesh_refusal(b: int, h: int) -> Optional[str]:
    """Why the flash kernel cannot run [b, S, h, d] over the constraint
    mesh; None when it can. On several devices the kernel runs under
    shard_map — batch over dp, heads over tp — so both must divide."""
    from ..parallel import mesh as mesh_lib
    mesh = mesh_lib.get_constraint_mesh()
    dp, tp = mesh.shape["dp"], mesh.shape["tp"]
    if mesh.size > 1 and (b % dp or h % tp):
        return (f"batch {b} / heads {h} do not divide over the mesh's "
                f"dp={dp} / tp={tp} ({_NO_AUTO_PARTITION} unless they do)")
    return None


def _flash_over_mesh(q, k, v, scale):
    """The flash kernel over the constraint mesh. Attention is independent
    across batch rows and heads, so on several devices each runs the kernel
    on its own [b/dp, S, h/tp, d] shard under shard_map. A shape that does
    not divide (or a one-device mesh) calls the kernel directly — right in
    a single-device jit, and JAX's own error in a multi-device one."""
    from ..ops.pallas.flash_attention import flash_attention
    from ..parallel import mesh as mesh_lib
    fn = partial(flash_attention, causal=True, sm_scale=scale)
    mesh = mesh_lib.get_constraint_mesh()
    if mesh.size == 1 or _mesh_refusal(q.shape[0], q.shape[2]) is not None:
        return fn(q, k, v)
    spec = P("dp", None, "tp", None)
    return jax.shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)


def causal_attention(q, k, v, *, dtype, impl: str = "xla", sparse_config=None,
                     mask: Optional[jnp.ndarray] = None,
                     scale: Optional[float] = None,
                     window: Optional[int] = None):
    """q,k,v: [B, S, H, D]. Routes to the configured attention kernel.
    ``window``: local (sliding-window) attention over the last N keys."""
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if impl in ("auto", "pallas"):
        from ..ops.pallas import _utils as kernels
        from ..ops.pallas.flash_attention import flash_refusal
        refusal = ("the flash kernel has no local-window mask"
                   if window is not None else flash_refusal(q.shape[1]))
        if impl == "pallas" and refusal is not None:
            kernels.refuse("attention_impl='pallas'", q.shape, refusal)
        if impl == "pallas" or kernels.auto_path(
                "attention", refusal or _mesh_refusal(q.shape[0],
                                                      q.shape[2])):
            return _flash_over_mesh(q, k, v, scale)
    if impl == "sparse" and sparse_config is not None:
        from ..ops.sparse_attention.sparse_self_attention import sparse_attention
        # causal=True regardless of the layout's attention mode: a decoder
        # LM must never see the future even through a bidirectional layout
        return sparse_attention(q, k, v, sparse_config, sm_scale=scale,
                                causal=True)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    s = q.shape[1]
    causal = jnp.tril(jnp.ones((s, s), dtype=bool))
    if window is not None:
        causal = jnp.logical_and(causal,
                                 jnp.triu(jnp.ones((s, s), dtype=bool),
                                          k=-(window - 1)))
    logits = jnp.where(causal[None, None], logits, -1e10)
    if mask is not None:
        logits = jnp.where(mask[:, None, None, :], logits, -1e10)
    probs = jax.nn.softmax(logits, axis=-1).astype(dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _decode_mesh_refusal() -> Optional[str]:
    """Compiled by Mosaic, the decode kernels run on a one-device mesh only
    (one serving engine per chip is ROADMAP R9's placement). Interpreted on
    the CPU test mesh there is no custom call for GSPMD to refuse."""
    from ..parallel import mesh as mesh_lib
    from ..utils.platform import on_chip
    n = mesh_lib.get_constraint_mesh().size
    if n > 1 and on_chip():
        return f"mesh of {n} devices: {_NO_AUTO_PARTITION}"
    return None


_NO_WINDOW_KERNEL = "the decode kernels have no local-window path"


def live_read_block(cfg: GPTConfig, b: int, s: int = 1,
                    window: Optional[int] = None) -> Optional[int]:
    """Rows a block of the live-rows decode read carries, where a dense
    decode call of ``b`` lanes and query width ``s`` under ``cfg`` takes
    that read (ops/pallas/decode_attention.live_decode_attention: each
    lane's ``ceil(fill / block)`` blocks of the rank-4 rows); None where it
    reads the layer's whole ``[b, S, h, d]`` rows with the masked einsum.
    ``decode_impl="auto"`` alone chooses, from what a trace can see: the
    platform, the mesh, the cache dtype, the width, a window, the shape.
    The serving engine asks the same question to count what a step read
    (``serve/kv_blocks_read``)."""
    if cfg.decode_impl != "auto":
        return None
    from ..ops.pallas import _utils as kernels
    from ..ops.pallas.decode_attention import (live_block,
                                               live_decode_refusal)
    kv_dt = jnp.int8 if cfg.kv_cache_dtype == "int8" else cfg.dtype
    refusal = (_NO_WINDOW_KERNEL if window is not None else
               live_decode_refusal(b, cfg.max_seq_len, cfg.num_heads,
                                   cfg.head_dim, kv_dt, s)
               or _decode_mesh_refusal())
    if not kernels.auto_path("decode_attention", refusal):
        return None
    return live_block(cfg.max_seq_len)


class SelfAttention(nn.Module):
    cfg: GPTConfig
    window: Optional[int] = None    # local-attention window (GPT-Neo style)

    @nn.compact
    def __call__(self, x, positions, deterministic=True, layer=None):
        """Training/prefill path (full sequence) OR single-token decode when
        a ``cache`` variable collection is mutable (flax autoregressive
        cache idiom — the TPU analogue of the reference inference kernel's
        KV-cache arena, csrc/transformer/inference/includes/context.h).
        ``layer``: this block's index in the layer-stacked cache leaves,
        given when the layer loop carries the cache (GPT.__call__)."""
        cfg = self.cfg
        qkv = nn.Dense(3 * cfg.d_model, use_bias=True, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        b, s, _ = x.shape
        shp = (b, s, cfg.num_heads, cfg.head_dim)
        q, k, v = q.reshape(shp), k.reshape(shp), v.reshape(shp)
        if cfg.rotary:
            rd = int(cfg.rotary_pct * cfg.head_dim)
            q = rotary_embedding(q, positions, rd, cfg.rotary_base)
            k = rotary_embedding(k, positions, rd, cfg.rotary_base)

        decode = self.has_variable("cache", "cached_key") or \
            (not self.is_initializing() and self.is_mutable_collection("cache"))
        if decode:
            out = self._decode_attention(q, k, v, layer)
        else:
            impl = cfg.attention_impl
            if cfg.sequence_parallel and cfg.cp_impl == "ring":
                if self.window is not None or cfg.sparse_attention is not None:
                    raise NotImplementedError(
                        "cp_impl='ring' computes full causal attention; "
                        "local windows / sparse layouts are not ring-aware "
                        "yet — use cp_impl='ulysses' for those configs")
                # KV shards rotate the sp ring; q stays sequence-sharded
                from ..ops.ring_attention import ring_attention
                from ..parallel import mesh as mesh_lib
                scale = (cfg.qk_scale if cfg.qk_scale is not None
                         else 1.0 / math.sqrt(cfg.head_dim))
                out = ring_attention(q, k, v, mesh_lib.get_constraint_mesh(),
                                     scale=scale, causal=True)
            else:
                if cfg.sequence_parallel:
                    # Ulysses: seq-sharded -> head-sharded (all-to-all);
                    # each chip attends over the FULL sequence for H/sp
                    # heads. The einsum path partitions over heads under
                    # GSPMD; the pallas custom call does not
                    # auto-partition, so Ulysses runs the einsum
                    q, k, v = map(sp_shard_heads, (q, k, v))
                    why = ("sequence_parallel (Ulysses) head-shards q/k/v "
                           "under GSPMD, which cannot partition the Pallas "
                           "custom call")
                    from ..ops.pallas import _utils as kernels
                    if impl == "pallas":
                        kernels.refuse("attention_impl='pallas'", q.shape,
                                       why)
                    if impl == "auto":
                        kernels.log_path_once("attention", "xla", why)
                        impl = "xla"
                out = causal_attention(q, k, v, dtype=cfg.dtype,
                                       impl=impl,
                                       sparse_config=cfg.sparse_attention,
                                       scale=cfg.qk_scale, window=self.window)
                if cfg.sequence_parallel:
                    out = sp_shard_heads(out)
        out = out.reshape(b, s, cfg.d_model)
        if cfg.sequence_parallel and not decode:
            # back to sequence sharding for the projection/MLP/LN
            out = sp_shard_sequence(out)
        return nn.Dense(cfg.d_model, use_bias=True, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="out_proj")(out)

    def _decode_attention(self, q, k, v, layer=None):
        """KV-cache attention (reference ``softmax_context`` kernel with
        cache append, inference/csrc/softmax.cu): writes this step's k/v at
        ``cache_index`` and attends over the filled prefix. The cache is
        rank-4 [b, S, h, d] under ``decode_impl`` "auto" and "xla"; asked
        for BY NAME ("pallas") the all-lanes kernel keeps it FLAT
        [b, S, h*d], since XLA lane-pads a trailing d=64 dim (to 128) and a
        rank-4 cache would pay a full-cache relayout copy on every call.

        ``layer`` None: the ``cache`` leaves are this layer's own
        ([b, S, ...]; the cache is being created, or the layers are not
        scanned). ``layer`` an index: the leaves are the layer-stacked
        [L, b, S, ...] arrays that the layer loop carries; the write lands
        at ``(layer, row, pos)`` in place (:func:`_kv_write`). What the
        step then READS: under "auto" on a TPU, where
        :func:`live_read_block` accepts the call, a kernel that is handed
        the stacked leaves whole and DMAs ``ceil(fill / block)`` blocks of
        each lane's rows (none of a masked lane); otherwise the masked
        einsum over all S rows of the layer where they lie
        (:func:`_layer_rows`, fused into the dot). Neither produces an
        array of a layer's cache size. The flat kernel under "pallas" is
        handed :func:`_layer_rows` of the leaf as a custom call's operand,
        which IS a copy of the layer's rows. The masked-lane sentinel drops
        the write in every layer either way.

        ``cache_index`` may be a scalar (every row at the same fill — the
        single-stream generate path) or a [b] vector (per-slot fills — the
        continuous-batching serving arena, serving/kv_cache.py): writes and
        masks are elementwise per row in the vector case, and positions
        passed by the caller must equal the per-row fills. ``s > 1`` with a
        vector ``cache_index`` is the speculative-verify shape
        (serving/speculative.py): each row writes s candidate positions
        starting at its own fill, and attention masks causally from the
        per-row first query position.

        ``kv_cache_dtype="int8"``: the payload leaves store int8 with
        per-position f32 ``key_scale``/``value_scale`` leaves [b, S, 1]
        (one symmetric group per token's concatenated heads,
        ops/quantizer.quantize_kv); dequant happens inside this jit so XLA
        fuses the scale-multiply into the attention contractions."""
        cfg = self.cfg
        b, s, h, d = q.shape
        if cfg.sequence_parallel and s > 1:
            # Ulysses over the chunk-width cache path (the sp long-prompt
            # prefill, serving/engine.py): heads shard over sp with the
            # full sequence per chip — the all-to-all happens in the
            # constraint; exact identity when the mesh's sp axis is 1
            q, k, v = (sp_shard_heads(q), sp_shard_heads(k),
                       sp_shard_heads(v))
        if self.has_variable("cache", "block_tables"):
            # paged block-pool cache (serving/paged_kv.py): the engine
            # injected per-slot block tables, so reads and writes route
            # through them instead of slot rows
            return self._paged_decode_attention(q, k, v, layer)
        from ..ops.pallas import _utils as kernels
        from ..ops.pallas.decode_attention import decode_refusal
        int8 = cfg.kv_cache_dtype == "int8"
        kv_dt = jnp.int8 if int8 else cfg.dtype
        use_flat = cfg.decode_impl == "pallas"
        if use_flat:
            # asked for by name: the flat cache LAYOUT follows, so the gate
            # is asked at the decode width s=1 whatever this call's width
            refusal = (_NO_WINDOW_KERNEL if self.window is not None else
                       decode_refusal(b, cfg.max_seq_len, h, d, cfg.dtype)
                       or _decode_mesh_refusal())
            if refusal is not None:
                kernels.refuse("decode_impl='pallas'",
                               f"b={b} S={cfg.max_seq_len} h={h} d={d}",
                               refusal)
        live_read = live_read_block(cfg, b, s, self.window) is not None
        scale = (cfg.qk_scale if cfg.qk_scale is not None
                 else 1.0 / math.sqrt(d))
        idx = self.variable("cache", "cache_index",
                            lambda: jnp.zeros((), jnp.int32))
        cur = _layer_rows(idx.value, layer)
        rows = partial(_layer_rows, layer=layer)
        write = partial(_kv_write, cur=cur, layer=layer)
        ksc = vsc = None
        if int8:
            from ..ops.quantizer import quantize_kv
            ksc = self.variable("cache", "key_scale", jnp.zeros,
                                (b, cfg.max_seq_len, 1), jnp.float32)
            vsc = self.variable("cache", "value_scale", jnp.zeros,
                                (b, cfg.max_seq_len, 1), jnp.float32)
            kq, ks = quantize_kv(k.reshape(b, s, h * d))
            vq, vs = quantize_kv(v.reshape(b, s, h * d))
            ksc.value = write(ksc.value, ks)
            vsc.value = write(vsc.value, vs)
        if use_flat:
            ck = self.variable("cache", "cached_key", jnp.zeros,
                               (b, cfg.max_seq_len, h * d), kv_dt)
            cv = self.variable("cache", "cached_value", jnp.zeros,
                               (b, cfg.max_seq_len, h * d), kv_dt)
            if int8:
                ck.value = write(ck.value, kq)
                cv.value = write(cv.value, vq)
            else:
                ck.value = write(
                    ck.value, k.astype(cfg.dtype).reshape(b, s, h * d))
                cv.value = write(
                    cv.value, v.astype(cfg.dtype).reshape(b, s, h * d))
            idx.value = _set_layer_rows(idx.value, layer, cur + s)
            from ..ops.pallas.decode_attention import (MAX_SPEC_S,
                                                       decode_attention)
            if s == 1 or (s <= MAX_SPEC_S and not cfg.sequence_parallel):
                # fused prefix-only decode (reference softmax_context):
                # O(cache_len) compute AND HBM traffic per token — int8
                # blocks are DMA-streamed and dequantized in VMEM. s > 1
                # is the k+1 speculative-verify shape, handled in-kernel
                # by the s-position qmat, so the spec hot loop never
                # materializes a dequantized f32 cache view (a carried
                # cache hands the kernel a COPY of its layer's rows)
                return decode_attention(
                    q, rows(ck.value), rows(cv.value), cur + s, scale=scale,
                    k_scale=rows(ksc.value)[..., 0] if int8 else None,
                    v_scale=rows(vsc.value)[..., 0] if int8 else None)
            # prefill: one relayout of the cache view per call
            if int8:
                from ..ops.quantizer import dequantize_kv
                kf = dequantize_kv(rows(ck.value), rows(ksc.value),
                                   cfg.dtype)
                vf = dequantize_kv(rows(cv.value), rows(vsc.value),
                                   cfg.dtype)
            else:
                kf, vf = rows(ck.value), rows(cv.value)
            ck4 = kf.reshape(b, cfg.max_seq_len, h, d)
            cv4 = vf.reshape(b, cfg.max_seq_len, h, d)
            return self._cache_einsum(q, ck4, cv4, cur, s, scale)
        ck = self.variable("cache", "cached_key", jnp.zeros,
                           (b, cfg.max_seq_len, h, d), kv_dt)
        cv = self.variable("cache", "cached_value", jnp.zeros,
                           (b, cfg.max_seq_len, h, d), kv_dt)
        if int8:
            ck.value = write(ck.value, kq.reshape(b, s, h, d))
            cv.value = write(cv.value, vq.reshape(b, s, h, d))
        else:
            ck.value = write(ck.value, k.astype(cfg.dtype))
            cv.value = write(cv.value, v.astype(cfg.dtype))
        idx.value = _set_layer_rows(idx.value, layer, cur + s)
        if live_read:
            from ..ops.pallas.decode_attention import live_decode_attention
            return live_decode_attention(q, [(ck.value, cv.value, cur + s)],
                                         layer, scale=scale)
        if int8:
            from ..ops.quantizer import dequantize_kv
            kf = dequantize_kv(rows(ck.value), rows(ksc.value)[..., None],
                               cfg.dtype)
            vf = dequantize_kv(rows(cv.value), rows(vsc.value)[..., None],
                               cfg.dtype)
        else:
            kf, vf = rows(ck.value), rows(cv.value)
        return self._cache_einsum(q, kf, vf, cur, s, scale)

    def _paged_decode_attention(self, q, k, v, layer=None):
        """Block-table decode (vLLM PagedAttention shape): the cache is a
        flat block pool [nb, bs, h*d] shared by every slot (layer-stacked
        [L, nb, bs, h*d] and written in place when the layer loop carries
        it, as in :meth:`_decode_attention`); this slot's
        blocks are named by its ``block_tables`` row. Writes scatter
        through the table (:func:`_kv_write_paged`); attention gathers
        through it (ops/pallas/decode_attention.paged_decode_attention —
        the ``jnp.take`` reference path is bit-identical to the dense
        masked einsum, the Pallas kernel DMAs per-(row, block)). Prefill
        never runs here: it stays cacheless-dense and is scattered into
        the pool by PagedKVCacheManager.insert_batch. ``s > 1`` is the
        speculative-verify shape: s candidate positions write through the
        table per row (out-of-reservation positions hit the sentinel-padded
        table entries and drop) and the gather-attention masks causally
        from each row's own first query position."""
        cfg = self.cfg
        b, s, h, d = q.shape
        if self.window is not None:
            raise NotImplementedError(
                "paged KV decode has no local-window path")
        int8 = cfg.kv_cache_dtype == "int8"
        scale = (cfg.qk_scale if cfg.qk_scale is not None
                 else 1.0 / math.sqrt(d))
        idx = self.variable("cache", "cache_index")
        ck = self.variable("cache", "cached_key")
        cv = self.variable("cache", "cached_value")
        bt = _layer_rows(self.get_variable("cache", "block_tables"), layer)
        from ..ops.pallas import _utils as kernels
        from ..ops.pallas.decode_attention import paged_decode_refusal
        refusal = paged_decode_refusal(b, ck.value.shape[-2], h, d,
                                       ck.value.dtype, s) \
            or _decode_mesh_refusal()
        impl = cfg.decode_impl
        if impl == "pallas" and refusal is not None:
            kernels.refuse("decode_impl='pallas'",
                           f"q={q.shape} pool={ck.value.shape}", refusal)
        if impl == "auto":
            impl = ("pallas" if kernels.auto_path("paged_decode_attention",
                                                  refusal) else "xla")
        cur = _layer_rows(idx.value, layer)   # [b] per-slot write positions
        write = partial(_kv_write_paged, block_tables=bt, cur=cur,
                        layer=layer)
        ksc = vsc = None
        if int8:
            from ..ops.quantizer import quantize_kv
            ksc = self.variable("cache", "key_scale")
            vsc = self.variable("cache", "value_scale")
            kq, ks = quantize_kv(k.reshape(b, s, h * d))
            vq, vs = quantize_kv(v.reshape(b, s, h * d))
            ck.value = write(ck.value, kq)
            cv.value = write(cv.value, vq)
            ksc.value = write(ksc.value, ks)
            vsc.value = write(vsc.value, vs)
        else:
            dt = ck.value.dtype
            ck.value = write(ck.value, k.astype(dt).reshape(b, s, h * d))
            cv.value = write(cv.value, v.astype(dt).reshape(b, s, h * d))
        idx.value = _set_layer_rows(idx.value, layer, cur + s)
        from ..ops.pallas.decode_attention import paged_decode_attention
        return paged_decode_attention(
            q, ck.value, cv.value, bt, cur + s, scale=scale, impl=impl,
            k_scale=ksc.value[..., 0] if int8 else None,
            v_scale=vsc.value[..., 0] if int8 else None, layer=layer)

    def _cache_einsum(self, q, ck, cv, cur, s, scale):
        from ..ops.pallas.decode_attention import masked_cache_attention
        out = masked_cache_attention(q, ck, cv, cur, scale,
                                     window=self.window)
        if self.cfg.sequence_parallel and s > 1:
            # hand the head-sharded context back sequence-replicated so
            # the out-projection sees the layout the dense path expects
            out = sp_shard_heads(out)
        return out


class MLP(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, x, deterministic=True):
        cfg = self.cfg
        h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     name="up_proj")(x)
        h = nn.gelu(h, approximate=True)
        return nn.Dense(cfg.d_model, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="down_proj")(h)


class Block(nn.Module):
    """One transformer block. Returns ``(x, l_aux)`` so it can be the body of
    ``nn.scan`` directly (carry, per-step-output) — the scan-over-layers
    structure is what makes ZeRO-3 gather/release and per-layer remat
    idiomatic on TPU. ``l_aux`` is the MoE load-balancing loss (0 for dense
    blocks), summed over layers by GPT. ``layer_idx`` is set only on the
    non-scanned path (heterogeneous layers, e.g. GPT-Neo local windows);
    ``cache_layer`` is the scanned path's traced layer index, given when
    the layer loop carries a layer-stacked cache (GPT.__call__)."""
    cfg: GPTConfig
    layer_idx: Optional[int] = None

    def _ffn(self, cfg, h, deterministic):
        if cfg.moe:
            from ..moe.layer import MoE
            out, l_aux, _counts = MoE(
                hidden_size=cfg.d_model,
                expert=MLP(cfg),
                num_experts=cfg.num_experts,
                k=cfg.moe_top_k,
                capacity_factor=cfg.moe_capacity_factor,
                eval_capacity_factor=cfg.moe_eval_capacity_factor,
                min_capacity=cfg.moe_min_capacity,
                use_residual=cfg.moe_use_residual,
                name="moe")(h, deterministic=deterministic)
            return out, l_aux
        return MLP(cfg, name="mlp")(h, deterministic), jnp.zeros((), jnp.float32)

    @nn.compact
    def __call__(self, x, positions, deterministic=True, layer_frac=None,
                 pld_theta=None, cache_layer=None):
        cfg = self.cfg
        if cfg.partition_activations and x.ndim == 3:
            x = tp_shard_sequence(x)
        if cfg.cpu_checkpointing and x.ndim == 3:
            from jax.ad_checkpoint import checkpoint_name
            x = checkpoint_name(x, "ds_block_carry")
        ln1 = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name="ln_1")
        ln2 = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                           param_dtype=cfg.param_dtype, name="ln_2")
        window = None
        if cfg.attn_windows is not None and self.layer_idx is not None:
            window = cfg.attn_windows[self.layer_idx]
        attn = SelfAttention(cfg, window=window, name="attn")
        if cfg.parallel_residual:
            # NeoX: x + attn(ln1(x)) + ffn(ln2(x))
            ffn_out, l_aux = self._ffn(cfg, ln2(x), deterministic)
            attn_out = attn(ln1(x), positions, deterministic, cache_layer)
            if (cfg.tp_overlap and not self.is_initializing()
                    and self.is_mutable_collection("cache")):
                # decode only: pin the attn branch hidden-sharded so its
                # tp all-reduce splits into RS/AG around the MLP gemm
                from ..ops.tp_overlap import defer_attn_allreduce
                attn_out = defer_attn_allreduce(attn_out)
            out = x + attn_out + ffn_out
        else:
            h = x + attn(ln1(x), positions, deterministic, cache_layer)
            ffn_out, l_aux = self._ffn(cfg, ln2(h), deterministic)
            out = h + ffn_out
        if pld_theta is not None:
            # progressive layer drop (runtime/progressive_layer_drop.py):
            # deeper layers drop more; theta is traced so its decay reuses
            # the compiled program. A dropped block is the identity and
            # contributes no MoE aux loss. `deterministic` may itself be
            # traced (under remat), so eval-mode keep is fused as logical_or
            # rather than a Python branch.
            keep_p = 1.0 - layer_frac * (1.0 - pld_theta)
            keep = jax.random.bernoulli(self.make_rng("pld"), keep_p)
            keep = jnp.logical_or(keep, deterministic)
            out = jnp.where(keep, out, x)
            l_aux = jnp.where(keep, l_aux, 0.0)
        return out, l_aux


class GPT(nn.Module):
    """Decoder-only LM. __call__(input_ids [B,S]) -> logits [B,S,V]."""
    cfg: GPTConfig

    @nn.nowrap
    def stacked_spec(self, loss_fn=None):
        """prefix/block/suffix factoring for the structure-driving
        runtimes (SPMD pipeline, layer-streamed capacity tier)."""
        from ..runtime.pipe.spmd import gpt_pipe_spec
        return gpt_pipe_spec(self.cfg, loss_fn)

    @nn.compact
    def __call__(self, input_ids, deterministic=True, positions=None,
                 pld_theta=None, lengths=None):
        cfg = self.cfg
        b, s = input_ids.shape
        if positions is None:
            positions = jnp.arange(s)[None, :].repeat(b, axis=0)

        embed = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="wte")
        x = embed(input_ids)
        if cfg.sequence_parallel:
            # constrain the lookup output BEFORE anything mixes with it:
            # born [dp, sp, ·], the vocab-sharded table gather partitions by
            # its (dp, sp)-sharded indices instead of materializing a
            # replicated [B, S, D] and repartitioning it (the involuntary
            # full-remat XLA warns about when the constraint comes later)
            x = sp_shard_sequence(x)
        if not cfg.rotary:
            pos_emb = self.param(
                "wpe", nn.initializers.normal(0.02),
                (cfg.max_seq_len, cfg.d_model), cfg.param_dtype)
            x = x + pos_emb[positions].astype(cfg.dtype)
            if cfg.sequence_parallel:
                # re-constrain after the wpe add (its own gather output
                # would otherwise set the layout)
                x = sp_shard_sequence(x)

        block = Block
        if cfg.remat:
            if cfg.cpu_checkpointing:
                # save nothing on device; the named block inputs offload to
                # pinned host memory and stream back for backward
                policy = jax.checkpoint_policies.save_and_offload_only_these_names(
                    names_which_can_be_saved=[],
                    names_which_can_be_offloaded=["ds_block_carry"],
                    offload_src="device", offload_dst="pinned_host")
            else:
                policy = {
                    "nothing": jax.checkpoint_policies.nothing_saveable,
                    "dots": jax.checkpoint_policies.dots_saveable,
                    "dots_no_batch":
                        jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                }[cfg.remat_policy]
            # deterministic stays STATIC through remat: MoE gating and
            # dropout branch on it in Python (tracing it breaks, and a
            # traced train/eval flag would bake both branches anyway)
            block = nn.remat(_layer(cfg), prevent_cse=False, policy=policy,
                             static_argnums=(3,))   # arg 0 is the module

        if cfg.attn_windows is not None and cfg.scan_layers:
            raise ValueError("attn_windows (heterogeneous layers) requires "
                             "scan_layers=False")
        if cfg.scan_layers and cfg.block is None:
            # pld_theta (when given) rides as a broadcast arg with a scanned
            # per-layer depth fraction, so the SAME "blocks" params serve
            # both plain and layer-drop training
            extra_in = () if pld_theta is None else (
                (jnp.arange(1, cfg.num_layers + 1, dtype=jnp.float32)
                 / cfg.num_layers), pld_theta)
            extra_axes = () if pld_theta is None else (0, nn.broadcast)
            # The cache, where there is one. CREATED by this call (prefill,
            # the arena's eval_shape): scanned like the params, each layer's
            # leaves come out stacked [L, ...] and nothing is copied twice.
            # PASSED IN (every decode, speculative-verify and fused-prefill
            # step): the loop CARRIES the stacked leaves and each layer
            # writes its tokens at its own index in place. Scanning over a
            # cache that came in would slice every layer's rows out of the
            # arena, restack them into a fresh array and copy that back
            # into the caller's carry: three passes over the whole arena a
            # step (PERF.md, PR 25).
            cache_axes, cache_carry = {"cache": 0}, False
            if "blocks" in self.variables.get("cache", {}):
                cache_axes, cache_carry = {}, "cache"
                extra_in = (extra_in or (None, None)) + (
                    jnp.arange(cfg.num_layers, dtype=jnp.int32),)
                extra_axes = (extra_axes or (nn.broadcast, nn.broadcast)) \
                    + (0,)
            ScannedBlock = nn.scan(
                block,
                variable_axes={"params": 0, **cache_axes},
                variable_carry=cache_carry,
                split_rngs={"params": True, "dropout": True, "gating": True,
                            "pld": True},
                in_axes=(nn.broadcast, nn.broadcast) + extra_axes,
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
                unroll=cfg.scan_unroll,
            )
            x, aux = ScannedBlock(cfg, name="blocks")(
                x, positions, deterministic, *extra_in)
            moe_aux = jnp.sum(aux) if cfg.moe else jnp.zeros((), jnp.float32)
        elif cfg.block is None:
            moe_aux = jnp.zeros((), jnp.float32)
            for i in range(cfg.num_layers):
                extra = {} if pld_theta is None else {
                    "layer_frac": (i + 1) / cfg.num_layers,
                    "pld_theta": pld_theta}
                x, aux = block(cfg, layer_idx=i,
                               name=f"block_{i}")(x, positions, deterministic,
                                                  **extra)
                moe_aux = moe_aux + aux
        else:
            # another kind of block: its own stack of layers over its own
            # cache leaves, and what it hands out beside the logits
            kind = _kind(cfg.block)
            x, handed = kind.Stack(cfg, name="blocks")(x, positions, lengths)

        if cfg.block is None:
            x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                             param_dtype=cfg.param_dtype, name="ln_f")(x)
        else:
            x = kind.FinalNorm(cfg, name="ln_f")(x)
        if cfg.block is not None and hasattr(kind, "Head"):
            logits = kind.Head(cfg, name="lm_head")(x)
        elif cfg.tie_embeddings:
            logits = embed.attend(x)
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=cfg.dtype,
                              param_dtype=cfg.param_dtype, name="lm_head")(x)
        if cfg.moe:
            return logits, cfg.moe_aux_loss_coef * moe_aux
        if cfg.block is not None and handed is not None:
            # what the serving programs count and the reference check reads
            # (the latent kind: the experts each token chose)
            return logits, handed
        return logits

    @nn.nowrap
    def decode_read_block(self, b: int) -> Optional[int]:
        """Rows a block of what a one-token decode step of ``b`` lanes reads
        of each lane's dense cache rows, None where the step reads every
        row of every lane (:func:`live_read_block`; a model with window
        layers reads them all; another kind of block answers for itself)."""
        if self.cfg.block is not None:
            return _kind(self.cfg.block).decode_read_block(self.cfg, b)
        if self.cfg.attn_windows is not None:
            return None
        return live_read_block(self.cfg, b)

    @nn.nowrap
    def routing_counters(self, routed, live):
        """What a call's expert layers routed, as scalars a serving program
        sums on the device: ``routed`` is the dict a model with expert layers
        returns beside its logits, ``live [b, s]`` the tokens that count."""
        return _kind(self.cfg.block).routing_counters(self.cfg, routed, live)

    @property
    def prefill_takes_lengths(self) -> bool:
        """A call that creates a cache from padded rows has to be told where
        each row ends (``lengths [b]``): the cache this kind of block hands
        out depends on it, where a cache of one row a position just leaves
        the padding above the fill."""
        return getattr(_kind(self.cfg.block), "PREFILL_TAKES_LENGTHS", False)

    @nn.nowrap
    def lane_rows(self) -> int:
        """Rows of state one lane holds in one layer: a row a position,
        unless the block's kind keeps another count."""
        rows = getattr(_kind(self.cfg.block), "lane_rows", None)
        return self.cfg.max_seq_len if rows is None else rows(self.cfg)

    @nn.nowrap
    def blocks_read(self, positions, block: int):
        """Blocks of ``block`` rows the live-rows decode read takes of a lane
        whose next token is at ``positions`` (ints or an array): the rows
        under its fill, this token's among them, unless the block's kind
        keeps another count."""
        count = getattr(_kind(self.cfg.block), "blocks_read", None)
        if count is None:
            return -(-(positions + 1) // block)
        return count(self.cfg, positions, block)

    @nn.nowrap
    def step_counters(self, positions, live):
        """What one decode step of lanes at ``positions [b]`` read of their
        state, as named scalars a serving program sums on the device over
        the lanes ``live [b]``; None for a kind of block that counts
        nothing there."""
        count = getattr(_kind(self.cfg.block), "step_counters", None)
        return None if count is None else count(self.cfg, positions, live)

    @nn.nowrap
    def serving_refusal(self, **asked) -> Optional[str]:
        """Why this model cannot be served with the widths and options
        ``asked`` of ``ServingEngine`` that are on (``speculative``,
        ``fused_prefill``, ``paged``, ``tp``); None when it can, or when the
        block's kind leaves the question to the programs' traces."""
        refusal = getattr(_kind(self.cfg.block), "serving_refusal", None)
        return None if refusal is None else refusal(self.cfg, **asked)


def _kind(block):
    """The module that defines a block kind's config is the kind's door:
    ``Stack(cfg)(x, positions, lengths) -> (x, handed out or None)``,
    ``FinalNorm(cfg)``, ``decode_read_block(cfg, b)``; where it has them
    ``Head(cfg)``, ``routing_counters(cfg, routed, live)``,
    ``PREFILL_TAKES_LENGTHS``, ``lane_rows(cfg)``, ``blocks_read(cfg,
    positions, block)``, ``step_counters(cfg, positions, live)``,
    ``serving_refusal(cfg, **asked)`` (models/mla.py, models/eva.py,
    models/afmoe.py). None: this file."""
    import sys
    return sys.modules[type(block).__module__] if block is not None else None


def _layer(cfg):
    """The block class the remat wraps. Under scan-over-layers it carries
    the ZeRO-3 trainer's statement of the per-layer gather (a no-op class
    transform outside such a trace; runtime/sharding.py)."""
    if not cfg.scan_layers:
        return Block
    from ..runtime.sharding import gathered_where_used
    return gathered_where_used(Block)


def lm_loss_fn(logits, batch):
    """Next-token cross entropy. batch: {input_ids, labels?} — labels default
    to shifted input_ids. When the model returns (logits, moe_aux_loss) the
    aux load-balancing loss is added (reference: l_aux returned from
    MoE.forward, moe/layer.py:106, added to the training loss by the user
    script in the MoE tutorials)."""
    aux = None
    if isinstance(logits, tuple):
        logits, aux = logits
    labels = batch.get("labels")
    if labels is None:
        labels = batch["input_ids"][:, 1:]
        logits = logits[:, :-1]
    # nll = logsumexp - label logit, NOT -log_softmax[label]: the latter
    # materializes the full [B, S, V] fp32 log-softmax (1.6 GB of HBM
    # traffic at 8x1024x50k) while lse reduces it in-register and the label
    # logit is a gather (+4% train throughput at 125M on v5e)
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll.astype(jnp.float32)
    mask = batch.get("loss_mask")
    if mask is not None:
        mask = mask[:, :nll.shape[1]]
        loss = jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1)
    else:
        loss = jnp.mean(nll)
    if aux is not None:
        loss = loss + aux
    return loss


def count_params(params) -> int:
    return sum(int(p.size) for p in jax.tree.leaves(params))


def gpt_flops_per_token(cfg: GPTConfig, seq_len: Optional[int] = None) -> float:
    """Training flops per token for MFU accounting: 6 per matmul weight
    (forward + backward) plus the attention score/context term. Each
    matmul weight counts ONCE: per layer the qkv and out projections
    (4 d^2) and the MLP's up and down (2 d d_ff); the logits matmul
    (V d — the embedding lookup is a gather and costs nothing).
    Recomputation under remat does not count."""
    s = seq_len or cfg.max_seq_len
    n = (4 * cfg.d_model ** 2 + 2 * cfg.d_model * cfg.d_ff) * cfg.num_layers \
        + cfg.vocab_size * cfg.d_model
    return 6.0 * n + 12.0 * cfg.num_layers * cfg.d_model * s
