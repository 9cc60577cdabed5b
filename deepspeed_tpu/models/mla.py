"""The sandwich-normed latent-attention block, and the stack ``GPT`` runs it in.

The second block this repo runs (``GPTConfig.block`` a
:class:`LatentBlockConfig`; ``None`` is ``models/gpt.py``'s GPT-2/NeoX block).
With ``RMS(x) = x / sqrt(mean(x^2) + eps) * g``:

  block   h = x + RMS_post_attn(MLA(RMS_in(x)))
          y = h + RMS_post_mlp(FFN(RMS_pre_mlp(h)))          four norms
  MLA     c_q = RMS(x W_dq);  [q_nope | q_rope] = c_q W_uq   per head
          [c_kv | k_rope] = x W_dkv;  c = RMS(c_kv)
          [k_nope | v] = c [W_uk | W_uv]                      per head
          rotary on q_rope and on the ONE k_rope all heads share
          softmax((q_nope.k_nope + q_rope.k_rope) / sqrt(d_nope + d_rope)) v
  FFN     leading layers: (silu(x W_g) * (x W_u)) W_d, width ``d_ff``
          the rest: Shared(x) + sum over the chosen experts held here
          (moe/grouped.py), every one a gated MLP of width ``moe_d_ff``

The cache holds ``[c | rotated k_rope]``, ``kv_lora_rank + qk_rope_head_dim``
values a token a layer, in ONE layer-stacked leaf ``latent [L, B, S, row]``
beside ``cache_index [L]`` (``[L, B]`` in the serving arena). ``row`` is those
values padded with zeros to the next multiple of 128 (576 -> 640): the TPU
stores an array whose last dim is no multiple of its 128 lanes with ANOTHER
dim minor (here S: every feature a vector over positions), the attention
products then want it back, and the compiler turned the whole arena round on
the way into every chunk program and again on the way out (compile
rehearsal, PR 26: two copies of the arena and a temporary of its size). A
row of 640 is what the tiled 576 occupies anyway. The layer loop carries the
leaf and every layer, dense or expert, writes its rows at ``(layer, lane,
pos)`` in place (``gpt._kv_write``).

A call that is HANDED a cache (a decode step, a speculative verify) attends
in ABSORBED form over the latent: ``q_lat = q_nope W_uk^T``, scores
``q_lat.c + q_rope.k_rope``, ``o = (P c) W_uv`` — the same numbers, and
never a per-head key or value for a cached position. A call that has none,
or creates one (prefill), attends in EXPANDED heads over its own tokens
through ``gpt.causal_attention`` (the flash kernel where its gate allows).

Weights are declared layer-stacked by :class:`LatentStack` and the blocks are
pure functions of them: the expert loop (``grouped_experts``) reads one
expert's matrices out of the stacked bank where they lie, which a scanned
submodule's per-layer copy of the bank would defeat.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..moe.grouped import gated_mlp, grouped_experts, sigmoid_topk
from .gpt import _kv_write, _layer_rows, causal_attention, rotary_embedding

f32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class LatentBlockConfig:
    """What the block needs beyond ``GPTConfig`` (which gives ``d_model``,
    ``num_heads``, ``num_layers``, ``d_ff`` of the dense layers,
    ``rotary_base``, ``layer_norm_eps`` as the RMS epsilon). An expert layer
    is told the experts it HOLDS: the router keeps ``n_routed_experts``
    outputs and ``experts_per_token`` choices whatever the share."""
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    dense_layers: int = 1            # leading layers with the dense FFN
    n_routed_experts: int = 0
    experts_per_token: int = 8
    moe_d_ff: int = 0
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    experts_held: int = 0
    expert_offset: int = 0

    def __post_init__(self):
        if not (0 <= self.expert_offset
                and self.expert_offset + self.experts_held
                <= self.n_routed_experts):
            raise ValueError(
                f"experts {self.expert_offset}..{self.expert_offset + self.experts_held}"
                f" are not among the router's {self.n_routed_experts}")

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """``latent_dim`` padded to the TPU's 128 lanes (the module's
        docstring says what an unpadded row cost)."""
        return -(-self.latent_dim // 128) * 128


def rms_norm(x, gain, eps: float):
    x32 = x.astype(f32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return (y * gain.astype(f32)).astype(x.dtype)


class RMSNorm(nn.Module):
    cfg: object

    @nn.compact
    def __call__(self, x):
        gain = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                          self.cfg.param_dtype)
        return rms_norm(x, gain, self.cfg.layer_norm_eps)


def _dot(x, w):
    return jnp.dot(x, w, preferred_element_type=f32).astype(x.dtype)


def latent_attention(cfg, p, x, positions, latent, cur, layer, absorbed):
    """MLA over one layer's weights ``p``; with a ``latent`` leaf this call's
    rows are written at ``(layer, lane, cur)`` and the leaf is returned. Not
    ``absorbed`` (prefill): expanded heads over its own tokens, flash kernel.
    ``absorbed`` (a handed cache): :func:`decode_read_block` says the read."""
    lc = cfg.block
    b, s, _ = x.shape
    h, r = cfg.num_heads, lc.kv_lora_rank
    dn, dr, dv = lc.qk_nope_head_dim, lc.qk_rope_head_dim, lc.v_head_dim
    eps = cfg.layer_norm_eps
    scale = 1.0 / math.sqrt(dn + dr)

    c_q = rms_norm(_dot(x, p["q_down"]), p["q_norm"], eps)
    q = _dot(c_q, p["q_up"]).reshape(b, s, h, dn + dr)
    q = jnp.concatenate(
        [q[..., :dn],
         rotary_embedding(q[..., dn:], positions, dr, cfg.rotary_base)], -1)
    ckv = _dot(x, p["kv_down"])
    c = rms_norm(ckv[..., :r], p["kv_norm"], eps)
    k_rope = rotary_embedding(ckv[..., None, r:], positions, dr,
                              cfg.rotary_base)[:, :, 0]
    pad = lc.cache_row - lc.latent_dim
    if latent is not None:
        row = jnp.concatenate(
            [c, k_rope, jnp.zeros((b, s, pad), x.dtype)], axis=-1)
        latent = _kv_write(latent, row, cur, layer)
    w_uk = p["k_up"].reshape(r, h, dn)
    w_uv = p["v_up"].reshape(r, h, dv)

    if absorbed:
        with jax.named_scope("mla/decode"):
            q_lat = jnp.einsum("bshn,chn->bshc", q[..., :dn], w_uk,
                               preferred_element_type=f32).astype(x.dtype)
            q_row = jnp.concatenate(
                [q_lat, q[..., dn:], jnp.zeros((b, s, h, pad), x.dtype)], -1)
            if s == 1 and decode_read_block(cfg, b) is not None:
                # one token a lane: the kernel takes the carried leaf whole
                # and streams each lane's live blocks once, key and value
                # in one buffer (ops/pallas/decode_attention.py)
                from ..ops.pallas.decode_attention import \
                    live_latent_attention
                o_lat = live_latent_attention(
                    q_row[:, 0], latent, cur + 1, layer, scale, r)[:, None]
            else:
                # any other handed width: every row of every lane
                o_lat = _absorbed_over_the_whole_leaf(
                    q_row, _layer_rows(latent, layer), cur, scale)[..., :r]
            ctx = jnp.einsum("bshc,chv->bshv", o_lat.astype(x.dtype), w_uv,
                             preferred_element_type=f32).astype(x.dtype)
    else:
        with jax.named_scope("mla/prefill"):
            k_nope = jnp.einsum("bsc,chn->bshn", c, w_uk,
                                preferred_element_type=f32).astype(x.dtype)
            v = jnp.einsum("bsc,chv->bshv", c, w_uv,
                           preferred_element_type=f32).astype(x.dtype)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_rope[:, :, None], (b, s, h, dr))],
                axis=-1)
            ctx = causal_attention(q, k, v, dtype=cfg.dtype,
                                   impl=cfg.attention_impl, scale=scale)
    return _dot(ctx.reshape(b, s, h * dv), p["out_proj"]), latent


def _tile_rows(cfg, tokens: int) -> int:
    """Rows of one tile of the expert loop: four times what an expert
    receives when routing is even (``tokens * k / n_routed_experts``), as a
    power of two from 32 to 256. A tile reads its expert's matrices whole,
    so an expert that needs a second tile is read twice: with tiles of 8
    rows at 64 lanes a popular expert did, and tokens/s moved 4 % with the
    seed's router (PERF.md, PR 26); at 32 rows a decode step reads every
    touched expert once. A tile's other costs follow its rows (the scatter
    of its result most of all: a tile of 256 under 16 rows was most of a
    short prefill), so it is no larger than that, and never 512: PERF.md,
    PR 34. Nothing is dropped: a popular expert takes more tiles."""
    lc = cfg.block
    even = tokens * lc.experts_per_token / max(lc.n_routed_experts, 1)
    return int(min(256, max(32, 2 ** math.ceil(math.log2(max(4 * even, 1))))))


def expert_ffn(cfg, p, banks, x):
    """Shared expert + this chip's routed experts over ``x [b, s, d]``;
    ``banks`` = (the layer-stacked held experts, the layer to read them at).
    Returns the sum and the experts each token chose ``[b, s, k]``."""
    lc = cfg.block
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    with jax.named_scope("moe/route"):
        choice, weights = sigmoid_topk(
            flat, p["router"], lc.experts_per_token,
            lc.routed_scaling_factor, lc.norm_topk_prob)
    with jax.named_scope("moe/experts"):
        stacked, at = banks
        routed = grouped_experts(
            flat, choice, weights, stacked["expert_gate"],
            stacked["expert_up"], stacked["expert_down"], lead=(at,),
            expert_offset=lc.expert_offset, tile=_tile_rows(cfg, b * s))
    with jax.named_scope("moe/shared"):
        shared = gated_mlp(flat, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    return ((routed + shared).astype(x.dtype).reshape(b, s, d),
            choice.reshape(b, s, -1))


def latent_block(cfg, p, banks, x, positions, latent, cur, layer, absorbed):
    """One sandwich-normed block over one layer's weights ``p`` (``banks``
    None: the dense FFN). Returns ``(y, latent, choice or None)``."""
    eps = cfg.layer_norm_eps
    a, latent = latent_attention(cfg, p, rms_norm(x, p["ln_in"], eps),
                                 positions, latent, cur, layer, absorbed)
    h = x + rms_norm(a, p["ln_post_attn"], eps)
    f_in = rms_norm(h, p["ln_pre_mlp"], eps)
    if banks is None:
        f = gated_mlp(f_in, p["gate_proj"], p["up_proj"],
                      p["down_proj"]).astype(x.dtype)
        choice = None
    else:
        f, choice = expert_ffn(cfg, p, banks, f_in)
    return h + rms_norm(f, p["ln_post_mlp"], eps), latent, choice


def _stacked_normal(key, shape, dtype):
    """``normal / sqrt(fan_in)`` over the last two dims, drawn one leading
    slice at a time: a bank of a billion weights is never a float32 array."""
    if len(shape) > 2:
        return jax.lax.map(lambda k: _stacked_normal(k, shape[1:], dtype),
                           jax.random.split(key, shape[0]))
    return (jax.random.normal(key, shape, f32)
            / math.sqrt(shape[0])).astype(dtype)


class _LayerWeights(nn.Module):
    """``n`` layers' weights, every leaf stacked ``[n, ...]``."""
    cfg: object
    n: int
    routed: bool

    @nn.compact
    def __call__(self):
        cfg, lc = self.cfg, self.cfg.block
        d, h = cfg.d_model, cfg.num_heads
        out = {}

        def kernel(name, *shape):
            out[name] = self.param(name, _stacked_normal, (self.n,) + shape,
                                   cfg.param_dtype)

        def gain(name, width):
            out[name] = self.param(name, nn.initializers.ones,
                                   (self.n, width), cfg.param_dtype)

        for name in ("ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp"):
            gain(name, d)
        kernel("q_down", d, lc.q_lora_rank)
        gain("q_norm", lc.q_lora_rank)
        kernel("q_up", lc.q_lora_rank,
               h * (lc.qk_nope_head_dim + lc.qk_rope_head_dim))
        kernel("kv_down", d, lc.latent_dim)
        gain("kv_norm", lc.kv_lora_rank)
        kernel("k_up", lc.kv_lora_rank, h * lc.qk_nope_head_dim)
        kernel("v_up", lc.kv_lora_rank, h * lc.v_head_dim)
        kernel("out_proj", h * lc.v_head_dim, d)
        if not self.routed:
            kernel("gate_proj", d, cfg.d_ff)
            kernel("up_proj", d, cfg.d_ff)
            kernel("down_proj", cfg.d_ff, d)
            return out
        f = lc.moe_d_ff
        kernel("router", d, lc.n_routed_experts)
        kernel("shared_gate", d, lc.n_shared_experts * f)
        kernel("shared_up", d, lc.n_shared_experts * f)
        kernel("shared_down", lc.n_shared_experts * f, d)
        kernel("expert_gate", lc.experts_held, d, f)
        kernel("expert_up", lc.experts_held, d, f)
        kernel("expert_down", lc.experts_held, f, d)
        return out


_BANKS = ("expert_gate", "expert_up", "expert_down")


class LatentStack(nn.Module):
    """``dense_layers`` blocks with the dense FFN, then the expert layers
    under one ``lax.scan``, all over the one latent cache leaf. Returns the
    hidden state and ``{"expert_choice": [expert layers, b, s, k]}`` of the
    PUBLISHED router width (None without expert layers); reads no ``lengths``."""
    cfg: object

    @nn.compact
    def __call__(self, x, positions, lengths=None):
        cfg, lc = self.cfg, self.cfg.block
        b, s, _ = x.shape
        n_dense = min(lc.dense_layers, cfg.num_layers)
        n_sparse = cfg.num_layers - n_dense
        dense = _LayerWeights(cfg, n_dense, False, name="dense")() \
            if n_dense else {}
        sparse = _LayerWeights(cfg, n_sparse, True, name="sparse")() \
            if n_sparse else {}

        # prefill is expanded and decode is absorbed because the call has a
        # cache or has not, as GPT's own loop decides by what it is handed
        handed = self.has_variable("cache", "latent")
        caching = handed or (not self.is_initializing()
                             and self.is_mutable_collection("cache"))
        latent = cur = None
        if caching:
            lat = self.variable(
                "cache", "latent", jnp.zeros,
                (cfg.num_layers, b, cfg.max_seq_len, lc.cache_row), cfg.dtype)
            idx = self.variable("cache", "cache_index", jnp.zeros,
                                (cfg.num_layers,), jnp.int32)
            latent, cur = lat.value, idx.value

        def layer_of(tree, i):
            return {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
                    for k, v in tree.items() if k not in _BANKS}

        def at(i):
            return None if cur is None else _layer_rows(cur, i)

        for i in range(n_dense):
            x, latent, _ = latent_block(cfg, layer_of(dense, i), None, x,
                                        positions, latent, at(i), i, handed)
        choice = None
        if n_sparse:
            banks = {k: sparse[k] for k in _BANKS}

            # the scan's index picks the layer's weights; its cache rows
            # sit at n_dense + i, behind the dense layers' in the same leaf
            def body(carry, i):
                x, latent = carry
                x, latent, chosen = latent_block(
                    cfg, layer_of(sparse, i), (banks, i), x, positions,
                    latent, at(n_dense + i), n_dense + i, handed)
                return (x, latent), chosen

            (x, latent), choice = jax.lax.scan(
                body, (x, latent), jnp.arange(n_sparse, dtype=jnp.int32),
                unroll=cfg.scan_unroll)
        if caching:
            lat.value = latent
            idx.value = cur + s
        return x, (None if choice is None else {"expert_choice": choice})


# ---- what models/gpt.py asks of a block kind's module ---------------------
Stack = LatentStack
FinalNorm = RMSNorm


def decode_read_block(cfg, b: int):
    """Rows a block of the live-rows decode read carries, where a one-token
    decode step of ``b`` lanes takes it; None where absorbed attention reads
    every row of every lane. Which call attends how: prefill (no cache
    handed) in EXPANDED heads over its own tokens through the flash kernel;
    a handed cache and one token a lane ABSORBED over each lane's live
    blocks of the latent leaf (ops/pallas/decode_attention.
    live_latent_attention, kernel ``mla_decode_attention_live``); any other
    handed width (speculative verify, fused prefill), and every call where
    the gate refuses, ABSORBED over the whole leaf by the masked einsum
    (:func:`_absorbed_over_the_whole_leaf`). ``decode_impl="auto"`` alone
    chooses, from the platform, the mesh, the dtype and the shapes, as
    ``gpt.live_read_block`` does for a NeoX block."""
    if cfg.decode_impl != "auto":
        return None
    from ..ops.pallas import _utils as kernels
    from ..ops.pallas.decode_attention import (live_latent_block,
                                               live_latent_refusal)
    from .gpt import _decode_mesh_refusal
    refusal = live_latent_refusal(b, cfg.max_seq_len, cfg.num_heads,
                                  cfg.block.cache_row, cfg.dtype) \
        or _decode_mesh_refusal()
    if not kernels.auto_path("mla_decode_attention", refusal):
        return None
    return live_latent_block(cfg.max_seq_len)


def routing_counters(cfg, routed, live):
    """moe/grouped.py::routing_counters over what :class:`LatentStack`
    handed out, for the experts this chip holds. The tiles are
    :func:`expert_ffn`'s: its tile rows at the call's tokens, through the
    kernel where its call is."""
    from ..moe.grouped import routing_counters as count, takes_kernel
    choice = routed["expert_choice"]
    tokens = choice.shape[1] * choice.shape[2]
    tile = _tile_rows(cfg, tokens)
    return count(choice, live, expert_offset=cfg.block.expert_offset,
                 experts_held=cfg.block.experts_held, tile=tile,
                 kernel=takes_kernel(tokens, cfg.d_model, cfg.block.moe_d_ff,
                                     tile, cfg.dtype))


def _absorbed_over_the_whole_leaf(q_row, rows, cur, scale):
    """Absorbed attention of ``q_row [b, s, h, row]`` over ALL of one layer's
    cached ``rows [b, S, row]``, masked afterwards: query j of a lane sits at
    ``cur + j`` and sees the keys up to there. Returns the float32
    ``[b, s, h, row]`` of probabilities times rows; the caller drops the rope
    part (slicing ``c`` out of the cached rows first would copy the layer's
    cache). Below the prefill's flash call site, whose compiled body carries
    the line numbers above it."""
    s = q_row.shape[1]
    scores = jnp.einsum("bshc,btc->bhst", q_row, rows,
                        preferred_element_type=f32) * scale
    last = jnp.reshape(cur, (-1, 1, 1, 1)) \
        + jnp.arange(s, dtype=jnp.int32)[None, None, :, None]
    seen = jnp.arange(rows.shape[1], dtype=jnp.int32) <= last
    probs = jax.nn.softmax(jnp.where(seen, scores, -1e10), axis=-1)
    return jnp.einsum("bhst,btc->bshc", probs.astype(q_row.dtype), rows,
                      preferred_element_type=f32)
