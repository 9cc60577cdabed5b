"""The AFMoE block: gated grouped-query attention, sliding-window layers
beside global ones, and an expert layer that holds every expert.

The fourth block this repo runs (``GPTConfig.block`` an
:class:`AfmoeBlockConfig`; ``None`` is ``models/gpt.py``'s GPT-2/NeoX block,
``models/mla.py`` the latent one, ``models/eva.py`` the window beside chunk
summaries). With ``RMS(x) = x / sqrt(mean(x^2) + eps) * g``, ``h`` query
heads over ``hk`` key heads of size ``dh`` (``h * dh`` need not be
``d_model``), window ``w``:

  embed     x = E[id] * sqrt(d_model)                          (muP)
  block     h = x + RMS_post_attn(Attn(RMS_in(x)))
            y = h + RMS_post_mlp(FFN(RMS_pre_mlp(h)))          four norms
  attention q = x W_q, k = x W_k, v = x W_v, g = x W_gate; q and k normed
            per head over their dh features with a learned gain. A SLIDING
            layer turns q and k by rotary over half-split pairs and query i
            sees keys j with 0 <= i - j < w; a FULL layer applies no
            positional encoding and sees every j <= i. Query head n reads
            key head n // (h / hk). softmax(q.k / sqrt(dh)) in float32;
            o = (concat_heads(P v) * sigmoid(g)) W_o. No bias anywhere.
  FFN       leading layers: (silu(x W_g) * (x W_u)) W_d, width ``d_ff``
            the rest: Shared(x) + sum over the chosen experts of w_i E_i(x)
            (moe/grouped.py: sigmoid scores, the k largest of score + a
            per-expert selection bias that does not enter the weights),
            every one a gated MLP of width ``moe_d_ff``; all experts held

**Two kinds of KV leaf in one cache, and a layer owns one of them.** The
keys and values of a position are one flat row of ``hk * dh`` values (512
here: whole 128-lane rows; a rank-5 ``[.., hk, dh]`` leaf would put 4 heads
in the TPU's 16-row sublane tile). A sliding layer owns a slot of the RING
leaves ``window_key/value [sliding layers, B, w, hk * dh]``, written at row
``t mod w``: keys are stored AFTER rotary, so a row's order in the ring does
not matter and a lane at position ``t`` reads rows ``< min(t + 1, w)``. A
full layer owns a slot of the GLOBAL leaves ``global_key/value [full layers,
B, max_seq_len, hk * dh]``, written at row ``t``, and reads rows ``<= t``.
Beside them ``cache_index [1]`` (``[1, B]`` in the serving arena), the
position of the next token. Static per-layer tables (:func:`layer_tables`)
say which kind a layer is and which slot of its leaf it owns:

  * a call that is HANDED the cache (a decode step, one token a lane) carries
    all four leaves through the layer loop. Every layer issues BOTH writes
    and the one into the leaf it does not own is sent out of range and
    dropped (``gpt._kv_write``), so the writes do not branch; the READ
    branches on the kind (``lax.cond``), each side a read of its own leaf's
    rows where they lie: on a TPU the live-rows kernel over each lane's
    LIVE blocks of that one pair (``ops/pallas/decode_attention.
    live_decode_attention``, :func:`decode_read_block` says where), else a
    masked einsum over the leaf whole (:func:`cache_attention`, also the
    kernel's reference). Either way the queries meet the flat rows as
    ``[h, hk * dh]`` with zeros outside their own head's columns, so no
    per-head view of the rows is made (a reshape of a tiled leaf is a copy of
    it). A cursor at or past ``max_seq_len`` is the serving engine's
    retired-lane sentinel: ``max_seq_len mod w`` is a row of the ring, so a
    dead lane's ring row, and its fill of either leaf, is sent past the leaf
    explicitly.
  * a call that has no cache, or creates one (prefill), attends over its own
    tokens through ONE attention program for both kinds, the window a traced
    scalar (``ops/pallas/flash_attention.flash_attention_band`` where its
    gate accepts, a masked einsum elsewhere). It hands out, for the layers
    that own them, the ring rows of each row's LAST ``w`` tokens
    (``lengths``: a padded prompt's padding would otherwise fill the ring)
    and the global rows padded to ``max_seq_len``; told the lengths it
    returns the hidden state of each row's last token ALONE, since a head
    over 200,192 entries at 16,384 positions would be 6.6 GB of logits of
    which the server reads one row.

Weights are declared layer-stacked by :class:`AfmoeStack` (a ``dense`` and a
``sparse`` group, as ``models/mla.py``) and the blocks are pure functions of
them; the expert layers run under one ``lax.scan`` whatever their kinds.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from ..moe.grouped import gated_mlp, grouped_experts, sigmoid_topk
from .eva import rotary_half
from .gpt import _kv_write, _layer_rows
from .mla import RMSNorm, _dot, _stacked_normal, rms_norm

f32 = jnp.float32
NEG_INF = -1e10
SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeBlockConfig:
    """What the block needs beyond ``GPTConfig`` (which gives ``d_model``,
    ``num_heads`` query heads, ``num_layers``, ``d_ff`` of the dense layers,
    ``rotary_base``, ``layer_norm_eps`` as the RMS epsilon, ``vocab_size``,
    ``max_seq_len``). Every routed expert is held here: ``moe/grouped.py``
    is told no share (``expert_offset`` 0)."""
    num_kv_heads: int
    head_dim: int
    sliding_window: int
    layer_types: Tuple[str, ...]     # SLIDING or FULL, one a layer
    dense_layers: int = 1            # leading layers with the dense FFN
    n_routed_experts: int = 0
    experts_per_token: int = 8
    moe_d_ff: int = 0
    n_shared_experts: int = 1
    routed_scaling_factor: float = 1.0
    norm_topk_prob: bool = True
    embed_scale: bool = True         # x = E[id] * sqrt(d_model)

    def __post_init__(self):
        object.__setattr__(self, "layer_types", tuple(self.layer_types))
        bad = set(self.layer_types) - {SLIDING, FULL}
        if bad:
            raise ValueError(f"layer_types {sorted(bad)}: a layer is "
                             f"{SLIDING!r} or {FULL!r}")

    @property
    def row(self) -> int:
        """Values of one position's keys (or values) in one layer."""
        return self.num_kv_heads * self.head_dim


def layer_tables(cfg):
    """``(is_full [L] bool, slot [L] int32, sliding layers, full layers)``:
    which kind of leaf each layer owns and which slot of it, as numpy."""
    types = cfg.block.layer_types
    if len(types) != cfg.num_layers:
        raise ValueError(f"{len(types)} layer_types for {cfg.num_layers} "
                         f"layers")
    full = np.asarray([t == FULL for t in types])
    slot = np.where(full, np.cumsum(full) - 1, np.cumsum(~full) - 1)
    return full, slot.astype(np.int32), int((~full).sum()), int(full.sum())


def lane_rows(cfg) -> int:
    """Rows one lane holds in one layer, the mean over the two kinds (``w``
    in a sliding layer, ``max_seq_len`` in a full one), rounded down."""
    _, _, n_sliding, n_full = layer_tables(cfg)
    return (n_sliding * cfg.block.sliding_window
            + n_full * cfg.max_seq_len) // cfg.num_layers


def live_rows(cfg, t):
    """``(ring rows a sliding layer, global rows a full layer)`` a lane whose
    next token is at position ``t`` reads in that step (after its own write):
    ``min(t + 1, w)`` and ``t + 1``. Works on ints, numpy and jax arrays."""
    lib = jnp if isinstance(t, jax.Array) else np
    return lib.minimum(t + 1, cfg.block.sliding_window), t + 1


# ---- attention --------------------------------------------------------------
def banded_reference(q, k, v, window, scale, dtype):
    """The masked einsum the band kernel is held to and the path where its
    gate refuses: ``q [b, s, h, d]``, ``k, v [b, s, hk, d]``, query ``i``
    over keys ``j`` with ``0 <= i - j < window`` (a traced scalar)."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    qg = q.reshape(b, s, hk, h // hk, d)
    sc = jnp.einsum("bqkgd,bjkd->bkgqj", qg, k,
                    preferred_element_type=f32) * scale
    i = jnp.arange(s, dtype=jnp.int32)[:, None]
    j = jnp.arange(s, dtype=jnp.int32)[None, :]
    seen = (i >= j) & (i - j < window)
    probs = jax.nn.softmax(jnp.where(seen, sc, NEG_INF), axis=-1)
    out = jnp.einsum("bkgqj,bjkd->bqkgd", probs.astype(dtype), v,
                     preferred_element_type=f32)
    return out.reshape(b, s, h, d).astype(dtype)


def banded_attention(cfg, q, k, v, window):
    """Attention of a call over its own tokens: the band kernel where
    ``attention_impl`` and its gate allow, the masked einsum elsewhere."""
    from ..ops.pallas import _utils as kernels
    from ..ops.pallas.flash_attention import (flash_attention_band,
                                              flash_band_refusal)
    from ..parallel import mesh as mesh_lib
    b, s, h, d = q.shape
    scale = 1.0 / math.sqrt(d)
    impl = cfg.attention_impl
    if impl in ("auto", "pallas"):
        refusal = flash_band_refusal(s, d, h, k.shape[2])
        if impl == "pallas" and refusal is not None:
            kernels.refuse("attention_impl='pallas'", q.shape, refusal)
        n = mesh_lib.get_constraint_mesh().size
        if refusal is None and n > 1 and not kernels.interpret_mode():
            refusal = (f"mesh of {n} devices: the band kernel is not "
                       f"wrapped in shard_map")
        if impl == "pallas" or kernels.auto_path("attention_band", refusal):
            return flash_attention_band(q, k, v, window, scale)
    return banded_reference(q, k, v, window, scale, cfg.dtype)


def _own_columns(h: int, hk: int, dtype):
    """``[h, hk]``: 1 where query head ``n`` reads key head ``n // (h/hk)``."""
    return (jnp.arange(h)[:, None] // (h // hk)
            == jnp.arange(hk)[None, :]).astype(dtype)


def cache_attention(q, rows_k, rows_v, seen, hk: int, dtype):
    """One query a lane ``q [b, 1, h, d]`` over flat cache rows ``rows_k,
    rows_v [b, R, hk * d]`` where ``seen [b, R]``, one float32 softmax a
    head. The queries are widened to the rows' ``hk * d`` columns with
    zeros outside their own key head's, and the context is read back out of
    their own columns: the same numbers as a per-head product, with the
    rows met where they lie."""
    b, _, h, d = q.shape
    own = _own_columns(h, hk, q.dtype)                          # [h, hk]
    wide = (q[:, 0, :, None, :] * own[None, :, :, None]
            ).reshape(b, h, hk * d)
    sc = jnp.einsum("bhc,brc->bhr", wide, rows_k,
                    preferred_element_type=f32) / math.sqrt(d)
    probs = jax.nn.softmax(jnp.where(seen[:, None, :], sc, NEG_INF), axis=-1)
    out = jnp.einsum("bhr,brc->bhc", probs.astype(dtype), rows_v,
                     preferred_element_type=f32).reshape(b, h, hk, d)
    out = jnp.sum(out * own.astype(f32)[None, :, :, None], axis=2)
    return out.astype(dtype)[:, None]


def afmoe_attention(cfg, p, x, positions, is_full, leaves, cur, slot):
    """Gated grouped-query attention over one layer's weights ``p``; ``x``
    the normed input ``[b, s, d]``, ``is_full`` the layer's kind (a bool,
    traced under the layer scan). ``leaves`` None: the call attends over its
    own tokens and returns ``(out, (k, v))``, the flat rows for a cache.
    Otherwise the four carried leaves with ``cur [b]`` each lane's position
    and ``slot`` the layer's slot in the leaf it owns; returns ``(out,
    leaves)``."""
    bc = cfg.block
    b, s, _ = x.shape
    h, hk, dh = cfg.num_heads, bc.num_kv_heads, bc.head_dim
    eps = cfg.layer_norm_eps
    q = rms_norm(_dot(x, p["q_proj"]).reshape(b, s, h, dh), p["q_norm"], eps)
    k = rms_norm(_dot(x, p["k_proj"]).reshape(b, s, hk, dh), p["k_norm"],
                 eps)
    v = _dot(x, p["v_proj"])                                  # [b, s, hk*dh]
    gate = _dot(x, p["attn_gate"])                            # [b, s, h*dh]
    # a full layer has no positional encoding
    q = jnp.where(is_full, q, rotary_half(q, positions, cfg.rotary_base))
    k = jnp.where(is_full, k, rotary_half(k, positions, cfg.rotary_base))
    k = k.reshape(b, s, hk * dh)
    if leaves is None:
        with jax.named_scope("afmoe/prefill"):
            window = jnp.where(is_full, s, bc.sliding_window)
            ctx = banded_attention(cfg, q, k.reshape(b, s, hk, dh),
                                   v.reshape(b, s, hk, dh), window)
        state = (k, v)
    else:
        wk, wv, gk, gv = leaves
        w, S = wk.shape[2], gk.shape[2]
        # both writes, the one into the leaf this layer does not own sent
        # past its rows and dropped, as a retired lane's (max_seq_len mod w
        # is a row of the ring)
        dead = cur >= cfg.max_seq_len
        ring_row = jnp.where(dead | is_full, w, cur % w)
        glob_row = jnp.where(dead | jnp.logical_not(is_full), S, cur)
        wk = _kv_write(wk, k.astype(wk.dtype), ring_row, slot)
        wv = _kv_write(wv, v.astype(wv.dtype), ring_row, slot)
        gk = _kv_write(gk, k.astype(gk.dtype), glob_row, slot)
        gv = _kv_write(gv, v.astype(gv.dtype), glob_row, slot)
        n_ring, n_glob = live_rows(cfg, cur)
        block = decode_read_block(cfg, b)

        def read(key, value, fill):
            if block is not None:
                # each lane's live blocks of the pair this layer owns; a dead
                # lane's fill lies past the leaf (max_seq_len mod w would
                # read as live ring rows)
                from ..ops.pallas.decode_attention import \
                    live_decode_attention
                past = key.shape[2] + 1
                return live_decode_attention(
                    q, [(key, value, jnp.where(dead, past, fill))], slot,
                    block_k=block)
            seen = jnp.arange(key.shape[2], dtype=jnp.int32)[None, :] \
                < fill[:, None]
            return cache_attention(q, _layer_rows(key, slot),
                                   _layer_rows(value, slot), seen, hk,
                                   cfg.dtype)

        with jax.named_scope("afmoe/decode"):
            if isinstance(is_full, (bool, np.bool_)):
                ctx = read(gk, gv, n_glob) if is_full \
                    else read(wk, wv, n_ring)
            else:
                ctx = jax.lax.cond(is_full,
                                   lambda: read(gk, gv, n_glob),
                                   lambda: read(wk, wv, n_ring))
        state = (wk, wv, gk, gv)
    gated = (ctx.reshape(b, s, h * dh).astype(f32)
             * jax.nn.sigmoid(gate.astype(f32))).astype(cfg.dtype)
    return _dot(gated, p["o_proj"]), state


# Rows of the largest tile of the expert loop. models/mla.py's rule (four
# times an expert's even share, a power of two from 32) with a lower top: at
# 512 rows a tile the prefill of 3, 5 or 7 prompts in the 512 bucket (1,536,
# 2,560, 3,584 tokens) never came back from the chip, and one prompt of 2,048
# did not inside the serving process though it did alone; at 256 they do
# (my chip runs, PR 32; PERF.md section 7: not explained, the loop's own
# static bound did not help). A decode step's tiles are 32 rows either way.
_TILE_ROWS_MAX = 256


def _tile_rows(cfg, tokens: int) -> int:
    bc = cfg.block
    even = tokens * bc.experts_per_token / max(bc.n_routed_experts, 1)
    return int(min(_TILE_ROWS_MAX,
                   max(32, 2 ** math.ceil(math.log2(max(4 * even, 1))))))


def expert_ffn(cfg, p, banks, x):
    """Shared expert + every routed expert over ``x [b, s, d]`` (``models/
    mla.py::expert_ffn`` with the selection bias and this block's tile);
    ``banks`` = (the layer-stacked experts, the layer to read them at).
    Returns the sum and the experts each token chose ``[b, s, k]``."""
    bc = cfg.block
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    with jax.named_scope("moe/route"):
        choice, weights = sigmoid_topk(
            flat, p["router"], bc.experts_per_token,
            bc.routed_scaling_factor, bc.norm_topk_prob,
            bias=p["router_bias"])
    with jax.named_scope("moe/experts"):
        stacked, at = banks
        routed = grouped_experts(
            flat, choice, weights, stacked["expert_gate"],
            stacked["expert_up"], stacked["expert_down"], lead=(at,),
            tile=_tile_rows(cfg, b * s))
    with jax.named_scope("moe/shared"):
        shared = gated_mlp(flat, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    return ((routed + shared).astype(x.dtype).reshape(b, s, d),
            choice.reshape(b, s, -1))


def afmoe_block(cfg, p, banks, x, positions, is_full, leaves, cur, slot):
    """One sandwich-normed block over one layer's weights ``p`` (``banks``
    None: the dense FFN). Returns ``(y, attention state, choice or None)``."""
    eps = cfg.layer_norm_eps
    a, state = afmoe_attention(cfg, p, rms_norm(x, p["ln_in"], eps),
                               positions, is_full, leaves, cur, slot)
    h = x + rms_norm(a, p["ln_post_attn"], eps)
    f_in = rms_norm(h, p["ln_pre_mlp"], eps)
    if banks is None:
        f = gated_mlp(f_in, p["gate_proj"], p["up_proj"],
                      p["down_proj"]).astype(x.dtype)
        choice = None
    else:
        f, choice = expert_ffn(cfg, p, banks, f_in)
    return h + rms_norm(f, p["ln_post_mlp"], eps), state, choice


# ---- weights ----------------------------------------------------------------
class _LayerWeights(nn.Module):
    """``n`` layers' weights, every leaf stacked ``[n, ...]``."""
    cfg: object
    n: int
    routed: bool

    @nn.compact
    def __call__(self):
        cfg, bc = self.cfg, self.cfg.block
        d, h, dh = cfg.d_model, cfg.num_heads, bc.head_dim
        out = {}

        def kernel(name, *shape):
            out[name] = self.param(name, _stacked_normal, (self.n,) + shape,
                                   cfg.param_dtype)

        def gain(name, width):
            out[name] = self.param(name, nn.initializers.ones,
                                   (self.n, width), cfg.param_dtype)

        for name in ("ln_in", "ln_post_attn", "ln_pre_mlp", "ln_post_mlp"):
            gain(name, d)
        kernel("q_proj", d, h * dh)
        kernel("k_proj", d, bc.row)
        kernel("v_proj", d, bc.row)
        kernel("attn_gate", d, h * dh)
        kernel("o_proj", h * dh, d)
        gain("q_norm", dh)
        gain("k_norm", dh)
        if not self.routed:
            kernel("gate_proj", d, cfg.d_ff)
            kernel("up_proj", d, cfg.d_ff)
            kernel("down_proj", cfg.d_ff, d)
            return out
        f, e = bc.moe_d_ff, bc.n_routed_experts
        kernel("router", d, e)
        out["router_bias"] = self.param("router_bias", nn.initializers.zeros,
                                        (self.n, e), cfg.param_dtype)
        kernel("shared_gate", d, bc.n_shared_experts * f)
        kernel("shared_up", d, bc.n_shared_experts * f)
        kernel("shared_down", bc.n_shared_experts * f, d)
        kernel("expert_gate", e, d, f)
        kernel("expert_up", e, d, f)
        kernel("expert_down", e, f, d)
        return out


_BANKS = ("expert_gate", "expert_up", "expert_down")
_LEAVES = ("window_key", "window_value", "global_key", "global_value")


def _ring_rows(rows, lengths, w: int):
    """The ring a prefill hands a sliding layer: ``rows [b, T, c]`` by
    position -> ``[b, w, c]`` with ring row ``r`` holding the LAST position
    ``p < length`` with ``p mod w = r`` (a row no position of the prompt maps
    to holds whatever, under no lane's fill)."""
    last = jnp.maximum(lengths, 1)[:, None] - 1                  # [b, 1]
    r = jnp.arange(w, dtype=jnp.int32)[None, :]
    at = jnp.clip(last - (last - r) % w, 0, rows.shape[1] - 1)    # [b, w]
    return jnp.take_along_axis(rows, at[:, :, None], axis=1)


class AfmoeStack(nn.Module):
    """``dense_layers`` blocks with the dense FFN, then the expert layers
    under one ``lax.scan``, each over the KV leaf of its kind. ``lengths
    [b]``: the tokens of each row that exist, for a call that creates a cache
    from padded rows; such a call returns each row's LAST hidden state alone
    ``[b, 1, d]``. Returns the hidden state and ``{"expert_choice": [expert
    layers, b, s, k]}`` (None without expert layers)."""
    cfg: object

    @nn.compact
    def __call__(self, x, positions, lengths=None):
        cfg, bc = self.cfg, self.cfg.block
        b, s, _ = x.shape
        refusal = serving_refusal(cfg)
        if refusal is not None:
            raise NotImplementedError(refusal)
        is_full, slot, n_sliding, n_full = layer_tables(cfg)
        n_dense = min(bc.dense_layers, cfg.num_layers)
        n_sparse = cfg.num_layers - n_dense
        dense = _LayerWeights(cfg, n_dense, False, name="dense")() \
            if n_dense else {}
        sparse = _LayerWeights(cfg, n_sparse, True, name="sparse")() \
            if n_sparse else {}
        w, S = bc.sliding_window, cfg.max_seq_len

        handed = self.has_variable("cache", _LEAVES[0])
        caching = handed or (not self.is_initializing()
                             and self.is_mutable_collection("cache"))
        if caching:
            held = [self.variable("cache", name, jnp.zeros,
                                  (n, b, rows, bc.row), cfg.dtype)
                    for name, n, rows in zip(
                        _LEAVES, (n_sliding, n_sliding, n_full, n_full),
                        (w, w, S, S))]
            idx = self.variable("cache", "cache_index", jnp.zeros, (1,),
                                jnp.int32)
        positions = jnp.broadcast_to(positions, (b, s))
        if bc.embed_scale:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)

        def layer_of(tree, i):
            return {k: jax.lax.dynamic_index_in_dim(v, i, 0, keepdims=False)
                    for k, v in tree.items() if k not in _BANKS}

        leaves = cur = None
        if handed:
            if s != 1:
                raise NotImplementedError(
                    f"a call that is handed the ring and global leaves "
                    f"writes one token a lane; {s} tokens could wrap the "
                    f"ring inside the call (the speculative and "
                    f"fused-prefill widths are not built for this block)")
            leaves = tuple(v.value for v in held)
            cur = jnp.broadcast_to(idx.value[0], (b,))

        states = []
        for i in range(n_dense):
            x, state, _ = afmoe_block(
                cfg, layer_of(dense, i), None, x, positions, bool(is_full[i]),
                leaves, cur, int(slot[i]))
            if handed:
                leaves = state
            else:
                states.append(state)
        choice = None
        if n_sparse:
            banks = {k: sparse[k] for k in _BANKS}
            kinds = (jnp.arange(n_sparse, dtype=jnp.int32),
                     jnp.asarray(is_full[n_dense:]),
                     jnp.asarray(slot[n_dense:]))

            # the scan's index picks the layer's weights; the tables say
            # which leaf the layer owns and which slot of it
            def body(carry, at):
                x, leaves = carry
                i, full, own = at
                x, state, chosen = afmoe_block(
                    cfg, layer_of(sparse, i), (banks, i), x, positions, full,
                    leaves, cur, own)
                if handed:
                    return (x, state), (chosen, None)
                return (x, None), (chosen, state if caching else None)

            (x, leaves), (choice, scanned) = jax.lax.scan(
                body, (x, leaves), kinds, unroll=cfg.scan_unroll)
        if caching and not handed:
            if s > S:
                raise ValueError(f"{s} tokens exceed max_seq_len {S}")
            ends = jnp.full((b,), s, jnp.int32) if lengths is None \
                else lengths
            # every layer's flat rows, then each kind's own by static slices
            # (indexing a stacked [L, ...] array with a list is a gather)
            ks = [st[0] for st in states] + (
                [scanned[0][i] for i in range(n_sparse)] if n_sparse else [])
            vs = [st[1] for st in states] + (
                [scanned[1][i] for i in range(n_sparse)] if n_sparse else [])
            pad = ((0, 0), (0, S - s), (0, 0))

            def of_kind(rows, full, make):
                mine = [make(r) for r, f in zip(rows, is_full) if f == full]
                return jnp.stack(mine) if mine else jnp.zeros(
                    (0, b, S if full else w, bc.row), cfg.dtype)

            leaves = (
                of_kind(ks, False, lambda r: _ring_rows(r, ends, w)),
                of_kind(vs, False, lambda r: _ring_rows(r, ends, w)),
                of_kind(ks, True, lambda r: jnp.pad(r, pad)),
                of_kind(vs, True, lambda r: jnp.pad(r, pad)))
        if caching:
            for var, leaf in zip(held, leaves):
                var.value = leaf.astype(cfg.dtype)
            idx.value = idx.value + s
        if lengths is not None and caching and not handed:
            # told where each row ends, the call hands out that token's
            # hidden state alone (the module docstring says why)
            x = jnp.take_along_axis(
                x, (jnp.maximum(lengths, 1) - 1)[:, None, None], axis=1)
        return x, (None if choice is None else {"expert_choice": choice})


# ---- what models/gpt.py asks of a block kind's module ---------------------
Stack = AfmoeStack
FinalNorm = RMSNorm
PREFILL_TAKES_LENGTHS = True


def serving_refusal(cfg, **asked):
    """Why this block cannot be served the way ``asked`` (the widths and
    options of ``ServingEngine`` that are on: ``speculative``,
    ``fused_prefill``, ``paged``, ``tp``) or built under ``cfg``; None when
    it can. ``ServingEngine`` asks at construction."""
    if cfg.kv_cache_dtype == "int8":
        return ("the ring and global leaves of the AFMoE block have no int8 "
                "rows and scales (kv_dtype='int8')")
    if not cfg.scan_layers:
        return "the AFMoE block runs its layers under scan_layers=True alone"
    for name in ("speculative", "fused_prefill"):
        if asked.get(name):
            return (f"{name}: a call that is handed the ring and global "
                    f"leaves writes one token a lane (more could wrap the "
                    f"ring inside the call)")
    if asked.get("paged"):
        return ("paged: the block pool builds block tables beside a "
                "cached_key alone; a window layer's blocks would have to be "
                "freed as the window passes them")
    if int(asked.get("tp", 1)) > 1:
        return (f"tp={asked['tp']}: runtime/sharding.kv_spec knows "
                f"cached_key / cached_value by name, not the ring and global "
                f"leaves")
    return None


def decode_read_block(cfg, b: int):
    """Rows a block of the live-rows decode read carries where a decode step
    of ``b`` lanes takes it (``ops/pallas/decode_attention.
    live_decode_attention`` over the flat rows of the ONE pair a layer owns,
    its block sized by the rows' bytes); None where it reads the leaf of the
    layer's kind whole with the masked einsum. ``decode_impl="auto"`` alone
    chooses, from the platform, the mesh, the dtype and both leaves'
    shapes, as ``gpt.live_read_block`` does for a NeoX block;
    ``step_counters`` and the serving engine count the blocks it reads."""
    if cfg.decode_impl != "auto":
        return None
    from ..ops.pallas import _utils as kernels
    from ..ops.pallas.decode_attention import live_block, live_decode_refusal
    from .gpt import _decode_mesh_refusal
    bc = cfg.block
    rows = (bc.sliding_window, cfg.max_seq_len)
    refusal = live_decode_refusal(b, rows, cfg.num_heads, bc.head_dim,
                                  cfg.dtype, row=bc.row) \
        or _decode_mesh_refusal()
    if not kernels.auto_path("decode_attention", refusal):
        return None
    return live_block(min(rows), bc.row * jnp.dtype(cfg.dtype).itemsize)


def blocks_read(cfg, t, block: int):
    """Blocks of ``block`` rows a live-rows read would take of a lane whose
    next token is at position ``t``, a layer (the mean over the two kinds,
    as :func:`lane_rows`). Works on ints and numpy arrays."""
    _, _, n_sliding, n_full = layer_tables(cfg)
    n_ring, n_glob = live_rows(cfg, t)
    return (n_sliding * -(-n_ring // block)
            + n_full * -(-n_glob // block)) / cfg.num_layers


def routing_counters(cfg, routed, live):
    """moe/grouped.py::routing_counters over what :class:`AfmoeStack` handed
    out: every expert is held here. The tiles are :func:`expert_ffn`'s: its
    tile rows at the call's tokens, through the kernel where its call is."""
    from ..moe.grouped import routing_counters as count, takes_kernel
    choice = routed["expert_choice"]
    tokens = choice.shape[1] * choice.shape[2]
    tile = _tile_rows(cfg, tokens)
    return count(choice, live, expert_offset=0,
                 experts_held=cfg.block.n_routed_experts, tile=tile,
                 kernel=takes_kernel(tokens, cfg.d_model, cfg.block.moe_d_ff,
                                     tile, cfg.dtype))


def step_counters(cfg, positions, live):
    """What ONE decode step of lanes at ``positions [b]`` read, as scalars a
    serving program sums on the device: the ring rows (over the sliding
    layers) and global rows (over the full layers) that were live in the
    lanes that ``live [b]`` says are somebody's, from their positions alone;
    and the rows the step read: both kinds' leaves of EVERY lane whole under
    the masked einsum (it does not know a lane is idle), the live blocks of
    the live lanes where a live-rows read runs (:func:`decode_read_block`)."""
    _, _, n_sliding, n_full = layer_tables(cfg)
    n_ring, n_glob = live_rows(cfg, positions)
    b = positions.shape[0]
    block = decode_read_block(cfg, b)
    if block is None:
        read = jnp.asarray(b * (n_sliding * cfg.block.sliding_window
                                + n_full * cfg.max_seq_len))
    else:
        read = block * jnp.sum(jnp.where(
            live, n_sliding * -(-n_ring // block)
            + n_full * -(-n_glob // block), 0))
    return {
        "kv_window_rows_live": n_sliding * jnp.sum(jnp.where(live, n_ring,
                                                             0)),
        "kv_global_rows_live": n_full * jnp.sum(jnp.where(live, n_glob, 0)),
        "kv_rows_read": read,
    }
