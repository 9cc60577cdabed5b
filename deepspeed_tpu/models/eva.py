"""The EVA block: an exact window beside chunk summaries of everything older.

The third block this repo runs (``GPTConfig.block`` an
:class:`EvaBlockConfig`; ``None`` is ``models/gpt.py``'s GPT-2/NeoX block,
``models/mla.py`` the latent one). A byte-level model's context is long and
its tokens are cheap, so attention keeps the last ``w`` (``window_size``)
tokens exactly and everything older as one summary per ``c``
(``chunk_size``) tokens (Zheng et al., "Efficient Attention via Control
Variates", ICLR 2023, as the released EvaByte code specialises it). With
``RMS(x) = x / sqrt(mean(x^2) + eps) * (1 + g)``, hidden ``x`` in float32
between blocks, token ``t`` (from 0) in window ``W(t) = t // w`` and chunk
``t // c``, chunk ``j`` in window ``(j * c) // w``:

  block     h = x + EVA(RMS_1(x));  y = h + MLP(RMS_2(h))
            MLP(u) = (silu(u W_g) * (u W_u)) W_d.  No bias anywhere.
  project   q_t, k_t = rotary_t(u W_q), rotary_t(u W_k) over the whole head
            (half-split pairs); v_t = u W_v
  summarise per head, with the learned vectors mu and phi of the head's size:
            k~_j = sum_{m in chunk j} softmax_m(mu . k_m) k_m
            v~_j = sum_{m in chunk j} softmax_m(phi . k_m) v_m
            each softmax over the tokens of the chunk that exist, float32
  attend    ONE softmax, float32, over (a) the exact tokens of t's own
            window, m with W(m) = W(t) and m <= t, scores q_t . k_m / sqrt(d),
            values v_m, and (b) the summaries of every chunk of an EARLIER
            window, j with (j * c) // w < W(t), scores q_t . k~_j / sqrt(d),
            values v~_j.  o_t = concat_h(P [v ; v~]) W_o
  head      final RMS, then W_head to ``num_pred_heads x vocab`` float32
            logits; head i predicts token t + 1 + i

The windows do not slide: the first token of a window sees itself and the
summaries only, and a chunk of the running window is never seen as a summary.
Under ``w`` tokens of context this is plain causal attention.

**Two kinds of lane state in one cache, under one cursor.** A layer holds
``window_key/value [L, B, w, h, d]``, written at row ``t mod w``, and
``chunk_key/value [L, B, max_seq_len / c, h, d]``, written at row ``t // c``,
beside ``cache_index [L]`` (``[L, B]`` in the serving arena), the position
``t`` of the next token. A lane at position ``t`` holds ``(t mod w) + 1``
live window rows and ``(w / c) * (t // w)`` live summary rows
(:func:`live_rows`); every other row is a previous window's, a previous
occupant's or zero, and is masked:

  * a call that is HANDED the cache (a decode step) writes ``k_t, v_t`` at
    window row ``t mod w``, recomputes the running chunk's summary from its
    (at most ``c``) window rows and writes it at summary row ``t // c`` on
    EVERY step: the row is overwritten until its chunk is full and is masked
    until its window has closed, so no step branches and no lane is treated
    differently at a window edge. It attends over window rows ``<= t mod w``
    and summary rows ``< (w / c) * W(t)``: two prefixes, so where
    :func:`decode_read_block` accepts (a TPU, one device, whole 128-lane
    heads) ONE kernel call a layer reads each lane's live blocks of both
    leaf pairs under one softmax
    (``ops/pallas/decode_attention.live_decode_attention``), and elsewhere
    the masked einsum reads both leaves whole. A cursor at or past
    ``max_seq_len`` is the serving engine's retired-lane sentinel:
    ``max_seq_len mod w`` is row 0 of the window leaf, in range, so both
    writes are sent out of range explicitly and dropped (``gpt._kv_write``)
    and both fills are sent past their leaves (nothing of the lane is read).
  * a call that has no cache, or creates one (prefill), attends window by
    window in blocks of queries, each block against its own window's keys
    and against the summaries, never a ``[T, T]`` matrix. It hands out the
    rows of the window that holds each row's LAST token (``lengths``: a
    padded prompt's padding would otherwise pick the window) and all the
    summaries.

Weights are declared layer-stacked by :class:`EvaStack` and the layers run
under one ``lax.scan`` that carries the four leaves, as PR 25's layer loop
carries ``cached_key``.
"""

from __future__ import annotations

import dataclasses
import math

import flax.linen as nn
import jax
import jax.numpy as jnp

from ..moe.grouped import gated_mlp
from .gpt import _kv_write, _layer_rows
from .mla import _dot, _stacked_normal

f32 = jnp.float32
NEG_INF = -1e10


@dataclasses.dataclass(frozen=True)
class EvaBlockConfig:
    """What the block needs beyond ``GPTConfig`` (which gives ``d_model``,
    ``num_heads``, ``num_layers``, ``d_ff``, ``rotary_base``,
    ``layer_norm_eps`` as the RMS epsilon, ``vocab_size``, ``max_seq_len``)."""
    window_size: int
    chunk_size: int
    num_pred_heads: int = 1
    # prediction heads a call multiplies, of the head kernel's published
    # [d, num_pred_heads * vocab] (head-major). A server that does not draft
    # emits the argmax of head 0 and multiplies that head alone; with more
    # the logits come out [b, s, heads_out, vocab]
    heads_out: int = 1

    def __post_init__(self):
        if self.window_size % self.chunk_size:
            raise ValueError(
                f"chunk_size {self.chunk_size} does not divide window_size "
                f"{self.window_size}: a chunk would lie in two windows")
        if not 1 <= self.heads_out <= self.num_pred_heads:
            raise ValueError(f"heads_out {self.heads_out} is not among the "
                             f"{self.num_pred_heads} prediction heads")

    @property
    def chunks_per_window(self) -> int:
        return self.window_size // self.chunk_size


def summary_rows(cfg) -> int:
    """Rows of a lane's summary leaf: one a chunk of ``max_seq_len``."""
    return cfg.max_seq_len // cfg.block.chunk_size


def lane_rows(cfg) -> int:
    """Rows one lane holds in one layer, window and summary leaf together."""
    return cfg.block.window_size + summary_rows(cfg)


def live_rows(cfg, t):
    """``(window rows, summary rows)`` a lane whose next token is at position
    ``t`` reads in that step (after its own write): ``(t mod w) + 1`` and
    ``(w / c) * (t // w)``. Works on ints, numpy and jax arrays."""
    ec = cfg.block
    return t % ec.window_size + 1, ec.chunks_per_window * (t // ec.window_size)


def rms_norm(x, gain, eps: float):
    """``x / rms(x) * (1 + g)`` in float32 (``norm_add_unit_offset``)."""
    x32 = x.astype(f32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True) + eps)
    return y * (1.0 + gain.astype(f32))


def rotary_half(x, positions, base: float):
    """``[b, s, h, d]`` turned over half-split pairs ``(x_i, x_{i + d/2})``,
    the published layout (``gpt.rotary_embedding`` turns interleaved ones)."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=f32) / half))
    ang = positions[..., None].astype(f32) * freqs          # [b, s, d/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    a, b = x[..., :half].astype(f32), x[..., half:].astype(f32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def summarise(k, v, mu, phi, exists):
    """One summary a chunk: ``k, v [..., c, h, d]`` the chunk's rotated keys
    and its values, ``mu, phi [h, d]``, ``exists [..., c]`` the tokens that
    are there. Returns ``k~, v~ [..., h, d]`` in float32."""
    with jax.named_scope("eva/summarise"):
        k32, gone = k.astype(f32), ~exists[..., None]
        pool_k = jax.nn.softmax(jnp.where(
            gone, NEG_INF, jnp.einsum("...chd,hd->...ch", k32,
                                      mu.astype(f32))), axis=-2)
        pool_v = jax.nn.softmax(jnp.where(
            gone, NEG_INF, jnp.einsum("...chd,hd->...ch", k32,
                                      phi.astype(f32))), axis=-2)
        return (jnp.einsum("...ch,...chd->...hd", pool_k, k32),
                jnp.einsum("...ch,...chd->...hd", pool_v, v.astype(f32)))


def _joint_attention(q, kw, vw, seen_w, ks, vs, seen_s, dtype):
    """ONE float32 softmax of ``q [b, s, h, d]`` over window rows ``kw, vw
    [b, n, h, d]`` where ``seen_w`` and summary rows ``ks, vs [b, m, h, d]``
    where ``seen_s`` (masks broadcast against ``[b, h, s, rows]``). Returns
    ``[b, s, h, d]``."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    sw = jnp.einsum("bqhd,bkhd->bhqk", q, kw,
                    preferred_element_type=f32) * scale
    sw = jnp.where(seen_w, sw, NEG_INF)
    ss = jnp.einsum("bqhd,bkhd->bhqk", q, ks,
                    preferred_element_type=f32) * scale
    ss = jnp.where(seen_s, ss, NEG_INF)
    probs = jax.nn.softmax(jnp.concatenate([sw, ss], axis=-1),
                           axis=-1).astype(dtype)
    n = kw.shape[1]
    out = (jnp.einsum("bhqk,bkhd->bqhd", probs[..., :n], vw,
                      preferred_element_type=f32)
           + jnp.einsum("bhqk,bkhd->bqhd", probs[..., n:], vs,
                        preferred_element_type=f32))
    return out.astype(dtype)


# queries of one block of a prefill's attention: a window's scores are
# [b, h, block, w + summaries] in float32, 268 MB a row of the batch at the
# published widths, where a whole window's would be four times that
_QUERY_BLOCK = 512


def _prefill_attention(cfg, q, k, v, mu, phi, lengths):
    """Attention of a call that has no cache: ``q, k, v [b, T, h, d]``
    (rotated), ``lengths [b]`` the tokens of each row that
    exist. Returns the context ``[b, T, h, d]`` and, for the cache, the
    rows of the window that holds each row's last token ``[b, w, h, d]``
    x 2 and every summary ``[b, chunks, h, d]`` x 2."""
    ec = cfg.block
    w, c = ec.window_size, ec.chunk_size
    b, T, h, d = q.shape
    # the window's extent in this call: w, or a short call's own length
    span = min(w, -(-T // c) * c)
    Tp = -(-T // span) * span
    if Tp != T:
        q, k, v = (jnp.pad(a, ((0, 0), (0, Tp - T), (0, 0), (0, 0)))
                   for a in (q, k, v))
    n_chunks = Tp // c
    exists = (jnp.arange(Tp, dtype=jnp.int32)[None] < lengths[:, None]
              ).reshape(b, n_chunks, c)
    ksum, vsum = summarise(k.reshape(b, n_chunks, c, h, d),
                           v.reshape(b, n_chunks, c, h, d), mu, phi, exists)
    ksum, vsum = ksum.astype(cfg.dtype), vsum.astype(cfg.dtype)

    qb = _QUERY_BLOCK if span % _QUERY_BLOCK == 0 else span

    def block(i):
        t0 = i * qb
        w0 = (t0 // span) * span            # the window's first position
        qi = jax.lax.dynamic_slice_in_dim(q, t0, qb, axis=1)
        kw = jax.lax.dynamic_slice_in_dim(k, w0, span, axis=1)
        vw = jax.lax.dynamic_slice_in_dim(v, w0, span, axis=1)
        seen_w = (w0 + jnp.arange(span, dtype=jnp.int32))[None, :] \
            <= (t0 + jnp.arange(qb, dtype=jnp.int32))[:, None]
        seen_s = jnp.arange(n_chunks, dtype=jnp.int32) < w0 // c
        return _joint_attention(qi, kw, vw, seen_w, ksum, vsum, seen_s,
                                cfg.dtype)

    with jax.named_scope("eva/prefill"):
        ctx = jax.lax.map(block, jnp.arange(Tp // qb, dtype=jnp.int32))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, Tp, h, d)[:, :T]

    # the cache's rows: the window of each row's LAST token, all summaries
    if Tp < w:
        k, v = (jnp.pad(a, ((0, 0), (0, w - Tp), (0, 0), (0, 0)))
                for a in (k, v))
    start = (jnp.maximum(lengths, 1) - 1) // w * w

    def last_window(rows):
        return jax.vmap(lambda a, s: jax.lax.dynamic_slice_in_dim(
            a, s, w, axis=0))(rows, start)

    n_sum = summary_rows(cfg)
    if n_chunks > n_sum:
        raise ValueError(f"{T} tokens exceed max_seq_len {cfg.max_seq_len}")
    pad = ((0, 0), (0, n_sum - n_chunks), (0, 0), (0, 0))
    return ctx, (last_window(k), last_window(v),
                 jnp.pad(ksum, pad), jnp.pad(vsum, pad))


def _decode_attention(cfg, q, k, v, mu, phi, leaves, cur, layer):
    """Attention of a call that was HANDED the cache: one token a lane,
    ``q, k, v [b, 1, h, d]``, ``leaves`` the four layer-stacked leaves,
    ``cur [b]`` each lane's position. Returns the context and the leaves."""
    ec = cfg.block
    w, c = ec.window_size, ec.chunk_size
    wk, wv, ck, cv = leaves
    b = q.shape[0]
    n_sum = ck.shape[2]
    # the retired-lane sentinel: max_seq_len mod w is a row of the window
    # leaf, so a dead lane's rows are sent past both leaves' ends and dropped
    dead = cur >= cfg.max_seq_len
    row = cur % w
    write_row = jnp.where(dead, w, row)
    wk = _kv_write(wk, k.astype(wk.dtype), write_row, layer)
    wv = _kv_write(wv, v.astype(wv.dtype), write_row, layer)
    # the running chunk, from the window rows it has so far (read after
    # write: this token's row is among them)
    lanes = jnp.arange(b, dtype=jnp.int32)[:, None]
    at = (row // c * c)[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    ksum, vsum = summarise(wk[layer, lanes, at], wv[layer, lanes, at], mu,
                           phi, at <= row[:, None])
    chunk = jnp.where(dead, n_sum, cur // c)
    ck = _kv_write(ck, ksum[:, None].astype(ck.dtype), chunk, layer)
    cv = _kv_write(cv, vsum[:, None].astype(cv.dtype), chunk, layer)
    with jax.named_scope("eva/decode"):
        n_win, n_old = live_rows(cfg, cur)
        if decode_read_block(cfg, b) is not None:
            # each lane's live blocks of both leaf pairs, one softmax; a
            # dead lane's fills lie past both leaves (max_seq_len mod w = 0
            # would read as one live window row)
            from ..ops.pallas.decode_attention import live_decode_attention
            ctx = live_decode_attention(
                q, [(wk, wv, jnp.where(dead, w + 1, n_win)),
                    (ck, cv, jnp.where(dead, n_sum + 1, n_old))], layer)
            return ctx, (wk, wv, ck, cv)
        seen_w = jnp.arange(w, dtype=jnp.int32) < n_win[:, None, None, None]
        seen_s = jnp.arange(n_sum, dtype=jnp.int32) \
            < n_old[:, None, None, None]
        ctx = _joint_attention(
            q, _layer_rows(wk, layer), _layer_rows(wv, layer), seen_w,
            _layer_rows(ck, layer), _layer_rows(cv, layer), seen_s,
            cfg.dtype)
    return ctx, (wk, wv, ck, cv)


def eva_block(cfg, p, x, positions, lengths, leaves, cur, layer):
    """One block over one layer's weights ``p``; ``x`` float32. ``leaves``
    None: the call has no cache to read; returns ``(y, rows for a cache)``.
    Otherwise the carried leaves; returns ``(y, leaves)``."""
    b, s, _ = x.shape
    h, d = cfg.num_heads, cfg.d_model // cfg.num_heads
    eps = cfg.layer_norm_eps
    u = rms_norm(x, p["ln_1"], eps).astype(cfg.dtype)
    q = rotary_half(_dot(u, p["q_proj"]).reshape(b, s, h, d), positions,
                    cfg.rotary_base)
    k = rotary_half(_dot(u, p["k_proj"]).reshape(b, s, h, d), positions,
                    cfg.rotary_base)
    v = _dot(u, p["v_proj"]).reshape(b, s, h, d)
    mu, phi = p["adaptive_mu_k"], p["adaptive_phi"]
    if leaves is None:
        ctx, state = _prefill_attention(cfg, q, k, v, mu, phi, lengths)
    else:
        ctx, state = _decode_attention(cfg, q, k, v, mu, phi, leaves, cur,
                                       layer)
    # the branches' products stay float32 into the float32 stream
    hid = x + jnp.dot(ctx.reshape(b, s, h * d), p["o_proj"],
                      preferred_element_type=f32)
    y = hid + gated_mlp(rms_norm(hid, p["ln_2"], eps).astype(cfg.dtype),
                        p["gate_proj"], p["up_proj"], p["down_proj"])
    return y, state


def _pooling_vectors(key, shape, dtype):
    """``normal / sqrt(d)``: pooling logits of order 1 against keys of unit
    entries, as a trained model's are."""
    return (jax.random.normal(key, shape, f32)
            / math.sqrt(shape[-1])).astype(dtype)


_LEAVES = ("window_key", "window_value", "chunk_key", "chunk_value")


class EvaStack(nn.Module):
    """``num_layers`` blocks under one ``lax.scan`` over their layer-stacked
    weights. ``lengths [b]``: the tokens of each row that exist, for a call
    that creates a cache from padded rows (None: all of them)."""
    cfg: object

    @nn.compact
    def __call__(self, x, positions, lengths=None):
        cfg, ec = self.cfg, self.cfg.block
        b, s, d = x.shape
        L, h, f = cfg.num_layers, cfg.num_heads, cfg.d_ff
        dh, w = d // h, ec.window_size
        if cfg.max_seq_len % w:
            raise ValueError(f"window_size {w} does not divide max_seq_len "
                             f"{cfg.max_seq_len}")

        def param(name, init, *shape):
            return self.param(name, init, (L,) + shape, cfg.param_dtype)

        p = {name: param(name, nn.initializers.zeros, d)
             for name in ("ln_1", "ln_2")}
        for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
            p[name] = param(name, _stacked_normal, d, d)
        for name in ("adaptive_mu_k", "adaptive_phi"):
            p[name] = param(name, _pooling_vectors, h, dh)
        p["gate_proj"] = param("gate_proj", _stacked_normal, d, f)
        p["up_proj"] = param("up_proj", _stacked_normal, d, f)
        p["down_proj"] = param("down_proj", _stacked_normal, f, d)

        # prefill attends over its own tokens and decode over the cache
        # because the call has a cache or has not, as GPT's own loop decides
        handed = self.has_variable("cache", _LEAVES[0])
        caching = handed or (not self.is_initializing()
                             and self.is_mutable_collection("cache"))
        if caching:
            n_sum = summary_rows(cfg)
            held = [self.variable("cache", name, jnp.zeros,
                                  (L, b, rows, h, dh), cfg.dtype)
                    for name, rows in zip(_LEAVES, (w, w, n_sum, n_sum))]
            idx = self.variable("cache", "cache_index", jnp.zeros, (L,),
                                jnp.int32)
        positions = jnp.broadcast_to(positions, (b, s))
        x = x.astype(f32)
        layers = jnp.arange(L, dtype=jnp.int32)
        if handed:
            if s != 1:
                raise NotImplementedError(
                    f"a call that is handed the window and summary leaves "
                    f"writes one token a lane; {s} tokens could cross a "
                    f"window edge inside the call (the speculative and "
                    f"fused-prefill widths are not built for this block)")
            cur = idx.value

            def body(carry, at):
                x, leaves = carry
                pl, i = at
                lane_cur = jnp.broadcast_to(_layer_rows(cur, i), (b,))
                x, leaves = eva_block(cfg, pl, x, positions, None, leaves,
                                      lane_cur, i)
                return (x, leaves), None

            (x, leaves), _ = jax.lax.scan(
                body, (x, tuple(v.value for v in held)), (p, layers),
                unroll=cfg.scan_unroll)
        else:
            if lengths is None:
                lengths = jnp.full((b,), s, jnp.int32)

            def body(x, pl):
                x, state = eva_block(cfg, pl, x, positions, lengths, None,
                                     None, None)
                return x, (state if caching else None)

            x, leaves = jax.lax.scan(body, x, p, unroll=cfg.scan_unroll)
        if caching:
            for var, leaf in zip(held, leaves):
                var.value = leaf
            idx.value = idx.value + s
        return x, None


class FinalNorm(nn.Module):
    cfg: object

    @nn.compact
    def __call__(self, x):
        gain = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                          self.cfg.param_dtype)
        return rms_norm(x, gain, self.cfg.layer_norm_eps
                        ).astype(self.cfg.dtype)


class Head(nn.Module):
    """``num_pred_heads x vocab`` float32 logits (``fp32_logits``), the
    kernel at its published ``[d, heads * vocab]``, head-major; a call
    multiplies the first ``heads_out`` heads."""
    cfg: object

    @nn.compact
    def __call__(self, x):
        cfg, ec = self.cfg, self.cfg.block
        kernel = self.param(
            "kernel", _stacked_normal,
            (x.shape[-1], ec.num_pred_heads * cfg.vocab_size),
            cfg.param_dtype)
        logits = jnp.dot(x, kernel[:, :ec.heads_out * cfg.vocab_size],
                         preferred_element_type=f32)
        if ec.heads_out == 1:
            return logits
        return logits.reshape(x.shape[:-1] + (ec.heads_out, cfg.vocab_size))


# ---- what models/gpt.py asks of a block kind's module ---------------------
Stack = EvaStack
PREFILL_TAKES_LENGTHS = True


def decode_read_block(cfg, b: int):
    """Rows a block of the live-rows decode read carries, where a decode
    step of ``b`` lanes takes it
    (ops/pallas/decode_attention.live_decode_attention over the two leaf
    pairs: each lane's live window blocks, then its live summary blocks);
    None where it reads both leaves of every lane whole with the masked
    einsum. The question ``gpt.live_read_block`` asks, of both leaves'
    shapes: ``decode_impl="auto"`` alone chooses, from the platform, the
    mesh, the dtype and the shapes."""
    if cfg.decode_impl != "auto":
        return None
    from ..ops.pallas import _utils as kernels
    from ..ops.pallas.decode_attention import live_block, live_decode_refusal
    from .gpt import _decode_mesh_refusal
    rows = (cfg.block.window_size, summary_rows(cfg))
    refusal = live_decode_refusal(b, rows, cfg.num_heads, cfg.head_dim,
                                  cfg.dtype) or _decode_mesh_refusal()
    if not kernels.auto_path("decode_attention", refusal):
        return None
    return live_block(min(rows))


def blocks_read(cfg, t, block: int):
    """Blocks of ``block`` rows the live-rows read takes of a lane whose
    next token is at position ``t``: its live window rows and its live
    summary rows, each rounded up to whole blocks. Works on ints, numpy and
    jax arrays."""
    n_win, n_old = live_rows(cfg, t)
    return -(-n_win // block) + -(-n_old // block)


def step_counters(cfg, positions, live):
    """What ONE decode step of lanes at ``positions [b]`` read, as scalars a
    serving program sums on the device: the window and summary rows that were
    live in the lanes that ``live [b]`` says are somebody's; the rows the
    step read (the live blocks of those lanes where the live-rows read runs,
    :func:`decode_read_block`; else both leaves of EVERY lane: the masked
    einsum does not know a lane is idle); the lanes that wrote a window's
    last row."""
    n_win, n_old = live_rows(cfg, positions)
    w = cfg.block.window_size
    b = positions.shape[0]
    block = decode_read_block(cfg, b)
    if block is None:
        read = jnp.asarray(b * lane_rows(cfg))
    else:
        read = block * jnp.sum(
            jnp.where(live, blocks_read(cfg, positions, block), 0))
    return {
        "eva_window_rows_live": jnp.sum(jnp.where(live, n_win, 0)),
        "eva_summary_rows_live": jnp.sum(jnp.where(live, n_old, 0)),
        "eva_rows_read": read,
        "eva_windows_closed": jnp.sum(live & (n_win == w)),
    }
