"""Architecture ``evabyte`` (EvaByte 6.5B, ``attention_class`` ``eva``), for the
chip benchmark: its plain float32 reference, its counts from shapes, the
lengths its check needs, and the mapping from the published ``config.json``
keys to the program's model.

An architecture file is what a driver asks of a configuration that names it
(``"arch": "<this file's stem>"``). ``drivers/serve_closed_arch.py`` asks

  ``build_model(config)``            -> the program's flax module
  ``init_params(model, key)``        -> the seeded weights
  ``param_count(config)``            parameters on this chip, from shapes
  ``reference_logits(config, params, ids, lower=None)``
                                     -> float32 logits, a report (or None)
  ``LOGIT_ATOL``, ``TOKEN_GAP_ATOL`` the comparison's limits, with reasons

and ``drivers/serve_closed_long.py``, for an architecture whose mechanism
only shows at lengths the first driver's 5-40 token check never reaches,
besides ``check_lengths(config)``: groups of prompt lengths, each group
prefilled together. The readers of the cell's own metrics ask
``decode_step_bytes``. **A third architecture comes in the same way:** a file
here with these names, a configuration that names it, a mix whose ``kind`` is
the driver that suits its check (``serve_closed_arch`` if 5-40 tokens hold it
to its reference, ``serve_closed_long`` with ``check_lengths`` if not), the
readers of its own metrics under ``layer_metrics/``, and entries appended to
``BENCHMARK.json``; nothing that exists is edited.

Everything but ``build_model`` and ``init_params`` is independent of
``deepspeed_tpu``: plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no cache, no kernel, a Python
loop over the layers, the rows and the windows, over the parameter tree the
program serves. One layer is upcast at a time and one window of one row
attends at a time (its scores are ``[32, 2048, 2048 + summaries]`` float32,
0.6 GB at the published widths), so the reference fits beside the served
bfloat16 copy.

The equations (``config`` keys in brackets). ``RMS(x) = x / sqrt(mean(x^2) +
[rms_norm_eps]) * (1 + g)`` [norm_add_unit_offset]; hidden ``x`` in float32
between blocks [fp32_skip_add]; ``w`` = [window_size], ``c`` = [chunk_size],
``d`` = [hidden_size] / [num_attention_heads]; token ``t`` (from 0) lies in
window ``W(t) = t // w`` and chunk ``t // c``; chunk ``j`` in window
``(j * c) // w``.

  block     h = x + EVA(RMS_1(x)); y = h + MLP(RMS_2(h)); MLP(u) = (silu(u
            W_g) * (u W_u)) W_d of width [intermediate_size]. No bias.
  project   q_t, k_t = rotary_t(u W_q), rotary_t(u W_k) over the whole head,
            base [rope_theta], half-split pairs (x_i, x_{i + d/2}); v_t = u
            W_v; [num_attention_heads] heads and as many KV heads.
  summarise per head, with the learned vectors mu [adaptive_mu_k] and phi
            [adaptive_phi] of d: k~_j = sum_{m in chunk j} softmax_m(mu . k_m)
            k_m and v~_j = sum_{m in chunk j} softmax_m(phi . k_m) v_m, each
            softmax over the (up to c) tokens of the chunk that exist.
  attend    ONE softmax in float32 [mixedp_attn] over (a) the exact tokens
            of t's own window, m with W(m) = W(t) and m <= t, scores q_t . k_m
            / sqrt(d), values v_m; (b) the summaries of every chunk of an
            EARLIER window, j with (j * c) // w < W(t), scores q_t . k~_j /
            sqrt(d), values v~_j. o_t = concat_h(P [v ; v~]) W_o. The
            windows do not slide; a chunk of the running window is never seen
            as a summary; under w tokens this is plain causal attention.
  head      final RMS, then W_head to [num_pred_heads] x [vocab_size] logits
            in float32 [fp32_logits]; head i predicts byte t + 1 + i.

Assumed (the config fixes none of these; the configuration file lists them
and program and reference follow them alike): the pooling logits ``mu . k_m``
and ``phi . k_m`` are unscaled and taken on the ROTATED keys; the head's
outputs are head-major (``[heads, vocab]``); the seeded weights
(:func:`init_params`). Departures of the program, stated in the
configuration file: the serving path multiplies head 0 alone; where bfloat16
results are rounded.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32

# bf16 compute against this float32 reference on the same bf16-rounded
# weights, through drivers/serve_closed_long.py over check_lengths' 11 prompts
# (prefill + four decode steps through the cache, on head 0's logits, which
# span about +-4.4). Two readings of ONE comparison on the v5e at the
# published widths place the limit (my chip runs, PR 30; PERF.md section 6):
# the program's largest |logit difference| was 0.0203-0.0262 in 15 seeds
# (by group of lengths at most 0.0262 at 5-40, 0.0229 at 2044-2048, 0.0168 at
# 4107, 0.0196 at 6013: the long prompts read no further than the short);
# the CONTROL, this reference with every matmul's operands rounded to
# float8_e4m3, the nearest precision below the configuration's bfloat16, in
# the program's place (the driver's ``--control``), read 0.8986, 1.0276 and
# 1.1057 in three seeds (no group under 0.52) and came out as not correct.
# 0.12 is 4.6 times the first and a seventh of the second. Summaries seen one window early, mu and
# phi swapped, a dropped unit offset or another rotary base move logits by
# more already at toy widths (tests/benchmark/test_arch_evabyte.py).
LOGIT_ATOL = 0.12
# a greedy token is the argmax of the server's own bf16 logits; under the
# reference it can trail the reference's argmax by the error on two logits.
# Found (my chip runs, PR 30): 0.0000-0.0120 over the 11 prompts through the
# real server in 15 runs
TOKEN_GAP_ATOL = 2 * LOGIT_ATOL
# one key and one value over every head, bfloat16: a row of either leaf
BYTES_PER_EL = 2


# ------------------------------------------------------------------ counts
def _shape(config: Dict[str, Any]) -> Dict[str, int]:
    h = config["num_attention_heads"]
    if config["num_key_value_heads"] != h:
        raise ValueError("the eva block has as many KV heads as heads")
    return dict(
        d=config["hidden_size"], h=h, dh=config["hidden_size"] // h,
        f=config["intermediate_size"], layers=config["num_hidden_layers"],
        vocab=config["vocab_size"], heads=config["num_pred_heads"],
        w=config["window_size"], c=config["chunk_size"],
        positions=config["max_position_embeddings"])


def attention_params(config) -> int:
    """W_q, W_k, W_v, W_o, and mu and phi of every head."""
    z = _shape(config)
    return 4 * z["d"] * z["d"] + 2 * z["h"] * z["dh"]


def mlp_params(config) -> int:
    z = _shape(config)
    return 3 * z["d"] * z["f"]


def layer_params(config) -> int:
    """Attention, MLP and the two norms."""
    return attention_params(config) + mlp_params(config) \
        + 2 * _shape(config)["d"]


def param_count(config) -> int:
    """The layers, the embedding, the head of every prediction head and the
    final norm."""
    z = _shape(config)
    return (z["layers"] * layer_params(config) + z["vocab"] * z["d"]
            + z["d"] * z["heads"] * z["vocab"] + z["d"])


def row_bytes(config) -> int:
    """One row of a lane's window or summary leaf in one layer: a key and a
    value over every head."""
    z = _shape(config)
    return 2 * z["h"] * z["dh"] * BYTES_PER_EL


def live_rows(config, t):
    """``(window rows, summary rows)`` live in a lane whose next token is at
    position ``t``: ``(t mod w) + 1`` and ``(w / c) * (t // w)``."""
    z = _shape(config)
    return t % z["w"] + 1, (z["w"] // z["c"]) * (t // z["w"])


def decode_step_bytes(config, live_window_rows: float,
                      live_summary_rows: float) -> float:
    """Bytes one decode step MUST read: every matmul weight of the layers
    (with mu and phi), head 0's columns of the head (the one the serving
    path multiplies; the embedding is a gather of a few rows), and the LIVE
    rows of both leaves, ``live_*_rows`` summed over the step's lanes, in
    every layer. A step that reads both leaves whole reads more and cannot
    pass 100 % for it."""
    z = _shape(config)
    weights = z["layers"] * (attention_params(config) + mlp_params(config)) \
        + z["d"] * z["vocab"]
    return (weights * BYTES_PER_EL
            + (live_window_rows + live_summary_rows) * z["layers"]
            * row_bytes(config))


def check_lengths(config) -> List[List[int]]:
    """The check's prompt lengths, in groups that are prefilled together
    (padded to the group's longest, as the server pads to a bucket): four
    inside the first window, where the block is plain causal attention;
    ``w - 4 .. w``, whose four decode steps cross a window edge through the
    cache (and ``w`` itself: a prompt that ends ON the edge); one past two
    windows with a length that is no multiple of the chunk (two windows of
    summaries and a partial chunk); one most of the way through the third.
    At the published sizes 5-40, 2044-2048, 4107 and 6013."""
    z = _shape(config)
    w, c = z["w"], z["c"]
    short = sorted({int(n) for n in np.linspace(min(5, w // 3),
                                                min(40, w - 2), 4)})
    return [short, list(range(w - 4, w + 1)), [2 * w + c // 2 + 3],
            [2 * w + 15 * w // 16 - 3]]


# ------------------------------------------------------- the program's model
def build_model(config: Dict[str, Any]):
    """The program's module for this configuration (with ``init_params`` the
    one use of ``deepspeed_tpu`` in this file): published keys onto
    ``GPTConfig`` and ``EvaBlockConfig``, then the file's own ``model``
    group (dtypes; ``heads_out`` where a test asks every head)."""
    from deepspeed_tpu.models.eva import EvaBlockConfig
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    z = _shape(config)
    kw = dict(config.get("model", {}))
    block = EvaBlockConfig(
        window_size=z["w"], chunk_size=z["c"], num_pred_heads=z["heads"],
        heads_out=kw.pop("heads_out", 1))
    kw = dict(dict(
        d_model=z["d"], num_heads=z["h"], num_layers=z["layers"],
        d_ff=z["f"], vocab_size=z["vocab"], max_seq_len=z["positions"],
        rotary=True, rotary_base=float(config["rope_theta"]),
        tie_embeddings=config["tie_word_embeddings"],
        layer_norm_eps=config["rms_norm_eps"], block=block), **kw)
    for key in ("dtype", "param_dtype"):
        if isinstance(kw.get(key), str):
            kw[key] = jnp.dtype(kw[key]).type
    return GPT(GPTConfig(**kw))


GAIN_STD = 0.1


def init_params(model, key):
    """The seeded weights, as the configuration file's ``assumed`` has them:
    the program's own initialisation (every matrix ``normal / sqrt(fan_in)``,
    mu and phi ``normal / sqrt(d)`` so that the pooling logits are of order
    1, as a trained model's are: at the config's ``init_std`` of 0.01275 the
    pooling is a plain mean and no comparison could tell mu from phi or
    either from nothing), every gain ``g`` drawn ``0.1 x normal`` (the
    program starts them at zero, where a dropped unit offset would show and
    a dropped gain would not), and the embedding's rows ``normal`` of unit
    RMS: a lookup has no fan-in, and rows of RMS ``1 / sqrt(d)`` would be a
    hundredth of the first branch, every token's keys alike (PERF.md
    section 6, PR 26, met the same in another block)."""
    params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.fold_in(key, 0x6A1), 4))

    def drawn(leaf, std):
        return (std * jax.random.normal(next(keys), leaf.shape, f32)
                ).astype(leaf.dtype)

    blocks = dict(params["blocks"])
    for name in ("ln_1", "ln_2"):
        blocks[name] = drawn(blocks[name], GAIN_STD)
    return {**params, "blocks": blocks,
            "ln_f": {"scale": drawn(params["ln_f"]["scale"], GAIN_STD)},
            "wte": {"embedding": drawn(params["wte"]["embedding"], 1.0)}}


# ------------------------------------------------------------ the reference
def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * (1.0 + g)


def _rotary(x, positions, base: float):
    """``[S, H, D]``: the pairs ``(x_i, x_{i + D/2})`` turned by position x
    frequency."""
    half = x.shape[-1] // 2
    freqs = 1.0 / (base ** (jnp.arange(half, dtype=f32) / half))
    ang = positions[:, None].astype(f32) * freqs            # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _round(x, lower):
    """``lower`` None: float32 as it is. Else the operand rounded to that
    dtype (float8_e4m3fn: the nearest precision below the configuration's
    bfloat16), for the reading that places ``LOGIT_ATOL``."""
    return x if lower is None else x.astype(lower).astype(f32)


def _mm(x, w, lower):
    return _round(x, lower) @ _round(w, lower)


@partial(jax.jit, static_argnames=("h", "base", "eps", "lower"))
def _project(x, p, *, h, base, eps, lower):
    """One row's ``q, k, v [S, h, d]`` from its hidden ``x [S, D]``."""
    with jax.default_matmul_precision("highest"):
        s = x.shape[0]
        u = _rms(x, p["ln_1"].astype(f32), eps)
        positions = jnp.arange(s)
        q = _rotary(_mm(u, p["q_proj"].astype(f32), lower).reshape(s, h, -1),
                    positions, base)
        k = _rotary(_mm(u, p["k_proj"].astype(f32), lower).reshape(s, h, -1),
                    positions, base)
        v = _mm(u, p["v_proj"].astype(f32), lower).reshape(s, h, -1)
        return q, k, v


@partial(jax.jit, static_argnames=("c", "lower"))
def _summaries(k, v, mu, phi, *, c, lower):
    """The ``k~, v~ [chunks, h, d]`` of a CLOSED window from its tokens'
    ``k, v [w, h, d]``: every chunk of it is whole."""
    with jax.default_matmul_precision("highest"):
        kr = _round(k, lower).reshape((-1, c) + k.shape[1:])
        vr = _round(v, lower).reshape(kr.shape)
        pool_k = jax.nn.softmax(
            jnp.einsum("jnhd,hd->jnh", kr, _round(mu.astype(f32), lower)), 1)
        pool_v = jax.nn.softmax(
            jnp.einsum("jnhd,hd->jnh", kr, _round(phi.astype(f32), lower)), 1)
        return (jnp.einsum("jnh,jnhd->jhd", _round(pool_k, lower), kr),
                jnp.einsum("jnh,jnhd->jhd", _round(pool_v, lower), vr))


@partial(jax.jit, static_argnames=("lower",))
def _attend(q, k, v, ksum, vsum, *, lower):
    """One window of one row: its queries ``q [n, h, d]`` over its own keys
    ``k, v [n, h, d]``, causally, and over the summaries of the windows
    before it ``ksum, vsum [m, h, d]`` (m may be 0), in ONE softmax."""
    with jax.default_matmul_precision("highest"):
        n, _, d = q.shape
        qr = _round(q, lower)
        own = jnp.einsum("qhd,khd->hqk", qr, _round(k, lower)) / math.sqrt(d)
        own = jnp.where(jnp.tril(jnp.ones((n, n), bool))[None], own,
                        -jnp.inf)
        old = jnp.einsum("qhd,khd->hqk", qr, _round(ksum, lower)) \
            / math.sqrt(d)
        probs = _round(jax.nn.softmax(jnp.concatenate([own, old], -1), -1),
                       lower)
        return (jnp.einsum("hqk,khd->qhd", probs[..., :n], _round(v, lower))
                + jnp.einsum("hqk,khd->qhd", probs[..., n:],
                             _round(vsum, lower)))


@partial(jax.jit, static_argnames=("eps", "lower"))
def _close(x, ctx, p, *, eps, lower):
    """h = x + ctx W_o; y = h + MLP(RMS_2(h))."""
    with jax.default_matmul_precision("highest"):
        hid = x + _mm(ctx.reshape(ctx.shape[0], -1), p["o_proj"].astype(f32),
                      lower)
        u = _rms(hid, p["ln_2"].astype(f32), eps)
        g = _mm(u, p["gate_proj"].astype(f32), lower)
        up = _mm(u, p["up_proj"].astype(f32), lower)
        return hid + _mm(jax.nn.silu(g) * up, p["down_proj"].astype(f32),
                         lower)


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, gain, kernel, *, eps, lower):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(x, gain.astype(f32), eps), kernel.astype(f32), lower)


def _eva_row(q, k, v, mu, phi, w: int, c: int, lower):
    """One row's context ``[S, h, d]``: a loop over its windows, each over
    its own tokens and the summaries of the chunks of the windows before."""
    s = q.shape[0]
    ksum = jnp.zeros((0,) + k.shape[1:], f32)
    vsum = jnp.zeros((0,) + v.shape[1:], f32)
    out = []
    for w0 in range(0, s, w):
        w1 = min(w0 + w, s)
        out.append(_attend(q[w0:w1], k[w0:w1], v[w0:w1], ksum, vsum,
                           lower=lower))
        if w1 < s:      # this window has closed: its chunks become summaries
            ks, vs = _summaries(k[w0:w1], v[w0:w1], mu, phi, c=c, lower=lower)
            ksum = jnp.concatenate([ksum, ks])
            vsum = jnp.concatenate([vsum, vs])
    return jnp.concatenate(out)


def reference_logits(config: Dict[str, Any], params, input_ids, lower=None):
    """``[B, S]`` ids -> (``[B, S, num_pred_heads, vocab]`` float32 logits,
    None: this block routes nothing). ``params`` is the tree the program
    serves (``wte``, ``blocks`` of layer-stacked leaves, ``ln_f``,
    ``lm_head``)."""
    z = _shape(config)
    eps, base = config["rms_norm_eps"], float(config["rope_theta"])
    ids = np.asarray(input_ids)
    rows = [jnp.take(params["wte"]["embedding"], jnp.asarray(r), axis=0
                     ).astype(f32) for r in ids]
    for i in range(z["layers"]):
        p = {k: v[i] for k, v in params["blocks"].items()}
        for b, x in enumerate(rows):
            q, k, v = _project(x, p, h=z["h"], base=base, eps=eps,
                               lower=lower)
            ctx = _eva_row(q, k, v, p["adaptive_mu_k"], p["adaptive_phi"],
                           z["w"], z["c"], lower)
            rows[b] = _close(x, ctx, p, eps=eps, lower=lower)
    logits = jnp.stack([
        _head(x, params["ln_f"]["scale"], params["lm_head"]["kernel"],
              eps=eps, lower=lower) for x in rows])
    return logits.reshape(ids.shape + (z["heads"], z["vocab"])), None
