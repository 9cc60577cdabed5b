"""Architecture ``afmoe`` (Trinity-Mini, 26B-A3B), for the chip benchmark: its
plain float32 reference, its counts from shapes, the check's prompt lengths,
and the mapping from the published ``config.json`` keys to the program's
model. What a driver asks of an arch file is listed in
``docs/latent_moe_block.md``; this one answers ``check_lengths`` AND takes
the program's routing (``drivers/serve_closed_long_routed.py``).

Everything but ``build_model`` and ``init_params`` is independent of
``deepspeed_tpu``: plain ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``, no cache, no ring, no grouping
(a loop over the 128 experts), a dense band mask for the sliding layers, the
key heads repeated, over the parameter tree the program serves. One layer,
and inside an expert layer one expert, is upcast at a time, and attention
runs a block of queries at a time, so that the reference fits beside the
served bfloat16 copy at the published widths; the head is multiplied over
the rows a caller asks for (200,192 logits a position).

The equations (``config`` keys in brackets; (assumed) marks what the config
does not bear out, listed under ``assumed`` in the configuration file),
``RMS(x) = x / sqrt(mean(x^2) + rms_norm_eps) * g``:

  embed   x = E[id] * sqrt(hidden_size) [mup_enabled] (the multiplier:
          assumed)
  block   h = x + RMS_post_attn(Attn(RMS_in(x)));
          y = h + RMS_post_mlp(FFN(RMS_pre_mlp(h)))      (four norms: assumed)
  Attn    q = x W_q [num_attention_heads x head_dim], k = x W_k, v = x W_v
          [num_key_value_heads], g = x W_gate (assumed); q, k <- RMS over
          each head's features with a learned gain (assumed). A
          sliding_attention layer [layer_types] turns q and k by rotary over
          half-split pairs, base [rope_theta], and query i sees keys j with
          0 <= i - j < [sliding_window]; a full_attention layer applies NO
          positional encoding and sees every j <= i (assumed). Query head n
          reads key head n // (heads / key heads). softmax(q.k /
          sqrt(head_dim)) in float32; o = (concat_h(P v) * sigmoid(g)) W_o.
  FFN     the first [num_dense_layers] layers: (silu(x W_g) * (x W_u)) W_d,
          width [intermediate_size]. The rest: s = sigmoid(x W_r) in float32
          over [num_experts] [score_func]; the [num_experts_per_tok] largest
          of s + b, b a per-expert selection bias that does not enter the
          weights (assumed); w_i = [route_scale] * s_i / (sum of the chosen
          s + 1e-20) [route_norm]; FFN(x) = Shared(x) + sum w_i E_i(x), all
          gated SiLU MLPs of width [moe_intermediate_size],
          [num_shared_experts] shared. No token is dropped.
  head    final RMSNorm, an untied head over [vocab_size].

**Routing under rounding** is ``archs/pangu_ultra_moe.py``'s rule, on the
BIASED scores the choice is made of: ``reference_logits`` is handed the
experts the program chose and follows a set that differs from its own only
where the biased scores of the experts displaced and of those taken instead
differ by less than ``ROUTE_EPS``; it reports the sets that differed.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32
BYTES_PER_EL = 2
FULL = "full_attention"

# bf16 compute against this float32 reference on the same bf16-rounded
# weights, through drivers/serve_closed_long_routed.py over check_lengths'
# three groups (short, across the window's edge, past two windows), on
# logits that span about +-5.4 over 200,192 entries. Two readings of ONE
# comparison on the v5e at the published widths place the limit (my chip
# runs, PR 32; PERF.md section 6): the program's largest |logit difference|
# was 0.0346-0.0379 over ten seeds (by group 0.0346-0.0379, 0.0342-0.0361,
# 0.0317-0.0355: the long groups no worse than the short one); the CONTROL,
# this reference with every matmul's operands rounded to float8_e4m3, the
# nearest precision below the configuration's bfloat16, in the program's
# place (the driver's ``--control``), read 1.8149 (by group 0.7339, 1.4842,
# 1.8149) and came out as not correct. 0.2 is 5.3 times the first's largest and a
# quarter of the control's smallest group. A window off by one, rotary on
# the full layer, an unscaled embedding, a gate or a norm gain left out, a
# bias that enters the weights move a logit by more already at toy widths
# (tests/benchmark/test_arch_afmoe.py).
LOGIT_ATOL = 0.2
# a greedy token is the argmax of the server's own bf16 logits; under the
# reference it can trail the reference's argmax by the error on two logits
TOKEN_GAP_ATOL = 2 * LOGIT_ATOL
# Scores are sigmoid(z), z = x W_r with x of unit RMS and columns of W_r of
# unit norm, so z ~ N(0, 1); among 128 scores the 8th and 9th lie some 0.01
# apart. The router's input is a bfloat16 activation whose rounding moves z
# by a few thousandths, and d(sigmoid) <= 1/4 (archs/pangu_ultra_moe.py has
# the reckoning). Found on the v5e (my chip runs, PR 32, seven seeds): 4.06-
# 4.25 % of the 90,736 (token, expert layer) sets differ from the
# reference's own and the largest difference of a displaced expert's biased
# score from that of the one taken instead was 0.0037-0.0049. 0.015 is three
# times that. What it refuses: a router fed int8 or fp8 activations, another
# router matrix, a selection bias left out (the seeded bias moves a score by
# 0.02 on average).
ROUTE_EPS = 0.015


# ------------------------------------------------------------------ counts
def _shape(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes as they are run."""
    layers = config["num_hidden_layers"]
    types = list(config["layer_types"])
    if len(types) != layers:
        raise ValueError(f"{len(types)} layer_types for {layers} layers")
    return dict(
        d=config["hidden_size"], h=config["num_attention_heads"],
        hk=config["num_key_value_heads"], dh=config["head_dim"],
        f_dense=config["intermediate_size"],
        f=config["moe_intermediate_size"], layers=layers,
        dense=min(config["num_dense_layers"], layers),
        experts=config["num_experts"], k=config["num_experts_per_tok"],
        shared=config["num_shared_experts"], vocab=config["vocab_size"],
        w=config["sliding_window"], types=types,
        positions=config["max_position_embeddings"],
        n_full=sum(t == FULL for t in types),
        n_sliding=sum(t != FULL for t in types))


def attention_params(config) -> int:
    """W_q, W_gate, W_o, W_k, W_v and the two head norms' gains."""
    z = _shape(config)
    return (3 * z["d"] * z["h"] * z["dh"] + 2 * z["d"] * z["hk"] * z["dh"]
            + 2 * z["dh"])


def expert_params(config) -> int:
    """One routed expert: gate, up, down."""
    z = _shape(config)
    return 3 * z["d"] * z["f"]


def dense_layer_params(config) -> int:
    z = _shape(config)
    return attention_params(config) + 4 * z["d"] + 3 * z["d"] * z["f_dense"]


def expert_layer_params(config) -> int:
    """Attention, four norms, every routed expert, the shared expert(s), the
    router and the selection bias."""
    z = _shape(config)
    return (attention_params(config) + 4 * z["d"]
            + (z["experts"] + z["shared"]) * expert_params(config)
            + z["d"] * z["experts"] + z["experts"])


def param_count(config) -> int:
    z = _shape(config)
    return (z["dense"] * dense_layer_params(config)
            + (z["layers"] - z["dense"]) * expert_layer_params(config)
            + 2 * z["vocab"] * z["d"] + z["d"])


def row_bytes(config) -> int:
    """A key and a value of one position in one layer."""
    z = _shape(config)
    return 2 * z["hk"] * z["dh"] * BYTES_PER_EL


def lane_bytes(config) -> int:
    """One lane's rows through every layer: the window in a sliding layer,
    every position in a full one."""
    z = _shape(config)
    return (z["n_sliding"] * z["w"] + z["n_full"] * z["positions"]) \
        * row_bytes(config)


def live_rows(config, t):
    """``(ring rows, global rows)`` live in ONE sliding and ONE full layer
    of a lane whose next token is at position ``t``: ``min(t + 1, w)`` and
    ``t + 1``."""
    return np.minimum(t + 1, _shape(config)["w"]), t + 1


def decode_step_bytes(config, live_window_rows: float,
                      live_global_rows: float,
                      experts_touched_per_layer: float) -> float:
    """Bytes one decode step MUST read: every matmul weight outside the
    routed experts (attention, the dense FFN, the shared experts, the
    routers, the head ONCE; the input embedding is a gather of a few rows),
    the routed experts that a token of this step TOUCHED (a layer that skips
    idle experts reads no more, so the share cannot pass 100 % by skipping),
    and the LIVE rows of both kinds of leaf, ``live_*_rows`` summed over the
    step's lanes and over the layers of the kind (what the program's
    counters sum). A step that reads both leaves whole reads more and cannot
    pass 100 % for it."""
    z = _shape(config)
    n_sparse = z["layers"] - z["dense"]
    fixed = (z["layers"] * (attention_params(config) - 2 * z["dh"])
             + z["dense"] * 3 * z["d"] * z["f_dense"]
             + n_sparse * (z["shared"] * expert_params(config)
                           + z["d"] * z["experts"])
             + z["vocab"] * z["d"])
    touched = n_sparse * experts_touched_per_layer * expert_params(config)
    return ((fixed + touched) * BYTES_PER_EL
            + (live_window_rows + live_global_rows) * row_bytes(config))


def check_lengths(config) -> List[List[int]]:
    """The check's prompt lengths, in groups that are prefilled together
    (padded to a prefill bucket, as the server pads): four short ones,
    where every layer is plain causal attention; ``w - 4 .. w + 4``, whose
    four decode steps cross the window's edge through the ring (a prompt of
    ``w - 4`` writes ring row ``w - 1`` last, one of ``w`` wraps with its
    first decoded token, the longer ones are prefilled past the edge); one
    past two windows, so that the ring it is handed has wrapped twice. At
    the published sizes 5-40, 2044-2052 and 4107."""
    w = _shape(config)["w"]
    short = sorted({int(n) for n in np.linspace(min(5, w // 3),
                                                min(40, w - 2), 4)})
    return [short, list(range(w - 4, w + 5)), [2 * w + 11]]


# ------------------------------------------------------- the program's model
def build_model(config: Dict[str, Any]):
    """The program's module for this configuration (with ``init_params`` the
    one use of ``deepspeed_tpu`` in this file): published keys onto
    ``GPTConfig`` and ``AfmoeBlockConfig``, then the file's own ``model``
    group (dtypes)."""
    from deepspeed_tpu.models.afmoe import AfmoeBlockConfig
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    z = _shape(config)
    block = AfmoeBlockConfig(
        num_kv_heads=z["hk"], head_dim=z["dh"], sliding_window=z["w"],
        layer_types=tuple(z["types"]), dense_layers=z["dense"],
        n_routed_experts=z["experts"], experts_per_token=z["k"],
        moe_d_ff=z["f"], n_shared_experts=z["shared"],
        routed_scaling_factor=config["route_scale"],
        norm_topk_prob=config["route_norm"],
        embed_scale=config["mup_enabled"])
    kw = dict(d_model=z["d"], num_heads=z["h"], num_layers=z["layers"],
              d_ff=z["f_dense"], vocab_size=z["vocab"],
              max_seq_len=z["positions"], rotary=True,
              rotary_base=float(config["rope_theta"]),
              tie_embeddings=config["tie_word_embeddings"],
              layer_norm_eps=config["rms_norm_eps"], block=block)
    kw.update(config.get("model", {}))
    for key in ("dtype", "param_dtype"):
        if isinstance(kw.get(key), str):
            kw[key] = jnp.dtype(kw[key]).type
    return GPT(GPTConfig(**kw))


EMBEDDING_RMS = 4.0
GAIN_STD = 0.1
BIAS_STD = 0.02


def init_params(model, key):
    """The seeded weights, as the configuration file's ``assumed`` has them:
    the program's own initialisation (every matrix ``normal / sqrt(fan_in)``)
    with (a) the embedding's rows brought to an RMS of ``EMBEDDING_RMS``
    AFTER the muP multiplier: a sandwich-normed block adds a branch of unit
    RMS whatever it computed, a seeded model's attention averages its
    context, and tokens then all route alike unless the stream carries what
    each IS (archs/pangu_ultra_moe.py::init_params, PERF.md PR 26); (b)
    every gain drawn ``1 + 0.1 x normal`` (at exactly one a gain left out
    would not show); (c) the selection bias drawn ``0.02 x normal``, twice
    the distance of the 8th from the 9th score: at zero a bias left out, or
    one that entered the weights, would not show."""
    params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    keys = iter(jax.random.split(jax.random.fold_in(key, 0xAF), 64))

    def drawn(leaf, mean, std):
        return (mean + std * jax.random.normal(next(keys), leaf.shape, f32)
                ).astype(leaf.dtype)

    blocks = {}
    for group, leaves in params["blocks"].items():
        blocks[group] = dict(leaves)
        for name, leaf in leaves.items():
            if name.startswith("ln_") or name.endswith("_norm"):
                blocks[group][name] = drawn(leaf, 1.0, GAIN_STD)
            elif name == "router_bias":
                blocks[group][name] = drawn(leaf, 0.0, BIAS_STD)
    table = params["wte"]["embedding"]
    return {**params, "blocks": blocks,
            "ln_f": {"scale": drawn(params["ln_f"]["scale"], 1.0, GAIN_STD)},
            "wte": {"embedding": (table.astype(f32) * EMBEDDING_RMS
                                  ).astype(table.dtype)}}


# ------------------------------------------------------------ the reference
def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


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


_QUERY_BLOCK = 512


@partial(jax.jit, static_argnames=("h", "hk", "full", "base", "eps",
                                   "lower"))
def _project(x, p, *, h, hk, full, base, eps, lower):
    """One row's ``q [S, h, d]``, ``k, v [S, h, d]`` (the key heads repeated)
    and gate ``[S, h * d]`` from its hidden ``x [S, D]``."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        s = x.shape[0]
        u = _rms(x, p["ln_in"], eps)
        q = _rms(_mm(u, p["q_proj"], lower).reshape(s, h, -1), p["q_norm"],
                 eps)
        k = _rms(_mm(u, p["k_proj"], lower).reshape(s, hk, -1), p["k_norm"],
                 eps)
        v = _mm(u, p["v_proj"], lower).reshape(s, hk, -1)
        if not full:
            positions = jnp.arange(s)
            q, k = _rotary(q, positions, base), _rotary(k, positions, base)
        k, v = (jnp.repeat(a, h // hk, axis=1) for a in (k, v))
        return q, k, v, _mm(u, p["attn_gate"], lower)


@partial(jax.jit, static_argnames=("window", "lower"))
def _attend(q, k, v, first, *, window, lower):
    """A block of one row's queries ``q [n, h, d]``, the first at position
    ``first``, over the row's keys ``k, v [S, h, d]`` under a dense mask:
    ``0 <= i - j`` and, with a window, ``i - j < window``."""
    with jax.default_matmul_precision("highest"):
        n, _, d = q.shape
        sc = jnp.einsum("qhd,khd->hqk", _round(q, lower), _round(k, lower)) \
            / math.sqrt(d)
        i = first + jnp.arange(n)[:, None]
        j = jnp.arange(k.shape[0])[None, :]
        seen = i >= j
        if window is not None:
            seen &= i - j < window
        probs = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", _round(probs, lower),
                          _round(v, lower))


@partial(jax.jit, static_argnames=("eps", "lower"))
def _close_attention(x, ctx, gate, p, *, eps, lower):
    """h = x + RMS_post_attn((ctx * sigmoid(g)) W_o) and RMS_pre_mlp(h)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        o = _mm(ctx.reshape(ctx.shape[0], -1) * jax.nn.sigmoid(gate),
                p["o_proj"], lower)
        hid = x + _rms(o, p["ln_post_attn"], eps)
        return hid, _rms(hid, p["ln_pre_mlp"], eps)


@partial(jax.jit, static_argnames=("lower",))
def _gated_mlp(x, gate, up, down, *, lower=None):
    with jax.default_matmul_precision("highest"):
        g = _mm(x, gate.astype(f32), lower)
        u = _mm(x, up.astype(f32), lower)
        return _mm(jax.nn.silu(g) * u, down.astype(f32), lower)


@jax.jit
def _scores(x, router):
    with jax.default_matmul_precision("highest"):
        return jax.nn.sigmoid(x @ router.astype(f32))


@partial(jax.jit, static_argnames=("eps",))
def _close(h, f, gain, *, eps):
    return h + _rms(f, gain.astype(f32), eps)


@partial(jax.jit, static_argnames=("eps", "lower"))
def _head(x, gain, kernel, *, eps, lower):
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(x, gain.astype(f32), eps), kernel.astype(f32), lower)


def _own_choice(biased: np.ndarray, k: int) -> np.ndarray:
    """The reference's own ``k`` largest biased scores of each token."""
    return np.argsort(-biased, axis=-1, kind="stable")[:, :k]


def _route(biased: np.ndarray, k: int, program_choice: Optional[np.ndarray],
           report: Dict[str, Any]) -> np.ndarray:
    """The experts each token runs, ``[T, k]``: the reference's own ``k``
    largest biased scores, or the program's choice where it gave one (not
    -1) and the rule of this file's docstring allows it."""
    own = _own_choice(biased, k)
    if program_choice is None:
        return own
    out = own.copy()
    for t in np.nonzero((program_choice >= 0).all(axis=-1))[0]:
        theirs = program_choice[t]
        report["sets"] += 1
        taken = np.setdiff1d(theirs, own[t])
        if taken.size == 0 and len(set(theirs.tolist())) == k:
            continue
        displaced = np.setdiff1d(own[t], theirs)
        gap = float(biased[t, displaced].max() - biased[t, taken].min()) \
            if taken.size and displaced.size else float("inf")
        report["sets_differing"] += 1
        report["largest_gap"] = max(report["largest_gap"], gap)
        if gap < ROUTE_EPS:
            out[t] = theirs
            report["pairs_swapped"] += int(taken.size)
        else:
            report["sets_refused"] += 1
    return out


def _attention(x, p, z, full: bool, base: float, eps: float, lower):
    """h and RMS_pre_mlp(h) of one layer for every row of ``x [B, S, D]``,
    a row and a block of queries at a time."""
    hids, f_ins = [], []
    for row in x:
        q, k, v, gate = _project(row, p, h=z["h"], hk=z["hk"], full=full,
                                 base=base, eps=eps, lower=lower)
        ctx = jnp.concatenate([
            _attend(q[t0:t0 + _QUERY_BLOCK], k, v, t0,
                    window=None if full else z["w"], lower=lower)
            for t0 in range(0, q.shape[0], _QUERY_BLOCK)])
        hid, f_in = _close_attention(row, ctx, gate, p, eps=eps, lower=lower)
        hids.append(hid)
        f_ins.append(f_in)
    return jnp.stack(hids), jnp.stack(f_ins)


def reference_logits(config: Dict[str, Any], params, input_ids,
                     program_choice=None, lower=None,
                     rows: Optional[Sequence[Tuple[int, int]]] = None):
    """``[B, S]`` ids -> (float32 logits, routing report). ``params`` is the
    tree the program serves (``wte``, ``blocks`` with ``dense`` and
    ``sparse`` groups of layer-stacked leaves, ``ln_f``, ``lm_head``).
    ``rows``: a ``(start, stop)`` a batch row; the head is multiplied over
    those positions alone and the logits come back as a list of ``[stop -
    start, vocab]`` (None: ``[B, S, vocab]``). ``program_choice [expert
    layers, B, S, k]``: the experts the program chose, -1 where it ran no
    such token. The report counts, per (token, expert layer): ``sets``
    compared, ``sets_differing``, ``sets_refused`` (a difference of
    ROUTE_EPS or more: the reference kept its own), ``largest_gap``,
    ``pairs_swapped``; and ``pairs_held`` / ``pairs_absent`` of the
    reference's OWN choice over the tokens ``program_choice`` covers (every
    expert is held here: none absent)."""
    z = _shape(config)
    eps, base = config["rms_norm_eps"], float(config["rope_theta"])
    ids = jnp.asarray(input_ids)
    b, s = ids.shape
    x = jnp.take(params["wte"]["embedding"], ids, axis=0).astype(f32)
    if config["mup_enabled"]:
        x = x * math.sqrt(z["d"])
    report = {"sets": 0, "sets_differing": 0, "sets_refused": 0,
              "largest_gap": 0.0, "pairs_swapped": 0, "pairs_held": 0,
              "pairs_absent": 0}
    banks = ("expert_gate", "expert_up", "expert_down")

    def layer(group, i):
        return {k: v[i] for k, v in group.items()
                if k not in banks + ("router", "router_bias")}

    dense = params["blocks"].get("dense", {})
    sparse = params["blocks"].get("sparse", {})
    for n in range(z["layers"]):
        routed = n >= z["dense"]
        group, i = (sparse, n - z["dense"]) if routed else (dense, n)
        p = layer(group, i)
        hid, f_in = _attention(x, p, z, z["types"][n] == FULL, base, eps,
                               lower)
        if not routed:
            f = _gated_mlp(f_in, p["gate_proj"], p["up_proj"],
                           p["down_proj"], lower=lower)
            x = _close(hid, f, p["ln_post_mlp"], eps=eps)
            continue
        flat = f_in.reshape(b * s, -1)
        scores = np.asarray(_scores(flat, group["router"][i]))
        biased = scores + np.asarray(group["router_bias"][i], np.float32)
        theirs = None if program_choice is None else \
            np.asarray(program_choice[i]).reshape(b * s, -1)
        chosen = _route(biased, z["k"], theirs, report)
        top = np.take_along_axis(scores, chosen, axis=-1)
        weight = top / (top.sum(-1, keepdims=True) + 1e-20) \
            if config["route_norm"] else top
        weight = weight * config["route_scale"]
        if theirs is not None:
            report["pairs_held"] += int((theirs >= 0).all(axis=-1).sum()
                                        ) * z["k"]
        f = _gated_mlp(flat, p["shared_gate"], p["shared_up"],
                       p["shared_down"], lower=lower)
        for e in range(z["experts"]):          # a loop over the 128 experts
            w_e = np.where(chosen == e, weight, 0.0).sum(-1)
            if not w_e.any():
                continue
            f = f + jnp.asarray(w_e, f32)[:, None] * _gated_mlp(
                flat, group["expert_gate"][i, e], group["expert_up"][i, e],
                group["expert_down"][i, e], lower=lower)
        x = _close(hid, f.reshape(b, s, -1), p["ln_post_mlp"], eps=eps)
    gain, kernel = params["ln_f"]["scale"], params["lm_head"]["kernel"]
    if rows is None:
        return _head(x, gain, kernel, eps=eps, lower=lower), report
    return [_head(x[i, a:c], gain, kernel, eps=eps, lower=lower)
            for i, (a, c) in enumerate(rows)], report
