"""Architecture ``pangu_ultra_moe`` (openPangu-Ultra-MoE-718B), for the chip
benchmark: its plain float32 reference, its counts from shapes, and the
mapping from the published ``config.json`` keys to the program's model.

An architecture file is what ``drivers/serve_closed_arch.py`` asks of a
configuration that names it (``"arch": "<this file's stem>"``):

  ``build_model(config)``            -> the program's flax module
  ``param_count(config)``            parameters on this chip, from shapes
  ``decode_step_bytes(config, ...)`` the least bytes of one decode step
  ``reference_logits(config, params, ids, program_choice)``
                                     -> float32 logits, routing report
  ``LOGIT_ATOL``, ``ROUTE_EPS``      the comparison's limits, with reasons

Everything but ``build_model`` is independent of ``deepspeed_tpu``: plain
``jax.numpy`` in float32 under ``jax.default_matmul_precision("highest")``,
expanded attention only (no cache, no absorbed form, no grouping: a loop
over the held experts), over the parameter tree the program serves, given
the same share of the deployment (``experts_held`` experts from
``expert_offset``, the vocabulary slice). One layer, and inside an expert
layer one expert, is upcast at a time, so that the reference fits beside the
served bfloat16 copy at the published widths.

The equations (``config`` keys in brackets), ``RMS(x) = x / sqrt(mean(x^2)
+ rms_norm_eps) * g``, hidden ``x``:

  block   h = x + RMS_post_attn(MLA(RMS_in(x)));
          y = h + RMS_post_mlp(FFN(RMS_pre_mlp(h)))           [sandwich_norm]
  MLA     c_q = RMS(x W_dq) [q_lora_rank]; [q_nope | q_rope] = c_q W_uq per
          head [qk_nope_head_dim + qk_rope_head_dim]; [c_kv | k_rope] =
          x W_dkv [kv_lora_rank + qk_rope_head_dim]; c = RMS(c_kv);
          k_nope = c W_uk, v = c W_uv per head [v_head_dim]; rotary
          [rope_theta] on q_rope and on the ONE k_rope all heads share;
          causal softmax((q_nope.k_nope + q_rope.k_rope) / sqrt(d_nope +
          d_rope)) in float32; o = concat_h(P v) W_o. No bias anywhere.
  FFN     the first [first_k_dense_replace] layers: (silu(x W_g) * (x W_u))
          W_d of width [intermediate_size]. The rest: s = sigmoid(x W_r) in
          float32 over the PUBLISHED [n_routed_experts]; the
          [num_experts_per_tok] largest; w_i = [routed_scaling_factor] * s_i
          / (sum of the chosen + 1e-20) [norm_topk_prob]; FFN(x) = Shared(x)
          + sum over the chosen experts HELD HERE of w_i E_i(x), all gated
          SiLU MLPs of width [moe_intermediate_size]. No token is dropped.
  head    final RMSNorm, an untied head over the vocabulary slice.

Departures, both of the program and followed here: rotary turns interleaved
pairs (x0, x1), (x2, x3), ... where the published code turns half-splits, a
fixed permutation of q_rope's and k_rope's features under seeded weights;
the multi-token-prediction layer is not built (the logits do not depend on
it). Assumed, the config giving none of them: the score function (sigmoid,
no expert groups, no selection bias), that the inner norms of c_q and c_kv
carry a gain and no offset, the initialisation.

**Routing under rounding.** The program computes in bfloat16 and this file
in float32, so the k-th and (k+1)-th score of a token can change places.
``reference_logits`` is handed the experts the program chose. Where a
token's set differs from the reference's own, the reference follows the
program ONLY if its own scores of the experts displaced and the experts
taken instead differ by less than ``ROUTE_EPS``; it reports how many sets
differed and the largest such difference, and a difference of ``ROUTE_EPS``
or more is a fault of the program's router, not rounding. (Following or
not hardly moves the logits: 0.047 either way in two seeds. The rule is
there for the router.)
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32

# bf16 compute against this float32 reference on the same bf16-rounded
# weights. Two readings of ONE comparison on the v5e at the published widths
# place the limit (my chip runs, PR 26; PERF.md section 6), both through
# drivers/serve_closed_arch.py over its 16 check prompts of 5-40 tokens, on
# logits that span about +-5: the program's largest |logit difference| was
# 0.034-0.037 in five seeds (0.031-0.035 over the two prompts of the thirteen
# runs before them, 0.045-0.061 in the thirteen runs of two earlier inits);
# the CONTROL, this reference with every matmul's operands rounded to
# float8_e4m3, the nearest precision below the configuration's bfloat16, in
# the program's place (the driver's ``--control``), read 0.596 and 0.605 and
# came out as not correct (0.489-0.545 over two prompts, three seeds). 0.12
# is 3.3 times the first and a fifth of the second. A missing post-norm,
# another rotary base or an unscaled routed sum move logits by more already
# at toy widths (tests/benchmark/test_arch_pangu_ultra_moe.py).
LOGIT_ATOL = 0.12
# a greedy token is the argmax of the server's own bf16 logits; under the
# reference it can trail the reference's argmax by the error on two logits.
# Found (my chip runs, PR 26): 0.0000-0.0115 over the 16 prompts through the
# real server in five runs, the reference's rows following the program's
# routing inside ROUTE_EPS. Rows of the reference's OWN routing do not bound
# it: a set that changes places across the edge of the held experts drops or
# adds a whole expert, and a token then trailed by 0.14 and 0.16 in two of
# seven runs (0.002-0.035 in the others).
TOKEN_GAP_ATOL = 2 * LOGIT_ATOL
# Scores are sigmoid(z), z = x W_r with x of unit RMS and columns of W_r of
# unit norm, so z ~ N(0, 1) and among 256 scores the 8th and 9th lie some
# 0.01 apart. The router's input is a bfloat16 activation: its last rounding
# alone (2^-9 relative, independent over 7680 features) moves z by ~0.001,
# what the layers before it carry by a few times that, and d(sigmoid) <= 1/4.
# Found on the v5e (my chip runs, PR 26): 2.4-9.9 % of the (token, expert
# layer) sets differ from the reference's own (212 sets a run in twenty-six
# runs, 1,676 a run in the last five), and the largest difference of a
# displaced expert's score from the score of the one taken instead was 0.0038
# (0.0010-0.0038 by run; 0.0017-0.0022 in the last five). 0.01 is 2.6 times
# that. What it refuses: a router fed int8 or fp8 activations (errors 10-30
# times bfloat16's), another router matrix, a score function of another
# order. What it cannot: a router whose OUTPUT is rounded to bfloat16 moves a
# score by ~0.0005, less than its bfloat16 input already does.
ROUTE_EPS = 0.01


# ------------------------------------------------------------------ counts
def _shape(config: Dict[str, Any]) -> Dict[str, int]:
    """The sizes as they are run. ``n_routed_experts`` in the file is the
    number HELD here; the router's width is the published one."""
    return dict(
        d=config["hidden_size"], h=config["num_attention_heads"],
        rq=config["q_lora_rank"], r=config["kv_lora_rank"],
        dn=config["qk_nope_head_dim"], dr=config["qk_rope_head_dim"],
        dv=config["v_head_dim"], f_dense=config["intermediate_size"],
        f=config["moe_intermediate_size"], layers=config["num_hidden_layers"],
        dense=min(config["first_k_dense_replace"],
                  config["num_hidden_layers"]),
        held=config["n_routed_experts"],
        routed=config["published"]["n_routed_experts"],
        offset=config["deployment_share"]["expert_offset"],
        shared=config["n_shared_experts"], k=config["num_experts_per_tok"],
        vocab=config["vocab_size"])


def attention_params(config) -> int:
    """W_dq, W_uq, W_dkv, W_uk, W_uv, W_o and the two inner norms."""
    z = _shape(config)
    return (z["d"] * z["rq"] + z["rq"] * z["h"] * (z["dn"] + z["dr"])
            + z["d"] * (z["r"] + z["dr"]) + z["r"] * z["h"] * z["dn"]
            + z["r"] * z["h"] * z["dv"] + z["h"] * z["dv"] * z["d"]
            + z["rq"] + z["r"])


def expert_params(config) -> int:
    """One routed expert: gate, up, down."""
    z = _shape(config)
    return 3 * z["d"] * z["f"]


def dense_layer_params(config) -> int:
    z = _shape(config)
    return attention_params(config) + 4 * z["d"] + 3 * z["d"] * z["f_dense"]


def expert_layer_params(config) -> int:
    """Attention, four norms, the shared expert(s), the router over the
    PUBLISHED experts, and the experts held here."""
    z = _shape(config)
    return (attention_params(config) + 4 * z["d"]
            + z["shared"] * 3 * z["d"] * z["f"] + z["d"] * z["routed"]
            + z["held"] * expert_params(config))


def param_count(config) -> int:
    z = _shape(config)
    return (z["dense"] * dense_layer_params(config)
            + (z["layers"] - z["dense"]) * expert_layer_params(config)
            + 2 * z["vocab"] * z["d"] + z["d"])


def latent_bytes_per_token(config, bytes_per_el: int = 2) -> int:
    """[c | k_rope] of one position through every layer."""
    z = _shape(config)
    return z["layers"] * (z["r"] + z["dr"]) * bytes_per_el


def decode_step_bytes(config, live_positions: float,
                      experts_touched_per_layer: float,
                      bytes_per_el: int = 2) -> float:
    """Bytes one decode step MUST read: every matmul weight outside the
    routed experts (attention, dense FFN, shared experts, routers, the head
    ONCE; the input embedding is a gather of a few rows), the routed experts
    that a token of this step TOUCHED (a layer that skips idle experts reads
    no more, so the share cannot pass 100 % by skipping), and the LIVE
    latent rows."""
    z = _shape(config)
    n_sparse = z["layers"] - z["dense"]
    norms = z["rq"] + z["r"]
    fixed = (z["layers"] * (attention_params(config) - norms)
             + z["dense"] * 3 * z["d"] * z["f_dense"]
             + n_sparse * (z["shared"] * 3 * z["d"] * z["f"]
                           + z["d"] * z["routed"])
             + z["vocab"] * z["d"])
    touched = n_sparse * experts_touched_per_layer * expert_params(config)
    return ((fixed + touched) * bytes_per_el
            + live_positions * latent_bytes_per_token(config, bytes_per_el))


# ------------------------------------------------------- the program's model
def build_model(config: Dict[str, Any]):
    """The program's module for this configuration (the one import of
    ``deepspeed_tpu`` in this file): published keys onto ``GPTConfig`` and
    ``LatentBlockConfig``, then the file's own ``model`` group (dtypes)."""
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    from deepspeed_tpu.models.mla import LatentBlockConfig
    z = _shape(config)
    block = LatentBlockConfig(
        q_lora_rank=z["rq"], kv_lora_rank=z["r"], qk_nope_head_dim=z["dn"],
        qk_rope_head_dim=z["dr"], v_head_dim=z["dv"],
        dense_layers=z["dense"], n_routed_experts=z["routed"],
        experts_per_token=z["k"], moe_d_ff=z["f"],
        n_shared_experts=z["shared"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"], experts_held=z["held"],
        expert_offset=z["offset"])
    kw = dict(d_model=z["d"], num_heads=z["h"], num_layers=z["layers"],
              d_ff=z["f_dense"], vocab_size=z["vocab"],
              max_seq_len=config["max_position_embeddings"], rotary=True,
              rotary_base=float(config["rope_theta"]),
              tie_embeddings=config["tie_word_embeddings"],
              layer_norm_eps=config["rms_norm_eps"], block=block)
    kw.update(config.get("model", {}))
    for key in ("dtype", "param_dtype"):
        if isinstance(kw.get(key), str):
            kw[key] = jnp.dtype(kw[key]).type
    return GPT(GPTConfig(**kw))


EMBEDDING_RMS = 4.0


def init_params(model, key):
    """The seeded weights: the program's own initialisation (normal /
    sqrt(fan_in) for every matrix, ones for every gain), with the embedding's
    rows brought to an RMS of ``EMBEDDING_RMS``.

    Why: a sandwich-normed block adds a branch of unit RMS to the stream
    whatever the branch computed, and the attention of a SEEDED model
    averages its context, so its branch is much the same for every token of
    a request. Under the program's init an embedding row has RMS 1/sqrt(d),
    a hundredth of that: all tokens of a request chose the same experts,
    the share of pairs on the 16 held experts moved 6.0-7.2 % with the seed
    and tokens/s 2.2 % with it (PERF.md, PR 26). A trained model's stream
    carries its tokens and its router spreads them. Rows four times the
    size of a branch leave the context a few percent of the router's input,
    so that a token routes by what it is, as in a trained model, and the
    chip's work does not depend on which seed drew the router."""
    params = model.init(key, jnp.zeros((1, 8), jnp.int32))["params"]
    table = params["wte"]["embedding"]
    scale = EMBEDDING_RMS * math.sqrt(table.shape[-1])
    return {**params, "wte": {"embedding": (table.astype(f32) * scale
                                            ).astype(table.dtype)}}


# ------------------------------------------------------------ the reference
def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _rotary(x, positions, base: float):
    """[B, S, H, D]: every feature pair (x0, x1), (x2, x3), ... turned by
    position x frequency."""
    d = x.shape[-1]
    freqs = 1.0 / (base ** (jnp.arange(0, d, 2, dtype=f32) / d))
    ang = positions[..., None].astype(f32) * freqs            # [B, S, d/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def _round(x, lower):
    """``lower`` None: float32 as it is. Else the operand rounded to that
    dtype (float8_e4m3fn: the nearest precision below the configuration's
    bfloat16), for the reading that places ``LOGIT_ATOL``."""
    return x if lower is None else x.astype(lower).astype(f32)


def _mm(x, w, lower):
    return _round(x, lower) @ _round(w, lower)


_STATIC = ("h", "dn", "dr", "dv", "r", "base", "eps", "lower")


@partial(jax.jit, static_argnames=_STATIC)
def _attention_half(x, p, positions, *, h, dn, dr, dv, r, base, eps, lower):
    """h = x + RMS_post_attn(MLA(RMS_in(x))) and RMS_pre_mlp(h)."""
    with jax.default_matmul_precision("highest"):
        p = jax.tree.map(lambda a: a.astype(f32), p)
        b, s, _ = x.shape
        y = _rms(x, p["ln_in"], eps)
        c_q = _rms(_mm(y, p["q_down"], lower), p["q_norm"], eps)
        q = _mm(c_q, p["q_up"], lower).reshape(b, s, h, dn + dr)
        ckv = _mm(y, p["kv_down"], lower)
        c = _rms(ckv[..., :r], p["kv_norm"], eps)
        k_rope = _rotary(ckv[..., None, r:], positions, base)  # one "head"
        q_rope = _rotary(q[..., dn:], positions, base)
        k_nope = _mm(c, p["k_up"], lower).reshape(b, s, h, dn)
        v = _mm(c, p["v_up"], lower).reshape(b, s, h, dv)
        scores = (jnp.einsum("bqhd,bkhd->bhqk", _round(q[..., :dn], lower),
                             _round(k_nope, lower))
                  + jnp.einsum("bqhd,bkd->bhqk", _round(q_rope, lower),
                               _round(k_rope[:, :, 0], lower))
                  ) / math.sqrt(dn + dr)
        causal = jnp.tril(jnp.ones((s, s), bool))
        probs = jax.nn.softmax(
            jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        ctx = jnp.einsum("bhqk,bkhd->bqhd", _round(probs, lower),
                         _round(v, lower)).reshape(b, s, h * dv)
        hid = x + _rms(_mm(ctx, p["out_proj"], lower), p["ln_post_attn"], eps)
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


def _own_choice(scores: np.ndarray, k: int) -> np.ndarray:
    """The reference's own ``k`` largest scores of each token, ``[T, k]``."""
    return np.argsort(-scores, axis=-1, kind="stable")[:, :k]


def _route(scores: np.ndarray, k: int, program_choice: Optional[np.ndarray],
           report: Dict[str, Any]) -> np.ndarray:
    """The experts each token runs, ``[T, k]``: the reference's own ``k``
    largest scores, or the program's choice where it gave one (not -1) and
    the rule of this file's docstring allows it."""
    own = _own_choice(scores, k)
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
        gap = float(scores[t, displaced].max() - scores[t, taken].min()) \
            if taken.size and displaced.size else float("inf")
        report["sets_differing"] += 1
        report["largest_gap"] = max(report["largest_gap"], gap)
        if gap < ROUTE_EPS:
            out[t] = theirs
            report["pairs_swapped"] += int(taken.size)
        else:
            report["sets_refused"] += 1
    return out


def reference_logits(config: Dict[str, Any], params, input_ids,
                     program_choice=None, lower=None):
    """``[B, S]`` ids -> (``[B, S, vocab slice]`` float32 logits, routing
    report). ``params`` is the tree the program serves (``wte``, ``blocks``
    with ``dense`` and ``sparse`` groups of layer-stacked leaves, ``ln_f``,
    ``lm_head``). ``program_choice [expert layers, B, S, k]``: the experts
    the program chose, -1 where it ran no such token. The report counts, per
    (token, expert layer): ``sets`` compared, ``sets_differing``,
    ``sets_refused`` (a difference of ROUTE_EPS or more: the reference kept
    its own), ``largest_gap``, ``pairs_swapped`` (the experts that the sets
    it followed took in place of its own); and the routing counters of the
    reference's OWN choice, followed or not, over the tokens
    ``program_choice`` covers (``pairs_held``, ``pairs_absent``): the
    program's counters may differ from them by the swapped pairs at most."""
    z = _shape(config)
    eps, base = config["rms_norm_eps"], float(config["rope_theta"])
    kw = dict(h=z["h"], dn=z["dn"], dr=z["dr"], dv=z["dv"], r=z["r"],
              base=base, eps=eps, lower=lower)
    ids = jnp.asarray(input_ids)
    b, s = ids.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    x = jnp.take(params["wte"]["embedding"], ids, axis=0).astype(f32)
    report = {"sets": 0, "sets_differing": 0, "sets_refused": 0,
              "largest_gap": 0.0, "pairs_swapped": 0, "pairs_held": 0,
              "pairs_absent": 0}
    banks = ("expert_gate", "expert_up", "expert_down")

    def layer(group, i):
        return {k: v[i] for k, v in group.items() if k not in banks}

    dense = params["blocks"].get("dense", {})
    for i in range(z["dense"]):
        p = layer(dense, i)
        hid, f_in = _attention_half(x, p, positions, **kw)
        f = _gated_mlp(f_in, p["gate_proj"], p["up_proj"], p["down_proj"],
                       lower=lower)
        x = _close(hid, f, p["ln_post_mlp"], eps=eps)
    sparse = params["blocks"].get("sparse", {})
    for i in range(z["layers"] - z["dense"]):
        p = layer(sparse, i)
        hid, f_in = _attention_half(x, p, positions, **kw)
        flat = f_in.reshape(b * s, -1)
        scores = np.asarray(_scores(flat, p["router"]))
        theirs = None if program_choice is None else \
            np.asarray(program_choice[i]).reshape(b * s, -1)
        chosen = _route(scores, z["k"], theirs, report)
        top = np.take_along_axis(scores, chosen, axis=-1)
        weight = top / (top.sum(-1, keepdims=True) + 1e-20) \
            if config["norm_topk_prob"] else top
        weight = weight * config["routed_scaling_factor"]
        if theirs is not None:     # counted over the reference's OWN choice
            seen = (theirs >= 0).all(axis=-1)
            own = _own_choice(scores, z["k"])
            here = (own >= z["offset"]) & (own < z["offset"] + z["held"])
            report["pairs_held"] += int(here[seen].sum())
            report["pairs_absent"] += int((~here[seen]).sum())
        f = _gated_mlp(flat, p["shared_gate"], p["shared_up"],
                       p["shared_down"], lower=lower)
        for e in range(z["held"]):             # a loop over the experts held
            w_e = np.where(chosen == z["offset"] + e, weight, 0.0).sum(-1)
            if not w_e.any():
                continue
            f = f + jnp.asarray(w_e, f32)[:, None] * _gated_mlp(
                flat, sparse["expert_gate"][i, e], sparse["expert_up"][i, e],
                sparse["expert_down"][i, e], lower=lower)
        x = _close(hid, f.reshape(b, s, -1), p["ln_post_mlp"], eps=eps)
    logits = _head(x, params["ln_f"]["scale"], params["lm_head"]["kernel"],
                   eps=eps, lower=lower)
    return logits, report
