"""Plain float32 ``jax.numpy`` forward of the GPT family — the reference.

No flax, no kernels, no cache, no remat, no sharding: the block equations
written out over the SAME param tree ``models/gpt.py`` trains and serves
(scan-stacked ``blocks`` leaves with a leading layer dim), in float32 under
``jax.default_matmul_precision("highest")``. It is what logit-level checks
compare against (chip_smoke.py on the chip, tests on the CPU), so it must
stay independent of the code it checks: it shares nothing with
``models/gpt.py`` but the rotary formula and the config.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .gpt import GPTConfig, rotary_embedding


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _attention(cfg: GPTConfig, x, p, positions):
    b, s, _ = x.shape
    h, d = cfg.num_heads, cfg.head_dim
    q, k, v = jnp.split(_dense(x, p["qkv"]), 3, axis=-1)
    q, k, v = (t.reshape(b, s, h, d) for t in (q, k, v))
    if cfg.rotary:
        rd = int(cfg.rotary_pct * d)
        q = rotary_embedding(q, positions, rd)
        k = rotary_embedding(k, positions, rd)
    scale = cfg.qk_scale if cfg.qk_scale is not None else 1.0 / math.sqrt(d)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    causal = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(causal[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, h * d)
    return _dense(ctx, p["out_proj"])


def _mlp(x, p):
    return _dense(jax.nn.gelu(_dense(x, p["up_proj"]), approximate=True),
                  p["down_proj"])


def reference_logits(cfg: GPTConfig, params, input_ids) -> jnp.ndarray:
    """input_ids [B, S] -> float32 logits [B, S, V] for a dense,
    scan-stacked, full-attention configuration."""
    if cfg.moe or not cfg.scan_layers or cfg.attn_windows is not None:
        raise NotImplementedError(
            "the reference covers dense scan-stacked full-attention GPT "
            "configurations (no MoE, no per-layer windows)")
    params = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    b, s = input_ids.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    eps = cfg.layer_norm_eps
    with jax.default_matmul_precision("highest"):
        wte = params["wte"]["embedding"]
        x = wte[input_ids]
        if not cfg.rotary:
            x = x + params["wpe"][positions]
        for i in range(cfg.num_layers):
            p = jax.tree.map(lambda a: a[i], params["blocks"])
            attn = _attention(cfg, _layer_norm(x, p["ln_1"], eps), p["attn"],
                              positions)
            if cfg.parallel_residual:
                x = x + attn + _mlp(_layer_norm(x, p["ln_2"], eps), p["mlp"])
            else:
                x = x + attn
                x = x + _mlp(_layer_norm(x, p["ln_2"], eps), p["mlp"])
        x = _layer_norm(x, params["ln_f"], eps)
        if cfg.tie_embeddings:
            return x @ wte.T
        return x @ params["lm_head"]["kernel"]


def reference_lm_loss(cfg: GPTConfig, params, input_ids) -> jnp.ndarray:
    """Mean next-token cross entropy of ``input_ids`` under the reference
    (labels are the inputs shifted by one — ``lm_loss_fn``'s default)."""
    logits = reference_logits(cfg, params, input_ids)[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)[..., 0]
    return -jnp.mean(ll)
