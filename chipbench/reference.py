"""The configurations' plain reference: GPT-NeoX forward, loss, in float32.

A copy, kept with the benchmark, of what ``deepspeed_tpu/models/
gpt_reference.py`` does (PR 21), made independent of the program: it
imports nothing from ``deepspeed_tpu`` (its own rotary), so no later PR can
move the yardstick by editing the model. No flax, kernels, cache, remat or
sharding: the block equations over the param tree the program trains and
serves (scan-stacked ``blocks`` leaves with a leading layer dim), under
``jax.default_matmul_precision("highest")``.

Weights are upcast to float32 ONE LAYER AT A TIME (a float32 copy of 3.6 B
parameters is 14.5 GB and does not fit beside the served bf16 copy): the
reference is given the same bf16-rounded weights the server holds, so what
is compared is the arithmetic, not the rounding of the weights.

For training the reference also gives per-token negative log-likelihoods and
the global norm of the gradient of one row's mean loss, a layer at a time
(``jax.vjp`` per block, only the squared norms kept), so that neither a
float32 copy of the model nor its gradients are ever held whole. ``device``
pulls each layer's weights onto one chip as it is used: over several chips
the tree stays sharded and no chip is given the whole model.

Departures from the published GPT-NeoX, both following the program (see the
configuration files): tanh-approximated GELU, and rotary over interleaved
pairs with qkv stored as three contiguous blocks.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

f32 = jnp.float32


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _dense(x, p):
    return x @ p["kernel"] + p["bias"]


def _rotary(x, positions, rotary_dim: int, base: float):
    """[B, S, H, D]: rotate the first ``rotary_dim`` features of each head
    in interleaved pairs (x0, x1), (x2, x3), ... by position x frequency."""
    rot, keep = x[..., :rotary_dim], x[..., rotary_dim:]
    freqs = 1.0 / (base ** (jnp.arange(0, rotary_dim, 2, dtype=f32)
                            / rotary_dim))
    ang = positions[..., None].astype(f32) * freqs          # [B, S, rd/2]
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    a, b = rot[..., 0::2], rot[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return jnp.concatenate([out.reshape(rot.shape), keep], axis=-1)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(f32), tree)


def _block_body(x, p, positions, *, heads, rotary_dim, base, eps, parallel):
    """One block over float32 weights ``p``; traced under highest matmul
    precision by its callers."""
    b, s, dm = x.shape
    d = dm // heads
    h1 = _layer_norm(x, p["ln_1"], eps)
    q, k, v = jnp.split(_dense(h1, p["attn"]["qkv"]), 3, axis=-1)
    q, k, v = (t.reshape(b, s, heads, d) for t in (q, k, v))
    q = _rotary(q, positions, rotary_dim, base)
    k = _rotary(k, positions, rotary_dim, base)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(d)
    causal = jnp.tril(jnp.ones((s, s), bool))
    logits = jnp.where(causal[None, None], logits, -jnp.inf)
    probs = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, dm)
    attn = _dense(ctx, p["attn"]["out_proj"])

    def mlp(y):
        up = jax.nn.gelu(_dense(y, p["mlp"]["up_proj"]), approximate=True)
        return _dense(up, p["mlp"]["down_proj"])

    if parallel:
        return x + attn + mlp(_layer_norm(x, p["ln_2"], eps))
    x = x + attn
    return x + mlp(_layer_norm(x, p["ln_2"], eps))


_BLOCK_STATIC = ("heads", "rotary_dim", "base", "eps", "parallel")


@partial(jax.jit, static_argnames=_BLOCK_STATIC)
def _block(x, p, positions, **kw):
    with jax.default_matmul_precision("highest"):
        return _block_body(x, _f32(p), positions, **kw)


@partial(jax.jit, static_argnames=_BLOCK_STATIC)
def _block_vjp(x, p, positions, dy, **kw):
    """Cotangent of the block's input, and the squared norm of the
    cotangents of its (float32) weights."""
    with jax.default_matmul_precision("highest"):
        _, back = jax.vjp(lambda x_, p_: _block_body(x_, p_, positions, **kw),
                          x, _f32(p))
        dx, dp = back(dy)
        return dx, sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(dp))


@partial(jax.jit, static_argnames=("eps",))
def _head(x, ln_f, kernel, *, eps):
    with jax.default_matmul_precision("highest"):
        return _layer_norm(x, _f32(ln_f), eps) @ kernel.astype(f32)


def _nll(logits, input_ids):
    """[B, S, V] logits -> [B, S-1]: -log p(token j+1 | tokens <= j)."""
    logp = jax.nn.log_softmax(logits[:, :-1], axis=-1)
    return -jnp.take_along_axis(logp, input_ids[:, 1:, None], axis=-1)[..., 0]


@partial(jax.jit, static_argnames=("eps",))
def _head_vjp(x, ln_f, kernel, input_ids, *, eps):
    """Per-token NLL, the cotangent of the head's input under the MEAN NLL,
    and the cotangents of the final norm and the head's kernel."""
    with jax.default_matmul_precision("highest"):
        def f(x_, ln_f_, kernel_):
            nll = _nll(_layer_norm(x_, ln_f_, eps) @ kernel_, input_ids)
            return jnp.mean(nll), nll
        (_, nll), grads = jax.value_and_grad(f, argnums=(0, 1, 2),
                                             has_aux=True)(
            x, _f32(ln_f), kernel.astype(f32))
        return (nll,) + grads


class _Walk:
    """The pieces of one forward, weights pulled where they are used."""

    def __init__(self, cfg: Dict[str, Any], params, input_ids, device=None):
        if cfg.get("architecture") != "GPTNeoXForCausalLM":
            raise NotImplementedError(
                f"the reference covers GPTNeoXForCausalLM, not "
                f"{cfg.get('architecture')!r}: a new architecture brings "
                f"its own reference file")
        self.cfg, self.params = cfg, params
        self.pull = ((lambda t: jax.device_put(t, device)) if device
                     is not None else (lambda t: t))
        self.ids_host = np.asarray(input_ids)
        self.ids = self.pull(jnp.asarray(self.ids_host))
        b, s = self.ids.shape
        self.positions = self.pull(
            jnp.broadcast_to(jnp.arange(s)[None, :], (b, s)))
        heads = cfg["num_attention_heads"]
        self.kw = dict(
            heads=heads,
            rotary_dim=int(cfg["rotary_pct"] * (cfg["hidden_size"] // heads)),
            base=float(cfg["rotary_emb_base"]), eps=cfg["layer_norm_eps"],
            parallel=cfg["use_parallel_residual"])
        self.eps = cfg["layer_norm_eps"]
        self.tied = bool(cfg["tie_word_embeddings"])

    def embed(self):
        # gathered where the table lies (host indices commit to no chip)
        return self.pull(
            self.params["wte"]["embedding"][self.ids_host]).astype(f32)

    def layer(self, i: int):
        return self.pull(jax.tree.map(lambda a: a[i], self.params["blocks"]))

    def layers(self):
        return range(self.cfg["num_hidden_layers"])

    def head_weights(self):
        kernel = (self.params["wte"]["embedding"].T if self.tied
                  else self.params["lm_head"]["kernel"])
        return self.pull(self.params["ln_f"]), self.pull(kernel)


def reference_logits(cfg: Dict[str, Any], params, input_ids,
                     device=None) -> jnp.ndarray:
    """input_ids [B, S] -> float32 logits [B, S, V]. ``cfg`` is the
    configuration file's dict (published GPT-NeoX keys)."""
    w = _Walk(cfg, params, input_ids, device)
    x = w.embed()
    for i in w.layers():
        x = _block(x, w.layer(i), w.positions, **w.kw)
    return _head(x, *w.head_weights(), eps=w.eps)


def reference_token_nll(cfg: Dict[str, Any], params, input_ids,
                        device=None) -> jnp.ndarray:
    """[B, S] -> float32 [B, S-1]: the negative log-likelihood of each next
    token (labels = inputs shifted by one)."""
    return jax.jit(_nll)(reference_logits(cfg, params, input_ids, device),
                         jnp.asarray(input_ids))


def reference_lm_loss(cfg: Dict[str, Any], params, input_ids,
                      device=None) -> jnp.ndarray:
    """Mean next-token cross entropy."""
    return jnp.mean(reference_token_nll(cfg, params, input_ids, device))


def reference_nll_and_grad_norm(cfg: Dict[str, Any], params, input_ids,
                                device=None):
    """[B, S] -> (per-token NLL [B, S-1], the global L2 norm of the gradient
    of their MEAN with respect to every weight). Backward is a ``jax.vjp``
    per block, last to first, over the block inputs the forward kept; of each
    block's weight cotangents only the squared norm is kept."""
    w = _Walk(cfg, params, input_ids, device)
    xs = [w.embed()]
    for i in w.layers():
        xs.append(_block(xs[-1], w.layer(i), w.positions, **w.kw))
    nll, dx, d_ln_f, d_kernel = _head_vjp(xs.pop(), *w.head_weights(),
                                          w.ids, eps=w.eps)
    sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(d_ln_f))
    for i in reversed(w.layers()):
        dx, sq_i = _block_vjp(xs.pop(), w.layer(i), w.positions, dx, **w.kw)
        sq = sq + sq_i
    # the embedding's rows gather the cotangents of the positions that read
    # them; a tied head adds its own cotangent to the same table
    vocab, dm = w.params["wte"]["embedding"].shape
    d_wte = jnp.zeros((vocab, dm), f32).at[w.ids.reshape(-1)].add(
        dx.reshape(-1, dm))
    if w.tied:
        d_wte = d_wte + d_kernel.T
    else:
        sq = sq + jnp.sum(jnp.square(d_kernel))
    return nll, jnp.sqrt(sq + jnp.sum(jnp.square(d_wte)))
