"""DeepSpeedTransformerLayer / DeepSpeedTransformerConfig: the
user-facing fused transformer layer API.

Reference analogue: ``deepspeed/ops/transformer/transformer.py:39,460``
(config + layer wrapping the fused CUDA kernels,
``csrc/transformer/ds_transformer_cuda.cpp``). On TPU the "fusion" is the
compiler's: the layer body is plain jnp + the Pallas attention kernel,
and one jit of the surrounding step compiles it into fused MXU/VPU
programs — so this module is an API-parity layer (same config surface,
same BERT-style block semantics), not a monolithic kernel binding. The
reference's memory/rounding toggles map to their honest TPU equivalents:

  normalize_invertible / gelu_checkpoint / attn_dropout_checkpoint
      -> any of them enables remat of the layer body (recompute instead
         of store — the XLA expression of "drop this activation")
  stochastic_mode
      -> the layer output's fp32 -> compute-dtype cast uses stochastic
         rounding in training (the StochasticTransformerBuilder mode,
         ds_transformer_cuda.cpp:1031-1046), drawn from the flax "sr"
         rng stream
  fp16 -> compute dtype float16 (bfloat16 is the TPU-native default)
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass
class DeepSpeedTransformerConfig:
    """Reference-keyed layer config (transformer.py:39). ``batch_size``,
    ``local_rank`` and ``seed`` exist for signature parity: XLA programs
    are shape-polymorphic at trace time and flax owns rngs, so they carry
    no behavior here."""
    batch_size: int = -1
    hidden_size: int = -1
    intermediate_size: int = -1
    heads: int = -1
    attn_dropout_ratio: float = 0.0
    hidden_dropout_ratio: float = 0.0
    num_hidden_layers: int = -1
    initializer_range: float = 0.02
    layer_norm_eps: float = 1e-12
    local_rank: int = -1
    seed: int = -1
    fp16: bool = False
    bf16: bool = True
    pre_layer_norm: bool = True
    normalize_invertible: bool = False
    gelu_checkpoint: bool = False
    adjust_init_range: bool = True
    attn_dropout_checkpoint: bool = False
    stochastic_mode: bool = False
    return_tuple: bool = False
    training: bool = True

    def __post_init__(self):
        if self.hidden_size <= 0 or self.heads <= 0:
            raise ValueError("hidden_size and heads are required")
        if self.intermediate_size <= 0:
            self.intermediate_size = 4 * self.hidden_size
        if self.hidden_size % self.heads:
            raise ValueError(
                f"hidden_size {self.hidden_size} not divisible by heads "
                f"{self.heads}")
        if self.fp16 and self.bf16:
            self.bf16 = False      # explicit fp16 wins over the default
        if self.stochastic_mode and not self.bf16:
            raise ValueError(
                "stochastic_mode is implemented as an fp32 body with a "
                "stochastically-rounded bf16 output write; with "
                f"{'fp16' if self.fp16 else 'fp32'} compute it would "
                "silently not apply — use bf16 (the TPU-native precision) "
                "or drop the flag")

    @property
    def compute_dtype(self):
        if self.fp16:
            return jnp.float16
        return jnp.bfloat16 if self.bf16 else jnp.float32

    @property
    def remat(self) -> bool:
        return (self.normalize_invertible or self.gelu_checkpoint
                or self.attn_dropout_checkpoint)


class DeepSpeedTransformerLayer(nn.Module):
    """BERT-style transformer layer (reference transformer.py:460):
    self-attention + FFN with Pre-LN or Post-LN residuals, dropout on
    attention probs and both residual branches.

    __call__(hidden_states [B, S, H], attention_mask [B, S] optional,
    deterministic) -> [B, S, H] (or a 1-tuple when return_tuple).
    Training with dropout needs a "dropout" rng; stochastic_mode needs an
    "sr" rng."""
    config: DeepSpeedTransformerConfig

    @nn.compact
    def __call__(self, hidden_states, attention_mask=None,
                 deterministic: Optional[bool] = None):
        cfg = self.config
        if deterministic is None:
            deterministic = not cfg.training
        if attention_mask is not None and attention_mask.ndim != 2:
            raise ValueError(
                f"attention_mask must be a [batch, seq] binary key-padding "
                f"mask (1 = attend); got rank {attention_mask.ndim}. "
                f"BERT-style extended additive masks ([B,1,1,S] with "
                f"0/-10000) are a framework-internal encoding — pass the "
                f"original binary mask instead")
        dt = cfg.compute_dtype
        sr_active = (cfg.stochastic_mode
                     and jnp.dtype(cfg.compute_dtype) == jnp.bfloat16)
        if sr_active:
            # the reference stochastic mode rounds fp32 ACCUMULATIONS into
            # the low-precision output write (ds_transformer_cuda.cpp:
            # 1031-1046) — so the body runs fp32 and only the final cast
            # narrows (stochastically in training, nearest in eval);
            # SR of an already-bf16 value would be the identity
            dt = jnp.float32
        h = cfg.hidden_size
        heads = cfg.heads
        hd = h // heads
        # reference adjust_init_range: residual-output projections start
        # at initializer_range / sqrt(2 * num_layers)
        out_std = cfg.initializer_range
        if cfg.adjust_init_range and cfg.num_hidden_layers > 0:
            out_std /= math.sqrt(2.0 * cfg.num_hidden_layers)
        init = nn.initializers.normal
        def body(x):
            # submodules are constructed INSIDE the (possibly remat'd) body:
            # flax's lift machinery rejects calls to modules born in the
            # outer trace scope from within a jax transform
            ln_attn = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dt,
                                   name="attn_ln")
            ln_out = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=dt,
                                  name="out_ln")
            x = x.astype(dt)
            b, s, _ = x.shape
            a_in = ln_attn(x) if cfg.pre_layer_norm else x
            qkv = nn.Dense(3 * h, dtype=dt,
                           kernel_init=init(cfg.initializer_range),
                           name="attn_qkv")(a_in)
            q, k, v = jnp.split(qkv, 3, axis=-1)
            q = q.reshape(b, s, heads, hd)
            k = k.reshape(b, s, heads, hd)
            v = v.reshape(b, s, heads, hd)
            drop_attn = cfg.attn_dropout_ratio and not deterministic
            from ..pallas._utils import log_path_once
            from ..pallas.flash_attention import (flash_attention,
                                                  flash_refusal)
            use_flash = attention_mask is None and not drop_attn
            if use_flash and flash_refusal(s) is not None:
                log_path_once("transformer attention", "xla",
                              f"kernel gate refused: {flash_refusal(s)}")
                use_flash = False
            if use_flash:
                # hot path: the fused Pallas flash kernel (key-padding
                # masks and attention-prob dropout need the materialized
                # probs, so those configs take the einsum path below)
                ctx = flash_attention(q, k, v, causal=False,
                                      sm_scale=1.0 / math.sqrt(hd))
                ctx = ctx.astype(dt).reshape(b, s, h)
            else:
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, k
                                    ).astype(jnp.float32) / math.sqrt(hd)
                if attention_mask is not None:
                    logits = jnp.where(
                        attention_mask.astype(bool)[:, None, None, :],
                        logits, jnp.float32(-1e10))
                probs = jax.nn.softmax(logits, axis=-1).astype(dt)
                if drop_attn:
                    probs = nn.Dropout(cfg.attn_dropout_ratio)(
                        probs, deterministic=False)
                ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v
                                 ).reshape(b, s, h)
            attn_out = nn.Dense(h, dtype=dt, kernel_init=init(out_std),
                                name="attn_out")(ctx)
            if cfg.hidden_dropout_ratio and not deterministic:
                attn_out = nn.Dropout(cfg.hidden_dropout_ratio)(
                    attn_out, deterministic=False)
            x = x + attn_out
            if not cfg.pre_layer_norm:
                x = ln_attn(x)
            f_in = ln_out(x) if cfg.pre_layer_norm else x
            ff = nn.Dense(cfg.intermediate_size, dtype=dt,
                          kernel_init=init(cfg.initializer_range),
                          name="inter")(f_in)
            ff = nn.gelu(ff, approximate=False)
            ff = nn.Dense(h, dtype=dt, kernel_init=init(out_std),
                          name="output")(ff)
            if cfg.hidden_dropout_ratio and not deterministic:
                ff = nn.Dropout(cfg.hidden_dropout_ratio)(
                    ff, deterministic=False)
            x = x + ff
            if not cfg.pre_layer_norm:
                x = ln_out(x)
            return x

        if cfg.remat:
            # normalize_invertible / gelu_checkpoint /
            # attn_dropout_checkpoint all say "drop this activation" — the
            # XLA expression is remat of the layer body (recompute in
            # backward instead of storing). nn.remat lifts variables/rngs
            # through the checkpoint; the module-first-arg form keeps the
            # submodule definitions in this compact scope.
            out = nn.remat(lambda mdl, x: body(x), prevent_cse=False)(
                self, hidden_states)
        else:
            out = body(hidden_states)
        if sr_active:
            # training-mode stochastic rounding of the layer's output cast
            # (the StochasticTransformerBuilder contract: unbiased rounding
            # in the hot path, reproducible kernels for fine-tuning)
            if deterministic:
                out = out.astype(jnp.bfloat16)
            else:
                from ..quantizer import stochastic_round_bf16
                out = stochastic_round_bf16(out, self.make_rng("sr"))
        return (out,) if cfg.return_tuple else out
