"""BERT encoder family (BASELINE config #5: BERT-large TP inference).

Reference analogues: the vendored BERT the reference tests kernels against
(``tests/unit/modeling.py``), ``HFBertLayerPolicy``
(``module_inject/replace_policy.py:50``) and the fused inference module it
feeds (``ops/transformer/inference/transformer_inference.py:566``).

TPU-native shape: one flax module whose parameter names reuse the GPT
family's TP vocabulary (``qkv``/``out_proj``/``up_proj``/``down_proj``/
``wte``), so the mesh sharding rules (runtime/sharding.py) — column-split
qkv+up, row-split out+down with the psum inserted by GSPMD — apply to BERT
with zero new code. Post-LayerNorm residuals per the original architecture;
encoder blocks ride one ``nn.scan`` like GPT (under ZeRO-3 ``dp`` shards a
dim inside the layer, runtime/sharding.py, so a scan step gathers one layer).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    max_seq_len: int = 512
    type_vocab_size: int = 2     # 0 = no token-type embedding (DistilBERT)
    use_pooler: bool = True      # False = raw [CLS] state (DistilBERT)
    num_layers: int = 12
    num_heads: int = 12
    d_model: int = 768
    d_ff: int = 3072
    layer_norm_eps: float = 1e-12
    hidden_dropout: float = 0.1
    dtype: any = jnp.float32
    param_dtype: any = jnp.float32
    scan_layers: bool = True
    # "sparse" routes every encoder layer through the block-sparse Pallas
    # kernel with the (padded) attention_mask as its key-padding mask — the
    # reference's BertSparseSelfAttention integration
    # (ops/sparse_attention/sparse_self_attention.py:13 +
    # sparse_attention_utils.py:225). Pad inputs with
    # SparseAttentionUtils.pad_to_block_size first.
    attention_impl: str = "xla"      # xla | sparse
    sparse_attention: any = None     # SparsityConfig when attention_impl=sparse

    def __post_init__(self):
        if self.attention_impl not in ("xla", "sparse"):
            raise ValueError(f"unknown attention_impl "
                             f"{self.attention_impl!r}")
        if self.attention_impl == "sparse" and self.sparse_attention is None:
            raise ValueError("attention_impl='sparse' needs a "
                             "sparse_attention SparsityConfig")

    @property
    def head_dim(self) -> int:
        return self.d_model // self.num_heads


def bert_base(**kw) -> BertConfig:
    return BertConfig(**kw)


def bert_large(**kw) -> BertConfig:
    return BertConfig(num_layers=24, num_heads=16, d_model=1024,
                      d_ff=4096, **kw)


class BertSelfAttention(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, attention_mask=None, deterministic=True):
        cfg = self.cfg
        b, s, _ = x.shape
        qkv = nn.Dense(3 * cfg.d_model, dtype=cfg.dtype,
                       param_dtype=cfg.param_dtype, name="qkv")(x)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shp = (b, s, cfg.num_heads, cfg.head_dim)
        q, k, v = q.reshape(shp), k.reshape(shp), v.reshape(shp)
        if cfg.attention_impl == "sparse":
            from ..ops.sparse_attention.sparse_self_attention import \
                sparse_attention
            out = sparse_attention(
                q, k, v, cfg.sparse_attention,
                sm_scale=1.0 / math.sqrt(cfg.head_dim),
                causal=False, key_padding_mask=attention_mask)
        else:
            logits = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32)
            logits = logits / math.sqrt(cfg.head_dim)
            if attention_mask is not None:
                logits = jnp.where(attention_mask[:, None, None, :], logits,
                                   -1e10)
            probs = jax.nn.softmax(logits, axis=-1).astype(cfg.dtype)
            out = jnp.einsum("bhqk,bkhd->bqhd", probs, v)
        return nn.Dense(cfg.d_model, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype,
                        name="out_proj")(out.reshape(b, s, -1))


class BertLayer(nn.Module):
    """Post-LN encoder block (original BERT): LN(x + attn(x)), then
    LN(x + ffn(x)). Returns (x, ()) so it can be an nn.scan body."""
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, attention_mask=None, deterministic=True):
        cfg = self.cfg
        a = BertSelfAttention(cfg, name="attn")(x, attention_mask,
                                                deterministic)
        if cfg.hidden_dropout and not deterministic:
            a = nn.Dropout(cfg.hidden_dropout)(a, deterministic=False)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln_attn")(x + a)
        h = nn.Dense(cfg.d_ff, dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                     name="up_proj")(x)
        h = nn.gelu(h, approximate=False)
        h = nn.Dense(cfg.d_model, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="down_proj")(h)
        if cfg.hidden_dropout and not deterministic:
            h = nn.Dropout(cfg.hidden_dropout)(h, deterministic=False)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln_ffn")(x + h)
        return x, ()


class BertModel(nn.Module):
    """Encoder + pooler. __call__(input_ids [B,S]) ->
    (sequence_output [B,S,D], pooled_output [B,D])."""
    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic=True):
        cfg = self.cfg
        b, s = input_ids.shape
        if token_type_ids is None:
            token_type_ids = jnp.zeros_like(input_ids)
        x = nn.Embed(cfg.vocab_size, cfg.d_model, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="wte")(input_ids)
        wpe = self.param("wpe", nn.initializers.normal(0.02),
                         (cfg.max_seq_len, cfg.d_model), cfg.param_dtype)
        x = x + wpe[None, :s].astype(cfg.dtype)
        if cfg.type_vocab_size:
            x = x + nn.Embed(cfg.type_vocab_size, cfg.d_model,
                             dtype=cfg.dtype, param_dtype=cfg.param_dtype,
                             name="wtt")(token_type_ids)
        x = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln_emb")(x)
        if attention_mask is not None:
            attention_mask = attention_mask.astype(bool)

        if cfg.scan_layers:
            Scanned = nn.scan(
                BertLayer,
                variable_axes={"params": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast, nn.broadcast),
                length=cfg.num_layers,
                metadata_params={nn.PARTITION_NAME: "layers"},
            )
            x, _ = Scanned(cfg, name="blocks")(x, attention_mask,
                                               deterministic)
        else:
            for i in range(cfg.num_layers):
                x, _ = BertLayer(cfg, name=f"block_{i}")(
                    x, attention_mask, deterministic)

        if not cfg.use_pooler:
            return x, x[:, 0]
        pooled = nn.Dense(cfg.d_model, dtype=cfg.dtype,
                          param_dtype=cfg.param_dtype, name="pooler")(x[:, 0])
        return x, jnp.tanh(pooled)


class BertForMaskedLM(nn.Module):
    """MLM head over the encoder (tied decoder on the word embedding)."""
    cfg: BertConfig

    @nn.nowrap
    def stacked_spec(self, loss_fn):
        """prefix/block/suffix factoring for the structure-driving
        runtimes (SPMD pipeline, layer-streamed capacity tier)."""
        from ..runtime.pipe.spmd import bert_mlm_pipe_spec
        return bert_mlm_pipe_spec(self.cfg, loss_fn)

    @nn.compact
    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic=True):
        cfg = self.cfg
        encoder = BertModel(cfg, name="bert")
        x, _pooled = encoder(input_ids, token_type_ids, attention_mask,
                             deterministic)
        h = nn.Dense(cfg.d_model, dtype=cfg.dtype,
                     param_dtype=cfg.param_dtype, name="transform")(x)
        h = nn.gelu(h, approximate=False)
        h = nn.LayerNorm(epsilon=cfg.layer_norm_eps, dtype=cfg.dtype,
                         param_dtype=cfg.param_dtype, name="ln_head")(h)
        # decoder stored untied (the HF policy fills it with the word
        # embedding, which is how the tie materializes after conversion)
        return nn.Dense(cfg.vocab_size, dtype=cfg.dtype,
                        param_dtype=cfg.param_dtype, name="decoder")(h)
