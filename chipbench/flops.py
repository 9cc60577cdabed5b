"""Operations and bytes from shapes: the yardstick's own arithmetic.

Kept with the benchmark so that no PR that claims a gain can change how
work is counted. ``cfg`` is a configuration file's dict (the published keys).
Recomputed operations (remat) never count.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_params(cfg: Dict[str, Any]) -> int:
    """One GPT-NeoX block: qkv + out projections (4 d^2 + 4d biases), the
    MLP (2 d d_ff + d_ff + d) and two LayerNorms (4d)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return 4 * d * d + 4 * d + 2 * d * f + f + d + 4 * d


def embedding_params(cfg: Dict[str, Any]) -> int:
    """Input embedding, the final LayerNorm and the output head (untied:
    a second V x d matrix; rotary models have no position table)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    head = 0 if cfg["tie_word_embeddings"] else v * d
    return v * d + 2 * d + head


def param_count(cfg: Dict[str, Any]) -> int:
    return (cfg["num_hidden_layers"] * layer_params(cfg)
            + embedding_params(cfg))


def matmul_params(cfg: Dict[str, Any]) -> int:
    """Weights that multiply every token: per layer 4 d^2 + 2 d d_ff, and
    the head V d ONCE (the input embedding is a gather)."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return (cfg["num_hidden_layers"] * (4 * d * d + 2 * d * f)
            + cfg["vocab_size"] * d)


def attention_flops_fwd(cfg: Dict[str, Any], seq_len: int,
                        causal: bool = True) -> float:
    """Forward attention FLOPs of ONE sequence in ONE layer: QK^T and PV,
    2 * 2 * S^2 * d, halved under a causal mask (the kernel skips the
    masked half; counting it would flatter the roofline share)."""
    full = 4.0 * seq_len * seq_len * cfg["hidden_size"]
    return full / 2 if causal else full


def train_flops_per_token(cfg: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward FLOPs a training token requires: 6 per matmul
    weight, plus attention at 3x its forward (backward is 2x), causal.
    12 L d S / 2 = 6 L d S per token."""
    attn = 3.0 * cfg["num_hidden_layers"] \
        * attention_flops_fwd(cfg, seq_len) / seq_len
    return 6.0 * matmul_params(cfg) + attn


def kv_bytes_per_token(cfg: Dict[str, Any], bytes_per_el: int = 2) -> int:
    """K and V of one position through every layer."""
    return 2 * cfg["num_hidden_layers"] * cfg["hidden_size"] * bytes_per_el


def decode_step_bytes(cfg: Dict[str, Any], live_kv_positions: float,
                      bytes_per_el: int = 2) -> float:
    """Bytes one decode step MUST read: every weight that multiplies a
    token (the input embedding is a 12-row gather, not counted) plus the
    LIVE keys and values. What a path reads beyond that (the whole arena
    on the XLA path) is the gap the roofline share shows."""
    return (matmul_params(cfg) * bytes_per_el
            + live_kv_positions * kv_bytes_per_token(cfg, bytes_per_el))
