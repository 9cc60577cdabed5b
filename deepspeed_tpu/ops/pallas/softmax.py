"""Fused (masked) softmax, Pallas/TPU.

Reference analogue: ``csrc/transformer/softmax_kernels.cu`` (training) and
the inference ``softmax`` kernel with triangular/local masking modes
(``csrc/transformer/inference/csrc/softmax.cu``). Supports the same masking
vocabulary: none, causal (triangular), and an additive attention mask.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._utils import interpret_mode, require_rows, rows_block

NEG_INF = -1e30


def _fwd_kernel(x_ref, y_ref, *, causal, row_offset_per_block, block_rows):
    x = x_ref[...].astype(jnp.float32)                  # [bn, S]
    if causal:
        i = pl.program_id(0)
        s = x.shape[-1]
        # global row index within the [S, S] score matrix
        rows = (i * block_rows + jax.lax.broadcasted_iota(
            jnp.int32, x.shape, x.ndim - 2)) % row_offset_per_block
        cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, x.ndim - 1)
        x = jnp.where(rows >= cols, x, NEG_INF)
    m = jnp.max(x, axis=-1, keepdims=True)
    p = jnp.exp(x - m)
    y_ref[...] = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(y_ref.dtype)


def _bwd_kernel(y_ref, dy_ref, dx_ref):
    y = y_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    dot = jnp.sum(y * dy, axis=-1, keepdims=True)
    dx_ref[...] = (y * (dy - dot)).astype(dx_ref.dtype)




def _softmax_fwd(x, causal):
    orig = x.shape
    s = x.shape[-1]
    rows_per_mat = x.shape[-2] if x.ndim >= 2 else 1
    x2 = x.reshape(-1, s)
    n = x2.shape[0]
    bn = rows_block(n, 128)
    kernel = functools.partial(_fwd_kernel, causal=causal,
                               row_offset_per_block=rows_per_mat,
                               block_rows=bn)
    y = pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, s), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, s), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s), x.dtype),
        name="softmax_fwd",
        interpret=interpret_mode(),
    )(x2)
    return y.reshape(orig), (y, orig)


def _softmax_bwd(causal, res, g):
    y, orig = res
    s = y.shape[-1]
    dy2 = g.reshape(-1, s)
    n = dy2.shape[0]
    bn = rows_block(n, 128)
    dx = pl.pallas_call(
        _bwd_kernel,
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((bn, s), lambda i: (i, 0)),
                  pl.BlockSpec((bn, s), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((bn, s), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, s), dy2.dtype),
        name="softmax_bwd",
        interpret=interpret_mode(),
    )(y, dy2)
    return (dx.reshape(orig),)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _fused_softmax_pallas(x, causal: bool = False):
    y, _ = _softmax_fwd(x, causal)
    return y


_fused_softmax_pallas.defvjp(lambda x, causal: _softmax_fwd(x, causal),
                             _softmax_bwd)


def softmax_reference(x, causal: bool = False):
    """The XLA expression the kernel is checked against."""
    xf = x.astype(jnp.float32)
    if causal:
        s_len = x.shape[-1]
        xf = jnp.where(jnp.tril(jnp.ones((s_len, s_len), bool)), xf, NEG_INF)
    return jax.nn.softmax(xf, axis=-1).astype(x.dtype)


def fused_softmax(x, causal: bool = False):
    """Softmax over the last dim with optional causal (triangular) masking.
    For causal masking x must be [..., S, S] score matrices. Row counts the
    kernel cannot tile raise ``KernelUnsupported``."""
    require_rows("fused_softmax", x.shape, 128)
    return _fused_softmax_pallas(x, causal)


def masked_softmax(x, mask: Optional[jnp.ndarray] = None,
                   causal: bool = False, scale: float = 1.0):
    """Reference ``attn_softmax`` semantics: optional pre-scale + additive
    mask, then fused softmax (inference softmax.cu applies alibi/mask the
    same way)."""
    if scale != 1.0:
        x = x * scale
    if mask is not None:
        x = x + mask
    return fused_softmax(x, causal)
