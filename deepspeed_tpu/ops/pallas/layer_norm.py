"""Fused LayerNorm, Pallas/TPU.

Reference analogue: ``csrc/transformer/normalize_kernels.cu`` (2121 LoC of
fused layer-norm fwd/bwd variants, incl. residual fusions) exposed through
the transformer kernel. Here: one row-parallel Pallas kernel each for
forward and input-gradient; the (small) parameter gradients are XLA
reductions. The backward kernel recomputes each row's mean/rstd from the
saved input instead of reading saved statistics: two row reductions over
data already in VMEM, against two extra HBM operands — and the rank-1
``[rows]`` statistics outputs the first version wrote do not pass Mosaic's
operand-layout check on a v5e (XLA tiles f32[8192] as T(1024), a 256-row
block asks for T(256); PERF.md, PR 21).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._utils import interpret_mode, require_rows, rows_block


def _row_stats(x, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return mean, jax.lax.rsqrt(var + eps)


def _fwd_kernel(x_ref, g_ref, b_ref, y_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mean, rstd = _row_stats(x, eps)
    y = ((x - mean) * rstd * g_ref[...].astype(jnp.float32)
         + b_ref[...].astype(jnp.float32))
    y_ref[...] = y.astype(y_ref.dtype)


def _dx_kernel(x_ref, g_ref, dy_ref, dx_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    dy = dy_ref[...].astype(jnp.float32)
    mean, rstd = _row_stats(x, eps)
    xhat = (x - mean) * rstd
    wdy = dy * g_ref[...].astype(jnp.float32)
    c1 = jnp.mean(wdy, axis=-1, keepdims=True)
    c2 = jnp.mean(wdy * xhat, axis=-1, keepdims=True)
    dx_ref[...] = ((wdy - c1 - xhat * c2) * rstd).astype(dx_ref.dtype)


def _ln_fwd(x, gamma, beta, eps):
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    n = x2.shape[0]
    bn = rows_block(n, 256)
    y = pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x.dtype),
        name="layer_norm_fwd",
        interpret=interpret_mode(),
    )(x2, gamma, beta)
    return y.reshape(x.shape), (x2, gamma, x.shape)


def _ln_bwd(eps, res, g):
    x2, gamma, orig_shape = res
    n, d = x2.shape
    dy2 = g.reshape(-1, d)
    bn = rows_block(n, 256)
    dx = pl.pallas_call(
        functools.partial(_dx_kernel, eps=eps),
        grid=(n // bn,),
        in_specs=[
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
            pl.BlockSpec((bn, d), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), x2.dtype),
        name="layer_norm_bwd",
        interpret=interpret_mode(),
    )(x2, gamma, dy2)
    # parameter grads: plain XLA cross-row reductions
    xf = x2.astype(jnp.float32)
    mean, rstd = _row_stats(xf, eps)
    dyf = dy2.astype(jnp.float32)
    dgamma = jnp.sum(dyf * (xf - mean) * rstd, axis=0).astype(gamma.dtype)
    dbeta = jnp.sum(dyf, axis=0).astype(gamma.dtype)
    return dx.reshape(orig_shape), dgamma, dbeta


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _layer_norm_pallas(x, gamma, beta, eps: float = 1e-5):
    y, _ = _ln_fwd(x, gamma, beta, eps)
    return y


_layer_norm_pallas.defvjp(_ln_fwd, _ln_bwd)


def layer_norm_reference(x, gamma, beta, eps: float = 1e-5):
    """The XLA expression the kernel is checked against."""
    xf = x.astype(jnp.float32)
    mean, rstd = _row_stats(xf, eps)
    return ((xf - mean) * rstd * gamma.astype(jnp.float32)
            + beta.astype(jnp.float32)).astype(x.dtype)


def layer_norm(x, gamma, beta, eps: float = 1e-5):
    """Fused layer norm over the last dim. x: [..., D]; gamma/beta: [D].
    Row counts the kernel cannot tile raise ``KernelUnsupported``."""
    require_rows("layer_norm", x.shape, 256)
    return _layer_norm_pallas(x, gamma, beta, eps)
