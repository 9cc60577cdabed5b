"""Fused bias + GeLU, Pallas/TPU.

Reference analogue: ``csrc/transformer/gelu_kernels.cu`` (330 LoC:
``gelu_kernel``, ``fused_bias_gelu``, ``d_gelu_func``) and the inference
``bias_gelu`` binding. Uses the same tanh approximation as the reference
kernels.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ._utils import interpret_mode, require_rows, rows_block

_SQRT_2_OVER_PI = 0.7978845608028654


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(_SQRT_2_OVER_PI * (x + 0.044715 * x ** 3)))


def _dgelu(x):
    # d/dx of the tanh-approximated gelu (reference d_gelu_func,
    # gelu_kernels.cu)
    t = jnp.tanh(_SQRT_2_OVER_PI * (x + 0.044715 * x ** 3))
    dt = (1.0 - t * t) * _SQRT_2_OVER_PI * (1.0 + 3 * 0.044715 * x * x)
    return 0.5 * (1.0 + t) + 0.5 * x * dt


def _fwd_kernel(x_ref, b_ref, y_ref):
    x = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    y_ref[...] = _gelu(x).astype(y_ref.dtype)


def _bwd_kernel(x_ref, b_ref, dy_ref, dx_ref):
    x = x_ref[...].astype(jnp.float32) + b_ref[...].astype(jnp.float32)
    dx_ref[...] = (_dgelu(x) * dy_ref[...].astype(jnp.float32)).astype(dx_ref.dtype)




def _run_rowwise(kernel, name, inputs, d, out_dtype):
    n = inputs[0].shape[0]
    bn = rows_block(n, 256)
    specs = []
    for a in inputs:
        if a.ndim == 1:
            specs.append(pl.BlockSpec((d,), lambda i: (0,)))
        else:
            specs.append(pl.BlockSpec((bn, d), lambda i: (i, 0)))
    return pl.pallas_call(
        kernel,
        grid=(n // bn,),
        in_specs=specs,
        out_specs=pl.BlockSpec((bn, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, d), out_dtype),
        name=name,
        interpret=interpret_mode(),
    )(*inputs)


@jax.custom_vjp
def _bias_gelu_pallas(x, bias):
    orig = x.shape
    d = x.shape[-1]
    y = _run_rowwise(_fwd_kernel, "bias_gelu_fwd", (x.reshape(-1, d), bias),
                     d, x.dtype)
    return y.reshape(orig)


def _bias_gelu_fwd(x, bias):
    return _bias_gelu_pallas(x, bias), (x, bias)


def _bias_gelu_bwd(res, g):
    x, bias = res
    orig = x.shape
    d = x.shape[-1]
    dx = _run_rowwise(_bwd_kernel, "bias_gelu_bwd",
                      (x.reshape(-1, d), bias, g.reshape(-1, d)), d, x.dtype)
    dx = dx.reshape(orig)
    dbias = jnp.sum(dx.astype(jnp.float32),
                    axis=tuple(range(x.ndim - 1))).astype(bias.dtype)
    return dx, dbias


_bias_gelu_pallas.defvjp(_bias_gelu_fwd, _bias_gelu_bwd)


def bias_gelu_reference(x, bias):
    """The XLA expression the kernel is checked against."""
    xf = x.astype(jnp.float32) + bias.astype(jnp.float32)
    return jax.nn.gelu(xf, approximate=True).astype(x.dtype)


def bias_gelu(x, bias):
    """gelu(x + bias) fused. x: [..., D]; bias: [D]. Row counts the kernel
    cannot tile raise ``KernelUnsupported``."""
    require_rows("bias_gelu", x.shape, 256)
    return _bias_gelu_pallas(x, bias)


def gelu(x):
    """Unfused-bias variant (zero bias)."""
    return bias_gelu(x, jnp.zeros((x.shape[-1],), x.dtype))
