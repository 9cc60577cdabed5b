"""Error-feedback 1-bit compressed allreduce, in-jit.

TPU-native analogue of the reference 1-bit communication backends
(``deepspeed/runtime/comm/nccl.py:52-203``: worker sign-compression with
error feedback, phase-1 ``all_to_all`` of packed sign bits + allgather of
per-worker scales, server-side recompression with its own error buffer,
phase-2 allgather of server signs+scales). Re-designed for TPU:

  * The whole exchange runs INSIDE the jitted train step as ``jax.lax``
    collectives over a mesh axis (callers wrap it in ``shard_map``) — no
    host round-trips, no cupy staging buffers, and XLA overlaps the
    all_to_all/all_gather with surrounding compute on ICI.
  * Sign bits are packed 8-per-byte with integer arithmetic (the
    ``cupy.packbits`` analogue), so the dominant phase-1 payload is n/8
    bytes + one fp32 scale per rank: ~26x less wire volume than a dense
    fp32 ring allreduce, matching the reference's published reduction.

The compression scheme (identical math to the reference):

  worker:  buf += worker_error
           scale = ||buf||_2 / sqrt(n)
           worker_error = buf - scale * sign(buf)      # sign(0) := +1
  server:  m = sum_r scale_r * sign_r / world          # my 1/world chunk
           m += server_error
           s_scale = ||m||_2 / sqrt(n/world)
           server_error = m - s_scale * sign(m)
  result:  concat_r s_scale_r * sign_r                 # via allgather
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax import shard_map

import numpy as np

_BIT_WEIGHTS = np.asarray([1, 2, 4, 8, 16, 32, 64, 128], np.uint8)


def _bit_weights():
    return jnp.asarray(_BIT_WEIGHTS)


def padded_size(n: int, world_size: int) -> int:
    """Smallest size >= n divisible by world*lcm(world, 8), so each rank's
    server chunk is itself a whole number of packed bytes (the reference's
    ``divider`` math, zoadam.py corrected_tensor_size)."""
    divider = world_size * 8 // math.gcd(world_size, 8)  # lcm(world, 8)
    unit = world_size * divider
    return ((n + unit - 1) // unit) * unit


def pack_signs(bits: jnp.ndarray) -> jnp.ndarray:
    """bool [..., 8k] -> uint8 [..., k]; bit i of byte j = bits[..., 8j+i]."""
    b = bits.reshape(bits.shape[:-1] + (-1, 8)).astype(jnp.uint8)
    return jnp.sum(b * _bit_weights(), axis=-1, dtype=jnp.uint8)


def unpack_signs(packed: jnp.ndarray) -> jnp.ndarray:
    """uint8 [..., k] -> bool [..., 8k] (inverse of pack_signs)."""
    bits = (packed[..., None] & _bit_weights()) != 0
    return bits.reshape(packed.shape[:-1] + (-1,))


def _pm1(bits: jnp.ndarray, dtype=jnp.float32) -> jnp.ndarray:
    """bool -> {-1, +1} with the reference's sign(0) := +1 convention."""
    return jnp.where(bits, jnp.ones((), dtype), -jnp.ones((), dtype))


def compressed_allreduce(buf: jnp.ndarray,
                         worker_error: jnp.ndarray,
                         server_error: jnp.ndarray,
                         axis_name: str,
                         world_size: int):
    """1-bit averaging allreduce with error feedback. Call inside shard_map.

    Args:
      buf: [n] local fp32 buffer; n must be ``padded_size(n, world)``-aligned.
      worker_error: [n] this rank's worker error-feedback buffer.
      server_error: [n/world] this rank's server error buffer.
      axis_name: mapped mesh axis to reduce over.
      world_size: size of that axis.

    Returns (avg [n], new_worker_error [n], new_server_error [n/world]).
    """
    n = buf.shape[0]
    if n % (world_size * 8):
        raise ValueError(f"buffer size {n} not aligned for world={world_size}; "
                         f"pad to {padded_size(n, world_size)}")
    chunk = n // world_size

    corrected = buf + worker_error
    scale = jnp.linalg.norm(corrected) / jnp.sqrt(jnp.float32(n))
    sign_bits = corrected >= 0
    new_worker_error = corrected - scale * _pm1(sign_bits)

    # phase 1: all_to_all of packed sign chunks + allgather of scales
    packed = pack_signs(sign_bits).reshape(world_size, chunk // 8)
    recv = jax.lax.all_to_all(packed, axis_name, split_axis=0, concat_axis=0,
                              tiled=True)                    # [world, chunk/8]
    scales = jax.lax.all_gather(scale, axis_name)            # [world]

    # server-side: sum my chunk's contributions, recompress
    signs_r = _pm1(unpack_signs(recv))                       # [world, chunk]
    m = jnp.einsum("r,rc->c", scales / world_size, signs_r)  # [chunk]
    m = m + server_error
    s_scale = jnp.linalg.norm(m) / jnp.sqrt(jnp.float32(chunk))
    s_bits = m >= 0
    new_server_error = m - s_scale * _pm1(s_bits)

    # phase 2: allgather server signs + scales
    all_s = jax.lax.all_gather(pack_signs(s_bits), axis_name)  # [world, chunk/8]
    all_scales = jax.lax.all_gather(s_scale, axis_name)        # [world]
    result = (all_scales[:, None] * _pm1(unpack_signs(all_s))).reshape(n)
    return result, new_worker_error, new_server_error


def wire_bytes_compressed(n: int, world_size: int) -> int:
    """Bytes a rank puts on the wire for one compressed allreduce of n fp32:
    phase-1 all_to_all sends (world-1)/world * n/8 sign bytes + phase-2
    allgather receives the same; scales are world fp32s. (Accounting helper
    for the ds_bench-style comparison against 2*4*n dense ring bytes.)"""
    signs = n // 8  # sent once in a2a, received once in allgather
    scales = 2 * world_size * 4
    return 2 * signs + scales


def wire_bytes_dense(n: int, world_size: int) -> int:
    """Ring-allreduce bytes per rank for n fp32: 2 * (world-1)/world * 4n."""
    return int(2 * (world_size - 1) / world_size * 4 * n)


class CompressedBackend:
    """Eager wrapper over the in-jit kernel, for tests and host-driven loops.

    API parity with the reference ``NcclBackend``/``MpiBackend``
    (runtime/comm/nccl.py:52): operates on the *stacked global view* used by
    the rest of ``deepspeed_tpu.comm`` — buffers/errors carry a leading
    world axis sharded over the group's mesh axis.
    """

    def __init__(self, group=None):
        from . import comm as dist
        self.group = group if group is not None else dist.new_group("dp")
        self.size = self.group.size
        self._fn = None

    def error_shapes(self, n: int):
        npad = padded_size(n, self.size)
        return (self.size, npad), (self.size, npad // self.size)

    def compressed_allreduce(self, stacked_buf, worker_errors, server_errors):
        """stacked_buf: [G, n] per-rank buffers -> ([G, n] averaged results,
        new worker errors, new server errors). n is padded internally."""
        g = self.size
        ax = self.group.axis_name
        n = stacked_buf.shape[1]
        npad = padded_size(n, g)
        if worker_errors.shape != (g, npad):
            raise ValueError(f"worker_errors must be [G, {npad}]")
        buf = jnp.pad(jnp.asarray(stacked_buf, jnp.float32),
                      ((0, 0), (0, npad - n)))
        spec2 = P(ax, None)
        sharded = lambda x, s: jax.device_put(x, NamedSharding(self.group.mesh, s))
        buf = sharded(buf, spec2)
        worker_errors = sharded(worker_errors, spec2)
        server_errors = sharded(server_errors, spec2)

        def f(b, we, se):
            out, we2, se2 = compressed_allreduce(
                b[0], we[0], se[0], ax, g)
            return out[None], we2[None], se2[None]

        out, we2, se2 = shard_map(
            f, mesh=self.group.mesh, in_specs=(spec2, spec2, spec2),
            out_specs=(spec2, spec2, spec2), check_vma=False)(
                buf, worker_errors, server_errors)
        return out[:, :n], we2, se2
