"""Sort-free fused sampling epilogue: top-k/top-p filter + draw, one kernel.

Reference analogue: DeepSpeed's fused-softmax/sampling epilogues — the last
ops of every decode step run fused instead of as a separate XLA subgraph.
The composed path (serving/sampling.filter_logits + sample_tokens) pays a
``top_k`` partial sort plus a FULL [V] sort for nucleus filtering plus a
``categorical`` draw — three HBM round-trips over the logits per decode
step. This kernel keeps the [V] row in VMEM once and replaces both sorts
with monotonic-int bisections:

  * order keys: an IEEE-754 trick — ``bitcast(f32 -> i32)`` then reflect
    the negative range (``INT32_MAX - bits``, wraparound intended) gives a
    SIGNED int32 key that is strictly monotonic in the float order, so
    "the k-th largest logit" becomes an exact integer bisection (~32
    count-reductions over the VMEM-resident row), never a sort;
  * top-k: bisect for the largest key ``t`` with ``count(key >= t) >= k``
    — exactly ``jax.lax.top_k``'s k-th value, ties kept like the
    reference's ``logits < kth`` mask;
  * top-p: bisect on kept probability mass — find the largest key ``T``
    with ``mass(key > T) >= p``; the cut value is the smallest present
    key above ``T``. The kept SET matches the reference's minimal-
    covering-set semantics up to f32 summation rounding on the mass
    comparison (the reference cumsums post-division, we sum exps and
    compare against ``p * Z``);
  * draw: greedy is a first-index argmax (bit-identical to
    ``jnp.argmax``); temperature sampling is Gumbel-max over the filtered
    row (``argmax(x + g)`` with caller-supplied gumbel noise), the same
    distribution ``jax.random.categorical`` draws from.

Greedy outputs are bit-identical to the composed path — the megakernel
correctness contract. Temperature > 0 draws are distributionally
identical but consume a different rng stream than ``categorical``.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._utils import interpret_mode, refuse

_NEG_CAP = -1e10                 # the reference filter's masked-logit value
_INT32_MAX = 2147483647          # python int: jnp arrays here would be
#                                  closure-captured consts the kernel rejects

# Each program holds _ROWS rows of the vocab in VMEM: the logits block,
# the optional gumbel block, the output block (each double-buffered by the
# pipeline) and the bisection's [rows, V] temporaries (keys, exps, masks)
# — about 16 row-blocks in all, asked for explicitly below because it is
# past Mosaic's default scoped limit at real vocabularies.
_ROWS = 8                        # the f32 sublane tile
_MAX_VOCAB = 128 * 1024
_BISECT_ITERS = 33               # > log2(int32 key range): exact convergence


def _order_key(x: jnp.ndarray) -> jnp.ndarray:
    """Strictly monotonic f32 -> i32 order key. Non-negative floats keep
    their bit pattern; negative floats reflect (``INT32_MAX - bits``
    wraps for -0.0 by design) so every negative key < every non-negative
    key and ordering matches the float order. Finite inputs only."""
    b = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.int32)
    return jnp.where(b >= 0, b, _INT32_MAX - b)


def _mid(lo, hi):
    # overflow-safe floor((lo + hi) / 2) for int32 of either sign
    return (lo >> 1) + (hi >> 1) + (lo & hi & 1)


def _row_bounds(x: jnp.ndarray):
    """Per-row (min key, max key) as [r, 1] int32. The key is monotonic in
    the float, so the bounds come from FLOAT row reductions — Mosaic's
    float min/max reductions are the well-trodden ones."""
    return (_order_key(jnp.min(x, axis=-1, keepdims=True)),
            _order_key(jnp.max(x, axis=-1, keepdims=True)))


def _bisect_kth_key(x: jnp.ndarray, key: jnp.ndarray, k: int) -> jnp.ndarray:
    """Exact per-row k-th largest key: the largest t with
    count(key >= t) >= k. Invariant: count(>= lo) >= k, count(>= hi) < k.
    Counts ride f32 (exact below 2^24, and the vocab gate is far under)."""
    lo, hi = _row_bounds(x)
    hi = hi + 1                  # finite floats: max key < INT32_MAX

    def body(_, carry):
        lo, hi = carry
        mid = _mid(lo, hi)
        c = jnp.sum((key >= mid).astype(jnp.float32), axis=-1,
                    keepdims=True)
        take = c >= float(k)
        return (jnp.where(take, mid, lo), jnp.where(take, hi, mid))

    lo, _ = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo, hi))
    return lo


def _bisect_top_p_key(x: jnp.ndarray, key: jnp.ndarray, e: jnp.ndarray,
                      pz: jnp.ndarray) -> jnp.ndarray:
    """Per-row nucleus cut: with e = exp(x - max) and pz = top_p * sum(e),
    find the largest key T whose strictly-above mass still reaches pz; the
    kept set is ``key > T`` (the reference's minimal covering set: a token
    survives iff the mass strictly above it is < top_p). Invariant:
    mass(> lo) >= pz, mass(> hi) < pz."""
    lo, hi = _row_bounds(x)      # mass(> max) == 0 < pz for top_p > 0
    lo = lo - 1

    def body(_, carry):
        lo, hi = carry
        mid = _mid(lo, hi)
        mass = jnp.sum(jnp.where(key > mid, e, 0.0), axis=-1, keepdims=True)
        take = mass >= pz
        return (jnp.where(take, mid, lo), jnp.where(take, hi, mid))

    lo, _ = jax.lax.fori_loop(0, _BISECT_ITERS, body, (lo, hi))
    return lo


def _filter_rows(x: jnp.ndarray, top_k: Optional[int],
                 top_p: Optional[float]) -> jnp.ndarray:
    """The shared row transform, semantics of serving.sampling.filter_logits
    with the sorts replaced by bisections. x: [r, V] f32, ALREADY
    temperature-scaled by the wrapper — scaling outside the kernel keeps
    kept values bitwise identical to the reference (the in-kernel divide
    can round differently from the surrounding program's), and the kernel
    itself only compares and masks. Rows are independent: every reduction
    is along the vocab axis."""
    v = x.shape[-1]
    if top_k is not None and top_k < v:
        key = _order_key(x)
        kth = _bisect_kth_key(x, key, top_k)
        x = jnp.where(key >= kth, x, _NEG_CAP)
    if top_p is not None and top_p < 1.0:
        key = _order_key(x)
        m = jnp.max(x, axis=-1, keepdims=True)
        e = jnp.exp(x - m)       # masked entries underflow to exact zeros
        pz = jnp.float32(top_p) * jnp.sum(e, axis=-1, keepdims=True)
        cut = _bisect_top_p_key(x, key, e, pz)
        x = jnp.where(key > cut, x, _NEG_CAP)
    return x


def _sampling_kernel(logits_ref, *rest, temperature, top_k, top_p, v,
                     emit):
    """Grid programs over blocks of ``_ROWS`` rows (logits pre-scaled by
    temperature). emit='logits' writes the filtered rows; emit='tokens'
    additionally draws (argmax, or Gumbel-max when a gumbel operand is
    present) and writes one int32 per row."""
    if emit == "tokens" and temperature != 0.0:
        gumbel_ref, out_ref = rest
    else:
        (out_ref,) = rest
    x = _filter_rows(logits_ref[...].astype(jnp.float32), top_k, top_p)
    if emit == "logits":
        out_ref[...] = x
        return
    if temperature != 0.0:
        x = x + gumbel_ref[...]
    m = jnp.max(x, axis=-1, keepdims=True)
    # first-index argmax (jnp.argmax's tie-break) as a float min-reduction:
    # lane indices are exact in f32 below 2^24
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1).astype(jnp.float32)
    first = jnp.min(jnp.where(x == m, idx, float(v)), axis=-1, keepdims=True)
    out_ref[...] = first.astype(jnp.int32)


def sampling_refusal(b: int, v: int) -> Optional[str]:
    """Why the kernel cannot run [b, v] logits; None when it can."""
    if b < 1:
        return "empty batch"
    if v % 128 != 0:
        return f"vocab {v} is not a multiple of the 128-lane tile"
    if v > _MAX_VOCAB:
        return (f"vocab {v} exceeds {_MAX_VOCAB}: {_ROWS} rows plus the "
                f"bisection temporaries would not fit VMEM")
    return None


def sampling_supported(b: int, v: int) -> bool:
    return sampling_refusal(b, v) is None


def _run(kernel, operands, b: int, v: int, out_cols: int, out_dtype):
    """Launch ``kernel`` over row blocks of [b, v] operands. Batches past
    one block are padded to a whole number of blocks (padded rows filter
    zeros and are sliced off)."""
    reason = sampling_refusal(b, v)
    if reason is not None:
        refuse("sampling", (b, v), reason)
    rows = b if b <= _ROWS else _ROWS
    pad = (-b) % rows
    if pad:
        operands = [jnp.pad(x, ((0, pad), (0, 0))) for x in operands]
    n = b + pad
    out = pl.pallas_call(
        kernel,
        grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, v), lambda i: (i, 0))
                  for _ in operands],
        out_specs=pl.BlockSpec((rows, out_cols), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n, out_cols), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(32 << 20, 16 * _ROWS * v * 4)),
        name="sampling",
        interpret=interpret_mode(),
    )(*operands)
    return out[:b]


def threshold_filter_logits(logits: jnp.ndarray, temperature: float,
                            top_k: Optional[int],
                            top_p: Optional[float] = None) -> jnp.ndarray:
    """Fused sort-free filter over [b, V] logits -> filtered f32 [b, V].
    Same masked-logit contract as serving.sampling.filter_logits (masked
    entries pinned at -1e10). A vocab the kernel cannot take raises
    ``KernelUnsupported`` (ask :func:`sampling_refusal` first)."""
    b, v = logits.shape
    logits = logits.astype(jnp.float32)
    if temperature != 0.0:
        logits = logits / temperature
    kernel = functools.partial(_sampling_kernel, temperature=temperature,
                               top_k=top_k, top_p=top_p, v=v, emit="logits")
    return _run(kernel, [logits], b, v, v, jnp.float32)


def fused_sample(logits: jnp.ndarray, gumbel: Optional[jnp.ndarray],
                 temperature: float, top_k: Optional[int],
                 top_p: Optional[float] = None) -> jnp.ndarray:
    """Fused filter + draw over [b, V] logits -> int32 tokens [b].
    temperature == 0: first-index argmax, bit-identical to the composed
    greedy path. temperature > 0: Gumbel-max with the caller's [b, V]
    gumbel noise. A vocab the kernel cannot take raises
    ``KernelUnsupported``."""
    b, v = logits.shape
    sample = temperature != 0.0
    logits = logits.astype(jnp.float32)
    if sample:
        logits = logits / temperature
    kernel = functools.partial(_sampling_kernel, temperature=temperature,
                               top_k=top_k, top_p=top_p, v=v, emit="tokens")
    operands = [logits]
    if sample:
        if gumbel is None:
            raise ValueError("temperature != 0 needs gumbel noise")
        operands.append(gumbel.astype(jnp.float32))
    return _run(kernel, operands, b, v, 1, jnp.int32)[:, 0]
