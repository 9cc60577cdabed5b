"""The ONE sampling policy for serving: filter + draw, shared everywhere.

``filter_logits``/``sample_tokens`` used to live in serving/engine.py with
serving/speculative.py importing across — one reference, two call sites.
The fused Pallas epilogue (ops/pallas/sampling.py) adds a third consumer,
so the policy now lives here and CANNOT drift: the engine's sampler, the
speculative verifier's acceptance math (rejection resamples draw from the
SAME filtered distribution), and the megakernel epilogue all share this
module. engine.py re-exports both names for API stability.

``fused_filter_logits``/``fused_sample_tokens`` are the megakernel's
entry points to the sort-free Pallas kernel. A vocab the kernel's gate
refuses raises ``KernelUnsupported`` (the engine checks
``sampling_refusal`` at construction) — ``megakernel=True`` never runs the
sort-based reference under a name that says fused. Greedy draws are
bit-identical to the reference (the megakernel correctness contract);
temperature > 0 draws are distributionally identical but consume the rng
as Gumbel noise instead of ``jax.random.categorical``'s internal stream.
"""

from __future__ import annotations

from typing import Optional


def filter_logits(logits, temperature: float, top_k: Optional[int],
                  top_p: Optional[float] = None):
    """Temperature / top-k / nucleus (top-p) filtering over [..., V]
    logits, in f32. The filtered logits DEFINE the sampling distribution:
    ``sample_tokens`` draws ``categorical(filter_logits(...))``, and the
    speculative verifier (serving/speculative.verify_rejection) softmaxes
    the same function — acceptance math matches the sampler exactly
    because they share this code.

    Every temperature != 0 takes the same path (x / 1.0 is the bitwise
    identity, so temperature=1.0 no longer skips the scaling branch — the
    old ``not in (0.0, 1.0)`` guard forked the code path for no numeric
    effect). top-p keeps the smallest set of tokens whose cumulative
    probability reaches ``top_p`` (the argmax token always survives);
    applied after top-k when both are set."""
    import jax
    import jax.numpy as jnp
    logits = logits.astype(jnp.float32)
    if temperature != 0.0:
        logits = logits / temperature
    if top_k is not None:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -1e10, logits)
    if top_p is not None:
        srt = jnp.flip(jnp.sort(logits, axis=-1), axis=-1)
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep token i while the mass BEFORE it is < top_p: the first
        # token is always kept, and the set is the minimal one covering p
        keep = (cum - probs) < top_p
        kth = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1,
                      keepdims=True)
        logits = jnp.where(logits < kth, -1e10, logits)
    return logits


def sample_tokens(logits, rng, temperature: float, top_k: Optional[int],
                  top_p: Optional[float] = None):
    """Greedy / temperature / top-k / top-p sampling over [b, V] logits —
    the same policy as InferenceEngine.generate's sampler."""
    import jax
    import jax.numpy as jnp
    logits = filter_logits(logits, temperature, top_k, top_p)
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def fused_filter_logits(logits, temperature: float, top_k: Optional[int],
                        top_p: Optional[float] = None):
    """filter_logits through the sort-free Pallas kernel. Accepts
    [..., V]; the kernel sees rows."""
    from ..ops.pallas.sampling import threshold_filter_logits
    shape = logits.shape
    out = threshold_filter_logits(logits.reshape(-1, shape[-1]),
                                  temperature, top_k, top_p)
    return out.reshape(shape)


def fused_sample_tokens(logits, rng, temperature: float,
                        top_k: Optional[int],
                        top_p: Optional[float] = None):
    """sample_tokens through the fused Pallas epilogue (greedy stays
    bit-identical; temperature > 0 becomes Gumbel-max)."""
    import jax
    import jax.numpy as jnp
    from ..ops.pallas.sampling import fused_sample
    b, v = logits.shape
    gumbel = None
    if temperature != 0.0:
        gumbel = jax.random.gumbel(rng, (b, v), jnp.float32)
    return fused_sample(logits, gumbel, temperature, top_k, top_p)
