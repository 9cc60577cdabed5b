"""Fused decode megakernel: dispatch boundaries, the s-position
speculative-verify kernels, the sort-free sampling epilogue, the tp
collective/MLP overlap, and the engine-level greedy bit-parity matrix.

The PR's correctness contract is a single sentence: turning the
megakernel on must never move a greedy token. These tests pin that at
every layer — the kernel wrappers' supported() gates (so dispatch can't
silently mis-route a shape into the kernel), the s>1 kernels against the
masked-einsum reference, the Pallas filter against the sorted reference
BITWISE, the ring all-reduce against psum BITWISE at tp=2, and finally
the ServingEngine matrix (dense/paged x fp32/int8 x spec on/off x tp)
composed-vs-fused."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import Mesh, PartitionSpec as P


# ---------------------------------------------------------------------------
# dispatch boundaries: supported() is the router — it must say no at
# every edge the kernels can't take, and yes for the shapes they claim
# ---------------------------------------------------------------------------

class TestDispatchBoundaries:

    def test_spec_width_gates_both_layouts(self):
        from deepspeed_tpu.ops.pallas.decode_attention import (
            MAX_SPEC_S, paged_decode_supported, pallas_decode_supported)
        for s in range(1, MAX_SPEC_S + 1):
            assert pallas_decode_supported(4, 512, 2, 64, jnp.float32, s)
            assert paged_decode_supported(4, 32, 2, 64, jnp.int8, s)
        for s in (0, -1, MAX_SPEC_S + 1, 64):
            assert not pallas_decode_supported(4, 512, 2, 64,
                                               jnp.float32, s)
            assert not paged_decode_supported(4, 32, 2, 64, jnp.int8, s)

    def test_lane_misaligned_heads_rejected(self):
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_supported, pallas_decode_supported)
        # h*d = 60 and 96: not multiples of the 128-lane tile
        for h, d in ((3, 20), (3, 32)):
            assert not pallas_decode_supported(4, 512, h, d, jnp.float32)
            assert not paged_decode_supported(4, 32, h, d, jnp.float32)

    def test_sub_minimum_block_sizes_rejected(self):
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_supported)
        # f32 sublane is 8; int8 sublane is 32 (the DMA unit)
        assert paged_decode_supported(4, 8, 2, 64, jnp.float32)
        assert not paged_decode_supported(4, 4, 2, 64, jnp.float32)
        assert paged_decode_supported(4, 32, 2, 64, jnp.int8)
        assert not paged_decode_supported(4, 16, 2, 64, jnp.int8)
        assert not paged_decode_supported(4, 24, 2, 64, jnp.int8)

    def test_vmem_budget_rejects_oversized_windows(self):
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_supported)
        # blow the double-buffered staging window: huge b * block * h*d
        assert not paged_decode_supported(256, 512, 16, 128, jnp.float32)


# ---------------------------------------------------------------------------
# s>1 kernels vs the masked-einsum reference (interpret mode on CPU)
# ---------------------------------------------------------------------------

def _spec_ref(q, ck4, cv4, cache_len, scale):
    from deepspeed_tpu.ops.pallas.decode_attention import (
        masked_cache_attention)
    s_q = q.shape[1]
    return masked_cache_attention(q, ck4, cv4,
                                  jnp.asarray(cache_len) - s_q, scale)


@pytest.mark.parametrize("s_q", [2, 5, 8])
@pytest.mark.parametrize("quantized", [False, True])
def test_dense_spec_kernel_parity(s_q, quantized):
    """The s-position dense kernel (block-diagonal qmat, staggered causal
    mask, in-window int8 dequant) against the masked einsum at mixed
    per-row fills. Argmax agreement is the greedy contract; values agree
    to online-softmax tolerance."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        decode_attention, pallas_decode_supported)
    from deepspeed_tpu.ops.quantizer import quantize_kv
    b, S, h, d = 2, 256, 2, 64
    assert pallas_decode_supported(
        b, S, h, d, jnp.int8 if quantized else jnp.float32, s_q)
    rng = np.random.default_rng(s_q * 10 + quantized)
    q = jnp.asarray(rng.standard_normal((b, s_q, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, S, h * d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, S, h * d)), jnp.float32)
    fills = jnp.asarray([s_q + 3, 200], jnp.int32)
    scale = 1.0 / np.sqrt(d)
    kw = {}
    if quantized:
        k, ks = quantize_kv(k)
        v, vs = quantize_kv(v)
        kw = dict(k_scale=ks[..., 0], v_scale=vs[..., 0])
        kd = (k.astype(jnp.float32) * ks).reshape(b, S, h, d)
        vd = (v.astype(jnp.float32) * vs).reshape(b, S, h, d)
    else:
        kd, vd = k.reshape(b, S, h, d), v.reshape(b, S, h, d)

    out = decode_attention(q, k, v, fills, scale=scale, **kw)
    ref = _spec_ref(q, kd, vd, fills, scale)
    assert out.shape == (b, s_q, h, d)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        np.argmax(np.asarray(out).reshape(b * s_q, h * d), -1),
        np.argmax(np.asarray(ref).reshape(b * s_q, h * d), -1))


@pytest.mark.parametrize("fills", [(3, 32), (31, 32), (5, 187),
                                   (192 - 3, 64)])
def test_paged_spec_kernel_boundary_fills(fills):
    """The paged s>1 kernel at block-boundary fills (fill == s_q so
    nothing precedes the verify window, exactly one block, mid-block,
    cache-full) — impl='pallas' vs the gather+einsum fallback, int8
    pools. cache_len counts the s_q in-flight tokens, so s_q is the
    minimum legal fill."""
    from deepspeed_tpu.ops.pallas.decode_attention import (
        paged_decode_attention, paged_decode_supported)
    from deepspeed_tpu.ops.quantizer import quantize_kv
    b, h, d, bs, s_q = 2, 2, 64, 32, 3
    S = 192
    rng = np.random.default_rng(sum(fills))
    q = jnp.asarray(rng.standard_normal((b, s_q, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, S, h * d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, S, h * d)), jnp.float32)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    bpr = S // bs
    table = jnp.asarray(
        np.arange(b * bpr, dtype=np.int32).reshape(b, bpr))
    kp = kq.reshape(b * bpr, bs, h * d)
    vp = vq.reshape(b * bpr, bs, h * d)
    ksp = ks[..., 0].reshape(b * bpr, bs)
    vsp = vs[..., 0].reshape(b * bpr, bs)
    assert paged_decode_supported(b, bs, h, d, kp.dtype, s_q)
    clen = jnp.asarray(fills, jnp.int32)
    out = paged_decode_attention(q, kp, vp, table, clen, scale=0.125,
                                 k_scale=ksp, v_scale=vsp, impl="pallas")
    ref = paged_decode_attention(q, kp, vp, table, clen, scale=0.125,
                                 k_scale=ksp, v_scale=vsp, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(
        np.argmax(np.asarray(out).reshape(b * s_q, h * d), -1),
        np.argmax(np.asarray(ref).reshape(b * s_q, h * d), -1))


# ---------------------------------------------------------------------------
# sort-free sampling epilogue: the filter is BITWISE vs the sorted
# reference — that equality is what makes the megakernel flag safe
# ---------------------------------------------------------------------------

class TestFusedSampling:

    def _logits(self, b=3, v=256, seed=0, ties=False):
        rng = np.random.default_rng(seed)
        x = rng.standard_normal((b, v)).astype(np.float32)
        if ties:
            x[:, 17] = x[:, 5]          # exact duplicate values
            x[0, 200] = x[0].max()      # duplicate maximum
        return jnp.asarray(x)

    @pytest.mark.parametrize("t,k,p", [
        (1.0, 8, None), (0.7, None, 0.9), (1.3, 4, 0.5),
        (1.0, None, None), (1.0, 1, None), (1.0, 256, None),
        (0.9, None, 1.0), (1.0, 3, 0.99),
    ])
    def test_filter_bitwise_vs_reference(self, t, k, p):
        from deepspeed_tpu.ops.pallas.sampling import (
            sampling_supported, threshold_filter_logits)
        from deepspeed_tpu.serving.sampling import filter_logits
        logits = self._logits(ties=True)
        assert sampling_supported(*logits.shape)
        ref = filter_logits(logits, t, k, p)
        got = threshold_filter_logits(logits, t, k, p)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_greedy_first_index_on_ties(self):
        from deepspeed_tpu.ops.pallas.sampling import fused_sample
        logits = self._logits(ties=True)
        toks = fused_sample(logits, None, 0.0, None, None)
        np.testing.assert_array_equal(
            np.asarray(toks), np.argmax(np.asarray(logits), -1))

    def test_fused_sample_tokens_greedy_bitwise(self):
        from deepspeed_tpu.serving.sampling import (fused_sample_tokens,
                                                    sample_tokens)
        logits = self._logits(seed=7)
        ref = sample_tokens(logits, None, 0.0, None, None)
        got = fused_sample_tokens(logits, None, 0.0, None, None)
        np.testing.assert_array_equal(np.asarray(ref), np.asarray(got))

    def test_temperature_draws_stay_inside_the_filter(self):
        """Gumbel-max draws must land only on tokens the filter kept."""
        from deepspeed_tpu.serving.sampling import (filter_logits,
                                                    fused_sample_tokens)
        logits = self._logits(b=8, seed=3)
        kept = np.asarray(filter_logits(logits, 0.8, 4, None)) > -1e9
        for seed in range(4):
            toks = np.asarray(fused_sample_tokens(
                logits, jax.random.PRNGKey(seed), 0.8, 4, None))
            assert kept[np.arange(8), toks].all()
        # determinism under the same key
        a = fused_sample_tokens(logits, jax.random.PRNGKey(5), 0.8, 4)
        bb = fused_sample_tokens(logits, jax.random.PRNGKey(5), 0.8, 4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(bb))

    def test_unsupported_vocab_raises(self):
        from deepspeed_tpu.ops.pallas import KernelUnsupported
        from deepspeed_tpu.ops.pallas.sampling import sampling_supported
        from deepspeed_tpu.serving.sampling import fused_filter_logits
        assert not sampling_supported(2, 100)
        assert not sampling_supported(2, 257 * 1024)
        logits = jnp.zeros((2, 100), jnp.float32)
        with pytest.raises(KernelUnsupported, match="vocab 100"):
            fused_filter_logits(logits, 0.7, 5, 0.9)


# ---------------------------------------------------------------------------
# tp collective/MLP overlap
# ---------------------------------------------------------------------------

class TestTpOverlap:

    def _mesh(self, n):
        devs = jax.devices()
        if len(devs) < n:
            pytest.skip(f"needs {n} devices")
        return Mesh(np.array(devs[:n]), ("tp",))

    def _ring_vs_psum(self, n, rows=8, cols=16):
        from deepspeed_tpu.ops.tp_overlap import _ring_local
        from jax import shard_map
        mesh = self._mesh(n)
        x = jnp.asarray(
            np.random.default_rng(n).standard_normal((rows, cols)),
            jnp.float32)

        def f(x):
            r = jax.lax.axis_index("tp")
            part = x * (r + 1).astype(x.dtype)   # distinct partials
            ring = _ring_local(part, axis_name="tp", n=n)
            ps = jax.lax.psum(part, "tp")
            return ring, ps

        spec = P(None, None)
        return shard_map(f, mesh=mesh, in_specs=(spec,),
                         out_specs=(spec, spec), check_vma=False)(x)

    def test_ring_bitwise_psum_at_tp2(self):
        """One add per element either way at n=2 — BITWISE, which is
        what keeps deferred-collective greedy decode bit-identical."""
        ring, ps = self._ring_vs_psum(2)
        np.testing.assert_array_equal(np.asarray(ring), np.asarray(ps))

    def test_ring_allclose_psum_at_tp4(self):
        ring, ps = self._ring_vs_psum(4)
        np.testing.assert_allclose(np.asarray(ring), np.asarray(ps),
                                   rtol=1e-6, atol=1e-6)

    def test_ring_allreduce_shape_guard(self):
        from deepspeed_tpu.ops.tp_overlap import ring_allreduce
        mesh = self._mesh(2)
        with pytest.raises(ValueError):
            ring_allreduce(jnp.ones((3, 4)), mesh)

    def test_defer_is_identity_math(self):
        """The constraint is a layout statement: under a tp=2 constraint
        mesh the values are bitwise-unchanged; with no tp axis (or a
        non-dividing hidden dim) the input passes through untouched."""
        from deepspeed_tpu.ops.tp_overlap import (defer_attn_allreduce,
                                                  overlap_supported)
        from deepspeed_tpu.parallel.mesh import use_constraint_mesh
        mesh = self._mesh(2)
        y = jnp.asarray(
            np.random.default_rng(0).standard_normal((2, 4, 16)),
            jnp.float32)
        with use_constraint_mesh(mesh):
            out = jax.jit(defer_attn_allreduce)(y)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(y))
        # unsupported shapes fall through as the SAME array
        y_odd = jnp.ones((2, 4, 15))
        assert not overlap_supported(y_odd, mesh)
        assert defer_attn_allreduce(y_odd, mesh=mesh) is y_odd
        assert defer_attn_allreduce(y, mesh=None) is not None

    def test_overlap_step_model(self):
        from deepspeed_tpu.ops.tp_overlap import decode_step_overlap_model
        m = decode_step_overlap_model(1.0, 0.4, 0.6)
        assert m["step_unhidden_s"] == pytest.approx(2.0)
        assert m["step_overlapped_s"] == pytest.approx(1.6)
        assert m["overlap_ratio"] == pytest.approx(0.8)
        assert m["hidden_s"] == pytest.approx(0.4)

    def test_tp_overlap_requires_parallel_residual(self):
        from deepspeed_tpu.models.gpt import GPTConfig
        with pytest.raises(ValueError):
            GPTConfig(vocab_size=64, max_seq_len=32, num_layers=1,
                      num_heads=2, d_model=32, d_ff=64, tp_overlap=True)


# ---------------------------------------------------------------------------
# engine-level greedy bit-parity matrix: the megakernel flag must never
# move a token, in any cache layout / dtype / decode mode
# ---------------------------------------------------------------------------

def _mk_model(vocab=128, parallel_residual=False):
    """vocab 128 (lane-aligned) so the fused sampling kernel actually
    engages rather than falling back."""
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    cfg = GPTConfig(vocab_size=vocab, max_seq_len=48, num_layers=2,
                    num_heads=2, d_model=32, d_ff=64, dtype=jnp.float32,
                    param_dtype=jnp.float32, remat=False,
                    parallel_residual=parallel_residual)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def mega_model():
    return _mk_model()


def _serve(model, params, prompts, megakernel, prepare=None, **kw):
    from deepspeed_tpu.serving import ServingEngine
    eng = ServingEngine(model, model_parameters=params,
                        dtype=jnp.float32, max_batch=4, max_prompt_len=16,
                        decode_chunk=4, megakernel=megakernel, **kw)
    if prepare is not None:
        prepare(eng)
    return eng, eng.run([p.copy() for p in prompts], max_new_tokens=10)


class TestMegakernelEngineParity:

    def _prompts(self, vocab=128, n=4):
        rng = np.random.default_rng(11)
        return [rng.integers(1, vocab, int(rng.integers(3, 12)))
                .astype(np.int32) for _ in range(n)]

    @pytest.mark.parametrize("paged", [False, True])
    @pytest.mark.parametrize("kv_dtype", [None, "int8"])
    @pytest.mark.parametrize("speculative", [False, True])
    def test_greedy_bit_parity(self, mega_model, paged, kv_dtype,
                               speculative):
        model, params = mega_model
        prompts = self._prompts()
        kw = dict(paged=paged, speculative=speculative)
        if kv_dtype:
            kw["kv_dtype"] = kv_dtype
        _, base = _serve(model, params, prompts, megakernel=False, **kw)
        _, mega = _serve(model, params, prompts, megakernel=True, **kw)
        for b, g in zip(base, mega):
            assert g.status == "done"
            np.testing.assert_array_equal(b.output_ids, g.output_ids)

    def test_variant_name_and_cache_isolation(self, mega_model):
        from deepspeed_tpu.analysis.auditor import TraceAuditor
        model, params = mega_model
        prompts = self._prompts()
        with TraceAuditor(audit_jaxprs=False) as aud:
            _serve(model, params, prompts, megakernel=True)
        assert aud.compiles("decode_chunk_megakernel_fn") >= 1
        assert aud.compiles("decode_chunk_fn") == 0

    def test_sampled_decode_deterministic_under_seed(self, mega_model):
        """temperature>0 through the fused Gumbel-max epilogue: same
        engine seed -> identical streams, different seed -> different."""
        from deepspeed_tpu.serving import ServingEngine
        model, params = mega_model
        prompts = self._prompts()

        def run(seed):
            eng = ServingEngine(model, model_parameters=params,
                                dtype=jnp.float32, max_batch=4,
                                max_prompt_len=16, decode_chunk=4,
                                megakernel=True, temperature=1.0,
                                top_k=8, seed=seed)
            return [r.tokens for r in
                    eng.run(list(prompts), max_new_tokens=8)]

        assert run(0) == run(0)
        assert run(0) != run(1)

    def test_tp2_megakernel_bit_parity_with_overlap(self, telemetry_on):
        """tp=2 + parallel residual: the megakernel engine flips
        cfg.tp_overlap on, decodes under its own variant name, and the
        deferred RS/AG collective keeps greedy bit-identical to the
        composed tp=2 engine (two-term sum either way). Its chunks' device
        time is on the serve loop's device timeline (tp > 1 is one SPMD
        queue), where the launch-to-retire wall seconds of a gauge of its
        own used to be."""
        if len(jax.devices()) < 2:
            pytest.skip("needs 2 devices")
        from deepspeed_tpu.analysis.auditor import TraceAuditor
        from tests.test_device_timeline import never_ready
        model, params = _mk_model(parallel_residual=True)
        prompts = self._prompts()
        _, base = _serve(model, params, prompts, megakernel=False, tp=2)
        telemetry_on.clear()
        # never_ready: every sync finds its array not ready (on the CPU a
        # toy chunk may have ended before the host looks) and is a stamp
        with TraceAuditor(audit_jaxprs=False) as aud:
            eng, mega = _serve(model, params, prompts, megakernel=True,
                               prepare=never_ready, tp=2)
        assert eng.module.cfg.tp_overlap is True
        assert eng._overlap_active
        stats = telemetry_on.span_stats()
        assert stats["serve/device_decode_chunk"]["count"] == \
            stats["serve/chunk_host_wait"]["count"] >= 2
        assert aud.compiles("decode_chunk_megakernel_tp2_fn") >= 1
        assert aud.compiles("decode_chunk_tp2_fn") == 0
        for b, g in zip(base, mega):
            assert g.status == "done"
            np.testing.assert_array_equal(b.output_ids, g.output_ids)
