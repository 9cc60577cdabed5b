"""Speculative decoding + int8 KV cache (serving/speculative.py,
kv_dtype="int8").

Covers the three layers separately so a failure localizes:
  * NGramDrafter — pure-function proposal semantics on hand-built
    histories (periodic continuation, fallback repetition, batching).
  * verify_greedy / verify_rejection — the acceptance math, including
    the SEEDED DISTRIBUTION test: over many lanes the emitted-token
    marginal must match the target softmax exactly (the
    rejection-resampling identity), which is the property that makes
    sampled speculative decoding lossless.
  * ServingEngine integration — greedy outputs bit-identical to the
    sequential loops (dense AND paged), EOS/budget edge cases, seeded
    determinism at temperature > 0, and the int8 arena halving with
    dense==paged parity.
"""

import numpy as np
import pytest

from deepspeed_tpu.serving import ServingEngine


def _tiny(vocab=64, max_seq=48):
    import jax
    import jax.numpy as jnp
    from deepspeed_tpu.models.gpt import GPT, GPTConfig
    cfg = GPTConfig(vocab_size=vocab, max_seq_len=max_seq, num_layers=2,
                    num_heads=2, d_model=32, d_ff=64, dtype=jnp.float32,
                    param_dtype=jnp.float32, remat=False)
    model = GPT(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 4), jnp.int32))["params"]
    return model, params


@pytest.fixture(scope="module")
def tiny_engine():
    import jax.numpy as jnp
    import deepspeed_tpu as ds
    model, params = _tiny()
    return ds.init_inference(model, model_parameters=params,
                             dtype=jnp.float32)


# ------------------------------------------------------------ drafter
class TestNGramDrafter:
    def test_constructor_validation(self):
        from deepspeed_tpu.serving.speculative import NGramDrafter
        with pytest.raises(ValueError):
            NGramDrafter(k=0)
        with pytest.raises(ValueError):
            NGramDrafter(k=4, n=0)

    def test_periodic_history_proposes_continuation(self):
        """A repeating motif must be continued: the trailing n-gram
        matches its previous occurrence and the proposal walks the cycle
        (wrapping with the period past the matched span)."""
        import jax.numpy as jnp
        from deepspeed_tpu.serving.speculative import NGramDrafter
        S = 16
        row = ([1, 2, 3] * 6)[:8] + [0] * (S - 8)    # 1 2 3 1 2 3 1 2
        hist = jnp.asarray([row], jnp.int32)
        pos = jnp.asarray([7], jnp.int32)            # last token == 2
        tok = hist[:, 7]
        drafts = np.asarray(NGramDrafter(k=4, n=2).propose(hist, tok, pos))
        # sequential continuation of the motif after ...1 2 is 3 1 2 3
        np.testing.assert_array_equal(drafts[0], [3, 1, 2, 3])

    def test_no_match_falls_back_to_last_token(self):
        import jax.numpy as jnp
        from deepspeed_tpu.serving.speculative import NGramDrafter
        hist = jnp.asarray([list(range(10, 26))], jnp.int32)  # all distinct
        pos = jnp.asarray([5], jnp.int32)
        tok = hist[:, 5]
        drafts = np.asarray(NGramDrafter(k=3, n=2).propose(hist, tok, pos))
        np.testing.assert_array_equal(drafts[0], [int(tok[0])] * 3)

    def test_batched_lanes_are_independent(self):
        import jax.numpy as jnp
        from deepspeed_tpu.serving.speculative import NGramDrafter
        S = 16
        periodic = ([7, 9] * 8)[:S]                  # bigram (7,9) repeats
        distinct = list(range(30, 30 + S))
        hist = jnp.asarray([periodic, distinct], jnp.int32)
        pos = jnp.asarray([5, 5], jnp.int32)
        tok = hist[jnp.arange(2), pos]
        drafts = np.asarray(NGramDrafter(k=2, n=2).propose(hist, tok, pos))
        # periodic lane continues the cycle; distinct lane repeats
        assert list(drafts[0]) == [periodic[6], periodic[7]]
        assert list(drafts[1]) == [distinct[5]] * 2


# ---------------------------------------------------------- verifiers
class TestVerify:
    def test_verify_greedy_accepts_matching_prefix(self):
        import jax.numpy as jnp
        from deepspeed_tpu.serving.speculative import verify_greedy
        B, k, V = 3, 2, 8
        tgt = np.array([[1, 2, 3], [4, 5, 6], [2, 0, 7]], np.int32)
        logits = np.full((B, k + 1, V), -5.0, np.float32)
        for b in range(B):
            for j in range(k + 1):
                logits[b, j, tgt[b, j]] = 5.0
        drafts = np.array([[1, 2],      # full match      -> acc 2
                           [4, 9],      # mismatch at 1   -> acc 1
                           [9, 0]],     # mismatch at 0   -> acc 0
                          np.int32)
        emitted, acc = verify_greedy(jnp.asarray(logits),
                                     jnp.asarray(drafts))
        np.testing.assert_array_equal(np.asarray(acc), [2, 1, 0])
        # emitted IS argmax(target) at every position: the accepted
        # prefix equals the drafts and position acc is the correction
        np.testing.assert_array_equal(np.asarray(emitted), tgt)

    def test_rejection_resampling_marginal_matches_target(self):
        """The exactness property, measured: with every lane fed the
        SAME target logits and drafts, the emitted-token histogram must
        reproduce the target softmax at position 0 unconditionally, and
        at position 1 conditioned on position 0 being accepted (the
        per-position rejection-resampling identity). Seeded, 20k lanes,
        tolerances several sigma above the binomial noise floor."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.serving.speculative import verify_rejection
        B, k, V = 20000, 2, 8
        rng = np.random.default_rng(7)
        base = rng.normal(size=(1, k + 1, V)).astype(np.float32)
        logits = jnp.asarray(np.tile(base, (B, 1, 1)))
        p = np.asarray(jax.nn.softmax(jnp.asarray(base[0]), axis=-1))
        d0 = int(np.argmax(p[0]))                 # high acceptance at 0
        d1 = int(np.argsort(p[1])[V // 2])        # middling acceptance
        drafts = jnp.asarray(np.tile([[d0, d1]], (B, 1)).astype(np.int32))
        emitted, acc = verify_rejection(logits, drafts,
                                        jax.random.PRNGKey(0),
                                        1.0, None, None)
        emitted, acc = np.asarray(emitted), np.asarray(acc)
        freq0 = np.bincount(emitted[:, 0], minlength=V) / B
        assert np.max(np.abs(freq0 - p[0])) < 0.015
        sel = acc >= 1
        assert sel.sum() > B * p[0, d0] * 0.8     # acceptance ~ p0(d0)
        freq1 = np.bincount(emitted[sel, 1], minlength=V) / sel.sum()
        assert np.max(np.abs(freq1 - p[1])) < 0.03
        # a rejected position resamples from the RESIDUAL: the draft's
        # index carries zero mass, so it can never be re-emitted there
        assert not np.any(emitted[acc == 0, 0] == d0)
        assert not np.any(emitted[(acc == 1), 1] == d1)

    def test_rejection_respects_top_k_filter(self):
        """Acceptance math runs against the FILTERED distribution —
        every emitted token inside the valid prefix must come from each
        position's top-k set, exactly like the sequential sampler."""
        import jax
        import jax.numpy as jnp
        from deepspeed_tpu.serving.speculative import verify_rejection
        B, k, V, topk = 512, 2, 16, 3
        rng = np.random.default_rng(3)
        logits_np = rng.normal(size=(B, k + 1, V)).astype(np.float32)
        allowed = np.argsort(logits_np, axis=-1)[..., -topk:]
        # draft from inside the nucleus so acceptance is exercised too
        drafts = jnp.asarray(allowed[:, :k, -1].astype(np.int32))
        emitted, acc = verify_rejection(jnp.asarray(logits_np), drafts,
                                        jax.random.PRNGKey(1),
                                        1.0, topk, None)
        emitted, acc = np.asarray(emitted), np.asarray(acc)
        for b in range(B):
            for j in range(int(acc[b]) + 1):
                assert emitted[b, j] in allowed[b, j]


# ------------------------------------------------------ engine: spec
class TestSpeculativeEngine:
    def test_spec_greedy_parity_dense(self, tiny_engine):
        """Speculative greedy output is BIT-identical to the plain scan
        at a chunk of one step and to generate(): mixed-length prompts, K not dividing the
        budget, mid-chunk EOS, and EOS on the very first token."""
        rng = np.random.default_rng(4)
        vocab = tiny_engine.module.cfg.vocab_size
        prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
                   for n in [3, 7, 5, 9, 4, 6]]
        pt = ServingEngine(engine=tiny_engine, max_batch=3,
                           max_prompt_len=16, max_queue=8, decode_chunk=1)
        sp = ServingEngine(engine=tiny_engine, max_batch=3,
                           max_prompt_len=16, max_queue=8, decode_chunk=4,
                           speculative=True, spec_k=3)

        def both(**kw):
            a = pt.run(list(prompts), **kw)
            b = sp.run(list(prompts), **kw)
            for x, y in zip(a, b):
                assert x.status == y.status == "done"
                np.testing.assert_array_equal(x.output_ids, y.output_ids)
            return a

        base = both(max_new_tokens=11)
        ref = np.asarray(tiny_engine.generate(
            prompts[0][None], max_new_tokens=11, temperature=0.0))[0]
        np.testing.assert_array_equal(base[0].output_ids, ref)
        mid_eos = base[0].tokens[2]
        both(max_new_tokens=11, eos_token_id=int(mid_eos))
        first_eos = base[1].tokens[0]
        res = both(max_new_tokens=11, eos_token_id=int(first_eos))
        assert any(len(r.tokens) == 1 for r in res)
        assert sp.metrics.spec_proposed > 0
        assert 0.0 <= sp.metrics.spec_acceptance_rate <= 1.0

    def test_spec_greedy_parity_paged(self, tiny_engine):
        """Same tokens through the paged arena: speculative writes land
        through block tables (out-of-reservation writes drop on the
        sentinel block) without changing a single emitted token."""
        rng = np.random.default_rng(5)
        vocab = tiny_engine.module.cfg.vocab_size
        prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
                   for n in [16, 7, 12, 4]]
        pt = ServingEngine(engine=tiny_engine, max_batch=4,
                           max_prompt_len=16, max_queue=8, decode_chunk=1)
        sp = ServingEngine(engine=tiny_engine, max_batch=4,
                           max_prompt_len=16, max_queue=8, decode_chunk=4,
                           speculative=True, paged=True, prefix_cache=False)
        a = pt.run(list(prompts), max_new_tokens=10)
        b = sp.run(list(prompts), max_new_tokens=10)
        for x, y in zip(a, b):
            assert x.status == y.status == "done"
            np.testing.assert_array_equal(x.output_ids, y.output_ids)

    def test_spec_sampled_deterministic_under_seed(self, tiny_engine):
        """temperature/top-k/top-p sampling through the speculative loop:
        same engine seed -> identical streams; different seed ->
        different. Rejection-resampling consumes per-step PRNG splits
        carried in the scan, so determinism is structural."""
        rng = np.random.default_rng(6)
        vocab = tiny_engine.module.cfg.vocab_size
        prompts = [rng.integers(0, vocab, (5,)).astype(np.int32)
                   for _ in range(3)]

        def run(seed):
            serving = ServingEngine(engine=tiny_engine, max_batch=3,
                                    max_prompt_len=8, decode_chunk=4,
                                    speculative=True, temperature=1.0,
                                    top_k=8, top_p=0.95, seed=seed)
            res = serving.run(list(prompts), max_new_tokens=8)
            assert all(r.status == "done" for r in res)
            assert all(0 <= t < vocab for r in res for t in r.tokens)
            return [r.tokens for r in res]

        assert run(seed=0) == run(seed=0)
        assert run(seed=0) != run(seed=1)


# -------------------------------------------------- engine: int8 KV
class TestInt8KV:
    def test_int8_dense_paged_parity_and_arena_halving(self, tiny_engine):
        """int8 KV is one quantization decision with two layouts: dense
        and paged arenas must emit identical greedy tokens, and the
        arena accounting must show the payload at <= half the
        fp-equivalent bytes with the saved delta reported."""
        rng = np.random.default_rng(8)
        vocab = tiny_engine.module.cfg.vocab_size
        prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
                   for n in [16, 7, 12, 4]]
        dense = ServingEngine(engine=tiny_engine, max_batch=4,
                              max_prompt_len=16, max_queue=8,
                              decode_chunk=4, kv_dtype="int8")
        paged = ServingEngine(engine=tiny_engine, max_batch=4,
                              max_prompt_len=16, max_queue=8,
                              decode_chunk=4, kv_dtype="int8", paged=True,
                              prefix_cache=False)
        a = dense.run(list(prompts), max_new_tokens=10)
        b = paged.run(list(prompts), max_new_tokens=10)
        for x, y in zip(a, b):
            assert x.status == y.status == "done"
            np.testing.assert_array_equal(x.output_ids, y.output_ids)
        for eng in (dense, paged):
            rep = eng.kv.arena_report()
            assert rep["int8_payload_bytes"] > 0
            assert rep["scale_bytes"] > 0
            assert rep["kv_bytes"] <= 0.5 * rep["kv_bytes_fp_equiv"]
            assert (rep["kv_bytes_saved"]
                    == rep["kv_bytes_fp_equiv"] - rep["kv_bytes"])
        # an fp arena reports nothing saved — same key, zero delta
        fp = ServingEngine(engine=tiny_engine, max_batch=4,
                           max_prompt_len=16, max_queue=8, decode_chunk=4)
        assert fp.kv.arena_report()["kv_bytes_saved"] == 0

    def test_spec_over_int8_arena_parity(self, tiny_engine):
        """The combined case: speculative decode over the quantized
        arena matches the non-speculative int8 scan at K = 1 — the
        drafter/verifier sees quantized-model logits, so exactness holds
        against the int8 model, not the fp one."""
        rng = np.random.default_rng(9)
        vocab = tiny_engine.module.cfg.vocab_size
        prompts = [rng.integers(0, vocab, (n,)).astype(np.int32)
                   for n in [3, 9, 6]]
        pt = ServingEngine(engine=tiny_engine, max_batch=3,
                           max_prompt_len=16, max_queue=8, decode_chunk=1,
                           kv_dtype="int8")
        sp = ServingEngine(engine=tiny_engine, max_batch=3,
                           max_prompt_len=16, max_queue=8, decode_chunk=4,
                           speculative=True, kv_dtype="int8")
        a = pt.run(list(prompts), max_new_tokens=9)
        b = sp.run(list(prompts), max_new_tokens=9)
        for x, y in zip(a, b):
            assert x.status == y.status == "done"
            np.testing.assert_array_equal(x.output_ids, y.output_ids)
