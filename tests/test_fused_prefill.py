"""Fused chunked prefill: prompts consumed as in-scan chunks by the
SAME scan body that decodes (ROADMAP item 4), replacing the separate
bucketed prefill program behind a per-lane prefill/decode mode mask.

Covered here:
  * greedy bit-parity fused-vs-bucketed across mixed prompt lengths
    (prompt > one chunk), mid-chunk EOS, first-token EOS, paged + dense,
    speculative (greedy), int8 KV, and the sp-threshold route;
  * staggered mid-prompt admission (new requests arriving while other
    lanes are still consuming prompt chunks);
  * paged PrefixCache hits short-circuiting every remaining chunk;
  * scheduler chunk-token-budget admission (token_budget / lane_cost);
  * engine budget accounting (_budget_drain / _lane_cost);
  * AdmissionConfig.cost_tokens (ceil(L/C) + max_new fused estimate vs
    the bucket-weight estimate) and the frontend auto-wiring of it.
"""

import numpy as np
import pytest

from deepspeed_tpu.serving import (ContinuousBatchScheduler, Request,
                                   ServingEngine, SlotAllocator)


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


@pytest.fixture(scope="module")
def prompts():
    rng = np.random.default_rng(0)
    # mixed lengths straddling the 4-token chunk: several prompts need
    # multiple chunks, one fits in a single chunk with padding
    lens = [3, 7, 5, 9, 4, 13, 6, 11]
    return [rng.integers(0, 64, (n,)).astype(np.int32) for n in lens]


def _pair(tiny_engine, **extra):
    """A bucketed reference engine and a fused engine, same config."""
    base = dict(engine=tiny_engine, max_batch=3, max_prompt_len=16,
                max_queue=16, decode_chunk=4)
    base.update(extra)
    ref = ServingEngine(**base)
    fz = ServingEngine(fused_prefill=True, prefill_chunk=4, **base)
    return ref, fz


def _assert_parity(a, b):
    assert len(a) == len(b)
    for x, y in zip(a, b):
        assert x.status == y.status == "done", (x.status, y.status)
        np.testing.assert_array_equal(x.output_ids, y.output_ids)


# ------------------------------------------------ greedy bit-parity matrix
class TestFusedParity:
    def test_dense_mixed_lengths(self, tiny_engine, prompts):
        """More requests than slots, prompts spanning 1..4 chunks: the
        in-scan prompt path must be bit-identical to bucketed prefill,
        and every prompt token must be consumed in-scan."""
        ref, fz = _pair(tiny_engine)
        a = ref.run(list(prompts), max_new_tokens=8)
        b = fz.run(list(prompts), max_new_tokens=8)
        _assert_parity(a, b)
        assert fz.inline_prefill_tokens == sum(len(p) for p in prompts)
        assert fz.metrics.prefill_programs == 0

    def test_mid_chunk_and_first_token_eos(self, tiny_engine, prompts):
        """EOS inside a scan chunk and EOS on the very first (prompt-
        completing) token both terminate identically to bucketed."""
        ref, fz = _pair(tiny_engine)
        a = ref.run(list(prompts), max_new_tokens=8)
        mid_eos = int(a[0].tokens[2])
        first_eos = int(a[1].tokens[0])
        for eos in (mid_eos, first_eos):
            x = ref.run(list(prompts), max_new_tokens=8, eos_token_id=eos)
            y = fz.run(list(prompts), max_new_tokens=8, eos_token_id=eos)
            _assert_parity(x, y)
        assert any(len(r.tokens) == 1
                   for r in fz.run(list(prompts), max_new_tokens=8,
                                   eos_token_id=first_eos))

    def test_paged(self, tiny_engine, prompts):
        ref, fz = _pair(tiny_engine, paged=True, kv_block_size=8)
        a = ref.run(list(prompts), max_new_tokens=8)
        b = fz.run(list(prompts), max_new_tokens=8)
        _assert_parity(a, b)
        assert fz.inline_prefill_tokens > 0

    def test_speculative_greedy(self, tiny_engine, prompts):
        ref, fz = _pair(tiny_engine, speculative=True, spec_k=3)
        a = ref.run(list(prompts), max_new_tokens=8)
        b = fz.run(list(prompts), max_new_tokens=8)
        _assert_parity(a, b)

    def test_int8_kv(self, tiny_engine, prompts):
        ref, fz = _pair(tiny_engine, kv_dtype="int8")
        a = ref.run(list(prompts), max_new_tokens=8)
        b = fz.run(list(prompts), max_new_tokens=8)
        _assert_parity(a, b)

    def test_sp_threshold_route(self, tiny_engine, prompts):
        """Prompts at/above sp_prefill_threshold take the one sequence-
        parallel bucketed prefill and join the scan in decode mode; on a
        1-chip mesh every sharding constraint is the identity, so the
        outputs stay bitwise equal to the plain bucketed reference."""
        ref, _ = _pair(tiny_engine)
        a = ref.run(list(prompts), max_new_tokens=8)
        spf = ServingEngine(engine=tiny_engine, max_batch=3,
                            max_prompt_len=16, max_queue=16,
                            decode_chunk=4, fused_prefill=True,
                            prefill_chunk=4, sp_prefill_threshold=9)
        b = spf.run(list(prompts), max_new_tokens=8)
        _assert_parity(a, b)
        # the short prompts still went in-scan; the >=9 ones did not
        short_tokens = sum(len(p) for p in prompts if len(p) < 9)
        assert spf.inline_prefill_tokens == short_tokens

    def test_staggered_mid_prompt_admission(self, tiny_engine, prompts):
        """Requests submitted while other lanes are still mid-prompt
        (multi-chunk prefill in flight) must not perturb either side:
        drive both engines pump-by-pump with identical submission
        schedules and compare the full token streams."""
        def drive(serving):
            reqs = []
            pending = [p.copy() for p in prompts]
            for _ in range(2):                       # two t0 submissions
                r = Request(prompt=pending.pop(0), max_new_tokens=8)
                serving.submit(r)
                reqs.append(r)
            pumps = 0
            while serving.scheduler.has_work() or serving.chunk_in_flight \
                    or pending:
                if pending and pumps % 2 == 1:       # mid-stream arrivals
                    r = Request(prompt=pending.pop(0), max_new_tokens=8)
                    serving.submit(r)
                    reqs.append(r)
                serving.pump()
                pumps += 1
            return reqs

        ref, fz = _pair(tiny_engine)
        a = drive(ref)
        b = drive(fz)
        _assert_parity(a, b)

    def test_prefix_cache_hit_short_circuits_chunks(self, tiny_engine,
                                                    prompts):
        """A paged prefix-cache HIT replays the stored first token and
        enters the scan in decode mode — zero prompt chunks consumed for
        the hit, bit-identical output."""
        from deepspeed_tpu import telemetry
        telemetry.enable()
        try:
            telemetry.get_runtime().clear()
            ph = ServingEngine(engine=tiny_engine, max_batch=2,
                               max_prompt_len=16, max_queue=16,
                               decode_chunk=4, paged=True, kv_block_size=8,
                               fused_prefill=True, prefill_chunk=4)
            shared = prompts[5]                      # 13 tokens: 4 chunks
            r1 = ph.run([shared.copy()], max_new_tokens=6)
            inline_after_miss = ph.inline_prefill_tokens
            r2 = ph.run([shared.copy()], max_new_tokens=6)
            np.testing.assert_array_equal(r1[0].output_ids,
                                          r2[0].output_ids)
            hits = telemetry.get_runtime().counter_totals().get(
                "serve/prefix_cache_hit", 0)
            assert hits >= 1
            # the second run consumed NO prompt chunks in-scan
            assert ph.inline_prefill_tokens == inline_after_miss
        finally:
            telemetry.disable()
            telemetry.get_runtime().clear()


# ------------------------------------------- scheduler chunk token budget
class TestBudgetAdmission:
    def _sched(self, max_batch=4):
        return ContinuousBatchScheduler(SlotAllocator(max_batch, 32),
                                        max_queue=16)

    def test_budget_breaks_at_first_over_budget_request(self):
        """FIFO head-of-line is deliberate: admission stops at the first
        request that would overflow the budget (no out-of-order fill)."""
        s = self._sched()
        for n in (4, 8, 2):
            s.submit(Request(prompt=np.zeros(n, np.int32),
                             max_new_tokens=4))
        admitted = s.admit(token_budget=6,
                           lane_cost=lambda r: min(4, r.prompt_len))
        # first costs 4 (fits), second costs 4 (over at budget 2) ->
        # stop; the 2-token prompt behind it must NOT jump the line
        assert [r.prompt_len for r in admitted] == [4]
        assert [r.prompt_len for r in s.queue] == [8, 2]

    def test_idle_engine_always_admits_one(self):
        """A budget must never wedge an empty scan: with nothing running
        and nothing admitted yet, the head request goes in even when its
        lane cost exceeds the budget."""
        s = self._sched()
        s.submit(Request(prompt=np.zeros(8, np.int32), max_new_tokens=4))
        admitted = s.admit(token_budget=0,
                           lane_cost=lambda r: min(4, r.prompt_len))
        assert len(admitted) == 1

    def test_no_budget_is_plain_fifo(self):
        s = self._sched(max_batch=2)
        for n in (4, 8, 2):
            s.submit(Request(prompt=np.zeros(n, np.int32),
                             max_new_tokens=4))
        admitted = s.admit()
        assert [r.prompt_len for r in admitted] == [4, 8]

    def test_engine_budget_accounting(self, tiny_engine):
        """_lane_cost prices a new lane at its first prompt chunk (or
        one decode token past the sp threshold); _budget_drain charges
        running lanes their remaining chunk / decode token."""
        fz = ServingEngine(engine=tiny_engine, max_batch=3,
                           max_prompt_len=16, max_queue=16,
                           decode_chunk=4, fused_prefill=True,
                           prefill_chunk=4, sp_prefill_threshold=12)
        # default budget: 2*C + max_batch
        assert fz.chunk_token_budget == 2 * 4 + 3
        short = Request(prompt=np.zeros(3, np.int32), max_new_tokens=4)
        multi = Request(prompt=np.zeros(9, np.int32), max_new_tokens=4)
        sp = Request(prompt=np.zeros(13, np.int32), max_new_tokens=4)
        assert fz._lane_cost(short) == 3     # one (partial) chunk
        assert fz._lane_cost(multi) == 4     # first full chunk
        assert fz._lane_cost(sp) == 1        # sp leg joins as decode lane
        assert fz._budget_drain() == 0       # nothing running yet

    def test_tight_budget_staggers_admission(self, tiny_engine, prompts):
        """chunk_token_budget=4 can only afford one prompt chunk per
        scan step, so admission staggers — and the token streams STILL
        match the bucketed reference exactly."""
        ref = ServingEngine(engine=tiny_engine, max_batch=3,
                            max_prompt_len=16, max_queue=16,
                            decode_chunk=4)
        fz = ServingEngine(engine=tiny_engine, max_batch=3,
                           max_prompt_len=16, max_queue=16,
                           decode_chunk=4, fused_prefill=True,
                           prefill_chunk=4, chunk_token_budget=4)
        a = ref.run(list(prompts), max_new_tokens=8)
        b = fz.run(list(prompts), max_new_tokens=8)
        _assert_parity(a, b)


# -------------------------------------------- admission cost unification
class TestAdmissionCost:
    def test_fused_cost_is_chunks_plus_decode(self):
        from deepspeed_tpu.serving.frontend.admission import (
            AdmissionConfig, Ticket)
        cfg = AdmissionConfig(fused_prefill_chunk=8)
        t = Ticket(prompt_len=20, max_new_tokens=16)
        # ceil(20/8)=3 scan steps + 16 decode-token equivalents
        assert cfg.cost_tokens(t) == 19.0
        t2 = Ticket(prompt_len=8, max_new_tokens=4)
        assert cfg.cost_tokens(t2) == 5.0
        t3 = Ticket(prompt_len=1, max_new_tokens=1)
        assert cfg.cost_tokens(t3) == 2.0

    def test_bucket_weight_cost_without_fused_chunk(self):
        from deepspeed_tpu.serving.frontend.admission import (
            AdmissionConfig, Ticket)
        cfg = AdmissionConfig(prefill_token_weight=0.25)
        t = Ticket(prompt_len=20, max_new_tokens=16)
        assert cfg.cost_tokens(t) == t.cost_tokens(0.25)
        assert cfg.cost_tokens(t) == pytest.approx(21.0)

    def test_fused_estimate_admits_more_long_prompts(self):
        """The point of the unification: under the fused cost model a
        long prompt is priced at ceil(L/C) scan steps, far below the
        bucket-weight token estimate, so the same backlog bound admits
        more long-prompt work."""
        from deepspeed_tpu.serving.frontend.admission import (
            AdmissionConfig, Ticket)
        bucketed = AdmissionConfig(prefill_token_weight=1.0)
        fused = AdmissionConfig(fused_prefill_chunk=8)
        t = Ticket(prompt_len=448, max_new_tokens=2)
        assert bucketed.cost_tokens(t) == 450.0
        assert fused.cost_tokens(t) == 58.0

    def test_frontend_wires_chunk_from_fused_engine(self, tiny_engine):
        """ServingFrontend auto-derives fused_prefill_chunk from a fused
        engine so the admission controller prices tickets in scan steps
        without explicit configuration."""
        from deepspeed_tpu.serving.frontend import (AdmissionConfig,
                                                    ServingFrontend)
        fz = ServingEngine(engine=tiny_engine, max_batch=3,
                           max_prompt_len=16, max_queue=16,
                           decode_chunk=4, fused_prefill=True,
                           prefill_chunk=4)
        fe = ServingFrontend(fz, admission=AdmissionConfig())
        try:
            assert fe._controller.config.fused_prefill_chunk == 4
        finally:
            fe.close()

    def test_frontend_keeps_explicit_chunk_and_bucketed_none(
            self, tiny_engine):
        from deepspeed_tpu.serving.frontend import (AdmissionConfig,
                                                    ServingFrontend)
        ref = ServingEngine(engine=tiny_engine, max_batch=3,
                            max_prompt_len=16, max_queue=16,
                            decode_chunk=4)
        fe = ServingFrontend(ref, admission=AdmissionConfig())
        try:
            assert fe._controller.config.fused_prefill_chunk is None
        finally:
            fe.close()
        fz = ServingEngine(engine=tiny_engine, max_batch=3,
                           max_prompt_len=16, max_queue=16,
                           decode_chunk=4, fused_prefill=True,
                           prefill_chunk=4)
        fe2 = ServingFrontend(
            fz, admission=AdmissionConfig(fused_prefill_chunk=16))
        try:
            assert fe2._controller.config.fused_prefill_chunk == 16
        finally:
            fe2.close()

    def test_frontend_streaming_parity_fused(self, tiny_engine, prompts):
        """End-to-end: the frontend streaming path over a fused engine
        stays bit-identical to the bucketed ServingEngine.run."""
        from deepspeed_tpu.serving.frontend import ServingFrontend
        ref = ServingEngine(engine=tiny_engine, max_batch=3,
                            max_prompt_len=16, max_queue=16,
                            decode_chunk=4)
        a = ref.run(list(prompts), max_new_tokens=6)
        fz = ServingEngine(engine=tiny_engine, max_batch=3,
                           max_prompt_len=16, max_queue=16,
                           decode_chunk=4, fused_prefill=True,
                           prefill_chunk=4)
        fe = ServingFrontend(fz)
        try:
            handles = [fe.submit(p.copy(), max_new_tokens=6)
                       for p in prompts]
            for h, ref_r in zip(handles, a):
                streamed = list(h)
                assert h.status == "done"
                assert streamed == h.tokens
                np.testing.assert_array_equal(h.output_ids,
                                              ref_r.output_ids)
        finally:
            fe.close()
