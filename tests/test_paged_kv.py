"""Paged KV cache: block allocator, prefix cache, COW forking, and
paged-vs-dense bit-exact greedy parity through the serving engine.

Layered like the subsystem: pure host-side unit tests first (no JAX),
then the Pallas paged-attention kernel against its gather reference,
then engine integration — the dense arena stays the oracle and the
paged block pool must reproduce its greedy outputs bit for bit."""

import numpy as np
import pytest

from deepspeed_tpu.serving.paged_kv import (BlockAllocator,
                                            PagedSlotAllocator,
                                            PrefixCache)
from deepspeed_tpu.serving.scheduler import (ContinuousBatchScheduler,
                                             Request, REJECT_KV_OOM)


# ------------------------------------------------------ block allocator
class TestBlockAllocator:
    def test_alloc_free_refcount(self):
        ba = BlockAllocator(4, 16)
        b0, b1 = ba.alloc(), ba.alloc()
        assert b0 != b1
        assert ba.n_used == 2 and ba.n_free == 2
        ba.incref(b0)                       # two holders now
        ba.decref(b0)
        assert ba.n_used == 2               # still held once
        ba.decref(b0)
        ba.decref(b1)
        assert ba.n_free == 4 and ba.peak_used == 2

    def test_oom_returns_none_not_crash(self):
        ba = BlockAllocator(2, 16)
        assert ba.alloc() is not None and ba.alloc() is not None
        assert ba.alloc() is None           # exhausted: reject, not raise

    def test_double_decref_raises(self):
        ba = BlockAllocator(2, 16)
        b = ba.alloc()
        ba.decref(b)
        with pytest.raises(ValueError):
            ba.decref(b)

    def test_freed_blocks_recycle_lru(self):
        """A freed block goes to the TAIL of the free list — just-freed
        blocks (stale speculative writes) stay cold longest."""
        ba = BlockAllocator(3, 16)
        b0 = ba.alloc()
        ba.decref(b0)
        assert ba.alloc() != b0             # colder blocks leave first


# -------------------------------------------------------- prefix cache
class TestPrefixCache:
    def test_put_lookup_and_refcounts(self):
        ba = BlockAllocator(8, 16)
        pc = PrefixCache(capacity=4)
        blocks = (ba.alloc(), ba.alloc())
        key = pc.key_for(np.arange(20, dtype=np.int32))
        assert pc.put(key, blocks, prompt_len=20, first_token=7,
                      block_allocator=ba)
        assert int(ba.refcount[blocks[0]]) == 2   # request + cache
        entry = pc.lookup(key)
        assert entry is not None and entry.first_token == 7
        assert pc.lookup(b"missing") is None
        # releasing the request's refs leaves the cache holding them
        for b in blocks:
            ba.decref(b)
        assert ba.n_used == 2 and pc.blocks_held == 2

    def test_eviction_releases_blocks(self):
        ba = BlockAllocator(8, 16)
        pc = PrefixCache(capacity=2)
        keys = []
        for i in range(3):
            b = ba.alloc()
            key = pc.key_for(np.array([i], np.int32))
            pc.put(key, (b,), 1, i, ba)
            ba.decref(b)                    # cache is the only holder
            keys.append(key)
        # capacity 2: inserting the third evicted the LRU (first) entry
        assert len(pc) == 2 and pc.lookup(keys[0]) is None
        assert pc.evictions == 1 and ba.n_used == 2
        assert pc.evict_lru(ba) and pc.evict_lru(ba)
        assert not pc.evict_lru(ba)         # empty: nothing to evict
        assert ba.n_free == 8

    def test_duplicate_key_not_republished(self):
        ba = BlockAllocator(4, 16)
        pc = PrefixCache(capacity=4)
        b = ba.alloc()
        key = pc.key_for(np.array([1, 2], np.int32))
        assert pc.put(key, (b,), 2, 5, ba)
        assert not pc.put(key, (b,), 2, 5, ba)
        assert int(ba.refcount[b]) == 2     # no double incref


# ------------------------------------------------- paged slot allocator
class TestPagedSlotAllocator:
    def test_upfront_reservation_and_remaining(self):
        pa = PagedSlotAllocator(4, 64, block_size=16)
        req = Request(prompt=np.arange(20), max_new_tokens=8)
        slot = pa.alloc_request(req)
        # ceil(28/16) = 2 blocks; remaining mirrors the dense arithmetic
        assert len(pa.tables[slot]) == 2
        assert pa.remaining(slot) == 2 * 16 - 20
        pa.advance([slot])
        assert pa.fill[slot] == 21
        pa.free(slot)
        assert pa.blocks.n_free == pa.blocks.num_blocks

    def test_pending_key_defers_identical_inflight_prompt(self):
        pa = PagedSlotAllocator(4, 64, block_size=16)
        r1 = Request(prompt=np.arange(20), max_new_tokens=8)
        r2 = Request(prompt=np.arange(20), max_new_tokens=8)
        s1 = pa.alloc_request(r1)
        assert s1 is not None
        assert pa.alloc_request(r2) is None     # deferred, not a miss
        assert pa.prefix.misses == 1 and pa.prefix.hits == 0
        plan = pa.plans[s1]
        pa.commit_prefix(s1, plan.key, first_token=3)
        s2 = pa.alloc_request(r2)               # now a hit
        assert s2 is not None and pa.plans[s2].hit
        assert pa.prefix.hits == 1

    def test_hit_shares_full_blocks_and_cows_tail(self):
        pa = PagedSlotAllocator(4, 64, block_size=16)
        r1 = Request(prompt=np.arange(20), max_new_tokens=8)
        s1 = pa.alloc_request(r1)
        pa.commit_prefix(s1, pa.plans[s1].key, first_token=3)
        r2 = Request(prompt=np.arange(20), max_new_tokens=8)
        s2 = pa.alloc_request(r2)
        p2 = pa.plans[s2]
        # block 0 holds tokens [0,16): full, shared by refcount; block 1
        # holds the partial tail [16,20): privatized by COW
        assert pa.tables[s2][0] == pa.tables[s1][0]
        assert pa.tables[s2][1] != pa.tables[s1][1]
        assert p2.cow is not None and p2.n_shared == 1
        shared = pa.tables[s1][0]
        # holders: r1, r2, the cache entry
        assert int(pa.blocks.refcount[shared]) == 3
        pa.release_cow_hold(p2.cow[0])
        pa.free(s1)
        assert int(pa.blocks.refcount[shared]) == 2

    def test_block_aligned_prompt_needs_no_cow(self):
        pa = PagedSlotAllocator(4, 64, block_size=16)
        r1 = Request(prompt=np.arange(16), max_new_tokens=8)
        s1 = pa.alloc_request(r1)
        assert pa.commit_prefix(s1, pa.plans[s1].key, 3) is None
        r2 = Request(prompt=np.arange(16), max_new_tokens=8)
        s2 = pa.alloc_request(r2)
        assert pa.plans[s2].cow is None and pa.plans[s2].n_shared == 1

    def test_ensure_free_evicts_cold_prefixes(self):
        # 4 blocks total; one cached 2-block prefix with no live holder
        pa = PagedSlotAllocator(2, 64, block_size=16, num_blocks=4)
        r1 = Request(prompt=np.arange(17), max_new_tokens=8)
        s1 = pa.alloc_request(r1)
        pa.commit_prefix(s1, pa.plans[s1].key, 3)
        pa.free(s1)
        assert pa.blocks.n_free == 2        # cache still pins its blocks
        # a 3-block request can only fit by evicting the cached prefix
        r2 = Request(prompt=np.arange(40), max_new_tokens=8)
        s2 = pa.alloc_request(r2)
        assert s2 is not None and len(pa.tables[s2]) == 3
        assert len(pa.prefix) == 0

    def test_block_oom_returns_none(self):
        pa = PagedSlotAllocator(4, 64, block_size=16, num_blocks=4,
                                prefix_caching=False)
        r1 = Request(prompt=np.arange(40), max_new_tokens=8)
        assert pa.alloc_request(r1) is not None     # 3 blocks
        r2 = Request(prompt=np.arange(20), max_new_tokens=16)
        assert pa.alloc_request(r2) is None         # needs 3, 1 free
        r3 = Request(prompt=np.arange(10), max_new_tokens=4)
        assert pa.alloc_request(r3) is not None     # 1 block fits

    def test_dense_compat_alloc_reserves_full_sequence(self):
        pa = PagedSlotAllocator(2, 64, block_size=16)
        slot = pa.alloc(5)
        assert len(pa.tables[slot]) == 4 and pa.fill[slot] == 5
        assert pa.remaining(slot) == 64 - 5

    def test_scheduler_rejects_unservable_request(self):
        pa = PagedSlotAllocator(2, 64, block_size=16, num_blocks=2)
        sched = ContinuousBatchScheduler(pa, max_queue=4)
        req = Request(prompt=np.arange(30), max_new_tokens=30)
        assert not sched.submit(req)        # 60 tokens > 32-token pool
        assert req.reject_reason == REJECT_KV_OOM
        ok = Request(prompt=np.arange(10), max_new_tokens=10)
        assert sched.submit(ok)


# ------------------------------------------------- pallas paged kernel
class TestPagedKernel:
    def test_pallas_matches_gather_reference(self):
        import jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_attention, paged_decode_supported)
        rng = np.random.default_rng(0)
        b, h, d, bs, T, nb = 4, 2, 64, 8, 4, 24
        assert paged_decode_supported(b, bs, h, d, jnp.float32)
        k_pool = jnp.asarray(
            rng.standard_normal((nb, bs, h * d)), jnp.float32)
        v_pool = jnp.asarray(
            rng.standard_normal((nb, bs, h * d)), jnp.float32)
        bt = jnp.asarray(
            rng.permutation(nb)[:b * T].reshape(b, T), jnp.int32)
        clen = jnp.asarray([5, 13, 32, 1], jnp.int32)
        q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
        ref = paged_decode_attention(q, k_pool, v_pool, bt, clen,
                                     impl="xla")
        pal = paged_decode_attention(q, k_pool, v_pool, bt, clen,
                                     impl="pallas")
        np.testing.assert_allclose(np.asarray(pal), np.asarray(ref),
                                   atol=2e-5, rtol=2e-5)

    def test_unsupported_shapes_are_refused(self):
        import jax.numpy as jnp
        from deepspeed_tpu.ops.pallas.decode_attention import (
            paged_decode_supported)
        assert not paged_decode_supported(4, 8, 2, 33, jnp.float32)
        assert not paged_decode_supported(4, 3, 2, 64, jnp.float32)


# ------------------------------------------------ engine (integration)
def _tiny(vocab=64, max_seq=64):
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


class TestPagedEngineParity:
    def test_paged_matches_dense_mixed_lengths(self, tiny_engine):
        """Paged greedy output is BIT-identical to the dense arena for
        mixed prompt lengths, more requests than slots — at chunks of
        one step and of eight."""
        from deepspeed_tpu.serving import ServingEngine
        rng = np.random.default_rng(0)
        prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
                   for n in [3, 7, 5, 9, 4, 6]]
        dense = ServingEngine(engine=tiny_engine, max_batch=3,
                              max_prompt_len=16, max_queue=8)
        ref = dense.run(list(prompts), max_new_tokens=6)
        for kw in (dict(decode_chunk=1), dict(decode_chunk=8)):
            paged = ServingEngine(engine=tiny_engine, max_batch=3,
                                  max_prompt_len=16, max_queue=8,
                                  paged=True, kv_block_size=8, **kw)
            got = paged.run(list(prompts), max_new_tokens=6)
            for x, y in zip(ref, got):
                assert x.status == y.status == "done"
                np.testing.assert_array_equal(x.output_ids, y.output_ids)

    def test_paged_mid_chunk_eos_parity(self, tiny_engine):
        from deepspeed_tpu.serving import ServingEngine
        rng = np.random.default_rng(1)
        prompts = [rng.integers(0, 64, (n,)).astype(np.int32)
                   for n in [3, 7, 5, 9]]
        dense = ServingEngine(engine=tiny_engine, max_batch=3,
                              max_prompt_len=16, max_queue=8,
                              decode_chunk=8)
        paged = ServingEngine(engine=tiny_engine, max_batch=3,
                              max_prompt_len=16, max_queue=8,
                              decode_chunk=8, paged=True, kv_block_size=8)
        base = dense.run(list(prompts), max_new_tokens=11)
        eos = int(base[0].tokens[2])         # retires mid-chunk
        a = dense.run(list(prompts), max_new_tokens=11, eos_token_id=eos)
        b = paged.run(list(prompts), max_new_tokens=11, eos_token_id=eos)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x.output_ids, y.output_ids)

    def test_shared_prefix_forks_share_blocks_until_divergence(
            self, tiny_engine):
        """Two requests with one long common prompt: the second admits
        as a prefix-cache hit (prefill runs once), shares every full
        prompt block by refcount, and privatizes only the tail — and
        still produces bit-identical output to a dense run."""
        from deepspeed_tpu.serving import ServingEngine
        rng = np.random.default_rng(3)
        common = rng.integers(0, 64, (52,)).astype(np.int32)
        prompts = [common.copy(), common.copy()]
        dense = ServingEngine(engine=tiny_engine, max_batch=2,
                              max_prompt_len=52, prefill_buckets=(52,),
                              max_queue=4)
        # decode_chunk=1 so request 1 is still mid-decode when request 2
        # admits as a hit — the overlap the table inspection needs (a K=8
        # chunk would finish the 8-token request inside one step)
        paged = ServingEngine(engine=tiny_engine, max_batch=2,
                              max_prompt_len=52, prefill_buckets=(52,),
                              max_queue=4, paged=True, kv_block_size=16,
                              decode_chunk=1)
        ref = dense.run([p.copy() for p in prompts], max_new_tokens=8)
        # run the paged engine manually so tables can be inspected LIVE
        # (slots free — and decref — at completion)
        reqs = [paged.submit(p, max_new_tokens=8) for p in prompts]
        alloc = paged.kv.allocator
        seen_shared = False
        while paged.scheduler.has_work():
            paged.step()
            live = [r for r in reqs if r.status == "running"
                    and r.slot is not None]
            if len(live) == 2 and not seen_shared:
                t0 = alloc.tables[live[0].slot]
                t1 = alloc.tables[live[1].slot]
                assert t0[:3] == t1[:3]          # 48 shared prompt tokens
                assert t0[3] != t1[3]            # COW'd tail + decode
                for blk in t0[:3]:
                    # holders: both requests + the prefix-cache entry
                    assert int(alloc.blocks.refcount[blk]) == 3
                seen_shared = True
        assert seen_shared, "requests never overlapped — no sharing seen"
        assert paged.metrics.n_prefix_hits == 1
        assert paged.metrics.n_prefix_misses == 1
        assert paged.metrics.prefill_prompt_tokens == 52   # prefill once
        for x, r in zip(ref, reqs):
            np.testing.assert_array_equal(x.output_ids, r.output_ids)

    def test_block_oom_queues_instead_of_crashing(self, tiny_engine):
        """A pool too small for all requests at once: later requests
        WAIT for blocks (admission returns no slot) and complete once
        earlier ones free theirs — nothing crashes, nothing corrupts."""
        from deepspeed_tpu.serving import ServingEngine
        rng = np.random.default_rng(5)
        prompts = [rng.integers(0, 64, (12,)).astype(np.int32)
                   for _ in range(4)]
        dense = ServingEngine(engine=tiny_engine, max_batch=4,
                              max_prompt_len=16, max_queue=8)
        # 3 blocks of 16 = 48 tokens: holds ONE 12+8 request per wave
        # comfortably, never all four
        paged = ServingEngine(engine=tiny_engine, max_batch=4,
                              max_prompt_len=16, max_queue=8,
                              paged=True, kv_block_size=16,
                              kv_pool_blocks=3, prefix_cache=False)
        ref = dense.run([p.copy() for p in prompts], max_new_tokens=8)
        got = paged.run([p.copy() for p in prompts], max_new_tokens=8)
        for x, y in zip(ref, got):
            assert y.status == "done"
            np.testing.assert_array_equal(x.output_ids, y.output_ids)

    def test_paged_telemetry_and_report(self, tiny_engine):
        from deepspeed_tpu import telemetry
        from deepspeed_tpu.serving import ServingEngine
        telemetry.enable()
        rng = np.random.default_rng(7)
        common = rng.integers(0, 64, (20,)).astype(np.int32)
        paged = ServingEngine(engine=tiny_engine, max_batch=2,
                              max_prompt_len=20, prefill_buckets=(20,),
                              max_queue=4, paged=True, kv_block_size=16)
        paged.run([common.copy(), common.copy()], max_new_tokens=4)
        rt = telemetry.get_runtime()
        gauges = rt.gauge_values()
        assert "serve/block_pool_used" in gauges
        assert "serve/block_pool_free" in gauges
        assert rt.counter_totals().get("serve/prefix_cache_hit") == 1.0
        assert rt.counter_totals().get("serve/prefix_cache_miss") == 1.0
        assert rt.instant_counts().get("serve/cow_fork", 0) >= 1
        snap = paged.metrics.snapshot(0, 0.0)
        assert snap["serving/prefix_cache_hits"] == 1.0
        assert snap["serving/prefix_hit_rate"] == 0.5
        rep = paged.kv.arena_report()
        assert rep["layout"] == "paged"
        # dense report keys survive: dashboards and the admission cost
        # model read the same names either way
        for key in ("arena_bytes", "kv_bytes", "bytes_per_token",
                    "headroom_bytes", "n_active", "n_free"):
            assert key in rep
        assert rep["blocks_total"] == rep["blocks_used"] + rep["blocks_free"]
        assert rep["bytes_per_block"] > 0
