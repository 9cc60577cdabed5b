"""Tiered KV cache: HBM -> host DRAM -> NVMe demotion ladder, async
promotion, fleet prefix fetch, tier-aware admission.

Layered like the subsystem: pure host-side KVTierManager units first
(no JAX — eviction order, spill round-trip bit-parity, watermark
cascade, close cleanup), then engine integration (demote/promote
round trips must reproduce the dense arena's greedy outputs bit for
bit — fp32, int8, and speculative compositions; the async promotion
race pinned with a slowed worker), then the fleet surface (loopback
ReplicaServer peer fetch with ZERO re-prefill, router tier-fetch
fallback)."""

import os
import time

import numpy as np
import pytest

from deepspeed_tpu.serving.kv_tiers import (KVTierManager,
                                            PREFIX_FETCH_SCHEMA,
                                            TIERS_SCHEMA)


def _leaves(nbytes_per_leaf=256, n=2, seed=0, dtype=np.float32):
    rng = np.random.default_rng(seed)
    per = nbytes_per_leaf // np.dtype(dtype).itemsize
    return {f"layer{i}/k": rng.standard_normal(per).astype(dtype)
            for i in range(n)}


# ------------------------------------------------- host-side tier units
class TestTierManagerUnits:
    def test_admit_holds_and_report_schema(self):
        with KVTierManager(dram_bytes=1 << 20) as tier:
            lv = _leaves()
            assert tier.admit(b"k1", 16, 7, lv)
            assert tier.holds(b"k1") and not tier.holds(b"k2")
            assert not tier.admit(b"k1", 16, 7, lv)   # already tiered
            rep = tier.report()
            assert rep["schema"] == TIERS_SCHEMA
            assert rep["dram_entries"] == 1
            assert rep["demotions_dram"] == 1
            assert rep["dram_bytes"] == sum(a.nbytes for a in lv.values())

    def test_dram_overflow_spills_coldest_first(self):
        # room for exactly two 512B entries: admitting the third spills
        # the LRU (first-admitted) entry to NVMe
        with KVTierManager(dram_bytes=1100) as tier:
            for i in range(3):
                assert tier.admit(f"k{i}".encode(), 8, i,
                                  _leaves(256, seed=i))
            assert tier.report()["nvme_entries"] == 1
            assert tier.demotions_nvme == 1
            # k0 went down; it is still held (promotable), not dropped
            assert tier.holds(b"k0")
            assert len(tier.spill_files()) == 1
            assert os.path.exists(tier.spill_files()[0])

    def test_fetch_refreshes_lru_order(self):
        with KVTierManager(dram_bytes=1100) as tier:
            tier.admit(b"a", 8, 0, _leaves(256, seed=0))
            tier.admit(b"b", 8, 1, _leaves(256, seed=1))
            assert tier.fetch_bundle(b"a") is not None   # touches "a"
            tier.admit(b"c", 8, 2, _leaves(256, seed=2))
            # "b" was coldest after the touch: it spilled, "a" stayed
            spilled = {k for k, e in tier._nvme.items()}
            assert spilled == {b"b"}

    def test_nvme_capacity_drops_coldest_spill(self):
        with KVTierManager(dram_bytes=0, nvme_bytes=1100) as tier:
            for i in range(3):
                tier.admit(f"k{i}".encode(), 8, i, _leaves(256, seed=i))
            assert tier.dropped == 1 and not tier.holds(b"k0")
            assert tier.report()["nvme_entries"] == 2

    def test_spill_round_trip_bit_exact_mixed_dtypes(self, tmp_path):
        """NVMe spill/unspill preserves every byte across dtypes —
        including the non-native ml_dtypes kinds the KV pools use."""
        import ml_dtypes
        rng = np.random.default_rng(3)
        lv = {
            "l0/k": rng.standard_normal((2, 8, 4)).astype(np.float32),
            "l0/v": rng.standard_normal((2, 8, 4)).astype(
                ml_dtypes.bfloat16),
            "l0/q": rng.integers(-128, 127, (2, 8, 4)).astype(np.int8),
            "l0/s": rng.standard_normal((2, 8, 1)).astype(np.float32),
        }
        with KVTierManager(dram_bytes=0,
                           spill_dir=str(tmp_path)) as tier:
            assert tier.admit(b"kx", 16, 5, lv)
            assert tier.report()["nvme_entries"] == 1
            assert tier.request_promotion(b"kx")
            deadline = time.monotonic() + 10
            ready = []
            while not ready and time.monotonic() < deadline:
                ready = tier.drain_ready()
                time.sleep(0.001)
            assert ready
            key, plen, ftok, got = ready[0]
            assert (key, plen, ftok) == (b"kx", 16, 5)
            assert set(got) == set(lv)
            for name, a in lv.items():
                assert got[name].dtype == a.dtype
                assert got[name].shape == a.shape
                np.testing.assert_array_equal(
                    got[name].view(np.uint8), a.view(np.uint8))
            assert tier.promotions_nvme == 1

    def test_abandon_ready_returns_entry_to_dram(self):
        with KVTierManager(dram_bytes=1 << 20) as tier:
            lv = _leaves()
            tier.admit(b"k", 8, 3, lv)
            tier.request_promotion(b"k")
            deadline = time.monotonic() + 10
            ready = []
            while not ready and time.monotonic() < deadline:
                ready = tier.drain_ready()
                time.sleep(0.001)
            key, plen, ftok, got = ready[0]
            assert not tier.holds(b"k")       # drained: engine owns it
            tier.abandon_ready(key, (plen, ftok, got))
            assert tier.holds(b"k")           # pool was full: retry later
            assert tier.report()["dram_entries"] == 1

    def test_close_removes_spill_files_and_dir(self):
        tier = KVTierManager(dram_bytes=0)
        tier.admit(b"k", 8, 0, _leaves(256))
        files = tier.spill_files()
        sdir = tier.spill_dir
        assert files and all(os.path.exists(f) for f in files)
        tier.close()
        assert not any(os.path.exists(f) for f in files)
        assert not os.path.exists(sdir)
        tier.close()                          # idempotent
        assert not tier.admit(b"k2", 8, 0, _leaves(256))  # closed

    def test_failed_nvme_promotion_unlinks_caller_dir_spill(
            self, tmp_path, monkeypatch):
        """``_promote_one`` pops the NVMe entry BEFORE the disk read: a
        failing read must still unlink the popped entry's spill file —
        with a caller-provided spill_dir ``close()`` never rmtrees, so
        a missed unlink is a permanent leak."""
        with KVTierManager(dram_bytes=0, spill_dir=str(tmp_path)) as tier:
            assert tier.admit(b"k", 8, 0, _leaves(256))
            path = tier.spill_files()[0]

            def boom(spilled):
                raise OSError("injected read failure")

            monkeypatch.setattr(tier, "_unspill", boom)
            assert tier.request_promotion(b"k")
            deadline = time.monotonic() + 10
            while tier.holds(b"k") and time.monotonic() < deadline:
                time.sleep(0.001)
            assert not tier.holds(b"k")     # dropped: re-prefills as miss
            assert tier.promote_failures == 1
            assert not os.path.exists(path)  # no spill-file leak

    def test_fetch_pin_defers_concurrent_unlink(self):
        """A peer fetch mid-read pins the spill file: a concurrent
        promotion's unlink parks until the pin releases (the fetch's
        per-leaf reads would otherwise race the file's removal)."""
        with KVTierManager(dram_bytes=0) as tier:
            assert tier.admit(b"k", 8, 0, _leaves(256))
            path = tier.spill_files()[0]
            with tier._lock:
                tier._pins[b"k"] = 1          # a fetch is mid-read
            assert tier.request_promotion(b"k")
            deadline = time.monotonic() + 10
            while not tier._ready and time.monotonic() < deadline:
                time.sleep(0.001)
            assert tier.promotions_nvme == 1
            assert os.path.exists(path)       # unlink deferred by pin
            with tier._lock:
                tier._unpin_locked(b"k")
            assert not os.path.exists(path)   # performed at unpin

    def test_concurrent_spill_and_fetch_bit_exact(self):
        """Spills (engine thread, map lock held) and peer fetches'
        NVMe reads (transport threads, map lock dropped) hammer the
        SHARED AsyncIOHandle concurrently: the I/O mutex keeps every
        payload bit-exact — an unserialized ``wait()`` would drain the
        other thread's in-flight ops and hand back uninitialized read
        buffers."""
        import threading
        ref = {f"k{i}".encode(): _leaves(1024, seed=100 + i)
               for i in range(8)}
        with KVTierManager(dram_bytes=0) as tier:  # every admit spills
            errs = []

            def fetcher():
                try:
                    for _ in range(20):
                        for key, lv in ref.items():
                            b = tier.fetch_bundle(key)
                            if b is None:
                                continue       # not admitted yet
                            for name, a in lv.items():
                                got = np.asarray(b["kv"][name])
                                np.testing.assert_array_equal(
                                    got.view(np.uint8), a.view(np.uint8))
                except Exception as e:  # noqa: BLE001 — collected
                    errs.append(e)

            def admitter():
                try:
                    for key, lv in ref.items():
                        assert tier.admit(key, 8, 0, lv)
                        time.sleep(0.001)
                except Exception as e:  # noqa: BLE001 — collected
                    errs.append(e)

            threads = [threading.Thread(target=admitter),
                       threading.Thread(target=fetcher),
                       threading.Thread(target=fetcher)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert not errs, errs
            # promotion after the storm still round-trips every byte
            for key in ref:
                assert tier.request_promotion(key)
            got = {}
            deadline = time.monotonic() + 10
            while len(got) < len(ref) and time.monotonic() < deadline:
                for k, _pl, _ft, leaves in tier.drain_ready():
                    got[k] = leaves
                time.sleep(0.001)
            assert set(got) == set(ref)
            for key, lv in ref.items():
                for name, a in lv.items():
                    np.testing.assert_array_equal(
                        got[key][name].view(np.uint8), a.view(np.uint8))

    def test_bundle_wire_schema_and_install(self):
        with KVTierManager(dram_bytes=1 << 20) as src, \
                KVTierManager(dram_bytes=1 << 20) as dst:
            lv = _leaves(seed=9)
            src.admit(b"\x01\x02", 16, 4, lv)
            bundle = src.fetch_bundle(b"\x01\x02")
            assert bundle["schema"] == PREFIX_FETCH_SCHEMA
            assert bundle["key"] == "0102"
            assert src.holds(b"\x01\x02")     # non-destructive fetch
            assert dst.install_bundle(bundle)
            assert dst.holds(b"\x01\x02") and dst.peer_installs == 1
            assert src.peer_fetches == 1
            with pytest.raises(ValueError):
                dst.install_bundle({"schema": "bogus"})


# --------------------------------------------- tier-aware admission gate
class TestTierAwareAdmission:
    def _ticket(self, prompt_len, mnt):
        from deepspeed_tpu.serving.frontend.admission import Ticket
        return Ticket(prompt_len=prompt_len, max_new_tokens=mnt)

    def test_tier_extends_backlog_not_per_ticket_cap(self):
        from deepspeed_tpu.serving.frontend.admission import (
            AdmissionConfig, AdmissionController,
            REJECT_MEMORY_INFEASIBLE)
        # the per-ticket wall stays pure HBM even with a tier: the tier
        # only holds COLD prefix entries — an active sequence's KV can
        # never demote, so a request past one slot row / the pool can
        # NEVER be served; admitting it would defer forever instead of
        # shedding (liveness)
        tiered = AdmissionController(AdmissionConfig(
            shed_memory_infeasible=True, slot_tokens=32,
            pool_tokens=32, tier_tokens=32, tier_discount=0.5))
        assert tiered.offer(self._ticket(30, 10)) \
            == REJECT_MEMORY_INFEASIBLE
        # what the tier buys is AGGREGATE headroom: 32 pool + 0.5 * 32
        # tier = 48 pending KV tokens — two 24-token tickets queue,
        # the third sheds instead of thrashing the ladder
        assert tiered.offer(self._ticket(16, 8)) is None
        assert tiered.offer(self._ticket(16, 8)) is None
        assert tiered.offer(self._ticket(16, 8)) \
            == REJECT_MEMORY_INFEASIBLE
        assert tiered.n_memory_infeasible == 2
        # popping a ticket releases its backlog budget
        admits, sheds = tiered.pop(room=1, rate=None, backlog_tokens=0.0)
        assert len(admits) == 1 and not sheds
        assert tiered.offer(self._ticket(16, 8)) is None
        # without a tier there is no aggregate gate — the historical
        # behavior queues past the pool instead of shedding
        hbm_only = AdmissionController(AdmissionConfig(
            shed_memory_infeasible=True, slot_tokens=32,
            pool_tokens=32))
        assert hbm_only.offer(self._ticket(30, 10)) \
            == REJECT_MEMORY_INFEASIBLE
        for _ in range(4):
            assert hbm_only.offer(self._ticket(16, 8)) is None


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


def _prompt(n=16, seed=0, vocab=64):
    rng = np.random.default_rng(seed)
    return rng.integers(0, vocab, (n,)).astype(np.int32)


def _tiered(tiny_engine, **kw):
    from deepspeed_tpu.serving import ServingEngine
    base = dict(engine=tiny_engine, max_batch=2, max_prompt_len=16,
                max_queue=8, paged=True, kv_block_size=8,
                decode_chunk=8, tiered_kv=True,
                tier_dram_bytes=1 << 20)
    base.update(kw)
    return ServingEngine(**base)


class TestEngineTierParity:
    def test_tiered_requires_paged_and_prefix(self, tiny_engine):
        from deepspeed_tpu.serving import ServingEngine
        with pytest.raises(ValueError):
            ServingEngine(engine=tiny_engine, tiered_kv=True)
        with pytest.raises(ValueError):
            ServingEngine(engine=tiny_engine, paged=True,
                          prefix_cache=False, tiered_kv=True)

    def test_demote_promote_round_trip_bit_parity(self, tiny_engine):
        """Serve a prompt, demote its cached prefix to DRAM, serve it
        again: the re-serve admits through an async promotion (prefix
        hit, zero re-prefill) and the output stays BIT-identical to the
        dense arena's."""
        from deepspeed_tpu.serving import ServingEngine
        p = _prompt(16, seed=1)               # block-aligned: full hit
        dense = ServingEngine(engine=tiny_engine, max_batch=2,
                              max_prompt_len=16, max_queue=8,
                              decode_chunk=8)
        ref = dense.run([p.copy()], max_new_tokens=8)
        tiered = _tiered(tiny_engine)
        try:
            first = tiered.run([p.copy()], max_new_tokens=8)
            np.testing.assert_array_equal(ref[0].output_ids,
                                          first[0].output_ids)
            key = tiered.kv.allocator.prefix.key_for(p)
            assert tiered.kv.demote_prefix(key)
            assert key not in tiered.kv.allocator.prefix
            assert tiered.kv_tier.holds(key)
            assert tiered.kv_tier.demotions_dram == 1
            hits0 = tiered.metrics.n_prefix_hits
            prefill0 = tiered.metrics.prefill_prompt_tokens
            second = tiered.run([p.copy()], max_new_tokens=8)
            np.testing.assert_array_equal(ref[0].output_ids,
                                          second[0].output_ids)
            assert tiered.kv_tier.promotions_dram == 1
            assert tiered.metrics.n_prefix_hits == hits0 + 1
            # the promoted prefix covered the whole prompt: no prefill
            assert tiered.metrics.prefill_prompt_tokens == prefill0
        finally:
            tiered.close()

    def test_nvme_cascade_promotes_bit_identical(self, tiny_engine):
        """A DRAM watermark too small for the entry cascades the
        demotion straight to an NVMe spill file; the re-serve promotes
        from disk and still matches the dense output bit for bit."""
        from deepspeed_tpu.serving import ServingEngine
        p = _prompt(16, seed=2)
        dense = ServingEngine(engine=tiny_engine, max_batch=2,
                              max_prompt_len=16, max_queue=8,
                              decode_chunk=8)
        ref = dense.run([p.copy()], max_new_tokens=8)
        tiered = _tiered(tiny_engine, tier_dram_bytes=1024)
        try:
            tiered.run([p.copy()], max_new_tokens=8)
            key = tiered.kv.allocator.prefix.key_for(p)
            assert tiered.kv.demote_prefix(key)
            assert tiered.kv_tier.report()["nvme_entries"] == 1
            spill = tiered.kv_tier.spill_files()
            assert spill and os.path.exists(spill[0])
            got = tiered.run([p.copy()], max_new_tokens=8)
            np.testing.assert_array_equal(ref[0].output_ids,
                                          got[0].output_ids)
            assert tiered.kv_tier.promotions_nvme == 1
            assert not os.path.exists(spill[0])   # consumed by promote
        finally:
            tiered.close()
        assert tiered.kv_tier.spill_files() == []

    def test_int8_demote_promote_parity(self, tiny_engine):
        """The quantized pool's paired (q, scale) leaves survive the
        tier round trip: int8 tiered == int8 untiered, bit for bit."""
        from deepspeed_tpu.serving import ServingEngine
        p = _prompt(16, seed=3)
        plain = ServingEngine(engine=tiny_engine, max_batch=2,
                              max_prompt_len=16, max_queue=8, paged=True,
                              kv_block_size=8, decode_chunk=8,
                              kv_dtype="int8")
        ref = plain.run([p.copy()], max_new_tokens=8)
        tiered = _tiered(tiny_engine, kv_dtype="int8")
        try:
            tiered.run([p.copy()], max_new_tokens=8)
            key = tiered.kv.allocator.prefix.key_for(p)
            assert tiered.kv.demote_prefix(key)
            got = tiered.run([p.copy()], max_new_tokens=8)
            np.testing.assert_array_equal(ref[0].output_ids,
                                          got[0].output_ids)
            assert tiered.kv_tier.promotions_dram == 1
        finally:
            tiered.close()

    def test_speculative_demote_promote_parity(self, tiny_engine):
        """Tiering composes with the speculative decode loop: the
        promoted prefix feeds the drafter and the greedy outputs still
        match the non-tiered speculative run exactly."""
        from deepspeed_tpu.serving import ServingEngine
        p = _prompt(16, seed=4)
        spec = dict(speculative=True, spec_k=2, decode_chunk=1)
        plain = ServingEngine(engine=tiny_engine, max_batch=2,
                              max_prompt_len=16, max_queue=8, paged=True,
                              kv_block_size=8, **spec)
        ref = plain.run([p.copy()], max_new_tokens=8)
        tiered = _tiered(tiny_engine, **spec)
        try:
            tiered.run([p.copy()], max_new_tokens=8)
            key = tiered.kv.allocator.prefix.key_for(p)
            assert tiered.kv.demote_prefix(key)
            got = tiered.run([p.copy()], max_new_tokens=8)
            np.testing.assert_array_equal(ref[0].output_ids,
                                          got[0].output_ids)
            assert tiered.kv_tier.promotions_dram == 1
        finally:
            tiered.close()

    def test_async_promote_race_defers_until_ready(self, tiny_engine):
        """A slowed promotion worker pins the race: while the payload is
        in flight the allocator keeps DEFERRING the request (holds()
        stays True, no slot leased, no re-prefill miss), and the install
        lands at a later admission pass with bit-identical output."""
        from deepspeed_tpu.serving import ServingEngine
        p = _prompt(16, seed=5)
        dense = ServingEngine(engine=tiny_engine, max_batch=2,
                              max_prompt_len=16, max_queue=8,
                              decode_chunk=8)
        ref = dense.run([p.copy()], max_new_tokens=8)
        tiered = _tiered(tiny_engine)
        try:
            tiered.run([p.copy()], max_new_tokens=8)
            key = tiered.kv.allocator.prefix.key_for(p)
            assert tiered.kv.demote_prefix(key)
            tier = tiered.kv_tier
            orig = tier._promote_one

            def slow_promote(k):              # instance attr shadows
                time.sleep(0.05)              # the bound method
                orig(k)

            tier._promote_one = slow_promote
            misses0 = tiered.metrics.n_prefix_misses
            req = tiered.submit(p.copy(), max_new_tokens=8)
            deferred_steps = 0
            while tiered.scheduler.has_work():
                if req.slot is None and tier.holds(key):
                    deferred_steps += 1       # promotion still in flight
                tiered.step()
            assert deferred_steps > 0, \
                "request was never deferred — race not exercised"
            assert req.status == "done"
            np.testing.assert_array_equal(ref[0].output_ids,
                                          req.output_ids)
            assert tier.promotions_dram == 1
            assert tiered.metrics.n_prefix_misses == misses0
            assert tier.report()["promote_wait_p50_s"] > 0.0
        finally:
            tiered.close()

    def test_tier_report_and_gauges(self, tiny_engine):
        from deepspeed_tpu import telemetry
        telemetry.enable()
        telemetry.get_runtime().clear()
        p = _prompt(16, seed=6)
        tiered = _tiered(tiny_engine)
        try:
            tiered.run([p.copy()], max_new_tokens=4)
            key = tiered.kv.allocator.prefix.key_for(p)
            tiered.kv.demote_prefix(key)
            tiered.run([p.copy()], max_new_tokens=4)
            rep = tiered.kv.arena_report()
            tiers = rep["tiers"]
            assert tiers["schema"] == TIERS_SCHEMA
            assert tiers["hbm_capacity_bytes"] == rep["kv_bytes"]
            assert tiers["demotions_dram"] == 1
            assert tiers["promotions_dram"] == 1
            gauges = telemetry.get_runtime().gauge_values()
            for g in ("serve/tier_dram_bytes", "serve/tier_nvme_bytes",
                      "serve/tier_demotions", "serve/tier_promotions"):
                assert g in gauges, g
            totals = telemetry.get_runtime().counter_totals()
            assert totals.get("serve/tier_promote") == 1.0
        finally:
            tiered.close()
            telemetry.disable()
            telemetry.get_runtime().clear()


# ------------------------------------------------- fleet prefix fetch
class TestFleetPrefixFetch:
    def test_peer_fetch_over_loopback_zero_reprefill(self, tiny_engine):
        """Replica A demotes a warm prefix; replica B pulls it over the
        REAL wire (``GET /v1/prefix?fetch=1`` through a loopback
        ReplicaServer, ``POST /v1/prefix`` install) and serves the same
        prompt with ZERO prefill tokens — bit-identical output."""
        from deepspeed_tpu.serving import ServingEngine
        from deepspeed_tpu.serving.fleet import (RemoteReplica,
                                                 ReplicaServer)
        from deepspeed_tpu.serving.frontend.frontend import \
            ServingFrontend
        p = _prompt(16, seed=7)
        dense = ServingEngine(engine=tiny_engine, max_batch=2,
                              max_prompt_len=16, max_queue=8,
                              decode_chunk=8)
        ref = dense.run([p.copy()], max_new_tokens=8)
        serve_a = _tiered(tiny_engine)
        serve_b = _tiered(tiny_engine)
        fe_a = ServingFrontend(serve_a)
        fe_b = ServingFrontend(serve_b)
        srv_a = ReplicaServer(fe_a)
        srv_b = ReplicaServer(fe_b)
        rem_a = RemoteReplica("127.0.0.1", srv_a.port)
        rem_b = RemoteReplica("127.0.0.1", srv_b.port)
        try:
            # warm A, then demote so the prefix becomes fetchable
            h = fe_a.submit(p.copy(), max_new_tokens=8)
            assert h.result(timeout=60) == "done"
            key = serve_a.kv.allocator.prefix.key_for(p)
            assert serve_a.kv.demote_prefix(key)
            assert rem_a.holds_prefix(key)
            assert not rem_b.holds_prefix(key)
            # the tier-fetch hop the router's fallback performs
            bundle = rem_a.fetch_prefix(key)
            assert bundle is not None
            assert bundle["schema"] == PREFIX_FETCH_SCHEMA
            assert rem_b.install_prefix(bundle)
            assert rem_b.holds_prefix(key)
            assert serve_a.kv_tier.peer_fetches == 1
            assert serve_b.kv_tier.peer_installs == 1
            # B serves the prompt warm: promotion, not re-prefill
            h2 = rem_b.submit(p.copy(), max_new_tokens=8)
            assert h2.result(timeout=60) == "done"
            assert [int(t) for t in h2.tokens] \
                == [int(t) for t in ref[0].tokens]
            assert serve_b.metrics.prefill_prompt_tokens == 0
            assert serve_b.metrics.n_prefix_hits == 1
            assert serve_b.kv_tier.promotions_dram == 1
        finally:
            for rem in (rem_a, rem_b):
                rem.close(timeout=5)
            for srv in (srv_a, srv_b):
                srv.close()
            for fe in (fe_a, fe_b):
                fe.close(timeout=5)
            serve_a.close()
            serve_b.close()

    def test_single_candidate_affinity_short_circuits_tier_fetch(self):
        """A sole routable candidate that already holds the prefix in
        its own HBM cache must count as an affinity hit, NOT trigger
        the tier-fetch fallback (a wasted cross-replica transfer plus
        a redundant DRAM-tier copy on the target)."""
        from collections import deque
        from deepspeed_tpu.serving import PrefixCache
        from deepspeed_tpu.serving.fleet import FleetRouter

        class _Sched:
            def __init__(self):
                self.queue = deque()
                self.running = {}
                self.finished = []

            def has_work(self):
                return False

        class _KV:
            prefix_enabled = True

            def __init__(self):
                self.prefix_cache = set()

        class _Eng:
            def __init__(self):
                self.max_seq_len = 64
                self.max_batch = 4
                self.scheduler = _Sched()
                self.chunk_in_flight = False
                self.kv = _KV()

        prompt = np.arange(1, 9, dtype=np.int32)
        key = PrefixCache.key_for(prompt)
        fetches = []
        with FleetRouter([_Eng(), _Eng()]) as router:
            router._tier_fetch = \
                lambda holder, target, k: fetches.append(k) or True
            router.replicas[1].draining = True    # unroutable holder
            router.replicas[1].engine.kv.prefix_cache.add(key)
            # the sole candidate holds the prefix in HBM: affinity hit
            router.replicas[0].engine.kv.prefix_cache.add(key)
            rep, decision = router._place_decision(prompt)
            assert rep.rid == 0 and decision["affinity_hit"]
            assert not fetches and router.n_tier_fetches == 0
            # once it does NOT hold it, the fallback still fires
            router.replicas[0].engine.kv.prefix_cache.discard(key)
            rep, decision = router._place_decision(prompt)
            assert rep.rid == 0 and not decision["affinity_hit"]
            assert decision.get("tier_fetch") == 1
            assert fetches == [key] and router.n_tier_fetches == 1

    def test_router_tier_fetch_helper_best_effort(self):
        """The router's fallback hop is best-effort plumbing around the
        frontend pair: success installs, a miss or a raising frontend
        just means the request prefills normally."""
        from types import SimpleNamespace
        from deepspeed_tpu.serving.fleet.router import FleetRouter
        installed = []
        holder = SimpleNamespace(frontend=SimpleNamespace(
            fetch_prefix=lambda key: {"schema": PREFIX_FETCH_SCHEMA,
                                      "key": key.hex(), "prompt_len": 8,
                                      "first_token": 1, "kv": {}}))
        target = SimpleNamespace(frontend=SimpleNamespace(
            install_prefix=lambda bundle: installed.append(bundle)
            or True))
        assert FleetRouter._tier_fetch(holder, target, b"\x01")
        assert installed and installed[0]["key"] == "01"
        empty = SimpleNamespace(frontend=SimpleNamespace(
            fetch_prefix=lambda key: None))
        assert not FleetRouter._tier_fetch(empty, target, b"\x01")
        def _boom(key):
            raise RuntimeError("wire down")
        dead = SimpleNamespace(frontend=SimpleNamespace(
            fetch_prefix=_boom))
        assert not FleetRouter._tier_fetch(dead, target, b"\x01")
        bare = SimpleNamespace(frontend=SimpleNamespace())
        assert not FleetRouter._tier_fetch(bare, target, b"\x01")
