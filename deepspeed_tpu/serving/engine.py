"""ServingEngine: continuous-batching façade over the inference stack.

Reference analogue: ``deepspeed/inference/engine.py`` serves ONE
``generate`` call at a time; production serving (the ROADMAP north star)
needs many concurrent streams. This engine composes

  * the existing :class:`~deepspeed_tpu.inference.engine.InferenceEngine`
    (TP placement, int8 dequant-in-program, multi-host input handling),
  * a slotted KV arena (serving/kv_cache.py) with per-slot fills,
  * an iteration-level scheduler (serving/scheduler.py),
  * live metrics through the monitor fan-out (serving/metrics.py),

into a DEVICE-PACED serve loop. The compiled model programs:

  prefill  (params, ids[n, P], lens[n], rng) -> (tok[n], cache)
           bucketed: P is the smallest power-of-two bucket (16/32/64/...)
           covering the batch's longest prompt, n <= max_batch; compiled
           lazily per (n, P) pair so a burst of short prompts stops
           paying ``max_prompt_len`` of padded compute
  decode_chunk
           (params, arena, tok[B], pos[B], act[B], eos[B], rem[B], rng)
           -> (toks[B, K], valid[B, K], arena, carry...)
           a ``lax.scan`` running K = ``decode_chunk`` decode steps per
           host iteration: sampling, per-slot EOS / token-budget stop
           masking, and KV writes all stay on device; retired lanes pin
           their write index at ``max_seq_len`` (models/gpt.py drops the
           write) so a dead lane never dirties KV rows. The host syncs
           ONCE per chunk and hands the token buffer to the scheduler in
           one ``step_tokens_chunk`` call. K = 1 is a chunk of one step
           through the same scan (one host sync per token).

(plus the trivial non-model insert programs that move prefilled caches
into arena slot rows). ``run()`` additionally double-buffers: the next
chunk is enqueued from the previous chunk's device-resident carry BEFORE
the host blocks on its token buffer, so scheduler bookkeeping overlaps
device compute (JAX async dispatch). This converts the serving tier from
host-paced (one dispatch + one sync per token) to device-paced (one per
K tokens) — the difference that shows up wherever dispatch latency
rivals the model's step time.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple, Union

import numpy as np

from ..telemetry import core as telemetry
from ..utils.logging import log_dist
from .device_timeline import DISAGGREGATED, DeviceTimeline
from .kv_cache import SlotKVCacheManager
from .metrics import ServingMetrics
# The sampling policy moved to serving/sampling.py (one reference shared
# by the engine, the speculative verifier, and the fused Pallas epilogue);
# re-exported here for API stability.
from .sampling import (filter_logits, fused_filter_logits,  # noqa: F401
                       fused_sample_tokens, sample_tokens)
from .scheduler import ContinuousBatchScheduler, Request


def default_prefill_buckets(max_prompt_len: int) -> List[int]:
    """Power-of-two prefill buckets from 16 up to ``max_prompt_len``
    (which always caps the list so every admissible prompt has a
    bucket)."""
    out: List[int] = []
    b = 16
    while b < max_prompt_len:
        out.append(b)
        b *= 2
    out.append(max_prompt_len)
    return out


MIGRATE_SCHEMA = "dstpu-migrate-v1"


class MigrationError(RuntimeError):
    """A live KV-block migration could not run — the request is NOT
    movable right now (mid-prefill, unsupported layout) or the target
    cannot host it (block-pool OOM, shape mismatch). The request keeps
    running wherever it already lives; migration failure is a
    load-balancing miss, never a lost stream."""


@dataclasses.dataclass
class _InflightChunk:
    """One enqueued decode chunk: device handles (nothing synced yet) plus
    the slot->request-uid snapshot at launch time, so tokens are never
    attributed to a slot's NEXT occupant."""
    slot_uids: Dict[int, int]
    tokens: Any          # [B, K] device ([B, K*(k+1)] speculative)
    valid: Any           # [B, K] device (lane was live entering the step)
    state: Tuple         # (tok[B], pos[B], act[B], rem[B], eos[B]) device,
    #                      + hist[B, S] in speculative mode
    # its handle in the device timeline (None while telemetry is off)
    program: Any = None
    # the chunk's routing counters, summed on the device over its steps
    # (a model with expert layers; fetched with the tokens, no sync of its own)
    routing: Any = None


class ServingEngine:
    """Continuous-batching server over a decoder LM.

    Pass an existing ``InferenceEngine`` (keeps its TP/quantization setup),
    or ``model`` + ``model_parameters`` to build one. Minimal use::

        serving = ServingEngine(model, model_parameters=params,
                                max_batch=8, dtype=jnp.float32)
        results = serving.run([prompt_ids_1, prompt_ids_2, ...],
                              max_new_tokens=32)
        results[0].output_ids      # prompt + generated tokens

    ``decode_chunk`` is the number of decode steps fused into one device
    program invocation (K). ``decode_chunk=1`` is a chunk of one step
    through the same scan (one host sync per token); greedy outputs are
    bit-identical across all K. Deadlines are only observed at chunk
    boundaries — a request may overrun its deadline by up to K-1 tokens
    of device work.
    """

    def __init__(self, model=None, model_parameters=None, *,
                 engine=None,
                 max_batch: int = 8,
                 max_prompt_len: Optional[int] = None,
                 max_queue: int = 64,
                 decode_chunk: int = 8,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 temperature: float = 0.0,
                 top_k: Optional[int] = None,
                 top_p: Optional[float] = None,
                 speculative: bool = False,
                 spec_k: int = 4,
                 spec_ngram: int = 2,
                 drafter=None,
                 kv_dtype: str = "auto",
                 monitor=None,
                 emit_every_steps: int = 16,
                 seed: int = 0,
                 paged: bool = False,
                 kv_block_size: int = 16,
                 kv_pool_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 prefix_cache_capacity: int = 64,
                 tp: int = 1,
                 disaggregate_prefill: bool = False,
                 fused_prefill: bool = False,
                 megakernel: bool = False,
                 prefill_chunk: int = 16,
                 chunk_token_budget: Optional[int] = None,
                 sp_prefill_threshold: Optional[int] = None,
                 tiered_kv: bool = False,
                 tier_dram_bytes: int = 256 << 20,
                 tier_nvme_bytes: Optional[int] = None,
                 tier_spill_dir: Optional[str] = None,
                 **inference_kwargs):
        import jax
        import jax.numpy as jnp

        if engine is None:
            from ..inference.engine import InferenceEngine
            if int(tp) > 1:
                # the serving-level tp knob rides the inference engine's
                # existing mesh/ShardingRules machinery (mp_size)
                inference_kwargs.setdefault("mp_size", int(tp))
            engine = InferenceEngine(model, model_parameters=model_parameters,
                                     **inference_kwargs)
        self.engine = engine
        mesh = getattr(engine, "mesh", None)
        self.tp = int(mesh.shape.get("tp", 1)) if mesh is not None else 1
        if int(tp) > 1 and self.tp != int(tp):
            raise ValueError(
                f"tp={tp} requested but the engine's mesh has tp={self.tp} "
                f"(pass mp_size={tp} when building the InferenceEngine, or "
                f"drop the engine= argument)")
        self.module = engine.module
        cfg = getattr(self.module, "cfg", None)
        max_seq = getattr(cfg, "max_seq_len", None)
        if max_seq is None:
            raise ValueError("ServingEngine needs a model with "
                             "cfg.max_seq_len (the KV arena extent)")
        self.kv_dtype = str(kv_dtype)
        if self.kv_dtype not in ("auto", "int8"):
            raise ValueError(f"kv_dtype must be 'auto' or 'int8', "
                             f"got {kv_dtype!r}")
        if self.kv_dtype == "int8":
            # rebuild the module with the int8 cache config BEFORE the
            # arena is shaped from it: every cache leaf the engine
            # compiles against (int8 payload + f32 scale leaves) comes
            # from this module's eval_shape
            cfg = dataclasses.replace(cfg, kv_cache_dtype="int8")
            self.module = type(self.module)(cfg)
        # ---- fused decode megakernel ----
        # One knob asks for the fused decode stack BY NAME. On the chip the
        # module is rebuilt with ``decode_impl="pallas"``: the all-lanes
        # decode kernel over a FLAT cache (int8 dequant inside the DMA
        # window, in-kernel k+1 speculative verify; its window is sized by
        # the deepest lane and, under the carried cache, it is handed a
        # copy of its layer's rows — the default ``"auto"`` read of each
        # lane's live blocks, models/gpt.py::live_read_block, is NOT what
        # this knob runs). On the CPU mesh the decode stays on the
        # partition-friendly einsum, where Pallas would only interpret, so
        # CPU parity gates run the program they always did. With it: the
        # sort-free sampling epilogue
        # (ops/pallas/sampling.py, swapped in below), and — when the mesh
        # has a tp axis under a parallel-residual model — the RS/AG
        # collective/MLP overlap (ops/tp_overlap.py). The knob asks for
        # the kernels BY NAME: shapes their gates refuse raise
        # ``KernelUnsupported`` here, at construction
        # (:meth:`_check_megakernel_gates`). Greedy outputs are
        # bit-identical with the knob on or off on the CPU (the megakernel
        # contract, gated by tests); temperature > 0 draws are
        # distributionally identical but consume the rng as Gumbel noise
        # instead of ``categorical``'s internal stream.
        self.megakernel = bool(megakernel)
        if self.megakernel:
            from ..utils.platform import on_chip
            rebuild = {}
            if on_chip() and getattr(cfg, "decode_impl", "pallas") != "pallas":
                rebuild["decode_impl"] = "pallas"
            if (self.tp > 1 and getattr(cfg, "parallel_residual", False)
                    and hasattr(cfg, "tp_overlap")):
                rebuild["tp_overlap"] = True
            if rebuild:
                cfg = dataclasses.replace(cfg, **rebuild)
                self.module = type(self.module)(cfg)
        self._overlap_active = bool(getattr(cfg, "tp_overlap", False))
        self.max_batch = int(max_batch)
        self.max_seq_len = int(max_seq)
        self.max_prompt_len = int(max_prompt_len or max_seq)
        if self.max_prompt_len > max_seq:
            raise ValueError(f"max_prompt_len {self.max_prompt_len} exceeds "
                             f"the model's max_seq_len {max_seq}")
        self.decode_chunk = int(decode_chunk)
        if self.decode_chunk < 1:
            raise ValueError(
                f"decode_chunk must be >= 1, got {decode_chunk}")
        # ---- fused chunked prefill (Sarathi-style, in-scan) ----
        # Prompts are split into ``prefill_chunk``-token pieces consumed by
        # the SAME scan body as decode steps under a per-lane mode mask, so
        # a long prompt can never stall every running stream's next chunk
        # launch. The bucketed prefill program stays behind
        # ``fused_prefill=False`` as the bit-parity reference.
        self.fused_prefill = bool(fused_prefill)
        self.prefill_chunk = min(int(prefill_chunk), self.max_prompt_len)
        if self.fused_prefill and self.prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        if self.fused_prefill and disaggregate_prefill:
            raise ValueError(
                "fused_prefill folds prefill into the decode scan; "
                "disaggregate_prefill needs a standalone prefill program "
                "on its own device slice — the two are mutually exclusive")
        if self.fused_prefill and speculative and float(temperature) != 0.0:
            raise ValueError(
                "fused_prefill + speculative supports greedy sampling only "
                "(temperature=0): the fused scan body verifies drafts with "
                "the greedy rule")
        # one token budget per scan iteration shared by prompt chunks and
        # decode lanes — the scheduler fills admission against it. Default:
        # room for ~2 concurrent prompt chunks on top of a full decode
        # batch (prefill keeps flowing without ever monopolizing a step).
        if chunk_token_budget is not None:
            self.chunk_token_budget = int(chunk_token_budget)
        else:
            self.chunk_token_budget = 2 * self.prefill_chunk + self.max_batch
        if self.fused_prefill and self.chunk_token_budget < 1:
            raise ValueError(
                f"chunk_token_budget must be >= 1, got {chunk_token_budget}")
        # prompts at/above this length skip inline chunking and run one
        # sequence-parallel (Ulysses) bucketed prefill instead — sp shards
        # the long forward over the mesh's sp axis, then hands the finished
        # KV to decode. None disables the sp leg. At mesh sp=1 (CPU tests)
        # every sp constraint is the identity, so outputs stay bitwise
        # equal to the plain bucketed program.
        self.sp_prefill_threshold = (None if sp_prefill_threshold is None
                                     else int(sp_prefill_threshold))
        if prefill_buckets is None:
            self._buckets = default_prefill_buckets(self.max_prompt_len)
        else:
            self._buckets = sorted(
                {int(b) for b in prefill_buckets
                 if 0 < int(b) <= self.max_prompt_len}
                | {self.max_prompt_len})
        self.temperature = float(temperature)
        self.top_k = top_k
        self.top_p = top_p
        self.speculative = bool(speculative)
        if self.speculative:
            from .speculative import NGramDrafter
            self.drafter = (drafter if drafter is not None
                            else NGramDrafter(spec_k, spec_ngram))
            self.spec_k = int(self.drafter.k)
        else:
            self.drafter = None
            self.spec_k = 0

        self.paged = bool(paged)
        # a block kind that cannot take a width or option says so by name
        refusal = getattr(self.module, "serving_refusal", None)
        why = refusal and refusal(
            speculative=self.speculative, fused_prefill=self.fused_prefill,
            paged=self.paged, tp=self.tp)
        if why:
            raise NotImplementedError(why)
        if self.megakernel:
            self._check_megakernel_gates(cfg, int(kv_block_size))
        if self.paged:
            from .paged_kv import PagedKVCacheManager
            # prefix reuse replays a stored first token, which is only
            # faithful when sampling is deterministic — greedy only
            self.kv = PagedKVCacheManager(
                self.module, engine.params, self.max_batch,
                block_size=kv_block_size, num_blocks=kv_pool_blocks,
                prefix_cache_capacity=prefix_cache_capacity,
                prefix_caching=prefix_cache and self.temperature == 0.0)
        else:
            self.kv = SlotKVCacheManager(self.module, engine.params,
                                         self.max_batch)

        # ---- tiered KV (serving/kv_tiers.py) ----
        # Demote cold prefix entries HBM -> host DRAM -> NVMe instead of
        # evicting; promote back asynchronously on a later hit.
        self.kv_tier = None
        if tiered_kv:
            if not self.paged:
                raise ValueError(
                    "tiered_kv requires paged=True (demotion is "
                    "block-granular behind the paged allocator)")
            if not self.kv.prefix_enabled:
                raise ValueError(
                    "tiered_kv needs the prefix cache (prefix_cache="
                    "True and temperature=0): demotion operates on "
                    "prefix-cache entries")
            from .kv_tiers import KVTierManager
            self.kv_tier = KVTierManager(
                dram_bytes=int(tier_dram_bytes),
                nvme_bytes=tier_nvme_bytes,
                spill_dir=tier_spill_dir)
            self.kv.attach_tier(self.kv_tier)

        # ---- mesh placement: tp-sharded KV + disaggregated prefill ----
        # Which params each program family sees. Default: the inference
        # engine's own placement for both. Disaggregation re-places two
        # committed copies on disjoint device slices of the engine mesh.
        self._decode_params = engine.params
        self._prefill_params = engine.params
        self._handoff_sharding = None       # set in disaggregated mode
        head_dim = self.kv.head_dim(getattr(cfg, "num_heads", None))
        self.disaggregated = bool(disaggregate_prefill)
        if self.disaggregated:
            from jax.sharding import NamedSharding, PartitionSpec
            from ..parallel import mesh as mesh_lib
            from ..runtime.sharding import ShardingRules, kv_shardings
            if getattr(engine, "quantized", False):
                raise ValueError(
                    "disaggregate_prefill with int8-quantized weights is "
                    "unsupported (two placements of the quantized tree)")
            devs = list(mesh.devices.flat)
            if len(devs) < 2:
                raise ValueError(
                    "disaggregate_prefill needs >= 2 devices (one decode "
                    "slice + one prefill slice)")
            half = len(devs) // 2
            dec_tp = self.tp if half % max(self.tp, 1) == 0 else 1
            dshape = mesh_lib.MeshShape.infer(half, tp=dec_tp)
            pshape = mesh_lib.MeshShape.infer(len(devs) - half, tp=dec_tp)
            self._decode_mesh = mesh_lib.build_mesh(dshape,
                                                    devices=devs[:half])
            self._prefill_mesh = mesh_lib.build_mesh(pshape,
                                                     devices=devs[half:])
            drules = ShardingRules(self._decode_mesh, zero_stage=0)
            prules = ShardingRules(self._prefill_mesh, zero_stage=0)
            self._decode_params = jax.device_put(
                engine.params,
                drules.shardings(drules.param_specs(engine.params)))
            self._prefill_params = jax.device_put(
                engine.params,
                prules.shardings(prules.param_specs(engine.params)))
            # prompt KV is born on the prefill slice and handed to the
            # decode slice replicated; the insert scatter then lands it in
            # the (possibly tp-sharded) pool rows
            self._handoff_sharding = NamedSharding(self._decode_mesh,
                                                   PartitionSpec())
            self.kv.update(jax.device_put(
                self.kv.cache,
                kv_shardings(self.kv.cache, self._decode_mesh,
                             head_dim=head_dim)))
        elif self.tp > 1:
            from ..runtime.sharding import kv_shardings
            # commit the fresh arena/pool with its tp NamedShardings so
            # the first insert/decode never sees an unplaced cache
            self.kv.update(jax.device_put(
                self.kv.cache,
                kv_shardings(self.kv.cache, mesh, head_dim=head_dim)))

        self.scheduler = ContinuousBatchScheduler(
            self.kv.allocator, max_queue=max_queue,
            max_prompt_len=self.max_prompt_len)
        self.metrics = ServingMetrics(monitor,
                                      emit_every_steps=emit_every_steps)
        self._rng = jax.random.PRNGKey(seed)
        self._last_token = np.zeros(self.max_batch, np.int32)
        # distinct (batch, bucket[, "sp"]) prefill shapes seen so far —
        # the compile count ServingMetrics reports
        self._prefill_shapes: Set[Tuple] = set()
        # host corrections to device-carried chunk state, applied at the
        # NEXT chunk launch (see _device_state)
        self._deact_slots: Set[int] = set()
        self._admit_patches: Dict[int, Tuple] = {}
        # fused-prefill host mirrors (slot-keyed, fused mode only).
        # Prompt-chunk consumption is DETERMINISTIC (a prefilling lane
        # can't EOS or exhaust its budget), so the host tracks it with two
        # cursors instead of syncing device state: _pf_consumed advances
        # at chunk CONSUME (authoritative — scheduler-facing state),
        # _pf_launched advances at chunk LAUNCH (the speculative horizon
        # the next prompt_buf is built from, one chunk ahead under the
        # double-buffered loop).
        self._pf_consumed: Dict[int, int] = {}
        self._pf_launched: Dict[int, int] = {}
        # slots whose token #1 has not been emitted yet: the first valid
        # token routes through scheduler.record_first_token (TTFT stamp,
        # no allocator advance), the rest through step_tokens_chunk
        self._pf_first_pending: Set[int] = set()
        # paged MISS admission plans deferred to first-token time: the
        # prefix-cache commit needs the sampled token #1, which the fused
        # path only learns when the completing chunk retires
        self._pf_plans: Dict[int, Any] = {}
        # prompt tokens consumed inside the decode scan (the fused
        # analogue of serve/prefill_tokens) — the frontend throughput
        # estimator folds this into its one-EWMA budget rate
        self.inline_prefill_tokens = 0
        # the at-most-one in-flight chunk of the double-buffered loop
        # (run()'s pipelined drain and external pump() drivers share it)
        self._pending: Optional[_InflightChunk] = None
        # the open serve/starved_* span, while telemetry is on and a host
        # sync has returned with nothing dispatched behind it: the chip
        # idles until the next dispatch leaves the span (_device_fed)
        self._starved = None
        self._timeline = DeviceTimeline(    # device time by program
            DISAGGREGATED if self._handoff_sharding is not None else None)
        # crash flight recorder (telemetry.flight_recorder), attached by
        # the owning ServingFrontend; engine-side records are host-only
        # deque appends — no device work, no retrace surface
        self.flight = None

        mat = engine._materialize
        module = self.module
        temperature_, top_k_ = self.temperature, self.top_k
        top_p_ = self.top_p
        # megakernel: every sampler call in the compiled programs routes
        # through the fused Pallas epilogue (its vocab gate was checked at
        # construction), and the speculative verifier filters with the
        # same fused kernel
        sample_ = fused_sample_tokens if self.megakernel else sample_tokens
        spec_filter_ = fused_filter_logits if self.megakernel else None
        max_seq_ = self.max_seq_len
        B_ = self.max_batch
        spec_k_ = self.spec_k
        drafter_ = self.drafter
        K = self.decode_chunk
        C_ = self.prefill_chunk

        # a model with expert layers hands out the experts its tokens chose
        # beside the logits and says how to count them; the prefill and
        # chunk programs sum the counters on the device over the tokens that
        # are somebody's (not a prompt's padding, not an idle lane) and
        # return them beside the tokens, a third output that the other
        # models' programs do not have
        count_routing = getattr(module, "routing_counters", None)

        def logits_and_routing(out, live):
            if not isinstance(out, tuple):
                return out, None
            if count_routing is None or not isinstance(out[1], dict):
                return out[0], None
            return out[0], count_routing(out[1], live)

        # a model whose cache is not a row a position (a window beside chunk
        # summaries) is told where each padded prompt ends, and says what a
        # decode step read of its lanes' state, from their positions alone
        def told(true_lens):
            if getattr(module, "prefill_takes_lengths", False):
                return {"lengths": true_lens}
            return {}

        count_state = getattr(module, "step_counters", None)

        def prefill(params, ids, true_lens, rng):
            pm = mat(params)
            positions = jnp.arange(ids.shape[1])[None, :]
            out, vc = module.apply({"params": pm}, ids, positions=positions,
                                   mutable=["cache"], **told(true_lens))
            logits, routing = logits_and_routing(
                out, positions < true_lens[:, None])
            # a model told where each row ends may hand out that row's
            # last logits alone (a large vocabulary at a long bucket)
            last = logits[:, 0] if logits.shape[1] == 1 else \
                jnp.take_along_axis(
                    logits, (true_lens - 1)[:, None, None], axis=1)[:, 0]
            tok = sample_(last, rng, temperature_, top_k_, top_p_)
            if routing is not None:
                return tok, vc["cache"], routing
            return tok, vc["cache"]

        # sequence-parallel (Ulysses) prefill for very long prompts: the
        # same bucketed program shape, but the module constrains q/k/v
        # head-sharded over the mesh's sp axis so the one long forward
        # spreads across chips before its KV is handed to decode. The
        # einsum paths are forced (the pallas custom calls don't
        # auto-partition under GSPMD); at sp=1 every constraint is the
        # identity, so outputs are bitwise equal to ``prefill``.
        sp_module = None
        if self.sp_prefill_threshold is not None:
            sp_cfg = dataclasses.replace(
                self.module.cfg, sequence_parallel=True,
                cp_impl="ulysses", attention_impl="xla",
                decode_impl="xla")
            sp_module = type(self.module)(sp_cfg)
        self._sp_module = sp_module

        def prefill_sp(params, ids, true_lens, rng):
            pm = mat(params)
            positions = jnp.arange(ids.shape[1])[None, :]
            logits, vc = sp_module.apply({"params": pm}, ids,
                                         positions=positions,
                                         mutable=["cache"])
            if isinstance(logits, tuple):
                logits = logits[0]
            last = jnp.take_along_axis(
                logits, (true_lens - 1)[:, None, None], axis=1)[:, 0]
            tok = sample_(last, rng, temperature_, top_k_, top_p_)
            return tok, vc["cache"]

        def _with_write_index(cache, write_pos):
            # the engine owns the per-slot write cursor: overwrite every
            # cache_index leaf with this step's write positions (retired
            # lanes carry the max_seq sentinel -> models/gpt.py drops the
            # write entirely)
            def leaf(path, x):
                if "cache_index" in jax.tree_util.keystr(path):
                    return jnp.broadcast_to(
                        write_pos.astype(x.dtype), x.shape)
                return x
            return jax.tree_util.tree_map_with_path(leaf, cache)

        def decode_chunk_fn(params, cache, tokens, positions, active,
                            eos, remaining, rng):
            pm = mat(params)

            def body(carry, _):
                c, tok, pos, act, rem, key = carry
                write_pos = jnp.where(act, pos,
                                      jnp.int32(max_seq_))  # masked lanes
                c = _with_write_index(c, write_pos)
                out, vc = module.apply(
                    {"params": pm, "cache": c}, tok[:, None],
                    positions=pos[:, None], mutable=["cache"])
                logits, routing = logits_and_routing(out, act[:, None])
                state = count_state(pos, act) if count_state else None
                if state is not None:
                    routing = dict(routing or {}, state=state)
                key, sub = jax.random.split(key)
                nxt = sample_(logits[:, -1], sub,
                              temperature_, top_k_, top_p_)
                nxt = jnp.where(act, nxt, tok)       # frozen lanes hold
                emitted = act                        # validity of nxt
                rem = jnp.where(act, rem - 1, rem)
                hit_eos = jnp.logical_and(eos >= 0, nxt == eos)
                act = jnp.logical_and(
                    act, jnp.logical_and(rem > 0,
                                         jnp.logical_not(hit_eos)))
                pos = jnp.where(emitted, pos + 1, pos)
                return ((vc["cache"], nxt, pos, act, rem, key),
                        (nxt, emitted, routing))

            (c, tok_f, pos_f, act_f, rem_f, _), (toks, valid, routing) = \
                jax.lax.scan(
                    body, (cache, tokens, positions, active, remaining, rng),
                    None, length=K)
            out = (jnp.moveaxis(toks, 0, 1), jnp.moveaxis(valid, 0, 1),
                   c, tok_f, pos_f, act_f, rem_f)
            if routing is not None:
                out += (jax.tree.map(lambda x: jnp.sum(x, axis=0), routing),)
            return out

        def decode_chunk_spec_fn(params, cache, tokens, positions, active,
                                 eos, remaining, hist, rng):
            """Speculative chunk: each scan step drafts k tokens per lane
            (drafter gathers over the device-resident [B, S] history),
            scores all k+1 positions in ONE target forward, and emits the
            accepted prefix + correction token — up to k+1 tokens per lane
            per step, with exactly the sampler's distribution (greedy:
            bit-identical to the sequential loop; see
            serving/speculative.py for the argument). The per-lane
            accepted length n advances the write cursor and positions;
            KV rows written for rejected drafts sit ABOVE the new fill,
            so they are dead (masked by every later read) until a later
            step overwrites them."""
            from .speculative import verify_greedy, verify_rejection
            pm = mat(params)
            kp1 = spec_k_ + 1
            rows = jnp.arange(B_, dtype=jnp.int32)
            j = jnp.arange(kp1, dtype=jnp.int32)[None, :]

            def body(carry, _):
                c, tok, pos, act, rem, key, h = carry
                # keep the invariant hist[b, pos[b]] == tok[b] (idempotent
                # after the first step; fresh admits are patched by the
                # host, this covers the launch-time carry)
                h = h.at[rows, jnp.where(act, pos, jnp.int32(max_seq_))
                         ].set(tok, mode="drop")
                drafts = drafter_.propose(h, tok, pos)          # [B, k]
                inputs = jnp.concatenate([tok[:, None], drafts], axis=1)
                write_pos = jnp.where(act, pos, jnp.int32(max_seq_))
                c = _with_write_index(c, write_pos)
                qpos = pos[:, None] + j
                logits, vc = module.apply(
                    {"params": pm, "cache": c}, inputs,
                    positions=qpos, mutable=["cache"])
                if isinstance(logits, tuple):
                    logits = logits[0]                          # [B,k+1,V]
                if temperature_ == 0.0:
                    emitted, acc = verify_greedy(logits, drafts)
                    key_n = key
                else:
                    key_n, sub = jax.random.split(key)
                    emitted, acc = verify_rejection(
                        logits, drafts, sub, temperature_, top_k_, top_p_,
                        filter_fn=spec_filter_)
                # candidate validity: live lane, within the accepted
                # prefix (+ the correction/bonus at j == acc), within the
                # remaining token budget
                cand = act[:, None] & (j <= acc[:, None]) & \
                    (j < rem[:, None])
                hit = (eos[:, None] >= 0) & (emitted == eos[:, None])
                cut = (cand & hit).astype(jnp.int32)
                prior_hits = jnp.cumsum(cut, axis=1) - cut
                valid = cand & (prior_hits == 0)    # stop AFTER first EOS
                n = jnp.sum(valid.astype(jnp.int32), axis=1)    # [B]
                last = jnp.take_along_axis(
                    emitted, jnp.clip(n - 1, 0, spec_k_)[:, None],
                    axis=1)[:, 0]
                tok_n = jnp.where(n > 0, last, tok)
                stopped = jnp.any(valid & hit, axis=1)
                rem_n = rem - n
                act_n = act & (rem_n > 0) & jnp.logical_not(stopped)
                # emitted token j landed at history index pos + 1 + j
                widx = jnp.where(valid, pos[:, None] + 1 + j,
                                 jnp.int32(max_seq_))
                h = h.at[rows[:, None], widx].set(emitted, mode="drop")
                pos_n = pos + n
                return ((vc["cache"], tok_n, pos_n, act_n, rem_n, key_n, h),
                        (emitted, valid))

            (c, tok_f, pos_f, act_f, rem_f, _, hist_f), (toks, valid) = \
                jax.lax.scan(
                    body,
                    (cache, tokens, positions, active, remaining, rng,
                     hist),
                    None, length=K)
            toks = jnp.moveaxis(toks, 0, 1).reshape(B_, K * kp1)
            valid = jnp.moveaxis(valid, 0, 1).reshape(B_, K * kp1)
            return (toks, valid, c, tok_f, pos_f, act_f, rem_f, hist_f)

        def decode_chunk_fused_fn(params, cache, tokens, positions, active,
                                  eos, remaining, pf_rem, prompt_buf, rng):
            """Fused chunked-prefill decode scan (the Sarathi-Serve /
            vLLM chunked-prefill idea, in-scan): each scan step a live
            lane either consumes its next <= C prompt tokens (prefill
            mode — incremental KV append, nothing emitted until the
            completing chunk samples token #1) or emits one decode token.
            ONE C-wide forward serves both modes under the per-lane mode
            mask ``pf_rem > 0``; decode lanes broadcast their last token
            across the C columns and sample at column 0. ``prompt_buf``
            [K, B, C] carries each prefilling lane's next K*C prompt
            tokens (zeros elsewhere — the host builds it per launch).

            Write-cursor discipline is unchanged: pad columns write KV
            ABOVE the lane's logical fill (or through the paged table's
            sentinel rows), where every causal read masks them until a
            later step legitimately overwrites — the same argument that
            covers the speculative verify's rejected-draft rows. Greedy
            outputs are bitwise identical to bucketed prefill + decode
            because both run the same masked cache attention per
            position (tests/test_fused_prefill.py)."""
            pm = mat(params)
            cspan = jnp.arange(C_, dtype=jnp.int32)[None, :]

            def body(carry, pchunk):
                c, tok, pos, act, rem, pf, key = carry
                is_pf = jnp.logical_and(act, pf > 0)
                n_cons = jnp.where(is_pf, jnp.minimum(pf, C_), 0)
                completing = jnp.logical_and(is_pf, pf <= C_)
                inputs = jnp.where(is_pf[:, None], pchunk, tok[:, None])
                qpos = pos[:, None] + cspan
                write_pos = jnp.where(act, pos, jnp.int32(max_seq_))
                c = _with_write_index(c, write_pos)
                logits, vc = module.apply(
                    {"params": pm, "cache": c}, inputs,
                    positions=qpos, mutable=["cache"])
                if isinstance(logits, tuple):
                    logits = logits[0]                      # [B, C, V]
                key, sub = jax.random.split(key)
                # sample at the lane's LAST real column: n_cons-1 for a
                # completing prefill lane (token #1), 0 for decode lanes
                sel = jnp.where(is_pf, jnp.maximum(n_cons - 1, 0), 0)
                last = jnp.take_along_axis(
                    logits, sel[:, None, None], axis=1)[:, 0]   # [B, V]
                nxt = sample_(last, sub, temperature_, top_k_,
                              top_p_)
                emits = jnp.logical_and(
                    act, jnp.logical_or(completing,
                                        jnp.logical_not(is_pf)))
                nxt = jnp.where(emits, nxt, tok)
                rem = jnp.where(emits, rem - 1, rem)
                hit_eos = (eos >= 0) & (nxt == eos) & emits
                act = jnp.logical_and(
                    act, jnp.where(emits,
                                   (rem > 0) & jnp.logical_not(hit_eos),
                                   True))
                pos = pos + jnp.where(is_pf, n_cons,
                                      jnp.where(emits, 1, 0))
                pf = pf - n_cons
                return ((vc["cache"], nxt, pos, act, rem, pf, key),
                        (nxt, emits))

            (c, tok_f, pos_f, act_f, rem_f, pf_f, _), (toks, valid) = \
                jax.lax.scan(
                    body,
                    (cache, tokens, positions, active, remaining, pf_rem,
                     rng),
                    prompt_buf)
            return (jnp.moveaxis(toks, 0, 1), jnp.moveaxis(valid, 0, 1),
                    c, tok_f, pos_f, act_f, rem_f, pf_f)

        def decode_chunk_fused_spec_fn(params, cache, tokens, positions,
                                       active, eos, remaining, pf_rem,
                                       prompt_buf, hist, rng):
            """Fused chunked prefill + speculative decode (greedy only —
            enforced at construction). Step width is W = max(C, k+1):
            prefill-mode lanes consume their next prompt chunk through
            the first C columns; decode-mode lanes verify k drafts
            through the first k+1. A completing prefill lane emits token
            #1 at ys column 0; the host excludes prefill-mode steps from
            acceptance accounting via its own deterministic replay of
            the pf cursor (engine._sim_chunk_prefill)."""
            from .speculative import verify_greedy
            pm = mat(params)
            kp1 = spec_k_ + 1
            W = max(C_, kp1)
            rows = jnp.arange(B_, dtype=jnp.int32)
            j = jnp.arange(kp1, dtype=jnp.int32)[None, :]
            wspan = jnp.arange(W, dtype=jnp.int32)[None, :]

            def body(carry, pchunk):
                c, tok, pos, act, rem, pf, key, h = carry
                is_pf = jnp.logical_and(act, pf > 0)
                n_cons = jnp.where(is_pf, jnp.minimum(pf, C_), 0)
                completing = jnp.logical_and(is_pf, pf <= C_)
                is_dec = jnp.logical_and(act, jnp.logical_not(is_pf))
                # hist invariant h[b, pos] == tok for DECODE lanes only —
                # a prefilling lane's row already holds its prompt at
                # [0, L), and pos points inside it
                h = h.at[rows, jnp.where(is_dec, pos, jnp.int32(max_seq_))
                         ].set(tok, mode="drop")
                drafts = drafter_.propose(h, tok, pos)          # [B, k]
                dec_in = jnp.concatenate([tok[:, None], drafts], axis=1)
                if W > kp1:
                    dec_in = jnp.pad(dec_in, ((0, 0), (0, W - kp1)))
                pf_in = pchunk
                if W > C_:
                    pf_in = jnp.pad(pf_in, ((0, 0), (0, W - C_)))
                inputs = jnp.where(is_pf[:, None], pf_in, dec_in)
                write_pos = jnp.where(act, pos, jnp.int32(max_seq_))
                c = _with_write_index(c, write_pos)
                qpos = pos[:, None] + wspan
                logits, vc = module.apply(
                    {"params": pm, "cache": c}, inputs,
                    positions=qpos, mutable=["cache"])
                if isinstance(logits, tuple):
                    logits = logits[0]                      # [B, W, V]
                # ---- decode lanes: greedy verify over the first k+1 ----
                emitted, acc = verify_greedy(logits[:, :kp1], drafts)
                cand = is_dec[:, None] & (j <= acc[:, None]) & \
                    (j < rem[:, None])
                hitv = (eos[:, None] >= 0) & (emitted == eos[:, None])
                cut = (cand & hitv).astype(jnp.int32)
                prior_hits = jnp.cumsum(cut, axis=1) - cut
                dvalid = cand & (prior_hits == 0)
                n = jnp.sum(dvalid.astype(jnp.int32), axis=1)   # [B]
                last = jnp.take_along_axis(
                    emitted, jnp.clip(n - 1, 0, spec_k_)[:, None],
                    axis=1)[:, 0]
                # ---- prefill lanes: token #1 at column n_cons - 1 ----
                sel = jnp.maximum(n_cons - 1, 0)
                t1 = jnp.argmax(jnp.take_along_axis(
                    logits, sel[:, None, None], axis=1)[:, 0],
                    axis=-1).astype(jnp.int32)
                pf_emit = jnp.logical_and(act, completing)
                t1_eos = (eos >= 0) & (t1 == eos) & pf_emit
                # ---- merge the two modes' carries ----
                tok_n = jnp.where(is_pf, jnp.where(pf_emit, t1, tok),
                                  jnp.where(n > 0, last, tok))
                stopped = jnp.any(dvalid & hitv, axis=1) | t1_eos
                n_all = jnp.where(is_pf, pf_emit.astype(jnp.int32), n)
                rem_n = rem - n_all
                act_n = act & jnp.where(
                    jnp.logical_and(is_pf, jnp.logical_not(pf_emit)),
                    True, (rem_n > 0) & jnp.logical_not(stopped))
                # ys fixed at width W: decode lanes at columns 0..k, a
                # completing prefill lane's token #1 at column 0
                ys_tok = jnp.where(is_pf[:, None],
                                   jnp.broadcast_to(t1[:, None],
                                                    (B_, kp1)), emitted)
                ys_val = jnp.where(is_pf[:, None],
                                   pf_emit[:, None] & (j == 0), dvalid)
                if W > kp1:
                    ys_tok = jnp.pad(ys_tok, ((0, 0), (0, W - kp1)))
                    ys_val = jnp.pad(ys_val, ((0, 0), (0, W - kp1)))
                # history: decode-lane token j landed at pos + 1 + j;
                # a completing lane's token #1 at index prompt_len
                widx = jnp.where(dvalid, pos[:, None] + 1 + j,
                                 jnp.int32(max_seq_))
                h = h.at[rows[:, None], widx].set(emitted, mode="drop")
                h = h.at[rows, jnp.where(pf_emit, pos + n_cons,
                                         jnp.int32(max_seq_))
                         ].set(t1, mode="drop")
                pos_n = pos + jnp.where(is_pf, n_cons, n)
                pf_n = pf - n_cons
                return ((vc["cache"], tok_n, pos_n, act_n, rem_n, pf_n,
                         key, h), (ys_tok, ys_val))

            (c, tok_f, pos_f, act_f, rem_f, pf_f, _, hist_f), \
                (toks, valid) = jax.lax.scan(
                    body,
                    (cache, tokens, positions, active, remaining, pf_rem,
                     rng, hist),
                    prompt_buf)
            toks = jnp.moveaxis(toks, 0, 1).reshape(B_, K * W)
            valid = jnp.moveaxis(valid, 0, 1).reshape(B_, K * W)
            return (toks, valid, c, tok_f, pos_f, act_f, rem_f, pf_f,
                    hist_f)

        # prefill retraces lazily per (n, bucket) shape — the jit cache IS
        # the bucket program table
        self._jit_prefill = jax.jit(prefill)
        # the sp prefill is its own program family ("prefill_sp_fn"),
        # bucket-lazy exactly like the plain prefill
        if sp_module is not None:
            prefill_sp.__name__ = "prefill_sp_fn"
            self._jit_prefill_sp = jax.jit(prefill_sp)
        else:
            self._jit_prefill_sp = None
        # distinct function name => distinct TraceAuditor budget: every
        # fused / spec / int8 / paged combination is a different compiled
        # program family whose retrace count is pinned separately
        # ("decode_chunk" + "_megakernel"? + "_fused"? + "_spec"? +
        # "_int8"? + "_paged"? + "_fn")
        variant = "decode_chunk"
        if self.megakernel:
            variant += "_megakernel"
        if self.fused_prefill:
            variant += "_fused"
        if self.speculative:
            variant += "_spec"
        if self.kv_dtype == "int8":
            variant += "_int8"
        if self.paged:
            variant += "_paged"
        # tp-sharded and disaggregated engines compile against different
        # placement metadata, so they are their own program families with
        # their own pinned budgets — the dense/paged budgets stay exact
        if self.tp > 1:
            variant += f"_tp{self.tp}"
        if self.disaggregated:
            variant += "_disagg"
        variant += "_fn"
        if self.fused_prefill:
            chunk_fn = (decode_chunk_fused_spec_fn if self.speculative
                        else decode_chunk_fused_fn)
        else:
            chunk_fn = (decode_chunk_spec_fn if self.speculative
                        else decode_chunk_fn)
        chunk_fn.__name__ = variant
        # donate the arena: every slot's KV rows are updated in place. The
        # donation alone does not do that — the model does: a cache that is
        # passed in is CARRIED by its layer loop (models/gpt.py), each layer
        # scatters its tokens into the stacked leaves at (layer, lane, pos),
        # and the chunk's scan carries the same buffers from step to step,
        # so the chunk program aliases the arena and holds no copy of it
        # (tests/test_decode_arena_in_place.py)
        self._jit_decode_chunk = jax.jit(chunk_fn, donate_argnums=(1,))

        # the host's corrections to the lane state a chunk carries
        # (_device_state): lanes retired go inactive, lanes admitted take
        # their state, every other lane keeps what the device computed
        def lane_patch(tok, pos, act, rem, eos, deact, admit, vals):
            return (jnp.where(admit, vals[0], tok),
                    jnp.where(admit, vals[1], pos),
                    (act & ~deact) | admit,
                    jnp.where(admit, vals[2], rem),
                    jnp.where(admit, vals[3], eos))

        self._jit_lane_patch = jax.jit(lane_patch)
        # arena-size gauges at init: the KV footprint is fixed for the
        # engine's lifetime, headroom varies (re-gauged per chunk)
        arena = self.kv.arena_report()
        telemetry.gauge("serve/arena_bytes", float(arena["arena_bytes"]))
        telemetry.gauge("serve/arena_headroom_bytes",
                        float(arena["headroom_bytes"]))
        # int8 KV: bytes the quantized arena saves vs the fp layout it
        # replaces (0.0 in fp mode — the gauge is always present so
        # dashboards need no mode branch)
        telemetry.gauge("serve/kv_bytes_saved",
                        float(arena.get("kv_bytes_saved", 0.0)))
        if self.paged:
            self._bytes_per_block = arena["bytes_per_block"]
            self._gauge_block_pool()
        else:
            self._arena_bytes_per_slot = arena["bytes_per_slot"]
        # what a decode step reads of the dense arena, for the
        # serve/kv_blocks_* counters: the block of the live-rows read where
        # the chunk program takes it (the model says; the plain one-token
        # chunk body alone can), else every row of every lane
        from ..ops.pallas.decode_attention import live_block
        read_block = getattr(self.module, "decode_read_block", None)
        self._kv_read_block = None
        if read_block is not None and not (
                self.paged or self.speculative or self.fused_prefill):
            self._kv_read_block = read_block(self.max_batch)
        self._kv_count_block = (self._kv_read_block
                                or live_block(self.max_seq_len))
        log_dist(f"serving engine ready: slots={self.max_batch} "
                 f"prefill_buckets={self._buckets} "
                 f"decode_chunk={self.decode_chunk} "
                 f"max_seq={max_seq} "
                 f"kv={'paged' if self.paged else 'dense'} "
                 f"tp={self.tp} "
                 f"disaggregated={self.disaggregated}", ranks=[0])

    def _check_megakernel_gates(self, cfg, kv_block_size: int) -> None:
        """``megakernel=True`` names its kernels: refuse at construction
        any shape the fused sampling epilogue or — on the chip, where the
        decode runs the Pallas kernel — the decode kernel's gate refuses,
        with the shape and the reason, instead of serving the reference
        under a configuration that says fused."""
        import jax.numpy as jnp
        from ..ops.pallas._utils import refuse
        from ..ops.pallas.decode_attention import (decode_refusal,
                                                   paged_decode_refusal)
        from ..ops.pallas.sampling import sampling_refusal
        from ..utils.platform import on_chip
        vocab = int(cfg.vocab_size)
        reason = sampling_refusal(self.max_batch, vocab)
        if reason is not None:
            refuse("megakernel=True (fused sampling epilogue)",
                   (self.max_batch, vocab), reason)
        if not on_chip():
            return
        mesh = getattr(self.engine, "mesh", None)
        n_dev = int(mesh.size) if mesh is not None else 1
        if n_dev > 1:
            # found on four v5e chips (PR 21): the first compile dies with
            # "Mosaic kernels cannot be automatically partitioned. Please
            # wrap the call in a shard_map"
            refuse("megakernel=True", f"engine mesh of {n_dev} devices",
                   "GSPMD cannot partition a Mosaic custom call and the "
                   "decode and sampling kernels are not wrapped in "
                   "shard_map; build one engine per chip on a one-device "
                   "mesh (ROADMAP R9)")
        if cfg.decode_impl != "pallas":
            return
        # (asked before the arena exists: the config's own head size)
        h, d = int(cfg.num_heads), int(cfg.head_dim)
        # query positions per decode-scan step
        width = (self.spec_k + 1) if self.speculative else 1
        if self.fused_prefill:
            width = max(width, self.prefill_chunk)
        kv_dt = jnp.int8 if self.kv_dtype == "int8" else cfg.dtype
        if self.paged:
            reason = paged_decode_refusal(self.max_batch, kv_block_size, h,
                                          d, kv_dt, width)
            shape = (f"batch={self.max_batch} kv_block_size={kv_block_size} "
                     f"h={h} d={d} kv={jnp.dtype(kv_dt).name} s={width}")
        else:
            reason = decode_refusal(self.max_batch, self.max_seq_len, h, d,
                                    cfg.dtype, width)
            shape = (f"batch={self.max_batch} S={self.max_seq_len} h={h} "
                     f"d={d} s={width}")
        if reason is not None:
            refuse("megakernel=True (Pallas decode)", shape, reason)

    # --------------------------------------------------------------- API
    def submit(self, prompt: Union[Request, Sequence[int], np.ndarray],
               **request_kwargs) -> Request:
        """Enqueue one request (token-id prompt or a prebuilt Request).
        Rejections (bounded queue, oversized prompt) come back as
        ``status == "rejected"`` with ``reject_reason`` set — the
        backpressure signal, not an exception."""
        req = prompt if isinstance(prompt, Request) else Request(
            prompt=np.asarray(prompt, np.int32), **request_kwargs)
        self.metrics.start()
        if not self.scheduler.submit(req):
            self.metrics.on_rejected()
        return req

    def cancel(self, req: Request) -> bool:
        """Caller-initiated termination: a queued request never prefills;
        a running one frees its slot immediately (host side) and its
        device lane is deactivated at the NEXT chunk launch through the
        host-event patch path (``_deact_slots``), so at most K-1 tokens of
        speculative device work are wasted — and none are delivered,
        because the launch-time slot->uid snapshot drops tokens from
        retired occupants. Returns False if the request was already
        terminal."""
        slot = req.slot if req.status == "running" else None
        cancelled = self.scheduler.cancel(req)
        if cancelled and slot is not None:
            self._deact_slots.add(slot)
            self._admit_patches.pop(slot, None)
            self._clear_pf_slot(slot)
        return cancelled

    def _clear_pf_slot(self, slot: int) -> None:
        """Drop a slot's fused-prefill mirrors (lane retired or admitted
        through a non-inline path). An uncommitted paged MISS plan also
        releases its duplicate-prompt hold so an identical prompt can
        admit again."""
        self._pf_consumed.pop(slot, None)
        self._pf_launched.pop(slot, None)
        self._pf_first_pending.discard(slot)
        plan = self._pf_plans.pop(slot, None)
        if plan is not None:
            self.kv.abandon_plan(plan)

    # ------------------------------------------------- live migration
    def can_migrate(self, req: Request) -> bool:
        """Is ``req`` movable right now? Paged KV only (blocks are the
        portable unit), tp=1 (a sharded pool's leaves live on a mesh this
        bundle format doesn't describe), running with at least one
        emitted token, and fully prefilled — a mid-prompt fused lane's KV
        is still being written by the scan."""
        if not self.paged or self.tp > 1 or self.disaggregated:
            return False
        if req.status != "running" or not req.tokens:
            return False
        slot = req.slot
        if slot is None or self.scheduler.running.get(slot) is not req:
            return False
        if self.fused_prefill and self._pf_consumed.get(
                slot, req.prompt_len) < req.prompt_len:
            return False
        return True

    def export_request(self, req: Request) -> Dict[str, Any]:
        """Serialize a RUNNING request's full decode state: KV blocks
        (in table order, written blocks only), the decode cursor, and
        the request identity — the bundle ``import_request`` re-homes on
        another engine. Consistency argument: at a chunk boundary
        ``fill == prompt_len + len(tokens) - 1`` and the last token's KV
        row is NOT yet written (it is written when the token is fed), so
        rows ``[0, fill)`` are final even with the next chunk in flight —
        that chunk only writes at/above ``fill``, and gathering the
        post-chunk pool syncs after those writes land harmlessly in rows
        the importer masks (its write cursor starts at ``fill``). Does
        NOT cancel ``req`` — the caller re-homes first, then cancels."""
        if not self.can_migrate(req):
            raise MigrationError(
                f"request uid={req.uid} is not migratable "
                f"(status={req.status!r}, paged={self.paged}, "
                f"tp={self.tp})")
        slot = req.slot
        fill = req.prompt_len + len(req.tokens) - 1
        have = int(self.kv.fill[slot])
        if have != fill:
            raise MigrationError(
                f"slot {slot} fill {have} != expected {fill} "
                f"(chunk boundary invariant violated)")
        bs = self.kv.allocator.block_size
        n_blocks = max(1, -(-fill // bs))
        leaves = self.kv.export_blocks(slot, n_blocks)
        kv_bytes = sum(int(a.nbytes) for a in leaves.values())
        telemetry.instant("serve/migrate_export", uid=req.uid,
                          slot=slot, n_blocks=n_blocks, bytes=kv_bytes)
        if self.flight is not None:
            self.flight.record("migrate_export", uid=req.uid, slot=slot,
                               n_blocks=n_blocks, bytes=kv_bytes)
        return {
            "schema": MIGRATE_SCHEMA,
            "prompt": [int(t) for t in np.asarray(req.prompt)],
            "tokens": [int(t) for t in req.tokens],
            "max_new_tokens": int(req.max_new_tokens),
            "eos_token_id": (None if req.eos_token_id is None
                             else int(req.eos_token_id)),
            "deadline_s": (None if req.deadline_s is None
                           else float(req.deadline_s)),
            "tenant": req.tenant,
            "trace_id": req.trace_id,
            "fill": int(fill),
            "block_size": int(bs),
            "n_blocks": int(n_blocks),
            "kv_bytes": int(kv_bytes),
            "kv": leaves,
        }

    def import_request(self, bundle: Dict[str, Any]) -> Request:
        """Re-home an exported request: lease a slot + its full block
        reservation (``alloc_span``), scatter the shipped blocks, and
        join the running set mid-decode — the next chunk feeds the
        carried last token at position ``fill``, exactly as the source
        engine would have. Raises :class:`MigrationError` when this
        engine cannot host it (layout mismatch, pool OOM); the caller
        re-imports at the source or fails the stream structurally."""
        if not self.paged or self.tp > 1 or self.disaggregated:
            raise MigrationError(
                "import_request needs a paged, unsharded engine")
        if bundle.get("schema") != MIGRATE_SCHEMA:
            raise MigrationError(
                f"unknown migration schema {bundle.get('schema')!r}")
        bs = self.kv.allocator.block_size
        if int(bundle["block_size"]) != bs:
            raise MigrationError(
                f"block_size mismatch: bundle {bundle['block_size']} "
                f"vs engine {bs}")
        prompt = np.asarray(bundle["prompt"], np.int32)
        tokens = [int(t) for t in bundle["tokens"]]
        fill = int(bundle["fill"])
        max_new = int(bundle["max_new_tokens"])
        if fill != prompt.shape[0] + len(tokens) - 1:
            raise MigrationError(
                f"bundle cursor fill={fill} inconsistent with "
                f"prompt_len={prompt.shape[0]} + {len(tokens)} tokens")
        if fill + 1 > self.max_seq_len:
            raise MigrationError(
                f"sequence length {fill + 1} exceeds this engine's "
                f"max_seq_len {self.max_seq_len}")
        n_lease = min(-(-(prompt.shape[0] + max_new) // bs),
                      self.kv.allocator.blocks_per_seq)
        if n_lease < int(bundle["n_blocks"]):
            raise MigrationError(
                f"lease of {n_lease} blocks cannot hold the bundle's "
                f"{bundle['n_blocks']} written blocks")
        slot = self.kv.allocator.alloc_span(fill, n_lease)
        if slot is None:
            raise MigrationError(
                "kv_blocks_exhausted: no slot/blocks for the incoming "
                "request")
        try:
            self.kv.import_blocks(slot, bundle["kv"])
        except Exception:
            self.kv.allocator.free(slot)
            raise
        req = Request(
            prompt=prompt, max_new_tokens=max_new,
            eos_token_id=bundle.get("eos_token_id"),
            deadline_s=bundle.get("deadline_s"),
            trace_id=bundle.get("trace_id"),
            tenant=bundle.get("tenant") or "default")
        now = self.scheduler.clock()
        req.submit_t = now
        req.first_token_t = now
        req.status = "running"
        req.slot = slot
        req.tokens = tokens
        self.scheduler.running[slot] = req
        self._last_token[slot] = tokens[-1]
        if self.fused_prefill:
            self._clear_pf_slot(slot)
        rem = min(max_new - len(tokens),
                  self.kv.allocator.remaining(slot))
        eos = -1 if req.eos_token_id is None else int(req.eos_token_id)
        # admit-style patch, but the lane resumes at the migrated
        # cursor (pos = fill, not prompt_len): the carried last
        # token's KV row is written by the lane's first step here
        patch = (tokens[-1], fill, rem, eos)
        if self.fused_prefill:
            patch = patch + (0,)        # pf_rem: fully prefilled
        if self.speculative:
            patch = patch + (self._history_row(req),)
        self._admit_patches[slot] = patch
        self._deact_slots.discard(slot)
        telemetry.instant("serve/migrate_import", uid=req.uid,
                          slot=slot, fill=fill,
                          n_blocks=int(bundle["n_blocks"]))
        if self.flight is not None:
            self.flight.record("migrate_import", uid=req.uid, slot=slot,
                               fill=fill, tenant=req.tenant)
        self._gauge_block_pool()
        return req

    def pump(self) -> List[Request]:
        """One iteration of the double-buffered serve loop for EXTERNAL
        drivers (the serving frontend's engine thread): admit, keep one
        chunk in flight, and return every request that reached a terminal
        state during the call. Unlike ``step()`` this does not force a
        launch+sync pair per call — the in-flight chunk carries over
        between calls, so an external driver gets the same device-paced
        overlap ``run()`` has. Call until ``has_work()`` is False AND the
        last call returned with nothing in flight to drain completely."""
        before = len(self.scheduler.finished)
        with telemetry.span("serve/pump"):
            if self._pending is None:
                self._admit()
                if self.scheduler.running:
                    self._pending = self._launch_chunk(self._host_state())
            else:
                nxt = None
                if self._may_outlive_chunk():
                    nxt = self._launch_chunk(
                        self._device_state(self._pending))
                self._consume_chunk(self._pending,
                                    device_queue_empty=nxt is None)
                self._admit(ahead=nxt)
                self._pending = nxt
        self._drop_starved_if_idle()
        return self.scheduler.finished[before:]

    @property
    def chunk_in_flight(self) -> bool:
        """True while a launched decode chunk has not been consumed —
        drain loops must keep pumping until this clears even after the
        scheduler reports no work."""
        return self._pending is not None

    def step(self) -> List[Request]:
        """One synchronous continuous-batching iteration: admit
        newly-runnable requests into free slots (bucketed batched prefill
        + arena insert), then one K-step device-resident decode chunk
        over all live slots, launched and consumed. Returns requests
        finished this iteration."""
        before = len(self.scheduler.finished)
        self._admit()
        if self.scheduler.running:
            self._consume_chunk(self._launch_chunk(self._host_state()),
                                device_queue_empty=True)
        self._drop_starved_if_idle()
        return self.scheduler.finished[before:]

    def run(self, prompts: Optional[Sequence] = None,
            **request_kwargs) -> List[Request]:
        """Serve until drained. ``prompts``: token-id sequences (or Request
        objects) submitted up front; per-request kwargs (max_new_tokens,
        eos_token_id, deadline_s) apply to all of them. The loop is
        double-buffered: the next chunk is enqueued from device-resident
        carry state before the previous chunk's token buffer is synced.
        Returns the submitted requests in submission order (rejected ones
        included, flagged by status)."""
        submitted = [self.submit(p, **request_kwargs)
                     for p in (prompts or [])]
        self._serve_pipelined()
        self.metrics.maybe_emit(self.scheduler.queue_depth,
                                self.kv.occupancy, force=True)
        return submitted

    def _abstract_chunk_args(self) -> list:
        """The chunk program's arguments as ``ShapeDtypeStruct``s (params,
        arena, lane state, the variant's extras, rng): what the analyses
        below — and the structural test of the in-place arena — lower
        ``_jit_decode_chunk`` with, touching no device buffer."""
        import jax

        def abst(x):
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype)

        B = self.max_batch
        i32 = jax.ShapeDtypeStruct((B,), np.int32)
        chunk_args = [
            jax.tree.map(abst, self.engine.params),
            jax.tree.map(abst, self.kv.cache),
            i32, i32, jax.ShapeDtypeStruct((B,), bool), i32, i32]
        if self.fused_prefill:
            chunk_args.append(i32)    # pf_rem
            chunk_args.append(jax.ShapeDtypeStruct(
                (self.decode_chunk, B, self.prefill_chunk), np.int32))
        if self.speculative:
            chunk_args.append(
                jax.ShapeDtypeStruct((B, self.max_seq_len), np.int32))
        chunk_args.append(abst(self._rng))
        return chunk_args

    def estimate_chunk_cost(self) -> Optional[Dict[str, Any]]:
        """XLA cost analysis of one decode-chunk program invocation, for
        MFU reporting (telemetry.mfu). Lowers ``_jit_decode_chunk`` with
        abstract ``ShapeDtypeStruct`` args — no device buffers touched —
        but pays ONE extra XLA compile, so benches call this strictly
        AFTER their timed/audited passes (the pinned decode retrace
        budget stays exact; see docs/observability.md).

        XLA counts the chunk's ``lax.scan`` body once, not K times, so
        ``flops_per_chunk`` scales the program count by K — an estimate,
        flagged as such in the result. Returns None when the backend
        reports no costs."""
        from ..telemetry import mfu as _mfu

        ca = _mfu.compiled_cost_analysis(
            self._jit_decode_chunk, *self._abstract_chunk_args())
        if ca is None:
            return None
        B = self.max_batch
        K = self.decode_chunk
        # each spec step scores spec_k + 1 positions in the one target
        # forward, so the per-position flop denominator scales with k+1
        per_step = (self.spec_k + 1) if self.speculative else 1
        flops_per_chunk = ca["flops"] * K
        return {
            "program_flops": ca["flops"],
            "bytes_accessed": ca["bytes_accessed"],
            "scan_length": K,
            "flops_per_chunk": flops_per_chunk,
            "flops_per_token": flops_per_chunk / (B * K * per_step),
            "max_batch": B,
            "scan_body_counted_once": True,
            "peak_flops_per_device": _mfu.peak_flops_per_device(),
        }

    def estimate_hbm(self) -> Optional[Dict[str, Any]]:
        """XLA memory analysis of the engine's own compiled programs
        (telemetry.memory) plus arena accounting and a live-buffer
        census — the ``hbm`` block in ``BENCH_serving.json``.

        Same discipline as :meth:`estimate_chunk_cost`: abstract
        lowering does not grow the audited jit cache (the pinned
        ``decode_chunk_fn == 3`` budget stays exact) but pays one extra
        XLA compile per analyzed program, so benches call this strictly
        AFTER their timed/audited passes. Returns None when the backend
        reports nothing for the decode program."""
        import jax
        from ..telemetry import memory as _mem

        def abst(x):
            return jax.ShapeDtypeStruct(np.shape(x), x.dtype)

        B = self.max_batch
        i32 = jax.ShapeDtypeStruct((B,), np.int32)
        params = jax.tree.map(abst, self.engine.params)
        cache = jax.tree.map(abst, self.kv.cache)
        rng = abst(self._rng)
        decode = _mem.compiled_memory_analysis(
            self._jit_decode_chunk, *self._abstract_chunk_args())
        if decode is None:
            return None
        top = self._buckets[-1]
        prefill = _mem.compiled_memory_analysis(
            self._jit_prefill, params,
            jax.ShapeDtypeStruct((B, top), np.int32), i32, rng)
        return {
            "decode_chunk": decode,
            "prefill_top_bucket": prefill,
            "prefill_bucket_len": top,
            "arena": self.kv.arena_report(),
            "live": _mem.live_array_census(top=8),
        }

    # ---------------------------------------------------------- internals
    # ------------------------------------------------ starved-chip spans
    def _starve(self, name: str) -> None:
        """A host sync has just returned and nothing is dispatched behind
        it: from here to the next dispatch the chip has no work because
        the host has not handed it any. ``name`` says which sync."""
        if self._starved is None:
            span = telemetry.span(name)
            if span is not telemetry.NOOP_SPAN:
                self._starved = span.__enter__()

    def _device_fed(self) -> None:
        """Called where the next program is about to be dispatched."""
        if self._starved is not None:
            self._starved.__exit__(None, None, None)
            self._timeline.starved(self._starved.t1 - self._starved.t0)
            self._starved = None

    def _drop_starved_if_idle(self) -> None:
        """No request queued, running or in flight: what follows is a
        server waiting for traffic, not a chip waiting for its host."""
        if (self._starved is not None and self._pending is None
                and not self.scheduler.has_work()):
            self._starved.drop()
            self._starved = None
            self._timeline.reset()

    # ------------------------------------------------- the device timeline
    def _tl(self) -> Optional[DeviceTimeline]:
        """The device timeline while telemetry is on (and the engine's
        programs share one device queue), else None. Off means off: a
        caller that gets None asks no array ``is_ready()``, waits on
        nothing it did not wait on before and calls nothing of the
        timeline's."""
        if not telemetry.get_runtime().enabled:
            return None
        return self._timeline.on()

    def _prefill_wait_stamped(self, tl: DeviceTimeline,
                              ahead: Optional[_InflightChunk], program,
                              toks) -> np.ndarray:
        """``serve/prefill_wait`` with the timeline on: its two waits told
        apart. The device runs the chunk ``pump()`` launched ahead of this
        admission first, then the prefill, and ``np.asarray(toks)`` alone
        would wait through both; blocking on the chunk's tokens first costs
        nothing and makes its end a stamp of its own."""
        import jax
        if ahead is not None and tl.is_open(ahead.program):
            exact = not ahead.tokens.is_ready()
            with telemetry.span("serve/prefill_wait_chunk_ahead") as wait:
                jax.block_until_ready(ahead.tokens)
            tl.stamp(ahead.program, wait, exact)
        exact = not toks.is_ready()
        with telemetry.span("serve/prefill_wait_own") as wait:
            toks_host = np.asarray(toks)
        tl.stamp(program, wait, exact)
        return toks_host

    def _count_routing(self, kind: str, routing) -> None:
        """``routing``: [] or [the counters a program summed on the device]
        (moe/grouped.py::routing_counters). Its tokens were fetched just
        before, so the program has ended and this waits for nothing."""
        import jax
        counted = dict(jax.device_get(routing[0] if routing else {}))
        # what the lanes' state held and the steps read of it (the model's
        # step_counters, under the names it gave)
        for name, value in counted.pop("state", {}).items():
            self.metrics.on_state_rows(name, float(value))
            telemetry.count(f"serve/{name}", float(value))
        for name, value in counted.items():
            self.metrics.on_routing(kind, name, float(value))
            telemetry.count(f"serve/moe_{kind}_{name}", float(value))

    def _next_rng(self):
        import jax
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _bucket_for(self, prompt_len: int) -> int:
        for b in self._buckets:
            if prompt_len <= b:
                return b
        return self._buckets[-1]    # unreachable: submit() length guard

    def _admit(self, ahead: Optional[_InflightChunk] = None) -> None:
        """Admit every currently-runnable request (``ahead``: the chunk
        ``pump()`` has in flight in front of whatever is dispatched here,
        for the device timeline). Dense: group by
        prefill bucket, ONE batched prefill per group, one fused arena
        insert per group. Paged: prefix-cache HITS skip prefill entirely
        (a block-table fork + the cached first token); MISSES take the
        dense prefill path, block-scattered on insert, then publish
        their prompt blocks to the prefix cache. Hit forks dispatch
        BEFORE miss inserts — dispatch order is the device write order,
        so a fork's COW source is copied before anything could recycle
        its block."""
        if self.kv_tier is not None:
            self._install_promotions()
        with telemetry.span("serve/admit"):
            if self.fused_prefill:
                # chunk-budget fill policy: running lanes drain the per-step
                # token budget (a prompt chunk for prefilling lanes, one
                # decode token — k+1 speculative — for the rest); admission
                # fills what's left. The scheduler still admits one request
                # into an otherwise-idle engine so the budget can't wedge.
                admitted = self.scheduler.admit(
                    token_budget=max(0, self.chunk_token_budget
                                     - self._budget_drain()),
                    lane_cost=self._lane_cost)
            else:
                admitted = self.scheduler.admit()
            if not admitted:
                return
            if self.fused_prefill:
                self._fused_admit(admitted, ahead)
                if self.paged:
                    self._gauge_block_pool()
                return
            if not self.paged:
                self._prefill_admit(admitted, ahead=ahead)
                return
            hits: List[Tuple[Request, Any]] = []
            misses: List[Tuple[Request, Any]] = []
            for req in admitted:
                plan = self.kv.take_plan(req.slot)
                (hits if plan.hit else misses).append((req, plan))
            for req, plan in hits:
                self._admit_prefix_hit(req, plan)
            if misses:
                self._prefill_admit([r for r, _ in misses],
                                    plans={r.slot: p for r, p in misses},
                                    ahead=ahead)
            self._gauge_block_pool()

    def _admit_prefix_hit(self, req: Request, plan) -> None:
        """A cached prompt: share its full blocks, COW its tail, replay
        the stored first token. No prefill program runs — the whole
        admission is one small fork dispatch."""
        with telemetry.span("serve/prefix_fork", slot=req.slot,
                            n_shared=plan.n_shared):
            self.kv.apply_fork(plan)
        telemetry.count("serve/prefix_cache_hit")
        self.metrics.on_prefix(True)
        if plan.cow is not None:
            telemetry.instant("serve/cow_fork", slot=req.slot)
            self.metrics.on_cow()
        first = int(plan.first_token)
        self._last_token[req.slot] = first
        self.metrics.on_tokens(1)
        self.scheduler.record_first_token(req, first)
        self._record_admit_patch(req)

    def _budget_drain(self) -> int:
        """Tokens the RUNNING lanes consume per fused scan step: one
        prompt chunk (<= C) while a lane is prefilling, one decode token
        (k+1 speculative) after."""
        C = self.prefill_chunk
        base = (1 + self.spec_k) if self.speculative else 1
        drain = 0
        for slot, req in self.scheduler.running.items():
            done = self._pf_consumed.get(slot, req.prompt_len)
            if done < req.prompt_len:
                drain += min(C, req.prompt_len - done)
            else:
                drain += base
        return drain

    def _lane_cost(self, req: Request) -> int:
        """Per-step budget cost of ADMITTING ``req`` now: its first
        prompt chunk for an inline lane; one decode token when the
        prompt takes the out-of-scan sp prefill leg instead (it joins
        the scan already in decode mode). Prefix-cache hits are priced
        as inline lanes (the hit is only known after the lease) —
        conservatively high, never starving."""
        if (self.sp_prefill_threshold is not None
                and req.prompt_len >= self.sp_prefill_threshold):
            return (1 + self.spec_k) if self.speculative else 1
        return min(self.prefill_chunk, req.prompt_len)

    def _fused_admit(self, admitted: List[Request],
                     ahead: Optional[_InflightChunk] = None) -> None:
        """Fused-mode admission: no bucketed prefill program. Inline
        lanes enter the scan in prefill mode (the scan body appends
        their KV chunk by chunk); paged MISSES only install their block
        table now (the prefix commit waits for token #1); prefix HITS
        short-circuit every prompt chunk exactly like the bucketed path
        (fork + replayed first token -> straight to decode mode); and
        prompts at/above sp_prefill_threshold run the one
        sequence-parallel bucketed prefill before joining as decode
        lanes."""
        sp_reqs: List[Request] = []
        sp_plans: Dict[int, Any] = {}
        for req in admitted:
            plan = self.kv.take_plan(req.slot) if self.paged else None
            if plan is not None and plan.hit:
                self._clear_pf_slot(req.slot)
                self._admit_prefix_hit(req, plan)
                continue
            if (self.sp_prefill_threshold is not None
                    and req.prompt_len >= self.sp_prefill_threshold):
                self._clear_pf_slot(req.slot)
                sp_reqs.append(req)
                if plan is not None:
                    sp_plans[req.slot] = plan
                continue
            if plan is not None:
                # wire up the lane's block table without a KV insert —
                # the scan's chunk writes scatter through it from pos 0
                self.kv.install_table(req.slot)
                self._pf_plans[req.slot] = plan
            self._pf_consumed[req.slot] = 0
            self._pf_launched[req.slot] = 0
            self._pf_first_pending.add(req.slot)
            self._record_fused_admit_patch(req)
            telemetry.instant("serve/prefill_inline_admit",
                              slot=req.slot, prompt_len=req.prompt_len)
            if self.flight is not None:
                self.flight.record("prefill_inline_admit", uid=req.uid,
                                   slot=req.slot,
                                   prompt_len=req.prompt_len)
        if sp_reqs:
            self._prefill_admit(sp_reqs, plans=sp_plans or None,
                                ahead=ahead)

    def _record_fused_admit_patch(self, req: Request) -> None:
        """Lane state for a freshly admitted INLINE prefill lane: pos 0,
        the full prompt outstanding (pf = prompt_len), nothing emitted.
        The carried token is a don't-care until the completing chunk
        samples token #1."""
        slot = req.slot
        rem = min(req.max_new_tokens,
                  self.kv.allocator.remaining(slot))
        eos = -1 if req.eos_token_id is None else int(req.eos_token_id)
        patch = (0, 0, rem, eos, req.prompt_len)
        if self.speculative:
            patch = patch + (self._history_row(req),)
        self._admit_patches[slot] = patch
        self._deact_slots.discard(slot)

    def _install_promotions(self) -> None:
        """Drain completed async promotions (KVTierManager's worker ran
        the NVMe read / decode off-thread) and scatter them back into
        the HBM pool — the ONLY place tier payloads touch the device, so
        the pool stays engine-thread-owned. Everything that drained
        ready in this pass installs through ONE batched scatter
        (``readmit_prefix_many`` — eager-op dispatch dominates, so k
        promotions cost one entry's dispatch). A promotion the pool
        cannot take right now goes back to the tier and retries at a
        later, less-pressured pump; nothing blocks the chunk launch."""
        ready = self.kv_tier.drain_ready()
        if not ready:
            return
        with telemetry.span("serve/tier_promote_install",
                            n=len(ready)):
            installed, rejected = self.kv.readmit_prefix_many(ready)
        for _ in installed:
            telemetry.count("serve/tier_promote")
        for key, prompt_len, first_token, leaves in rejected:
            self.kv_tier.abandon_ready(
                key, (prompt_len, first_token, leaves))

    def _gauge_block_pool(self) -> None:
        blocks = self.kv.allocator.blocks
        telemetry.gauge("serve/block_pool_used", float(blocks.n_used))
        telemetry.gauge("serve/block_pool_free", float(blocks.n_free))
        tier = self.kv_tier
        if tier is not None:
            rep = tier.report()
            telemetry.gauge("serve/tier_dram_bytes",
                            float(rep["dram_bytes"]))
            telemetry.gauge("serve/tier_nvme_bytes",
                            float(rep["nvme_bytes"]))
            telemetry.gauge("serve/tier_dram_entries",
                            float(rep["dram_entries"]))
            telemetry.gauge("serve/tier_nvme_entries",
                            float(rep["nvme_entries"]))
            telemetry.gauge("serve/tier_demotions",
                            float(rep["demotions_dram"]
                                  + rep["demotions_nvme"]))
            telemetry.gauge("serve/tier_promotions",
                            float(rep["promotions_dram"]
                                  + rep["promotions_nvme"]))
            telemetry.gauge("serve/tier_promote_wait_p50_s",
                            float(rep["promote_wait_p50_s"]))

    def _prefill_admit(self, admitted: List[Request],
                       plans: Optional[Dict[int, Any]] = None,
                       ahead: Optional[_InflightChunk] = None) -> None:
        """Bucketed batched prefill + fused cache insert for ``admitted``
        (the dense path verbatim; paged misses ride it too, with the
        block-scatter insert and a prefix-cache commit per request)."""
        import jax.numpy as jnp
        tl = self._tl()
        groups: Dict[Tuple[int, bool], List[Request]] = {}
        for req in admitted:
            use_sp = (self._jit_prefill_sp is not None
                      and self.sp_prefill_threshold is not None
                      and req.prompt_len >= self.sp_prefill_threshold)
            groups.setdefault((self._bucket_for(req.prompt_len), use_sp),
                              []).append(req)
        for (bucket, use_sp), reqs in sorted(groups.items()):
            n = len(reqs)
            prefill_fn = (self._jit_prefill_sp if use_sp
                          else self._jit_prefill)
            shape_key = (n, bucket) if not use_sp else (n, bucket, "sp")
            if shape_key not in self._prefill_shapes:
                # first sighting of this (batch, bucket) shape = the call
                # below compiles a fresh prefill program — mark it on the
                # timeline so a long prefill span is explainable
                telemetry.instant("serve/prefill_compile", n=n,
                                  bucket=bucket, sp=use_sp)
            self._prefill_shapes.add(shape_key)
            # serve/prefill is HOST time from building the ids to the
            # first tokens on the host, not the prefill's device time:
            # its dispatch queues behind whatever is already dispatched
            # (pump() launches the next decode chunk before it admits),
            # and serve/prefill_wait waits for all of that. Its two
            # children tell the waits apart (serve/prefill_wait_chunk_ahead,
            # serve/prefill_wait_own), and the device's own time for this
            # call is serve/device_prefill, which the timeline records from
            # the ends of the two (a group's insert_batch runs after its
            # sync and rides with the next group's prefill or the next chunk)
            with telemetry.span("serve/prefill", n=n, bucket=bucket,
                                sp=use_sp,
                                uids=str([r.uid for r in reqs])):
                with telemetry.span("serve/prefill_dispatch"):
                    ids = np.zeros((n, bucket), np.int32)
                    lens = np.empty(n, np.int32)
                    for i, r in enumerate(reqs):
                        ids[i, :r.prompt_len] = r.prompt
                        lens[i] = r.prompt_len
                    self._device_fed()
                    toks, cache, *routing = prefill_fn(
                        self._prefill_params, jnp.asarray(ids),
                        jnp.asarray(lens), self._next_rng())
                    program = None if tl is None else tl.dispatched(
                        "prefill", n=n, bucket=bucket, sp=use_sp,
                        prompt_tokens=int(lens.sum()),
                        padded_tokens=n * bucket)
                    if self._handoff_sharding is not None:
                        cache = self._handoff(cache, reqs, bucket)
                    self.kv.insert_batch(cache, [r.slot for r in reqs],
                                         lens)
                    if tl is not None:
                        tl.dispatched("insert_batch")
                with telemetry.span("serve/prefill_wait"):
                    if tl is None:
                        toks_host = np.asarray(toks)
                    else:
                        toks_host = self._prefill_wait_stamped(
                            tl, ahead, program, toks)
                    self._count_routing("prefill", routing)
                # everything dispatched before that sync has run
                self._starve("serve/starved_after_prefill")
            telemetry.count("serve/prefill_tokens", float(lens.sum()))
            if use_sp:
                # long prompts routed over the sp mesh axis (Ulysses)
                telemetry.count("serve/sp_prefill_tokens",
                                float(lens.sum()))
            self.metrics.on_prefill(n, bucket, int(lens.sum()),
                                    len(self._prefill_shapes))
            self.metrics.on_tokens(n)
            if self.flight is not None:
                self.flight.record("prefill", n=n, bucket=bucket,
                                   uids=[r.uid for r in reqs])
            for i, r in enumerate(reqs):
                first = int(toks_host[i])
                self._last_token[r.slot] = first
                if plans is not None:
                    # publish the prompt blocks BEFORE the request can
                    # retire (retiring frees its slot refs; the cache
                    # holds its own) — may dispatch the tail COW copy
                    cow = self.kv.commit_prefix(plans[r.slot], first)
                    if self.kv.prefix_enabled:
                        telemetry.count("serve/prefix_cache_miss")
                        self.metrics.on_prefix(False)
                    if cow is not None:
                        telemetry.instant("serve/cow_fork", slot=r.slot)
                        self.metrics.on_cow()
                # may retire the request immediately (max_new_tokens == 1
                # or an instant EOS) — its slot frees before any decode
                self.scheduler.record_first_token(r, first)
                self._record_admit_patch(r)

    def _handoff(self, cache, reqs: List[Request], bucket: int):
        """Disaggregation: the finished prompt KV leaves the prefill
        slice here — a device-to-device transfer of the batch's cache
        rows onto the decode slice, where the insert scatters them
        through each request's table row / slot lane."""
        import jax
        n = len(reqs)
        nbytes = sum(int(getattr(leaf, "nbytes", 0))
                     for leaf in jax.tree.leaves(cache))
        # the handoff span carries the requests' journey ids so the
        # transfer shows up under each trace in the merged fleet export
        with telemetry.span(
                "serve/disagg_handoff", n=n, bucket=bucket,
                uids=str([r.uid for r in reqs]),
                trace_ids=str([r.trace_id for r in reqs])):
            cache = jax.device_put(cache, self._handoff_sharding)
        telemetry.count("serve/disagg_handoff_bytes", float(nbytes))
        telemetry.count("serve/disagg_handoffs", float(n))
        if self.flight is not None:
            self.flight.record("disagg_handoff", n=n, bytes=int(nbytes),
                               uids=[r.uid for r in reqs])
        return cache

    def _record_admit_patch(self, req: Request) -> None:
        slot = req.slot
        if self.fused_prefill:
            # this lane was admitted through a NON-inline path (prefix
            # hit / sp prefill): it joins the scan in pure decode mode —
            # stale inline mirrors from the slot's previous occupant
            # must not shadow it
            self._clear_pf_slot(slot)
        if req.status == "running":
            rem = min(req.max_new_tokens - len(req.tokens),
                      self.kv.allocator.remaining(slot))
            eos = -1 if req.eos_token_id is None else int(req.eos_token_id)
            patch = (int(req.tokens[-1]), req.prompt_len, rem, eos)
            if self.fused_prefill:
                patch = patch + (0,)        # pf_rem: already prefilled
            if self.speculative:
                # the drafter mines the lane's full history: patch in the
                # prompt + first token so n-gram lookup sees the prompt
                patch = patch + (self._history_row(req),)
            self._admit_patches[slot] = patch
            self._deact_slots.discard(slot)
        else:
            # instantly retired: the slot must stay dead on device
            self._admit_patches.pop(slot, None)
            self._deact_slots.add(slot)

    # ------------------------------------------------------ decode chunks
    def _host_state(self) -> Tuple:
        """Full chunk-input state vectors rebuilt from scheduler/allocator
        mirrors (authoritative — any pending patches are subsumed)."""
        B = self.max_batch
        tokens = np.zeros(B, np.int32)
        positions = np.zeros(B, np.int32)
        active = np.zeros(B, bool)
        remaining = np.zeros(B, np.int32)
        eos = np.full(B, -1, np.int32)
        hist = (np.zeros((B, self.max_seq_len), np.int32)
                if self.speculative else None)
        pf = np.zeros(B, np.int32) if self.fused_prefill else None
        for slot, req in self.scheduler.running.items():
            done = (self._pf_consumed.get(slot, req.prompt_len)
                    if self.fused_prefill else req.prompt_len)
            if pf is not None and done < req.prompt_len:
                # mid-prompt lane: resumes in prefill mode; tokens come
                # from the prompt buffer, not the carried last token
                tokens[slot] = 0
                positions[slot] = done
                pf[slot] = req.prompt_len - done
                remaining[slot] = min(
                    req.max_new_tokens - len(req.tokens),
                    self.kv.allocator.remaining(slot))
            else:
                tokens[slot] = self._last_token[slot]
                positions[slot] = self.kv.fill[slot]
                remaining[slot] = min(
                    req.max_new_tokens - len(req.tokens),
                    self.kv.allocator.remaining(slot))
            active[slot] = True
            if req.eos_token_id is not None:
                eos[slot] = int(req.eos_token_id)
            if hist is not None:
                hist[slot] = self._history_row(req)
        self._deact_slots.clear()
        self._admit_patches.clear()
        if self.fused_prefill:
            # a host rebuild collapses the launch horizon back onto the
            # consumed cursor (any launched-but-unconsumed chunk is gone
            # with the discarded in-flight chunk)
            self._pf_launched = dict(self._pf_consumed)
        out = (tokens, positions, active, remaining, eos)
        if pf is not None:
            out = out + (pf,)
        if hist is not None:
            out = out + (hist,)
        return out

    def _history_row(self, req: Request) -> np.ndarray:
        """One lane's token history (prompt + emitted) padded to
        [max_seq_len] — the drafter's lookup corpus. Invariant:
        ``row[positions[slot]] == last_token[slot]``."""
        row = np.zeros(self.max_seq_len, np.int32)
        seq = list(np.asarray(req.prompt).tolist()) + \
            [int(t) for t in req.tokens]
        n = min(len(seq), self.max_seq_len)
        row[:n] = seq[:n]
        return row

    def _device_state(self, chunk: _InflightChunk) -> Tuple:
        """Chunk-input state propagated on DEVICE from the previous
        chunk's carry (no host sync), with the host's corrections patched
        in: lanes the scheduler finished for its own reasons (deadline)
        go inactive; freshly admitted requests get their full lane
        state."""
        tok, pos, act, rem, eos = chunk.state[:5]
        i = 5
        pf = None
        if self.fused_prefill:
            pf = chunk.state[i]
            i += 1
        hist = chunk.state[i] if self.speculative else None
        if self._deact_slots or self._admit_patches:
            # ONE program of one shape whatever the number of lanes patched:
            # masks and values over all max_batch lanes, built on the host.
            # (A scatter per vector at the patched lanes' indices was one
            # small program per COUNT of lanes, max_batch of each to warm,
            # and six dispatches a patch.) Host time while the previous
            # chunk may already have ended on the device.
            with telemetry.span("serve/lane_patch",
                                n_deact=len(self._deact_slots),
                                n_admit=len(self._admit_patches)):
                deact = np.zeros(self.max_batch, bool)
                admit = np.zeros(self.max_batch, bool)
                vals = np.zeros((4, self.max_batch), np.int32)
                if self._deact_slots:
                    telemetry.instant("serve/deact_patch",
                                      n=len(self._deact_slots))
                    if self.flight is not None:
                        self.flight.record("deact_patch",
                                           slots=sorted(self._deact_slots))
                    deact[sorted(self._deact_slots)] = True
                if self._admit_patches:
                    telemetry.instant("serve/admit_patch",
                                      n=len(self._admit_patches))
                    if self.flight is not None:
                        self.flight.record("admit_patch",
                                           slots=sorted(self._admit_patches))
                    slots = sorted(self._admit_patches)
                    patches = [self._admit_patches[s] for s in slots]
                    admit[slots] = True
                    vals[:, slots] = np.array([v[:4] for v in patches]).T
                tok, pos, act, rem, eos = self._jit_lane_patch(
                    tok, pos, act, rem, eos, deact, admit, vals)
                tl = self._tl()
                if tl is not None:
                    tl.dispatched("lane_patch")
                if self._admit_patches:
                    # the fused and speculative programs' extra state: the
                    # admitted rows alone (a history row is max_seq_len wide)
                    idx = np.array(slots, np.int32)
                    vi = 4
                    if pf is not None:
                        pf = pf.at[idx].set(
                            np.array([v[vi] for v in patches], np.int32))
                        vi += 1
                    if hist is not None:
                        hist = hist.at[idx].set(
                            np.stack([v[vi] for v in patches]))
        self._deact_slots.clear()
        self._admit_patches.clear()
        out = (tok, pos, act, rem, eos)
        if pf is not None:
            out = out + (pf,)
        if hist is not None:
            out = out + (hist,)
        return out

    def _launch_chunk(self, state: Tuple) -> _InflightChunk:
        """Enqueue one K-step decode chunk (returns immediately — JAX
        async dispatch; nothing here blocks on device results)."""
        import jax.numpy as jnp
        self._device_fed()
        # dispatch-only span BY DESIGN (no sync=): the chunk is meant to
        # run asynchronously; the honest device wait is measured at
        # consume time as serve/chunk_host_wait
        routing = []    # the plain chunk program of an expert model only
        with telemetry.span("serve/chunk_launch", k=self.decode_chunk):
            if self.fused_prefill:
                state = tuple(jnp.asarray(a) for a in state)
                tokens, positions, active, remaining, eos, pf = (
                    state[0], state[1], state[2], state[3], state[4],
                    state[5])
                pbuf = jnp.asarray(self._build_prompt_buf())
                if self.speculative:
                    hist = state[6]
                    (toks, valid, new_cache, tok_f, pos_f, act_f, rem_f,
                     pf_f, hist_f) = self._jit_decode_chunk(
                        self._decode_params, self.kv.cache, tokens,
                        positions, active, eos, remaining, pf, pbuf,
                        hist, self._next_rng())
                    carry = (tok_f, pos_f, act_f, rem_f, eos, pf_f,
                             hist_f)
                else:
                    (toks, valid, new_cache, tok_f, pos_f, act_f, rem_f,
                     pf_f) = self._jit_decode_chunk(
                        self._decode_params, self.kv.cache, tokens,
                        positions, active, eos, remaining, pf, pbuf,
                        self._next_rng())
                    carry = (tok_f, pos_f, act_f, rem_f, eos, pf_f)
            elif self.speculative:
                (tokens, positions, active, remaining, eos, hist) = (
                    jnp.asarray(a) for a in state)
                (toks, valid, new_cache, tok_f, pos_f, act_f, rem_f,
                 hist_f) = self._jit_decode_chunk(
                    self._decode_params, self.kv.cache, tokens, positions,
                    active, eos, remaining, hist, self._next_rng())
                carry = (tok_f, pos_f, act_f, rem_f, eos, hist_f)
            else:
                tokens, positions, active, remaining, eos = (
                    jnp.asarray(a) for a in state)
                (toks, valid, new_cache, tok_f, pos_f, act_f, rem_f,
                 *routing) = self._jit_decode_chunk(
                        self._decode_params, self.kv.cache, tokens,
                        positions, active, eos, remaining,
                        self._next_rng())
                carry = (tok_f, pos_f, act_f, rem_f, eos)
            self.kv.update(new_cache)
        inflight = _InflightChunk(
            slot_uids={s: r.uid for s, r in self.scheduler.running.items()},
            tokens=toks, valid=valid, state=carry, routing=routing)
        tl = self._tl()
        if tl is not None:
            inflight.program = tl.dispatched(
                "decode_chunk", k=self.decode_chunk,
                lanes=len(inflight.slot_uids))
        if self.flight is not None:
            self.flight.record("chunk_launch", k=self.decode_chunk,
                               slot_uids=dict(inflight.slot_uids))
        return inflight

    def _count_kv_read(self, per_slot: Dict[int, List[int]]) -> None:
        """Count the blocks of one layer's rows that the chunk's steps read
        (``serve/kv_blocks_read``) beside the blocks its K steps would read
        of the whole arena (``serve/kv_blocks_arena``), from what the host
        holds before the chunk's tokens advance the fills: a lane that
        entered the chunk with ``fill`` positions written and was live for
        n steps read, in step j = 1..n, what the model says a lane at
        position ``fill + j - 1`` reads (``GPT.blocks_read``: ``ceil((fill
        + j) / block)`` of a row a position; a window's and its summaries'
        blocks of a block that keeps both). Where the step reads every row
        (the einsum, the paged pool, the speculative and fused widths) the
        two are equal."""
        block = self._kv_count_block
        # (the arena's lanes are counted in the model's rows, kv_cache.py)
        arena = self.decode_chunk * self.max_batch * \
            -(-getattr(self.kv, "rows_per_slot", self.max_seq_len) // block)
        read = arena
        if self._kv_read_block is not None:
            # every lane's positions in the chunk, asked of the model at once
            # and summed once (a block kind's count may be a mean over its
            # layers, a fraction a lane)
            fill = self.kv.allocator.fill
            positions = np.concatenate([np.zeros(0, np.int64)] + [
                int(fill[slot]) + np.arange(len(seq))
                for slot, seq in per_slot.items()])
            read = float(np.sum(self.module.blocks_read(positions, block)))
        telemetry.count("serve/kv_blocks_read", float(read))
        telemetry.count("serve/kv_blocks_arena", float(arena))
        self.metrics.on_kv_read(read, arena)

    def _consume_chunk(self, chunk: _InflightChunk, *,
                       device_queue_empty: bool) -> List[Request]:
        """Block on the chunk's token buffer (the ONE host sync per K
        steps) and feed it through the scheduler. ``device_queue_empty``:
        no chunk was launched ahead of this sync, so when it returns the
        chip has nothing to run."""
        # a chunk the timeline has already closed (stamped from inside a
        # prefill's wait a pump ago) is ready by now and stamps nothing
        tl = self._tl()
        stamps = tl is not None and tl.is_open(chunk.program)
        exact = stamps and not chunk.tokens.is_ready()
        with telemetry.span("serve/chunk_host_wait") as wait:
            toks = np.asarray(chunk.tokens)
            valid = np.asarray(chunk.valid)
            self._count_routing("decode", chunk.routing)
        if stamps:
            tl.stamp(chunk.program, wait, exact)
        if device_queue_empty:
            self._starve("serve/starved_after_chunk")
        inline_tokens = 0
        n_first = 0
        pf_steps = None
        with telemetry.span("serve/chunk_retire"):
            if self.fused_prefill:
                # deterministic host replay of the chunk's prefill-mode
                # evolution: advances the consumed cursors and yields the
                # per-lane pf-step mask for accounting
                consumed, pf_steps = self._sim_chunk_prefill(chunk)
                for slot, done in consumed.items():
                    prev = self._pf_consumed.get(slot, done)
                    inline_tokens += max(done - prev, 0)
                    self._pf_consumed[slot] = done
            fin_before = len(self.scheduler.finished)
            per_slot: Dict[int, List[int]] = {}
            for slot, uid in chunk.slot_uids.items():
                req = self.scheduler.running.get(slot)
                if req is None or req.uid != uid:
                    continue        # slot retired/re-leased since launch
                seq = [int(t) for t, v in
                       zip(toks[slot], valid[slot]) if v]
                if (self.fused_prefill and seq
                        and slot in self._pf_first_pending):
                    # the lane completed its prompt inside this chunk:
                    # token #1 routes through record_first_token (TTFT
                    # stamp, NO allocator advance — its KV row is written
                    # by the next decode step), and a deferred paged
                    # admit plan publishes the prompt blocks now
                    self._pf_first_pending.discard(slot)
                    first = seq.pop(0)
                    n_first += 1
                    plan = self._pf_plans.pop(slot, None)
                    if plan is not None:
                        cow = self.kv.commit_prefix(plan, first)
                        if self.kv.prefix_enabled:
                            telemetry.count("serve/prefix_cache_miss")
                            self.metrics.on_prefix(False)
                        if cow is not None:
                            telemetry.instant("serve/cow_fork", slot=slot)
                            self.metrics.on_cow()
                    self._last_token[slot] = first
                    self.scheduler.record_first_token(req, first)
                    if req.status != "running":
                        seq = []    # retired on token #1: drop the rest
                if seq:
                    per_slot[slot] = seq
                    self._last_token[slot] = seq[-1]
            self._count_kv_read(per_slot)
            self.scheduler.step_tokens_chunk(per_slot)
            finished = self.scheduler.finished[fin_before:]
        n_tokens = sum(len(v) for v in per_slot.values())
        if self.flight is not None:
            self.flight.record("chunk_retire", n_tokens=n_tokens,
                               finished=[r.uid for r in finished],
                               queue_depth=self.scheduler.queue_depth,
                               occupancy=float(self.kv.occupancy))
        telemetry.count("serve/decode_tokens", float(n_tokens))
        if inline_tokens:
            telemetry.count("serve/prefill_inline_tokens",
                            float(inline_tokens))
            self.inline_prefill_tokens += inline_tokens
        if n_first:
            self.metrics.on_tokens(n_first)
        if self.speculative:
            # acceptance accounting from the validity mask itself: a
            # step is live iff its base position (j == 0, the correction
            # /bonus slot always valid on live lanes) is valid; accepted
            # drafts = valid tokens beyond that guaranteed one. In fused
            # mode a prefill-mode step also has column 0 valid on its
            # completing iteration (token #1) but verified no drafts —
            # the host-replayed pf mask excludes those steps
            kp1 = self.spec_k + 1
            W = max(self.prefill_chunk, kp1) if self.fused_prefill \
                else kp1
            v3 = valid.reshape(self.max_batch, -1, W)
            live_steps = v3[:, :, 0]
            if pf_steps is not None:
                live_steps = live_steps & ~pf_steps
            proposed = int(live_steps.sum()) * self.spec_k
            accepted = int(np.maximum(
                np.where(live_steps, v3.sum(axis=2), 0) - live_steps,
                0).sum())
            if proposed:
                telemetry.count("serve/spec_proposed", float(proposed))
                telemetry.count("serve/spec_accepted", float(accepted))
            self.metrics.on_spec(proposed, accepted)
        telemetry.gauge("serve/queue_depth",
                        float(self.scheduler.queue_depth))
        telemetry.gauge("serve/occupancy", float(self.kv.occupancy))
        if self.paged:
            self._gauge_block_pool()
            telemetry.gauge("serve/arena_headroom_bytes",
                            float(self.kv.allocator.blocks.n_free
                                  * self._bytes_per_block))
        else:
            telemetry.gauge("serve/arena_headroom_bytes",
                            float(self.kv.allocator.n_free
                                  * self._arena_bytes_per_slot))
        self.metrics.on_tokens(n_tokens)
        self.metrics.on_decode_step()
        self.metrics.on_finished(finished)
        for req in finished:
            if req.slot is not None:
                self._deact_slots.add(req.slot)
                if self.fused_prefill:
                    self._clear_pf_slot(req.slot)
        self.metrics.maybe_emit(self.scheduler.queue_depth,
                                self.kv.occupancy)
        return finished

    def _build_prompt_buf(self) -> np.ndarray:
        """Per-scan-step prompt chunks [K, B, C] for lanes still in
        prefill mode, advancing the LAUNCH cursor (it runs one chunk
        horizon ahead of the consumed cursor under double-buffering).
        Prefill-mode evolution on device is deterministic — a lane mid-
        prompt cannot EOS or exhaust its budget — so this host mirror
        stays exact without a device sync."""
        K, B, C = self.decode_chunk, self.max_batch, self.prefill_chunk
        buf = np.zeros((K, B, C), np.int32)
        for slot, req in self.scheduler.running.items():
            done = self._pf_launched.get(slot)
            if done is None:
                continue
            prompt = np.asarray(req.prompt, np.int32)
            L = req.prompt_len
            for k in range(K):
                if done >= L:
                    break
                n = min(C, L - done)
                buf[k, slot, :n] = prompt[done:done + n]
                done += n
            self._pf_launched[slot] = done
        return buf

    def _sim_chunk_prefill(
            self, chunk: _InflightChunk
    ) -> Tuple[Dict[int, int], np.ndarray]:
        """Deterministic host replay of the consumed chunk's prefill-
        mode evolution (mirrors the device mask exactly: each step a
        mid-prompt lane consumes ``min(pf, C)`` tokens). Returns the
        advanced consumed cursors and the [B, K] mask of steps each
        lane spent in prefill mode (its completing step — the one that
        emits token #1 — included)."""
        K, C = self.decode_chunk, self.prefill_chunk
        pf_steps = np.zeros((self.max_batch, K), bool)
        consumed: Dict[int, int] = {}
        for slot, uid in chunk.slot_uids.items():
            req = self.scheduler.running.get(slot)
            if req is None or req.uid != uid:
                continue
            done = self._pf_consumed.get(slot)
            if done is None or done >= req.prompt_len:
                continue
            L = req.prompt_len
            for k in range(K):
                if done >= L:
                    break
                pf_steps[slot, k] = True
                done += min(C, L - done)
            consumed[slot] = done
        return consumed, pf_steps

    def _may_outlive_chunk(self) -> bool:
        """Could any lane still be live AFTER the in-flight chunk? (Host
        mirrors are pre-chunk here, so a lane survives it only if its
        remaining budget exceeds K.) Gates the speculative next-chunk
        launch so the drain tail doesn't pay a fully-dead chunk."""
        K = self.decode_chunk
        for slot, req in self.scheduler.running.items():
            if (self.fused_prefill
                    and self._pf_consumed.get(slot, req.prompt_len)
                    < req.prompt_len):
                return True      # still mid-prompt: more chunks coming
            rem = min(req.max_new_tokens - len(req.tokens),
                      self.kv.allocator.remaining(slot))
            if rem > K:
                return True
        return False

    def _serve_pipelined(self) -> None:
        """The async host loop: always keep one chunk in flight, and
        enqueue its successor (from device-carried state) BEFORE blocking
        on its token buffer — host-side scheduling/bookkeeping overlaps
        device compute. Host-only events (deadline expiry, cancellation,
        admissions) take effect one chunk late; device-detected stops
        (EOS, budget) take effect immediately via the carried active
        mask. One ``pump()`` call per iteration — the same loop an
        external driver (the serving frontend) runs incrementally."""
        while self.scheduler.has_work() or self._pending is not None:
            self.pump()

    def close(self) -> None:
        """Release host-side serving resources: the KV tier's promotion
        worker and its NVMe spill files. Idempotent; engines without a
        tier have nothing to release."""
        if self._starved is not None:
            self._starved.drop()
            self._starved = None
        if self.kv_tier is not None:
            self.kv_tier.close()
