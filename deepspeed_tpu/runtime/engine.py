"""The core training engine.

Reference analogue: ``DeepSpeedEngine`` (``deepspeed/runtime/engine.py:175``)
with ``forward``:1552 / ``backward``:1665 / ``step``:1867 /
``save_checkpoint``:2768 / ``load_checkpoint``:2438.

TPU-native redesign:

  * The reference engine orchestrates eager CUDA work (hooks, side streams,
    bucketed allreduce, loss-scale host syncs). Here the whole
    forward+backward+accumulate+update of one global batch is ONE jitted
    program — ``lax.scan`` over the gradient-accumulation microbatches
    followed by the guarded optimizer update — so XLA fuses, overlaps
    collectives with compute, and never syncs to host mid-step.
  * ZeRO stages are sharding rules (runtime/sharding.py), not code paths:
    stage 1 shards master+optimizer state over ``dp``; stage 2 additionally
    constrains grads to the sharded spec (psum -> reduce_scatter); stage 3
    shards params. The reference's bucketing/overlap machinery
    (stage_1_and_2.py:783-1014) is XLA's latency-hiding scheduler here.
  * fp16 dynamic loss scaling runs fully in-graph (fp16/loss_scaler.py);
    an overflow step selects the old state with ``jnp.where`` instead of
    raising to host (engine.py:1798 overflow-skip accounting).
  * The 3-call API (forward / backward / step) is preserved. On TPU the
    gradient is computed with the forward pass (one fused program), so
    ``forward`` runs micro-step + accumulation and ``backward`` is the GAS
    bookkeeping point; semantics (losses returned, update cadence, lr
    schedule, clipping, overflow skipping) match the reference.
"""

from __future__ import annotations

import os
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import comm
from ..checkpoint import saving as ckpt_saving
from ..telemetry import core as telemetry
from ..ops.adam import fused_adagrad, fused_adam
from ..ops.lamb import fused_lamb
from ..parallel import mesh as mesh_lib
from ..utils.logging import log_dist, logger
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from .config import DeepSpeedConfig
from .dataloader import DeepSpeedDataLoader, RepeatingLoader
from .fp16.loss_scaler import (LossScaleState, grads_finite,
                               make_loss_scale_state, update_scale)
from .lr_schedules import build_lr_scheduler
from .sharding import ShardingRules

MEMORY_OPT_ALLREDUCE_SIZE = 500_000_000


def _cast_tree(tree, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), tree)


class _LazyLocalShard:
    """Defers a dp-sharded flat array's local-shard assembly (the blocking
    D2H wait) until np.asarray() is called inside the host optimizer's
    per-leaf step loop — the host hop's double-buffering."""

    __slots__ = ("_f",)

    def __init__(self, f):
        self._f = f

    def __array__(self, dtype=None, copy=None):
        arr = DeepSpeedEngine._extract_local_shard(self._f)
        return arr.astype(dtype) if dtype is not None else arr


def _global_norm(tree):
    leaves = [jnp.sum(jnp.square(g.astype(jnp.float32))) for g in jax.tree.leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


class DeepSpeedEngine:
    def __init__(self, model=None, optimizer=None, model_parameters=None,
                 training_data=None, lr_scheduler=None, mpu=None,
                 collate_fn=None, config=None, loss_fn=None, rng=None,
                 dont_change_device=False):
        comm.init_distributed()

        # ---- mesh ----------------------------------------------------------
        raw = config if isinstance(config, dict) else None
        pre_cfg = DeepSpeedConfig(config, dp_world_size=1) if not isinstance(config, DeepSpeedConfig) else config
        mc = pre_cfg.mesh
        n_dev = len(jax.devices())
        shape = mesh_lib.MeshShape.infer(n_dev, tp=mc.tp, pp=mc.pp, ep=mc.ep,
                                         sp=mc.sp, dp=mc.dp)
        self.mesh = mesh_lib.build_mesh(shape)
        mesh_lib.set_global_mesh(self.mesh, shape)
        self.dp_world_size = shape.dp
        self.mp_world_size = shape.tp

        # ---- config (batch algebra against real dp world) ------------------
        self.config = DeepSpeedConfig(
            config if not isinstance(config, DeepSpeedConfig) else config._raw,
            dp_world_size=self.dp_world_size)
        self._config = self.config  # reference-name parity

        self.module = self._apply_activation_checkpointing_config(model)
        self.loss_fn = loss_fn
        self.collate_fn = collate_fn
        self.mpu = mpu
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0

        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_batch_size(),
            steps_per_output=self.steps_per_print())

        # monitor (rank-0 writers)
        from ..monitor.monitor import MonitorMaster
        self.monitor = MonitorMaster(self.config)

        # flops profiler
        from ..profiling.flops_profiler import FlopsProfiler
        self.flops_profiler = FlopsProfiler(self) if self.config.flops_profiler.enabled else None

        # ---- training-efficiency features ----------------------------------
        # curriculum learning (reference engine.py:1577-1583 kwargs injection)
        cc = self.config.curriculum_learning
        self.curriculum_scheduler = None
        if cc.enabled:
            from .data_pipeline import CurriculumScheduler
            self.curriculum_scheduler = CurriculumScheduler({
                "curriculum_type": cc.curriculum_type,
                "min_difficulty": cc.min_difficulty,
                "max_difficulty": cc.max_difficulty,
                "schedule_type": cc.schedule_type,
                "schedule_config": cc.schedule_config,
            })
        # progressive layer drop (reference engine.py:1571-1572)
        pld = self.config.progressive_layer_drop
        self.progressive_layer_drop = None
        if pld.enabled:
            from .progressive_layer_drop import ProgressiveLayerDrop
            self.progressive_layer_drop = ProgressiveLayerDrop(
                theta=pld.theta, gamma=pld.gamma)
        # eigenvalue + MoQ quantization (reference engine.py:1892-1907)
        ev = self.config.eigenvalue
        self.eigenvalue = None
        self.block_eigenvalue = None
        if ev.enabled:
            from .eigenvalue import Eigenvalue
            self.eigenvalue = Eigenvalue(
                verbose=ev.verbose, max_iter=ev.max_iter, tol=ev.tol,
                stability=ev.stability,
                gas_boundary_resolution=ev.gas_boundary_resolution,
                layer_name=ev.layer_name, layer_num=ev.layer_num)
        qt = self.config.quantize_training
        self.quantizer = None
        if qt.enabled:
            from .quantize import MoQQuantizer
            bits = qt.quantize_bits or {}
            sched = qt.quantize_schedule or {}
            mixed = qt.fp16_mixed_quantize or {}
            self.quantizer = MoQQuantizer(
                q_target_bits=bits.get("target_bits", 8),
                q_start_bits=bits.get("start_bits", 16),
                q_period=sched.get("quantize_period", 100),
                q_offset=sched.get("schedule_offset", 100),
                q_groups=qt.quantize_groups,
                q_mixed_fp16=mixed.get("enabled", False),
                q_change_ratio=mixed.get("quantize_change_ratio", 0.01),
                q_type=qt.quantize_type,
                q_rounding=qt.quantize_schedule.get("rounding", "nearest")
                if qt.quantize_schedule else "nearest",
                q_verbose=qt.quantize_verbose,
                q_eigenvalue=bool(qt.eigenvalue.get("enabled", False))
                if qt.eigenvalue else False)

        # ---- precision -----------------------------------------------------
        self.compute_dtype = self.config.compute_dtype
        self.fp16_enabled = self.config.fp16.enabled
        self.bfloat16_enabled = self.config.bf16.enabled
        self._sr_cast = bool(self.config.bf16.stochastic_rounding)
        if self._sr_cast and not self.bfloat16_enabled:
            raise ValueError(
                "bf16.stochastic_rounding rounds the fp32-master -> bf16 "
                "compute cast and requires bf16.enabled=true (fp16 keeps "
                "the loss-scaler path; fp32 has no cast to round)")
        self.dynamic_loss_scale = self.config.fp16.dynamic_loss_scale if self.fp16_enabled else False

        # ---- ZeRO sharding rules ------------------------------------------
        self.zero_stage = self.config.zero_optimization_stage
        self.rules = ShardingRules(
            self.mesh, self.zero_stage,
            param_persistence_threshold=(
                self.config.zero_config.param_persistence_threshold
                if self.zero_stage >= 3 else 0))

        # ---- ZeRO-Offload / Infinity --------------------------------------
        zc = self.config.zero_config
        self.offload_device = zc.offload_optimizer.device
        self.offload_enabled = self.offload_device in ("cpu", "nvme")
        if self._sr_cast and self.offload_enabled:
            raise NotImplementedError(
                "bf16.stochastic_rounding with offload_optimizer: the "
                "compute-dtype mirror is produced by the host CPU-Adam "
                "(csrc/cpu_adam.cpp, round-to-nearest-even) rather than a "
                "device cast, so the knob would silently not apply — "
                "rejecting loudly instead")
        self._offload_nvme_path = zc.offload_optimizer.nvme_path
        if self.offload_enabled and (self.progressive_layer_drop is not None
                                     or self.quantizer is not None):
            raise ValueError(
                "progressive_layer_drop / quantize_training are not wired "
                "into the offload train path; disable offload_optimizer or "
                "these features (silently ignoring them would train a "
                "different model than configured)")
        self._comm_dtype()   # validate communication_data_type at init,
        # not at first train step (a typo must not survive expensive setup)
        if self.config.amp and self.config.amp.get("enabled"):
            raise ValueError(
                "amp is the reference's NVIDIA-Apex integration and has no "
                "TPU analogue; use the fp16 or bf16 config blocks (same "
                "mixed-precision semantics, in-graph loss scaling)")
        if self.config.disable_allgather:
            log_dist(
                "disable_allgather is inert here: GSPMD emits the ZeRO "
                "step-tail collectives from shardings (the reference knob "
                "swaps allgather for broadcasts as a perf workaround, "
                "engine.py disable_allgather)", ranks=[0])
        if zc.offload_param.layer_streaming and not self.offload_enabled:
            raise ValueError(
                "offload_param.layer_streaming requires offload_optimizer "
                "(the host owns master+moments and serves the per-layer "
                "param fetches); a parsed knob must change the compiled "
                "program or error, never silently no-op")

        # ---- parameters ----------------------------------------------------
        if model_parameters is None:
            raise ValueError(
                "model_parameters (a param pytree) is required: init your "
                "flax module and pass variables['params']")
        self._init_state(model_parameters, optimizer, rng)

        # ---- lr scheduler --------------------------------------------------
        if lr_scheduler is not None:
            self.lr_scheduler = lr_scheduler
        else:
            self.lr_scheduler = build_lr_scheduler(self.config.scheduler)

        # fold schedule into the optimizer's lr (compiled into the step)
        self._rebuild_optimizer_with_schedule()

        # ---- dataloader ----------------------------------------------------
        self.training_dataloader = None
        if training_data is not None:
            self.training_dataloader = self.deepspeed_io(training_data)

        # jit caches
        self._jit_train = None
        self._jit_micro = None
        self._jit_apply = None
        self._pending_loss = None
        self._last_micro = None

        log_dist(
            f"engine ready: mesh={shape.as_dict()} zero_stage={self.zero_stage} "
            f"dtype={jnp.dtype(self.compute_dtype).name} "
            f"batch={self.train_batch_size()}={self.train_micro_batch_size_per_gpu()}"
            f"x{self.gradient_accumulation_steps()}x{self.dp_world_size}",
            ranks=[0])
        if self.config.dump_state:
            # reference dump_state: print the resolved config (engine.py
            # dump_state flag)
            import dataclasses as _dc
            log_dist("resolved config: "
                     f"{_dc.asdict(self.config)}", ranks=[0])

    # ------------------------------------------------------------------ init
    def _apply_activation_checkpointing_config(self, module):
        """Wire the ``activation_checkpointing`` block (reference
        activation_checkpointing/config.py) into the model, or reject knobs
        this design cannot honor — a parsed knob must change the compiled
        program or error, never silently no-op.

          * partition_activations / cpu_checkpointing: flipped on the model
            config (models gate the sharding constraint / host-offload remat
            policy on them; see models/gpt.py tp_shard_sequence and the
            ``ds_block_carry`` offload policy).
          * contiguous_memory_optimization / synchronize_checkpoint_boundary:
            rejected — XLA owns the activation arena and there are no host
            sync points inside a jitted step to align to.
        """
        ac = self.config.activation_checkpointing
        if ac.contiguous_memory_optimization:
            raise ValueError(
                "activation_checkpointing.contiguous_memory_optimization "
                "has no analogue here: XLA's allocator already lays remat "
                "buffers contiguously; remove the knob")
        if ac.synchronize_checkpoint_boundary:
            raise ValueError(
                "activation_checkpointing.synchronize_checkpoint_boundary "
                "cannot be honored: the whole step is one jitted program "
                "with no host sync points; remove the knob")
        if ac.number_checkpoints is not None:
            raise ValueError(
                "activation_checkpointing.number_checkpoints cannot be "
                "honored: remat granularity is structural here (one "
                "checkpoint per scanned block); control the trade with the "
                "model's remat_policy instead")
        if ac.profile:
            raise ValueError(
                "activation_checkpointing.profile is not wired; use "
                "wall_clock_breakdown or the flops_profiler block for "
                "per-phase timing")
        # cpu_checkpointing now composes with multi-chip SPMD — with one
        # compiler quirk: when jit is given explicit out_shardings, XLA's
        # sharding propagation leaves the host-offload
        # annotate_device_placement custom-calls unsharded and the SPMD
        # partitioner RET_CHECKs ("Side-effect HLO must have sharding").
        # The engine therefore records offload mode and its state-jits
        # constrain outputs INSIDE the program (with_sharding_constraint)
        # instead of via out_shardings (see _jit_state_step). Proven
        # multi-mesh by tests/test_engine.py::test_cpu_checkpointing_multichip.
        self._ckpt_offload = bool(
            ac.cpu_checkpointing
            or getattr(getattr(module, "cfg", None), "cpu_checkpointing",
                       False))
        if not (ac.partition_activations or ac.cpu_checkpointing):
            return module
        import dataclasses as _dc
        cfg = getattr(module, "cfg", None)
        if cfg is None or not _dc.is_dataclass(cfg) or not all(
                hasattr(cfg, f) for f in ("partition_activations",
                                          "cpu_checkpointing")):
            raise ValueError(
                "activation_checkpointing.partition_activations / "
                "cpu_checkpointing need a model config that supports them "
                f"(models.GPT does); got module {type(module).__name__}")
        new_cfg = _dc.replace(
            cfg,
            partition_activations=bool(ac.partition_activations
                                       or cfg.partition_activations),
            cpu_checkpointing=bool(ac.cpu_checkpointing
                                   or cfg.cpu_checkpointing))
        # clone() keeps any other constructor fields the module declares
        return module.clone(cfg=new_cfg) if new_cfg != cfg else module

    def _build_base_optimizer(self, optimizer):
        if optimizer is not None and not isinstance(optimizer, optax.GradientTransformation):
            raise TypeError("optimizer must be an optax.GradientTransformation")
        if optimizer is not None:
            if self.zero_stage >= 1 and \
                    not self.config.zero_allow_untested_optimizer:
                # reference _do_sanity_check: an arbitrary client optimizer
                # under ZeRO is unvalidated (sharded-state semantics depend
                # on the optimizer's state tree mirroring params); opt in
                # explicitly (engine.py ZERO_ALLOW_UNTESTED_OPTIMIZER)
                raise ValueError(
                    "a client optimizer with ZeRO >= 1 is untested: set "
                    "zero_optimization + zero_allow_untested_optimizer: "
                    "true to accept sharded-state behavior for it, or use "
                    "a config-named optimizer")
            self._client_optimizer = optimizer
            self._opt_factory = lambda lr: optimizer
            return
        oc = self.config.optimizer
        otype = (oc.type if oc else "Adam").lower()
        params = dict(oc.params) if oc else {}
        lr = params.pop("lr", 1e-3)
        betas = tuple(params.pop("betas", (0.9, 0.999)))
        eps = params.pop("eps", 1e-8)
        wd = params.pop("weight_decay", 0.0)
        params.pop("bias_correction", None)
        params.pop("torch_adam", None)
        params.pop("adam_w_mode", None)
        if otype in ("adam", "adamw", "fusedadam"):
            self._opt_factory = lambda lr_fn: fused_adam(
                lr_fn, betas=betas, eps=eps, weight_decay=wd,
                adam_w_mode=(otype != "adam"))
        elif otype == "lamb":
            self._opt_factory = lambda lr_fn: fused_lamb(
                lr_fn, betas=betas, eps=eps, weight_decay=wd, **params)
        elif otype == "adagrad":
            self._opt_factory = lambda lr_fn: fused_adagrad(
                lr_fn, eps=params.pop("eps", 1e-10), weight_decay=wd)
        elif otype == "sgd":
            mom = params.pop("momentum", 0.0)
            self._opt_factory = lambda lr_fn: optax.sgd(lr_fn, momentum=mom)
        else:
            raise ValueError(f"unknown optimizer type {oc.type!r}")
        self._base_lr = lr
        self._client_optimizer = None

    def _rebuild_optimizer_with_schedule(self):
        if getattr(self, "_onebit", None) is not None:
            return  # runner late-binds the schedule via engine.lr_scheduler
        if self.offload_enabled:
            return  # lr comes from get_lr() at each host step
        if self._client_optimizer is not None:
            self.optimizer = self._client_optimizer
            return
        if self.lr_scheduler is not None:
            sched = self.lr_scheduler
            lr_fn = lambda count: sched.lr_at(count)
        else:
            base = self._base_lr
            lr_fn = lambda count: base
        self.optimizer = self._opt_factory(lr_fn)
        # re-init opt state only if not yet created
        if getattr(self, "state", None) is not None and self.state.get("opt") is None:
            self._init_opt_state()

    def _init_state(self, model_parameters, optimizer, rng):
        oc = self.config.optimizer
        otype = (oc.type if oc else "").lower()
        if otype in ("onebitadam", "onebitlamb", "zerooneadam"):
            # 1-bit optimizers own their communication (compressed momentum
            # exchange) and state layout; they get a dedicated runner instead
            # of silently degrading to dense Adam/LAMB.
            if self.offload_enabled:
                raise ValueError(f"{oc.type} is incompatible with "
                                 "offload_optimizer (reference parity)")
            if self.progressive_layer_drop is not None or \
                    self.quantizer is not None:
                raise ValueError(
                    "progressive_layer_drop / quantize_training are not "
                    "wired into the 1-bit train path; disable them or use a "
                    "dense optimizer")
            if self._sr_cast:
                raise NotImplementedError(
                    "bf16.stochastic_rounding with 1-bit optimizers: the "
                    "OnebitRunner casts master->compute inside its fused "
                    "step without an SR rng stream yet — the knob would "
                    "silently not apply, so it rejects loudly")
            from .fp16.onebit.integration import OnebitRunner
            self._onebit = OnebitRunner(self, otype, dict(oc.params),
                                        model_parameters, rng)
            self.state = self._onebit.state
            self.master_shardings = self._onebit.master_shardings
            self.opt_shardings = self._onebit.opt_shardings
            self._client_optimizer = None
            self.optimizer = None
            return
        self._onebit = None
        if self.offload_enabled:
            # the cap contract applies to ZeRO-Infinity too (works on
            # abstract ShapeDtypeStruct trees — only shapes are read)
            self._check_zero3_working_set(model_parameters)
            self._init_offload_state(model_parameters, optimizer, rng)
            return
        from .zero.partition_params import is_abstract_tree
        if is_abstract_tree(model_parameters):
            raise ValueError(
                "model_parameters is a ShapeDtypeStruct tree: for the "
                "device path materialize it first with "
                "deepspeed_tpu.zero.sharded_init(model, rng, sample, "
                "shardings=...) — params then appear directly in their "
                "ZeRO shards; the abstract tree is accepted as-is only "
                "with offload_optimizer (host/NVMe streaming init)")
        self._build_base_optimizer(optimizer)

        # copy (not alias) the user's params: engine state buffers are donated
        # every step and must not share storage with caller-held arrays
        master = jax.tree.map(lambda x: jnp.array(x, dtype=jnp.float32, copy=True),
                              model_parameters)
        self.master_shardings = self.rules.shardings(self.rules.master_specs(master))
        self.param_shardings = self.rules.shardings(self.rules.param_specs(master))
        self.grad_shardings = self.rules.shardings(self.rules.grad_specs(master))
        self._check_zero3_working_set(master)
        master = jax.device_put(master, self.master_shardings)

        scale_state = make_loss_scale_state(
            static_scale=self.config.fp16.loss_scale if self.fp16_enabled else 1.0,
            initial_scale_power=self.config.fp16.initial_scale_power,
            hysteresis=self.config.fp16.hysteresis,
        ) if self.fp16_enabled else make_loss_scale_state(static_scale=1.0)

        if rng is None:
            rng = jax.random.PRNGKey(self.config.seed)

        self.state = {
            "master": master,
            "opt": None,
            "acc": None,
            "scale": scale_state,
            "rng": rng,
            "step": jnp.zeros((), jnp.int32),
            "skipped": jnp.zeros((), jnp.int32),
        }
        self._init_opt_state()

    def zero3_gather_plan(self, params=None):
        """What a stage-3 step gathers, read off the specs the program is
        built from (``params``: any tree with the model's shapes; default
        the master). Per dp-sharded leaf: the dim ``dp`` lies on, whether a
        scan over layers slices the leaf (``sharding._scan_dims``), and the
        compute-dtype bytes a chip holds of it while it is in use.

        * ``stacked_on_layer_axis`` — scanned leaves with ``dp`` on the dim
          the scan slices. Each iteration then all-gathers the WHOLE stack;
          the rules never place ``dp`` there, so this reads 0.
        * ``stacked_inside_layer`` / ``outside_scan`` — leaves gathered one
          layer a scan step / once a pass (``lm_head``, the final norm).
        * ``layers``, ``layer_gather_bytes`` — scan length, and the bytes one
          layer's gather brings to a chip: the (dp-1)/dp of the layer's
          gathered leaves that the chip does not own.
        * ``gather_bytes_per_micro_step`` — received per chip in the two
          loops over the layers, forward and backward (the recomputed
          forward reads the layer its backward iteration gathered), plus
          the leaves outside the scan in each direction.
        * ``persisted`` / ``largest_gathered`` — elements held replicated
          at all times, and the largest single gathered unit beside them
          (their sum is what ``stage3_max_live_parameters`` is held to).
        Embedding tables with ``dp`` on the vocabulary dim are never
        gathered: the lookup partitions by its indices."""
        if self.zero_stage < 3:
            return None
        if params is None:
            params = self.state["master"]
        from .sharding import _scan_dims, path_str
        mesh_sizes = dict(self.mesh.shape)
        itemsize = jnp.dtype(self.compute_dtype).itemsize
        flat, _ = jax.tree_util.tree_flatten_with_path(params)
        specs = jax.tree.leaves(self.rules.param_specs(params),
                                is_leaf=lambda x: isinstance(x, P))
        plan = {"stacked_on_layer_axis": 0, "stacked_inside_layer": 0,
                "outside_scan": 0, "layers": 0, "persisted": 0}
        layer_numel = outside_numel = largest = 0
        for (pth, p), spec in zip(flat, specs):
            path, shape = path_str(pth), tuple(int(d) for d in p.shape)
            scanned = _scan_dims(path)
            gathered = "dp" in tuple(spec) and \
                not self.rules._is_embed_table(path, shape)
            held = 1   # shards that stay apart while the leaf is in use
            for entry in spec:
                for a in ((entry,) if isinstance(entry, str)
                          else (entry or ())):
                    if a != "dp" or not gathered:
                        held *= mesh_sizes.get(a, 1)
            numel = -(-int(np.prod(shape, dtype=np.int64)) // held)
            if not gathered:
                plan["persisted"] += numel
            elif not scanned:
                plan["outside_scan"] += 1
                outside_numel += numel
                largest = max(largest, numel)
            elif tuple(spec).index("dp") < scanned:
                plan["stacked_on_layer_axis"] += 1
                largest = max(largest, numel)    # the whole stack is live
            else:
                plan["stacked_inside_layer"] += 1
                plan["layers"] = max(plan["layers"], shape[0])
                layer_numel += numel // shape[0]
                largest = max(largest, -(-numel // shape[0]))
        received = (self.rules.dp - 1) / self.rules.dp * itemsize
        plan["layer_gather_bytes"] = int(layer_numel * received)
        plan["gather_bytes_per_micro_step"] = int(
            2 * (plan["layers"] * layer_numel + outside_numel) * received)
        plan["largest_gathered"] = largest
        return plan

    def _check_zero3_working_set(self, params):
        """Say what the stage-3 program gathers (one log line and
        ``train/zero3_*`` gauges from ``zero3_gather_plan``) and honor
        ``stage3_max_live_parameters`` (reference zero/config.py: max live
        params the coordinator may keep gathered,
        partitioned_param_coordinator.py:240-356). The live set is bounded
        by the program's structure: ``dp`` lies inside the layer on every
        scanned leaf and the scan body gathers its own layer's slice, so
        one layer is live at a time (whole-stack gathers would show as
        ``stacked_on_layer_axis``). What CAN violate the cap is its floor:
        persisted (sub-threshold, replicated) params plus the largest
        single unit that must be materialized for its matmul. If the user
        explicitly set a cap below that floor, no schedule could honor it;
        reject loudly rather than nod (an unwired knob must not no-op)."""
        plan = self.zero3_gather_plan(params)
        if plan is None:
            return
        log_dist(
            f"ZeRO-3 gathers: {plan['stacked_inside_layer']} scanned leaves "
            f"one layer a step ({plan['layer_gather_bytes']:,} B a layer a "
            f"chip, {plan['layers']} layers), {plan['outside_scan']} leaves "
            f"outside the scan, {plan['stacked_on_layer_axis']} scanned "
            f"leaves on the layer axis; "
            f"{plan['gather_bytes_per_micro_step']:,} B a micro-step a chip",
            ranks=[0])
        for k in ("stacked_on_layer_axis", "stacked_inside_layer",
                  "outside_scan", "layer_gather_bytes",
                  "gather_bytes_per_micro_step"):
            telemetry.gauge(f"train/zero3_{k}", float(plan[k]))
        zraw = self.config._raw.get("zero_optimization", {})
        if "max_live_parameters" not in zraw \
                and "stage3_max_live_parameters" not in zraw:
            return
        cap = self.config.zero_config.max_live_parameters
        floor = plan["persisted"] + plan["largest_gathered"]
        if cap < floor:
            raise ValueError(
                f"stage3_max_live_parameters={cap:,} is below the working-"
                f"set floor of this model: {plan['persisted']:,} persisted "
                f"params (under param_persistence_threshold="
                f"{self.rules.param_persistence_threshold:,}) + "
                f"{plan['largest_gathered']:,} for the largest single "
                f"tensor. The scan-over-layers program already keeps the "
                f"live set at its structural minimum; raise the cap to at "
                f"least {floor:,}, lower param_persistence_threshold, or "
                f"shard the model further (tp/pp)")

    def _init_opt_state(self):
        # Build a throwaway transformation just for init (lr constant — state
        # structure does not depend on lr).
        opt = self._client_optimizer or self._opt_factory(lambda c: 0.0)
        opt_state = jax.eval_shape(opt.init, self.state["master"])
        self.opt_shardings = self.rules.opt_state_shardings(
            opt_state, self.master_shardings, self.state["master"])
        init_fn = jax.jit(opt.init, out_shardings=self.opt_shardings)
        self.state["opt"] = init_fn(self.state["master"])
        zeros = jax.jit(
            lambda m: jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), m),
            out_shardings=self.grad_shardings)
        self.state["acc"] = zeros(self.state["master"])
        self._state_shardings = {
            "master": self.master_shardings,
            "opt": self.opt_shardings,
            "acc": self.grad_shardings,
            "scale": jax.tree.map(lambda _: NamedSharding(self.mesh, P()), self.state["scale"]),
            "rng": NamedSharding(self.mesh, P()),
            "step": NamedSharding(self.mesh, P()),
            "skipped": NamedSharding(self.mesh, P()),
        }

    # ------------------------------------------------------- config accessors
    def train_batch_size(self):
        return self.config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self.config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self.config.gradient_accumulation_steps

    def steps_per_print(self):
        return self.config.steps_per_print

    def gradient_clipping(self):
        return self.config.gradient_clipping

    def zero_optimization(self):
        return self.zero_stage > 0

    def get_global_grad_norm(self):
        return getattr(self, "_last_grad_norm", None)

    def get_lr(self):
        if self.lr_scheduler is not None:
            if self.offload_enabled:
                count = self.host_optimizer.step_count
            else:
                count = getattr(self.state["opt"], "count", None)
                count = int(jax.device_get(count)) if count is not None else self.global_steps
            return [float(jax.device_get(self.lr_scheduler.lr_at(jnp.asarray(count, jnp.float32))))]
        return [self._base_lr if self._client_optimizer is None else float("nan")]

    @property
    def loss_scale(self):
        if self.offload_enabled:
            return float(self._host_scale)
        return float(jax.device_get(self.state["scale"].cur_scale))

    # ------------------------------------------------------------- model fns
    @property
    def _module_params(self):
        """Parameter names the flax module's __call__ accepts, resolved ONCE
        by signature inspection (not try/except around the traced apply,
        which would mask unrelated TypeErrors and silently drop kwargs for
        **kwargs models)."""
        cached = getattr(self, "_module_params_cache", None)
        if cached is None:
            import inspect
            names, var_kw = set(), False
            if hasattr(self.module, "apply"):
                try:
                    sig = inspect.signature(type(self.module).__call__)
                    for p in sig.parameters.values():
                        if p.kind is inspect.Parameter.VAR_KEYWORD:
                            var_kw = True
                        names.add(p.name)
                except (TypeError, ValueError):
                    var_kw = True
            cached = self._module_params_cache = (names, var_kw)
        return cached

    def _apply_model(self, params, batch, rng, train=True, model_kwargs=None):
        if hasattr(self.module, "apply"):  # flax module
            rngs = {"dropout": rng, "gating": jax.random.fold_in(rng, 1),
                    "pld": jax.random.fold_in(rng, 2)}
            if isinstance(batch, dict):
                inputs = batch.get("input_ids", batch.get("inputs"))
                if inputs is None:
                    raise ValueError("flax-module path expects batch['input_ids']")
            else:
                inputs = batch
            names, var_kw = self._module_params
            kwargs = {}
            if var_kw or "deterministic" in names:
                kwargs["deterministic"] = not train
            for k, v in (model_kwargs or {}).items():
                if var_kw or k in names:
                    kwargs[k] = v
            with self.rules.stating_layer_gathers():
                return self.module.apply({"params": params}, inputs,
                                         rngs=rngs, **kwargs)
        return self.module(params, batch, rng)

    def _loss_of(self, params, batch, rng, train=True, model_kwargs=None):
        out = self._apply_model(params, batch, rng, train=train,
                                model_kwargs=model_kwargs)
        if self.loss_fn is not None:
            return self.loss_fn(out, batch)
        if isinstance(out, jnp.ndarray) and out.ndim == 0:
            return out
        raise ValueError("model output is not a scalar loss; pass loss_fn")

    def _cast_params(self, master, rng):
        """fp32 master -> compute-dtype params, sharded. Under
        bf16.stochastic_rounding the cast is unbiased (per-leaf PRNG
        streams), removing round-to-nearest drift from the training
        trajectory; returns (params, advanced rng)."""
        if getattr(self, "_sr_cast", False):
            from ..ops.quantizer import stochastic_round_bf16
            rng, k = jax.random.split(rng)
            leaves, treedef = jax.tree_util.tree_flatten(master)
            keys = jax.random.split(k, len(leaves))
            params = jax.tree_util.tree_unflatten(
                treedef, [stochastic_round_bf16(l, kk)
                          for l, kk in zip(leaves, keys)])
        else:
            params = _cast_tree(master, self.compute_dtype)
        return (jax.lax.with_sharding_constraint(
            params, self.param_shardings), rng)

    def _micro_grads(self, master, scale, batch, rng, params=None,
                     model_kwargs=None):
        if params is None:
            # compute-dtype copy of the master weights; callers that loop over
            # microbatches pass a pre-cast tree so the cast runs once per
            # train step, not once per micro step
            params, rng = self._cast_params(master, rng)

        def scaled_loss(p):
            loss = self._loss_of(p, batch, rng, model_kwargs=model_kwargs)
            return (loss.astype(jnp.float32) * scale), loss

        (_, loss), grads = jax.value_and_grad(scaled_loss, has_aux=True)(params)
        cdt = self._comm_dtype()
        if cdt is not None:
            # reference communication_data_type: the dp grad reduction runs
            # in this dtype (engine.py allreduce dtype override). The
            # sharding constraint lands while the grads are STILL narrow,
            # so GSPMD emits the reduce-scatter on the narrow type — half
            # the ICI bytes for bf16/fp16 — and only the already-reduced
            # shards widen back to the fp32 accumulator.
            grads = _cast_tree(grads, cdt)
            grads = jax.lax.with_sharding_constraint(grads,
                                                     self.grad_shardings)
            grads = _cast_tree(grads, jnp.float32)
        else:
            grads = _cast_tree(grads, jnp.float32)
            grads = jax.lax.with_sharding_constraint(grads,
                                                     self.grad_shardings)
        return loss.astype(jnp.float32), grads

    def _comm_dtype(self):
        """communication_data_type -> jnp dtype (None = keep fp32)."""
        cdt = self.config.communication_data_type
        if not cdt:
            return None
        names = {"fp16": jnp.float16, "float16": jnp.float16,
                 "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
                 "fp32": None, "float32": None}
        if cdt not in names:
            raise ValueError(
                f"communication_data_type={cdt!r}: use fp16/bf16/fp32 "
                "(reference engine.py communication_data_type)")
        return names[cdt]

    def _apply_update(self, state, gas):
        """Unscale+clip+update with overflow guard, all traced."""
        scale = state["scale"].cur_scale
        denom = scale * gas
        if self.config.prescale_gradients:
            denom = denom * self.config.gradient_predivide_factor
        grads = jax.tree.map(lambda a: a / denom, state["acc"])
        finite = grads_finite(grads) if self.fp16_enabled else jnp.asarray(True)
        gnorm = _global_norm(grads)
        clip = self.gradient_clipping()
        if clip and clip > 0:
            factor = clip / jnp.maximum(gnorm, clip)
            grads = jax.tree.map(lambda g: g * factor, grads)

        updates, new_opt = self.optimizer.update(grads, state["opt"], state["master"])
        new_master = optax.apply_updates(state["master"], updates)

        sel = lambda a, b: jax.tree.map(
            lambda x, y: jnp.where(finite, x, y), a, b)
        master = sel(new_master, state["master"])
        opt = sel(new_opt, state["opt"])
        master = jax.lax.with_sharding_constraint(master, self.master_shardings)

        new_scale = update_scale(
            state["scale"], finite,
            dynamic=self.dynamic_loss_scale,
            scale_window=self.config.fp16.loss_scale_window,
            min_scale=self.config.fp16.min_loss_scale,
            hysteresis=self.config.fp16.hysteresis)

        zeros = jax.tree.map(lambda a: jnp.zeros_like(a), state["acc"])
        return {
            "master": master,
            "opt": opt,
            "acc": zeros,
            "scale": new_scale,
            "rng": state["rng"],
            "step": state["step"] + 1,
            "skipped": state["skipped"] + (~finite).astype(jnp.int32),
        }, gnorm, finite

    # ------------------------------------------------------------ train APIs
    def _build_train_jit(self):
        gas = self.gradient_accumulation_steps()

        def train_step(state, batches, extras):
            # fp32->compute cast hoisted out of the micro loop (the scan body
            # would otherwise re-cast the full master tree every micro step)
            params, step_rng = self._cast_params(state["master"],
                                                 state["rng"])
            state = dict(state, rng=step_rng)

            def body(carry, batch):
                acc, loss_sum, rng = carry
                rng, sub = jax.random.split(rng)
                loss, grads = self._micro_grads(
                    state["master"], state["scale"].cur_scale, batch, sub,
                    params=params, model_kwargs=extras)
                acc = jax.tree.map(jnp.add, acc, grads)
                acc = jax.lax.with_sharding_constraint(acc, self.grad_shardings)
                return (acc, loss_sum + loss, rng), None

            (acc, loss_sum, rng), _ = jax.lax.scan(
                body, (state["acc"], jnp.zeros((), jnp.float32), state["rng"]),
                batches)
            state = dict(state, acc=acc, rng=rng)
            new_state, gnorm, finite = self._apply_update(state, float(gas))
            return new_state, {"loss": loss_sum / gas, "grad_norm": gnorm,
                               "finite": finite}

        return self._jit_state_step(train_step)

    def _jit_state_step(self, fn):
        """jit a ``(state, ...) -> (new_state, aux)`` step with state
        donation. Output shardings normally ride out_shardings; under
        cpu_checkpointing they are constrained INSIDE the program instead —
        explicit out_shardings flips XLA into a propagation mode that
        leaves the host-offload placement custom-calls unsharded and the
        SPMD partitioner rejects the module (RET_CHECK, spmd_partitioner
        .cc: "Side-effect HLO must have sharding")."""
        if not getattr(self, "_ckpt_offload", False):
            return jax.jit(fn, donate_argnums=(0,),
                           out_shardings=(self._state_shardings, None))

        def constrained(state, *args, **kwargs):
            new_state, aux = fn(state, *args, **kwargs)
            new_state = jax.lax.with_sharding_constraint(
                new_state, self._state_shardings)
            return new_state, aux

        return jax.jit(constrained, donate_argnums=(0,))

    def _forward_extras(self):
        """Traced per-step model kwargs (PLD theta etc.) — passed as jit
        arguments so host-side schedules never trigger recompiles."""
        extras = {}
        if self.progressive_layer_drop is not None:
            theta = self.progressive_layer_drop.update_state(self.global_steps)
            extras["pld_theta"] = jnp.asarray(theta, jnp.float32)
        return extras

    def _apply_curriculum(self, batches, stacked=True):
        """Truncate the sequence axis to the scheduled difficulty (seqlen
        curricula; reference injects curriculum_seqlen kwargs, engine.py:1577
        — here the batch itself is cut so attention/loss shapes shrink with
        difficulty, which is where the TPU speedup comes from)."""
        diff = self.curriculum_scheduler.update_difficulty(self.global_steps + 1)
        # non-seqlen types are rejected at CurriculumScheduler construction
        axis = 2 if stacked else 1

        def cut(x):
            if x.ndim > axis and x.shape[axis] > diff:
                return jax.lax.slice_in_dim(x, 0, diff, axis=axis)
            return x
        return jax.tree.map(cut, batches)

    def _apply_moq(self, metrics):
        """MoQ boundary hook (reference engine.py:1892-1907): optionally
        refresh block eigenvalues, then quantize-dequantize the master."""
        overflow = False
        if self.fp16_enabled:
            overflow = not bool(jax.device_get(metrics["finite"]))
        eig_on = (self.eigenvalue is not None and self.quantizer.q_eigenvalue)
        if eig_on and self.global_steps % \
                self.eigenvalue.gas_boundary_resolution == 0 and \
                self._last_micro is not None:
            loss_fn = lambda p, b, r: self._loss_of(
                _cast_tree(p, self.compute_dtype), b, r)
            self.block_eigenvalue = self.eigenvalue.compute_eigenvalue(
                loss_fn, self.state["master"], self._last_micro)
        self.state["master"] = self.quantizer.quantize(
            self.state["master"], overflow=overflow,
            eigenvalue_enabled=eig_on,
            block_eigenvalue=self.block_eigenvalue)

    def _shard_batch(self, batch, stacked: bool = False):
        sp = dict(self.mesh.shape).get("sp", 1)
        multiproc = jax.process_count() > 1

        def put(x):
            x = np.asarray(x) if multiproc else jnp.asarray(x)
            dim = 1 if stacked else 0
            spec = [None] * x.ndim
            if x.ndim > dim and x.shape[dim] % self.dp_world_size == 0:
                spec[dim] = "dp"
            # sequence parallelism: the seq axis lands pre-sharded over sp
            # (models constrain activations the same way — Ulysses)
            if sp > 1 and x.ndim > dim + 1 and x.shape[dim + 1] % sp == 0:
                spec[dim + 1] = "sp"
            sh = NamedSharding(self.mesh, P(*spec))
            if multiproc:
                # every process holds the SAME global batch (seeded loader);
                # device_put of non-addressable shards is illegal multi-host,
                # so each process contributes its addressable slices
                return jax.make_array_from_process_local_data(
                    sh, x, global_shape=x.shape)
            return jax.device_put(x, sh)

        return jax.tree.map(put, batch)

    def train_batch(self, data_iter=None):
        """Pull GAS micro-batches and run one full optimizer step (reference
        PipelineEngine.train_batch:302 generalized to the non-pipe engine)."""
        if data_iter is None:
            if self.training_dataloader is None:
                raise ValueError("no data_iter and no training_data")
            if not hasattr(self, "_train_iter"):
                self._train_iter = iter(RepeatingLoader(self.training_dataloader))
            data_iter = self._train_iter
        gas = self.gradient_accumulation_steps()
        with telemetry.span("train/data", gas=gas):
            micros = [next(data_iter) for _ in range(gas)]
            batches = jax.tree.map(lambda *xs: np.stack(xs), *micros)
            if self.curriculum_scheduler is not None:
                batches = self._apply_curriculum(batches, stacked=True)
            batches = self._shard_batch(batches, stacked=True)
        # only the eigenvalue refresh consumes a sample batch — don't pin one
        # in HBM for plain MoQ
        self._last_micro = jax.tree.map(lambda x: x[0], batches) \
            if (self.quantizer is not None and self.quantizer.q_eigenvalue
                and self.eigenvalue is not None) else None

        if getattr(self, "_onebit", None) is not None:
            self.tput_timer.start()
            metrics = self._onebit.train_batch(batches)
            self.state = self._onebit.state
            will_report = (self.global_steps + 1) % self.steps_per_print() == 0
            self.tput_timer.stop(sync=metrics["loss"] if will_report else None)
            self.global_steps += 1
            self.micro_steps += gas
            self.global_samples += self.train_batch_size()
            self._last_grad_norm = metrics["grad_norm"]
            self._after_step(metrics)
            return metrics["loss"]

        if self.offload_enabled:
            self.tput_timer.start()
            metrics = self._offload_train_batch(batches)
            self.tput_timer.stop(sync=metrics["loss"])
            self.global_steps += 1
            self.micro_steps += gas
            self.global_samples += self.train_batch_size()
            self._after_step(metrics)
            return metrics["loss"]

        if self._jit_train is None:
            self._jit_train = self._build_train_jit()

        # wall-clock breakdown (reference EngineTimers, engine.py:135-173):
        # one jitted program means fwd/bwd/step aren't host-separable —
        # the honest phases are host batch prep, async dispatch, and
        # device execution (dispatch->sync)
        wcb = self.config.wall_clock_breakdown
        self.tput_timer.start()
        if wcb:
            self.timers("train_batch_dispatch").start()
        # dispatch-only span BY DESIGN: JAX returns before the device
        # finishes; the device time lands in train/sync on report steps
        with telemetry.span("train/dispatch", step=self.global_steps):
            self.state, metrics = self._jit_train(self.state, batches,
                                                  self._forward_extras())
        if wcb:
            self.timers("train_batch_dispatch").stop()
            self.timers("train_batch_device").start()
            float(jax.device_get(metrics["loss"]))  # device_get IS the sync
            self.timers("train_batch_device").stop()
        # sync only on report steps: a per-step block_until_ready would
        # serialize dispatch against the device and stall the pipeline
        will_report = (self.global_steps + 1) % self.steps_per_print() == 0
        with telemetry.span("train/sync", report=will_report):
            self.tput_timer.stop(sync=metrics["loss"] if will_report
                                 else None)
        if will_report and telemetry.get_runtime().enabled:
            # already synced above, so this device_get is a cheap host
            # copy; off report steps nothing reads the device
            skipped = int(jax.device_get(self.state["skipped"]))  # tracelint: disable=host-sync
            prev = getattr(self, "_tel_skipped", 0)
            if skipped > prev:
                telemetry.instant("train/loss_scale_skip",
                                  total_skipped=skipped,
                                  new=skipped - prev)
            telemetry.gauge("train/skipped_steps", float(skipped))
            self._tel_skipped = skipped
        # shapes of the last stacked+sharded batch, kept abstract for
        # estimate_step_flops (MFU) — no device buffers retained
        self._step_aval_batches = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), batches)
        self.global_steps += 1
        self.micro_steps += gas
        self.global_samples += self.train_batch_size()
        self._last_grad_norm = metrics["grad_norm"]
        if self.quantizer is not None:
            self._apply_moq(metrics)
        self._after_step(metrics)
        return metrics["loss"]

    def estimate_step_flops(self) -> Optional[Dict[str, Any]]:
        """XLA cost analysis of one fused train-step program, for MFU
        reporting (telemetry.mfu / the flops profiler). Requires at
        least one completed ``train_batch`` on the jitted path (the
        batch avals are captured there). Lowers with abstract
        ``ShapeDtypeStruct`` args — no device work — but pays one extra
        XLA compile, so call it outside audited/timed regions. The GAS
        micro loop is a ``lax.scan`` whose body XLA counts once;
        ``flops_per_step`` scales by ``gradient_accumulation_steps``
        (flagged as an estimate). Returns None when unavailable."""
        avals = getattr(self, "_step_aval_batches", None)
        if self._jit_train is None or avals is None:
            return None
        from ..telemetry import mfu as _mfu

        def abst(x):
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                return jax.ShapeDtypeStruct(np.shape(x), x.dtype)
            return x
        ca = _mfu.compiled_cost_analysis(
            self._jit_train, jax.tree.map(abst, self.state), avals,
            jax.tree.map(abst, self._forward_extras()))
        if ca is None:
            return None
        gas = self.gradient_accumulation_steps()
        flops_per_step = ca["flops"] * gas
        return {
            "program_flops": ca["flops"],
            "bytes_accessed": ca["bytes_accessed"],
            "scan_length": gas,
            "flops_per_step": flops_per_step,
            "flops": flops_per_step,
            "scan_body_counted_once": True,
            "peak_flops_per_device": _mfu.peak_flops_per_device(),
        }

    # --- 3-call parity API -------------------------------------------------
    def forward(self, batch):
        """Run one micro forward(+grad) and buffer the accumulation."""
        if getattr(self, "_onebit", None) is not None:
            raise NotImplementedError(
                "1-bit optimizers fuse the micro loop with the compressed "
                "exchange — use engine.train_batch(data_iter)")
        if self.offload_enabled:
            raise NotImplementedError(
                "with offload_optimizer use engine.train_batch(data_iter) — "
                "the offload path fuses the micro loop with the host "
                "optimizer round-trip")
        if self._jit_micro is None:
            def micro(state, batch):
                rng, sub = jax.random.split(state["rng"])
                loss, grads = self._micro_grads(
                    state["master"], state["scale"].cur_scale, batch, sub)
                acc = jax.tree.map(jnp.add, state["acc"], grads)
                return dict(state, acc=acc, rng=rng), loss
            self._jit_micro = self._jit_state_step(micro)
        batch = self._shard_batch(batch)
        self.state, loss = self._jit_micro(self.state, batch)
        self._pending_loss = loss
        if self.flops_profiler:
            self.flops_profiler.on_forward(batch)
        return loss

    __call__ = forward

    def backward(self, loss=None, allreduce_gradients=True):
        """Gradient was produced with forward (fused on TPU); this is the GAS
        bookkeeping boundary (reference engine.backward:1665)."""
        self.micro_steps += 1
        self.global_samples += self.train_micro_batch_size_per_gpu() * self.dp_world_size
        return loss if loss is not None else self._pending_loss

    def is_gradient_accumulation_boundary(self):
        return self.micro_steps % self.gradient_accumulation_steps() == 0

    def step(self):
        if not self.is_gradient_accumulation_boundary():
            return
        if self._jit_apply is None:
            gas = float(self.gradient_accumulation_steps())
            def apply_only(state):
                new_state, gnorm, finite = self._apply_update(state, gas)
                return new_state, {"grad_norm": gnorm, "finite": finite,
                                   "loss": jnp.zeros((), jnp.float32)}
            self._jit_apply = self._jit_state_step(apply_only)
        self.state, metrics = self._jit_apply(self.state)
        self.global_steps += 1
        self._last_grad_norm = metrics["grad_norm"]
        self._after_step(metrics)

    def _after_step(self, metrics):
        if self.lr_scheduler is not None:
            self.lr_scheduler.step()
        if self.config.wall_clock_breakdown and \
                self.global_steps % self.steps_per_print() == 0:
            self.timers.log(["train_batch_dispatch", "train_batch_device"])
        if self.global_steps % self.steps_per_print() == 0:
            self._report_progress(self.global_steps, metrics)
            if self.config.memory_breakdown:
                # reference memory_breakdown: see_memory_usage at report
                # boundaries (runtime/utils.py)
                log_dist("memory: " + self.timers.memory_usage(), ranks=[0])
        if self.monitor.enabled and jax.process_index() == 0:
            evts = [("Train/Samples/train_loss", float(jax.device_get(metrics["loss"])),
                     self.global_samples)]
            self.monitor.write_events(evts)
        if self.flops_profiler:
            self.flops_profiler.on_step(self.global_steps)

    def _report_progress(self, step, metrics):
        loss = float(jax.device_get(metrics["loss"]))
        lr = self.get_lr()
        log_dist(f"step={step}, loss={loss:.4f}, lr={lr}, "
                 f"loss_scale={self.loss_scale:g}, "
                 f"samples/sec={self.tput_timer.avg_samples_per_sec():.2f}",
                 ranks=[0])

    # ---------------------------------------------------------------- eval
    def eval_batch(self, batch):
        if getattr(self, "_layer_streamer", None) is not None:
            # capacity tier: eval streams layers too — the full model must
            # never materialize on device (runtime/zero/layer_stream.py)
            if not hasattr(self, "_jit_stream_eval"):
                from .zero.layer_stream import build_streamed_eval
                self._jit_stream_eval = build_streamed_eval(
                    self._layer_streamer)
            res = jax.tree.map(
                jnp.asarray, self._layer_streamer.resident_host_tree())
            return self._jit_stream_eval(res, batch)
        if not hasattr(self, "_jit_eval"):
            cast = not self.offload_enabled
            def ev(master, batch, rng):
                params = _cast_tree(master, self.compute_dtype) if cast else master
                return self._loss_of(params, batch, rng, train=False)
            self._jit_eval = jax.jit(ev)
        batch = self._shard_batch(batch)
        src = (self._offload_params_view() if self.offload_enabled
               else self.state["master"])
        return self._jit_eval(src, batch, self.state["rng"])

    def _offload_params_view(self):
        """Device params for eval/export; with offload_param they are
        rebuilt from the mirrors on demand (and consumed by the next step)."""
        if getattr(self, "_layer_streamer", None) is not None:
            raise RuntimeError(
                "the layer-streamed tier never materializes the full model "
                "on device; use get_params() (host-side numpy) or "
                "save_16bit_model() instead")
        if self.state["params"] is None:
            self.state["params"] = self._offload_restore_params()
        return self.state["params"]

    def get_params(self, dtype=None):
        """Current (compute-dtype) parameters as a pytree. Always a COPY:
        engine state buffers are donated into the next train step, and a
        same-dtype astype would alias them (the caller's tree would read
        'Array has been deleted' after one more step).

        Layer-streamed tier: assembled HOST-side (numpy) from the mirrors —
        the capacity model is larger than HBM by design, so it must never
        materialize on device."""
        dt = dtype or self.compute_dtype
        if getattr(self, "_layer_streamer", None) is not None:
            tree = self.host_optimizer.mirror_tree()
            # copy=True: mirror() can return views of the live host mirror
            # buffers, which the next step overwrites in place
            return jax.tree.map(
                lambda x: np.array(x, dtype=dt, copy=True), tree)
        src = (self._offload_params_view() if self.offload_enabled
               else self.state["master"])
        return jax.tree.map(lambda x: jnp.array(x, dtype=dt, copy=True), src)

    # ------------------------------------------------------------ dataloader
    def deepspeed_io(self, dataset, batch_size=None, route="train",
                     data_sampler=None, collate_fn=None, num_local_io_workers=None):
        bs = batch_size or (self.train_micro_batch_size_per_gpu() * self.dp_world_size)
        return DeepSpeedDataLoader(dataset, batch_size=bs,
                                   collate_fn=collate_fn or self.collate_fn,
                                   drop_last=self.config.dataloader_drop_last)

    # ----------------------------------------------------------- checkpoints
    def _validate_checkpoint_tag(self, tag: str) -> None:
        """All ranks must save under the SAME tag (reference
        _checkpoint_tag_validation, engine.py:2750: a compare guard,
        warn|fail|ignore per config)."""
        mode = (self.config.checkpoint_tag_validation or "warn").lower()
        if mode not in ("warn", "fail", "ignore"):
            raise ValueError(
                f"checkpoint_tag_validation={mode!r}: use warn|fail|ignore")
        if mode == "ignore" or jax.process_count() == 1:
            return
        import zlib
        from jax.experimental import multihost_utils
        mine = np.asarray([zlib.crc32(tag.encode())], np.uint32)
        # SYMMETRIC check: every rank sees every hash, so on mismatch ALL
        # ranks take the same branch — a one-sided raise would leave the
        # passing ranks deadlocked at the save collectives
        all_hashes = np.asarray(
            multihost_utils.process_allgather(mine)).reshape(-1)
        if len(set(int(h) for h in all_hashes)) > 1:
            msg = (f"checkpoint tags differ across processes (this rank: "
                   f"{tag!r}) — mixed-tag checkpoints cannot be loaded back")
            if mode == "fail":
                raise ValueError(msg)
            log_dist("WARNING: " + msg, ranks=None)

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True):
        tag = tag or f"global_step{self.global_steps}"
        self._validate_checkpoint_tag(tag)
        meta = {
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "micro_steps": self.micro_steps,
            "skipped_steps": (self.skipped_steps if self.offload_enabled
                              else int(jax.device_get(self.state["skipped"]))),
            "loss_scale": self.loss_scale,
            "lr_scheduler": self.lr_scheduler.state_dict() if self.lr_scheduler else None,
            "zero_stage": self.zero_stage,
            "dp_world_size": self.dp_world_size,
            "client_state": client_state or {},
            "curriculum": (self.curriculum_scheduler.get_state()
                           if self.curriculum_scheduler else None),
            "quantizer": (self.quantizer.get_state()
                          if self.quantizer else None),
        }
        if self.offload_enabled:
            if self._use_sharded_checkpoint(host=True):
                return self._save_offload_sharded(save_dir, tag, meta)
            return ckpt_saving.save_checkpoint_dir(
                save_dir, tag,
                master_params=self.host_optimizer.master_tree(),
                opt_state=self.host_optimizer.opt_state_tree(), meta=meta)
        return ckpt_saving.save_checkpoint_dir(
            save_dir, tag, master_params=self.state["master"],
            opt_state=self.state["opt"], meta=meta,
            sharded=self._use_sharded_checkpoint())

    # Above this size the npz full-gather (O(model) host DRAM on rank 0)
    # stops being acceptable and the per-rank parallel shard path kicks in
    SHARDED_CKPT_AUTO_BYTES = 2_000_000_000

    def _use_sharded_checkpoint(self, host: bool = False) -> bool:
        mode = self.config.sharded_checkpoint
        if mode != "auto":
            return bool(mode)
        if jax.process_count() > 1:
            return True
        if host:
            return not self.host_optimizer.owns_all()
        total = sum(int(np.prod(l.shape)) * 4
                    for l in jax.tree.leaves(self.state["master"]))
        return total > self.SHARDED_CKPT_AUTO_BYTES

    def _save_offload_sharded(self, save_dir, tag, meta):
        """Per-host shard files for the host-DRAM/NVMe optimizer tier
        (reference zero_pp_rank_* per-rank files, engine.py:3076)."""
        ckpt_dir = os.path.join(save_dir, tag)
        os.makedirs(ckpt_dir, exist_ok=True)
        self.host_optimizer.save_shard(ckpt_dir)
        comm.barrier()
        if jax.process_index() == 0:
            import json as _json
            with open(os.path.join(ckpt_dir, "meta.json"), "w") as fh:
                _json.dump(dict(meta, format="host_sharded"), fh, indent=2)
            with open(os.path.join(save_dir, "latest"), "w") as fh:
                fh.write(tag)
            ckpt_saving.drop_recovery_script(ckpt_dir)
        log_dist(f"saved host-sharded checkpoint {ckpt_dir}", ranks=[0])
        return ckpt_dir

    def load_checkpoint(self, load_dir, tag=None,
                        load_optimizer_states=True,
                        load_lr_scheduler_states=True,
                        load_module_only=False):
        if self.offload_enabled:
            import glob as _glob
            tag2 = tag or ckpt_saving.read_latest_tag(load_dir)
            if tag2 and _glob.glob(os.path.join(
                    load_dir, tag2, "zero_host_shard_p*.json")):
                return self._load_offload_sharded(
                    load_dir, tag2, load_optimizer_states, load_module_only)
            res = ckpt_saving.load_checkpoint_dir(
                load_dir, tag,
                master_template=self.host_optimizer.master_tree(),
                opt_template=self.host_optimizer.opt_state_tree(),
                master_shardings=None, opt_shardings=None)
        else:
            res = ckpt_saving.load_checkpoint_dir(
                load_dir, tag, master_template=self.state["master"],
                opt_template=self.state["opt"],
                master_shardings=self.master_shardings,
                opt_shardings=self.opt_shardings)
        if res is None:
            log_dist(f"no checkpoint found in {load_dir}", ranks=[0])
            return None, {}
        meta = res["meta"]
        if self.offload_enabled:
            self.host_optimizer.load_state(
                master_tree=res["master_params"],
                opt_state=(res["opt_state"] if load_optimizer_states
                           and not load_module_only else None))
            if self._layer_streamer is None:
                self.state["params"] = self._offload_restore_params()
            # layer-streamed tier: params stay host-side; the next step
            # fetches the restored mirrors per layer (materializing the
            # full tree here would break the one-block HBM invariant)
            self._host_scale = float(meta["loss_scale"])
        else:
            self.state["master"] = res["master_params"]
            if load_optimizer_states and not load_module_only:
                self.state["opt"] = res["opt_state"]
            sc = self.state["scale"]
            self.state["scale"] = sc._replace(
                cur_scale=jnp.asarray(meta["loss_scale"], jnp.float32))
        if load_lr_scheduler_states and self.lr_scheduler and meta.get("lr_scheduler"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        if self.curriculum_scheduler is not None and meta.get("curriculum"):
            self.curriculum_scheduler.set_state(meta["curriculum"])
        if self.quantizer is not None and meta.get("quantizer"):
            self.quantizer.set_state(meta["quantizer"])
        if getattr(self, "_onebit", None) is not None:
            # phase selection (warmup vs compressed, 0/1 Adam intervals) is
            # keyed on APPLIED updates (step - skipped) — realign the device
            # counters and the host-side policy counters to the restored run
            self.state["step"] = jax.device_put(
                jnp.asarray(meta["global_steps"], jnp.int32),
                self._onebit._rep)
            skipped = int(meta.get("skipped_steps", 0) or 0)
            self.state["skipped"] = jax.device_put(
                jnp.asarray(skipped, jnp.int32), self._onebit._rep)
            self._onebit.restore_step(meta["global_steps"] - skipped)
        self.global_steps = meta["global_steps"]
        self.global_samples = meta["global_samples"]
        self.micro_steps = meta["micro_steps"]
        # the host counter feeds the next save's skipped_steps (offload
        # mode); without restoring it a resumed run under-reports skips
        self.skipped_steps = int(meta.get("skipped_steps", 0) or 0)
        log_dist(f"loaded checkpoint tag={res['tag']} step={self.global_steps}",
                 ranks=[0])
        return os.path.join(load_dir, res["tag"]), meta.get("client_state", {})

    def _load_offload_sharded(self, load_dir, tag, load_optimizer_states,
                              load_module_only):
        import json as _json
        ckpt_dir = os.path.join(load_dir, tag)
        with open(os.path.join(ckpt_dir, "meta.json")) as fh:
            meta = _json.load(fh)
        self.host_optimizer.load_shards(
            ckpt_dir,
            load_optimizer_states=load_optimizer_states and not load_module_only)
        if self._layer_streamer is None:
            self.state["params"] = self._offload_restore_params()
        self._host_scale = float(meta["loss_scale"])
        if self.lr_scheduler and meta.get("lr_scheduler"):
            self.lr_scheduler.load_state_dict(meta["lr_scheduler"])
        self.global_steps = meta["global_steps"]
        self.global_samples = meta["global_samples"]
        self.micro_steps = meta["micro_steps"]
        log_dist(f"loaded host-sharded checkpoint tag={tag} "
                 f"step={self.global_steps}", ranks=[0])
        return ckpt_dir, meta.get("client_state", {})

    def consolidated_fp32_state_dict(self):
        """Full fp32 weights, '/'-path-keyed numpy (the in-process
        zero_to_fp32; reference _zero3_consolidated_16bit_state_dict /
        deepspeed.utils.zero_to_fp32, engine.py:3089). Offload tiers
        consolidate host-side from the master shards."""
        if self.offload_enabled:
            return ckpt_saving.consolidated_fp32_state_dict(
                self.host_optimizer.master_tree())
        if jax.process_count() > 1:
            raise RuntimeError(
                "consolidated_fp32_state_dict gathers the FULL tree on this "
                "host; under multi-host sharding use the sharded checkpoint "
                "path (save_checkpoint) and consolidate offline with the "
                "dropped-in zero_to_fp32.py")
        return ckpt_saving.consolidated_fp32_state_dict(self.state["master"])

    def save_16bit_model(self, save_dir, save_filename="pytorch_model.npz"):
        os.makedirs(save_dir, exist_ok=True)
        if self.offload_enabled:
            params16 = self.host_optimizer.mirror_tree()
        else:
            params16 = _cast_tree(self.state["master"], self.compute_dtype)
        ckpt_saving.save_tree(os.path.join(save_dir, save_filename), params16)
        return True

    # =====================================================================
    # ZeRO-Offload / Infinity path: optimizer state lives in host DRAM (or
    # NVMe); the device program computes only grads. See
    # runtime/zero/offload.py for the design note and reference citations.
    # =====================================================================

    def _init_offload_state(self, model_parameters, optimizer, rng):
        from .zero.offload import HostOffloadOptimizer

        if optimizer is not None:
            raise ValueError(
                "offload_optimizer is driven by the config optimizer; do "
                "not pass a client optax optimizer")
        oc = self.config.optimizer
        params = dict(oc.params) if oc else {}
        otype = (oc.type if oc else "Adam").lower()
        if otype not in ("adam", "adamw", "fusedadam", "cpuadam"):
            raise ValueError(
                f"offload_optimizer supports Adam/AdamW, got {oc.type!r}")
        self._base_lr = params.get("lr", 1e-3)
        mirror = jnp.dtype(self.compute_dtype).name
        nvme = self._offload_nvme_path if self.offload_device == "nvme" else None
        if self.offload_device == "nvme" and not nvme:
            raise ValueError("offload_optimizer.device=nvme requires nvme_path")
        # ZeRO-Infinity PARAM tier (reference partitioned_param_swapper.py:37
        # via offload_param config): params are not kept in HBM between
        # steps — they are rebuilt from the host/NVMe mirrors at each step
        # start and donated away with the grads program. During compute they
        # are sharded over the whole mesh (param_shardings), so transient
        # HBM is model_size/num_chips; between steps it is ~0.
        op = self.config.zero_config.offload_param
        self._params_resident = op.device not in ("cpu", "nvme")
        mirror_nvme = None
        if op.device == "nvme":
            mirror_nvme = op.nvme_path or (
                os.path.join(nvme, "params") if nvme else None)
            if not mirror_nvme:
                raise ValueError("offload_param.device=nvme requires "
                                 "offload_param.nvme_path")
        self.host_optimizer = HostOffloadOptimizer(
            model_parameters,
            lr=self._base_lr,
            betas=tuple(params.get("betas", (0.9, 0.999))),
            eps=params.get("eps", 1e-8),
            weight_decay=params.get("weight_decay", 0.0),
            adamw=(otype != "adam"),
            mirror_dtype=mirror,
            nvme_path=nvme,
            aio_cfg=getattr(self.config, "aio", None),
            dp_shard=self._local_dp_shard(),
            init_seed=self.config.seed,
            mirror_nvme_path=mirror_nvme,
            # widen the swap window past the documented 2-buffer bound only
            # when the user explicitly asked for a prefetch budget (the
            # default would otherwise silently 4x host DRAM for big leaves)
            prefetch_numel=(
                self.config.zero_config.prefetch_bucket_size
                if any(k in self.config._raw.get("zero_optimization", {})
                       for k in ("prefetch_bucket_size",
                                 "stage3_prefetch_bucket_size")) else 0))
        self.optimizer = None
        self._client_optimizer = None

        self.master_shardings = self.rules.shardings(
            self.rules.master_specs(model_parameters))
        self.param_shardings = self.rules.shardings(
            self.rules.param_specs(model_parameters))
        self.grad_shardings = self.rules.shardings(
            self.rules.grad_specs(model_parameters))

        # flat-partition plumbing: grads leave the device program as padded
        # flat [padded] arrays sharded over dp (one per leaf), and updated
        # mirrors come back the same way — the reference's reduce-scatter of
        # grads to owner ranks + step-tail all-gather of updated partitions
        # (stage_1_and_2.py:889,1652-1792), here expressed as shardings.
        self._flat_sh = NamedSharding(self.mesh, P("dp"))
        self._off_meta = [(l.padded, l.global_numel, l.shape)
                          for l in self.host_optimizer.leaves]
        self._params_treedef = jax.tree_util.tree_structure(model_parameters)

        if rng is None:
            rng = jax.random.PRNGKey(self.config.seed)
        self._layer_streamer = None
        if op.layer_streaming:
            from .zero.layer_stream import LayerStreamer
            make_spec = getattr(self.module, "stacked_spec", None)
            if make_spec is None:
                raise ValueError(
                    "offload_param.layer_streaming drives the model's "
                    "stacked-trunk structure directly and needs a module "
                    "exposing .stacked_spec(loss_fn) -> StackedPipeSpec "
                    "(models.GPT and models.BertForMaskedLM do; see "
                    "runtime/pipe/spmd.py StackedPipeSpec for the "
                    "prefix/block/suffix contract)")
            if any(v > 1 for v in dict(self.mesh.shape).values()):
                raise ValueError(
                    "offload_param.layer_streaming is the SINGLE-chip "
                    "capacity tier (per-layer host fetches inside the "
                    "program); at mesh sizes > 1 use ZeRO-3 sharding for "
                    "capacity instead")
            self._layer_streamer = LayerStreamer(
                self.host_optimizer, make_spec(self.loss_fn),
                self.compute_dtype)
            # no full device params, no device grad accumulator: between
            # steps HBM holds nothing of the model (the capacity tier)
            self.state = {"params": None, "acc": None, "rng": rng}
        else:
            dev_params = self._offload_restore_params()
            zeros = jax.jit(
                lambda t: jax.tree.map(
                    lambda p: jnp.zeros(p.shape, jnp.float32), t),
                out_shardings=self.grad_shardings)(dev_params)
            self.state = {
                "params": dev_params if self._params_resident else None,
                "acc": zeros, "rng": rng}
        self._off_state_shardings = {
            "acc": self.grad_shardings,
            "rng": NamedSharding(self.mesh, P()),
        }
        # host-side loss-scale bookkeeping (fp16 only)
        self._host_scale = (self.config.fp16.loss_scale
                            if (self.fp16_enabled and
                                self.config.fp16.loss_scale > 0)
                            else 2.0 ** self.config.fp16.initial_scale_power
                            if self.fp16_enabled else 1.0)
        self._host_hysteresis = self.config.fp16.hysteresis
        self._host_scale_step = 0
        self._host_last_overflow = -1
        log_dist(
            f"ZeRO-Offload ready: {self.host_optimizer.numel():,}/"
            f"{self.host_optimizer.global_numel():,} params on this host "
            f"({self.offload_device}, dp_shard={self.host_optimizer.dp_shard})"
            f", native={self.host_optimizer.native}",
            ranks=[0])

    def _local_dp_shard(self):
        """(rank_start, rank_count, dp_world): which contiguous dp-rank range
        this process's addressable devices cover. Single-process: all of it."""
        dp = self.dp_world_size
        if jax.process_count() == 1:
            return (0, dp, dp)
        devs = self.mesh.devices  # [dp, pp, ep, sp, tp]
        me = jax.process_index()
        mine = sorted(i for i in range(devs.shape[0])
                      if any(d.process_index == me for d in devs[i].flat))
        if not mine or mine != list(range(mine[0], mine[-1] + 1)):
            raise RuntimeError(
                f"process {me}'s devices do not cover a contiguous dp range "
                f"({mine}); offload partitioning needs dp-major device order")
        return (mine[0], len(mine), dp)

    def _offload_restore_params(self):
        """Updated mirror shards -> device params: each host contributes its
        dp-shard of every flat leaf; the compiled unflatten re-gathers to the
        param sharding (the step-tail all-gather)."""
        # leaf-at-a-time: each mirror shard is shipped to device before the
        # next is read, so with the NVMe param tier host DRAM holds one
        # leaf's mirror at a time
        flats = [jax.make_array_from_process_local_data(self._flat_sh, s)
                 for s in (l.mirror_flat()
                           for l in self.host_optimizer.leaves)]
        if not hasattr(self, "_jit_unflatten_params"):
            meta, treedef = self._off_meta, self._params_treedef
            def unflat(flats):
                leaves = [f[:n].reshape(shape)
                          for f, (_p, n, shape) in zip(flats, meta)]
                return jax.tree_util.tree_unflatten(treedef, leaves)
            self._jit_unflatten_params = jax.jit(
                unflat, out_shardings=self.param_shardings)
        return self._jit_unflatten_params(flats)

    def _build_offload_jit(self):
        gas = self.gradient_accumulation_steps()

        def train_grads(params, state, batches, scale):
            def body(carry, batch):
                acc, loss_sum, rng = carry
                rng, sub = jax.random.split(rng)

                def scaled_loss(p):
                    loss = self._loss_of(p, batch, sub)
                    return loss.astype(jnp.float32) * scale, loss

                (_, loss), grads = jax.value_and_grad(
                    scaled_loss, has_aux=True)(params)
                grads = _cast_tree(grads, jnp.float32)
                acc = jax.tree.map(jnp.add, acc, grads)
                acc = jax.lax.with_sharding_constraint(acc, self.grad_shardings)
                return (acc, loss_sum + loss.astype(jnp.float32), rng), None

            (acc, loss_sum, rng), _ = jax.lax.scan(
                body, (state["acc"], jnp.zeros((), jnp.float32),
                       state["rng"]), batches)
            denom = scale * gas
            grads = jax.tree.map(lambda a: a / denom, acc)
            finite = grads_finite(grads) if self.fp16_enabled else jnp.asarray(True)
            gnorm = _global_norm(grads)
            zeros = jax.tree.map(jnp.zeros_like, acc)
            new_state = dict(state, acc=zeros, rng=rng)
            # flatten+pad each leaf and constrain to the dp sharding: XLA
            # reduce-scatters here, so each host's D2H copies only its shard
            flats = [
                jax.lax.with_sharding_constraint(
                    jnp.pad(g.reshape(-1), (0, padded - n)), self._flat_sh)
                for g, (padded, n, _shape) in zip(
                    jax.tree_util.tree_leaves(grads), self._off_meta)]
            # params are donated AND returned: XLA aliases them through, so
            # keeping them (resident mode, overflow-skip steps) costs no
            # transfer, while dropping the returned tree (param tier) frees
            # the HBM the moment the host releases the reference
            return new_state, flats, {"loss": loss_sum / gas,
                                      "grad_norm": gnorm,
                                      "finite": finite}, params

        out_sh = (self._off_state_shardings,
                  [self._flat_sh] * len(self._off_meta),
                  None, self.param_shardings)
        if getattr(self, "_ckpt_offload", False):
            # same XLA quirk as _jit_state_step: explicit out_shardings +
            # host-offload placement custom-calls -> SPMD partitioner
            # RET_CHECK; constrain inside the program instead
            def constrained(state, params, *args, **kwargs):
                new_state, flats, aux, out_params = train_grads(
                    state, params, *args, **kwargs)
                new_state = jax.lax.with_sharding_constraint(
                    new_state, self._off_state_shardings)
                flats = [jax.lax.with_sharding_constraint(f, self._flat_sh)
                         for f in flats]
                out_params = jax.lax.with_sharding_constraint(
                    out_params, self.param_shardings)
                return new_state, flats, aux, out_params
            return jax.jit(constrained, donate_argnums=(0, 1))
        return jax.jit(train_grads, donate_argnums=(0, 1), out_shardings=out_sh)

    def _host_update_scale(self, finite: bool):
        """Host mirror of fp16/loss_scaler.update_scale dynamics — same
        hysteresis (consecutive overflows within the hysteresis budget do
        not shrink again) and same clean-window growth."""
        if not (self.fp16_enabled and self.dynamic_loss_scale):
            return
        self._host_scale_step += 1
        step = self._host_scale_step
        window = self.config.fp16.loss_scale_window
        if finite:
            since = step - self._host_last_overflow
            if since >= window and since % window == 0:
                self._host_scale *= 2.0
                # only the clean-window growth path restores the budget:
                # under sustained overflow the scale then halves every step
                # (reference DynamicLossScaler leaves cur_hysteresis at 1
                # after the first shrink — fast descent from a bad scale)
                self._host_hysteresis = self.config.fp16.hysteresis
        else:
            if self._host_hysteresis <= 1:
                self._host_scale = max(self._host_scale / 2.0,
                                       self.config.fp16.min_loss_scale)
            else:
                self._host_hysteresis -= 1
            self._host_last_overflow = step

    def _streamed_train_batch(self, batches):
        """Layer-streamed capacity tier (runtime/zero/layer_stream.py):
        one jitted program fetches block params per layer and emits block
        grads per layer via callbacks; the host steps every leaf."""
        from .zero.layer_stream import build_streamed_step
        st = self._layer_streamer
        gas = self.gradient_accumulation_steps()
        if self._jit_train is None:
            self._jit_train = build_streamed_step(st, gas)
        scale = jnp.asarray(self._host_scale, jnp.float32)
        res = jax.tree.map(
            lambda a: jnp.asarray(a), st.resident_host_tree())
        st.reset_grads()
        flats, metrics = self._jit_train(res, batches, scale)
        # ordered emit callbacks are effects of the program: force them to
        # completion before reading the host buffers
        flats = jax.device_get(flats)
        jax.effects_barrier()
        finite = bool(jax.device_get(metrics["finite"]))
        denom = float(self._host_scale) * gas
        res_sq = float(jax.device_get(metrics["res_sq"]))
        gnorm = float(np.sqrt(res_sq + st.blocks_grad_sq())) / denom
        if finite:
            clip = self.gradient_clipping()
            combined = denom
            if clip and clip > 0 and gnorm > clip:
                combined *= gnorm / clip
            resident_flats = {}
            for li, g in zip(st.resident_idx, flats):
                leaf = self.host_optimizer.leaves[li]
                pad = np.zeros(leaf.numel, np.float32)
                pad[:leaf.global_numel] = np.asarray(g, np.float32)
                resident_flats[li] = pad
            self.host_optimizer.step(st.grads_flat_all(resident_flats),
                                     lr=self.get_lr()[0],
                                     combined_scale=combined)
        else:
            self.skipped_steps += 1
        self._host_update_scale(finite)
        self._last_grad_norm = gnorm
        return {"loss": metrics["loss"], "grad_norm": gnorm,
                "finite": finite}

    def _offload_train_batch(self, batches):
        if self._layer_streamer is not None:
            return self._streamed_train_batch(batches)
        if self._jit_train is None:
            self._jit_train = self._build_offload_jit()
        scale = jnp.asarray(self._host_scale, jnp.float32)
        params = self.state["params"]
        if params is None:   # offload_param tier: upload from mirrors
            params = self._offload_restore_params()
        self.state["params"] = None   # donated below either way
        sub = {"acc": self.state["acc"], "rng": self.state["rng"]}
        sub, flats, metrics, params_out = self._jit_train(
            params, sub, batches, scale)
        self.state.update(sub)
        finite = bool(jax.device_get(metrics["finite"]))
        gnorm = float(jax.device_get(metrics["grad_norm"]))
        if finite:
            clip = self.gradient_clipping()
            combined = 1.0
            if clip and clip > 0 and gnorm > clip:
                combined = gnorm / clip       # divide grads by this
            lr = self.get_lr()[0]
            # overlap: start ALL D2H copies now; the host step of leaf i
            # then only waits on leaf i while later leaves keep streaming
            # (the aio double-buffer discipline applied to the host hop;
            # reference async_accumulate_grad_in_cpu_via_gpu,
            # stage_1_and_2.py:1014)
            for f in flats:
                f.copy_to_host_async()
            if jax.process_count() > 1:
                # lazy: each leaf's shard assembly (the blocking host copy)
                # happens inside the step loop when THAT leaf is stepped, so
                # leaf i's CPU-Adam overlaps leaf i+1's D2H stream instead
                # of waiting for the full gradient volume up front
                grads_local = [_LazyLocalShard(f) for f in flats]
            else:
                grads_local = flats  # np.asarray per leaf inside the step
            self.host_optimizer.step(grads_local, lr=lr,
                                     combined_scale=combined)
            if self._params_resident:
                self.state["params"] = self._offload_restore_params()
        else:
            self.skipped_steps += 1
            if self._params_resident:
                # mirrors unchanged; the donated params were aliased through
                # the jit, so keeping them costs nothing
                self.state["params"] = params_out
        self._host_update_scale(finite)
        self._last_grad_norm = gnorm
        return metrics

    @staticmethod
    def _extract_local_shard(f):
        """Assemble this process's contiguous slice of a dp-sharded flat
        array from its addressable shards (no cross-host gather). Shards are
        deduplicated by global index: with tp/pp/ep axes > 1 the dp slice is
        replicated across this process's other local devices and would
        otherwise be concatenated k times."""
        uniq = {}
        for s in f.addressable_shards:
            start = s.index[0].start or 0
            if start not in uniq:
                uniq[start] = s
        return np.concatenate([np.asarray(uniq[k].data).reshape(-1)
                               for k in sorted(uniq)])

    @property
    def _offload_loss_scale(self):
        return self._host_scale
