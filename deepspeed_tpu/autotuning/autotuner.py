"""Autotuner: measured search over (ZeRO stage, micro-batch, mesh shape).

Reference: ``deepspeed/autotuning/autotuner.py:29`` — its loop is
(1) model-info profile run, (2) memory-model pruning of ZeRO stages,
(3) per-stage micro-batch sweep with short REAL runs harvesting a metric,
(4) emit the best config. The reference launches every experiment as a
separate cluster job through a ResourceManager (autotuning/scheduler.py)
because CUDA state can't be rebuilt in-process; on a TPU VM the XLA client
is re-usable, so experiments run IN-PROCESS — build engine, measure a few
train_batch calls, delete — which also reuses the compilation cache across
micro-batch variants of the same stage.

Search strategies (reference tuner/: GridSearchTuner, RandomTuner,
ModelBasedTuner): grid and random port directly; ``tuner_type="model"``
is the ModelBasedTuner analogue (tuner/model_based_tuner.py:158) with a
ridge regression over (stage, log-micro-batch, mesh) features standing in
for xgboost — after a bootstrap phase it measures candidates best-first by
predicted metric. The closed-form ZeRO memory model in ``memory.py`` does
hard pruning either way.

Isolation (reference autotuning/scheduler.py): ``isolation="process"``
runs every experiment through ``autotuning/runner.py`` in its own child
process with a timeout — compile caches and HBM fragmentation cannot leak
across experiments, and a hard XLA crash (OOM, sigkill) fails only that
point; the tune keeps going and still returns the measured best. A chip
belongs to one process at a time, so in this mode the tuner's own process
stays off JAX: device count and HBM come from a probe child that exits
before the first experiment starts (:meth:`Autotuner._device_facts`).
"""

from __future__ import annotations

import gc
import itertools
import json
import os
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ..utils.logging import log_dist, logger
from .memory import (chip_memory_bytes, max_micro_batch_for_budget,
                     model_states_memory_per_chip)

METRIC_THROUGHPUT = "throughput"     # samples/sec
METRIC_LATENCY = "latency"           # sec/step (lower is better)


@dataclass
class Experiment:
    name: str
    config: Dict[str, Any]
    group: str = ""          # (stage, mesh) family — plateau stops per group
    metric_val: Optional[float] = None
    error: Optional[str] = None

    def as_record(self):
        return {"name": self.name, "config": self.config, "group": self.group,
                "metric_val": self.metric_val, "error": self.error}


@dataclass
class TuningSpace:
    """The explored axes. Values are lists; singletons pin an axis."""
    zero_stages: Sequence[int] = (0, 1, 2, 3)
    micro_batches: Sequence[int] = (1, 2, 4, 8, 16, 32)
    mesh_shapes: Sequence[Dict[str, int]] = field(default_factory=lambda: [{}])
    extra: Dict[str, Sequence] = field(default_factory=dict)


class Autotuner:
    """In-process autotuner.

    Args:
      engine_factory: callable(config_dict) -> engine with .train_batch(it)
        (typically a closure over ds.initialize with the user's model).
      data_factory: callable(micro_batch) -> iterator factory; called per
        step to produce the GAS micro-batch iterator.
      base_config: user config; tuned keys are overridden per experiment.
      num_params: for memory-model pruning (0 disables pruning).
      model_dims: dict(seq_len=, hidden=, layers=) for activation estimates.
    """

    def __init__(self, engine_factory: Optional[Callable[[dict], Any]],
                 data_factory: Optional[Callable[[int], Callable[[], Any]]],
                 base_config: dict, *, num_params: int = 0,
                 model_dims: Optional[dict] = None,
                 metric: str = METRIC_THROUGHPUT,
                 warmup_steps: int = 2, measure_steps: int = 3,
                 results_dir: str = "autotuning_results",
                 tuner_type: str = "gridsearch", max_experiments: int = 64,
                 early_stop_plateau: int = 2, seed: int = 0,
                 isolation: str = "inproc",
                 factory_path: Optional[str] = None,
                 experiment_timeout: float = 900.0,
                 model_bootstrap: int = 4):
        """``isolation="process"`` requires ``factory_path`` ("module:fn",
        importable in the child; fn(config) -> (engine, make_iter)) instead
        of the in-process factories. ``model_bootstrap``: measured points
        before the ``tuner_type="model"`` regressor starts ranking."""
        if isolation not in ("inproc", "process"):
            raise ValueError(f"unknown isolation {isolation!r}")
        if isolation == "process" and not factory_path:
            raise ValueError("isolation='process' requires factory_path")
        if isolation == "inproc" and (engine_factory is None
                                      or data_factory is None):
            raise ValueError(
                "isolation='inproc' requires engine_factory and "
                "data_factory (with factory_path, pass "
                "isolation='process')")
        if tuner_type not in ("gridsearch", "random", "model"):
            raise ValueError(f"unknown tuner_type {tuner_type!r}")
        self.engine_factory = engine_factory
        self.data_factory = data_factory
        self.isolation = isolation
        self.factory_path = factory_path
        self.experiment_timeout = experiment_timeout
        self.model_bootstrap = model_bootstrap
        self.base_config = dict(base_config)
        self.num_params = num_params
        self.model_dims = model_dims or {}
        self.metric = metric
        self.warmup_steps = warmup_steps
        self.measure_steps = measure_steps
        self.results_dir = results_dir
        self.tuner_type = tuner_type
        self.max_experiments = max_experiments
        self.early_stop_plateau = early_stop_plateau
        self.rng = np.random.default_rng(seed)
        self.records: List[Experiment] = []
        self.best: Optional[Experiment] = None
        self._facts = None       # (n_devices, hbm bytes), probed once

    def _device_facts(self):
        """(device count, HBM bytes per chip). In-process experiments share
        this process's backend, so ask it. Under process isolation this
        process must never initialise the chip: a runner child started
        while the parent holds it dies at backend init ("Unable to
        initialize backend 'tpu': ABORTED: Internal error when accessing
        libtpu multi-process lockfile", v5e, PR 21) — so a probe child
        reports and exits first, and a parent that already holds a chip
        is refused with the reason."""
        if self._facts is not None:
            return self._facts
        import jax
        if self.isolation != "process":
            self._facts = (len(jax.devices()), chip_memory_bytes())
            return self._facts
        from jax._src import xla_bridge
        if xla_bridge.backends_are_initialized() and \
                jax.default_backend() != "cpu":
            raise RuntimeError(
                f"isolation='process' needs the chip free for its runner "
                f"children, but this process has already initialised the "
                f"{jax.default_backend()!r} backend and holds it; start the "
                f"tuner from a process that has not touched JAX, or use "
                f"isolation='inproc'")
        from ..launcher.env_report import probe_devices
        probe = probe_devices(timeout=120.0)
        if "error" in probe:
            raise RuntimeError(f"autotuner device probe failed: "
                               f"{probe['error']}")
        # the CPU backend is per-process and reports no memory: asking it
        # here holds nothing a child needs
        hbm = probe["hbm"] if probe["backend"] != "cpu" else \
            chip_memory_bytes()
        self._facts = (len(probe["devices"]), float(hbm))
        return self._facts

    # ---- pruning (the reference's fast mode, autotuner.py:222,261) ---------
    def _stage_fits(self, stage: int, dp: int, mp: int) -> bool:
        if not self.num_params:
            return True
        budget = self._device_facts()[1]
        need = model_states_memory_per_chip(
            self.num_params, zero_stage=stage, dp=dp, mp=mp)
        return need < 0.9 * budget

    def _prune_micro_batches(self, stage, dp, mp, micro_batches):
        if not (self.num_params and self.model_dims):
            return list(micro_batches)
        budget = 0.9 * self._device_facts()[1]
        cap = max_micro_batch_for_budget(
            budget, num_params=self.num_params, zero_stage=stage, dp=dp,
            mp=mp, **self.model_dims)
        kept = [m for m in micro_batches if m <= max(cap, 1)]
        dropped = sorted(set(micro_batches) - set(kept))
        if dropped:
            logger.info(f"autotuner: memory model drops micro-batches "
                        f"{dropped} at stage {stage} (cap {cap})")
        return kept

    # ---- experiment generation --------------------------------------------
    def _experiments(self, space: TuningSpace) -> List[Experiment]:
        n_dev = self._device_facts()[0]
        exps = []
        for mesh in space.mesh_shapes:
            mp = mesh.get("tp", 1) * mesh.get("sp", 1)
            pp = mesh.get("pp", 1)
            dp = n_dev // max(mp * pp * mesh.get("ep", 1), 1)
            for stage in space.zero_stages:
                if not self._stage_fits(stage, dp, mp):
                    logger.info(f"autotuner: stage {stage} pruned by memory "
                                f"model at dp={dp}, mp={mp}")
                    continue
                micros = self._prune_micro_batches(
                    stage, dp, mp, space.micro_batches)
                extra_axes = sorted(space.extra)
                extra_vals = [space.extra[k] for k in extra_axes]
                for micro, *extras in itertools.product(micros, *extra_vals):
                    cfg = json.loads(json.dumps(self.base_config))
                    cfg.setdefault("zero_optimization", {})["stage"] = stage
                    cfg["train_micro_batch_size_per_gpu"] = micro
                    cfg.pop("train_batch_size", None)
                    if mesh:
                        cfg.setdefault("mesh", {}).update(mesh)
                    for k, v in zip(extra_axes, extras):
                        _set_path(cfg, k, v)
                    group = f"z{stage}" + \
                        ("_" + "_".join(f"{a}{b}" for a, b in mesh.items())
                         if mesh else "")
                    name = f"{group}_mbs{micro}" + \
                        "".join(f"_{k.split('.')[-1]}{v}"
                                for k, v in zip(extra_axes, extras))
                    exps.append(Experiment(name=name, config=cfg,
                                           group=group))
        if self.tuner_type == "random":
            order = self.rng.permutation(len(exps))
            exps = [exps[i] for i in order]
        return exps[:self.max_experiments]

    # ---- measurement -------------------------------------------------------
    def _run_experiment(self, exp: Experiment) -> Optional[float]:
        if self.isolation == "process":
            return self._run_subprocess(exp)
        return self._run_inproc(exp)

    def _run_subprocess(self, exp: Experiment) -> Optional[float]:
        """One experiment = one child process through autotuning/runner.py
        (reference scheduler.py job launch): a crash or hang only loses
        this point."""
        with tempfile.NamedTemporaryFile("w", suffix=".json",
                                         delete=False) as fh:
            json.dump(exp.config, fh)
            cfg_path = fh.name
        cmd = [sys.executable, "-m", "deepspeed_tpu.autotuning.runner",
               "--factory", self.factory_path, "--config", cfg_path,
               "--warmup", str(self.warmup_steps),
               "--steps", str(self.measure_steps), "--metric", self.metric]
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=self.experiment_timeout)
        except subprocess.TimeoutExpired:
            raise RuntimeError(
                f"experiment timed out after {self.experiment_timeout:.0f}s")
        finally:
            os.unlink(cfg_path)
        for line in reversed((p.stdout or "").strip().splitlines()):
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict) and "metric_val" in obj:
                return float(obj["metric_val"])
        tail = ((p.stderr or "").strip().splitlines() or ["no output"])[-1]
        raise RuntimeError(f"experiment rc={p.returncode}: {tail[:300]}")

    def _run_inproc(self, exp: Experiment) -> Optional[float]:
        import jax
        engine = None
        try:
            engine = self.engine_factory(exp.config)
            micro = exp.config["train_micro_batch_size_per_gpu"]
            gas = exp.config.get("gradient_accumulation_steps", 1)
            make_iter = self.data_factory(micro)
            loss = None
            for _ in range(self.warmup_steps):
                loss = engine.train_batch(make_iter())
            if loss is not None:
                jax.block_until_ready(loss)    # sync before timing
            t0 = time.perf_counter()
            for _ in range(self.measure_steps):
                loss = engine.train_batch(make_iter())
            jax.block_until_ready(loss)
            dt = (time.perf_counter() - t0) / self.measure_steps
            if self.metric == METRIC_LATENCY:
                return dt
            return engine.train_batch_size() / dt
        finally:
            del engine
            gc.collect()

    def _better(self, a: float, b: float) -> bool:
        return a < b if self.metric == METRIC_LATENCY else a > b

    # ---- cost model (reference tuner/model_based_tuner.py:158) -------------
    @staticmethod
    def _features(exp: Experiment) -> np.ndarray:
        cfg = exp.config
        stage = float(cfg.get("zero_optimization", {}).get("stage", 0))
        micro = float(cfg.get("train_micro_batch_size_per_gpu", 1))
        mesh = cfg.get("mesh", {}) or {}
        lm = np.log2(max(micro, 1.0))
        return np.array([1.0, stage, lm, lm * lm, stage * lm,
                         float(mesh.get("pp", 1)), float(mesh.get("tp", 1)),
                         float(mesh.get("ep", 1))])

    def _fit_predict(self, measured: List[Experiment],
                     candidates: List[Experiment]) -> np.ndarray:
        """Ridge regression metric predictor (xgboost stand-in: the space
        is small and smooth in (stage, log mbs), so a quadratic linear
        model ranks candidates well after a few bootstrap points)."""
        X = np.stack([self._features(e) for e in measured])
        y = np.array([e.metric_val for e in measured])
        lam = 1e-3
        w = np.linalg.solve(X.T @ X + lam * np.eye(X.shape[1]), X.T @ y)
        return np.stack([self._features(e) for e in candidates]) @ w

    def _measure(self, exp: Experiment) -> None:
        """Run + record one experiment (shared by both tune loops)."""
        try:
            exp.metric_val = self._run_experiment(exp)
        except Exception as e:   # OOM / crash / timeout = infeasible point
            exp.error = f"{type(e).__name__}: {e}"
            logger.warning(f"autotuner: {exp.name} failed: {exp.error}")
        self.records.append(exp)
        self._write_record(exp)
        if exp.metric_val is not None:
            if self.best is None or self._better(exp.metric_val,
                                                 self.best.metric_val):
                self.best = exp
            log_dist(f"autotuner: {exp.name} {self.metric}="
                     f"{exp.metric_val:.2f} (best {self.best.name})",
                     ranks=[0])

    def _tune_model_based(self, exps: List[Experiment]) -> Optional[dict]:
        """Bootstrap a few points, then fit-predict-measure best-first;
        stop after `early_stop_plateau` consecutive non-improvements and
        prune the rest by predicted rank."""
        todo = list(exps)
        for exp in todo[:self.model_bootstrap]:
            self._measure(exp)
        todo = todo[self.model_bootstrap:]
        misses = 0
        while todo:
            measured = [r for r in self.records if r.metric_val is not None]
            if len(measured) < 2:     # model unfittable; fall back to order
                pick = todo.pop(0)
            else:
                preds = self._fit_predict(measured, todo)
                order = np.argsort(preds)
                idx = int(order[0 if self.metric == METRIC_LATENCY
                                else -1])
                pick = todo.pop(idx)
            prev_best = self.best.metric_val if self.best else None
            self._measure(pick)
            if pick.metric_val is not None:
                # like the grid loop, only MEASURED regressions count as
                # plateau misses; crashed/OOM points are infeasible-space
                # probes (capped by max_experiments), not evidence the
                # feasible region has stopped improving
                improved = (prev_best is None or
                            self._better(pick.metric_val, prev_best))
                misses = 0 if improved else misses + 1
            if misses >= self.early_stop_plateau:
                for exp in todo:
                    exp.error = "skipped: cost-model prune"
                    self.records.append(exp)
                    self._write_record(exp)
                break
        self._write_summary()
        return self.best.config if self.best else None

    # ---- main loop (reference tune(), autotuner.py:396) ---------------------
    def tune(self, space: Optional[TuningSpace] = None) -> Optional[dict]:
        space = space or TuningSpace()
        exps = self._experiments(space)
        log_dist(f"autotuner: {len(exps)} experiments", ranks=[0])
        os.makedirs(self.results_dir, exist_ok=True)
        if self.tuner_type == "model":
            return self._tune_model_based(exps)
        plateau: Dict[str, int] = {}
        best_in_group: Dict[str, float] = {}
        stopped: set = set()
        for exp in exps:
            if exp.group in stopped:
                # micro-batch sweeps are monotone until the knee; after N
                # consecutive regressions the rest of this (stage, mesh)
                # family is skipped (reference get_plauteu_mbs,
                # autotuner.py:638)
                exp.error = "skipped: plateau early-stop"
                self.records.append(exp)
                self._write_record(exp)
                continue
            self._measure(exp)
            if exp.metric_val is not None:
                # plateau is judged against this (stage, mesh) group's OWN
                # best — a family whose first points trail another group's
                # global best may still be climbing toward its knee
                gb = best_in_group.get(exp.group)
                if gb is None or self._better(exp.metric_val, gb):
                    best_in_group[exp.group] = exp.metric_val
                    plateau[exp.group] = 0
                else:
                    plateau[exp.group] = plateau.get(exp.group, 0) + 1
                if self.tuner_type == "gridsearch" and \
                        plateau[exp.group] >= self.early_stop_plateau:
                    stopped.add(exp.group)
        self._write_summary()
        return self.best.config if self.best else None

    def print_tuning_results(self):
        for r in self.records:
            logger.info(f"  {r.name}: {self.metric}={r.metric_val} "
                        f"{'ERROR ' + r.error if r.error else ''}")
        if self.best:
            logger.info(f"best: {self.best.name} -> {self.best.metric_val}")

    def _write_record(self, exp: Experiment):
        with open(os.path.join(self.results_dir, f"{exp.name}.json"), "w") as f:
            json.dump(exp.as_record(), f, indent=2)

    def _write_summary(self):
        with open(os.path.join(self.results_dir, "summary.json"), "w") as f:
            json.dump({
                "metric": self.metric,
                "best": self.best.as_record() if self.best else None,
                "records": [r.as_record() for r in self.records],
            }, f, indent=2)


def _set_path(cfg: dict, dotted: str, value):
    node = cfg
    parts = dotted.split(".")
    for p in parts[:-1]:
        node = node.setdefault(p, {})
    node[parts[-1]] = value
