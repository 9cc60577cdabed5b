"""Autotuning (reference: deepspeed/autotuning/autotuner.py): memory-model
pruning + measured in-process sweeps over (ZeRO stage, micro-batch, mesh
shape), emitting the best config."""

from .autotuner import (Autotuner, Experiment, TuningSpace,
                        METRIC_LATENCY, METRIC_THROUGHPUT)
from .memory import (activation_memory_per_chip, chip_memory_bytes,
                     estimate_zero_model_states_mem_needs,
                     max_micro_batch_for_budget,
                     model_states_memory_per_chip)

__all__ = ["Autotuner", "TuningSpace", "Experiment", "METRIC_THROUGHPUT",
           "METRIC_LATENCY", "model_states_memory_per_chip",
           "activation_memory_per_chip", "max_micro_batch_for_budget",
           "estimate_zero_model_states_mem_needs", "chip_memory_bytes"]
