"""decode_hbm_roofline.reason (%): the least time one decode step of an
expert model could take at the chip's HBM bandwidth over the step's device
time in the trace. The bytes a step MUST read, from shapes and counters
(the configuration's arch file, chipbench/archs/): every matmul weight
outside the routed experts, the routed experts a token of the step TOUCHED
(the program's counter, mean over the traced stretch's steps and expert
layers: a layer that skips idle experts cannot read over 100 % for it) and
the LIVE latent rows (what the client held while it was traced). All three
from the SAME traced stretch."""

import importlib

from chipbench.readers import decode_step_ms


def read(trace, spans, counters, cell):
    step_ms = decode_step_ms(trace, spans, counters, cell)
    live = counters.get("kv_live_mean_traced")
    t = counters.get("traced") or {}
    steps = t.get("moe_decode_steps")
    if step_ms is None or live is None or not steps \
            or counters["peaks"] is None:
        return None
    arch = importlib.import_module(
        f"chipbench.archs.{cell['config']['arch']}")
    least = arch.decode_step_bytes(
        cell["config"], live, t["moe_decode_experts_touched"] / steps) \
        / counters["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (step_ms / 1e3)
