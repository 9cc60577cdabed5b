"""decode_hbm_roofline.agent (%): the least time one decode step of a model
with sliding-window layers beside global ones and every expert on the chip
could take at the chip's HBM bandwidth, over the step's device time in the
trace. The bytes a step MUST read, from shapes and counters (the
configuration's arch file, chipbench/archs/): every matmul weight outside
the routed experts, the routed experts a token of the step TOUCHED (the
program's counter, mean over the traced stretch's steps and expert layers)
and the rows of both kinds of leaf that were LIVE in the step's lanes (the
program's counters ``kv_window_rows_live`` and ``kv_global_rows_live``,
summed on the device inside the chunk program from each live lane's position
over the layers of each kind; mean over the traced stretch's steps). Counted
from touched experts and live rows, so neither skipping idle experts nor a
read of whole leaves can read over 100 %. All from the SAME traced stretch.
A program without these counters, as the parent's, reports nothing."""

import importlib

from chipbench.readers import decode_step_ms


def read(trace, spans, counters, cell):
    step_ms = decode_step_ms(trace, spans, counters, cell)
    t = counters.get("traced") or {}
    steps = t.get("chunks", 0) * counters.get("decode_chunk", 0)
    routed = t.get("moe_decode_steps")
    if step_ms is None or not steps or not routed \
            or "kv_window_rows_live" not in t \
            or counters.get("peaks") is None:
        return None
    arch = importlib.import_module(
        f"chipbench.archs.{cell['config']['arch']}")
    least = arch.decode_step_bytes(
        cell["config"], t["kv_window_rows_live"] / steps,
        t["kv_global_rows_live"] / steps,
        t["moe_decode_experts_touched"] / routed) \
        / counters["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (step_ms / 1e3)
