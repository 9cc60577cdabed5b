"""decode_hbm_roofline.longdoc (%): the least time one decode step of a model
that keeps a window beside chunk summaries could take at the chip's HBM
bandwidth, over the step's device time in the trace. The bytes a step MUST
read, from shapes and counters (the configuration's arch file,
chipbench/archs/): every matmul weight, and the rows of both leaves that
were LIVE in the step's lanes (the program's counters
``eva_window_rows_live`` and ``eva_summary_rows_live``, summed on the device
inside the chunk program from each live lane's position; mean over the traced
stretch's steps). A step that reads both leaves whole reads more than that
and cannot read over 100 % for it. All from the SAME traced stretch."""

import importlib

from chipbench.readers import decode_step_ms


def read(trace, spans, counters, cell):
    step_ms = decode_step_ms(trace, spans, counters, cell)
    t = counters.get("traced") or {}
    steps = t.get("chunks", 0) * counters.get("decode_chunk", 0)
    if step_ms is None or not steps or "eva_window_rows_live" not in t \
            or counters.get("peaks") is None:
        return None
    arch = importlib.import_module(
        f"chipbench.archs.{cell['config']['arch']}")
    least = arch.decode_step_bytes(
        cell["config"], t["eva_window_rows_live"] / steps,
        t["eva_summary_rows_live"] / steps) \
        / counters["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (step_ms / 1e3)
