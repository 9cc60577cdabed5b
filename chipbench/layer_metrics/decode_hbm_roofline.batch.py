"""decode_hbm_roofline.batch (%): the least time one decode step could take —
the bytes it MUST read (matmul weights + LIVE keys and values, from shapes,
chipbench/flops.py) at the chip's HBM bandwidth — over the step's device time
in the trace. Memory-bound: 8 tokens of compute are far under the bytes.
Both from the SAME traced stretch: the step's time from the device trace,
the live positions from what the client held while it was traced."""

from chipbench import flops
from chipbench.readers import decode_step_ms


def read(trace, spans, counters, cell):
    step_ms = decode_step_ms(trace, spans, counters, cell)
    live = counters.get("kv_live_mean_traced")
    if step_ms is None or live is None or counters["peaks"] is None:
        return None
    least = flops.decode_step_bytes(cell["config"], live) \
        / counters["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / (step_ms / 1e3)
