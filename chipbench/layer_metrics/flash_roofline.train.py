"""flash_roofline.train (%): the least time the chip could take for the
FLOPs the three flash kernels performed in the traced steps (compute-bound:
FLOPs over the bf16 peak), over their summed device time. FLOPs per call
from shapes (chipbench/flops.py): causal forward = 1 unit, bwd_dq 1.5,
bwd_dkv 2; calls counted in the trace itself, so a forward that remat runs
twice is counted twice — it is work the kernel did."""

from chipbench import flops

UNITS = {"flash_attention_fwd": 1.0, "flash_attention_bwd_dq": 1.5,
         "flash_attention_bwd_dkv": 2.0}


def read(trace, spans, counters, cell):
    if trace is None or counters["peaks"] is None:
        return None
    per_call = flops.attention_flops_fwd(cell["config"], counters["seq_len"]) \
        * cell["mix"]["micro_batch_per_chip"]
    did = sum(trace.op_counts.get(k, 0) * u for k, u in UNITS.items())
    took = sum(trace.op_seconds.get(k, 0.0) for k in UNITS)
    if did == 0 or took <= 0:
        return None          # the einsum ran, not the kernel: nothing to read
    least = did * per_call / counters["peaks"]["bf16_flops_per_s"]
    return 100.0 * least / took
