"""mfu.train (%): the benchmark's own FLOPs per token (forward + backward,
recomputation not counted) x tokens per second of the measured window, over
chips x the bf16 peak in peaks.json."""

from chipbench import flops


def read(trace, spans, counters, cell):
    if counters["peaks"] is None:
        return None
    per_token = flops.train_flops_per_token(cell["config"],
                                            counters["seq_len"])
    peak = counters["chips"] * counters["peaks"]["bf16_flops_per_s"]
    return 100.0 * counters["train_tokens_per_s"] * per_token / peak
