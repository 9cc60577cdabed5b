"""decode_step_ms.window.batch (ms): device time of one decode step over the
WHOLE measured window, from the program's own timeline — telemetry span
serve/device_decode_chunk (the interval between two syncs that both had to
wait: the decode-chunk program and the small programs that rode with it, the
insert of a prefilled batch and the lane patch; starved time taken off),
total seconds over count x decode_chunk. decode_step_ms.batch is the same
program's time in the profiler's trace of the 4 s stretch."""


def read(trace, spans, counters, cell):
    chunks = spans.get("serve/device_decode_chunk")
    steps = counters.get("decode_chunk")
    if not chunks or chunks["count"] <= 0 or not steps:
        return None
    return 1e3 * chunks["total_s"] / (chunks["count"] * steps)
