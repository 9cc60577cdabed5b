"""Readers that more than one per-layer metric shares. Each metric still has
its own file under ``layer_metrics/``, which names the one it uses."""


def device_idle(trace, spans, counters, cell):
    """(%) 1 - union of device-op intervals over the traced stretch, mean
    over the chips used (jax.profiler trace, chipbench/trace_reduce.py)."""
    if trace is None:
        return None
    return 100.0 * trace.idle_share


def decode_step_ms(trace, spans, counters, cell):
    """(ms) device time of the decode-chunk program in the traced stretch
    (by its XLA module name) over the decode steps in it (programs x
    decode_chunk). None when no such program ran."""
    if trace is None:
        return None
    secs, count = trace.module_time("decode_chunk")
    if count == 0:
        return None
    return 1e3 * secs / (count * counters["decode_chunk"])
