"""prefill_share.longdoc (%): device time of the prefill programs in the
traced stretch (XLA modules named jit_prefill) over the device's busy time in
it: what the long prompts take of the chip beside the decode steps."""


def read(trace, spans, counters, cell):
    if trace is None or trace.busy_s <= 0:
        return None
    secs, count = trace.module_time("jit_prefill")
    if count == 0:
        return None
    return 100.0 * secs / trace.busy_s
