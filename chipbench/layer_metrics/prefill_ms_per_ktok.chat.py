"""prefill_ms_per_ktok.chat (ms): device time of the prefill programs in the
traced stretch (XLA modules named jit_prefill) over thousands of PADDED
prompt tokens prefilled in it (ServingMetrics' count over the same stretch)."""


def read(trace, spans, counters, cell):
    if trace is None:
        return None
    secs, count = trace.module_time("jit_prefill")
    padded = counters.get("traced", {}).get("prefill_padded_tokens", 0)
    if count == 0 or padded <= 0:
        return None
    return 1e3 * secs / (padded / 1e3)
