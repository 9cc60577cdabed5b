"""device_observed.window.batch (%): how much of the measured window the
program's own device timeline accounts for — telemetry spans
serve/device_decode_chunk + serve/device_prefill, total seconds over the
window. With device_starved.batch it should close on 100; what is missing is
the intervals a late stamp gave up (the host found a program already
finished, so nobody knows when it ended)."""


def read(trace, spans, counters, cell):
    seen = [spans[k] for k in ("serve/device_decode_chunk",
                               "serve/device_prefill") if k in spans]
    window_s = counters.get("window_s")
    if not seen or not window_s:
        return None
    return 100.0 * sum(s["total_s"] for s in seen) / window_s
