"""prefill_share.window.batch (%): what the prefill programs took of the chip
over the WHOLE measured window — telemetry span serve/device_prefill (the
interval from the end of the program before a prefill call to the end of the
call, both read off syncs that had to wait), total seconds over the window.
prefill_share.longdoc reads the same from 4 s of the profiler's trace."""


def read(trace, spans, counters, cell):
    prefill = spans.get("serve/device_prefill")
    window_s = counters.get("window_s")
    if not prefill or not window_s:
        return None
    return 100.0 * prefill["total_s"] / window_s
