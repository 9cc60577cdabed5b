"""device_starved.batch (%): the share of the measured window in which the
serve loop knew the chip had nothing to run — telemetry spans
serve/starved_after_prefill + serve/starved_after_chunk (from the return of a
host sync that left nothing dispatched to the next dispatch), total seconds
over the window. device_idle.batch is the same thing seen from the device,
in the traced stretch."""


def read(trace, spans, counters, cell):
    starved = [spans[k] for k in ("serve/starved_after_prefill",
                                  "serve/starved_after_chunk") if k in spans]
    window_s = counters.get("window_s")
    if not starved or not window_s:
        return None
    return 100.0 * sum(s["total_s"] for s in starved) / window_s
