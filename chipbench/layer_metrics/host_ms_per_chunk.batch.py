"""host_ms_per_chunk.batch (ms): the front end driver's own time per retired
decode chunk — telemetry span frontend/drive (an iteration of the driver that
had work) less the two device waits inside it, serve/chunk_host_wait and
serve/prefill_wait, totals over the window, over the count of
serve/chunk_retire."""


def read(trace, spans, counters, cell):
    drive = spans.get("frontend/drive")
    retire = spans.get("serve/chunk_retire")
    if not drive or not retire or retire["count"] <= 0:
        return None
    waits = sum(spans[k]["total_s"] for k in ("serve/chunk_host_wait",
                                              "serve/prefill_wait")
                if k in spans)
    return 1e3 * (drive["total_s"] - waits) / retire["count"]
