"""host_ms_per_chunk.chat (ms): host time the serve loop spends launching and
retiring a decode chunk — telemetry spans serve/chunk_launch +
serve/chunk_retire, totals over the window, per retired chunk."""


def read(trace, spans, counters, cell):
    launch = spans.get("serve/chunk_launch")
    retire = spans.get("serve/chunk_retire")
    if not launch or not retire or retire["count"] <= 0:
        return None
    return 1e3 * (launch["total_s"] + retire["total_s"]) / retire["count"]
