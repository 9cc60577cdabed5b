"""state_rows_read_over_live.longdoc (ratio): window and summary rows the
window's decode steps READ over the rows that were LIVE in their lanes, the
program's counters (deepspeed_tpu/models/eva.py::step_counters, summed on the
device inside the chunk program): 1.0 when a step reads live rows only, about
2.6 at this mix's depths when it reads both leaves of every lane whole."""


def read(trace, spans, counters, cell):
    w = counters["window"]
    live = w.get("eva_window_rows_live", 0) + w.get("eva_summary_rows_live", 0)
    if not live:
        return None
    return w["eva_rows_read"] / live
