"""kv_rows_read_over_live.agent (ratio): ring and global rows the window's
decode steps READ over the rows that were LIVE in their lanes, the program's
counters (deepspeed_tpu/models/afmoe.py::step_counters, summed on the device
inside the chunk program): about 1.0 when a step reads live blocks only, over
2 at this mix's depths when it reads both kinds' leaves of every lane whole
(the masked einsum: grouped heads are refused by the live-rows read). A
program without these counters, as the parent's, reports nothing."""


def read(trace, spans, counters, cell):
    w = counters["window"]
    live = w.get("kv_window_rows_live", 0) + w.get("kv_global_rows_live", 0)
    if not live:
        return None
    return w["kv_rows_read"] / live
