"""experts_touched.reason (%): held experts that received a token in a decode
step, over the experts held: the program's counters
(deepspeed_tpu/moe/grouped.py::routing_counters, summed on the device inside
the chunk program over live lanes), mean over the window's decode steps and
expert layers. What a step must read of the expert banks."""


def read(trace, spans, counters, cell):
    w = counters["window"]
    steps = w.get("moe_decode_steps")
    if not steps:
        return None
    return 100.0 * w["moe_decode_experts_touched"] \
        / (steps * cell["config"]["n_routed_experts"])
