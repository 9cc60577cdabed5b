"""experts_touched.agent (%): experts that received a token in a decode step
over the experts of a layer, all of them held here (``num_experts``): the
program's counters (deepspeed_tpu/moe/grouped.py::routing_counters, summed on
the device inside the chunk program over live lanes), mean over the window's
decode steps and expert layers. What a step must read of the expert banks,
and how many tiles its loop runs. (``experts_touched.reason`` asks its
configuration for ``n_routed_experts``, the count held of a share.)"""


def read(trace, spans, counters, cell):
    w = counters["window"]
    steps = w.get("moe_decode_steps")
    if not steps:
        return None
    return 100.0 * w["moe_decode_experts_touched"] \
        / (steps * cell["config"]["num_experts"])
