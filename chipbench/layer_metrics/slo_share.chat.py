"""slo_share.chat (%): requests due in the window that met both limits of the
mix (first token within limits.ttft_ms of the due time, limits.tpot_ms a
token), on the client's clock. For reading only."""


def read(trace, spans, counters, cell):
    return counters.get("slo_share")
