"""kv_live_share.batch (%): live KV positions over arena positions
(max_batch x max_seq_len). Live positions are what the client itself holds:
prompt length plus tokens received of every request in flight that has its
first token, sampled each time tokens arrive, mean over the window."""


def read(trace, spans, counters, cell):
    live = counters.get("kv_live_mean")
    if live is None:
        return None
    return 100.0 * live / counters["arena_positions"]
