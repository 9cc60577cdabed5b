"""prefill_ms_per_ktok.window.batch (ms): device milliseconds a thousand
PADDED prompt tokens cost over the whole measured window — telemetry span
serve/device_prefill, total seconds, over ServingMetrics'
prefill_padded_tokens in the window (every call's n x bucket; a call whose
interval a late stamp gave up is in the tokens and not in the seconds, so
read it beside the count of serve/device_stamp_late)."""


def read(trace, spans, counters, cell):
    prefill = spans.get("serve/device_prefill")
    padded = counters.get("window", {}).get("prefill_padded_tokens")
    if not prefill or not padded:
        return None
    return 1e3 * prefill["total_s"] / (padded / 1000.0)
