"""prefill_pad_ratio.batch (ratio): padded over true prompt tokens of the
window's prefill calls — ServingMetrics' prefill_padded_tokens (n x bucket a
call) over prefill_prompt_tokens: what the bucket ladder makes the prefill
programs compute beside the prompts themselves (1.0 = no padding)."""


def read(trace, spans, counters, cell):
    window = counters.get("window", {})
    prompt = window.get("prefill_prompt_tokens")
    padded = window.get("prefill_padded_tokens")
    if not prompt or padded is None:
        return None
    return padded / prompt
