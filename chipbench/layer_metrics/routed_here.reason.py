"""routed_here.reason (%): token-expert pairs that fell on the experts held
here over all pairs the window routed, prefill and decode together (the
program's counters): what the chip's share leaves of the expert work. 6.25 %
when 16 of 256 experts are held and routing is even."""


def read(trace, spans, counters, cell):
    w = counters["window"]
    held = sum(w.get(f"moe_{kind}_pairs_held", 0.0)
               for kind in ("prefill", "decode"))
    absent = sum(w.get(f"moe_{kind}_pairs_absent", 0.0)
                 for kind in ("prefill", "decode"))
    if held + absent <= 0:
        return None
    return 100.0 * held / (held + absent)
