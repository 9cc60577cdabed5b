"""One general traffic generator, driven by a mix's parameters.

Every seed gets the SAME request sizes and the SAME inter-arrival gaps in the
SAME order: sizes are the quantiles of the mix's length distributions (no
sampling noise), gaps the quantiles of the arrival process, and the mix fixes
ONE shuffled sequence of (gap, prompt length, output length) triples.
``--seed`` draws the token ids (and, in the drivers, the weights), which no
path of the server times differently: nothing is shared and no answer ends
early. So what differs between two runs is the system, not the luck of the
draw. Why not another order per seed: on the chip (PR 23) two runs of one
seed agreed to 0.1-0.7 % while another order per seed, even a rotation of
the one cycle, moved a closed loop's tokens per second by 2 % and an open
loop's p95 of time to first token by 12 % — how requests fall into lanes
and prefill groups IS the work. New sizes or another order are a new mix.

A length distribution is ``{"dist": "lognormal", "median": m, "sigma": s,
"min": lo, "max": hi}`` (clipped) or ``{"dist": "fixed", "value": v}``.
Arrivals are ``{"process": "poisson", "rate_per_s": r}`` (exponential gaps).
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np


@dataclasses.dataclass
class PlannedRequest:
    index: int
    due_s: float            # offset from the window's start (0 in a closed loop)
    prompt: np.ndarray      # int32 token ids
    max_new_tokens: int


def _mid_quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def length_quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of ``dist``, ascending."""
    kind = dist["dist"]
    if kind == "fixed":
        return np.full(n, int(dist["value"]), np.int64)
    if kind == "lognormal":
        mu = math.log(dist["median"])
        z = np.asarray([NormalDist().inv_cdf(u) for u in _mid_quantiles(n)])
        raw = np.exp(mu + dist["sigma"] * z)
        return np.clip(np.rint(raw), dist["min"], dist["max"]).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r}")


def gap_quantiles(arrivals: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` inter-arrival gaps (seconds) at the mid-quantiles of the
    arrival process, rescaled so that they sum to exactly ``n / rate``."""
    rate = float(arrivals["rate_per_s"])
    u = _mid_quantiles(n)
    process = arrivals.get("process", "poisson")
    if process != "poisson":
        raise ValueError(f"unknown arrival process {process!r}")
    gaps = -np.log1p(-u)
    return gaps * (n / rate) / gaps.sum()


def _cycle(mix: Dict[str, Any], n: int, gaps=None):
    """The mix's one shuffled sequence of sizes (and gaps): the shuffle is
    the mix's, not the seed's."""
    base = np.random.default_rng([0x5EED, n])
    prompts = base.permutation(length_quantiles(mix["prompt_len"], n))
    outputs = base.permutation(length_quantiles(mix["output_len"], n))
    return prompts, outputs, (base.permutation(gaps) if gaps is not None
                              else None)


def _requests(prompts, outputs, dues, vocab: int,
              rng: np.random.Generator) -> List[PlannedRequest]:
    return [PlannedRequest(i, float(d),
                           rng.integers(0, vocab, (int(p),)).astype(np.int32),
                           int(o))
            for i, (p, o, d) in enumerate(zip(prompts, outputs, dues))]


def open_loop_plan(mix: Dict[str, Any], seed: int, seconds: float,
                   vocab: int) -> List[PlannedRequest]:
    """Requests due in ``[0, seconds)``: ``round(rate x seconds)`` sizes and
    gaps in the mix's order. Due times are the running sum of the gaps, so
    arrival i is due at a fixed instant whether or not earlier ones finished
    (open loop)."""
    rng = np.random.default_rng([int(seed), 0x0A11])
    n = max(1, int(round(float(mix["arrivals"]["rate_per_s"]) * seconds)))
    prompts, outputs, gaps = _cycle(mix, n,
                                    gap_quantiles(mix["arrivals"], n))
    dues = np.cumsum(gaps) - gaps[0] * 0.5
    keep = dues < seconds
    return _requests(prompts[keep], outputs[keep], dues[keep], vocab, rng)


def closed_loop_plan(mix: Dict[str, Any], seed: int, vocab: int
                     ) -> List[PlannedRequest]:
    """``mix["population"]`` requests in the order the clients will take
    them; a closed loop has no due times (a client sends its next request
    when its last one ends)."""
    rng = np.random.default_rng([int(seed), 0xC105])
    n = int(mix["population"])
    prompts, outputs, _ = _cycle(mix, n)
    return _requests(prompts, outputs, np.zeros(n), vocab, rng)


def train_dataset(seed: int, vocab: int, seq_len: int):
    """An endless dataset of seeded token rows for the trainer's loader:
    sample ``i`` is a pure function of (seed, i), so every step sees a fresh
    batch and the same seed sees the same batches."""

    class SeededTokens:
        def __len__(self):
            return 1 << 20      # the loader indexes np.arange(len)

        def __getitem__(self, i):
            rng = np.random.default_rng([int(seed), 0x7A1, int(i)])
            return {"input_ids": rng.integers(
                0, vocab, (seq_len,), dtype=np.int32)}

    return SeededTokens()
