"""Request schedules drawn from a published length distribution, the same
for every seed.

Adapted from ``poisson_arrivals`` of ``repro.launch.traffic`` (exponential
gaps at a fixed rate, shapes from a mix). Here every quantity is a set of
evenly spaced quantiles of its distribution, so each schedule offers the
same load and the same work: prompt and output lengths are log-normal
quantiles rounded up to the serving buckets (a bucketed server pads a
request to the bucket that holds it), and the gaps are exponential
quantiles. The pairing of prompt with output length and the order of the
gaps are fixed by the traffic's own ``order_seed``, never by the run's
seed, which draws only the prompts' tokens and the weights.
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, List, Sequence, Tuple

import numpy as np

__all__ = ["Arrival", "bucketed_lengths", "schedule", "shapes"]

Arrival = Tuple[float, int, int]  # (virtual arrival time, prompt, output)


def bucketed_lengths(spec: Dict, n: int) -> List[int]:
    """``n`` log-normal quantiles (``median``, ``sigma``) at the probabilities
    (k + 0.5) / n, each rounded up to the smallest of ``edges`` that holds
    it; lengths above the last edge are cut to it."""
    edges = sorted(spec["edges"])
    nd = statistics.NormalDist()
    out = []
    for k in range(n):
        x = spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf((k + 0.5) / n))
        out.append(next((e for e in edges if e >= x), edges[-1]))
    return out


def schedule(n: int, rate: float, prompt: Dict, output: Dict,
             order_seed: int) -> List[Arrival]:
    """``n`` arrivals at ``rate`` per virtual time unit."""
    if rate <= 0:
        raise ValueError(f"arrival rate must be positive, got {rate}")
    rng = np.random.default_rng(order_seed)
    prompts = bucketed_lengths(prompt, n)
    outputs = [bucketed_lengths(output, n)[i] for i in rng.permutation(n)]
    gaps = [-math.log(1.0 - (k + 0.5) / n) / rate for k in rng.permutation(n)]
    times = np.cumsum(gaps).tolist()
    order = rng.permutation(n)
    return [(times[k], prompts[i], outputs[i]) for k, i in enumerate(order)]


def shapes(arrivals: Sequence[Arrival]) -> List[Tuple[int, int]]:
    """The distinct (prompt, output) shapes of a schedule, in order."""
    return sorted({(p, g) for _, p, g in arrivals})
