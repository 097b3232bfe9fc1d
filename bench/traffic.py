"""The one request generator: a traffic mix's data file in, requests out.

A mix file (``bench/traffic/<name>.json``) gives the shape of the traffic:
lognormal prompt and output lengths with their clips, the sampling
settings, and the share of greedy requests. The cell file
(``bench/cells/<cell>.json``) gives the fixed arrival rate. Arrivals are
open loop: each request is due at a time fixed before the run starts,
whether or not earlier requests have finished.

Every seed gets the same *set* of inter-arrival gaps, prompt lengths and
output lengths, drawn at evenly spaced quantiles of their distributions,
in an order of its own. So seeds change which prompt meets which output
and when bursts come, but never the total work in the window: run-to-run
spread measures the system, not the dice.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

_NORMAL = NormalDist()


@dataclasses.dataclass
class RequestSpec:
    """One generated request, in the benchmark's own terms."""

    rid: int
    arrival_s: float            # due time, from the window's start
    prompt: np.ndarray          # (prompt_len,) int32 token ids
    max_new_tokens: int
    temperature: float
    top_p: float

    @property
    def greedy(self) -> bool:
        """Whether the request decodes greedily (its tokens are checked)."""
        return self.temperature == 0.0

    @property
    def total_len(self) -> int:
        """Prompt plus output positions."""
        return len(self.prompt) + self.max_new_tokens


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int,
                        hi: int) -> np.ndarray:
    """``n`` lengths at the quantiles (k + 1/2)/n of a lognormal with the
    given median and sigma, rounded and clipped to [lo, hi], ascending."""
    z = np.array([_NORMAL.inv_cdf((k + 0.5) / n) for k in range(n)])
    return np.clip(np.round(median * np.exp(sigma * z)), lo, hi).astype(
        np.int64)


def exponential_gaps(n: int, rate: float) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles (k + 1/2)/n of an
    exponential distribution of the given rate, ascending."""
    return np.array([-math.log(1.0 - (k + 0.5) / n) / rate
                     for k in range(n)])


def generate(mix: dict, rate_per_s: float, seconds: float, seed: int,
             vocab_size: int) -> List[RequestSpec]:
    """Requests due over ``[0, seconds)`` at ``rate_per_s`` on average.

    ``mix`` holds ``prompt_tokens`` and ``output_tokens`` (each with
    ``median``, ``sigma``, ``min``, ``max``), ``sampling`` (``temperature``,
    ``top_p``) and ``greedy_share``. The request with the most positions
    is always greedy, so the correctness sample can hold the longest
    request; the other greedy requests are chosen by the seed."""
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError(f"rate {rate_per_s}/s over {seconds} s is empty")
    n = max(1, int(round(rate_per_s * seconds)))
    rng = np.random.default_rng(seed)
    pt, ot = mix["prompt_tokens"], mix["output_tokens"]
    prompts = rng.permutation(lognormal_quantiles(
        n, pt["median"], pt["sigma"], pt["min"], pt["max"]))
    outputs = rng.permutation(lognormal_quantiles(
        n, ot["median"], ot["sigma"], ot["min"], ot["max"]))
    # n - 1 gaps between n requests, the first due at 0; scaled (by the
    # same factor for every seed) so the mean rate is the cell's and the
    # last request is due before the window closes.
    gaps = exponential_gaps(max(n - 1, 1), rate_per_s)[:n - 1]
    if n > 1:
        gaps *= seconds * (n - 1) / n / gaps.sum()
    due = np.concatenate([[0.0], np.cumsum(rng.permutation(gaps))])
    n_greedy = min(n, max(1, int(round(mix["greedy_share"] * n))))
    longest = int(np.argmax(prompts + outputs))
    others = rng.permutation([i for i in range(n) if i != longest])
    greedy = {longest, *others[:n_greedy - 1].tolist()}
    samp = mix["sampling"]
    reqs = []
    for i in range(n):
        reqs.append(RequestSpec(
            rid=i, arrival_s=float(due[i]),
            prompt=rng.integers(0, vocab_size, int(prompts[i]),
                                dtype=np.int32),
            max_new_tokens=int(outputs[i]),
            temperature=0.0 if i in greedy else float(samp["temperature"]),
            top_p=1.0 if i in greedy else float(samp["top_p"])))
    return reqs
