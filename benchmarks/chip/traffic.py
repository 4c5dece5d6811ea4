"""Traffic for the chip benchmark: one generator driven by a mix file.

A mix file (``traffic/<mix>.json``) gives the length distributions of
prompts and outputs, the arrival kind (``backlog`` or ``poisson`` at a
rate in requests/s) and the size of the request set.  Every seed serves
the same multiset of prompt lengths, output lengths and inter-arrival
gaps: the values are the distribution's quantiles, and the seed only
orders them and draws the token ids.  The order is stratified: the
sorted values are cut into ``STRATA`` strata and each consecutive block
of ``STRATA`` requests takes one value from every stratum, so any prefix
of the queue (which is what a window serves) carries nearly the same
work for every seed.  An open-loop mix sends each request once; sized to
the window, every seed's window serves nearly the same requests.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np

STRATA = 8
# Ids 0 and 1 are the engine's pad and end-of-sequence tokens.
FIRST_TOKEN_ID = 2


@dataclasses.dataclass(frozen=True)
class Request:
    uid: int
    prompt: np.ndarray   # (P,) int32
    max_new: int
    due_s: float         # offset from the window's start (0 for backlog)


def quantile_lengths(spec: dict, n: int) -> List[int]:
    """``n`` lognormal quantiles at (i + 1/2)/n, clipped to [min, max] and
    rounded up to a multiple of ``round_to``."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    step = spec.get("round_to", 1)
    if spec["min"] % step or spec["max"] % step:
        raise ValueError("min and max must be multiples of round_to")
    mu, sigma, nd = math.log(spec["median"]), spec["sigma"], NormalDist()
    out = []
    for i in range(n):
        v = math.exp(mu + sigma * nd.inv_cdf((i + 0.5) / n))
        v = min(max(v, spec["min"]), spec["max"])
        out.append(int(math.ceil(v / step) * step))
    return out


def exponential_gaps(rate: float, n: int) -> List[float]:
    """``n`` quantiles of the exponential inter-arrival gap at ``rate``/s."""
    return [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]


def stratified_order(values, rng) -> list:
    """Sorted ``values`` as blocks of STRATA, one value per stratum each,
    in an order drawn from ``rng``."""
    values = sorted(values)
    if len(values) % STRATA:
        raise ValueError(f"request count must be a multiple of {STRATA}")
    k = len(values) // STRATA
    strata = [rng.permutation(values[s * k:(s + 1) * k]) for s in range(STRATA)]
    out = []
    for j in range(k):
        block = [strata[s][j] for s in range(STRATA)]
        rng.shuffle(block)
        out.extend(block)
    return out


class Traffic:
    """The requests of one mix under one seed, in arrival order."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        n = mix["requests"]
        self.kind = mix["arrival"]["kind"]
        self.vocab = vocab
        self.seed = seed
        self.prompt_lens = stratified_order(
            quantile_lengths(mix["prompt"], n), np.random.default_rng([seed, 1]))
        self.output_lens = stratified_order(
            quantile_lengths(mix["output"], n), np.random.default_rng([seed, 2]))
        if self.kind == "backlog":
            self.due = [0.0] * n
        elif self.kind == "poisson":
            gaps = stratified_order(
                exponential_gaps(mix["arrival"]["rate_per_s"], n),
                np.random.default_rng([seed, 3]))
            self.due = list(np.cumsum([0.0] + gaps[:-1]))
        else:
            raise ValueError(f"unknown arrival kind {self.kind!r}")

    def __len__(self) -> int:
        return len(self.prompt_lens)

    def request(self, i: int) -> Request:
        if i >= len(self):
            raise IndexError(f"the mix holds {len(self)} requests; the run "
                             f"needs more (raise 'requests')")
        p = int(self.prompt_lens[i])
        ids = np.random.default_rng([self.seed, 4, i]).integers(
            FIRST_TOKEN_ID, self.vocab, (p,), dtype=np.int32)
        return Request(uid=i, prompt=ids, max_new=int(self.output_lens[i]),
                       due_s=float(self.due[i]))
