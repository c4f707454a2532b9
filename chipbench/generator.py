"""The one traffic generator: reads a mix from ``chipbench/traffic/<mix>.json``
and a seed, and gives the requests of a run.

Every seed gets the same sizes and the same arrival gaps, in another order.
Sizes are drawn in blocks of ``block`` requests: a block holds the values of
the distribution at the ``block`` evenly spaced quantiles ``(i + 0.5) /
block``, and the seed shuffles each block (prompt and output lengths
independently) and draws the prompt tokens.  A block is sized to the
requests one window takes, so two seeds differ in which request is long and
which short, not in how much work a window holds.

A mix is a JSON object:

``arrival``
    ``{"process": "backlog", "requests": n}``: ``n`` requests all due at
    t = 0; or ``{"process": "poisson", "rate_per_s": r}``: open-loop
    arrivals at ``r`` per second, ``round(r * seconds)`` of them, their gaps
    the stratified quantiles of the exponential distribution in an order
    drawn from the seed.
``prompt_tokens``, ``output_tokens``
    ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``.
``block``
    the number of requests whose sizes form one stratified block: what a
    window serves of a backlog.  Left out, every request of the run is in
    one block, as a Poisson mix wants: its window holds them all.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    rid: str
    prompt: np.ndarray  # (P,) int32, ids in [1, vocab)
    max_new_tokens: int
    due_s: float  # seconds after the window opens


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one use of the seed (any whole number)."""
    return np.random.default_rng([seed % 2**64, stream])


def quantile_block(dist: Dict, block: int) -> np.ndarray:
    """The ``block`` stratified values of one length distribution."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = [NormalDist().inv_cdf((i + 0.5) / block) for i in range(block)]
    vals = np.exp(math.log(dist["median"]) + dist["sigma"] * np.asarray(z))
    return np.clip(np.rint(vals), dist["min"], dist["max"]).astype(np.int64)


def gap_block(rate: float, block: int) -> np.ndarray:
    """The ``block`` stratified gaps of a Poisson process at ``rate``/s."""
    q = (np.arange(block) + 0.5) / block
    return -np.log1p(-q) / rate


def _shuffled(block_vals: np.ndarray, n: int,
              rng: np.random.Generator) -> np.ndarray:
    blocks = -(-n // len(block_vals))
    return np.concatenate([rng.permutation(block_vals)
                           for _ in range(blocks)])[:n]


def make_requests(mix: Dict, *, vocab: int, seed: int,
                  seconds: float) -> List[Arrival]:
    """The requests of one run of ``mix``, in due order."""
    arrival = mix["arrival"]
    rng = rng_for(seed, 0)
    if arrival["process"] == "backlog":
        n = int(arrival["requests"])
        due = np.zeros(n)
    elif arrival["process"] == "poisson":
        # the window's expected count of arrivals, as one stratified block of
        # gaps: every seed gets the same count and the same gaps
        rate = float(arrival["rate_per_s"])
        n = max(int(round(rate * seconds)), 1)
        g = rng.permutation(gap_block(rate, n))
        due = np.concatenate([[0.0], np.cumsum(g)[:-1]])
        due = due[due < seconds]
        n = len(due)
    else:
        raise ValueError(f"unknown arrival process {arrival['process']!r}")
    block = int(mix.get("block", n))
    plens = _shuffled(quantile_block(mix["prompt_tokens"], block), n, rng)
    outs = _shuffled(quantile_block(mix["output_tokens"], block), n, rng)
    tok_rng = rng_for(seed, 1)
    return [Arrival(f"r{i}", tok_rng.integers(1, vocab, int(plens[i]),
                                              dtype=np.int32),
                    int(outs[i]), float(due[i]))
            for i in range(n)]
