"""The one traffic generator.  A traffic file gives parameters (rates,
length distributions, bursts); this turns them into requests.

Sizes and gaps are the quantiles of each distribution at ``(i + 0.5) / n``,
put in an order drawn from the mix's own ``schedule_seed``: like the head's
``pattern_seed``, the schedule is part of what is deployed, and every run
offers the same work at the same times.  The run's seed draws the token
ids.  (Orders drawn from the run's seed moved the tails by more than two
runs of one seed differ; see PERF.md.)
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np


@dataclasses.dataclass
class Req:
    due: float              # seconds after the window opens
    tokens: np.ndarray      # prompt token ids
    max_new: int            # output tokens (greedy, no early stop)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal(spec: Dict, u: np.ndarray) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
    vals = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(vals, spec["min"], spec["max"])


def _gaps(arrival: Dict, u: np.ndarray) -> np.ndarray:
    """Inter-arrival gaps with mean 1/rate: exponential (Poisson) or
    Gamma with the given coefficient of variation (bursts)."""
    rate = float(arrival["rate_per_s"])
    if arrival["process"] == "poisson":
        return -np.log1p(-u) / rate
    if arrival["process"] == "gamma":
        from scipy.special import gammaincinv
        shape = 1.0 / float(arrival["cv"]) ** 2
        return gammaincinv(shape, u) / (shape * rate)
    raise ValueError(f"unknown arrival process {arrival['process']!r}")


def round_up(lengths: np.ndarray, allowed: List[int]) -> np.ndarray:
    allowed = np.asarray(sorted(allowed))
    idx = np.searchsorted(allowed, np.ceil(lengths), side="left")
    return allowed[np.minimum(idx, len(allowed) - 1)]


def request_count(traffic: Dict, seconds: float) -> int:
    return max(1, int(round(float(traffic["arrival"]["rate_per_s"])
                            * seconds)))


def serve_requests(traffic: Dict, seed: int, seconds: float,
                   vocab_size: int) -> List[Req]:
    """The requests due in a window of ``seconds``, in arrival order."""
    n = request_count(traffic, seconds)
    u = _quantiles(n)
    order = np.random.default_rng(int(traffic["schedule_seed"]))
    gaps = order.permutation(_gaps(traffic["arrival"], u))
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    due = due * (seconds / gaps.sum())
    prompts = order.permutation(round_up(_lognormal(traffic["prompt"], u),
                                         traffic["prompt"]["lengths"]))
    outs = order.permutation(np.rint(_lognormal(traffic["output"], u))
                             .astype(int))
    ids = np.random.default_rng(int(seed))
    return [Req(due=float(d), tokens=ids.integers(0, vocab_size, int(p),
                                                  dtype=np.int32),
                max_new=int(o))
            for d, p, o in zip(due, prompts, outs)]


def longest_request(traffic: Dict) -> int:
    """Prompt plus output of the longest request the mix can send."""
    return int(max(traffic["prompt"]["lengths"])
               + math.ceil(traffic["output"]["max"]))
