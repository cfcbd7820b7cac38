"""The one traffic generator: reads a mix's parameters (``traffic/<mix>.json``)
and turns them into requests from ``--seed``, through the cell's family
(``families/<family>.py``), which makes each request's content.

Every seed gets the same schedule: the same request sizes and the same
due times, in the same order, drawn from the mix's own ``mix_seed``.  So a
seed changes which user sends what (the content comes from the seed
alone), and neither how much work a run holds nor when it arrives: under
Poisson arrivals near the knee, the order of the gaps alone moves a run's
tail by tens of percent.

Mix parameters:

- ``arrival``: ``"poisson"`` (open loop at ``rate_per_s``: exponential gaps
  drawn as quantiles, so a window of ``s`` seconds holds
  ``round(rate_per_s * s)`` requests, all due inside it) or ``"closed"``
  (``outstanding`` requests in flight; each completion sends the next).
- ``lengths``: fresh requests, sized in the family's unit (OneRec: history
  items); ``cap_share`` of them at ``cap_items``, the rest log-uniform over
  ``[min_items, max_items]`` (not needed where ``cap_share`` is 1).
- ``sessions`` (instead of ``lengths``): returning users, whose visits the
  family draws (``sessions(mix, model, n, rng)``).
- ``mix_seed``: the schedule's seed.
"""

from __future__ import annotations

import math
from typing import Dict, List

import numpy as np


def _shuffled(values, rng) -> list:
    values = list(values)
    order = rng.permutation(len(values))
    return [values[i] for i in order]


def fresh_items(lengths: dict, n: int) -> List[int]:
    """The fixed multiset of ``n`` request sizes, in the family's unit
    (sorted)."""
    n_cap = int(round(lengths["cap_share"] * n))
    if n_cap == n:
        return [lengths["cap_items"]] * n
    lo, hi = math.log(lengths["min_items"]), math.log(lengths["max_items"])
    rest = [int(round(math.exp(lo + (j + 0.5) / (n - n_cap) * (hi - lo))))
            for j in range(n - n_cap)]
    return sorted(rest + [lengths["cap_items"]] * n_cap)


def poisson_gaps(rate: float, n: int, seconds: float) -> List[float]:
    """``n`` exponential gaps of mean ``1 / rate`` taken at evenly spaced
    quantiles, scaled so that the last of ``n`` arrivals falls just inside
    ``seconds`` (sorted; the caller shuffles)."""
    q = [-math.log(1.0 - (j + 0.5) / n) / rate for j in range(n)]
    scale = seconds * (1.0 - 0.5 / n) / sum(q)
    return sorted(g * scale for g in q)


class Workload:
    """Requests of one run.  ``requests[i]`` is the engine's request dict;
    ``due_s[i]`` its due time from window start (open loop; empty for a
    closed loop, whose requests are sent as earlier ones complete)."""

    def __init__(self, requests: List[Dict], due_s: List[float],
                 mix: dict):
        self.requests = requests
        self.due_s = due_s
        self.mix = mix

    @property
    def closed(self) -> bool:
        return self.mix["arrival"] == "closed"


def generate(mix: dict, family, model: dict, seed: int,
             seconds: float) -> Workload:
    """The requests of one run of ``seconds`` under ``mix``; ``family``
    makes their content for ``model``."""
    rng = np.random.default_rng(seed)
    sched = np.random.default_rng(mix["mix_seed"])
    if mix["arrival"] == "poisson":
        n = max(int(round(mix["rate_per_s"] * seconds)), 1)
    elif mix["arrival"] == "closed":
        # enough for the fastest rate the chip could reach, plus the
        # requests in flight when the window closes
        n = int(mix["max_items_per_s"] * seconds) + mix["outstanding"]
    else:
        raise ValueError(f"unknown arrival {mix['arrival']!r}")

    if "sessions" in mix:
        requests = family.sessions(mix, model, n, rng)
    else:
        sizes = _shuffled(fresh_items(mix["lengths"], n), sched)
        requests = [family.fresh_request(model, k, rng) for k in sizes]
    due = []
    if mix["arrival"] == "poisson":
        due = list(np.cumsum(_shuffled(
            poisson_gaps(mix["rate_per_s"], n, seconds), sched)))
    return Workload(requests, [float(d) for d in due], mix)

