"""The one traffic generator: turns a mix file and a seed into jobs.

A mix is a JSON file under `bench/traffic/<name>.json`:

    {"loop": "closed", "clients": 1}
    {"loop": "open", "arrivals": "poisson", "rate_per_s": 120}

Closed loop: each client sends its next job when the previous one is
done, until the window closes.  Open loop: jobs are due at fixed times
whatever the system does, and each is timed from when it was due.

Every job gets its own seed, drawn from the run's `--seed`.  The open
loop's inter-arrival gaps are the quantiles of the exponential
distribution at the mix's rate, scaled to fill the window exactly, in an
order the seed shuffles: every seed offers the same number of jobs and the
same gaps, so seeds change which job comes when, not how much work a run
holds.
"""

from __future__ import annotations

import dataclasses
import math
from typing import List

import numpy as np

JOB_SEED_HIGH = 2**31 - 1


@dataclasses.dataclass(frozen=True)
class Mix:
    loop: str                   # "closed" | "open"
    clients: int = 1            # closed loop: jobs outstanding at a time
    rate_per_s: float = 0.0     # open loop: offered jobs per second
    arrivals: str = "poisson"   # open loop: the gap distribution

    def __post_init__(self):
        if self.loop not in ("closed", "open"):
            raise ValueError(f"loop must be 'closed' or 'open', "
                             f"got {self.loop!r}")
        if self.loop == "closed" and self.clients < 1:
            raise ValueError("a closed loop needs at least one client")
        if self.loop == "open":
            if self.rate_per_s <= 0:
                raise ValueError("an open loop needs rate_per_s > 0")
            if self.arrivals != "poisson":
                raise ValueError(f"unknown arrivals {self.arrivals!r}")

    @property
    def max_outstanding(self) -> float:
        return self.clients if self.loop == "closed" else math.inf


def mix_from_dict(d: dict) -> Mix:
    return Mix(**d)


def job_seeds(seed: int, count: int) -> List[int]:
    """`count` job seeds, a function of the run's seed alone."""
    rng = np.random.default_rng(int(seed))
    return [int(s) for s in rng.integers(1, JOB_SEED_HIGH, size=count)]


def open_schedule(mix: Mix, seed: int, seconds: float) -> List[float]:
    """Due times (s from the window's start) of every job of an open loop."""
    n = max(1, int(round(mix.rate_per_s * seconds)))
    q = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-q)                  # exponential quantiles, mean ~1
    rng = np.random.default_rng([int(seed), 1])
    gaps = rng.permutation(gaps)
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return [float(t) for t in due * (seconds / gaps.sum())]
