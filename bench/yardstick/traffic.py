"""Seeded request generator, one for every traffic mix.

Started as a copy of the serving launcher's ``LoadGen`` (Poisson arrivals
from exponential gaps, prompt tokens uniform over the vocabulary) and
extended with clipped lognormal lengths, a backlog mode and a due time for
every request.

A mix is a JSON file of parameters (``bench/traffic/<name>.json``)::

    {"arrivals": {"mode": "poisson", "rate": 1.1,        # requests / s
                  "schedule_seed": 0}
     or          {"mode": "backlog", "outstanding": 16, "pool": 16},
     "prompt": {"median": 256, "sigma": 0.6, "min": 64, "max": 1024},
     "output": {"median": 96, "sigma": 0.5, "min": 32, "max": 256}, ...}

Open loop: one Poisson schedule, drawn once from ``schedule_seed`` (i.i.d.
exponential gaps at ``rate``, as many arrivals as fall inside the window),
so every run of the mix offers the same arrivals. The lengths are evenly
spaced quantiles of their lognormal distributions, clipped, paired and
ordered by fixed permutations. The run's seed draws only the token ids
(and, in the harness, the weights and the faults). Backlog: a pool of
``pool`` requests, cycled.

Why the schedule does not follow the run's seed: at 0.8 of the knee the
90th-percentile TTFT of some fifty requests is a property of where the
bursts fall: with the seed reordering the gaps and sizes it moved
between 0.22 and 1.21 s, far more than any change to the engine would.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    rid: int
    tokens: np.ndarray          # prompt token ids, int32
    max_new: int                # tokens to serve, the first one included
    due: float = 0.0            # seconds after the window opens


def length_grid(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of the lognormal given by
    median and sigma, rounded and clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    z = np.asarray([NormalDist().inv_cdf(float(q)) for q in u])
    vals = np.rint(np.exp(math.log(spec["median"]) + spec["sigma"] * z))
    return np.clip(vals.astype(np.int64), spec["min"], spec["max"])


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, stream])


def poisson_due(rate: float, seconds: float, schedule_seed: int):
    """Due times of a Poisson process at ``rate`` inside ``[0, seconds)``:
    cumulative sums of unit exponential gaps drawn from ``schedule_seed``,
    over the rate (so one draw serves every rate of a sweep)."""
    rng = rng_for(schedule_seed, 1)
    gaps = rng.exponential(1.0, max(16, int(2 * rate * seconds) + 64))
    due = np.cumsum(gaps) / rate
    assert due[-1] >= seconds
    return due[due < seconds]


def pairs(mix: dict, n: int) -> np.ndarray:
    """The fixed multiset of (prompt length, output length) of ``n``
    requests."""
    prompt = length_grid(mix["prompt"], n)
    output = length_grid(mix["output"], n)
    return np.stack([prompt, output[rng_for(0, 0).permutation(n)]], axis=1)


class Generator:
    """The requests of one run of one mix, for a window of ``seconds``."""

    def __init__(self, mix: dict, vocab_size: int, seed: int,
                 seconds: float):
        self.mix = mix
        self.vocab = vocab_size
        arr = mix["arrivals"]
        self.mode = arr["mode"]
        if self.mode == "poisson":
            self.due = poisson_due(arr["rate"], seconds,
                                   arr["schedule_seed"])
            n = max(len(self.due), 1)
        elif self.mode == "backlog":
            n = int(arr["pool"])
        else:
            raise ValueError(f"unknown arrival mode {self.mode!r}")
        self.pool = pairs(mix, n)[rng_for(0, 1).permutation(n)]
        self.tokens = rng_for(seed, 2)
        self.next_rid = 0

    def make(self, due: float) -> Request:
        i = self.next_rid
        self.next_rid += 1
        plen, out = (int(v) for v in self.pool[i % len(self.pool)])
        toks = self.tokens.integers(0, self.vocab, plen, dtype=np.int64)
        return Request(rid=i, tokens=toks.astype(np.int32), max_new=out,
                       due=float(due))

    def schedule(self) -> List[Request]:
        """Open loop: every request of the window, in due order."""
        assert self.mode == "poisson"
        return [self.make(float(t)) for t in self.due]
