"""The one traffic generator: a mix's parameters -> the window's requests.

A mix is a JSON file in chip_bench/traffic/. Every seed gets the same
schedule: request sizes are quantiles of the mix's distributions and the
gaps between arrivals quantiles of the exponential, composed and ordered
by one fixed draw. The seed chooses the content (which documents each
request asks about, their words, the weights). Two seeds differ in what
is asked, not in how much work arrives when: which requests fall at the
window's end decides how many tokens it counts, so even a permutation
of the same sizes moved `output_tok_s` by 10% from seed to seed.

Open loop: `Pacer` submits each request at its due time and records how
late it ran (copied from `launch/serve._pace_arrivals`, changed to keep
each request's due time and its lateness).
"""
from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

# The schedule is drawn from this fixed seed, never from --seed.
COMPOSITION_SEED = 20251017


def seeded_rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per (seed, stream); any size of seed."""
    return np.random.default_rng(np.random.SeedSequence([seed, stream]))


def jax_seed(seed: int) -> int:
    """A 31-bit seed for `jax.random.key`, derived from any --seed."""
    state = np.random.SeedSequence([seed, 7]).generate_state(1)[0]
    return int(state >> 1)


def quantiles(dist: dict, n: int) -> np.ndarray:
    """n integer sizes at the mid-quantiles of a clipped lognormal
    {"median", "sigma", "min", "max"}."""
    from statistics import NormalDist

    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = dist["median"] * np.exp(dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def poisson_offsets(rate: float, seconds: float) -> np.ndarray:
    """Due times in [0, seconds) of round(rate * seconds) arrivals: the
    exponential gaps' mid-quantiles in a fixed random order, scaled so
    the arrivals span the window at exactly that mean rate."""
    n = max(1, int(round(rate * seconds)))
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    gaps = np.random.default_rng(COMPOSITION_SEED + 1).permutation(gaps)
    t = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return t * (seconds * (n - 1) / n) / max(t[-1], 1e-12)


@dataclasses.dataclass
class RagRequest:
    due: float                 # seconds after the window opens
    passages: tuple            # token lengths of its top_k passages
    answer: int                # tokens to generate


def rag_requests(mix: dict, seconds: float) -> list:
    """The window's RAG requests: due times and sizes, the same for
    every seed."""
    due = poisson_offsets(mix["rate_per_s"], seconds)
    n, k = len(due), mix["top_k"]
    comp = np.random.default_rng(COMPOSITION_SEED)
    passages = comp.permutation(quantiles(mix["passage_tokens"], n * k))
    answers = comp.permutation(quantiles(mix["answer_tokens"], n))
    return [RagRequest(float(due[i]),
                       tuple(int(x) for x in passages[i * k:(i + 1) * k]),
                       int(answers[i]))
            for i in range(n)]


class Pacer:
    """Open-loop submission at due times, with the generator's lag."""

    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.t0 = None
        self.due: list = []
        self.submitted: list = []

    def run(self, offsets, submit) -> float:
        """Sleep to each due time, call submit(i); returns the window's
        start on `clock`."""
        self.t0 = self.clock()
        for i, off in enumerate(offsets):
            due = self.t0 + off
            delay = due - self.clock()
            if delay > 0:
                time.sleep(delay)
            self.due.append(due)
            self.submitted.append(self.clock())
            submit(i)
        return self.t0

    def lag_ms(self) -> np.ndarray:
        return 1e3 * (np.asarray(self.submitted) - np.asarray(self.due))
