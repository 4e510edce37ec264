"""The one general traffic generator: a mix is a JSON file of
parameters under ``<path>/traffic/<name>.json`` and this module turns it
and ``--seed`` into the inputs of a run.

Every seed gets the SAME multiset of sizes and inter-arrival gaps, in
another order: sizes are the evenly spaced quantiles of the mix's
distribution, and the seed orders them so that every run of ``BLOCK``
consecutive draws is itself a spread over the whole distribution (the
sorted quantiles are dealt round-robin into blocks; the seed shuffles
inside each block and the order of the blocks). Runs with different
seeds then do the same work, at the same local intensity, and differ in
order and in token ids: their metrics spread by the system's noise and
not by the draw.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent generator per (seed, purpose). ``seed`` may be
    any non-negative whole number (the driver's pass 2**31)."""
    return np.random.default_rng(
        [int(seed), *(ord(c) for c in stream)])


def jax_seed(seed: int) -> int:
    """Fold any whole number into what ``jax.random.key`` takes with
    32-bit integers."""
    return int(np.random.SeedSequence(int(seed)).generate_state(1)[0]
               >> 1)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles of ``spec``'s distribution, as
    whole numbers clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "fixed":
        x = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(v)) for v in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif dist == "exponential":
        x = -np.log1p(-u) * spec["mean"]
        return x                       # gaps: real numbers, unclipped
    else:
        raise ValueError(f"unknown distribution {dist!r}")
    return np.clip(np.rint(x), spec.get("min", 1),
                   spec.get("max", math.inf)).astype(np.int64)


#: consecutive draws that together span the distribution
BLOCK = 16


def balanced_order(x: np.ndarray, rng: np.random.Generator
                   ) -> np.ndarray:
    """``x`` (sorted) dealt round-robin into blocks of about ``BLOCK``,
    each shuffled, in a shuffled order of blocks."""
    n_blocks = max(1, -(-len(x) // BLOCK))
    blocks = [rng.permutation(x[b::n_blocks]) for b in range(n_blocks)]
    return np.concatenate([blocks[i]
                           for i in rng.permutation(n_blocks)])


def lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    return balanced_order(quantiles(spec, n), rng)


def arrivals(spec: dict, horizon_s: float, rng: np.random.Generator
             ) -> np.ndarray:
    """Due times in seconds from the start of the ramp.

    ``poisson``: exponential gaps at ``rate_per_s``, enough of them to
    cover ``horizon_s`` (the same gaps for every seed, permuted).
    ``burst``: ``burst_size`` requests at once every
    ``burst_size / rate_per_s`` seconds. ``backlog``: ``count``
    requests, all due at 0."""
    proc = spec["process"]
    if proc == "backlog":
        return np.zeros(int(spec["count"]))
    rate = float(spec["rate_per_s"])
    if proc == "poisson":
        n = max(1, int(round(rate * horizon_s)))
        gaps = balanced_order(
            quantiles({"dist": "exponential", "mean": 1.0 / rate}, n),
            rng)
        return np.cumsum(gaps)
    if proc == "burst":
        k = int(spec["burst_size"])
        n_bursts = max(1, int(round(rate * horizon_s / k)))
        return np.repeat(np.arange(n_bursts) * (k / rate), k)
    raise ValueError(f"unknown arrival process {proc!r}")


def serve_requests(mix: dict, *, vocab_size: int, max_len: int,
                   horizon_s: float, seed: int) -> list[dict]:
    """The requests of a serving run: due time, prompt token ids and
    the number of tokens to generate, greedy. Output lengths are
    clipped so that prompt + output fits ``max_len``.

    A mix with a ``schedule_seed`` fixes its schedule — due times,
    lengths and which prompt meets which output — for every run, and
    ``seed`` draws only the token ids (and the weights): queueing tails
    depend on the ORDER of arrivals, so a schedule reshuffled by the
    run's seed spreads TTFT by several times the system's own noise
    (PERF.md, PR 23). Without one the run's seed orders the schedule."""
    sched = mix.get("schedule_seed", seed)
    due = arrivals(mix["arrivals"], horizon_s, rng_for(sched, "arrive"))
    n = len(due)
    p_len = lengths(mix["prompt_len"], n, rng_for(sched, "prompt_len"))
    o_len = lengths(mix["output_len"], n, rng_for(sched, "output_len"))
    o_len = np.minimum(o_len, max_len - p_len)
    if (o_len < 1).any():
        raise ValueError("a prompt leaves no room for output")
    tok = rng_for(seed, "tokens")
    return [{"due": float(due[i]),
             "prompt": tok.integers(1, vocab_size, int(p_len[i]),
                                    dtype=np.int32),
             "max_tokens": int(o_len[i])} for i in range(n)]


class Corpus:
    """A seeded corpus for ``hetu_tpu.data.build_data_loader``: anything
    with ``len`` and ``[]`` serves as its dataset."""

    def __init__(self, mix: dict, *, vocab_size: int, seed: int):
        n = int(mix["corpus_docs"])
        lens = lengths(mix["doc_len"], n, rng_for(seed, "doc_len"))
        flat = rng_for(seed, "tokens").integers(
            0, vocab_size, int(lens.sum()), dtype=np.int32)
        self.records = np.split(flat, np.cumsum(lens)[:-1])

    def __len__(self):
        return len(self.records)

    def __getitem__(self, i):
        return self.records[i]
