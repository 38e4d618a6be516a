"""The one general generator: a traffic mix is a data file of parameters.

Every seed gets the SAME set of sizes and arrival gaps: the sets are the
quantiles of the mix's distributions, so a run's work does not change with the
seed. For serving the ORDER is the mix's too (its ``arrangement`` number, the
seed of the one permutation): a queue's tails follow the clumps of the order.
Over six orders drawn from the seed, on one program, p90 time-to-first-token
read 266-461 ms and the p95 gap between tokens 146.1-148.0 ms; over six runs
of order 1 the gap read 145.4-146.6 ms (PERF.md, PR 23). A bound of five
times the spread has to be met by runs that differ in their seed alone, so
the order is replayed like a recorded trace, and the seed draws the tokens
(and the runner the weights). Order 1 is the first tried, not chosen among
others. (The draw arithmetic is loadgen/workload.py::build_trace's — open-loop
arrivals, heavy-tailed lengths — rewritten over quantile grids.)
"""

from __future__ import annotations

import math
import statistics

import numpy as np

_NORMAL = statistics.NormalDist()


def lognormal_quantiles(n: int, median: float, sigma: float, lo: int, hi: int):
    """``n`` lengths at the mid-quantiles of a log-normal, clipped."""
    qs = [(i + 0.5) / n for i in range(n)]
    vals = [median * math.exp(sigma * _NORMAL.inv_cdf(q)) for q in qs]
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def exponential_gaps(n: int, span_s: float):
    """``n`` inter-arrival gaps at the mid-quantiles of an exponential,
    scaled to fill ``span_s`` exactly: a Poisson process's gaps, as a set."""
    gaps = np.array([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return gaps * (span_s / gaps.sum())


def serve_requests(mix: dict, seed: int, seconds: float, vocab: int):
    """The requests of one run: ``preseat`` submitted in set-up (due None),
    then round(rate * seconds) arriving open-loop inside the window. Each is
    a dict(due_s, prompt (int32 array), max_new_tokens)."""
    order = np.random.default_rng([int(mix.get("arrangement", 0)), 0x5E12])
    rng = np.random.default_rng([seed, 0x5E12])
    p, o = mix["prompt"], mix["output"]

    def lengths(n):
        pl = lognormal_quantiles(n, p["median"], p["sigma"], p["min"], p["max"])
        ol = lognormal_quantiles(n, o["median"], o["sigma"], o["min"], o["max"])
        return order.permutation(pl), order.permutation(ol)

    out = []
    pre = int(mix.get("preseat", 0))
    if pre:
        pl, ol = lengths(pre)
        out += [(None, int(a), int(b)) for a, b in zip(pl, ol)]
    n = max(1, round(mix["rate_per_s"] * seconds))
    pl, ol = lengths(n)
    gaps = order.permutation(exponential_gaps(n, seconds))
    due = np.cumsum(gaps) - gaps[0]
    out += [(float(t), int(a), int(b)) for t, a, b in zip(due, pl, ol)]
    return [
        {"due_s": t, "max_new_tokens": b,
         "prompt": rng.integers(0, vocab, size=a, dtype=np.int64).astype(np.int32)}
        for t, a, b in out
    ]


def prompt_buckets(mix: dict) -> list[int]:
    """Every power-of-two prefill width the mix's prompts can land in."""
    lo = 1 << (int(mix["prompt"]["min"]) - 1).bit_length()
    hi = 1 << (int(mix["prompt"]["max"]) - 1).bit_length()
    out, b = [], lo
    while b <= hi:
        out.append(b)
        b *= 2
    return out


def token_rows(mix: dict, seed: int, vocab: int) -> np.ndarray:
    """The training stream: ``rows`` sequences of ``seq_len`` tokens, Zipf
    unigrams with a seeded bigram table (with probability ``bigram_p`` a
    token is a fixed function of the one before), so the loss can fall.
    Vectorised over rows; every row differs."""
    rng = np.random.default_rng([seed, 0x7EA1])
    rows, seq = int(mix["rows"]), int(mix["seq_len"])
    ranks = np.arange(1, vocab + 1, dtype=np.float64)
    pmf = ranks ** -float(mix["zipf_a"])
    pmf /= pmf.sum()
    perm = rng.permutation(vocab)  # which token holds which rank
    unigram = perm[rng.choice(vocab, size=(rows, seq), p=pmf)]
    follow = rng.integers(0, vocab, size=vocab)  # the bigram table
    use = rng.random((rows, seq)) < float(mix["bigram_p"])
    out = np.empty((rows, seq), np.int32)
    out[:, 0] = unigram[:, 0]
    for t in range(1, seq):
        out[:, t] = np.where(use[:, t], follow[out[:, t - 1]], unigram[:, t])
    return out
