"""Traffic from a mix's data file and a seed.

One generator serves every mix: a mix is a JSON file under ``traffic/``
(lengths, arrival process) and a cell's file under ``cells/`` adds the
numbers fixed on the chip (rate, ramp, client count).

Every seed gets the same work in another order.  Lengths are the quantiles
``(i + 0.5) / n`` of the mix's distribution, clipped to its range, and the
gaps between arrivals are the quantiles of the exponential distribution,
scaled to fill the stretch; the seed orders both and draws the prompt token
ids.  The order is balanced: consecutive blocks of ``BLOCK`` requests each
take one value from every stratum of the sorted quantiles, so every few
seconds of a run offer the same mix of long and short requests and the
same load, and the seed decides which value of each stratum lands in which
block and the order inside each block.  So two seeds differ in which
request comes when, not in how many requests or tokens a run serves, nor in
how they spread over the run.

The arrivals are therefore not a Poisson process (process
``balanced_exponential``).  The dealt gaps are the exponential's, as in
``repro.data.pipeline.request_stream`` (re-derived here so that the
yardstick does not move with the program), but each block of ``BLOCK``
consecutive gaps holds exactly one from each of the exponential's
``BLOCK`` quantile strata, so a block holds at most two gaps shorter than a
quarter of the mean and long runs of short gaps do not occur.  And each
arrival sits at the middle of its gap, so two successive arrivals lie the
mean of two neighbouring gaps apart: the time between arrivals varies less
than an exponential's.  Clusters of arrivals, and the tails of time to
first token they cause, are lighter than under Poisson arrivals at the
same rate.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

BLOCK = 8              # requests per balanced block


def balanced(values: np.ndarray, rng: np.random.Generator,
             block: int = BLOCK) -> np.ndarray:
    """``values`` (in any order) reordered in balanced blocks: sorted, cut
    into strata of one value per block, each stratum dealt to the blocks in
    an order drawn from ``rng``, and each block shuffled."""
    v = np.sort(np.asarray(values))
    n = len(v)
    k = max(-(-n // block), 1)                 # number of blocks
    blocks: list[list] = [[] for _ in range(k)]
    for start in range(0, n, k):
        stratum = v[start:start + k]
        for b, x in zip(rng.permutation(k)[:len(stratum)], stratum):
            blocks[b].append(x)
    out = [x for b in blocks for x in rng.permutation(np.asarray(b))]
    return np.asarray(out, dtype=v.dtype)


@dataclass
class Arrival:
    """One request of the schedule: when it is due (seconds from the start
    of the measured window; negative during the ramp), its prompt and its
    output budget."""

    due_s: float
    prompt: np.ndarray
    max_new_tokens: int


def lengths(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, in order."""
    q = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        out = lo + q * (hi - lo)
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        out = float(spec["median"]) * np.exp(float(spec["sigma"]) * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(out), lo, hi).astype(np.int64)


def arrival_gaps(n: int, rng: np.random.Generator) -> np.ndarray:
    """The ``n`` quantiles of the unit exponential, in balanced blocks."""
    q = (np.arange(n) + 0.5) / n
    return balanced(-np.log1p(-q), rng)


def arrival_times(mix: dict, rate: float, start: float, end: float,
                  rng: np.random.Generator) -> np.ndarray:
    """``round(rate * (end - start))`` arrival times over ``[start, end)``:
    the gaps of ``arrival_gaps`` laid end to end and scaled to fill the
    stretch, each arrival at the middle of its own gap."""
    process = mix["arrivals"]["process"]
    if process != "balanced_exponential":
        raise ValueError(f"no open-loop arrivals for {process!r}")
    n = int(round(rate * (end - start)))
    if n <= 0:
        return np.zeros(0)
    gaps = arrival_gaps(n, rng)
    cum = np.cumsum(gaps) - 0.5 * gaps
    return start + (end - start) * cum / gaps.sum()


def _requests(mix: dict, n: int, rng, vocab: int):
    """``n`` (prompt, output budget) pairs: stratified lengths, each
    ordered in balanced blocks on its own, and prompt ids drawn uniformly
    from the vocabulary."""
    plens = balanced(lengths(mix["prompt_tokens"], n), rng)
    olens = balanced(lengths(mix["output_tokens"], n), rng)
    return [(rng.integers(0, vocab, int(p), dtype=np.int64).astype(np.int32),
             int(o)) for p, o in zip(plens, olens)]


def open_loop(mix: dict, cell: dict, seed: int, seconds: float,
              vocab: int) -> list[Arrival]:
    """The open-loop schedule: the ramp ``[-ramp_s, 0)`` and the window
    ``[0, seconds)``, each with its own fixed number of arrivals."""
    rng = np.random.default_rng(seed)
    rate = float(cell["rate_rps"])
    out = []
    for a, b in ((-float(cell["ramp_s"]), 0.0), (0.0, float(seconds))):
        times = arrival_times(mix, rate, a, b, rng)
        reqs = _requests(mix, len(times), rng, vocab)
        out += [Arrival(float(t), p, o) for t, (p, o) in zip(times, reqs)]
    return out


def closed_loop(mix: dict, cell: dict, seed: int,
                vocab: int) -> list[list[Arrival]]:
    """One list of requests per client, taken in turn; ``due_s`` is filled
    in by the load loop when the client's previous request completes."""
    rng = np.random.default_rng(seed)
    clients = int(cell["clients"])
    per_client = int(cell["requests_per_client"])
    reqs = _requests(mix, clients * per_client, rng, vocab)
    return [[Arrival(0.0, p, o) for p, o in reqs[c::clients]]
            for c in range(clients)]


__all__ = ["Arrival", "BLOCK", "arrival_gaps", "arrival_times", "balanced", "closed_loop",
           "lengths", "open_loop"]
