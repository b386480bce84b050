"""The one traffic generator: a mix file's parameters plus a seed give the
requests a run offers.

Every seed offers the same schedule: the prompt lengths, output lengths
and gaps between arrivals are drawn at evenly spaced quantiles of the
mix's distributions and put in an order fixed by the mix's
``schedule_seed``; the run's seed draws the token ids.  So two seeds do
the same work on other tokens, and the spread between runs is the
system's, not the generator's: with some fifty requests in a window, the
order alone moved a time-to-first-token tail by a factor of two.

A mix file (``chipbench/traffic/<name>.json``) holds:

* ``driver``: ``open_loop`` (arrivals on a schedule) or ``backlog``
  (every request due at once), the file under ``chipbench/drivers``;
* ``prompt_len`` and ``output_len``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}``, clipped to ``[min, max]``;
* ``schedule_seed``: the order of the lengths and gaps;
* ``open_loop``: ``rate_rps`` (Poisson arrivals at that mean rate) and
  ``drain_s``, how long after the window the run keeps serving (and
  offering load) until every request due in the window has finished;
* ``backlog``: ``requests``, the number due at time 0.
"""

from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Offered:
    """One request as the generator offers it."""

    rid: int
    due_s: float          # seconds after the window opens
    prompt: List[int]
    max_new: int
    counted: bool         # due inside the window (the drain's are not)


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` lengths at evenly spaced quantiles of ``spec``, shuffled."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    raw = spec["median"] * np.exp(spec["sigma"] * z)
    out = np.clip(np.rint(raw), spec["min"], spec["max"]).astype(int)
    return rng.permutation(out)


def poisson_gaps(rate: float, n: int, rng) -> np.ndarray:
    """``n`` gaps between Poisson arrivals at ``rate`` per second: the
    exponential distribution's evenly spaced quantiles, shuffled."""
    return rng.permutation(-np.log1p(-_quantiles(n)) / rate)


def _requests(mix: dict, n: int, order, ids, vocab: int, first_rid: int,
              dues, counted: bool) -> List[Offered]:
    plens = lengths(mix["prompt_len"], n, order)
    outs = lengths(mix["output_len"], n, order)
    return [Offered(rid=first_rid + i, due_s=float(dues[i]),
                    prompt=ids.integers(0, vocab, int(plens[i])).tolist(),
                    max_new=int(outs[i]), counted=counted)
            for i in range(n)]


def generate(mix: dict, seed: int, seconds: float, vocab: int,
             cache_len: int) -> List[Offered]:
    """Every request the run offers, in order of due time."""
    longest = mix["prompt_len"]["max"] + mix["output_len"]["max"]
    if longest > cache_len - 1:
        raise ValueError(f"the mix's longest request ({longest} positions) "
                         f"does not fit a {cache_len}-position cache")
    order = np.random.default_rng(int(mix["schedule_seed"]))
    ids = np.random.default_rng(int(seed))
    if mix["driver"] == "backlog":
        n = int(mix["requests"])
        return _requests(mix, n, order, ids, vocab, 0, np.zeros(n), True)
    if mix["driver"] != "open_loop":
        raise ValueError(f"unknown driver {mix['driver']!r}")
    rate = float(mix["rate_rps"])
    n = max(1, int(round(rate * seconds)))
    gaps = poisson_gaps(rate, n, order)
    # arrivals k = 0..n-1 at the running sum of the gaps before them,
    # scaled so that the window holds exactly n arrivals
    dues = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) * (
        seconds / gaps.sum())
    window = _requests(mix, n, order, ids, vocab, 0, dues, True)
    # load that keeps coming while the window's requests drain
    m = max(1, int(round(rate * mix["drain_s"])))
    extra = seconds + np.cumsum(poisson_gaps(rate, m, order))
    return window + _requests(mix, m, order, ids, vocab, n, extra, False)
