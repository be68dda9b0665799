"""The one traffic generator: a mix file of parameters in, requests out.

Every seed gets the same work: lengths are the distribution's quantiles at
``(i + 0.5) / n`` and inter-arrival gaps the exponential's, shuffled into
one order by the mix's own ``order_seed``; the run's seed draws the token
ids (and the harness the weights). An open loop draws the warm-in and the
window as two such segments, so the window always holds the same requests.
The order is the mix's and not the run's: a window holds 3 to 30 requests
that live tens of seconds each, and shuffling them per run moved
throughput by 19% and TTFT by 37% (quartile spreads over six seeds,
``PERF.md``), against about 1% between two runs of one order.

Mix keys:
  ``loop``: ``open`` (Poisson arrivals at the cell's ``rate_rps``) or
  ``closed`` (``clients`` clients, each sending its next request when the
  last one completes);
  ``prompt`` / ``output``: a length distribution, ``{"dist": "lognormal",
  "median", "log_std", "min", "max"}`` or ``{"dist": "uniform", "min",
  "max"}``;
  ``warm_in_s``: seconds of traffic before the measured window;
  ``order_seed``: the order of lengths and gaps;
  closed loop only: ``requests_per_client``, and ``stagger``: client
  ``i``'s first request arrives aged, as if ``(i + 0.5) / clients`` of its
  output had been decoded already. Those tokens (drawn from the seed)
  extend its prompt, and it asks for the rest, so prompt plus output stays
  as drawn. The warm-in prefills them, and the window opens on contexts
  and completions spread as a loop that has run for a while spreads them,
  rather than on every client at its first token.
"""
from __future__ import annotations

from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass
class Req:
    idx: int
    prompt: np.ndarray            # int32 token ids
    max_new: int
    due: Optional[float] = None   # open loop: seconds after arrivals start
    client: int = -1              # closed loop: who sends it


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the distribution's mid-quantiles, ascending."""
    u = quantiles(n)
    kind = dist["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(x) for x in u])
        x = dist["median"] * np.exp(dist["log_std"] * z)
    elif kind == "uniform":
        x = dist["min"] + u * (dist["max"] - dist["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(n: int, span: float) -> np.ndarray:
    """``n`` inter-arrival gaps at the exponential's mid-quantiles, scaled
    to sum to exactly ``span``."""
    g = -np.log1p(-quantiles(n))
    return g * span / g.sum()


def _requests(mix: dict, n: int, vocab: int, seed: int,
              segment: int = 0) -> List[Req]:
    order = mix["order_seed"]
    pl = lengths(mix["prompt"], n)[_rng(order, 10 * segment + 1)
                                   .permutation(n)]
    ol = lengths(mix["output"], n)[_rng(order, 10 * segment + 2)
                                   .permutation(n)]
    tok = _rng(seed, 10 * segment + 3)
    return [Req(idx=i, prompt=tok.integers(0, vocab, int(p), dtype=np.int32),
                max_new=int(o)) for i, (p, o) in enumerate(zip(pl, ol))]


def open_loop(mix: dict, rate: float, warm_in: float, seconds: float,
              vocab: int, seed: int) -> List[Req]:
    """Requests due over the warm-in and the window, in due order: each
    segment holds ``round(rate * its length)`` requests (at least one) at
    shuffled exponential gaps, the last due before the segment ends."""
    out: List[Req] = []
    start = 0.0
    for seg, span in enumerate((warm_in, seconds)):
        n = max(int(round(rate * span)), 1)
        reqs = _requests(mix, n, vocab, seed, seg)
        # n + 1 gaps span the segment; the last one ends it
        gaps = exp_gaps(n + 1, span)[_rng(mix["order_seed"], 10 * seg + 4)
                                     .permutation(n + 1)]
        for r, t in zip(reqs, start + np.cumsum(gaps[:n])):
            r.due = float(t)
            r.idx = len(out)
            out.append(r)
        start += span
    return out


def closed_loop(mix: dict, vocab: int, seed: int) -> List[List[Req]]:
    """Each client's requests in the order it sends them."""
    c, k = mix["clients"], mix["requests_per_client"]
    reqs = _requests(mix, c * k, vocab, seed)
    per = [reqs[i * k:(i + 1) * k] for i in range(c)]
    aged = _rng(seed, 5)
    for i, q in enumerate(per):
        for r in q:
            r.client = i
        if mix.get("stagger"):
            r = q[0]
            done = min(int(round(r.max_new * (i + 0.5) / c)), r.max_new - 1)
            r.prompt = np.concatenate(
                [r.prompt, aged.integers(0, vocab, done, dtype=np.int32)])
            r.max_new -= done
    return per


def max_context(mix: dict) -> int:
    """The longest prompt plus output the mix can draw."""
    return mix["prompt"]["max"] + mix["output"]["max"]
