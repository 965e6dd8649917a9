"""The one request generator: reads a traffic mix (``traffic/<mix>.json``) and
turns it, with ``--seed``, into requests.

Every seed gets the same work in another order: arrival gaps are the
quantiles of an exponential distribution at the mix's rate, prompt lengths
come in equal shares of the mix's list, output lengths are spread evenly
over the mix's range, and adapters are the quantiles of the popularity law
(Zipf or uniform) over the fleet. The seed shuffles each of these, maps
popularity ranks to adapter ids, and draws the prompt tokens. A mix may
fix the order of its arrival gaps (``"arrival_order": "fixed"``), so that
every seed's requests arrive at the same times. Requests come
in blocks of ``block`` so a backlog can draw as many as it needs. An
open-loop schedule is one block of ``round(rate · seconds)`` requests whose
gaps are scaled to tile the window, so every seed offers the same requests
inside it, in another order.
"""

from __future__ import annotations

import dataclasses
from typing import Iterator, List

import numpy as np

FIXED_ARRIVALS = 0xA221     # the stream of a fixed arrival order


@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""

    index: int
    due: float                 # seconds after the window opens (open loop)
    adapter: int               # fleet index
    prompt: np.ndarray         # (T,) int32
    max_new: int


def _stratified(n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` evenly spaced quantile levels in (0, 1), shuffled."""
    return rng.permutation((np.arange(n) + 0.5) / n)


def _popularity(fleet: dict, u: np.ndarray, perm: np.ndarray) -> np.ndarray:
    n = fleet["adapters"]
    if fleet["popularity"] == "uniform":
        ranks = np.minimum((u * n).astype(np.int64), n - 1)
    elif fleet["popularity"] == "zipf":
        w = 1.0 / np.arange(1, n + 1) ** fleet["zipf_alpha"]
        cdf = np.cumsum(w) / w.sum()
        ranks = np.minimum(np.searchsorted(cdf, u), n - 1)
    else:
        raise ValueError(f"unknown popularity {fleet['popularity']!r}")
    return perm[ranks]


def block(traffic: dict, seed: int, vocab: int, b: int, n: int) -> List[Planned]:
    """Block ``b`` of ``n`` requests of this mix for this seed."""
    rng = np.random.default_rng([seed, b])
    perm = np.random.default_rng([seed, 1 << 20]).permutation(
        traffic["fleet"]["adapters"])
    lengths = np.asarray(traffic["prompt_tokens"])
    prompt_len = rng.permutation(np.resize(lengths, n))
    lo, hi = traffic["output_tokens"]["min"], traffic["output_tokens"]["max"]
    out_len = lo + np.floor(_stratified(n, rng) * (hi - lo + 1)).astype(int)
    adapter = _popularity(traffic["fleet"], _stratified(n, rng), perm)
    rate = traffic.get("rate_per_s")
    # a mix with "arrival_order": "fixed" offers every seed the same
    # arrival times; the seed then orders only what arrives at each
    grng = (np.random.default_rng([FIXED_ARRIVALS, b])
            if traffic.get("arrival_order") == "fixed" else rng)
    gaps = (-np.log1p(-_stratified(n, grng)) / rate if rate
            else np.zeros(n))
    due = np.cumsum(gaps)
    return [Planned(index=b * n + i, due=float(due[i]),
                    adapter=int(adapter[i]),
                    prompt=rng.integers(0, vocab, int(prompt_len[i]),
                                        dtype=np.int32),
                    max_new=int(out_len[i]))
            for i in range(n)]


def stream(traffic: dict, seed: int, vocab: int) -> Iterator[Planned]:
    """Requests of the mix in order, without end: for a backlog."""
    b = 0
    n = traffic["block"]
    while True:
        yield from block(traffic, seed, vocab, b, n)
        b += 1


def schedule(traffic: dict, seed: int, vocab: int,
             seconds: float) -> List[Planned]:
    """An open-loop schedule of exactly a window of ``seconds``: one block
    of ``round(rate · seconds)`` requests, the first due as the window
    opens, each next one a gap later; the gaps, and the one after the last
    request, are the block's gaps scaled by one factor so that together
    they fill the window."""
    n = max(1, int(round(traffic["rate_per_s"] * seconds)))
    plan = block(traffic, seed, vocab, 0, n)
    gaps = np.diff([0.0] + [p.due for p in plan])
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])]) * (seconds
                                                           / gaps.sum())
    for p, d in zip(plan, due):
        p.due = float(d)
    return plan
