"""One general traffic generator, driven by ``traffic/<mix>.json``.

A mix is data; a new one needs no code.  Keys of a mix file:

``loop``
    ``steps``   back-to-back training steps (``batch_per_chip``, ``seq``);
    ``open``    requests sent on a schedule whether or not earlier ones
                have finished (``rate`` requests/s, ``arrivals``);
    ``backlog`` a queue that never runs dry: the driver keeps
                ``queue_depth`` requests waiting.
``prompt_len`` / ``max_new``
    a length distribution: ``{"dist": "uniform", "lo", "hi"}``,
    ``{"dist": "lognormal", "median", "sigma", "lo", "hi"}`` or
    ``{"dist": "choice", "values", "weights"}``.
``arrivals``
    ``"poisson"`` or ``{"on_s", "off_s"}``: Poisson while on, silent
    while off, at the same mean ``rate``.
``shared_prefix``
    ``{"count", "length"}``: every prompt starts with one of ``count``
    seeded prefixes of ``length`` tokens (its own tokens follow).
``block``
    requests come in blocks of this many (default 32).  Every block
    holds the same set of lengths and of arrival gaps (the
    distribution's quantiles at the block's mid-points); the seed only
    permutes them and draws the token ids.  So every seed offers the
    same work in another order, and any stretch of a run sees nearly
    the same mix.
``schedule_seed``
    where given, the order of lengths and gaps is drawn from it and is
    the same for every ``--seed``; the seed then only draws the token
    ids (and the weights).  A tail over some hundred requests swings by
    a tenth with the order alone; a fixed order leaves the system's own
    jitter.
``why``
    one line on what the mix is for.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os
from statistics import NormalDist
from typing import Iterator, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


def load_mix(name: str, directory: Optional[str] = None) -> dict:
    path = os.path.join(directory or os.path.join(HERE, "traffic"),
                        name + ".json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("loop") not in ("steps", "open", "backlog"):
        raise ValueError(f"{path}: loop must be steps, open or backlog")
    return mix


def quantiles(dist: dict, n: int) -> List[int]:
    """``n`` lengths: the distribution's quantiles at (i + 0.5) / n."""
    us = [(i + 0.5) / n for i in range(n)]
    kind = dist["dist"]
    if kind == "uniform":
        lo, hi = dist["lo"], dist["hi"]
        return [min(hi, lo + int(u * (hi - lo + 1))) for u in us]
    if kind == "lognormal":
        nd = NormalDist()
        out = [dist["median"] * math.exp(dist["sigma"] * nd.inv_cdf(u))
               for u in us]
        return [int(min(dist["hi"], max(dist["lo"], round(x)))) for x in out]
    if kind == "choice":
        w = np.asarray(dist.get("weights") or [1] * len(dist["values"]),
                       float)
        edges = np.cumsum(w) / w.sum()
        return [int(dist["values"][int(np.searchsorted(edges, u))])
                for u in us]
    raise ValueError(f"unknown length distribution {kind!r}")


def _gaps(rate: float, n: int) -> List[float]:
    """``n`` exponential inter-arrival gaps of mean 1/rate: the
    quantiles at (i + 0.5) / n, rescaled so that they sum to n / rate."""
    g = np.asarray([-math.log(1.0 - (i + 0.5) / n) for i in range(n)])
    return list(g * (n / rate) / g.sum())


def _on_off(t_on: float, on_s: float, off_s: float) -> float:
    """Map time counted while the source is on to wall time."""
    return t_on + math.floor(t_on / on_s) * off_s


@dataclasses.dataclass
class Offered:
    """One request as the mix offers it; ``due_s`` is seconds after the
    window opens (0 for a backlog)."""

    index: int
    due_s: float
    prompt: List[int]
    max_new: int


def requests(mix: dict, seed: int, vocab_size: int) -> Iterator[Offered]:
    """The endless seeded sequence of requests of a serving mix."""
    if mix["loop"] not in ("open", "backlog"):
        raise ValueError("requests() is for the open and backlog loops")
    rng = np.random.RandomState(seed % (2 ** 32))
    order = (np.random.RandomState(int(mix["schedule_seed"]))
             if "schedule_seed" in mix else rng)
    block = int(mix.get("block", 32))
    plens = quantiles(mix["prompt_len"], block)
    nnews = quantiles(mix["max_new"], block)
    open_loop = mix["loop"] == "open"
    arrivals = mix.get("arrivals", "poisson")
    rate = float(mix["rate"]) if open_loop else None
    if open_loop and isinstance(arrivals, dict):
        duty = arrivals["on_s"] / (arrivals["on_s"] + arrivals["off_s"])
        gaps = _gaps(rate / duty, block)
    elif open_loop:
        gaps = _gaps(rate, block)
    shared = mix.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = [list(map(int, rng.randint(0, vocab_size,
                                              shared["length"])))
                    for _ in range(shared["count"])]
    t, index = 0.0, 0
    while True:
        p_order, n_order = order.permutation(block), order.permutation(block)
        g_order = order.permutation(block)
        for j in range(block):
            plen = plens[p_order[j]]
            if open_loop:
                t += gaps[g_order[j]]
                due = (_on_off(t, arrivals["on_s"], arrivals["off_s"])
                       if isinstance(arrivals, dict) else t)
            else:
                due = 0.0
            prompt: List[int] = []
            if prefixes:
                prompt = list(prefixes[int(order.randint(len(prefixes)))])
            own = max(1, plen - len(prompt))
            prompt = prompt + list(map(int, rng.randint(0, vocab_size, own)))
            yield Offered(index, due, prompt, int(nnews[n_order[j]]))
            index += 1
