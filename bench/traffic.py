"""One generator for every traffic mix.

A mix is a data file, ``bench/traffic/<mix>.json``:

- ``loop``: ``"open"`` (independent users on a schedule) or ``"closed"``
  (clients that each wait for their previous request);
- open loop: ``arrivals`` (``"poisson"``); the arrival rate is the
  cell's, ``rate_per_s`` in ``bench/cells/<cell>.json``, since one mix
  serves every configuration and each sustains its own rate;
- closed loop: ``clients`` (a number, or ``"slots"`` for one client per
  decode slot) and ``requests_per_client``;
- ``prompt`` and ``output``: length distributions, each
  ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``.

Every seed offers the same work: the lengths are a fixed quantile grid of
their distribution and the gaps between arrivals a fixed quantile grid of
the exponential, in one fixed order.  The run's seed draws the token ids.
The order is fixed because it is part of the work: it decides which long
prompts queue behind each other.  On a TPU v5e chip, chat runs whose
seeds also ordered the requests spread by 30% in their 95th percentile of
time to first token, where two runs of one order agreed within 1%.
``bench/sweep.py`` reads the cell under other orders.
"""
from __future__ import annotations

import dataclasses
import math
import statistics

import numpy as np


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""
    idx: int
    prompt: list[int]
    max_new: int
    due: float | None = None     # open loop: seconds after the window opens
    client: int | None = None    # closed loop: the client that sends it


def quantile_grid(dist: dict, n: int) -> np.ndarray:
    """n lengths at the mid-quantiles (i + 1/2)/n of the distribution,
    rounded and clipped to [min, max]."""
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([statistics.NormalDist().inv_cdf((i + 0.5) / n)
                  for i in range(n)])
    x = np.exp(math.log(dist["median"]) + dist["sigma"] * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(n: int, rate: float) -> np.ndarray:
    """n gaps at the mid-quantiles of the exponential with this rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def generate(mix: dict, *, seed: int, seconds: float, vocab: int,
             slots: int, rate: float | None = None) -> list[Item]:
    """The requests of one run.  Open loop: round(rate * seconds) requests
    due inside [0, seconds).  Closed loop: clients * requests_per_client
    requests, dealt to the clients in order."""
    order = np.random.default_rng(0)
    rng = np.random.default_rng(int(seed))
    if mix["loop"] == "open":
        if mix.get("arrivals", "poisson") != "poisson":
            raise ValueError(f"unknown arrivals {mix['arrivals']!r}")
        if rate is None:
            raise ValueError("an open-loop mix needs the cell's rate_per_s")
        n = max(1, round(rate * seconds))
        gaps = order.permutation(exp_gaps(n, rate))
        # arrival i is due after the first i gaps, scaled so that the
        # (n+1)-th would fall exactly at the window's end
        due = (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())
        clients = [None] * n
    elif mix["loop"] == "closed":
        k = slots if mix["clients"] == "slots" else int(mix["clients"])
        n = k * int(mix["requests_per_client"])
        due = [None] * n
        clients = [i % k for i in range(n)]
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    plen = order.permutation(quantile_grid(mix["prompt"], n))
    olen = order.permutation(quantile_grid(mix["output"], n))
    return [Item(idx=i, prompt=rng.integers(0, vocab, int(plen[i])).tolist(),
                 max_new=int(olen[i]),
                 due=None if due[i] is None else float(due[i]),
                 client=clients[i])
            for i in range(n)]


def reorder(items: list[Item], order: int) -> list[Item]:
    """The same requests in another order (``order`` 0: as generated): an
    open loop's requests moved among the due times, a closed loop's among
    the clients' turns."""
    if order == 0:
        return items
    perm = np.random.default_rng([int(order), 2]).permutation(len(items))
    return [dataclasses.replace(items[j], idx=i, due=items[i].due,
                                client=items[i].client)
            for i, j in enumerate(perm)]
