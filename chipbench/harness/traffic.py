"""One generator for every traffic mix: it reads a mix's parameters
(``chipbench/traffic/<name>.json``) and makes its requests from a seed.

Every seed gets the same multiset of sizes and of gaps between arrivals,
in another order: lengths and gaps are stratified quantiles of the mix's
distributions, ordered by the seed, and the token ids are drawn from the
seed. So runs with different seeds do the same amount of work, and a
difference between seeds is not a difference in load. With ``block``,
the order is balanced as well: each run of ``block`` consecutive requests
holds one value from each of ``block`` equal strata of every quantity, so
every stretch of the run carries the same load, whatever the seed.

Mix keys:

* ``prompt_len`` / ``output_len``: ``{"dist": "lognormal", "median" or
  "mean", "sigma", "min", "max"}``, clipped and rounded to whole tokens.
  A ``mean`` (what trace studies publish) places the median at
  ``mean / exp(sigma**2 / 2)``.
* ``arrivals``: ``{"kind": "poisson", "rate_per_s"}`` (open loop: each
  request is due at its scheduled offset, whatever the server does) or
  ``{"kind": "closed", "clients"}`` (each client sends its next request
  when its previous one completes, with no think time).
* ``ramp_s``: seconds of uncounted traffic before the window.
* ``drain_cap_s``: after the window, how long requests due in it may take
  to finish before they count as failed.
* ``pool``: closed loop only, how many requests the stratified set holds
  (taken in order and cycled).
* ``block`` (default 1): requests per balanced block (see above); the set
  is rounded up to a whole number of blocks.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List

import numpy as np

_STD_NORMAL = NormalDist()


@dataclasses.dataclass
class Traffic:
    kind: str                     # "poisson" | "closed"
    prompts: List[np.ndarray]     # int32 token ids, one per request
    max_new: List[int]
    offsets: List[float]          # poisson: due time from t0, seconds
    clients: int                  # closed: concurrency
    ramp_s: float
    drain_cap_s: float

    def lengths(self) -> List[int]:
        return [len(p) for p in self.prompts]


def _rng(seed: int, stream: int) -> np.random.Generator:
    # numpy seeds take any non-negative integer, so 64-bit seeds keep
    # every bit; ``stream`` separates the independent draws
    return np.random.default_rng([int(seed) & ((1 << 64) - 1), stream])


def quantiles(dist: Dict[str, Any], n: int) -> np.ndarray:
    """``n`` stratified quantiles (midpoints of n equal-probability bins)
    of a length distribution, clipped and rounded; sorted ascending."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {dist['dist']!r}")
    z = np.array([_STD_NORMAL.inv_cdf(float(x)) for x in u])
    sigma = dist["sigma"]
    median = dist["median"] if "median" in dist \
        else dist["mean"] / math.exp(sigma * sigma / 2)
    x = median * np.exp(sigma * z)
    return np.clip(np.rint(x), dist["min"], dist["max"]).astype(np.int64)


def exp_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` stratified quantiles of an exponential gap with mean 1/rate."""
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate


def count(mix: Dict[str, Any], seconds: float) -> int:
    """How many requests a run of ``seconds`` can send under ``mix``."""
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        horizon = mix["ramp_s"] + seconds + mix["drain_cap_s"]
        return int(math.ceil(arr["rate_per_s"] * horizon)) + 1
    if arr["kind"] == "closed":
        return int(mix["pool"])
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def order(values: np.ndarray, block: int,
          rng: np.random.Generator) -> np.ndarray:
    """``values`` (ascending, a whole number of blocks long) in an order
    drawn from ``rng`` in which block ``j`` (entries ``j * block`` to
    ``(j + 1) * block``) holds one value of each of ``block`` strata:
    runs of consecutive values of the sorted set."""
    if block <= 1:
        return rng.permutation(values)
    strata = np.asarray(values).reshape(block, -1)
    grid = np.stack([rng.permutation(s) for s in strata], axis=1)
    return np.concatenate([rng.permutation(row) for row in grid])


def generate(mix: Dict[str, Any], vocab: int, seed: int,
             seconds: float) -> Traffic:
    block = int(mix.get("block", 1))
    n = -(-count(mix, seconds) // block) * block
    plens = order(quantiles(mix["prompt_len"], n), block, _rng(seed, 1))
    outs = order(quantiles(mix["output_len"], n), block, _rng(seed, 2))
    tok = _rng(seed, 3)
    prompts = [tok.integers(0, vocab, size=int(m), dtype=np.int32)
               for m in plens]
    arr = mix["arrivals"]
    offsets: List[float] = []
    clients = 0
    if arr["kind"] == "poisson":
        gaps = order(exp_gaps(arr["rate_per_s"], n), block, _rng(seed, 4))
        offsets = np.cumsum(gaps).tolist()
    else:
        clients = int(arr["clients"])
    return Traffic(kind=arr["kind"], prompts=prompts,
                   max_new=[int(x) for x in outs], offsets=offsets,
                   clients=clients, ramp_s=float(mix["ramp_s"]),
                   drain_cap_s=float(mix["drain_cap_s"]))
