"""The one traffic generator: turns a traffic file and a seed into requests.

A traffic file (``bench/traffic/<name>.json``) holds only parameters:

- ``loop``: ``"open"`` (arrivals on a schedule, whatever the system does)
  or ``"closed"`` (a backlog of ``backlog_per_lane`` x max_batch requests,
  each completion replaced at once).
- ``arrivals`` (open loop): ``{"dist": "poisson" | "gamma", "rate_per_s",
  "shape", "block"}``; gamma inter-arrival times with shape k have a
  coefficient of variation 1/sqrt(k).
- ``prompt_len`` / ``output_len``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max", "round_up_to"}``, ``{"dist": "choice", "values"}``
  or ``{"dist": "uniform", "min", "max"}`` (integers, both ends included),
  each with an optional ``block``.

Every seed gets the same multiset of sizes and gaps, in another order: each
block of consecutive requests takes the distribution's quantiles at
(i + 0.5) / n over the block's n, permuted by the seed. A spec's ``block``
sets the block's length: by default the whole window in the open loop and
``BLOCK`` requests in the closed loop. So the work in a window does not
change with the seed; the order, the bursts' positions and the token ids
do. A short block bounds how far the seed can move work in time: with
``arrivals.block`` 4, every 4 consecutive arrivals span the same time.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional

import numpy as np
from scipy import special

#: closed-loop requests are drawn in blocks that each hold the whole
#: stratified multiset, so any prefix a window consumes is balanced
BLOCK = 16
#: closed-loop requests generated per run (far more than a window takes)
CLOSED_POOL = 4096


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray          # int32 token ids
    max_new: int
    due: Optional[float]        # seconds after the window opens (open loop)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent numpy streams of one run seed (any integer)."""
    return np.random.default_rng([abs(int(seed)), int(seed < 0), stream])


def _ppf(spec: dict, u: np.ndarray) -> np.ndarray:
    dist = spec["dist"]
    if dist == "lognormal":
        return spec["median"] * np.exp(spec["sigma"] * special.ndtri(u))
    if dist == "uniform":
        lo, hi = spec["min"], spec["max"]
        return np.floor(lo + u * (hi - lo + 1))
    if dist == "choice":
        vals = np.asarray(spec["values"])
        return vals[np.minimum((u * len(vals)).astype(int), len(vals) - 1)]
    if dist == "exponential":
        return -np.log1p(-u) / spec["rate_per_s"]
    if dist == "gamma":
        k = spec["shape"]
        return special.gammaincinv(k, u) / (k * spec["rate_per_s"])
    raise ValueError(f"unknown distribution {dist!r}")


def _lengths(spec: dict, u: np.ndarray) -> np.ndarray:
    x = _ppf(spec, u)
    if "round_up_to" in spec:
        allowed = np.asarray(sorted(spec["round_up_to"]))
        idx = np.minimum(np.searchsorted(allowed, x, side="left"),
                         len(allowed) - 1)
        x = allowed[idx]
    x = np.ceil(x)
    if "min" in spec:
        x = np.maximum(x, spec["min"])
    if "max" in spec:
        x = np.minimum(x, spec["max"])
    return x.astype(np.int64)


def _stratified(n: int, rng: np.random.Generator) -> np.ndarray:
    return rng.permutation((np.arange(n) + 0.5) / n)


def _blocks(n: int, block: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` quantile positions, stratified within each block of ``block``
    (a shorter last block is stratified over its own length)."""
    sizes = [block] * (n // block) + ([n % block] if n % block else [])
    return np.concatenate([_stratified(k, rng) for k in sizes]) if n else np.zeros(0)


def length_values(spec: dict) -> List[int]:
    """Every length the spec can produce (warm-up covers each)."""
    if spec["dist"] == "choice":
        return sorted(int(v) for v in spec["values"])
    if "round_up_to" in spec:
        return sorted(int(v) for v in spec["round_up_to"])
    return list(range(int(spec["min"]), int(spec["max"]) + 1))


def arrival_gaps(traffic: dict) -> dict:
    arr = dict(traffic["arrivals"])
    if arr["dist"] == "poisson":
        arr["dist"] = "exponential"
    return arr


def generate(traffic: dict, seed: int, seconds: float, vocab: int) -> List[Request]:
    """The run's requests, in submission order."""
    if traffic["loop"] == "open":
        n = int(round(traffic["arrivals"]["rate_per_s"] * seconds))
        gaps = _ppf(arrival_gaps(traffic),
                    _blocks(n, traffic["arrivals"].get("block", n), rng_for(seed, 0)))
        due = np.cumsum(gaps)
        # the quantile midpoints' mean gap is a little under 1/rate; keep
        # every arrival inside the window all the same
        due = due * min(1.0, 0.999 * seconds / due[-1]) if n else due
        prompt = _lengths(traffic["prompt_len"],
                          _blocks(n, traffic["prompt_len"].get("block", n), rng_for(seed, 1)))
        out = _lengths(traffic["output_len"],
                       _blocks(n, traffic["output_len"].get("block", n), rng_for(seed, 2)))
    else:
        n = CLOSED_POOL
        due = [None] * n
        prompt = _lengths(traffic["prompt_len"],
                          _blocks(n, traffic["prompt_len"].get("block", BLOCK), rng_for(seed, 1)))
        out = _lengths(traffic["output_len"],
                       _blocks(n, traffic["output_len"].get("block", BLOCK), rng_for(seed, 2)))
    toks = rng_for(seed, 3)
    return [Request(i, toks.integers(1, vocab, size=int(prompt[i]), dtype=np.int32),
                    int(out[i]), None if due[i] is None else float(due[i]))
            for i in range(n)]


def backlog(traffic: dict, max_batch: int) -> int:
    """Requests a closed loop keeps outstanding."""
    return int(traffic["backlog_per_lane"]) * max_batch
