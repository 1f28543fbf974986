"""The one traffic generator: reads a mix's parameters and draws its
requests from a seed.

Every seed gets the same work in the same order.  Lengths are the
quantiles of their distributions at the midpoints of ``block`` equal
strata, and arrival gaps the means of those strata, so each consecutive
block of ``block`` requests holds exactly one of each.  Within each
block they are shuffled, and prompt and output lengths paired, by one
fixed draw (``ORDER``) that every run shares; ``--seed`` draws the
token ids.  A window holds tens of requests, and when the seed drew the
order as well, the order alone moved the p95 of time to first token by
a third from seed to seed on a TPU v5e.

A mix file (``bench/traffic/<name>.json``) holds::

    arrivals  {"kind": "poisson", "rate_per_s": r, "lead_s": l}
                                                    open loop, due times;
              the first ``l`` seconds of arrivals fall before the window
              opens and are served as set-up, so that the window opens
              on a server at its steady state
              {"kind": "backlog", "requests": n}    all due at time 0
    prompt    {"dist": "lognormal", "median", "sigma", "min", "max"}
              {"dist": "uniform", "min", "max"}
    output    the same kinds, or {"dist": "fill_to", "total": t}
              (max_new = t - prompt length)
    block     strata per block
    engine    the server's settings for this mix
    check     how many finished requests the output check compares
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


@dataclasses.dataclass
class Request:
    index: int
    due_s: float            # seconds after the window opens (< 0: lead)
    prompt: np.ndarray      # int32 token ids
    max_new: int


#: The seed of the one draw that orders lengths and gaps in every run.
ORDER = 0


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """A numpy generator for one use of ``seed``; any non-negative whole
    number, 64 bits and more."""
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32, stream])


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratum-midpoint quantiles of a length distribution."""
    u = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif kind == "uniform":
        x = spec["min"] + u * (spec["max"] - spec["min"])
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _blocked(values: np.ndarray, count: int, rng: np.random.Generator
             ) -> np.ndarray:
    """``count`` values: whole blocks of ``values``, each shuffled."""
    reps = -(-count // len(values))
    out = np.concatenate([rng.permutation(values) for _ in range(reps)])
    return out[:count]


def request_count(mix: dict, seconds: float) -> int:
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        span = arr.get("lead_s", 0.0) + seconds
        return max(1, int(round(arr["rate_per_s"] * span)))
    if arr["kind"] == "backlog":
        return int(arr["requests"])
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def generate(mix: dict, seed: int, seconds: float, vocab: int
             ) -> List[Request]:
    """The mix's requests for one run, in due order."""
    n = request_count(mix, seconds)
    block = int(mix.get("block", 16))
    prompts = _blocked(_quantiles(mix["prompt"], block), n,
                       rng_for(ORDER, 1))
    out = mix["output"]
    if out["dist"] == "fill_to":
        max_new = out["total"] - prompts
    else:
        max_new = _blocked(_quantiles(out, block), n, rng_for(ORDER, 2))
    arr = mix["arrivals"]
    if arr["kind"] == "poisson":
        # Exponential gaps, each the mean of its stratum, so the mean
        # gap is 1/rate exactly: over [a, b] of u, the mean of
        # -ln(1-u) is (G(b) - G(a)) / (b - a), G(u) = (1-u)(ln(1-u) - 1).
        edges = np.arange(block + 1) / block
        one_minus = 1.0 - edges
        with np.errstate(divide="ignore", invalid="ignore"):
            g = np.where(one_minus > 0,
                         one_minus * (np.log(one_minus) - 1.0), 0.0)
        gaps = np.diff(g) * block / arr["rate_per_s"]
        gaps = _blocked(gaps, n, rng_for(ORDER, 3))
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]]) \
            - arr.get("lead_s", 0.0)
    else:
        due = np.zeros(n)
    tok_rng = rng_for(seed, 4)
    return [Request(i, float(due[i]),
                    tok_rng.integers(0, vocab, size=int(prompts[i]),
                                     dtype=np.int32),
                    int(max_new[i]))
            for i in range(n)]


def chunk_widths(mix: dict) -> List[int]:
    """Every prompt-chunk width the mix makes the server run: the
    page-rounded tail of each of its prompt lengths, and a full chunk
    where a prompt is longer than one."""
    eng = mix["engine"]
    chunk, page = eng["prefill_chunk"], eng["page_size"]
    block = int(mix.get("block", 16))
    widths = set()
    for plen in _quantiles(mix["prompt"], block).tolist():
        tail = (plen - 1) % chunk + 1
        widths.add(math.ceil(tail / page) * page)
        if plen > chunk:
            widths.add(chunk)
    return sorted(widths)
