"""One general traffic generator, driven by a cell's `traffic` parameters.

Every seed gets the SAME set of sizes and arrival gaps, in another
order: a length distribution is sampled on a fixed grid of quantiles
(so the multiset of lengths is a property of the file, not of the
seed) and the seed only permutes it and draws the token ids. Runs of
different seeds then do the same work and differ in order alone.

    "traffic": {
      "loop": "closed", "clients": 32            # or "open", "rate_per_s", "lead_in_s"
      "requests": 256, "block": 32,              # closed: how many exist; a permutation per block of the grid
      "prompt_tokens": {"dist": "uniform", "lo": 64, "hi": 256},
      "output_tokens": {"dist": "lognormal", "median": 100, "sigma": 0.7, "lo": 16, "hi": 256},
      "temperatures": [0.0, 0.8]                 # dealt round-robin over the grid
    }

An open loop's arrival gaps are the same kind of grid, of the
exponential distribution, scaled so that one block lasts exactly
block / rate_per_s seconds (midpoint quantiles cut the tail, so the
unscaled grid would run about half a percent fast). With `lead_in_s`
and the window whole numbers of blocks, every window of every seed
holds the same requests and the same gaps, in another order.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def _quantile(dist, u):
    kind = dist["dist"]
    if kind == "uniform":
        return dist["lo"] + (dist["hi"] - dist["lo"]) * u
    if kind == "lognormal":
        x = dist["median"] * math.exp(dist["sigma"] * NormalDist().inv_cdf(u))
        return min(max(x, dist["lo"]), dist["hi"])
    if kind == "exponential":
        return -dist["mean"] * math.log1p(-u)
    raise ValueError("unknown distribution %r" % (kind,))


def grid(dist, n):
    """n values of `dist` at the quantiles (i + 1/2) / n."""
    return [_quantile(dist, (i + 0.5) / n) for i in range(n)]


def _blocks(values, n_total, block, rng):
    """`values` (one block's grid) permuted afresh for each block."""
    out = []
    while len(out) < n_total:
        out.extend(values[i] for i in rng.permutation(block))
    return out[:n_total]


def n_requests(traffic, seconds):
    """An open loop's schedule spans its lead-in and the window at the
    file's rate; a closed loop's file says how many requests exist."""
    if traffic["loop"] == "open":
        return int(math.ceil(float(traffic["rate_per_s"])
                             * (float(traffic["lead_in_s"]) + seconds)))
    return int(traffic["requests"])


def generate(traffic, vocab, seed, seconds):
    """-> list of requests {prompt, max_new, temperature, seed, due_s},
    in submission order. `due_s` is None in a closed loop."""
    rng = np.random.default_rng(int(seed))
    n = n_requests(traffic, seconds)
    block = int(traffic.get("block", n))
    p_len = _blocks([int(round(v)) for v in grid(traffic["prompt_tokens"], block)],
                    n, block, rng)
    o_len = _blocks([int(round(v)) for v in grid(traffic["output_tokens"], block)],
                    n, block, rng)
    temps_grid = [traffic["temperatures"][i % len(traffic["temperatures"])]
                  for i in range(block)]
    temps = _blocks(temps_grid, n, block, rng)
    due = [None] * n
    if traffic["loop"] == "open":
        mean = 1.0 / float(traffic["rate_per_s"])
        one = grid({"dist": "exponential", "mean": mean}, block)
        scale = block * mean / sum(one)
        gaps = _blocks([g * scale for g in one], n, block, rng)
        due = list(np.cumsum(gaps) - np.asarray(gaps))  # gap i follows i
    out = []
    for i in range(n):
        prompt = rng.integers(0, vocab, max(1, p_len[i]), dtype=np.int32)
        out.append({"prompt": prompt, "max_new": int(o_len[i]),
                    "temperature": float(temps[i]),
                    "seed": int(rng.integers(0, 2 ** 31 - 1)),
                    "due_s": None if due[i] is None else float(due[i])})
    return out


def prompt_buckets(traffic, min_bucket, max_len, seconds):
    """The power-of-two prefill buckets this traffic's prompts can hit."""
    n = int(traffic.get("block", n_requests(traffic, seconds)))
    out = set()
    for v in grid(traffic["prompt_tokens"], n):
        b = min_bucket
        while b < int(round(v)):
            b *= 2
        out.add(min(b, max_len))
    return sorted(out)
