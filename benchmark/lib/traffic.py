"""One general traffic generator: a mix is a data file of parameters.

Every seed gets the SAME requests and arrival gaps, in another order: a
mix names distributions, the generator takes ``block`` evenly spaced
quantiles of each (a stratified sample, so no seed draws a heavier tail
than another), pairs prompt and answer lengths by a fixed rule, and the
seed only orders them, block after block, and draws the token ids; the
order keeps one request of every ``strata``-quantile of prompt length in
each run of ``strata`` requests.  Two runs with different seeds then do
the same work in a different order, which is what keeps a cell's spread
down to the system's own.

A mix (the ``traffic`` object of ``benchmark/workloads/<cell>.json``)::

    {"arrivals": {"kind": "backlog"}                 # all due at 0
               | {"kind": "poisson", "rate_per_s": 4.0},
     "prompt_len": {"dist": "lognormal", "median": 256, "sigma": 1.0,
                    "min": 32, "max": 2048},
     "output_len": {"dist": "lognormal", ...} | {"dist": "fixed", "value": 64}
                 | {"dist": "uniform", "min": 32, "max": 64},
     "block": 64, "strata": 8}

Nothing here imports JAX or the program.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One request as the generator offers it."""

    rid: int
    due_s: float          # seconds after the generator starts offering
    prompt: tuple         # token ids
    max_new_tokens: int


def quantile_sizes(spec: dict, n: int) -> list[int]:
    """``n`` evenly spaced quantiles ((i + 0.5) / n) of the length
    distribution ``spec``, rounded and clipped to its min and max."""
    qs = [(i + 0.5) / n for i in range(n)]
    dist = spec["dist"]
    if dist == "fixed":
        vals = [float(spec["value"])] * n
    elif dist == "uniform":
        vals = [spec["min"] + q * (spec["max"] - spec["min"]) for q in qs]
    elif dist == "lognormal":
        nd = NormalDist()
        vals = [spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(q))
                for q in qs]
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    lo = spec.get("min", 1)
    hi = spec.get("max", float("inf"))
    return [int(min(max(round(v), lo), hi)) for v in vals]


def quantile_gaps(arrivals: dict, n: int) -> list[float]:
    """Inter-arrival gaps of one block: ``n`` quantiles of the
    exponential law at ``rate_per_s``, rescaled so that a block lasts
    exactly ``n / rate`` seconds.  A backlog has no gaps."""
    kind = arrivals["kind"]
    if kind == "backlog":
        return [0.0] * n
    if kind == "poisson":
        rate = float(arrivals["rate_per_s"])
        gaps = [-math.log(1.0 - (i + 0.5) / n) / rate for i in range(n)]
        scale = (n / rate) / sum(gaps)
        return [g * scale for g in gaps]
    raise ValueError(f"unknown arrival kind {kind!r}")


def request_set(mix: dict) -> list:
    """The block's ``(prompt_len, output_len)`` pairs, the SAME for every
    seed: prompt quantile i (ascending) goes with output quantile
    ``(i * a) mod block`` for the odd ``a`` nearest 0.618 * block, which
    spreads long answers evenly over short and long prompts.  Sorted by
    prompt length."""
    block = int(mix.get("block", 64))
    prompts = quantile_sizes(mix["prompt_len"], block)
    outputs = quantile_sizes(mix["output_len"], block)
    a = max(1, int(0.618 * block)) | 1
    while math.gcd(a, block) != 1:
        a += 2
    return [(prompts[i], outputs[(i * a) % block]) for i in range(block)]


def block_order(rng, block: int, strata: int) -> list:
    """An order of one block's requests (indices into the set sorted by
    prompt length) in which every run of ``strata`` consecutive requests
    holds one request of each ``strata``-quantile of prompt length: the
    seed permutes within the strata and within each run, so that no seed
    puts the long prompts side by side."""
    strata = max(1, min(strata, block))
    per = block // strata
    groups = [list(rng.permutation(range(k * per, (k + 1) * per)))
              for k in range(strata)]
    rest = list(range(strata * per, block))
    order = []
    for j in range(per):
        run = [g[j] for g in groups]
        order += [run[i] for i in rng.permutation(len(run))]
    return [int(i) for i in order + [rest[i] for i in
                                      rng.permutation(len(rest))]]


def stream(mix: dict, seed: int, vocab_size: int, first_rid: int = 0):
    """The mix's requests under ``seed``, one after another, without end:
    a pure function of its arguments.  Every block holds the same
    requests (:func:`request_set`) and the same gaps; the seed orders
    them (:func:`block_order`) and draws the token ids."""
    block = int(mix.get("block", 64))
    pairs = request_set(mix)
    gaps = quantile_gaps(mix["arrivals"], block)
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32])
    rid, due = first_rid, 0.0
    while True:
        order = block_order(rng, block, int(mix.get("strata", 8)))
        g_order = rng.permutation(block)
        for j in range(block):
            due += gaps[g_order[j]]
            n_prompt, n_out = pairs[order[j]]
            toks = rng.integers(1, vocab_size, n_prompt)
            yield Arrival(rid=rid, due_s=due,
                          prompt=tuple(int(t) for t in toks),
                          max_new_tokens=n_out)
            rid += 1


def generate(mix: dict, seed: int, vocab_size: int, count: int) -> list:
    """The first ``count`` requests of :func:`stream`."""
    return list(itertools.islice(stream(mix, seed, vocab_size), count))


def mix_means(mix: dict) -> dict:
    """Mean prompt and output length of the mix (of its quantile set)."""
    block = int(mix.get("block", 64))
    p = quantile_sizes(mix["prompt_len"], block)
    o = quantile_sizes(mix["output_len"], block)
    return {"prompt_mean": sum(p) / block, "output_mean": sum(o) / block,
            "prompt_max": max(p), "output_max": max(o)}


def pctl(values, q: float) -> float:
    """Nearest-rank percentile, as ``serving/loadgen.pctl`` means it: the
    smallest value with at least ``q`` of the sample at or below it."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q * len(vals)))
    return float(vals[rank - 1])
