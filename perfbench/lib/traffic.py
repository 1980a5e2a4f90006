"""The one traffic generator: a data file of parameters in, a schedule
of requests out. Every seed gets the same set of lengths and the same
set of gaps between arrivals (stratified quantiles of the file's
distributions), in an order of its own and with token ids of its own;
so the seed changes which requests meet in the engine, not how much
work a run holds. The gaps are the exponential's quantiles, shuffled:
the count of arrivals and their total time are the same for every
seed, which is steadier than a Poisson process drawn anew (its count in
a window would swing by the root of the count). Bursts want a cell of
their own.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List, Optional

import numpy as np


@dataclass
class Item:
    due_s: Optional[float]      # open loop: when it is due; closed: None
    ids: np.ndarray             # int32 prompt
    max_new_tokens: int


def _quantiles(spec: dict, n: int) -> np.ndarray:
    """n stratified values of one length distribution, as integers."""
    u = (np.arange(n) + 0.5) / n
    dist = spec["dist"]
    if dist == "fixed":
        vals = np.full(n, float(spec["value"]))
    elif dist == "uniform":
        vals = spec["min"] + u * (spec["max"] - spec["min"])
    elif dist == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    vals = np.clip(np.rint(vals), spec.get("min", 1), spec.get("max"))
    return vals.astype(np.int64)


def _shuffled(spec: dict, n: int, block: int, rng) -> np.ndarray:
    """n lengths in blocks of `block`: every block holds the same
    stratified set, shuffled by itself. A window that takes any few
    hundred of them in a row then sees the same mix of lengths,
    whatever the seed and wherever it starts."""
    vals = _quantiles(spec, block)
    return np.concatenate([rng.permutation(vals)
                           for _ in range(-(-n // block))])[:n]


def serve_items(traffic: dict, vocab_size: int, seed: int,
                horizon_s: float) -> List[Item]:
    """Open loop: requests due over [0, horizon_s), one at a time at
    the file's fixed rate. Closed loop: a pool the clients draw from in
    order (due_s None)."""
    rng = np.random.default_rng([int(seed), 0x5e57])
    if traffic["loop"] == "open":
        rate = float(traffic["rate_per_s"])
        n = max(int(round(rate * horizon_s)), 1)
        u = (np.arange(n) + 0.5) / n
        due = np.cumsum(rng.permutation(-np.log1p(-u) / rate))
    else:
        n = int(traffic["pool_requests"])
        due = None
    block = int(traffic.get("stratify_block", n))
    prompts = _shuffled(traffic["prompt"], n, block, rng)
    outputs = _shuffled(traffic["output"], n, block, rng)
    outputs = np.minimum(outputs, int(traffic["max_total"]) - prompts)
    if (outputs < 1).any():
        raise ValueError("traffic file lets a prompt fill max_total")
    return [Item(None if due is None else float(due[i]),
                 rng.integers(0, vocab_size, int(prompts[i]),
                              dtype=np.int32), int(outputs[i]))
            for i in range(n)]


def jax_key(seed: int):
    """A PRNG key from any seed up to 2**63: the low 31 bits seed it,
    the rest is folded in (jax seeds are 32-bit without x64)."""
    import jax
    seed = int(seed)
    return jax.random.fold_in(jax.random.key(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)
