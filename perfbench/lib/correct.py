"""The comparison that decides ``correct``: what the timed path
produced against the plain reference, each number beside its limit.
Limits come from the cell's file (perfbench/cells/<workload>.json),
where the readings they were set from are written down too.
"""
from __future__ import annotations

import math
import sys
from typing import Dict, List, Optional, Tuple

import numpy as np

Number = Tuple[str, float, Optional[float]]   # name, value, limit


def verdict(numbers: List[Number]) -> bool:
    """Every number with a limit is finite and within it."""
    ok = True
    for _, value, limit in numbers:
        if limit is None:
            continue
        if not math.isfinite(value) or value > limit:
            ok = False
    return ok


def as_json(numbers: List[Number]) -> dict:
    """The numbers compared, each beside its limit (what has no limit
    is read and printed, not compared)."""
    return {name: {"value": value, "limit": limit}
            for name, value, limit in numbers if limit is not None}


def print_numbers(numbers: List[Number], correct: bool):
    for name, value, limit in numbers:
        if limit is None:
            print(f"perfbench read {name}={value:.6g} (not compared)",
                  file=sys.stderr)
    for name, value, limit in numbers:
        if limit is not None:
            print(f"perfbench compared {name}={value:.6g} "
                  f"limit={limit:.6g}", file=sys.stderr)
    print(f"perfbench correct={str(correct).lower()}", file=sys.stderr,
          flush=True)


# -- serving -----------------------------------------------------------------

def pick_sample(finished: list, seed: int, n_requests: int) -> list:
    """The longest finished request and n-1 others drawn from the seed.
    finished: [{"ids": prompt, "out": served tokens, ...}]."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i]["ids"])
                                   + len(finished[i]["out"])))
    rest = order[1:]
    rng = np.random.default_rng([int(seed), 0xC4EC])
    rng.shuffle(rest)
    return [finished[i] for i in [order[0]] + rest[:n_requests - 1]]


def served_arrays(sample: list, n_ctx: int, block_rows: int):
    """Right-padded token rows, the next-token picks and the mask of
    positions whose next token was served."""
    rows = -(-len(sample) // block_rows) * block_rows
    tokens = np.zeros((rows, n_ctx), np.int32)
    nxt = np.zeros((rows, n_ctx), np.int32)
    mask = np.zeros((rows, n_ctx), bool)
    for i, r in enumerate(sample):
        seq = np.concatenate([np.asarray(r["ids"], np.int32),
                              np.asarray(r["out"], np.int32)])
        p, n = len(r["ids"]), len(r["out"])
        fed = seq[:n_ctx]
        tokens[i, :fed.size] = fed
        nxt[i, :seq.size - 1] = seq[1:][:n_ctx]
        mask[i, p - 1:min(p + n - 1, n_ctx)] = True
    return tokens, nxt, mask


def by_length(sample: list, n_ctx: int, step: int = 128,
              block_tokens: int = 4096):
    """The sample in groups of one padded length (a multiple of `step`
    that holds prompt and served tokens), so that the reference does
    not compute n_ctx positions for a request of two hundred. Yields
    (length, requests, rows a block)."""
    groups: Dict[int, list] = {}
    for r in sample:
        t = min(n_ctx, -(-(len(r["ids"]) + len(r["out"])) // step) * step)
        groups.setdefault(t, []).append(r)
    for t in sorted(groups):
        yield t, groups[t], max(1, block_tokens // t)


# a served position is a near tie where the reference's best logit
# leads its second by less than this
NEAR_TIE = 0.1


def _gap_numbers(gaps, margins, limits: dict,
                 keep: Optional[dict] = None) -> List[Number]:
    """From the gap of every served position (how far the served
    token's logit lies below the reference's best) and the reference's
    own margin there (best over second):
    - the widest gap: one draw from a tail, swings from seed to seed;
    - the mean gap over all the tokens compared: steadier, but it
      scales with how many close calls the seed's weights make;
    - the gap per near tie: the gaps' sum over the count of positions
      where the reference's margin is under NEAR_TIE. A token other
      than the reference's first is served where noise outruns the
      margin, so with near ties of density rho the mean gap is
      rho * s^2 / 4 for noise of deviation s on a logit difference; the
      count of near ties is rho * NEAR_TIE, read off the reference
      alone, and the quotient, s^2 / (4 NEAR_TIE), is free of rho."""
    gaps = np.concatenate(gaps) if gaps else np.zeros(0)
    margins = np.concatenate(margins) if margins else np.zeros(0)
    if keep is not None:        # tools/readings.py writes them down
        keep["gaps"] = [float(g) for g in gaps]
        keep["margins"] = [float(m) for m in margins]
    near = int((margins < NEAR_TIE).sum())
    total = float(gaps.sum())
    if near:
        per_tie = total / near
    else:
        per_tie = 0.0 if total == 0.0 else float("inf")
    out = [("served_token_gap", float(gaps.max())),
           ("served_token_gap_mean", total / gaps.size),
           ("served_gap_per_near_tie", per_tie),
           ("served_near_ties_share", near / gaps.size),
           ("served_tokens_compared", float(gaps.size))]
    return [(name, value, limits.get(name)) for name, value in out]


def compare_serve(reference, params, cfg: dict, sample: list,
                  n_short: int, limits: dict,
                  keep: Optional[dict] = None) -> List[Number]:
    """Over the sample: the numbers of `_gap_numbers`; and how many
    finished requests came back with another count of tokens than they
    asked for (exact)."""
    numbers: List[Number] = [("requests_short_of_tokens", float(n_short),
                              0.0)]
    if not sample:
        return numbers + [(name, float("inf"), limits.get(name))
                          for name in ("served_token_gap",
                                       "served_token_gap_mean",
                                       "served_gap_per_near_tie")]
    gaps, margins = [], []
    for t, group, rows in by_length(sample, cfg["n_ctx"]):
        tokens, nxt, mask = served_arrays(group, t, rows)
        best, _, picked, margin = reference.score(
            params, cfg, tokens, nxt[None], block_rows=rows)
        gaps.append((best - picked[0])[mask])
        margins.append(margin[mask])
    return numbers + _gap_numbers(gaps, margins, limits, keep)


def control_serve(reference, params, cfg: dict, sample: list,
                  precision: str, limits: dict,
                  keep: Optional[dict] = None) -> List[Number]:
    """The control: the reference in a lower precision in the program's
    place. At each served position of the same prompts and tokens, the
    gap of the token that the lower precision puts first; the same
    numbers as `compare_serve` reads of the program, beside the same
    limits, for `verdict` to fail."""
    gaps, margins = [], []
    for t, group, rows in by_length(sample, cfg["n_ctx"]):
        tokens, nxt, mask = served_arrays(group, t, rows)
        first_lo = reference.score(params, cfg, tokens, nxt[None],
                                   precision=precision,
                                   block_rows=rows)[1]
        best, _, picked, margin = reference.score(
            params, cfg, tokens, first_lo[None], block_rows=rows)
        gaps.append((best - picked[0])[mask])
        margins.append(margin[mask])
    return _gap_numbers(gaps, margins, limits, keep)
