"""One generator for every traffic mix: a data file of parameters in, a
request schedule out.

Each length is a lognormal with the stated median, clipped to the stated
range [min, max].  The end of the range farther from the median lies two
sigma from it: sigma = max(ln(max / median), ln(median / min)) / 2, so
both ends are reached within a cycle of 32 or more.  Inter-arrival gaps of an open
loop are exponential, as in a Poisson process at ``rate_per_s``.

Steadiness by construction: a cycle of ``cycle`` blocks of ``block``
requests holds the stratified quantiles of each length (and of the gaps)
at (i + 1/2) / (block * cycle), tails included, so every seed asks the
same work of the system over a cycle.  The quantiles fall into ``block``
runs of ``cycle`` adjacent ones, and each block takes one of every run, so
each block is a cross-section of the whole distribution and long requests
do not pile up.  The seed decides which member of a run each block gets,
the order of the requests and gaps within a block, the pairing of prompt
and output lengths, and the token ids.

Arrival helpers follow ``harness/trace.py`` (exponential gaps of a Poisson
process) and percentiles follow ``core/telemetry.percentile``; both are
copied here so that the yardstick does not move with the program.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

_NORMAL = NormalDist()


@dataclasses.dataclass
class Planned:
    """One request of the schedule."""
    cls: str
    prompt: np.ndarray            # int32 token ids
    max_new: int
    due_s: Optional[float]        # offset from the window's start (open loop)


def percentile(samples: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile (as ``core/telemetry.percentile``)."""
    xs = sorted(samples)
    if not xs:
        raise ValueError("percentile of no samples")
    if len(xs) == 1:
        return xs[0]
    pos = (q / 100.0) * (len(xs) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    return xs[lo] * (1.0 - frac) + xs[hi] * frac


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for one named stream of a run's seed."""
    return np.random.default_rng([int(seed) & (2**64 - 1), *stream])


def sigma_of(spec: Dict[str, Any]) -> float:
    """The lognormal's sigma: the end of [min, max] farther from the
    median lies two sigma from it."""
    med = float(spec["median"])
    return max(math.log(float(spec["max"]) / med),
               math.log(med / float(spec["min"]))) / 2.0


def lognormal_quantiles(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` stratified lengths, ascending: the quantiles at (i + 1/2)/n
    of median·exp(sigma·z), rounded and clipped to [min, max].  A
    ``fixed`` spec gives ``n`` copies of its length."""
    if "fixed" in spec:
        return [int(spec["fixed"])] * n
    med, sig = float(spec["median"]), sigma_of(spec)
    lo, hi = int(spec["min"]), int(spec["max"])
    out = []
    for i in range(n):
        z = _NORMAL.inv_cdf((i + 0.5) / n)
        out.append(int(min(max(round(med * math.exp(sig * z)), lo), hi)))
    return out


def exponential_gaps(n: int, span_s: float) -> List[float]:
    """``n`` stratified gaps of a Poisson process, ascending, scaled to
    sum to ``span_s`` (rate n/span_s)."""
    raw = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = span_s / sum(raw)
    return [g * scale for g in raw]


def spread(values: Sequence[Any], per_block: int, cycle: int,
           rng: np.random.Generator) -> List[List[Any]]:
    """Deal ``per_block · cycle`` ascending values to ``cycle`` blocks:
    block b gets one value of each run of ``cycle`` adjacent ones, which
    one drawn from ``rng``, in an order drawn from ``rng``."""
    if len(values) != per_block * cycle:
        raise ValueError(f"{len(values)} values for {per_block} x {cycle}")
    pick = rng.permuted(np.tile(np.arange(cycle), (per_block, 1)), axis=1)
    return [[values[j * cycle + int(pick[j, b])]
             for j in rng.permutation(per_block)] for b in range(cycle)]


def _class_counts(classes: List[Dict[str, Any]], n: int) -> List[int]:
    counts = [int(round(c["share"] * n)) for c in classes[:-1]]
    counts.append(n - sum(counts))
    if min(counts) < 0:
        raise ValueError(f"class shares do not fit {n} requests")
    return counts


def make_cycle(traffic: Dict[str, Any], seed: int, cycle: int,
               vocab: int) -> List[List[Planned]]:
    """The blocks of cycle ``cycle``: the cycle's stratified lengths dealt
    to its blocks, each block shuffled, token ids drawn from the seed."""
    n, k = int(traffic["block"]), int(traffic["cycle"])
    rng = seed_rng(seed, 1, cycle & 0xFFFFFFFF)
    blocks: List[List[Planned]] = [[] for _ in range(k)]
    for cls, count in zip(traffic["classes"],
                          _class_counts(traffic["classes"], n)):
        if count == 0:
            continue
        segs = [spread(lognormal_quantiles(seg, count * k), count, k, rng)
                for seg in cls["prompt"]]
        outs = spread(lognormal_quantiles(cls["output"], count * k),
                      count, k, rng)
        for b in range(k):
            for j in range(count):
                parts = []
                for seg, lens in zip(cls["prompt"], segs):
                    lo, hi = seg.get("tokens", (0, vocab))
                    if not 0 <= lo < hi <= vocab:
                        raise ValueError(f"token range {lo, hi} outside "
                                         f"the vocabulary of {vocab}")
                    parts.append(rng.integers(lo, hi, size=lens[b][j],
                                              dtype=np.int32))
                blocks[b].append(Planned(cls["name"], np.concatenate(parts),
                                         outs[b][j], None))
    return [[blk[i] for i in rng.permutation(len(blk))] for blk in blocks]


def cycle_span(traffic: Dict[str, Any]) -> float:
    """Seconds one cycle of an open loop spans: block · cycle / rate."""
    return (int(traffic["block"]) * int(traffic["cycle"])
            / float(traffic["rate_per_s"]))


def open_schedule(traffic: Dict[str, Any], seed: int, vocab: int,
                  first_cycle: int, cycles: int) -> List[Planned]:
    """``cycles`` cycles of an open loop from ``first_cycle`` on.  Cycle c
    is due within [c·span, (c+1)·span): its requests in block order, with
    the cycle's stratified exponential gaps dealt to the blocks as the
    lengths are.  A negative cycle is the ramp before the window."""
    n, k = int(traffic["block"]), int(traffic["cycle"])
    span = cycle_span(traffic)
    out: List[Planned] = []
    for c in range(first_cycle, first_cycle + cycles):
        blocks = make_cycle(traffic, seed, c, vocab)
        gaps = spread(exponential_gaps(n * k, span), n, k,
                      seed_rng(seed, 2, c & 0xFFFFFFFF))
        t = c * span
        for blk, gs in zip(blocks, gaps):
            for r, g in zip(blk, gs):
                r.due_s = t
                t += g
            out.extend(blk)
    return out


def closed_stream(traffic: Dict[str, Any], seed: int, vocab: int):
    """An endless closed-loop request stream, cycle after cycle."""
    c = 0
    while True:
        for blk in make_cycle(traffic, seed, c, vocab):
            yield from blk
        c += 1
