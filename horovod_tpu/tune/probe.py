"""Paired-leg A/B measurement: the discipline of the autotuner's
in-situ probe race (`tune.insitu`).

A naive A/B on a shared noisy host crowns fake winners two ways:
monotone machine drift (thermal, cache warming) systematically favors
whichever leg runs second, and a single outlier sample swings a mean.
The discipline here kills both:

* legs run in TEMPORALLY ADJACENT PAIRS with alternating order
  (pair 0: A then B, pair 1: B then A, ...), so drift cancels across
  pairs instead of accumulating into one leg;
* the gate statistic is the MEDIAN of per-pair relative differences
  (outlier pairs cannot move it);
* pairs keep accumulating until the median is STABLE — median absolute
  deviation of the pair diffs <= ``mad_stop_pct`` — or the cap is hit
  (adaptive stop: quiet hosts converge in ``pairs_min`` pairs, noisy
  hosts buy resolution with wall clock).

The clock is injectable (``clock=``) so the discipline itself is
testable against a fake clock with no real legs at all.
"""

from __future__ import annotations

import dataclasses
import time

__all__ = ["PairedResult", "paired_compare", "median"]


def median(xs) -> float:
    """Upper median: sorted()[n // 2]."""
    xs = sorted(xs)
    if not xs:
        raise ValueError("median of empty sequence")
    return xs[len(xs) // 2]


@dataclasses.dataclass(frozen=True)
class PairedResult:
    """Outcome of one paired A/B race.

    ``median_pct`` is the median over pairs of ``(t_b - t_a) / t_a``
    in percent: POSITIVE means leg B is slower than leg A.
    """

    median_pct: float
    mad_pct: float          # median absolute deviation of the pair diffs
    pairs: int
    a_times: tuple          # per-pair leg-A seconds, chronological
    b_times: tuple
    converged: bool         # stopped on MAD stability, not the pair cap

    @property
    def b_wins(self) -> bool:
        return self.median_pct < 0.0


def paired_compare(leg_a, leg_b, *, pairs_min: int = 3, pairs_cap: int = 9,
                   mad_stop_pct: float = 0.75,
                   clock=time.perf_counter) -> PairedResult:
    """Race two zero-arg legs and return the paired-median verdict.

    Each leg callable runs one full measurement leg (including any
    device sync at its boundaries) and is timed here with ``clock``.
    Legs should be pre-warmed: the first invocation is already scored.
    """
    pairs_min = max(1, int(pairs_min))
    pairs_cap = max(pairs_min, int(pairs_cap))
    diffs: list[float] = []
    a_times: list[float] = []
    b_times: list[float] = []
    converged = False
    while True:
        p = len(diffs)
        order = ("a", "b") if p % 2 == 0 else ("b", "a")
        t = {}
        for which in order:
            fn = leg_a if which == "a" else leg_b
            t0 = clock()
            fn()
            t[which] = clock() - t0
        diffs.append((t["b"] - t["a"]) / t["a"] * 100.0)
        a_times.append(t["a"])
        b_times.append(t["b"])
        if len(diffs) >= pairs_min:
            med = median(diffs)
            spread = median([abs(d - med) for d in diffs])
            if spread <= mad_stop_pct:
                converged = True
                break
            if len(diffs) >= pairs_cap:
                break
    med = median(diffs)
    mad = median([abs(d - med) for d in diffs])
    return PairedResult(
        median_pct=med, mad_pct=mad, pairs=len(diffs),
        a_times=tuple(a_times), b_times=tuple(b_times),
        converged=converged,
    )
