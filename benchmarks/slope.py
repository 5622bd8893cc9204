"""Shared slope-method timing for the device benchmarks.

A single run carries a fixed dispatch+fetch cost that varies run-to-run —
often more than the measured workload.  The slope method cancels it: time the same workload at R and
m·R rounds and take

    per_round = (T(mR) − T(R)) / ((m − 1)·R)
    steady    = per_round · R          (the number to report)
    fixed     = T(R) − steady          (the cancelled overhead)

``m`` escalates adaptively until the span T(mR) − T(R) dominates the
jitter: sizing m from T(R) alone fails exactly when the fixed cost
dominates T(R) (the regime the method exists for).
"""

from __future__ import annotations

import time
from typing import Callable, NamedTuple


class SlopeResult(NamedTuple):
    steady_s: float
    fixed_s: float
    # measurement-quality telemetry (ADVICE r3): when escalation exits at
    # max_mult with span < min_span_s, the estimate may still be dominated
    # by run-to-run jitter — callers should mark such rows as noisy instead of
    # recording them silently (the round-3 rcv1-permuted row's clamped
    # fixed_s=0 had exactly this signature)
    span_s: float = 0.0
    degraded: bool = False


def slope_time(
    make_run: Callable[[int], Callable[[], object]],
    rounds: int,
    min_span_s: float = 1.0,
    reps: int = 3,
    max_mult: int = 32,
) -> SlopeResult:
    """SlopeResult(steady_s for ``rounds``, fixed_s, span_s, degraded).
    ``make_run(nr)`` returns a 0-arg callable executing exactly ``nr``
    rounds (compiled on first call; each point is best-of-``reps`` warm
    runs)."""

    def best(fn):
        fn()  # compile / warm
        b = None
        for _ in range(reps):
            t0 = time.perf_counter()
            fn()
            dt = time.perf_counter() - t0
            b = dt if b is None or dt < b else b
        return b

    t_lo = best(make_run(rounds))
    m = 4
    while True:
        t_hi = best(make_run(m * rounds))
        span = t_hi - t_lo
        if span >= min_span_s or m >= max_mult:
            break
        m *= 2
    per_round = max(0.0, span / ((m - 1) * rounds))
    steady = per_round * rounds
    return SlopeResult(steady, max(0.0, t_lo - steady), span,
                       degraded=span < min_span_s)
