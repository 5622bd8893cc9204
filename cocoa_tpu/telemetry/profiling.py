"""Round-windowed ``jax.profiler`` capture (``--profile``).

A whole-run trace of a production run is dominated by compile + warmup
and can reach GBs; what a perf question usually needs is a few
steady-state rounds.  :class:`RoundWindowProfiler` subscribes to the
telemetry event bus and starts/stops ``jax.profiler`` when the
``round_eval`` stream crosses the requested round bounds — which works on
the device-resident driver precisely BECAUSE the io_callback bridge emits
evals while the ``lax.while_loop`` is still running (a post-hoc trigger
would fire after the loop already finished).  On the fallback (replayed)
bridge the events arrive at the end-of-run fetch, so the window degrades
to a no-op capture — live streaming is what makes windowed capture real.

What such a capture holds of the program itself: the ``cocoa/<phase>``
host spans and the ``cocoa_*`` device scopes (telemetry/tracing.py).  The
reduction of a trace to numbers is the benchmark's (chipbench/), not this
package's.
"""

from __future__ import annotations

import os


def parse_profile_flag(value: str):
    """``--profile=DIR`` or ``--profile=DIR,START,STOP`` →
    (dir, start_round|None, stop_round|None)."""
    parts = str(value).split(",")
    if len(parts) == 1:
        return parts[0], None, None
    if len(parts) != 3:
        raise ValueError(
            f"--profile takes DIR or DIR,START,STOP (round window), got "
            f"{value!r}")
    try:
        start, stop = int(parts[1]), int(parts[2])
    except ValueError:
        raise ValueError(
            f"--profile window bounds must be round numbers, got {value!r}")
    if start < 1 or stop <= start:
        raise ValueError(
            f"--profile window needs 1 <= START < STOP, got {value!r}")
    return parts[0], start, stop


class RoundWindowProfiler:
    """Bus subscriber that traces the rounds in ``[start, stop)``.

    The trace starts at the first ``round_eval`` with t >= start and stops
    at the first with t >= stop (round numbers are only observable at the
    ``debugIter`` eval cadence, so the window snaps to it).  One window
    per process: the first algorithm whose trajectory crosses it wins —
    production runs profile one algorithm, and a second overlapping trace
    session would make jax.profiler raise.
    """

    def __init__(self, outdir: str, start_round: int, stop_round: int):
        self.outdir = outdir
        self.start_round = start_round
        self.stop_round = stop_round
        self.active = False
        self.done = False

    def __call__(self, rec: dict):
        ev = rec.get("event")
        if ev == "round_eval" and not self.done:
            t = rec.get("t")
            if not isinstance(t, int):
                return
            if not self.active and t >= self.start_round:
                import jax

                os.makedirs(self.outdir, exist_ok=True)
                jax.profiler.start_trace(self.outdir)
                self.active = True
            if self.active and t >= self.stop_round:
                self.close()
        elif ev in ("run_end", "divergence"):
            # a run ending inside the window must still flush the capture
            self.close()

    def close(self):
        if self.active:
            import jax

            jax.profiler.stop_trace()
            self.active = False
            self.done = True
