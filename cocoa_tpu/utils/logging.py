"""Run trajectory logging and reference-style console output.

The reference's observability is println-only: per-``debugIter`` lines
(CoCoA.scala:51-56) and end-of-run summaries (OptUtils.scala:102-126).  We
keep that exact console format (so trajectories are eyeball-comparable) and
add what the baseline work actually needs (SURVEY.md §5-6): a structured
per-round record (round, wall-clock, comm-rounds, primal, gap, test error)
that can be dumped as JSONL — the benchmark artifact.

Since the telemetry subsystem landed, :class:`Trajectory` is a thin
CONSUMER of the event bus (cocoa_tpu/telemetry/events.py): every record it
collects is mirrored as a typed ``round_eval`` / ``divergence`` /
``run_end`` event (a no-op while the bus is unconfigured), and the console
prints are the same bus data rendered in the reference format.  The
``--quiet`` policy silences the console ONLY — a quiet run still leaves
the machine-readable event trace, which is the point.
"""

from __future__ import annotations

import dataclasses
import json
import time
from typing import Optional

from cocoa_tpu.telemetry import events as _events


@dataclasses.dataclass
class RoundRecord:
    round: int
    wall_time: Optional[float]  # seconds since run start; None when per-round
                                # timing is unobservable (device-resident loop
                                # fetches the whole trajectory in one sync)
    primal: Optional[float] = None
    gap: Optional[float] = None
    test_error: Optional[float] = None
    sigma: Optional[float] = None  # σ′ in effect AFTER this eval's schedule
                                   # update (--sigmaSchedule=anneal runs only;
                                   # a change between consecutive records IS
                                   # the in-loop backoff event)
    class_gaps: Optional[list] = None  # a one-vs-rest job (T > 1 class
                                   # models over one set of rows): every
                                   # class's duality gap at this eval, by
                                   # class id; ``gap`` is the worst of them
                                   # and ``primal`` that class's.  None at
                                   # T = 1
    classes_done: Optional[int] = None  # of them, how many are at or under
                                   # the job's gap target (None without one)


class Trajectory:
    """Collects per-round records; one comm-round == one outer round (the
    baseline's #comm-rounds metric counts these)."""

    def __init__(self, algorithm: str, quiet: bool = False):
        self.algorithm = algorithm
        self.records: list[RoundRecord] = []
        self.quiet = quiet
        # why the run ended: None = ran its full round budget;
        # "target" = duality gap reached the gap_target early stop;
        # "diverged" = the gap stopped improving for STALL_EVALS straight
        # evals (the σ′-override guardrail — solvers/base.py)
        self.stopped: Optional[str] = None
        # extra manifest fields for dump_jsonl (dataset path, config hash,
        # seed, ...) — the CLI fills this in; library callers may too
        self.meta: dict = {}
        self._t0 = time.perf_counter()

    def _console(self, msg: str):
        """The one quiet/console policy every trajectory print routes
        through (log_round's reference-format lines, mark_diverged's
        bail-out notice, the end-of-run summary)."""
        if not self.quiet:
            print(msg)

    def mark_diverged(self, t: int, n_evals: int):
        """Record (and report) a divergence/stall bail-out at round ``t``.
        The ``divergence`` event is emitted regardless of ``quiet`` — a
        silenced console must still leave a machine-readable trace of the
        bail-out."""
        self.stopped = "diverged"
        _events.get_bus().emit("divergence", algorithm=self.algorithm,
                               t=int(t), n_evals=int(n_evals))
        self._console(f"{self.algorithm}: DIVERGED — best duality gap made no "
                      f"material progress over {n_evals} consecutive "
                      f"evaluations; stopped at round {t} "
                      f"(σ′ set below the safe K·γ bound? see --sigma)")

    def elapsed(self) -> float:
        return time.perf_counter() - self._t0

    _STAMP = object()  # sentinel: stamp elapsed() unless overridden

    def log_round(self, t, primal=None, gap=None, test_error=None,
                  wall_time=_STAMP, sigma=None, emit=True, sigma_stage=None,
                  stall=None, class_gaps=None, classes_done=None):
        """``wall_time=None`` marks the round's timing as unobservable (the
        device-resident driver syncs once for the whole run).

        ``emit=False`` suppresses the ``round_eval`` bus event — used by
        the device-resident driver, whose events were already emitted
        in-flight by the io_callback bridge (or replayed from the fetch)
        before this record is built.  ``sigma_stage``/``stall`` ride the
        event only (the σ′ ladder index and the stall-watch counter after
        this eval's update — the host drivers' twin of the device row).
        ``class_gaps`` / ``classes_done`` (a one-vs-rest job only): every
        class's gap and how many are at or under the target; they ride the
        record and, with ``emit``, the event."""
        rec = RoundRecord(
            round=t,
            wall_time=self.elapsed() if wall_time is self._STAMP else wall_time,
            primal=primal,
            gap=gap,
            test_error=test_error,
            sigma=sigma,
            class_gaps=class_gaps,
            classes_done=classes_done,
        )
        per_class = ({} if class_gaps is None else
                     dict(class_gaps=class_gaps, classes_done=classes_done))
        self.records.append(rec)
        if emit:
            _events.get_bus().emit(
                "round_eval", algorithm=self.algorithm, t=int(t),
                primal=primal, gap=gap, test_error=test_error, sigma=sigma,
                sigma_stage=sigma_stage, stall=stall, **per_class,
            )
        if not self.quiet:
            # reference console format (CoCoA.scala:52-55)
            print(f"Iteration: {t}")
            if primal is not None:
                print(f"primal objective: {primal}")
            if gap is not None:
                print(f"primal-dual gap: {gap}")
            if test_error is not None:
                print(f"test error: {test_error}")
            if class_gaps is not None:
                print(f"per-class gaps: {class_gaps}"
                      + ("" if classes_done is None else
                         f" ({classes_done} of {len(class_gaps)} at target)"))

    def summary(self, primal, gap=None, test_error=None):
        """End-of-run block (OptUtils.scala:102-126 format) + the
        ``run_end`` event (emitted even under ``quiet``)."""
        _events.get_bus().emit(
            "run_end", algorithm=self.algorithm, primal=primal, gap=gap,
            test_error=test_error, stopped=self.stopped,
            rounds=self.records[-1].round if self.records else 0,
            elapsed_s=self.elapsed(),
        )
        if self.quiet:
            return
        out = f"{self.algorithm} has finished running. Summary Stats: "
        out += f"\n Total Objective Value: {primal}"
        if gap is not None:
            out += f"\n Duality Gap: {gap}"
        if test_error is not None:
            out += f"\n Test Error: {test_error}"
        print(out + "\n")

    def manifest(self) -> dict:
        """The dump header: algorithm + run provenance (jax/device info,
        plus whatever the caller put in ``self.meta`` — dataset, config
        hash, seed).  ``config_hash`` defaults to a hash of the meta
        itself so the header always carries a run identity."""
        man = {"algorithm": self.algorithm,
               "records": len(self.records),
               **_events.environment_manifest(),
               **self.meta}
        man.setdefault("config_hash", _events.config_hash(
            {"algorithm": self.algorithm, **self.meta}))
        return man

    def dump_jsonl(self, path: str):
        """One manifest header line, then one line per record; the FINAL
        record carries the ``stopped`` reason (null = full round budget) —
        without it a dumped trajectory could not distinguish 'certified
        the target' from 'budget exhausted' from 'bailed out diverged'."""
        with open(path, "w") as f:
            f.write(json.dumps({"manifest": _events._clean(self.manifest())})
                    + "\n")
            for j, r in enumerate(self.records):
                d = {"algorithm": self.algorithm, **dataclasses.asdict(r)}
                if j == len(self.records) - 1:
                    d["stopped"] = self.stopped
                f.write(json.dumps(_events._clean(d)) + "\n")
