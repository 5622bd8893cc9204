"""Elastic multi-process supervision: gang restart, shrink-to-survivors,
checkpoint resume.

The reference inherits implicit fault recovery from Spark — a lost task is
recomputed from RDD lineage (OptClasses.scala:36 "ensure persistence and
shorter dependencies", hinge/CoCoA.scala:59-62 checkpoint truncation).
That model does not transplant to a multi-controller all-reduce runtime:
when one process of a JAX gang dies, the surviving processes are wedged
inside a collective — there is no per-task granularity to recompute.  The
honest equivalent is **gang restart from the last checkpoint**: a
supervisor launches the N worker processes, watches them, and on any
worker death kills the survivors and relaunches the whole gang with
``--resume``.  Round-keyed sampling makes the resumed trajectory identical
to an uninterrupted run (tests/test_crash_resume.py), so the only cost of
a failure is the rounds since the last ``--chkptIter`` save — the same
bound Spark's lineage recomputation gives, without keeping every round's
lineage alive.

**Shrink-to-survivors** (docs/DESIGN.md §13): same-size restart assumes
the dead worker's host is coming back.  When it is not (a preempted VM, a
failed machine), relaunching at the same P deadlocks forever — every
generation stalls at the rendezvous.  CoCoA+'s math is keyed to the K data
shards, not to the processes hosting them (Ma et al., arXiv:1502.03508:
the dual decomposition, the round-keyed sampling tables and the σ′/Θ/accel
schedules are all shard-count-keyed), so the process→shard mapping is a
free variable the runtime may re-solve after a failure.  With
``num_splits`` given, the supervisor reforms the gang at the largest
P′ < P whose device count still divides K (``--elastic=N``: after
``max_restarts`` consecutive failed same-size generations;
``--elastic=shrink``: immediately on the first loss), relaunches with
``--numProcesses=P′ --resume``, and each survivor re-ingests only its
newly inherited shards through the streaming two-pass pipeline
(data/ingest.py).  A K that no smaller gang can divide is rejected loudly
— never a silent hang.

Between restart generations the supervisor backs off exponentially with
seeded jitter (capped, reset on progress) instead of spinning on the
relaunch: a crash-looping gang must not hammer a shared coordinator or
filesystem at poll speed.

**Overlapped/stale exchanges across a resize** (docs/DESIGN.md §15):
workers running with ``--overlapComm``/``--staleRounds`` may hold
in-flight exchange handles and a window of pending stale joins when the
gang dies.  Nothing here needs to unwind them: the collector threads
are daemons bounded by the KV budget (parallel/distributed.py), so the
SIGKILL teardown above cannot deadlock on them, and the pending joins
die with the generation's processes (StaleJoinWindow.abort is the
in-process spelling of the same rule).  Soundness across the resize
comes from the checkpoint discipline — the gang path only checkpoints
at DRAINED boundaries, where every contribution has been applied and
w = w(α) holds exactly — so the reformed gang resumes from a state
that embeds no half-joined round (pinned: tests/test_overlap.py
``test_gang_resize_with_staleness_drops_pending_joins``).

**Serving across failures** (docs/DESIGN.md §17): a ``--serve`` process
pointed at this gang's ``--chkptDir`` is deliberately OUTSIDE the gang
— it reads validated checkpoint generations, never joins a collective —
so nothing the supervisor does (SIGKILL teardown, shrink, restart
backoff) can wedge or drop a query.  During an outage the server keeps
answering from the last validated generation with its gap-age gauge
climbing; the first save of the reformed gang is picked up by the swap
watcher like any other generation.  The checkpoint discipline this
relies on is already the shrink contract above: generations are
complete (full gathered α, shard-count-keyed state) and validated
newest-first with fallback, so a kill mid-save can never publish a torn
model to the server.

Activated by ``--elastic=N`` (or ``--elastic=N,shrink`` /
``--elastic=shrink``) on the CLI: the invoking process becomes the
supervisor and re-executes its own command line N times with
``--master=127.0.0.1:<port> --processId=i --numProcesses=N --resume``.
A fresh coordinator port is chosen per generation (a dying coordinator can
leave the old port lingering in TIME_WAIT, and a shrunk gang must not
rendezvous with a stale generation's store).

Each (re)launched worker ingests data exactly like any multi-process run:
``--ingest=auto`` streams — pass-1 index scan of 1/P of the LIBSVM file,
pass-2 parse of only that worker's own shards' byte ranges (data/ingest.py,
docs/DESIGN.md §12, README "Multi-host quickstart") — so a gang restart
re-pays ~2/P of a full parse per worker, not P redundant whole-file
parses; after a shrink the same pipeline hands each survivor its
inherited m = K/P′ shards with no resharding code of its own.

With ``--ingestCache=DIR`` (data/slab_cache.py, docs/DESIGN.md §18) a
restart generation re-pays NOTHING: the supervisor re-executes the
user's command line verbatim (``strip_elastic_flags`` removes only the
flags the supervisor owns, so the cache dir is forwarded to every
relaunched generation), and because the slab artifacts are keyed by
SHARD — not by process count or mesh — a shrunk gang's survivors re-map
their newly inherited shards warm: the shrink re-ingest parses zero
bytes (pinned by the chaos suite's cache variant).
"""

from __future__ import annotations

import random
import signal
import socket
import subprocess
import sys
import time
from typing import Optional


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def shrink_gang_size(num_splits: int, current: int,
                     devices_per_worker: int = 1) -> Optional[int]:
    """The largest gang size P′ < ``current`` whose device count divides
    the K logical shards, or None when no smaller gang can carry them.

    K must split evenly over the new gang's devices because the dp mesh
    multiplexes m = K/D whole shards per device (parallel/mesh.py
    ``dp_local_shards``) — the shard set, and with it the trajectory, is
    preserved exactly; only its placement moves.  With one device per
    worker P′=1 always qualifies (every K divides one device); multi-chip
    workers can genuinely strand a K, which callers must reject loudly.
    """
    for p in range(current - 1, 0, -1):
        if num_splits % (p * devices_per_worker) == 0:
            return p
    return None


def backoff_seconds(streak: int, base_s: float, cap_s: float,
                    jitter: float, rng: random.Random) -> float:
    """Exponential backoff with jitter for the ``streak``-th consecutive
    failed generation: min(cap, base·2^(streak-1)) scaled by a uniform
    factor in [1-jitter, 1+jitter].  ``base_s <= 0`` disables the wait
    (tests); the seeded ``rng`` keeps chaos runs deterministic."""
    if base_s <= 0 or streak <= 0:
        return 0.0
    delay = min(cap_s, base_s * (2.0 ** (streak - 1)))
    return delay * (1.0 + jitter * (2.0 * rng.random() - 1.0))


def _spawn(worker_argv, i, n, port, python, module, quiet_tail, resume):
    argv = [
        python, "-m", module, *worker_argv,
        f"--master=127.0.0.1:{port}",
        f"--processId={i}", f"--numProcesses={n}",
        *(["--resume"] if resume else []),
    ]
    out = None if (i == 0 or not quiet_tail) else subprocess.DEVNULL
    return subprocess.Popen(argv, stdout=out, stderr=out)


def supervise(
    worker_argv: list,
    num_processes: int,
    max_restarts: int = 5,
    poll_s: float = 0.25,
    python: Optional[str] = None,
    module: str = "cocoa_tpu.cli",
    quiet_tail: bool = True,
    on_generation=None,   # hook(gen_index, procs) after each gang spawn —
                          # fault-injection handle (tests/_faults.FaultPlan)
    resume: bool = True,  # pass --resume to workers (False when there is
                          # no --chkptDir: the CLI rejects --resume
                          # without one, and there is nothing to resume)
    progress_token=None,  # 0-arg callable capturing run progress (e.g. the
                          # checkpoint-directory state); when it CHANGES
                          # between generations the restart budget resets —
                          # "max_restarts" bounds CONSECUTIVE failed
                          # generations, not lifetime failures of a long
                          # run that keeps advancing
    stall_timeout_s: Optional[float] = None,
                          # no-progress watchdog (ADVICE r4): a gang can
                          # wedge with every process still alive — a lost
                          # device hangs the dispatch, or one worker
                          # exits 0 while its peers block in
                          # a collective that will never complete.  With
                          # ``progress_token`` set, a generation whose
                          # token has not changed for this many seconds is
                          # killed and restarted exactly like a death
                          # (counting against the consecutive-failure
                          # budget — a stalled generation made no
                          # progress, so the budget must not reset).
    num_splits: Optional[int] = None,
                          # K logical shards — what shrink re-divides.
                          # None disables shrink entirely (the pre-shrink
                          # kill-and-relaunch-same-N behavior).
    shrink: str = "auto", # "auto": same-size restarts until max_restarts
                          # consecutive failures, THEN reform at P′ < P
                          # instead of giving up; "now": reform on the
                          # first loss (--elastic=shrink — the dead host
                          # is known not to come back); "off": never
                          # resize (give up after the budget, as before)
    devices_per_worker: int = 1,
                          # local devices each worker process owns (1 for
                          # a localhost CPU gang; the per-host chip count
                          # on TPU) — the granularity K must divide
    backoff_base_s: float = 1.0,
    backoff_cap_s: float = 60.0,
    backoff_jitter: float = 0.5,
    backoff_seed: int = 0,
                          # exponential-backoff-with-jitter policy between
                          # restart generations; the seed keeps chaos runs
                          # deterministic.  base <= 0 disables the wait.
    on_restart=None,      # hook(generation, reason, old_size, new_size,
                          # backoff_s) before each relaunch — the restart
                          # decisions, observable without parsing stderr
) -> int:
    """Run the gang to completion, restarting it (from the latest
    checkpoint, via the workers' ``--resume``) whenever any member dies —
    or, with ``stall_timeout_s``, whenever it stops making progress —
    and, with ``num_splits``, reforming it at P′ < P survivors when the
    same-size gang cannot be kept alive (see module docstring).
    Returns the final exit code (0 on success; the failing worker's code
    after the budget is exhausted with no smaller gang to fall back to).

    ``worker_argv`` is the user's flag list WITHOUT --master/--processId/
    --numProcesses/--elastic (the supervisor owns those).  Worker 0
    inherits stdout (the reference prints from the driver); other workers
    are silenced unless ``quiet_tail=False``.  On a shrunk generation any
    user ``--mesh`` is dropped from the worker line — the old device grid
    no longer exists; the workers re-infer the mesh from P′.
    """
    python = python or sys.executable
    if stall_timeout_s is not None and progress_token is None:
        raise ValueError("stall_timeout_s needs progress_token — without "
                         "a token there is no progress signal to watch")
    if shrink not in ("auto", "now", "off"):
        raise ValueError(f"shrink must be auto|now|off, got {shrink!r}")
    rng = random.Random(backoff_seed)
    n_cur = num_processes
    argv_cur = list(worker_argv)
    restarts = 0   # consecutive failed generations at the CURRENT size —
                   # the give-up / shrink budget (reset on progress AND on
                   # resize: a reformed gang earns a fresh budget)
    streak = 0     # consecutive failed generations since the last
                   # PROGRESS — the backoff exponent (a resize does not
                   # reset it: the run is still failing, keep backing off)
    gen = 0
    last_token = progress_token() if progress_token else None
    from cocoa_tpu.telemetry import tracing as _tracing

    while True:
        port = free_port()
        # span numbering matches the restart/gang_resize EVENTS and the
        # flightrec manifest ("gangs spawned so far", 1-based: this gang
        # is gen+1 until the post-spawn increment below) — only the
        # on_generation test hook keeps its historical 0-based index
        with _tracing.span("gang_generation", generation=gen + 1,
                           gang_size=n_cur):
            procs = [
                _spawn(argv_cur, i, n_cur, port, python, module,
                       quiet_tail, resume)
                for i in range(n_cur)
            ]
            if on_generation is not None:
                on_generation(gen, procs)
            gen += 1
            failed = None
            failed_idx = None
            stalled = False
            last_change = time.monotonic()
            try:
                while True:
                    codes = [p.poll() for p in procs]
                    for idx, c in enumerate(codes):
                        if c not in (None, 0):
                            failed = c
                            failed_idx = idx
                            break
                    if failed is not None:
                        break
                    if all(c == 0 for c in codes):
                        return 0
                    if stall_timeout_s is not None:
                        token = progress_token()
                        if token != last_token:
                            last_token = token
                            last_change = time.monotonic()
                            restarts = 0  # live progress breaks the streak
                            streak = 0
                        elif (time.monotonic() - last_change
                                > stall_timeout_s):
                            stalled = True
                            break
                    time.sleep(poll_s)
            finally:
                # any survivors are wedged inside a collective whose peer
                # died (or we are unwinding on KeyboardInterrupt) — kill
                # the gang
                for p in procs:
                    if p.poll() is None:
                        try:
                            p.send_signal(signal.SIGKILL)
                        except OSError:
                            pass
                for p in procs:
                    try:
                        p.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        pass
        if progress_token is not None:
            token = progress_token()
            if token != last_token:
                restarts = 0      # the dead generation still advanced the
                streak = 0        # run — the failure streak is broken
                last_token = token
        restarts += 1
        streak += 1
        attempt_used = restarts   # what the restart event reports: the
        # consecutive failures that led HERE — a resize zeroes the budget
        # counter below, but the event must still say the budget was
        # exhausted, not "attempt 0"
        reason = "gang_stalled" if stalled else "worker_died"
        # machine-readable restart trace: the supervisor's bus (configured
        # by the CLI's --events; inert otherwise) appends to the same
        # JSONL the workers write — whole-line appends interleave safely
        from cocoa_tpu.telemetry import events as _tele

        # flight-recorder dump on the victim's behalf: a SIGKILLed worker
        # cannot dump its own ring, but its events were streaming to its
        # per-process JSONL — tail it and leave the `.flightrec`
        # explanation artifact next to it (telemetry/recorder.py).  A
        # stall has no single victim; dump worker 0's tail as the gang's
        # last-known state instead.
        if _tele.get_bus().jsonl_path:
            from cocoa_tpu.telemetry import recorder as _recorder

            # victim_pid scopes the tail to the dead process's own
            # records (worker 0 shares its file with the supervisor, and
            # every stream accumulates prior generations); a stall has
            # no single victim — dump worker 0's stream unscoped as the
            # gang's last-known state
            victim_pid = (getattr(procs[failed_idx], "pid", None)
                          if failed_idx is not None else None)
            _recorder.dump_victim(
                _tele.get_bus().jsonl_path,
                failed_idx if failed_idx is not None else 0,
                reason, exit_code=failed, generation=gen,
                victim_pid=victim_pid)

        old_n = n_cur
        can_shrink = (num_splits is not None and shrink != "off"
                      and n_cur > 1)
        # "now" fast-path applies to worker LOSS only: a stall has every
        # process alive (transient wedge — NFS hiccup, slow device), so
        # shrinking on the first one would permanently downsize a healthy
        # gang; stalls burn the restart budget instead (the fault model
        # table, docs/DESIGN.md §13) and shrink only when it exhausts
        if can_shrink and ((shrink == "now" and not stalled)
                           or restarts > max_restarts):
            n_new = shrink_gang_size(num_splits, n_cur, devices_per_worker)
            if n_new is None:
                # reject loudly: no smaller gang's devices divide K — a
                # relaunch at any P′ would fail its own divisibility
                # check, so say why and stop instead of crash-looping
                print(f"elastic: cannot reform the gang below {n_cur} "
                      f"workers — numSplits={num_splits} does not divide "
                      f"across any smaller gang's devices "
                      f"({devices_per_worker} per worker); giving up "
                      f"(pick a numSplits with more divisors to allow "
                      f"deeper shrink)", file=sys.stderr, flush=True)
                return int(failed or 1)
            _tele.get_bus().emit(
                "gang_resize", reason=reason, old_size=n_cur,
                new_size=n_new, generation=gen, num_splits=num_splits,
                exit_code=failed)
            stripped = [a for a in argv_cur
                        if a.lstrip("-").split("=", 1)[0] != "mesh"]
            if len(stripped) != len(argv_cur):
                print("elastic: dropping the explicit --mesh from the "
                      "worker line — the reformed gang re-infers its mesh "
                      f"from {n_new} worker(s)", file=sys.stderr)
            argv_cur = stripped
            n_cur = n_new
            restarts = 0   # a reformed gang earns a fresh same-size budget
        elif restarts > max_restarts:
            why = ("stalled" if stalled
                   else f"failed (last exit code {failed})")
            print(f"elastic: giving up after {max_restarts} consecutive "
                  f"{why} generations", file=sys.stderr)
            return int(failed or 1)
        backoff = backoff_seconds(streak, backoff_base_s, backoff_cap_s,
                                  backoff_jitter, rng)
        _tele.get_bus().emit(
            "restart", reason=reason,
            attempt=attempt_used, max_restarts=max_restarts,
            exit_code=failed, generation=gen, gang_size=n_cur,
            backoff_s=backoff)
        if on_restart is not None:
            on_restart(gen, reason, old_n, n_cur, backoff)
        what = (f"gang made no progress for {stall_timeout_s:g}s"
                if stalled else f"worker died (exit {failed})")
        if n_cur != old_n:
            print(f"elastic: {what}; reforming the gang at {n_cur} of "
                  f"{old_n} workers ({num_splits} shards re-divided over "
                  f"the survivors) from the latest checkpoint"
                  + (f" after {backoff:.1f}s backoff" if backoff else ""),
                  file=sys.stderr, flush=True)
        else:
            print(f"elastic: {what}; restarting gang "
                  f"(attempt {restarts}/{max_restarts}) from the latest "
                  f"checkpoint"
                  + (f" after {backoff:.1f}s backoff" if backoff else ""),
                  file=sys.stderr, flush=True)
        if backoff > 0:
            with _tracing.span("restart_backoff", generation=gen,
                               backoff_s=backoff):
                time.sleep(backoff)


def strip_elastic_flags(argv: list) -> list:
    """The worker command line = the user's line minus the flags the
    supervisor owns (it re-adds its own --master/--processId/...)."""
    own = ("elastic", "master", "processId", "numProcesses", "resume",
           "stallTimeout")
    out = []
    for a in argv:
        key = a.lstrip("-").split("=", 1)[0]
        if key not in own:
            out.append(a)
    return out
