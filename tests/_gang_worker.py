"""Minimal REAL-process gang worker for supervisor chaos tests.

Runs under the elastic supervisor exactly like the CLI would
(``python -m _gang_worker <flags> --master=... --processId=i
--numProcesses=n [--resume]``): joins the jax.distributed runtime (real
coordinator rendezvous), splits the K logical shards over the gang,
advances a deterministic round-keyed state with one
``host_allgather_bytes`` exchange per round (the hardened KV path,
exercised against a real coordination service), and checkpoints through
``cocoa_tpu.checkpoint`` — so the supervision mechanics (death
detection, shrink-to-survivors, resume, checkpoint-generation fallback)
run end to end with real processes WITHOUT cross-process XLA
collectives, which the pinned jax lacks on CPU (the real-training chaos
pin is tests/test_chaos.py's slow suite, same guard as the existing
multi-host gang tests).

Two modes:

- **toy** (default): the state is a pure function of (K, rounds) — each
  shard's per-round increment is owner-independent and each w[s]
  receives exactly one nonzero addend per round — so a kill/shrink/
  resume run must reproduce the unfailed control's final checkpoint bit
  for bit, the same invariant the real solvers get from round-keyed
  sampling.
- **--real=cocoa**: an actual CoCoA+ gang over the host-side KV
  exchange — numpy hinge SDCA (tests/oracle.py, the reference-faithful
  local solver) on deterministic per-shard synthetic data, σ′ = K·γ,
  exact duality-gap certificate at the ``--debugIter`` cadence.  This
  is the substrate for the round-barrier levers (docs/DESIGN.md §15):
  ``--overlapComm`` posts this worker's Δw the moment local solve
  finishes and collects peers' payloads on a background thread
  (parallel/distributed.async_host_allgather_bytes), and
  ``--staleRounds=S`` admits a peer's round-r Δw up to S rounds late
  under the safe-γ rule (solvers/cocoa.StaleJoinWindow), draining at
  every eval/checkpoint boundary so the certified gap is evaluated on
  an exact ``w = w(α)`` pair.  Contributions are applied in CANONICAL
  (round, process) order via a recompute from the contribution log, so
  every worker holds a bitwise-identical w at every drained boundary
  and the whole trajectory — including which rounds join when — is a
  pure function of round numbers, never of wall-clock (deterministic
  A/B tests; see StaleJoinWindow's determinism note).

Straggler fixtures: ``--stepSkew=S`` (worker i sleeps ``i*S`` extra per
round — the constant-skew fixture of the tracing tests) and
``--skewEvery=J`` (rotating skew: worker p sleeps the extra S only on
rounds with ``t % J == p % J`` — the transient-straggler fixture the
staleness window can actually absorb; a CONSTANT skew bounds the gang
to the slow worker's average pace no matter the window, bounded-lag
arithmetic, so the A/B acceptance measures the rotating fixture).
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

ALGORITHM = "ToyGang"
REAL_ALGORITHM = "GangCoCoA+"

# short KV budget everywhere in this harness: a dead peer must fail
# THIS worker quickly so the supervisor (which already saw the death)
# isn't racing a 10-minute hang in the teardown path
KV_TIMEOUT_S = 30.0
KV_ATTEMPT_S = 2.0

# the phases a worker can block (or hide blocking) on during the
# cross-gang exchange — the ONE definition the acceptance test
# (tests/test_overlap.py), the CI smoke (tests/chaos_smoke.py) and any
# future consumer sum straggler slack over, so the measured bar cannot
# silently drift between them
EXCHANGE_PHASES = ("kv_get", "kv_allgather", "kv_post", "exchange_join")


def supervise_gang(argv, n: int = 2, events=None, **kw):
    """One-shot supervised run of THIS worker module — the launch
    contract shared by the slow tests, the CI chaos smoke, and the
    gang cases of tests/test_count_gates.py (one place to change if the
    gang ever needs a new required flag or stream convention).

    Returns ``(rc, records)``: the supervisor's exit code and the
    parsed worker-0 events stream (empty when ``events`` is None or the
    file never appeared).  ``kw`` overrides the supervise defaults
    (max_restarts=0, poll_s=0.05, backoff_base_s=0.0, resume=False)."""
    import json

    from cocoa_tpu import elastic

    opts = dict(module="_gang_worker", max_restarts=0, poll_s=0.05,
                backoff_base_s=0.0, resume=False)
    opts.update(kw)
    argv = list(argv) + ([f"--events={events}"] if events else [])
    rc = elastic.supervise(argv, n, **opts)
    records = []
    if events and os.path.exists(str(events)):
        with open(str(events)) as f:
            records = [json.loads(ln) for ln in f if ln.strip()]
    return rc, records


def parse(argv):
    opts = {}
    for a in argv:
        s = a.lstrip("-")
        k, _, v = s.partition("=")
        opts[k] = v if v else "true"
    return opts


def round_increments(t: int, k: int, lo: int, hi: int) -> np.ndarray:
    """The deterministic per-round update for shards [lo, hi): keyed to
    (round, shard) only — never to the process layout."""
    out = np.zeros(k, np.float64)
    for s in range(lo, hi):
        out[s] = ((t * 1000003 + s * 7919) % 104729) / 104729.0
    return out


def _configure_telemetry(opts, pid):
    """The same telemetry surface the real CLI wires — worker 0 owns the
    given path, worker p > 0 streams to `.p<p>`
    (telemetry/recorder.worker_stream_path), spans tagged with the
    worker index — so the supervisor's flight-recorder dump and the
    trace_report merge run against real per-process artifacts here too."""
    from cocoa_tpu.telemetry import events as tele_events
    from cocoa_tpu.telemetry import recorder as tele_recorder
    from cocoa_tpu.telemetry import tracing

    stream = (tele_recorder.worker_stream_path(opts["events"], pid)
              if opts.get("events") else None)
    # same ownership split as the real CLI: worker 0 owns <metrics>; the
    # supervisor owns the sibling <metrics>.gang (families="gang")
    metrics = opts.get("metrics") if pid == 0 else None
    if stream or metrics:
        tele_events.get_bus().configure(jsonl_path=stream,
                                        metrics_path=metrics)
    if opts.get("trace"):
        tracing.configure(enabled=True, worker=pid)


def _skew_sleep(opts, pid, t) -> float:
    """The straggler fixture's extra sleep for worker ``pid`` at round
    ``t`` (see module docstring)."""
    skew_s = float(opts.get("stepSkew", 0.0))
    every = int(opts.get("skewEvery", 0))
    if skew_s <= 0.0:
        return 0.0
    if every > 0:
        return skew_s if t % every == pid % every else 0.0
    return pid * skew_s


def toy_main(opts, pid, nproc) -> int:
    from cocoa_tpu.parallel.distributed import host_allgather_bytes
    from cocoa_tpu.telemetry import tracing

    k = int(opts["numSplits"])
    rounds = int(opts["numRounds"])
    ckdir = opts.get("chkptDir", "")
    ck_iter = int(opts.get("chkptIter", 5))
    step_s = float(opts.get("stepSeconds", 0.05))
    m = k // nproc

    from cocoa_tpu import checkpoint as ckpt_lib

    w = np.zeros(k, np.float64)
    start = 1
    if "resume" in opts and ckdir:
        path = ckpt_lib.latest(ckdir, ALGORITHM)
        if path is not None:
            meta, w0, _ = ckpt_lib.load(path)
            w = np.array(w0, np.float64)
            start = meta["round"] + 1
            print(f"resuming {ALGORITHM} from round {meta['round']} "
                  f"({path})", flush=True)

    for t in range(start, rounds + 1):
        # the round span carries the round number; the nested
        # kv_allgather / local_step / checkpoint_save spans inherit it
        # (trace_report.attribute_rounds), which is what the per-round
        # critical path and the worker x phase straggler table key on
        with tracing.span("round", round=t):
            mine = round_increments(t, k, pid * m, (pid + 1) * m)
            parts = host_allgather_bytes(f"toy{t}", mine.tobytes(),
                                         timeout_s=KV_TIMEOUT_S,
                                         attempt_s=KV_ATTEMPT_S)
            for p in parts:
                w = w + np.frombuffer(p, np.float64)
            with tracing.span("local_step"):
                time.sleep(step_s + _skew_sleep(opts, pid, t))
            if ckdir and t % ck_iter == 0:
                ckpt_lib.save(ckdir, ALGORITHM, t, w, None, seed=0)
    print(f"{ALGORITHM}: done at round {rounds}", flush=True)
    return 0


# --- the real-math CoCoA+ gang (--real=cocoa) --------------------------------


def shard_data(shard: int, n_rows: int, d: int, seed: int):
    """Deterministic synthetic (X, y) for one logical shard — keyed to
    the SHARD, never to its owning process, so a shrunk gang re-derives
    identical data for its inherited shards."""
    rng = np.random.default_rng(970_001 + 131 * shard + seed)
    X = rng.standard_normal((n_rows, d)) / np.sqrt(d)
    w_true = np.random.default_rng(7 + seed).standard_normal(d)
    y = np.where(X @ w_true >= 0.0, 1.0, -1.0)
    flips = rng.random(n_rows) < 0.08   # a non-separable margin band
    return X, np.where(flips, -y, y)


def round_idxs(t: int, shard: int, n_rows: int, h: int,
               seed: int) -> np.ndarray:
    """Round-keyed per-shard coordinate draws: a fresh per-round
    permutation prefix (every dual touched once per full-H round),
    owner-independent like everything else."""
    rng = np.random.default_rng(seed * 1_000_003 + t * 9176 + shard)
    return rng.permutation(n_rows)[:h]


class _GangCocoa:
    """The per-process state of the real-math gang run (see module
    docstring).  All float64 host math — the certificate side of the
    repo's numerics policy."""

    def __init__(self, opts, pid, nproc):
        self.opts = opts
        self.pid = pid
        self.nproc = nproc
        self.k = int(opts["numSplits"])
        if self.k % nproc != 0:
            # main() already rejected this with a stderr message; keep a
            # diagnostic here for any future direct constructor caller
            raise ValueError(
                f"K={self.k} shards cannot divide over {nproc} workers")
        self.m = self.k // nproc
        self.mine = range(pid * self.m, (pid + 1) * self.m)
        self.n_rows = int(opts.get("rowsPerShard", 48))
        self.d = int(opts.get("numFeatures", 24))
        self.h = int(opts.get("localIters", self.n_rows))
        self.lam = float(opts.get("lambda", 0.05))
        self.seed = int(opts.get("seed", 0))
        self.gamma = 1.0
        self.sigma = self.k * self.gamma      # the safe σ′ = K·γ
        self.n = self.k * self.n_rows
        self.data = {s: shard_data(s, self.n_rows, self.d, self.seed)
                     for s in self.mine}
        self.alpha = {s: np.zeros(self.n_rows) for s in self.mine}
        # contribution log: (round, process) -> γ-unscaled Δw.  w is
        # recomputed from it in canonical (round, process) order on
        # every change, so the float addition order — and with it the
        # bitwise w — is identical on every worker at drained
        # boundaries, no matter when each contribution arrived.
        self.contribs: dict = {}
        self.w_base = np.zeros(self.d)
        self.w = self.w_base.copy()

    def recompute_w(self):
        w = self.w_base.copy()
        for key in sorted(self.contribs):
            w = w + self.gamma * self.contribs[key]
        self.w = w

    def local_solve(self, t: int) -> np.ndarray:
        import oracle

        dw_mine = np.zeros(self.d)
        for s in self.mine:
            X, y = self.data[s]
            idxs = round_idxs(t, s, self.n_rows, self.h, self.seed)
            da, dw = oracle.local_sdca(
                X, y, self.w, self.alpha[s], idxs, self.lam, self.n,
                plus=True, sigma=self.sigma)
            self.alpha[s] = self.alpha[s] + self.gamma * da
            dw_mine += dw
        return dw_mine

    def absorb(self, r: int, parts: list):
        """Apply one joined round's peer contributions (own round-r Δw
        was logged at solve time — the owner must never see its own
        progress late)."""
        for q, payload in enumerate(parts):
            if q == self.pid:
                continue
            self.contribs[(r, q)] = np.frombuffer(payload, np.float64)
        self.recompute_w()

    def partials(self):
        """This process's share of the certificate sums: Σ hinge(y·x·w)
        over its rows, Σ α over its duals."""
        loss = 0.0
        a_sum = 0.0
        for s in self.mine:
            X, y = self.data[s]
            loss += float(np.maximum(0.0, 1.0 - y * (X @ self.w)).sum())
            a_sum += float(self.alpha[s].sum())
        return loss, a_sum

    def gap_from_totals(self, loss_total: float, alpha_total: float):
        """The exact hinge duality gap on the ACTUAL (w, α) — the
        unmodified evaluator: P(w) − D(α) with w = w(α) at a drained
        boundary = λ‖w‖² + (Σ hinge)/n − (Σ α)/n."""
        wsq = float(self.w @ self.w)
        primal = 0.5 * self.lam * wsq + loss_total / self.n
        dual = alpha_total / self.n - 0.5 * self.lam * wsq
        return primal, primal - dual

    def alpha_full(self, parts: list) -> np.ndarray:
        """(K, n_rows) α assembled from per-process blocks."""
        out = np.zeros((self.k, self.n_rows))
        for q, payload in enumerate(parts):
            block = np.frombuffer(payload, np.float64).reshape(
                self.m, self.n_rows)
            out[q * self.m:(q + 1) * self.m] = block
        return out


def real_main(opts, pid, nproc) -> int:
    from cocoa_tpu import checkpoint as ckpt_lib
    from cocoa_tpu.parallel import distributed
    from cocoa_tpu.solvers.cocoa import StaleJoinWindow
    from cocoa_tpu.telemetry import events as tele_events
    from cocoa_tpu.telemetry import tracing

    rounds = int(opts["numRounds"])
    ckdir = opts.get("chkptDir", "")
    ck_iter = int(opts.get("chkptIter", 0))
    debug_iter = int(opts.get("debugIter", 5))
    gap_target = (float(opts["gapTarget"]) if opts.get("gapTarget")
                  else None)
    step_s = float(opts.get("stepSeconds", 0.0))
    stale = int(opts.get("staleRounds", 0))
    overlap_flag = str(opts.get("overlapComm", "off")).lower()
    if overlap_flag not in ("auto", "on", "off", "true"):
        print(f"error: --overlapComm must be auto|on|off, got "
              f"{overlap_flag!r}", file=sys.stderr)
        return 2
    overlap = (overlap_flag in ("on", "true")
               or (overlap_flag == "auto" and nproc > 1))
    if ck_iter > 0 and debug_iter > 0 and ck_iter % debug_iter != 0:
        # checkpoints must land on DRAINED boundaries (w = w(α) exactly,
        # so a resumed generation never embeds a half-joined round)
        print(f"error: --chkptIter ({ck_iter}) must be a multiple of "
              f"--debugIter ({debug_iter}) in --real=cocoa mode "
              f"(checkpoints land on drained eval boundaries)",
              file=sys.stderr)
        return 2

    gang = _GangCocoa(opts, pid, nproc)
    window = StaleJoinWindow(stale, algorithm=REAL_ALGORITHM)
    bus = tele_events.get_bus()

    start = 1
    if "resume" in opts and ckdir:
        path = ckpt_lib.latest(ckdir, REAL_ALGORITHM)
        if path is not None:
            meta, w0, a0 = ckpt_lib.load(path)
            gang.w_base = np.array(w0, np.float64)
            gang.recompute_w()
            a0 = np.asarray(a0, np.float64)
            for s in gang.mine:
                gang.alpha[s] = a0[s].copy()
            start = meta["round"] + 1
            print(f"resuming {REAL_ALGORITHM} from round {meta['round']} "
                  f"({path})", flush=True)

    gap = None
    stopped = None
    t = start - 1
    for t in range(start, rounds + 1):
        with tracing.span("round", round=t):
            with tracing.span("local_solve", round=t):
                dw_mine = gang.local_solve(t)
                extra = step_s + _skew_sleep(opts, pid, t)
                if extra > 0:
                    time.sleep(extra)
            # own contribution lands NOW (the local view must advance);
            # the posted payload unblocks peers the moment solve ends
            gang.contribs[(t, pid)] = dw_mine
            gang.recompute_w()
            payload = dw_mine.tobytes()
            if overlap:
                handle = distributed.async_host_allgather_bytes(
                    f"dw{t}", payload, timeout_s=KV_TIMEOUT_S,
                    attempt_s=KV_ATTEMPT_S, trace_attrs={"round": t})
            else:
                handle = distributed.host_allgather_bytes(
                    f"dw{t}", payload, timeout_s=KV_TIMEOUT_S,
                    attempt_s=KV_ATTEMPT_S)
            window.admit(t, handle)
            for r, parts, _late in window.join_due(t):
                gang.absorb(r, parts)

        if debug_iter > 0 and t % debug_iter == 0:
            # eval boundary: DRAIN first, so the certificate sees the
            # exact w = w(α) pair (docs/DESIGN.md §15)
            for r, parts, _late in window.drain(t):
                gang.absorb(r, parts)
            with tracing.span("eval", round=t):
                loss, a_sum = gang.partials()
                parts = distributed.host_allgather_bytes(
                    f"ev{t}", np.array([loss, a_sum]).tobytes(),
                    timeout_s=KV_TIMEOUT_S, attempt_s=KV_ATTEMPT_S)
                totals = np.sum([np.frombuffer(p, np.float64)
                                 for p in parts], axis=0)
                primal, gap = gang.gap_from_totals(totals[0], totals[1])
            bus.emit("round_eval", algorithm=REAL_ALGORITHM, t=t,
                     primal=primal, gap=gap, test_error=None, sigma=None,
                     stall=None)
            if pid == 0:
                print(f"{REAL_ALGORITHM}: round {t} gap {gap:.3e}",
                      flush=True)
            window.on_eval(gap)
            if ckdir and ck_iter > 0 and t % ck_iter == 0:
                a_mine = np.concatenate(
                    [gang.alpha[s] for s in gang.mine])
                parts = distributed.host_allgather_bytes(
                    f"ck{t}", a_mine.tobytes(), timeout_s=KV_TIMEOUT_S,
                    attempt_s=KV_ATTEMPT_S)
                ckpt_lib.save(ckdir, REAL_ALGORITHM, t, gang.w,
                              gang.alpha_full(parts), seed=gang.seed)
            if gap_target is not None and gap <= gap_target:
                stopped = "target"
                break

    # a fixed-round run may still hold pending joins for the tail
    # rounds; land them so the final state is drained (and a final
    # checkpoint, if due, was already written at the last boundary)
    for r, parts, _late in window.drain(t):
        gang.absorb(r, parts)
    bus.emit("run_end", algorithm=REAL_ALGORITHM, stopped=stopped,
             gap=gap, round=t)
    print(f"{REAL_ALGORITHM}: done at round {t}"
          + (f" (gap {gap:.3e})" if gap is not None else ""), flush=True)
    return 0


def main(argv=None) -> int:
    opts = parse(sys.argv[1:] if argv is None else argv)
    pid = int(opts.get("processId", 0))
    nproc = int(opts.get("numProcesses", 1))
    k = int(opts["numSplits"])

    _configure_telemetry(opts, pid)

    from cocoa_tpu.parallel.distributed import maybe_initialize

    maybe_initialize(opts.get("master"), pid, nproc)
    if k % nproc != 0:
        # the same loud divisibility rejection the real dataset builders
        # raise — a supervisor bug (non-divisor relaunch) fails fast here
        print(f"error: K={k} shards cannot divide over {nproc} workers",
              file=sys.stderr)
        return 2

    real = str(opts.get("real", "")).lower()
    if real in ("cocoa", "cocoa+"):
        return real_main(opts, pid, nproc)
    if real:
        print(f"error: --real takes 'cocoa', got {real!r}",
              file=sys.stderr)
        return 2
    return toy_main(opts, pid, nproc)


if __name__ == "__main__":
    sys.exit(main())
