"""Shared outer-loop machinery for all solvers.

Every algorithm's round has the same communication shape (the reference's
``mapPartitions`` → ``reduce`` skeleton, CoCoA.scala:45-47):

    fan out (w replicated, shard-local state pinned)
    → per-shard local solver
    → one O(d) sum-reduce of Δw
    → replicated driver-side w update

``fanout`` carries that shape on two execution paths with identical math:

- **mesh path** (K devices): ``shard_map`` over the dp axis; the Δw reduce is
  one ``lax.psum`` over ICI — the whole point of CoCoA's communication
  efficiency maps to exactly one collective per round.
- **local path** (mesh=None, e.g. a single TPU chip holding all K logical
  shards): ``vmap`` over the leading shard axis + an in-device sum.  Same
  numbers, no collective — used for single-chip benchmarking and as the
  K-logical-shards-on-1-device analogue of the reference's ``local[4]`` mode.
"""

from __future__ import annotations

import contextlib
from collections import OrderedDict
from typing import Callable, NamedTuple, Optional

import jax
import numpy as np

from cocoa_tpu import checkpoint as ckpt_lib
from cocoa_tpu.analysis import sanitize as _sanitize
from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import ShardedDataset
from cocoa_tpu.parallel.fanout import fanout  # noqa: F401  (re-export)
from cocoa_tpu.telemetry import tracing as _tracing
from cocoa_tpu.utils.logging import Trajectory
from cocoa_tpu.utils.prng import sample_indices_per_shard


# σ′-override guardrail (VERDICT r4): a σ′ below the problem's tolerance
# stops the duality gap from converging — the exact certificate reports it,
# but (before this guard) only after the full round budget burned.  The
# box constraint keeps α ∈ [0,1]^n, so "divergence" manifests as the gap
# OSCILLATING at a high level (measured: σ′=1 at K=4 on adversarially
# coherent shards bounces in [0.1, 20] forever), not as monotone growth —
# a consecutive-rise test never fires.  The robust detector is windowed
# no-improvement: a converging run keeps improving its best-seen gap
# (even the slow λ=1e-4 rcv1 tail improves ~6%/eval ⇒ ~50% per 10 evals),
# while an oscillating run's best barely moves.  Bail out when the best
# gap has not improved to ≤ STALL_REL × (best at the last reset) within
# the stall window.
#
# The window is denominated in ROUNDS, not evaluations: per-eval progress
# scales with the eval cadence (at --debugIter=1 a healthy run improves
# ~1/25th as much per eval as at the calibration cadence 25), so a fixed
# eval count would make the guard ~25x stricter at fine cadences and
# kill slow-but-converging runs (round-5 review finding).  STALL_EVALS
# is the floor so coarse cadences still get a meaningful window.
STALL_EVALS = 12
STALL_ROUNDS = 300     # = STALL_EVALS at the calibration cadence 25
STALL_REL = 0.75


def stall_window(debug_iter: int) -> int:
    """The no-improvement window in EVALS for this eval cadence."""
    return max(STALL_EVALS, -(-STALL_ROUNDS // max(1, int(debug_iter))))


# --- device-resident σ′ schedule (--sigmaSchedule=anneal) -------------------
#
# The sigma=auto trial-and-rerun (solvers/cocoa.run_cocoa, --sigmaSchedule=
# trial) pays for a wrong aggressive guess twice: the guarded trial burns a
# stall window AND the safe rerun restarts from round 1.  The anneal
# schedule instead carries σ′ IN the driver ladder's loop state: start
# aggressive, and when the stall watch fires, multiply σ′ toward the safe
# K·γ bound IN PLACE and keep going from the current iterate.  That is
# sound because the primal-dual correspondence w = (1/λn)·Σ y·α·x and the
# box constraint α ∈ [0,1]^n — everything the exact duality-gap
# certificate rests on — are maintained by the update rule under ANY σ′:
# σ′ only scales the local subproblem's coupling term, so any (w, α) pair
# a σ′-a run produced is a feasible starting point for a σ′-b run and the
# certificate stays exact across the switch.  The cost of a wrong guess
# drops from (stall window + full restart) to (stall window), and the
# iterate progress made before the backoff is kept, not discarded.
#
# The schedule state is a tiny float32 vector riding the solver state
# tuple (so it is donated, checkpointed, and resumed with w and α — a
# mid-schedule --resume is bit-identical):
#
#   sched[0] = stage       index into the static σ′ ladder
#   sched[1] = stall       consecutive no-improvement evals at this stage
#   sched[2] = best        best gap seen since the stage started
#   sched[3] = best_prev   best at the last watch reset (as _GapWatch's)
#   sched[4] = t_next      1-based round the NEXT chunk starts at (the
#                          chunk kernels advance it; the warm-start loss
#                          handoff reads it — solvers/cocoa.py)
#
# All five values are small integers or f32 gaps, so float32 carries them
# exactly; stage/stall arithmetic in f32 is exact far beyond any real
# ladder or window length.
SCHED_LEN = 5
MAX_SIGMA_LEVELS = 8

# --- accelerated outer loop (--accel, round 12) -----------------------------
#
# Secant (Anderson-1) extrapolation on the DUAL at eval-window boundaries,
# plus adaptive local accuracy Θ (the outer-acceleration + inexact-local-
# solve structure of Smith et al., arXiv:1711.05305 — PAPERS.md).  The
# solver state gains a (2, K, n_shard) dual-history leaf ``hist`` — the
# two previous eval-boundary α snapshots — and EIGHT more f32 slots
# appended to the sched vector, so an accelerated state tuple is
#
#   state = (w, alpha, hist, sched)     len(sched) = SCHED_LEN + ACCEL_LEN
#
#   sched[5]  = hist_len   valid α-window snapshots banked (0, 1, 2)
#   sched[6]  = jump       a secant jump is armed for the next chunk head
#   sched[7]  = restarts   cumulative gap-monitored momentum restarts
#   sched[8]  = last_gap   the previous eval's gap (the restart trigger)
#   sched[9]  = th_stage   Θ ladder index (inner steps per round)
#   sched[10] = th_stall   Θ watch: consecutive no-improvement evals
#   sched[11] = th_best    Θ watch best gap since the stage started
#   sched[12] = th_best_prev
#
# The jump itself is solvers/cocoa.secant_jump, at a chunk's head; a gap
# RISE at an eval boundary discards the bank (restart), so the damage of a
# bad jump is bounded to one eval cadence.  All slots are small integers or
# f32 gaps — exact in float32, exact in the checkpoint meta JSON round
# trip.  Why a signed secant coefficient and not momentum, and what was
# measured out: docs/DESIGN.md §11.
ACCEL_LEN = 8
A_HIST = SCHED_LEN
A_JUMP = SCHED_LEN + 1
A_RESTARTS = SCHED_LEN + 2
A_LASTGAP = SCHED_LEN + 3
A_TH_STAGE = SCHED_LEN + 4
A_TH_STALL = SCHED_LEN + 5
A_TH_BEST = SCHED_LEN + 6
A_TH_BPREV = SCHED_LEN + 7

# c = ρ/(1−min(ρ, RHO_CAP)) clipped to [CMIN, CMAX]: the cap keeps the
# pole at ρ→1 finite before the clip, CMIN = −0.5 is exact pairwise
# averaging (the stable limit for a pure oscillation), CMAX = 3 the
# measured knee — the rcv1-synth sweep resolved c ≈ 2.2–2.7 under a cap
# of 3 and of 8 identically (same 800-round trajectory), so 3 bounds a
# bad estimate without binding the good ones.
ACCEL_CMIN = -0.5
ACCEL_CMAX = 3.0
ACCEL_RHO_CAP = 0.9


def secant_coef(xp, rho):
    """The shared jump-coefficient rule (xp = jnp when traced, np for
    tests): c = ρ/(1−ρ) with the ρ-cap and [CMIN, CMAX] clip.  Exact f32
    ops only (one divide, min, clip)."""
    den = xp.float32(1.0) - xp.minimum(rho, xp.float32(ACCEL_RHO_CAP))
    return xp.clip(rho / den, xp.float32(ACCEL_CMIN),
                   xp.float32(ACCEL_CMAX))

# Θ (local accuracy) schedule: early rounds run H/divisor inner SDCA
# steps — cheap, imprecise local solves while the gap is far from the
# target — and the ladder tightens toward the full H as the run
# approaches certification.  Two advance triggers, both device-computable
# from the current gap estimate:
#   - near-target: gap ≤ THETA_NEAR × gap_target jumps straight to the
#     final (full-H) stage, so certification always happens at full
#     local accuracy;
#   - stall: the per-stage watch (same _watch_update arithmetic as the
#     σ′ anneal, rel = THETA_REL) fires after THETA_EVALS consecutive
#     evals without the best gap HALVING — a deliberately strict bar:
#     loose stages are only worth keeping while the gap is in its early
#     fast-decay phase (measured: an H/4 stage that merely *improves*
#     ~30%/eval never fires a 0.9-rel watch and the run crawls; the
#     0.5-rel watch moves it up within two evals).
# The ladder starts at H/2, not lower: H has strongly diminishing
# returns at the top (2×/10× MORE local work buys only 1.06–1.10×
# fewer rounds), so halving it costs almost nothing per
# round — but an H/4 stage was measured to push the λ=1e-4 rcv1-synth
# A/B from 800 to 925 rounds (the early fast-decay rounds ARE
# productive, and their secant windows degrade too: 6 restarts vs 2).
# A Θ stage advance also clears the secant window bank (the two banked
# windows came from a DIFFERENT round map — a jump across the seam
# extrapolates the wrong geometric tail).
THETA_DIVS = (2, 1)
THETA_REL = 0.5
THETA_EVALS = 1
THETA_NEAR = 10.0


def theta_ladder(h: int, adaptive: bool) -> tuple:
    """Per-Θ-stage inner-iteration counts, coarse → exact.  The final
    rung is always the full ``h`` (certification runs at full local
    accuracy); a small ``h`` collapses duplicate rungs away."""
    if not adaptive:
        return (int(h),)
    out = []
    for dv in THETA_DIVS:
        hs = min(int(h), max(1, int(h) // dv))
        if not out or hs > out[-1]:
            out.append(hs)
    return tuple(out)


def _emit_accel_events(name, t, restarted, restarts_total, staged, stage,
                       theta_hs: tuple, quiet):
    """The typed momentum_restart / theta_stage events for one eval
    boundary (emitted regardless of ``quiet`` — same policy as
    the σ′ back-off's)."""
    from cocoa_tpu.telemetry import events as _tele

    bus = _tele.get_bus()
    if restarted:
        bus.emit("momentum_restart", algorithm=name, t=int(t),
                 restarts_total=int(restarts_total))
        if not quiet:
            print(f"{name}: momentum restart at round {t} (gap rose; "
                  f"secant window bank discarded)")
    if staged:
        bus.emit("theta_stage", algorithm=name, t=int(t), stage=int(stage),
                 h=int(theta_hs[int(stage)]))
        if not quiet:
            print(f"{name}: Θ schedule — local accuracy raised to "
                  f"H={theta_hs[int(stage)]} at round {t}")


def anneal_levels(start: float, safe: float, factor: float = 2.0,
                  max_levels: int = MAX_SIGMA_LEVELS) -> tuple:
    """The static σ′ ladder: geometric from the aggressive ``start`` up to
    the paper-safe ``safe`` = K·γ (always the final rung — the schedule can
    never anneal PAST safety; a ladder that would exceed ``max_levels``
    jumps straight to safe on its last step)."""
    if start >= safe:
        return (float(safe),)
    levels = [float(start)]
    while levels[-1] * factor < safe and len(levels) < max_levels - 1:
        levels.append(levels[-1] * factor)
    levels.append(float(safe))
    return tuple(levels)


def sched_init_values(start_round: int, sched_init=None,
                      accel: bool = False) -> np.ndarray:
    """The initial sched vector (see the layout notes above) as host
    constants: a restored mid-schedule state, or a fresh stage-0 watch
    starting at ``start_round``.  With ``accel`` the vector carries the
    ACCEL_LEN momentum/Θ tail too; a restored plain (SCHED_LEN,) state is
    extended with fresh accel slots (resuming a pre-accel checkpoint
    restarts the momentum sequence — sound: any (w, α) is a valid
    primal-dual pair), and an accel-length state resumed WITHOUT accel
    keeps its σ′ head.  float32 NumPy: the SDCA family's start program
    takes it as an argument (nothing of it is computed on the device)."""
    head = np.array([0.0, 0.0, np.inf, np.inf, float(start_round)],
                    dtype=np.float32)
    tail = np.array([0.0, 0.0, 0.0, np.inf, 0.0, 0.0, np.inf, np.inf],
                    dtype=np.float32)
    if sched_init is not None:
        s = np.asarray(sched_init, dtype=np.float32)
        if s.shape not in ((SCHED_LEN,), (SCHED_LEN + ACCEL_LEN,)):
            raise ValueError(
                f"restored sigma-schedule state has shape {s.shape}, "
                f"expected ({SCHED_LEN},) or ({SCHED_LEN + ACCEL_LEN},) — "
                f"was the checkpoint written by an incompatible version?")
        if accel and s.shape == (SCHED_LEN,):
            s = np.concatenate([s, tail])
        elif not accel and s.shape == (SCHED_LEN + ACCEL_LEN,):
            s = s[:SCHED_LEN]
        return s
    return np.concatenate([head, tail]) if accel else head


def _watch_update(xp, gv, best, best_prev, stall, rel):
    """ONE windowed no-improvement step — the single arithmetic behind
    every stall watch (the device loops' carry watch, the host's
    :class:`_GapWatch`, and :func:`eval_boundary_update`'s σ′ and Θ
    watches; ``xp`` is jnp when traced, np on the host).  Callers pass
    ``rel`` at the dtype the comparison must run in (float32 for the
    schedule leaf's).  Returns (best, best_prev, stall)."""
    best = xp.minimum(best, gv)
    improved = best <= rel * best_prev
    stall = xp.where(improved, xp.zeros_like(stall), stall + 1)
    best_prev = xp.where(improved, best, best_prev)
    return best, best_prev, stall


def _write_back(state, sched_np, push: bool):
    """Write one eval boundary's update back into the state tuple: the
    sched vector (by convention the LAST leaf of a scheduled state — the
    checkpoint savers rely on the same invariant) and, where the update
    says ``push`` (a bank, no jump armed: an armed bank is frozen for the
    kernel head to consume), the current α banked as the newest window
    snapshot, hist ← [hist[1], α].  A replacement keeps the old leaf's
    placement: under an explicit mesh the initialization committed the
    leaves with a NamedSharding, and a bare array would re-enter the
    donating jitted step with mismatched sharding typing.  ``jnp.stack``
    materializes a fresh buffer, so the hist leaf never aliases the
    separately-donated α arg."""
    import jax.numpy as jnp

    def placed(new, old):
        sharding = getattr(old, "sharding", None)
        return new if sharding is None else jax.device_put(new, sharding)

    state = (*state[:-1], placed(jnp.asarray(sched_np), state[-1]))
    if push:
        hist = placed(jnp.stack([state[2][1], state[1]]), state[2])
        state = (*state[:2], hist, *state[3:])
    return state


class BoundaryUpdate(NamedTuple):
    """What :func:`eval_boundary_update` hands back.  ``head`` / ``tail``:
    the schedule leaf's new σ′ head (``SCHED_LEN`` fields on the last axis)
    and bank / Θ tail (``ACCEL_LEN``), None for the half the job does not
    run.  ``push``: bank the current α (no jump armed, no target hit).
    ``backed`` / ``restarted`` / ``staged``: this eval backed σ′ off,
    restarted the bank, advanced Θ.  ``cols``: the trajectory row's
    (σ′ stage, stall, Θ stage, restarts) after the update."""
    head: object
    tail: object
    push: object
    backed: object
    restarted: object
    staged: object
    cols: tuple


def eval_boundary_update(xp, sched, gv, done_tgt, *, stall_evals: int,
                         n_stages: int, n_theta: int, tgt) -> BoundaryUpdate:
    """THE eval-boundary update of the schedule leaf, for every driver:
    traced in the device loop (``xp`` = jnp, scalar fields) and in the
    fleet loop (a leading tenant axis), NumPy in the host-stepped drivers
    — the same float32 ``where`` arithmetic, so all of them make the same
    decisions and a resume is bit-identical.  ``sched``: the leaf, fields
    on its last axis (layout notes at :data:`SCHED_LEN` and
    :data:`ACCEL_LEN`); ``gv``: the eval's gap as float32, +inf for none;
    ``done_tgt``: the eval hit its target — the watches still count, and
    every ACTION (back-off, restart, arm, bank, Θ advance) is suppressed,
    so the run ends on the state the target was certified at.

    ``n_stages`` > 1 runs the σ′ stall watch: a window of ``stall_evals``
    evals without improvement at a non-final stage bumps the stage (the
    next chunk's kernel reads it) and starts a fresh watch; the final,
    safe stage is inert.  ``n_theta`` >= 1 (the state carries ``hist``)
    runs the secant bank, one of three outcomes an eval: the gap ROSE —
    restart, the bank is discarded and begins again from this eval's α;
    two windows banked and the gap still improving — ARM the jump for the
    next chunk head and freeze the bank; otherwise bank this eval's α.
    ``n_theta`` > 1 also runs the Θ ladder (near ``tgt``: straight to the
    full H; a missed halving: one rung up).  A σ′ back-off or a Θ advance
    is a seam in the round map: windows banked before it measured another
    map, so the bank is capped at the α just banked (an armed jump stays
    armed — all its points predate the seam)."""
    f32 = xp.float32
    head = tail = push = stg = stl = thst = rst = None
    bo = restart = step = False
    if n_stages > 1:
        stg, stl, bst, bpv = (sched[..., 0], sched[..., 1], sched[..., 2],
                              sched[..., 3])
        bst, bpv, stl = _watch_update(xp, gv, bst, bpv, stl, f32(STALL_REL))
        fired = stl >= f32(stall_evals)
        bo = (fired & (stg < f32(n_stages - 1)) & xp.logical_not(done_tgt))
        inf32 = f32(xp.inf)
        stg = xp.where(bo, stg + 1, stg)
        stl = xp.where(bo, f32(0), stl)
        bst = xp.where(bo, inf32, bst)
        bpv = xp.where(bo, inf32, bpv)
        head = xp.stack([stg, stl, bst, bpv, sched[..., 4]], axis=-1)
    if n_theta:
        hl, rst, lg = (sched[..., A_HIST], sched[..., A_RESTARTS],
                       sched[..., A_LASTGAP])
        restart = (gv > lg) & xp.logical_not(done_tgt)
        arm = ((hl >= f32(2)) & xp.logical_not(restart)
               & xp.logical_not(done_tgt))
        rst = xp.where(restart, rst + 1, rst)
        hl = xp.where(
            done_tgt, hl,
            xp.where(arm, f32(0),
                     xp.where(restart, f32(1), xp.minimum(hl + 1, f32(2)))))
        jmp = xp.where(arm, f32(1), f32(0))
        lg = xp.where(done_tgt, lg, gv)
        push = xp.logical_not(arm) & xp.logical_not(done_tgt)
        thst = sched[..., A_TH_STAGE]
        thstl, thb, thbp = (sched[..., A_TH_STALL], sched[..., A_TH_BEST],
                            sched[..., A_TH_BPREV])
        if n_theta > 1:
            thb, thbp, thstl = _watch_update(xp, gv, thb, thbp, thstl,
                                             f32(THETA_REL))
            tgt32 = f32(-xp.inf if tgt is None else tgt)
            near = gv <= f32(THETA_NEAR) * tgt32
            fire = thstl >= f32(THETA_EVALS)
            can = thst < f32(n_theta - 1)
            step = (near | fire) & can & xp.logical_not(done_tgt)
            thst = xp.where(step,
                            xp.where(near, f32(n_theta - 1), thst + 1), thst)
            inf32 = f32(xp.inf)
            thstl = xp.where(step, f32(0), thstl)
            thb = xp.where(step, inf32, thb)
            thbp = xp.where(step, inf32, thbp)
            hl = xp.where(step, xp.minimum(hl, f32(1)), hl)
        if n_stages > 1:
            hl = xp.where(bo, xp.minimum(hl, f32(1)), hl)
        tail = xp.stack([hl, jmp, rst, lg, thst, thstl, thb, thbp], axis=-1)
    return BoundaryUpdate(head, tail, push, bo, restart, step,
                          (stg, stl, thst, rst))


def _host_eval(traj, name, t, state, eval_fn, gap_target, stall_evals,
               sigma_levels, accel, quiet):
    """One eval boundary of a host-stepped driver: evaluate, run
    :func:`eval_boundary_update` in NumPy on ``state[-1]`` (where the job
    carries a schedule: ``sigma_levels`` the σ′ ladder where the anneal is
    on, ``accel`` the bank's Θ ladder), write the leaves back, log the
    round and emit the events (typed events regardless of ``quiet``: the
    machine-readable trace survives a silenced console).  Returns
    ``(state, hit)``."""
    from cocoa_tpu.telemetry import events as _tele

    with _tracing.span("eval", algorithm=name, round=t):
        primal, gap, test_err, *per_class = eval_fn(state)
        _sanitize.count_launch()
    hit = gap_target is not None and gap is not None and gap <= gap_target
    fields, backed = {}, False
    if sigma_levels is not None or accel is not None:
        sched = np.asarray(state[-1], dtype=np.float32)
        gv = (np.float32(np.inf) if gap is None or np.isnan(gap)
              else np.float32(gap))
        upd = eval_boundary_update(
            np, sched, gv, hit, stall_evals=stall_evals,
            n_stages=0 if sigma_levels is None else len(sigma_levels),
            n_theta=0 if accel is None else len(accel), tgt=gap_target)
        backed = bool(upd.backed)
        if upd.head is not None:
            stage = int(upd.cols[0])
            fields = dict(sigma=sigma_levels[stage], sigma_stage=stage,
                          stall=int(upd.cols[1]))
        state = _write_back(state, np.concatenate(
            [sched[:SCHED_LEN] if upd.head is None else upd.head,
             *(() if upd.tail is None else (upd.tail,))]), bool(upd.push))
        if accel is not None:
            _emit_accel_events(name, t, bool(upd.restarted),
                               int(upd.cols[3]), bool(upd.staged),
                               int(upd.cols[2]), accel, quiet)
    # (a one-vs-rest eval adds every class's gap past the three, which are
    # then the worst class's: stop rule, budget and watch read the worst)
    traj.log_round(t, primal=primal, gap=gap, test_error=test_err, **fields,
                   **_tele.per_class_fields(
                       per_class[0] if per_class else (), gap_target))
    if backed:      # (one rung an eval: the σ′ left is the stage below)
        _tele.get_bus().emit(
            "sigma_backoff", algorithm=name, t=int(t),
            sigma=sigma_levels[stage], from_sigma=sigma_levels[stage - 1],
            stage=stage)
        if not quiet:
            print(f"{name}: σ′ anneal — gap stalled for {stall_evals} "
                  f"evals; backing off to σ′={sigma_levels[stage]:g} at "
                  f"round {t} (iterate kept, certificate exact)")
    return state, hit


def resolve_divergence_guard(flag: str, mode: str, sigma: float, k: int,
                             gamma: float) -> bool:
    """Resolve the ``--divergenceGuard`` flag to an armed/disarmed bool.

    ``on``/``off`` force it.  ``auto`` (default) arms the guard only when
    σ′ is overridden BELOW the paper-safe K·γ bound — the one regime where
    certified divergence is an expected outcome the run should bail out of
    (the --sigma sweep / sigma=auto trials).  A safe-σ′ run that converges
    slowly is left to its round budget instead of being mislabeled
    DIVERGED (ADVICE r5: the always-armed guard killed slow-but-converging
    problems).  Modes whose subproblem never reads σ′ (cocoa's advancing
    local view, frozen's plain gradient) never arm on auto."""
    if flag not in ("auto", "on", "off"):
        raise ValueError(
            f"divergence guard must be auto|on|off, got {flag!r}")
    if flag != "auto":
        return flag == "on"
    return mode in ("plus", "prox") and sigma < k * gamma


def _last_gap(traj):
    """The most recent eval-cadence duality gap the trajectory holds
    (None before the first eval / on gap-less solvers) — what every
    checkpoint save stamps into its meta so the serving hot-swap
    watcher can report which certificate the model it publishes
    carries (cocoa_tpu/serving/, docs/DESIGN.md §17)."""
    for rec in reversed(traj.records):
        if rec.gap is not None:
            return float(rec.gap)
    return None


def checkpoint_arguments(debug: DebugParams, name: str, round_t: int,
                         state: tuple, traj, ckpt_rows=None) -> tuple:
    """``(args, kwargs)`` of the ``checkpoint.save`` every drive* path makes
    of ``state`` — ``(w,)``, ``(w, α)``, ``(w, α, sched)`` or ``(w, α, hist,
    sched)``.  ``ckpt_rows`` (``data.sharding.rows_as_built`` of a dataset
    whose rows were put in length order) maps what is kept by row — α and
    the ``--accel`` window bank — to the rows' positions as the shards were
    built: a checkpoint never depends on the order a dataset keeps its rows
    in, so it resumes under a freshly built one."""
    alpha = state[1] if len(state) > 1 else None
    hist = state[2] if len(state) > 3 else None
    if ckpt_rows is not None:
        alpha = None if alpha is None else ckpt_rows(alpha)
        hist = None if hist is None else ckpt_rows(hist)
    return ((debug.chkpt_dir, name, round_t, state[0], alpha),
            dict(seed=debug.seed,
                 sched=state[-1] if len(state) > 2 else None,
                 hist=hist, gap=_last_gap(traj)))


class _GapWatch:
    """Windowed no-improvement watch over eval-cadence gap values;
    ``update(gap)`` returns True when the run should bail out (diverged or
    irrecoverably stalled — the gap certificate is exact either way)."""

    def __init__(self, n_evals: int = STALL_EVALS, rel: float = STALL_REL):
        self.n = n_evals
        self.rel = rel
        self.best = float("inf")
        self.best_prev = float("inf")   # best at the last reset
        self.stall = 0

    def update(self, gap) -> bool:
        if gap is None:
            return False
        self.best, self.best_prev, self.stall = _watch_update(
            np, float(gap), self.best, self.best_prev, self.stall, self.rel)
        return bool(self.stall >= self.n)


def drive_chunked(
    name: str,
    params: Params,
    debug: DebugParams,
    state: tuple,
    chunk_fn: Callable[[int, int, tuple], tuple],
    eval_fn: Callable[[tuple], tuple],
    quiet: bool = False,
    gap_target: Optional[float] = None,
    start_round: int = 1,
    chunk: int = 50,
    divergence_guard: bool = True,
    sigma_levels: Optional[tuple] = None,
    accel: Optional[tuple] = None,
    ckpt_rows=None,
):
    """The host-stepped outer driver shared by every solver
    (CoCoA.scala:39-63 skeleton): run rounds, gate evaluation to every
    ``debugIter`` rounds, checkpoint every ``chkptIter`` rounds, optionally
    stop early on a duality-gap target (or on measured divergence — see
    STALL_EVALS; ``divergence_guard=False`` disarms the stall watch, see
    :func:`resolve_divergence_guard`).  Rounds run in blocks of up to
    ``chunk`` (one dispatch a block; ``chunk=1`` is the per-round driver),
    cut at the ``debugIter`` and ``chkptIter`` boundaries, so the
    observable trajectory does not depend on ``chunk`` and same-size
    blocks share one compiled executable.

    ``state`` is ``(w,)``, ``(w, alpha)`` or either with the leaves of a
    schedule; ``chunk_fn(t0, c, state) -> state`` advances rounds
    t0..t0+c-1; ``eval_fn(state) -> (primal, gap_or_None,
    test_error_or_None)``.  Returns (state, Trajectory).

    ``sigma_levels`` (more than one): the run carries the σ′-anneal
    schedule in ``state[-1]`` (layout note at :data:`SCHED_LEN`); the
    stall watch then BACKS OFF σ′ in place (:func:`eval_boundary_update`,
    what the device loop runs in its body) instead of bailing out, and the
    final (safe K·γ) stage simply runs to its round budget: a scheduled
    run never reports DIVERGED, because its last rung is the paper-safe
    bound.  ``accel`` (the Θ ladder of a job whose state carries the
    ``--accel`` bank, :func:`theta_ladder`): the restart / arm / bank step
    and the Θ step ride the same update; an armed jump executes at the
    head of the NEXT chunk dispatch — the kernel has the shard data in
    scope.
    """
    if chunk <= 0:
        raise ValueError(f"chunk must be positive, got {chunk}")
    anneal_on = (sigma_levels is not None and len(sigma_levels) > 1
                 and gap_target is not None and divergence_guard)
    traj = Trajectory(name, quiet=quiet)
    watch = _GapWatch(n_evals=stall_window(debug.debug_iter))
    t = start_round
    total = params.num_rounds
    ckpt_on = bool(debug.chkpt_dir) and debug.chkpt_iter > 0
    while t <= total:
        end = min(total, t + chunk - 1)
        if debug.debug_iter > 0:
            end = min(end, ((t - 1) // debug.debug_iter + 1) * debug.debug_iter)
        if ckpt_on:
            end = min(end, ((t - 1) // debug.chkpt_iter + 1) * debug.chkpt_iter)
        c = end - t + 1
        with _tracing.span("local_solve", algorithm=name, round=end,
                           t0=t, rounds=c):
            state = chunk_fn(t, c, state)
            _sanitize.count_launch()
        t = end + 1

        if debug.debug_iter > 0 and end % debug.debug_iter == 0:
            state, hit = _host_eval(
                traj, name, end, state, eval_fn, gap_target, watch.n,
                sigma_levels if anneal_on else None, accel, quiet)
            if hit:
                traj.stopped = "target"
                break
            if (not anneal_on and gap_target is not None and divergence_guard
                    and watch.update(traj.records[-1].gap)):
                traj.mark_diverged(end, watch.n)
                break

        if ckpt_on and end % debug.chkpt_iter == 0:
            args, kwargs = checkpoint_arguments(debug, name, end, state,
                                                traj, ckpt_rows)
            ckpt_lib.save(*args, **kwargs)
    return state, traj


class ExecutableCache(OrderedDict):
    """Bounded LRU for jitted executables (VERDICT r4: the per-config
    caches grew forever in the long-lived bench process, which sweeps
    dozens of configs).  Eviction drops the Python reference; XLA frees
    the underlying executable when the last reference dies.  The cap is
    sized so no realistic single run ever evicts (a run touches a handful
    of configs) while a sweep stays bounded."""

    def __init__(self, cap: int = 64):
        super().__init__()
        self.cap = cap

    def get(self, key, default=None):
        v = super().get(key, default)
        if key in self:
            self.move_to_end(key)
        return v

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        self.move_to_end(key)
        while len(self) > self.cap:
            self.popitem(last=False)


_DEVICE_RUNS: dict = ExecutableCache()

# cap on the resident (n_chunks, C, K, H) int32 index table per device-loop
# dispatch; runs needing more split into super-blocks (tests shrink this)
MAX_IDX_TABLE_BYTES = 256 << 20

# index-table size (ints) below which host-side sampling is cheap enough
# (~tens of ms) to do eagerly in one block — the geometric early-stop
# schedule only pays off above this
SMALL_TABLE_INTS = 4_000_000


class _Prefetch:
    """Run fn(*args) on a daemon thread; .result() joins and returns (or
    re-raises).  Used to overlap index sampling with device execution —
    daemon so an abandoned speculative block can never delay process
    exit."""

    def __init__(self, fn, *args):
        import threading

        self._out = self._err = None
        self._t = threading.Thread(target=self._run, args=(fn, args),
                                   daemon=True)
        self._t.start()

    def _run(self, fn, args):
        try:
            self._out = fn(*args)
        except BaseException as e:  # re-raised on the consumer side
            self._err = e

    def result(self):
        self._t.join()
        if self._err is not None:
            raise self._err
        return self._out


def _build_device_run(chunk_kernel, eval_kernel, gap_target, n_state,
                      mesh=None, stall_evals=STALL_EVALS,
                      divergence_guard=True, n_stages=0, stream=False,
                      accel=None):
    import functools

    import jax.numpy as jnp
    from jax import lax

    tgt = -jnp.inf if gap_target is None else float(gap_target)
    # divergence bail-out rides the loop carry only for gap-targeted runs
    # with the guard armed: fixed-round runs are the benchmark timing paths
    # and must execute exactly their round budget
    check_div = gap_target is not None and divergence_guard
    # n_stages > 1: σ′-anneal mode — the stall watch lives in the state
    # tuple's sched leaf (persisting across super-block dispatches and
    # into checkpoints), and firing BACKS OFF the schedule stage in place
    # instead of stopping the loop; the final stage is the safe K·γ bound,
    # so a scheduled run never stops "diverged" (eval_boundary_update).
    anneal = check_div and n_stages > 1
    # every eval writes one [primal, gap, test_err, sigma_stage, stall,
    # theta_stage, restarts] row: cols 0-2 are the eval metrics, col 3 the
    # post-update σ′ ladder stage (NaN outside anneal mode), col 4 the
    # post-update stall-watch counter, col 5 the post-update Θ ladder
    # stage and col 6 the cumulative momentum-restart count (both NaN
    # outside --accel runs).  The row feeds the trajectory buffer AND —
    # with ``stream`` — an ordered io_callback that posts it to the
    # telemetry bus while the loop is still on device (side-effect-only:
    # nothing in the loop carry reads it, so a streaming run is
    # bit-identical to a non-streaming one — the fetch-fallback replays
    # the same buffer).  An eval kernel that returns more than the three
    # (a one-vs-rest job's per-class gaps, evals/objectives.py) gets a
    # column for each past the seven; the loop reads none of them.
    n_cols = 7

    @functools.partial(jax.jit, donate_argnums=tuple(range(n_state)))
    def run(*args):
        state = args[:n_state]
        idxs_all, shard_arrays, test_arrays = args[n_state:]
        # idxs_all is a pytree (a bare (n_chunks, C, K, H) table, or a dict
        # also carrying a per-round (n_chunks, C) t leaf for η(t) solvers);
        # static at trace time — a different block length just retraces
        n_chunks = jax.tree.leaves(idxs_all)[0].shape[0]
        if isinstance(shard_arrays, dict):
            # the dense kernels' row ring reads whole lane tiles: a fold
            # cache that is not stored so is relaid once a dispatch, here,
            # by one kernel of the program's own (ops/pallas_sdca
            # .lane_aligned), never in the loop's body, where it would
            # run every round
            from cocoa_tpu.ops.pallas_sdca import with_aligned_rows

            shard_arrays = with_aligned_rows(shard_arrays, mesh)

        def cond(s):
            i, done_tgt, done_stall, stall, best, best_prev, state, traj = s
            return (i < n_chunks) & jnp.logical_not(done_tgt | done_stall)

        def body(s):
            i, done_tgt, done_stall, stall, best, best_prev, state, traj = s
            with jax.named_scope(_tracing.SCOPE_INDICES):
                chunk = jax.tree.map(lambda a: a[i], idxs_all)
            state = chunk_kernel(state, chunk, shard_arrays)
            metrics = eval_kernel(state, shard_arrays, test_arrays)
            done_tgt = metrics[1] <= tgt
            nanv = jnp.asarray(jnp.nan, metrics.dtype)
            if check_div and not anneal:
                # windowed no-improvement watch (_GapWatch's, traced): NaN
                # gaps (primal-only eval) map to +inf, leaving best — and
                # the always-true inf <= rel·inf reset — untouched
                gv = jnp.where(jnp.isnan(metrics[1]),
                               jnp.asarray(jnp.inf, best.dtype), metrics[1])
                best, best_prev, stall = _watch_update(
                    jnp, gv, best, best_prev, stall, STALL_REL)
                # the target wins a tie (the host drivers check that order)
                done_stall = (stall >= stall_evals) & jnp.logical_not(done_tgt)
                extra = jnp.stack([nanv, stall.astype(metrics.dtype)])
            elif not anneal:
                extra = jnp.stack([nanv, jnp.zeros((), metrics.dtype)])
            if anneal or accel is not None:
                # the schedule leaf's update, in-state: the stage a fired
                # watch bumps is read by the NEXT chunk's kernel, an armed
                # jump executes at its head (solvers/cocoa.py: the shard
                # data the correspondence update needs is in scope there)
                sched = state[-1]
                gv = jnp.where(jnp.isnan(metrics[1]), jnp.inf,
                               metrics[1]).astype(jnp.float32)
                upd = eval_boundary_update(
                    jnp, sched, gv, done_tgt, stall_evals=stall_evals,
                    n_stages=n_stages if anneal else 0,
                    n_theta=0 if accel is None else len(accel),
                    tgt=gap_target)
                stg, stl, thst, rst = upd.cols
                if accel is not None:
                    # the bank action: unless this eval armed a jump (the
                    # bank is then frozen for the kernel head to consume),
                    # the current α joins as the newest window snapshot;
                    # state is (w, alpha, hist, sched)
                    hist_leaf = jnp.where(
                        upd.push, jnp.stack([state[2][1], state[1]]),
                        state[2])
                    state = (state[0], state[1], hist_leaf,
                             jnp.concatenate(
                                 [sched[:SCHED_LEN] if upd.head is None
                                  else upd.head, upd.tail]))
                    extra2 = jnp.stack([thst.astype(metrics.dtype),
                                        rst.astype(metrics.dtype)])
                else:
                    state = (*state[:-1], upd.head)
                if anneal:
                    extra = jnp.stack([stg.astype(metrics.dtype),
                                       stl.astype(metrics.dtype)])
            if accel is None:
                extra2 = jnp.stack([nanv, nanv])
            row = jnp.concatenate([metrics[:3], extra, extra2, metrics[3:]]
                                  if n_more else [metrics, extra, extra2])
            if stream:
                # side-effect-only event bridge: post this eval's row to
                # the host WHILE THE LOOP RUNS.  Ordered, so the host sees
                # evals in execution order; nothing downstream reads it,
                # so the compute is untouched (telemetry/events.py).
                from jax.experimental import io_callback

                from cocoa_tpu.telemetry import events as _tele

                io_callback(_tele._device_sink, None, i, row, ordered=True)
            traj = lax.dynamic_update_index_in_dim(traj, row, i, 0)
            return (i + jnp.int32(1), done_tgt, done_stall, stall, best,
                    best_prev, state, traj)

        n_more = jax.eval_shape(eval_kernel, state, shard_arrays,
                                test_arrays).shape[0] - 3
        traj0 = jnp.full((n_chunks, n_cols + n_more), jnp.nan,
                         dtype=state[0].dtype)
        if mesh is not None:
            # metrics coming out of the shard_mapped eval carry the (Explicit)
            # mesh in their sharding type; the update target must match
            from jax.sharding import NamedSharding, PartitionSpec as P

            traj0 = lax.with_sharding_constraint(
                traj0, NamedSharding(mesh, P(None, None))
            )
        (i, done_tgt, done_stall, stall, best, best_prev, state,
         traj) = lax.while_loop(
            cond, body,
            (jnp.int32(0), jnp.asarray(False), jnp.asarray(False),
             jnp.int32(0),
             jnp.asarray(jnp.inf, dtype=state[0].dtype),
             jnp.asarray(jnp.inf, dtype=state[0].dtype), state, traj0),
        )
        # what the host reads of the loop, as two leaves: the stop round
        # and the two stop flags in one int32 vector, beside the buffer
        # (whose dtype is the state's and could not hold a round count)
        head = jnp.stack([i, done_tgt.astype(jnp.int32),
                          done_stall.astype(jnp.int32)])
        return head, state, traj

    return run


def fetch_loop_result(head, traj_buf, label: str):
    """The one read of a device loop, solo or fleet: ``head`` (the count
    of chunks done first, whatever stop flags follow it) and the WHOLE
    trajectory buffer come back as one host copy — every leaf's transfer
    is started before the first is waited for — and the rows the loop
    wrote are cut on the host, so no program is launched once the loop
    has ended.  Opens the ``fetch`` span and the sanctioned
    ``intended_fetch(label)``.  Returns ``(head, rows)`` as NumPy."""
    with _tracing.span("fetch"), _sanitize.intended_fetch(label):
        head_host, traj_host = jax.device_get((head, traj_buf))
    head_host = np.atleast_1d(head_host)
    return head_host, traj_host[:int(head_host[0])]


def drive_on_device(
    name: str,
    state: tuple,
    chunk_kernel: Callable,   # (state, idxs_ckh, shard_arrays) -> state, traceable
    eval_kernel: Callable,    # (state, shard_arrays, test_arrays) -> (3,) metrics
    idxs_all,                 # (n_chunks, C, K, H) int32, C = eval cadence
    shard_arrays,
    test_arrays=None,
    quiet: bool = False,
    gap_target: Optional[float] = None,
    start_round: int = 1,
    cache_key=None,
    mesh=None,
    stall_evals: int = STALL_EVALS,
    divergence_guard: bool = True,
    sigma_levels: Optional[tuple] = None,
    accel: Optional[tuple] = None,
):
    """Fully device-resident outer driver: the ENTIRE run — every round,
    every ``debugIter`` evaluation, and the gap-target early-stop test — is
    one ``lax.while_loop`` inside one jit.  One dispatch, one host fetch:
    between the two the host issues the loop program and nothing else, and
    reads its result once (:func:`fetch_loop_result`: the stop round, the
    stop flags and the whole trajectory buffer in one copy, the rows cut on
    the host).  ``idxs_all`` may be host arrays (the device-mode spec, a
    few dozen integers): their upload rides the dispatch.

    ``sigma_levels`` (more than one): σ′-anneal mode — the stall watch and
    schedule stage ride ``state[-1]`` (see :data:`SCHED_LEN`) and a fired
    window backs the σ′ stage off IN the loop instead of stopping it; the
    per-eval σ′ is decoded into the trajectory records.

    Rationale: the per-round device compute of these solvers is microseconds,
    so the wall-clock of the host-stepped drivers is pure host/device
    round-trip latency (one blocking scalar fetch per eval).
    The reference has the same structure (driver
    JVM ⇄ executors every round, CoCoA.scala:39-63) and pays it; riding the
    whole loop device-side is the TPU-native answer, not a benchmark trick —
    the observable trajectory (eval cadence, stopping round, printed lines)
    is identical to :func:`drive_chunked`.

    ``idxs_all`` carries the eval cadence as its chunk axis (chunks of
    exactly C = debugIter rounds; the caller finishes any num_rounds % C
    remainder through the host-stepped path).  Trajectory metrics land in a
    preallocated device buffer, fetched once.

    Checkpointing is host-side by nature, so it is NOT done here — the
    wrapper :func:`drive_device_full` saves at its super-block boundaries
    (where this function returns and the state is host-reachable).

    ``cache_key``: any hashable token fully determining the closures
    (algorithm + params + flags + mesh + chunk geometry + gap target).  When
    given, the built jit executable is reused across calls — without it every
    call re-jits (closures have fresh identity) and pays ~1s of recompile.
    """
    from cocoa_tpu.telemetry import events as _tele

    c = int(jax.tree.leaves(idxs_all)[0].shape[1])
    tgt = gap_target
    n_state = len(state)
    n_stages = len(sigma_levels) if sigma_levels is not None else 0
    anneal = (tgt is not None and divergence_guard and n_stages > 1)

    # telemetry: with the bus active, each eval's row leaves the while_loop
    # through an ordered io_callback AS IT HAPPENS (single-device paths;
    # the callback placement under an explicit mesh is runtime-dependent,
    # so mesh runs use the fetch replay below).  Where ordered callbacks
    # are unsupported, the SAME tap replays the fetched buffer — identical
    # events, emitted at the end-of-run sync instead of live.
    bus = _tele.get_bus()
    emit = bus.active()
    stream = emit and mesh is None and _tele.io_callback_supported()
    tap = None
    if emit:
        # seed backoff/restart/Θ detection with the values this dispatch
        # ENTERS at (the sched leaf rides super-block boundaries), so a
        # resumed or later-block run never fabricates a transition event
        # on its first eval
        init_stage = init_theta = init_restarts = None
        if anneal or accel is not None:
            with _sanitize.intended_fetch("sched_stage"):
                s0 = np.asarray(state[-1])
            if anneal:
                init_stage = int(s0[0])
            if accel is not None:
                init_theta = int(s0[A_TH_STAGE])
                init_restarts = int(s0[A_RESTARTS])
        tap = _tele.DeviceTap(bus, name, start_round, c,
                              sigma_levels if anneal else None,
                              init_stage=init_stage,
                              theta_hs=accel,
                              init_theta_stage=init_theta,
                              init_restarts=init_restarts, gap_target=tgt)

    run_key = None if cache_key is None else (cache_key, stream)
    run = _DEVICE_RUNS.get(run_key) if run_key is not None else None
    # the loop program's first call pays its trace, lower and compile (or
    # cache load): build_loop around that dispatch, first_run around its
    # fetch (telemetry/tracing.py, the cold path); a warm call opens neither
    first_call = run is None
    if first_call:
        run = _build_device_run(
            chunk_kernel, eval_kernel, tgt, n_state, mesh=mesh,
            stall_evals=stall_evals, divergence_guard=divergence_guard,
            n_stages=n_stages, stream=stream, accel=accel,
        )
        if run_key is not None:
            _DEVICE_RUNS[run_key] = run

    # the sanitizer's device-loop contract (analysis/sanitize.py): from
    # dispatch to the sanctioned fetch, nothing crosses host↔device on
    # this thread.  Inert unless a strict sanitizer armed it.  The one
    # exception is the dispatch itself, where arguments go up: the
    # device-mode spec (host integers), and on a streaming run the ordered
    # io_callback's zero-byte effect token — sanctioned machinery, not a
    # leak.
    #
    # the super-block span: one dispatch + the run's single host fetch —
    # the drive* ladder's host boundary.  Per-eval timing INSIDE the
    # device loop is unobservable by construction (one dispatch, one
    # sync; docs/DESIGN.md clock model), so this span is the finest
    # local-solve timing the device-resident path can honestly report.
    n_chunks = int(jax.tree.leaves(idxs_all)[0].shape[0])
    with _tracing.span("local_solve", algorithm=name, t0=start_round,
                       round=start_round - 1 + n_chunks * c,
                       rounds=n_chunks * c, cadence=c), \
            _sanitize.device_loop_guard(), \
            _tele.device_tap(tap if stream else None):
        # the dispatch is where arguments go up: a spec the ladder built
        # on the host (device-mode sampling) is uploaded by it, sanctioned
        # like the stream's effect token
        with (_tracing.cold_span("build_loop") if first_call
              else contextlib.nullcontext()) as cold:
            if first_call:
                cold.built(run, *state, idxs_all, shard_arrays, test_arrays)
            with _tracing.span("dispatch"), (
                    _sanitize.allow_transfers() if stream
                    else _sanitize.allow_uploads()):
                head, state, traj_buf = run(
                    *state, idxs_all, shard_arrays, test_arrays)
                _sanitize.count_launch()
        # the single host sync of the whole run — marked as the
        # sanctioned fetch point, so the transfer-guard sanitizer
        # (analysis/sanitize.py) can disallow every OTHER device→host
        # path and production --metrics runs count it
        # (host_transfers_total: ~1 per super-block, never per round)
        with (_tracing.cold_span("first_run") if first_call
              else contextlib.nullcontext()):
            head_host, traj_host = fetch_loop_result(head, traj_buf,
                                                     "device_loop_fetch")
        n_done = len(traj_host)
        stop_tgt, stop_stall = bool(head_host[1]), bool(head_host[2])
        if stream:
            # join the callback stream before leaving the tap context —
            # the fetch orders the computation, not the host callbacks
            jax.effects_barrier()
    if tap is not None and not stream:
        # fetch-fallback bridge: replay the buffer through the same tap
        # the stream path uses — same rows, same decode, same events
        for j in range(n_done):
            tap(j, traj_host[j])

    traj = Trajectory(name, quiet=quiet)
    prev_sigma = None
    with _tracing.span("decode_trajectory"):
        for j in range(n_done):
            end = start_round - 1 + (j + 1) * c
            primal, gap, test_err = (float(v) for v in traj_host[j, :3])
            sigma = (sigma_levels[int(traj_host[j, 3])] if anneal
                     else None)
            traj.log_round(
                end, primal=primal,
                # NaN slots mean "not applicable" (no dual state / no test
                # set) — decode to None exactly as objectives.evaluate does
                gap=None if np.isnan(gap) else gap,
                test_error=None if np.isnan(test_err) else test_err,
                # per-round wall-clock is unobservable here: the whole run
                # is one dispatch and one fetch — don't fabricate flat
                # timestamps
                wall_time=None,
                sigma=sigma,
                # events for this run were already emitted by the tap (live
                # stream or fetch replay) — don't double-emit
                emit=False,
                **_tele.per_class_fields(traj_host[j, 7:], tgt),
            )
            if (not quiet and anneal and prev_sigma is not None
                    and sigma != prev_sigma):
                print(f"{name}: σ′ anneal — backed off to σ′={sigma:g} in "
                      f"the device loop at round {end} (iterate kept, "
                      f"certificate exact)")
            prev_sigma = sigma
    # classify from the device-side stop flags themselves (not from
    # n_done < n_chunks, which misses a guard fire on the FINAL chunk —
    # ADVICE r5): the while_loop carried exactly why it stopped
    if tgt is not None:
        if stop_stall:
            traj.stopped = "diverged"   # caller reports (with the round)
        elif stop_tgt:
            traj.stopped = "target"
    return state, traj


def drive_device_full(
    name: str,
    params: Params,
    debug: DebugParams,
    state: tuple,
    chunk_kernel: Callable,   # (state, idxs_ckh, shard_arrays) -> state
    eval_kernel: Callable,    # (state, shard_arrays, test_arrays) -> (3,)
    chunk_fn: Callable,       # (t0, c, state) -> state, host-stepped (jitted)
    eval_fn: Callable,        # (state) -> (primal, gap|None, test_err|None)
    sampler: "IndexSampler",
    shard_arrays,
    test_arrays=None,
    quiet: bool = False,
    gap_target: Optional[float] = None,
    start_round: int = 1,
    cache_key=None,
    mesh=None,
    divergence_guard: bool = True,
    sigma_levels: Optional[tuple] = None,
    accel: Optional[tuple] = None,
    overlap_io: bool = False,
    ckpt_rows=None,
):
    """Cadence-aligned wrapper around :func:`drive_on_device`, usable by any
    solver whose round has the (state, idxs, shards) shape: host-steps the
    off-cadence head (a resumed ``start_round`` is usually not on a
    ``debugIter`` boundary), rides all full eval-cadence chunks device-side
    as one dispatch, then host-steps the sub-cadence tail (num_rounds %
    debugIter remainder, no eval — same observable behavior as
    :func:`drive_chunked`).  Returns (state, Trajectory).

    A super-block's tables come one of two ways, by ``sampler.device``:
    where the kernels sample in-jit they are a NumPy spec of round numbers
    built on this thread under ``wait_indices`` and uploaded by the loop's
    dispatch — no thread, no program; where they are host tables a staging
    thread samples block i+1's under ``stage_indices`` while the device
    runs block i.  Either way a block is one loop program and one read.

    With ``sigma_levels`` (σ′ anneal) the stall watch rides ``state[-1]``
    ACROSS super-block boundaries — the host's own watch below is then
    unnecessary (and skipped): the device loop's counters are the single
    source of truth, and the checkpoints written at block boundaries carry
    them, which is what makes a mid-schedule resume bit-identical."""
    if debug.debug_iter <= 0:
        raise ValueError(
            "the device loop requires debug_iter > 0 (the eval cadence is "
            "its chunk axis)"
        )
    c = debug.debug_iter
    anneal = (sigma_levels is not None and len(sigma_levels) > 1
              and gap_target is not None and divergence_guard)
    traj = Trajectory(name, quiet=quiet)
    watch = _GapWatch(n_evals=stall_window(debug.debug_iter))
    # ^ spans super-block boundaries (see block loop); inert under anneal
    # Device-loop checkpointing (reference anchor CoCoA.scala:59-62: the
    # production path checkpoints): state is host-reachable at every
    # super-block boundary (each drive_on_device return is the block's one
    # host sync), so save there — every chkptIter rounds, rounded UP to the
    # block boundary.  Block sizes are capped below so a boundary occurs at
    # least every ceil(chkptIter / debugIter) chunks.
    ckpt_on = bool(debug.chkpt_dir) and debug.chkpt_iter > 0
    last_saved = start_round - 1
    # --overlapComm on the device-resident path: the checkpoint WRITE —
    # the one host-side exchange this driver performs at super-block
    # boundaries — rides a daemon thread so its serialization + disk IO
    # overlaps the NEXT super-block's dispatch (and the index-table
    # prefetch already running alongside it) instead of extending the
    # boundary.  The state snapshot happens synchronously on THIS thread
    # as an OWNED host copy (a zero-copy view would alias the device
    # buffer the next dispatch donates — the same
    # nothing-shared-crosses-the-thread contract as
    # distributed._require_host_bytes), so the written bytes are
    # bit-identical to a synchronous save; only the write's timing
    # moves.  One write in flight at a time; the final join below makes
    # the function's completion imply every checkpoint landed.  Gated to
    # single-process runs by the callers: ckpt_lib.save's alpha
    # allgather is a collective that must not race a training dispatch.
    pending_io: list = []

    def _join_io():
        while pending_io:
            pending_io.pop().result()

    def maybe_ckpt(done_round):
        nonlocal last_saved
        if ckpt_on and done_round - last_saved >= debug.chkpt_iter:
            args, kwargs = checkpoint_arguments(debug, name, done_round,
                                                state, traj, ckpt_rows)
            if overlap_io:
                _join_io()
                # copy=True is load-bearing: np.asarray of a CPU jax
                # array is a zero-copy VIEW of the device buffer, and
                # the very next dispatch DONATES that buffer — the
                # writer thread must serialize an owned snapshot, not a
                # view of memory the run is about to reuse
                args = tuple(np.array(a, copy=True) if a is not None
                             and not isinstance(a, (str, int)) else a
                             for a in args)
                kwargs = {k2: (np.array(v, copy=True)
                               if k2 in ("sched", "hist")
                               and v is not None else v)
                          for k2, v in kwargs.items()}
                pending_io.append(_Prefetch(
                    lambda a, kw: ckpt_lib.save(*a, **kw), args, kwargs))
            else:
                ckpt_lib.save(*args, **kwargs)
            last_saved = done_round

    def hit_target():
        return (
            gap_target is not None and traj.records
            and traj.records[-1].gap is not None
            and traj.records[-1].gap <= gap_target
        )

    t = start_round
    # head: advance to the absolute debugIter boundary so eval rounds stay
    # anchored to t % debugIter == 0 exactly like the host drivers
    head_end = min(params.num_rounds, ((t - 1) // c + 1) * c)
    if (t - 1) % c != 0 and head_end >= t:
        with _tracing.span("local_solve", algorithm=name, round=head_end,
                           t0=t, rounds=head_end - t + 1):
            state = chunk_fn(t, head_end - t + 1, state)
            _sanitize.count_launch()
        t = head_end + 1
        if head_end % c == 0:
            # the SAME in-state watch the device loop reads
            state, _ = _host_eval(
                traj, name, head_end, state, eval_fn, gap_target, watch.n,
                sigma_levels if anneal else None, accel, quiet)
            if not anneal:
                watch.update(traj.records[-1].gap)
        maybe_ckpt(head_end)

    n_full = max(0, (params.num_rounds - (t - 1)) // c)
    if n_full > 0 and not hit_target():
        # bound the resident index table: one (n_chunks, C, K, H) int32 array
        # per dispatch.  With localIterFrac=1, H = n/K, so a whole-run table
        # is num_rounds × n ints — a memory cliff the chunked driver doesn't
        # have.  Split into super-blocks of at most ~256 MB of indices;
        # the early-stop test between blocks costs one host sync per block.
        chunk_ints = c * sampler.ints_per_round()
        max_block = max(1, MAX_IDX_TABLE_BYTES // (4 * chunk_ints))
        if ckpt_on:
            # a boundary (host sync + save opportunity) at least every
            # chkptIter rounds, rounded up to the chunk cadence
            max_block = min(max_block, max(1, -(-debug.chkpt_iter // c)))
        if gap_target is None or n_full * chunk_ints <= SMALL_TABLE_INTS:
            # no early stop possible (or the whole table is cheap anyway):
            # equal blocks → one executable, one host sync per ~256 MB
            n_blocks = -(-n_full // max_block)
            per_block = -(-n_full // n_blocks)
            g = per_block
        else:
            # a gap-targeted run may stop at a small fraction of num_rounds,
            # and host-side index sampling for rounds never executed is pure
            # waste (the whole-run table can cost seconds at epsilon scale).
            # Grow blocks geometrically in powers of two from a sampling-
            # cost-sized start — bounded distinct shapes, so the handful of
            # while-loop executables is reused across runs, and each block
            # costs one extra host sync (the early-stop check).
            per_block = None
            g = max(1, SMALL_TABLE_INTS // chunk_ints)
        sizes = []
        remaining = n_full
        while remaining > 0:
            b = min(per_block or g, max_block, remaining)
            g = min(g * 2, max_block)
            sizes.append(b)
            remaining -= b

        done = t - 1
        start = done + 1

        def block_tables(t0, nb):
            flat = sampler.chunk_indices(t0, nb * c)
            return jax.tree.map(
                lambda a: a.reshape(nb, c, *a.shape[1:]), flat)

        # Two ways to a block's ``idxs_all``, by what the sampler says of
        # itself.  ``sampler.device``: the kernels draw in-jit and the
        # block's "table" is a spec of its round numbers — NumPy, built
        # here on the driving thread for nothing, shaped (n_blocks, C) and
        # handed to the loop program as an argument (its upload rides the
        # dispatch; on a mesh jit places it).  No thread, no launch, no
        # prefetch: nothing costs.  Host tables (``--sampling=host``,
        # ``--rng=reference`` past int32): one-ahead sampling WITH
        # pre-staged tables — block i+1's are generated on a daemon host
        # thread while the device executes block i, hiding the numpy LCG
        # cost behind device time (at epsilon scale both are ~ms/round);
        # the thread also reshapes them to the (n_chunks, C, ...) chunk
        # layout and commits them to the device, so the table's h2d
        # transfer overlaps the previous block's execution instead of
        # landing on the next dispatch's critical path (see IndexSampler).
        # On early stop the in-flight speculative block is abandoned —
        # bounded waste, overlapped with the final device block either
        # way, and the daemon thread cannot delay interpreter exit.
        staged = not sampler.device

        def stage(t0, nb):
            # on the staging thread: overlaps whatever span the driving
            # thread holds (wait_indices, or the previous block's solve)
            with _tracing.span("stage_indices", t0=t0, rounds=nb * c):
                reshaped = block_tables(t0, nb)
                if mesh is not None:
                    # committing to the default device would conflict with
                    # the mesh-sharded state at dispatch ("incompatible
                    # devices"); on a mesh let jit place the tables as
                    # before
                    return reshaped
                return jax.tree.map(jax.device_put, reshaped)

        # wait_indices: what the driving thread spends on a block's
        # tables — in device mode the NumPy spec (microseconds: the span
        # opens all the same, the readers of a job's fixed cost go by its
        # name); with host tables starting the staging thread, then
        # blocked on its result (with one block a job all of block 0's
        # sampling is paid here).
        fut = None
        for bi, b in enumerate(sizes):
            with _tracing.span("wait_indices", t0=start, rounds=b * c):
                if staged:
                    idxs_all = (fut or _Prefetch(stage, start, b)).result()
                else:
                    idxs_all = block_tables(start, b)
            if staged and bi + 1 < len(sizes):
                fut = _Prefetch(stage, start + b * c, sizes[bi + 1])
            state, dev_traj = drive_on_device(
                name, state, chunk_kernel, eval_kernel, idxs_all,
                shard_arrays, test_arrays, quiet=quiet,
                gap_target=gap_target, start_round=start,
                cache_key=cache_key, mesh=mesh, stall_evals=watch.n,
                divergence_guard=divergence_guard,
                sigma_levels=sigma_levels, accel=accel,
            )
            traj.records.extend(dev_traj.records)
            if dev_traj.records:
                # the block's single host sync just happened — stamp it on
                # the block's final record.  Rounds inside the block keep
                # wall_time=None (genuinely unobservable: one dispatch, one
                # fetch); these block-boundary stamps give the benchmark
                # JSONL its monotone (round, time) pairs without fabricating
                # flat per-round times.
                traj.records[-1].wall_time = traj.elapsed()
            # rounds actually executed: a gap-target run can stop the
            # device while_loop mid-block, after fewer than b chunks —
            # each executed chunk logged exactly one eval record.  Saving
            # the nominal block end would overstate the checkpoint round
            # and a later --resume would skip never-executed rounds.
            done = start - 1 + len(dev_traj.records) * c
            start += b * c
            maybe_ckpt(done)
            # target first: a block can cross the target on a later eval
            # than the one that trips the stall window — reaching the
            # target always wins (the host drivers check in this order too)
            if hit_target():
                traj.stopped = "target"
                break
            # the in-loop watch state is per-block; the host's spans
            # block boundaries (geometric blocks start with < STALL_EVALS
            # evals, where the in-loop watch alone could never fire).
            # Under σ′ anneal the watch rides state[-1] across blocks
            # instead, and a fired window backs off rather than stops —
            # so there is no watch to run and nothing to mark diverged.
            diverged = not anneal and divergence_guard and (
                dev_traj.stopped == "diverged"
                or any(watch.update(r.gap) for r in dev_traj.records)
            )
            if gap_target is not None and diverged:
                traj.mark_diverged(done, watch.n)
                break
        t = done + 1

    rem = params.num_rounds - (t - 1)
    if rem > 0 and not hit_target() and traj.stopped is None:
        # sub-cadence tail: run it, no eval (off the debugIter cadence)
        with _tracing.span("local_solve", algorithm=name,
                           round=params.num_rounds, t0=t, rounds=rem):
            state = chunk_fn(t, rem, state)
            _sanitize.count_launch()
        maybe_ckpt(params.num_rounds)
    # every overlapped checkpoint write must have LANDED before this
    # driver reports done (a caller may read/validate the files next)
    _join_io()
    return state, traj


def align_alpha(alpha_init, ds: ShardedDataset, dtype):
    """(K, n_shard) alpha from a restored ``alpha_init``, zero-padding the
    shard axis when the checkpoint predates a larger padded ``n_shard``
    (rows ≥ counts[k] are never sampled, so zero padding is exact).  A clear
    error beats the opaque XLA shape mismatch it would otherwise hit."""
    import jax.numpy as jnp

    a = jnp.array(alpha_init, dtype=dtype, copy=True)
    if a.ndim != 2 or a.shape[0] != ds.k:
        raise ValueError(
            f"alpha_init shape {a.shape} is incompatible with K={ds.k} shards"
        )
    if a.shape[1] < int(ds.counts.max()) or a.shape[1] > ds.n_shard:
        raise ValueError(
            f"alpha_init has {a.shape[1]} rows per shard but the dataset "
            f"shards to counts={ds.counts.tolist()} (n_shard={ds.n_shard}) — "
            f"was the checkpoint written with different data or numSplits?"
        )
    if a.shape[1] < ds.n_shard:
        a = jnp.pad(a, ((0, 0), (0, ds.n_shard - a.shape[1])))
    return a


def check_shards(ds: ShardedDataset, rectangle: bool = False) -> None:
    """Reject empty shards up front: the reference crashes inside the task
    (``nextInt(0)``) when numSplits > rows; we fail with a clear message.
    ``rectangle``: the caller reads sparse rows as the (K, n_shard, W)
    rectangle (the primal solvers' subgradient passes), so rows kept as a
    stream (data/sharding.stream_suits) are refused, not misread."""
    if rectangle and ds.sp_row_ptr is not None:
        raise ValueError(
            "this solver reads padded-CSR rectangles; the dataset's rows "
            "(a hundred nonzeros or more on average, the longest a few "
            "times the mean) are kept as a stream, which the SDCA family "
            "solves (run_cocoa / run_minibatch_cd): shard them with "
            "shard_dataset(..., rectangle=True) for this one")
    if np.any(ds.counts <= 0):
        raise ValueError(
            f"every shard needs at least one example; shard sizes are "
            f"{ds.counts.tolist()} (n={ds.n} over K={ds.k} shards) — "
            f"lower numSplits"
        )


class IndexSampler:
    """Per-round local-coordinate sampling, in one of three modes.

    - ``reference``: java.util.Random replay — identical draws to the Scala
      code per (seed+t, n_local), correlated across equal-size shards
      exactly as the reference is (CoCoA.scala:45,144).
    - ``jax``: stateless counter-hash draws keyed per (seed, round, shard,
      position) — decorrelated across shards (statistical improvement, not
      reference-faithful).  NOT jax.random: batched-key threefry costs
      ~100 ms per dispatch through this device path (utils/prng.py module
      note); the mode's contract is decorrelation, not a specific stream.
    - ``permuted``: random reshuffling — each shard walks a fresh
      per-epoch permutation of its rows, so every coordinate is touched
      exactly once per n_local draws.  With-replacement sampling leaves
      ~1/e of the duals untouched per epoch-equivalent, and untouched
      duals stall the gap; measured on the epsilon config this reaches
      the 1e-4 duality gap in 20 rounds vs 100 (the decorrelation alone
      accounts for 100→90 — the reshuffle is the win).  A documented
      deviation from the reference's with-replacement draws
      (CoCoA.scala:151); the duality-gap certificate is computed exactly
      from (w, α) and stays valid under ANY index stream, which is what
      makes this safe to flag-gate.

    **Where the tables are generated** (``device`` attr): index draws are
    data-independent, so generation can happen anywhere; what matters is
    that the tables NOT cross the host↔device link — a per-round (K, H)
    table upload is an h2d copy plus a dispatch dependency that costs more
    than a millisecond-scale fused kernel round (the reference itself
    draws inside each partition's task, CoCoA.scala:144).  With ``device=True`` (the production default —
    solvers auto-enable it for the chunked/device-loop paths)
    :meth:`chunk_indices` returns a tiny ``{"t": (C,) int32}`` spec and the
    solver's jitted chunk generates the (C, K, H) tables in-jit via
    :meth:`tables_from_ts` — bit-identical to the host tables for every
    mode (reference replay validated in tests/test_device_sampling.py; jax
    and permuted draw from the same counter-hash / Feistel-bijection
    streams (utils/prng.py) whether expanded on host or in-jit — host ≡
    device because it is literally one integer-arithmetic implementation,
    not because any PRNG library is backend-invariant)."""

    MODES = ("reference", "jax", "permuted")

    def __init__(self, mode: str, seed: int, h: int, counts: np.ndarray,
                 device: bool = False):
        if mode not in self.MODES:
            raise ValueError(f"rng mode must be one of {self.MODES}, got {mode!r}")
        self.mode = mode
        self.seed = seed
        self.h = h
        self.counts = np.asarray(counts)
        self.device = device
        if np.any(self.counts <= 0):
            raise ValueError(
                f"all shards must be non-empty, got sizes {self.counts}")

    def cache_token(self):
        """Hashable identity of the in-jit generation closure (device mode
        bakes the sampling configuration into the executable)."""
        return (self.mode, self.seed, self.h, tuple(self.counts.tolist()),
                self.device)

    def device_capable(self, max_round: int) -> bool:
        """Whether in-jit generation is exact for this run.  Permuted mode
        walks global steps (t-1)·H..; int32 arithmetic bounds both it and
        the host twin (one implementation), so an overflowing config is
        rejected eagerly rather than degraded."""
        from cocoa_tpu.utils.prng import device_replay_ok

        if self.mode == "reference":
            return device_replay_ok(self.seed, max_round)
        if self.mode == "permuted":
            return (max_round + 1) * self.h < (1 << 31)
        return True

    def ints_per_round(self) -> int:
        """Index-table ints crossing the host↔device link per round — what
        the device-loop driver sizes its super-blocks by."""
        k = self.counts.shape[0]
        return 1 if self.device else k * self.h

    def round_indices(self, t: int) -> jax.Array:
        """(K, H) int32 index table for round t (1-based, as the reference).
        Always concrete (host-stepped drivers)."""
        return self._tables(t, 1)[0]

    def chunk_indices(self, t0: int, c: int):
        """Tables for rounds t0..t0+c-1: a concrete (C, K, H) int32 array,
        or — in device mode — the ``{"t": (C,) int32}`` spec the solver
        kernels expand in-jit via :meth:`tables_from_ts`."""
        if self.device:
            # host constants: a jitted consumer uploads them with its
            # dispatch, and no program is launched to make them
            return {"t": np.arange(t0, t0 + c, dtype=np.int32)}
        return self._tables(t0, c)

    def _tables(self, t0: int, c: int) -> jax.Array:
        import jax.numpy as jnp

        if self.mode == "reference":
            # numpy replay (handles the full java long seed range)
            tab = sample_indices_per_shard(
                self.seed, range(t0, t0 + c), self.h, self.counts
            )  # (K, C, H)
            return jnp.asarray(np.swapaxes(tab, 0, 1))
        # jax/permuted: one counter-hash/Feistel implementation for host
        # and device tables, so eager-vs-jit agree bitwise by construction
        return self.tables_from_ts(jnp.arange(t0, t0 + c, dtype=jnp.int32))

    def tables_from_ts(self, ts) -> jax.Array:
        """Traceable: (C,) int32 round numbers -> (C, K, H) int32 tables.
        The in-jit twin of :meth:`_tables`; rounds must be consecutive
        (chunk calls always are — the permuted stream slices on ts[0])."""
        from cocoa_tpu.utils import prng

        with jax.named_scope(_tracing.SCOPE_INDICES):
            if self.mode == "reference":
                return prng.device_sample_per_shard(self.seed, ts, self.h,
                                                    self.counts)
            if self.mode == "permuted":
                return prng.permuted_tables(self.seed, ts, self.h,
                                            self.counts)
            return prng.hash_tables(self.seed, ts, self.h, self.counts)


def resolve_sampling(sampling: str, sampler: "IndexSampler",
                     max_round: int) -> bool:
    """Resolve the ``--sampling`` flag to the sampler's ``device`` switch.

    ``auto`` (default) generates index tables in-jit on the device whenever
    the mode's in-jit arithmetic is exact for this run — the production
    choice: shipping index tables host→device every round costs more than
    the kernels themselves (see IndexSampler).  ``host`` forces concrete host-side tables (the
    validation/debug path); ``device`` asserts in-jit generation is usable.
    """
    if sampling not in ("auto", "device", "host"):
        raise ValueError(
            f"sampling must be auto|device|host, got {sampling!r}")
    capable = sampler.device_capable(max_round)
    if not capable and sampler.mode == "permuted":
        # permuted has ONE implementation (host tables are the same int32
        # jnp stream evaluated eagerly), so an overflowing config has no
        # exact fallback — reject it eagerly rather than silently wrap
        raise ValueError(
            f"rng=permuted overflows int32 global-step arithmetic for "
            f"num_rounds={max_round}, localIters={sampler.h} "
            f"((rounds+1)*H must stay below 2^31); split the run via "
            f"checkpoint/resume or lower localIterFrac"
        )
    if sampling == "host":
        return False
    if sampling == "device" and not capable:
        raise ValueError(
            f"device sampling is not exact for rng={sampler.mode!r} with "
            f"seed={sampler.seed}, num_rounds={max_round} (int32 range); "
            f"use --sampling=host"
        )
    return capable


def drive_device_paths(
    name: str,
    params: Params,
    debug: DebugParams,
    state: tuple,
    chunk_kernel: Callable,   # (state, xs, shard_arrays) -> state, traceable
    chunk_fn: Callable,       # (t0, c, state) -> state, host-stepped (jitted)
    eval_fn: Callable,
    sampler,
    shard_arrays,
    *,
    alpha_in_state: bool,
    mesh=None,
    test_ds=None,
    quiet: bool = False,
    gap_target: Optional[float] = None,
    start_round: int = 1,
    scan_chunk: int = 0,
    device_loop: bool = False,
    cache_key=None,
    eval_kernel=None,
    eval_arrays=None,
    divergence_guard: bool = True,
    sigma_levels: Optional[tuple] = None,
    accel: Optional[tuple] = None,
    overlap_io: bool = False,
    ckpt_rows=None,
):
    """The scan_chunk / device_loop dispatch shared by every solver: builds
    the fused eval kernel (dual state iff ``alpha_in_state``; overridable
    for non-classification objectives) and routes to
    :func:`drive_device_full` or :func:`drive_chunked`.  Returns
    (state, Trajectory).  ``ckpt_rows``: :func:`checkpoint_arguments`.
    ``eval_arrays``: what an ``eval_kernel`` of the caller's own takes as
    its third argument in place of the test set's arrays (the prox
    family's regression target): an argument of the device loop, never a
    constant inside it."""
    from cocoa_tpu.evals import objectives

    if device_loop:
        test_arrays = test_ds.shard_arrays() if test_ds is not None else None
        test_n = test_ds.n if test_ds is not None else 0
        if eval_arrays is not None:
            test_arrays = eval_arrays

        if eval_kernel is None:
            def eval_kernel(state, shard_arrays, test_arrays):
                alpha = state[1] if alpha_in_state else None
                return objectives.eval_metrics(
                    state[0], alpha, shard_arrays, params.lam, params.n,
                    mesh=mesh, test_shard_arrays=test_arrays, test_n=test_n,
                    loss=params.loss, smoothing=params.smoothing,
                )

        return drive_device_full(
            name, params, debug, state, chunk_kernel, eval_kernel, chunk_fn,
            eval_fn, sampler, shard_arrays, test_arrays, quiet=quiet,
            gap_target=gap_target, start_round=start_round,
            cache_key=None if cache_key is None
            else (*cache_key, test_n, divergence_guard),
            mesh=mesh, divergence_guard=divergence_guard,
            sigma_levels=sigma_levels, accel=accel,
            overlap_io=overlap_io, ckpt_rows=ckpt_rows,
        )
    return drive_chunked(
        name, params, debug, state, chunk_fn, eval_fn, quiet=quiet,
        gap_target=gap_target, start_round=start_round, chunk=scan_chunk,
        divergence_guard=divergence_guard, sigma_levels=sigma_levels,
        accel=accel, ckpt_rows=ckpt_rows,
    )


# --- fleet: the vmapped drive* ladder (--fleet, round 18) -------------------
#
# T independent tenants (per-tenant λ / dataset / gap target) run as ONE
# compiled round loop: every solver-state leaf, the sched vector, the
# accel hist bank, and the gap watch grow a leading T axis, the
# per-tenant chunk/eval kernels ride a jax.vmap over that axis, and the
# whole fleet anneals, extrapolates, and certifies inside one
# lax.while_loop — one dispatch, one compile, one fetch for the entire
# fleet.  Certified tenants MASK OUT of the update: the chunk still
# computes their lane (a masked lane, not a dispatch), but a lane-wise
# jnp.where discards its result so a finished tenant's (w, α, hist,
# sched) is bitwise-frozen from the eval that certified it, and the
# loop's stop predicate is the conjunction of per-tenant done flags.
#
# Independence argument: the adding-vs-averaging machinery
# (arXiv:1502.03508) makes every tenant's σ′/γ scaling self-contained —
# no cross-tenant term exists anywhere in the round — and the general
# CoCoA framework (arXiv:1611.02189) is local-solver/objective agnostic,
# so the per-tenant duality-gap certificate is exactly the solo
# certificate evaluated on that lane's (w, α).  A T=1 fleet run is
# bit-identical to the solo path (pinned by tests/test_fleet.py): the
# per-tenant kernels receive λ·n and σ′ as TRACED scalars carrying
# exactly the float32 values the solo path bakes in as constants, and
# IEEE arithmetic does not distinguish the two.
#
# The σ′ anneal ladder lowers from branch selection to data here: the
# solo path statically specializes one chunk kernel per σ′ stage and
# lax.switches between them, but a vmapped switch with a batched index
# executes EVERY branch for EVERY lane — so the fleet kernel instead
# reads σ′ = levels[stage_t] from the (L,) ladder array (same f32
# values, same update arithmetic) and one kernel serves every stage of
# every tenant.  Docs: docs/DESIGN.md §16 "Fleet execution model".

FLEET_N_COLS = 7   # the solo traj row layout, per tenant


def _build_fleet_run(chunk_kernel, eval_kernel, n_state,
                     per_tenant_idxs=False, stall_evals=STALL_EVALS,
                     divergence_guard=True, n_stages=0, accel=False,
                     jump_kernel=None, lane_exec="vmap"):
    """The fleet twin of :func:`_build_device_run`: one jitted
    while_loop advancing every tenant lane per chunk.

    ``chunk_kernel(state_t, idxs_ckh, data_t, scal_t) -> state_t`` and
    ``eval_kernel(state_t, data_t, scal_t) -> (3,)`` are PER-TENANT
    traceables (solvers/fleet.py builds them with traced λ·n/σ′ from the
    ``scal_t`` leaves); the batching over T happens here.

    ``lane_exec`` picks how tenant lanes execute inside the loop:

    - ``"vmap"`` (the throughput default): the hot chunk path batches
      across lanes — on CPU the per-step row ops vectorize across the
      whole fleet.  Batched reductions may round differently from the
      solo executable by ~1 ulp at T > 1 (a batched dot's accumulation
      order is the backend's choice), so per-lane trajectories match
      solo to ulps, bit-exactly at T=1.
    - ``"map"`` — lanes run sequentially via ``lax.map`` inside the SAME
      single compiled while_loop: each lane's body is the solo HLO
      exactly, so every lane is bit-identical to its solo run at ANY T
      (the parity/debug mode; pinned by tests/test_fleet.py).  The
      compile/dispatch amortization — the fleet's headline win — is
      identical in both modes.

    The EVAL (and the accel ``jump_kernel``, when given) always ride
    ``lax.map``: the certificate reduction is the bit-sensitive piece,
    and per-lane evaluation keeps it the solo computation.  The watch
    vectors (done/stall/best/cert) are explicit donated arguments so
    super-block chaining carries them across dispatches without a
    recompile."""
    import functools

    import jax.numpy as jnp
    from jax import lax

    check_div = divergence_guard
    anneal = check_div and n_stages > 1
    idx_axis = 1 if per_tenant_idxs else None

    @functools.partial(jax.jit, donate_argnums=tuple(range(7 + n_state)))
    def run(done_tgt0, done_stall0, stall0, best0, best_prev0, cert0,
            stall_chunk0, *args):
        state0 = args[:n_state]
        idxs_all, shard_arrays, scal, tgts = args[n_state:]
        n_chunks = jax.tree.leaves(idxs_all)[0].shape[0]
        t_fleet = tgts.shape[0]

        from cocoa_tpu.parallel.fanout import lane_fanout

        vchunk = lane_fanout(chunk_kernel, lane_exec=lane_exec,
                             idx_axis=idx_axis)

        def veval(state, data, scal_):
            return lax.map(lambda a: eval_kernel(*a), (state, data, scal_))

        def vjump(state, data, scal_):
            return lax.map(lambda a: jump_kernel(*a), (state, data, scal_))

        def bmask(flag, like):
            return flag.reshape(flag.shape + (1,) * (like.ndim - 1))

        def cond(s):
            i, done_tgt, done_stall = s[0], s[1], s[2]
            return ((i < n_chunks)
                    & jnp.logical_not(jnp.all(done_tgt | done_stall)))

        def body(s):
            (i, done_tgt, done_stall, stall, best, best_prev, cert,
             stall_chunk, state, traj) = s
            done0 = done_tgt | done_stall
            if jump_kernel is not None:
                # the accel secant jump, per lane at the chunk head —
                # the solo chunk kernel's position and arithmetic (an
                # unarmed or done lane's jump is the identity)
                state = vjump(state, shard_arrays, scal)
            chunk = jax.tree.map(lambda a: a[i], idxs_all)
            new_state = vchunk(state, chunk, shard_arrays, scal)
            # finished-tenant masking: a done lane's whole state is
            # bitwise-frozen — the lane still computes, its result is
            # discarded; live lanes see exactly the solo update
            state = tuple(
                jnp.where(bmask(done0, nw), o, nw)
                for o, nw in zip(state, new_state))
            metrics = veval(state, shard_arrays, scal)   # (T, 3)
            gap = metrics[:, 1]
            # the solo body's done_tgt, lane-wise (a frozen lane's gap
            # re-evaluates identically, so done_now stays true for it)
            done_now = (gap <= tgts) | done0
            newly = (gap <= tgts) & jnp.logical_not(done0)
            nans = jnp.full((t_fleet,), jnp.nan, metrics.dtype)
            if check_div and not anneal:
                # per-tenant no-improvement watch; only gap-targeted
                # lanes can stop diverged (the solo guard is tied to a
                # target's existence — lane-wise here)
                gv = jnp.where(jnp.isnan(gap),
                               jnp.asarray(jnp.inf, best.dtype), gap)
                bst, bpv, stl = _watch_update(jnp, gv, best, best_prev,
                                              stall, STALL_REL)
                best = jnp.where(done0, best, bst)
                best_prev = jnp.where(done0, best_prev, bpv)
                stall = jnp.where(done0, stall, stl)
                has_tgt = tgts > -jnp.inf
                newly_stalled = ((stall >= stall_evals) & has_tgt
                                 & jnp.logical_not(done_now)
                                 & jnp.logical_not(done_stall))
                done_stall = done_stall | newly_stalled
                # the eval a lane stalled OUT at (1-based chunk index;
                # 0 = never) — what lets the host decode a per-eval
                # still-training count without re-deriving the watch
                stall_chunk = jnp.where(newly_stalled, i + jnp.int32(1),
                                        stall_chunk)
                extra = jnp.stack([nans, stall.astype(metrics.dtype)],
                                  axis=1)
            elif not anneal:
                extra = jnp.stack([nans, jnp.zeros_like(nans)], axis=1)
            if anneal or accel:
                # the solo loop's schedule update with every field a (T,)
                # column; done_now gates every action exactly as the solo
                # done_tgt does, and a lane already frozen keeps its σ′
                # head bitwise (the watch must not keep counting a lane
                # that stopped updating).  The fleet runs the fixed-Θ
                # ladder (n_theta == 1): the Θ slots ride unchanged.
                sched = state[-1]
                gv = jnp.where(jnp.isnan(gap), jnp.inf,
                               gap).astype(jnp.float32)
                upd = eval_boundary_update(
                    jnp, sched, gv, done_now, stall_evals=stall_evals,
                    n_stages=n_stages if anneal else 0,
                    n_theta=1 if accel else 0, tgt=None)
                stg, stl, thst, rst = upd.cols
                head = sched[:, :SCHED_LEN]
                if anneal:
                    head = jnp.where(done0[:, None], head, upd.head)
                    extra = jnp.stack([stg, stl],
                                      axis=1).astype(metrics.dtype)
                if accel:
                    hist_leaf = jnp.where(
                        upd.push[:, None, None, None],
                        jnp.stack([state[2][:, 1], state[1]], axis=1),
                        state[2])
                    state = (state[0], state[1], hist_leaf,
                             jnp.concatenate([head, upd.tail], axis=1))
                    extra2 = jnp.stack([thst, rst],
                                       axis=1).astype(metrics.dtype)
                else:
                    state = (*state[:-1], head)
            if not accel:
                extra2 = jnp.stack([nans, nans], axis=1)
            done_tgt = done_tgt | newly
            cert = jnp.where(newly, i + jnp.int32(1), cert)
            row = jnp.concatenate([metrics, extra, extra2], axis=1)
            traj = lax.dynamic_update_index_in_dim(traj, row, i, 0)
            return (i + jnp.int32(1), done_tgt, done_stall, stall, best,
                    best_prev, cert, stall_chunk, state, traj)

        traj0 = jnp.full((n_chunks, t_fleet, FLEET_N_COLS), jnp.nan,
                         dtype=state0[0].dtype)
        (i, done_tgt, done_stall, stall, best, best_prev, cert,
         stall_chunk, state, traj) = lax.while_loop(
            cond, body,
            (jnp.int32(0), done_tgt0, done_stall0, stall0, best0,
             best_prev0, cert0, stall_chunk0, state0, traj0))
        return (i, done_tgt, done_stall, stall, best, best_prev, cert,
                stall_chunk, state, traj)

    return run


class FleetCarry(NamedTuple):
    """The per-tenant watch vectors chained across fleet super-block
    dispatches, in the order the loop program takes them (all donated run
    arguments; fresh via :meth:`init`).  ``cert_chunk`` / ``stall_chunk``
    record the 1-based eval a lane certified / stalled out at (0 = never)
    — what the host decodes per-eval active-lane counts and per-tenant
    outcomes from."""
    done_tgt: object
    done_stall: object
    stall: object
    best: object
    best_prev: object
    cert_chunk: object
    stall_chunk: object

    @classmethod
    def init(cls, t: int, dtype):
        import jax.numpy as jnp

        return cls(
            jnp.zeros((t,), bool), jnp.zeros((t,), bool),
            jnp.zeros((t,), jnp.int32),
            jnp.full((t,), jnp.inf, dtype),
            jnp.full((t,), jnp.inf, dtype),
            jnp.zeros((t,), jnp.int32), jnp.zeros((t,), jnp.int32))


def drive_fleet_on_device(
    name: str,
    state: tuple,
    chunk_kernel: Callable,   # per-tenant: (state, idxs_ckh, data, scal)
    eval_kernel: Callable,    # per-tenant: (state, data, scal) -> (3,)
    idxs_all,                 # (n_chunks, C, [T,] K, H) int32 tables
    shard_arrays,             # (T, K, ...) pytree
    scal,                     # (T,) per-tenant scalar pytree (λ·n, ...)
    gap_targets,              # (T,) targets in state dtype, -inf = none
    quiet: bool = False,
    start_round: int = 1,
    cache_key=None,
    stall_evals: int = STALL_EVALS,
    divergence_guard: bool = True,
    n_stages: int = 0,
    accel: bool = False,
    per_tenant_idxs: bool = False,
    carry: Optional["FleetCarry"] = None,
    jump_kernel: Optional[Callable] = None,
    lane_exec: str = "vmap",
):
    """Dispatch one fleet super-block: every chunk, every per-tenant
    eval, the per-tenant anneal/accel schedules, the per-tenant gap
    watch, and the all-lanes-done stop test ride ONE ``lax.while_loop``
    in one jit — one dispatch and one host fetch for the whole fleet.

    Returns ``(state, carry, n_done, traj_host)``: ``carry`` holds the
    per-tenant done/watch/cert vectors (chainable into the next block —
    the executable is cached per ``cache_key``, so a multi-block fleet
    still compiles exactly once), ``traj_host`` is the fetched
    ``(n_done, T, FLEET_N_COLS)`` eval buffer in the solo row layout."""
    t_fleet = int(gap_targets.shape[0])
    if carry is None:
        carry = FleetCarry.init(t_fleet, state[0].dtype)
    n_state = len(state)
    run_key = None if cache_key is None else ("fleet", cache_key)
    run = _DEVICE_RUNS.get(run_key) if run_key is not None else None
    if run is None:
        run = _build_fleet_run(
            chunk_kernel, eval_kernel, n_state,
            per_tenant_idxs=per_tenant_idxs, stall_evals=stall_evals,
            divergence_guard=divergence_guard, n_stages=n_stages,
            accel=accel, jump_kernel=jump_kernel, lane_exec=lane_exec)
        if run_key is not None:
            _DEVICE_RUNS[run_key] = run
    n_chunks = int(jax.tree.leaves(idxs_all)[0].shape[0])
    c = int(jax.tree.leaves(idxs_all)[0].shape[1])
    with _tracing.span("local_solve", algorithm=name, t0=start_round,
                       round=start_round - 1 + n_chunks * c,
                       rounds=n_chunks * c, cadence=c, tenants=t_fleet), \
            _sanitize.device_loop_guard():
        with _tracing.span("dispatch"):
            i, *watches, state, traj_buf = run(
                *carry, *state, idxs_all, shard_arrays, scal, gap_targets)
        # the single host sync of the whole fleet block: the solo loop's
        # read (the watch vectors stay on the device for the next block)
        _, traj_host = fetch_loop_result(i, traj_buf, "fleet_loop_fetch")
        n_done = len(traj_host)
    return state, FleetCarry(*watches), n_done, traj_host


class TsSampler:
    """Sampler adapter whose chunk tables also carry the round number.

    η(t)-scheduled solvers (SGD: η = 1/(λt), SGD.scala:44; DistGD:
    η = 1/(βt), DistGD.scala:35) need t inside the device-side scan.  The
    table becomes a dict pytree: ``{"idxs": (C, K, H), "t": (C,)}`` — the
    (C,) leaf is treated as a replicated per-round scalar by
    ``chunk_fanout`` and by the pytree-aware device-loop drivers.

    ``sampler=None`` (DistGD — deterministic full passes, no index draws)
    emits only the ``t`` leaf; ``h``/``counts`` then size the index-table
    memory cap as zero-ish (h=1).
    """

    def __init__(self, sampler: "IndexSampler | None", dtype, counts=None):
        self.sampler = sampler
        self.dtype = dtype
        self.h = sampler.h if sampler is not None else 1
        self.counts = sampler.counts if sampler is not None else np.asarray(counts)

    @property
    def device(self) -> bool:
        return self.sampler is not None and self.sampler.device

    def cache_token(self):
        return None if self.sampler is None else self.sampler.cache_token()

    def ints_per_round(self) -> int:
        return 1 if self.sampler is None else self.sampler.ints_per_round()

    def chunk_indices(self, t0: int, c: int):
        import jax.numpy as jnp

        out = {"t": jnp.arange(t0, t0 + c, dtype=self.dtype)}
        if self.sampler is not None:
            if self.sampler.device:
                # exact int32 round numbers for in-jit generation — the
                # float ``t`` leaf rides the compute dtype for the η(t)
                # schedules and cannot carry them (bf16 collapses integers
                # past 256)
                out["ti"] = np.arange(t0, t0 + c, dtype=np.int32)
            else:
                out["idxs"] = self.sampler.chunk_indices(t0, c)
        return out

    def materialize(self, xs):
        """Traceable: fill the ``idxs`` leaf from the int32 ``ti`` leaf
        when the inner sampler generates on device (the chunk tables are
        otherwise passed through untouched; the extra (C,) ``ti`` leaf
        scans as an inert per-round scalar)."""
        if self.sampler is None or "idxs" in xs:
            return xs
        return {**xs, "idxs": self.sampler.tables_from_ts(xs["ti"])}
