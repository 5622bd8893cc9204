"""The device-resident σ′ anneal schedule (--sigmaSchedule=anneal).

The sigma=auto trial-and-rerun (--sigmaSchedule=trial, the A/B control)
pays for a wrong aggressive guess twice: a guarded ~stall-window trial
PLUS a full restart.  The anneal schedule carries σ′ in the drive*
ladder's loop state instead: when the stall watch fires, σ′ backs off
multiplicatively toward the safe K·γ IN PLACE — same dispatch, same
while_loop, current iterate kept.  Soundness: the primal-dual
correspondence w = (1/λn)·Σ y·α·x and the α ∈ [0,1]^n box are maintained
by the update rule under ANY σ′, so the exact duality-gap certificate
survives the switch (docs/DESIGN.md §3e).

These tests pin, on shards built to NEED the full σ′ = K (every shard
holds identical rows — the adversarial coherence the K·γ bound protects
against):

- the in-loop backoff fires and the run still certifies, with no restart;
- host-chunked and device-loop drivers produce identical trajectories;
- a run that never backs off is BIT-IDENTICAL to the fixed-σ′ run;
- a mid-schedule checkpoint resume is BIT-IDENTICAL to uninterrupted;
- --sigmaSchedule=trial is preserved bit-exact as the A/B control;
- the --warmStart scanned handoff equals the manual two-run handoff.
"""

import dataclasses
import os

import numpy as np
import pytest

import jax.numpy as jnp

from cocoa_tpu import checkpoint as ckpt_lib
from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import shard_dataset
from cocoa_tpu.data.synth import synth_sparse
from cocoa_tpu.solvers import base, run_cocoa
from test_divergence import _coherent_dataset

K, LAM = 4, 1e-4


def _anneal_run(device_loop, sigma=1.0, num_rounds=1600, tmp=None,
                chkpt_iter=0, quiet=True, **kw):
    """Divergence-prone config: σ′ start 1.0 = K·γ/4 on adversarially
    coherent shards (≤ 3.5·γ·K/8 = 1.75 — the forced-divergence regime the
    acceptance criteria name), cadence 25 so the stall window is the
    calibration 12 evals."""
    ds, n = _coherent_dataset(k=K)
    params = Params(n=n, num_rounds=num_rounds, local_iters=16, lam=LAM,
                    sigma=sigma)
    debug = DebugParams(debug_iter=25, seed=0,
                        chkpt_iter=chkpt_iter or num_rounds + 1,
                        chkpt_dir=str(tmp) if tmp else "")
    return run_cocoa(ds, params, debug, plus=True, quiet=quiet, math="fast",
                     device_loop=device_loop, gap_target=1e-3, rng="jax",
                     sigma_schedule="anneal", **kw)


def _sigma_transitions(traj):
    sig = [(r.round, r.sigma) for r in traj.records if r.sigma is not None]
    return [sig[0]] + [b for a, b in zip(sig, sig[1:]) if b[1] != a[1]]


def test_anneal_levels_ladder():
    assert base.anneal_levels(4.0, 8.0) == (4.0, 8.0)
    assert base.anneal_levels(3.5, 8.0) == (3.5, 7.0, 8.0)
    assert base.anneal_levels(1.0, 4.0) == (1.0, 2.0, 4.0)
    # start at/above safe: the schedule is inert (one rung)
    assert base.anneal_levels(8.0, 8.0) == (8.0,)
    assert base.anneal_levels(9.0, 8.0) == (8.0,)
    # an absurdly low start is capped: the last step jumps to safe
    lv = base.anneal_levels(1e-6, 8.0)
    assert len(lv) <= base.MAX_SIGMA_LEVELS and lv[-1] == 8.0
    assert all(a < b for a, b in zip(lv, lv[1:]))


def _sigma_step(s, gap, stall_evals, n_stages):
    upd = base.eval_boundary_update(
        np, s, np.float32(gap), False, stall_evals=stall_evals,
        n_stages=n_stages, n_theta=0, tgt=None)
    return upd.head, bool(upd.backed)


def test_eval_boundary_update_is_gapwatch():
    """Same windowed no-improvement semantics as base._GapWatch, plus the
    backoff action (stage += 1, fresh watch) instead of a bail-out.  (NaN /
    None gaps reach it as +inf, as in the in-loop code, a policy only
    primal-only evals ever see; the anneal paths always have a real gap.)"""
    s = base.sched_init_values(1)
    seq = [1.0, 0.9, 0.7, 5.0, 0.6, 0.55]
    fires = []
    for g in seq:
        s, backed = _sigma_step(s, g, stall_evals=3, n_stages=2)
        fires.append(backed)
    # the _GapWatch fixture from test_divergence: reset at 0.7, then three
    # straight non-improving evals fire the window
    assert fires == [False] * 5 + [True]
    assert s[0] == 1.0 and s[1] == 0.0 and np.isinf(s[2]) and np.isinf(s[3])
    # at the last stage the watch is inert: it never "fires" again
    for g in (0.55, 0.55, 0.55, 0.55, 0.55):
        s, backed = _sigma_step(s, g, stall_evals=3, n_stages=2)
        assert not backed
    assert s[0] == 1.0


def test_anneal_backs_off_in_loop_and_certifies_host():
    """σ′ = K/4 on coherent shards diverges; the schedule must back off
    within one stall window of the watch firing — in place, no restart —
    and still certify the gap target inside the round budget."""
    w, alpha, traj = _anneal_run(device_loop=False)
    assert traj.stopped == "target"
    assert traj.records[-1].gap <= 1e-3
    trans = _sigma_transitions(traj)
    assert len(trans) >= 2, "the schedule never backed off"
    sigmas = [s for _, s in trans]
    assert sigmas[0] == 1.0                      # aggressive start
    assert all(a < b for a, b in zip(sigmas, sigmas[1:]))  # monotone backoff
    assert sigmas[-1] <= K * 1.0                 # never past the safe bound
    # the first backoff cannot beat the stall window (12 evals × 25 rounds)
    assert trans[1][0] >= 12 * 25
    # and the whole run (backoff included) beats the budget by a wide margin
    assert traj.records[-1].round < 1600


def test_anneal_device_loop_identical_to_host():
    """The while_loop-resident controller and the host-chunked twin make
    identical decisions and produce identical states (same f32 watch
    arithmetic, same branch kernels)."""
    w_h, a_h, t_h = _anneal_run(device_loop=False)
    w_d, a_d, t_d = _anneal_run(device_loop=True)
    np.testing.assert_array_equal(np.asarray(w_h), np.asarray(w_d))
    np.testing.assert_array_equal(np.asarray(a_h), np.asarray(a_d))
    assert _sigma_transitions(t_h) == _sigma_transitions(t_d)
    assert t_d.stopped == "target"
    assert [r.round for r in t_h.records] == [r.round for r in t_d.records]


def test_anneal_no_backoff_is_bitexact_vs_fixed_sigma():
    """Benign data at σ′ = K/2: the watch never fires, and the scheduled
    run must be bit-identical to the plain fixed-σ′ run with the same
    chunking — the stage-0 branch IS the fixed kernel."""
    data = synth_sparse(512, 128, nnz_mean=12, seed=3)
    ds = shard_dataset(data, k=4, layout="dense", dtype=jnp.float32)
    debug = DebugParams(debug_iter=10, seed=0)
    params = Params(n=data.n, num_rounds=100, local_iters=16, lam=1e-2,
                    sigma=2.0)
    kw = dict(plus=True, quiet=True, math="fast", gap_target=1e-6,
              rng="permuted")
    w_a, a_a, t_a = run_cocoa(ds, params, debug, sigma_schedule="anneal",
                              **kw)
    w_f, a_f, t_f = run_cocoa(ds, params, debug, scan_chunk=1, **kw)
    np.testing.assert_array_equal(np.asarray(w_a), np.asarray(w_f))
    np.testing.assert_array_equal(np.asarray(a_a), np.asarray(a_f))
    assert all(r.sigma == 2.0 for r in t_a.records)


def test_anneal_checkpoint_resume_mid_schedule_bit_identical(tmp_path,
                                                             monkeypatch):
    """Resume from a checkpoint taken MID-WINDOW at stage 0 (stall counters
    accumulated, no backoff yet): the restored schedule state must
    reproduce the uninterrupted run bit-for-bit — the backoff fires at the
    same round and the final state is identical."""
    # this test resumes from a SPECIFIC mid-run generation (r400, chosen
    # for its mid-window stage-0 sched state); keep every generation so
    # the default keep-2 pruning cannot rotate it away
    monkeypatch.setattr(ckpt_lib, "KEEP_GENERATIONS", 1000)
    w0, a0, t0 = _anneal_run(device_loop=True, tmp=tmp_path, chkpt_iter=100)
    assert t0.stopped == "target"
    path = os.path.join(str(tmp_path), "CoCoA+-r000400.npz")
    meta, wc, ac = ckpt_lib.load(path)
    sched = meta.get("sched")
    assert sched is not None and len(sched) == base.SCHED_LEN
    assert sched[0] == 0.0 and sched[1] > 0, \
        "the test premise needs a mid-window stage-0 checkpoint"
    assert sched[4] == meta["round"] + 1
    w_r, a_r, t_r = _anneal_run(
        device_loop=True, w_init=wc, alpha_init=ac,
        start_round=meta["round"] + 1,
        sched_init=np.asarray(sched, np.float32))
    assert t_r.stopped == "target"
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w_r))
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a_r))


def test_anneal_resume_without_sched_falls_back_to_safe(capsys):
    """A resumed run with no schedule state (pre-schedule checkpoint /
    bare w_init) cannot know its stage — it continues at the safe σ′,
    exactly like the trial path's resumed-run rule."""
    rng = np.random.default_rng(0)
    w0 = jnp.asarray(rng.normal(size=16) * 0.01, jnp.float32)
    w, a, traj = _anneal_run(device_loop=False, sigma="auto",
                             num_rounds=200, w_init=w0, start_round=5,
                             quiet=False)
    out = capsys.readouterr().out
    assert "continuing with the safe" in out


def test_sigma_auto_defaults_to_anneal_and_starts_aggressive():
    """--sigma=auto now rides the anneal schedule by default: the run
    starts at K·γ/2 with no trial/rerun machinery (on benign data it
    simply certifies at the aggressive σ′)."""
    ds, n = _coherent_dataset(k=K)
    params = Params(n=n, num_rounds=400, local_iters=16, lam=LAM,
                    sigma="auto")
    debug = DebugParams(debug_iter=4, seed=0)
    w, alpha, traj = run_cocoa(ds, params, debug, plus=True, quiet=True,
                               math="fast", gap_target=1e-3, rng="jax")
    assert traj.stopped == "target"
    assert traj.records[-1].sigma == K / 2.0


def test_trial_schedule_preserved_bit_exact():
    """--sigmaSchedule=trial is the A/B control: sigma=auto under it runs
    the aggressive trial exactly as the pre-schedule code did — on data
    where the trial certifies, bit-identical to the fixed σ′=K·γ/2 run."""
    ds, n = _coherent_dataset(k=K)
    debug = DebugParams(debug_iter=4, seed=0)
    p_auto = Params(n=n, num_rounds=400, local_iters=16, lam=LAM,
                    sigma="auto")
    p_half = Params(n=n, num_rounds=400, local_iters=16, lam=LAM,
                    sigma=K / 2.0)
    kw = dict(plus=True, quiet=True, math="fast", gap_target=1e-3,
              rng="jax")
    w_t, a_t, t_t = run_cocoa(ds, p_auto, debug, sigma_schedule="trial",
                              **kw)
    w_f, a_f, t_f = run_cocoa(ds, p_half, debug, **kw)
    assert t_t.stopped == "target"
    np.testing.assert_array_equal(np.asarray(w_t), np.asarray(w_f))
    np.testing.assert_array_equal(np.asarray(a_t), np.asarray(a_f))


def test_anneal_validations():
    ds, n = _coherent_dataset(k=K)
    params = Params(n=n, num_rounds=10, local_iters=4, lam=LAM,
                    sigma="auto")
    debug = DebugParams(debug_iter=2, seed=0)
    # anneal (the default) requires the gap-target path
    with pytest.raises(ValueError, match="gapTarget"):
        run_cocoa(ds, params, debug, plus=True, quiet=True)
    # ... and the guard (its firing IS the backoff trigger)
    with pytest.raises(ValueError, match="divergenceGuard"):
        run_cocoa(ds, params, debug, plus=True, quiet=True,
                  gap_target=1e-3, divergence_guard="off")
    # trial is only meaningful as the sigma=auto control
    with pytest.raises(ValueError, match="trial"):
        run_cocoa(ds, dataclasses.replace(params, sigma=2.0), debug,
                  plus=True, quiet=True, sigma_schedule="trial")
    with pytest.raises(ValueError, match="trial|anneal"):
        run_cocoa(ds, params, debug, plus=True, quiet=True,
                  sigma_schedule="nope")


def test_anneal_explicit_sigma_start():
    """--sigma=<float> --sigmaSchedule=anneal anneals from that start —
    the ladder's first rung is the explicit σ′, the last is safe K·γ."""
    w, alpha, traj = _anneal_run(device_loop=False, sigma=1.0,
                                 num_rounds=1600)
    sigmas = sorted({r.sigma for r in traj.records if r.sigma is not None})
    assert sigmas[0] == 1.0
    assert all(s in (1.0, 2.0, 4.0) for s in sigmas)


# --- the --warmStart scanned handoff ---------------------------------------


def _warm_ds():
    data = synth_sparse(512, 128, nnz_mean=12, seed=3)
    return shard_dataset(data, k=4, layout="dense", dtype=jnp.float32), data.n


@pytest.mark.slow
def test_warm_start_equals_manual_handoff():
    """The in-loop smooth_hinge→hinge handoff must equal the manual
    two-run procedure bit-for-bit:
    warm run to round W, then a hinge run resumed from its state."""
    ds, n = _warm_ds()
    debug = DebugParams(debug_iter=10, seed=0)
    p_hinge = Params(n=n, num_rounds=100, local_iters=16, lam=1e-2)
    kw = dict(plus=True, quiet=True, math="fast", rng="permuted")
    w_w, a_w, t_w = run_cocoa(ds, p_hinge, debug, warm_start=(0.5, 30),
                              **kw)
    p_warm = dataclasses.replace(p_hinge, num_rounds=30,
                                 loss="smooth_hinge", smoothing=0.5)
    w_1, a_1, _ = run_cocoa(ds, p_warm, debug, scan_chunk=1, **kw)
    w_2, a_2, _ = run_cocoa(ds, p_hinge, debug, scan_chunk=1, w_init=w_1,
                            alpha_init=a_1, start_round=31, **kw)
    np.testing.assert_array_equal(np.asarray(w_w), np.asarray(w_2))
    np.testing.assert_array_equal(np.asarray(a_w), np.asarray(a_2))
    # the device loop runs the same scanned handoff
    w_d, a_d, _ = run_cocoa(ds, p_hinge, debug, warm_start=(0.5, 30),
                            device_loop=True, **kw)
    np.testing.assert_array_equal(np.asarray(w_d), np.asarray(w_w))


def test_warm_start_rounds_up_to_cadence(capsys):
    ds, n = _warm_ds()
    debug = DebugParams(debug_iter=10, seed=0)
    p = Params(n=n, num_rounds=50, local_iters=16, lam=1e-2)
    w_a, a_a, _ = run_cocoa(ds, p, debug, warm_start=(0.5, 23), plus=True,
                            math="fast", rng="permuted", quiet=False)
    assert "rounded up to round 30" in capsys.readouterr().out
    w_b, a_b, _ = run_cocoa(ds, p, debug, warm_start=(0.5, 30), plus=True,
                            math="fast", rng="permuted", quiet=True)
    np.testing.assert_array_equal(np.asarray(w_a), np.asarray(w_b))


def test_warm_start_validations():
    ds, n = _warm_ds()
    debug = DebugParams(debug_iter=10, seed=0)
    p = Params(n=n, num_rounds=50, local_iters=16, lam=1e-2,
               loss="logistic")
    with pytest.raises(ValueError, match="hinge"):
        run_cocoa(ds, p, debug, plus=True, quiet=True,
                  warm_start=(0.5, 30))
    p2 = Params(n=n, num_rounds=50, local_iters=16, lam=1e-2)
    with pytest.raises(ValueError, match="smoothing"):
        run_cocoa(ds, p2, debug, plus=True, quiet=True,
                  warm_start=(0.0, 30))
    with pytest.raises(ValueError, match="rounds"):
        run_cocoa(ds, p2, debug, plus=True, quiet=True,
                  warm_start=(0.5, 0))
    with pytest.raises(ValueError, match="debugIter"):
        run_cocoa(ds, p2, DebugParams(debug_iter=0, seed=0), plus=True,
                  quiet=True, warm_start=(0.5, 30))


def test_warm_start_combines_with_anneal():
    """warm phase + σ′ schedule share one device loop: the branch table is
    the (stage × phase) product and both selectors ride the sched leaf."""
    ds, n = _warm_ds()
    debug = DebugParams(debug_iter=10, seed=0)
    p = Params(n=n, num_rounds=100, local_iters=16, lam=1e-2, sigma="auto")
    w, alpha, traj = run_cocoa(ds, p, debug, plus=True, quiet=True,
                               math="fast", rng="permuted",
                               gap_target=1e-6, warm_start=(0.5, 30),
                               device_loop=True)
    assert traj.records[-1].sigma is not None


@pytest.mark.slow
def test_rcv1_synth_anneal_certifies_at_575_rounds_no_restart():
    """The acceptance pin: the rcv1-synth production config (H=253,
    permuted, γ=1, λ=1e-4) under --sigma=auto --sigmaSchedule=anneal
    certifies the 1e-4 gap in ≤ 575 rounds — the measured σ′=K/2 sweet
    spot — with zero backoffs and zero restarts."""
    n, d, k = 20242, 47236, 8
    data = synth_sparse(n, d, nnz_mean=75, seed=0)
    ds = shard_dataset(data, k=k, layout="sparse", dtype=jnp.float32,
                       eval_dense=True)
    h = n // k // 10          # 253
    params = Params(n=n, num_rounds=1600, local_iters=h, lam=1e-4,
                    sigma="auto")
    debug = DebugParams(debug_iter=25, seed=0)
    w, alpha, traj = run_cocoa(ds, params, debug, plus=True, quiet=True,
                               math="fast", device_loop=True,
                               gap_target=1e-4, rng="permuted")
    assert traj.stopped == "target"
    assert traj.records[-1].round <= 575
    assert traj.records[-1].gap <= 1e-4
    # zero-detour: the aggressive start held — no backoff ever fired
    assert all(r.sigma == k / 2.0 for r in traj.records)
