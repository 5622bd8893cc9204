"""The accelerated outer loop (--accel / --theta, round 12).

Secant (Anderson-1) extrapolation of the DUAL at eval-window boundaries:
the drivers bank the two previous eval-boundary α snapshots in a
(2, K, n_shard) ``hist`` state leaf; once two consecutive improving
windows are banked, the next chunk opens with the jump α ← α + c·(α−h2)
— c = ρ/(1−ρ) signed and data-derived from the window displacements'
autocorrelation (base.secant_coef) — clipped back into the dual box,
with w advanced by the EXACT correspondence update Σ y·Δα·x/(λn)
(ops/rows.shards_axpy).  The certified pair (w, α) therefore stays a
feasible primal-dual pair and the unmodified duality-gap evaluation
stays the certificate; a gap rise at an eval boundary RESTARTS the bank.
``--theta=adaptive`` adds the Θ local-accuracy ladder: per-round
inner-step counts resolved on device from the current gap estimate
through the same statically-specialized ``lax.switch`` machinery as the
σ′ anneal stages.

What these tests pin:

- ``--accel=off`` is BIT-IDENTICAL to the pre-acceleration code across
  all three drive modes (per-round, host-chunked, device loop);
- the host-chunked and device-loop accelerated drivers make identical
  decisions and produce identical states (both run
  base.eval_boundary_update, NumPy on the host, traced on the device);
- a mid-momentum checkpoint resume (hist leaf + extended sched slots) is
  bit-identical to the uninterrupted run;
- the typed ``momentum_restart`` / ``theta_stage`` events flow through
  the bus identically on the host and device paths, and the sched-leaf
  accel machinery (bank/arm/jump rule, Θ ladder, restart action)
  is pinned slot for slot, and at np, at jnp and at a (T,) batch alike;
- the flag surface validations.
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
from cocoa_tpu.telemetry import events as tele_events


@pytest.fixture(autouse=True)
def clean_bus():
    tele_events.get_bus().reset()
    yield tele_events.get_bus()
    tele_events.get_bus().reset()


def _ds(n=512, d=128, k=4, seed=3):
    data = synth_sparse(n, d, nnz_mean=12, seed=seed)
    return shard_dataset(data, k=k, layout="dense", dtype=jnp.float32), data.n


def _run(ds, n, accel=None, theta=None, num_rounds=100, lam=1e-2,
         gap_target=1e-6, debug_iter=10, **kw):
    params = Params(n=n, num_rounds=num_rounds, local_iters=16, lam=lam)
    debug = DebugParams(debug_iter=debug_iter, seed=0,
                        chkpt_iter=kw.pop("chkpt_iter", num_rounds + 1),
                        chkpt_dir=kw.pop("chkpt_dir", ""))
    return run_cocoa(ds, params, debug, plus=True, quiet=True, math="fast",
                     rng="permuted", gap_target=gap_target, accel=accel,
                     theta=theta, **kw)


# --- unit: the schedule arithmetic ------------------------------------------


def _boundary(s, gap, n_theta, tgt, n_stages=0, stall_evals=3, done=False,
              xp=np):
    """One eval boundary through base.eval_boundary_update: (new sched
    leaf, the update)."""
    s = xp.asarray(s, xp.float32)
    upd = base.eval_boundary_update(
        xp, s, xp.asarray(gap, xp.float32), xp.asarray(done),
        stall_evals=stall_evals, n_stages=n_stages, n_theta=n_theta, tgt=tgt)
    head = s[..., :base.SCHED_LEN] if upd.head is None else upd.head
    return xp.concatenate(
        [head, *(() if upd.tail is None else (upd.tail,))], axis=-1), upd


def test_theta_ladder():
    assert base.theta_ladder(253, False) == (253,)
    # the ladder starts at H/2 — an H/4 rung was measured to COST rounds
    # (the early fast-decay rounds are productive; solvers/base.py note)
    assert base.theta_ladder(253, True) == (126, 253)
    assert base.theta_ladder(16, True) == (8, 16)
    # tiny H collapses duplicate rungs, the full H always last
    assert base.theta_ladder(2, True) == (1, 2)
    assert base.theta_ladder(1, True) == (1,)


def test_sched_init_values_accel_shapes():
    s = base.sched_init_values(7, accel=True)
    assert s.shape == (base.SCHED_LEN + base.ACCEL_LEN,)
    assert s[4] == 7.0
    assert s[base.A_HIST] == 0.0 and s[base.A_JUMP] == 0.0
    assert np.isinf(s[base.A_LASTGAP]) and s[base.A_RESTARTS] == 0.0
    # a plain (5,) restore under accel gains fresh accel slots
    plain = base.sched_init_values(3)
    ext = base.sched_init_values(3, sched_init=plain,
                                 accel=True)
    np.testing.assert_array_equal(ext[:base.SCHED_LEN], plain)
    assert ext.shape == (base.SCHED_LEN + base.ACCEL_LEN,)
    # an accel-length restore WITHOUT accel keeps its σ′ head
    back = base.sched_init_values(3, sched_init=ext)
    np.testing.assert_array_equal(back, plain)
    with pytest.raises(ValueError, match="shape"):
        base.sched_init_values(1, sched_init=np.zeros(9, np.float32))


def test_eval_boundary_bank_arm_restart():
    """The window bookkeeping: improving evals BANK α snapshots; two
    banked windows ARM the jump for the next chunk head (and freeze the
    bank); a gap RISE discards the bank (restarts += 1, the bank
    restarts from this eval's α).  All exact f32 arithmetic."""
    s = base.sched_init_values(1, accel=True)
    # first eval: last_gap is inf — bank one window
    s, upd = _boundary(s, 1.0, 1, None)
    assert not upd.restarted and not upd.staged and s[base.A_HIST] == 1.0
    assert s[base.A_JUMP] == 0.0 and upd.push
    assert s[base.A_LASTGAP] == np.float32(1.0)
    # second improving eval: two windows banked
    s, upd = _boundary(s, 0.5, 1, None)
    assert not upd.restarted and s[base.A_HIST] == 2.0
    assert s[base.A_JUMP] == 0.0
    # third improving eval: the jump ARMS and the bank is consumed
    s, upd = _boundary(s, 0.25, 1, None)
    assert not upd.restarted and not upd.push
    assert s[base.A_JUMP] == 1.0 and s[base.A_HIST] == 0.0
    # the chunk head clears the armed flag when it takes the jump
    s[base.A_JUMP] = 0.0
    # a RISE restarts: bank discarded, restarted from this eval's α
    s, upd = _boundary(s, 0.6, 1, None)
    assert upd.restarted and upd.push and s[base.A_HIST] == 1.0
    assert s[base.A_JUMP] == 0.0 and s[base.A_RESTARTS] == 1.0


def test_secant_coef():
    """The jump coefficient: c = ρ/(1−min(ρ, cap)) clipped to
    [ACCEL_CMIN, ACCEL_CMAX] — averaging on oscillation, capped
    extrapolation on drift."""
    # pure oscillation ρ = −1 → pairwise averaging c = −0.5 exactly
    assert base.secant_coef(np, np.float32(-1.0)) == np.float32(-0.5)
    # no correlation → no jump
    assert base.secant_coef(np, np.float32(0.0)) == np.float32(0.0)
    # measured rcv1-synth drift ρ ≈ 0.73 → c ≈ 2.7, inside the cap
    c = base.secant_coef(np, np.float32(0.73))
    assert np.isclose(float(c), 0.73 / 0.27, rtol=1e-5)
    # ρ → 1 pole is capped then clipped to CMAX
    assert base.secant_coef(np, np.float32(0.999)) == \
        np.float32(base.ACCEL_CMAX)
    # strong anti-correlation clips at CMIN
    assert base.secant_coef(np, np.float32(-5.0)) == \
        np.float32(base.ACCEL_CMIN)


def test_eval_boundary_theta_ladder_advance():
    """Θ advances on the halve-per-eval stall watch, jumps to the final
    stage near the target, and is inert at the last rung."""
    tgt = 1e-4
    s = base.sched_init_values(1, accel=True)
    # fast-decay phase: gap halves every eval — the loose stage holds
    s, upd = _boundary(s, 8.0, 3, tgt)
    assert not upd.staged and s[base.A_TH_STAGE] == 0.0
    s, upd = _boundary(s, 3.0, 3, tgt)
    assert not upd.staged
    # decay slows below 2x/eval -> one miss fires the watch
    s, upd = _boundary(s, 2.0, 3, tgt)
    assert upd.staged and s[base.A_TH_STAGE] == 1.0
    assert s[base.A_TH_STALL] == 0.0 and np.isinf(s[base.A_TH_BEST])
    # near the target: jump straight to the final stage
    s, upd = _boundary(s, 9e-4, 3, tgt)
    assert upd.staged and s[base.A_TH_STAGE] == 2.0
    # final rung: the ladder is inert
    s, upd = _boundary(s, 8.9e-4, 3, tgt)
    assert not upd.staged and s[base.A_TH_STAGE] == 2.0


# One fixed sequence of gaps through the ONE function, crossing everything
# it decides: an arm (eval 2), a restart with a Θ stage by a missed halving
# (4), a second restart (5), a σ′ back-off — three evals without the watch's
# best improving — that is also a seam under a bank of two (6), Θ's
# near-target jump (7), a target hit (8: counted, nothing acted on).
_GAPS = [1.0, 0.4, 0.19, 0.09, 0.3, 0.35, 0.3, 9e-4, 5e-5]
_KW = dict(n_theta=4, tgt=1e-4, n_stages=3, stall_evals=3)


def _walk(xp, gaps, **kw):
    s = xp.asarray(base.sched_init_values(1, accel=True))
    if np.ndim(gaps[0]):
        s = xp.stack([s] * len(gaps[0]))
    out, flags = [], []
    for g in gaps:
        s, upd = _boundary(s, g, done=np.asarray(g) <= kw["tgt"], xp=xp,
                           **kw)
        out.append(np.asarray(s))
        flags.append(tuple(np.asarray(f).tolist() for f in (
            upd.push, upd.backed, upd.restarted, upd.staged)))
        # what the chunk head does to an armed jump
        s = xp.asarray(np.where(
            np.arange(s.shape[-1]) == base.A_JUMP, 0.0, np.asarray(s)),
            xp.float32)
    return out, flags


@pytest.mark.parametrize("how", ["np", "jnp", "jit", "batch"])
def test_eval_boundary_update_one_arithmetic(how):
    """NumPy on the host, jnp eager, jnp under jit (the device loop's) and
    a (T,) batch (the fleet's) against T scalar calls: equal float32 fields
    after every eval of the sequence — the property the host twins' tests
    only sampled — and the sequence does cross every decision."""
    want, flags = _walk(np, _GAPS, **_KW)
    # (push, backed, restarted, staged) per eval
    assert flags == [
        (True, False, False, False), (True, False, False, False),
        (False, False, False, False),        # two windows banked: armed
        (True, False, False, False),
        (True, False, True, True),           # rose: restart; Θ: no halving
        (True, False, True, False),
        (True, True, False, True),           # σ′ backs off (and Θ steps)
        (True, False, False, True),          # near the target: Θ to full H
        (False, False, False, False)]        # hit: counted, nothing acted on
    assert want[2][base.A_JUMP] == 1.0 and want[2][base.A_HIST] == 0.0
    assert want[5][base.A_RESTARTS] == 2.0
    # the seam: a bank of one plus this eval's α, capped back to one
    assert want[5][base.A_HIST] == want[6][base.A_HIST] == 1.0
    assert want[6][0] == 1.0 and want[6][1] == 0.0 and np.isinf(want[6][2])
    assert [w[base.A_TH_STAGE] for w in want[3:8]] == [0, 1, 1, 2, 3]
    np.testing.assert_array_equal(want[8][base.A_HIST:base.A_TH_STAGE + 1],
                                  want[7][base.A_HIST:base.A_TH_STAGE + 1])
    assert all(w.dtype == np.float32 for w in want)
    if how == "np":
        return
    if how == "batch":
        # lane t runs the sequence from its t-th eval on (the tail padded
        # with the last gap): a batch of lanes in different states
        lanes = [_GAPS[t:] + [_GAPS[-1]] * t for t in range(4)]
        got, _ = _walk(jnp, [np.float32(g) for g in zip(*lanes)], **_KW)
        solo = [_walk(np, lane, **_KW)[0] for lane in lanes]
        for e, rows in enumerate(got):
            np.testing.assert_array_equal(
                rows, np.stack([solo[t][e] for t in range(4)]))
        return
    if how == "jit":
        import jax

        step = jax.jit(lambda s, g, d: _boundary(s, g, done=d, xp=jnp,
                                                 **_KW)[0])
        s = base.sched_init_values(1, accel=True)
        for e, g in enumerate(_GAPS):
            s = np.array(step(s, np.float32(g), g <= _KW["tgt"]))
            np.testing.assert_array_equal(s, want[e])
            s[base.A_JUMP] = 0.0
        return
    got, got_flags = _walk(jnp, _GAPS, **_KW)
    assert got_flags == flags
    for a, b in zip(got, want):
        assert a.dtype == np.float32
        np.testing.assert_array_equal(a, b)


# --- accel=off is the pre-acceleration code, bit for bit --------------------


@pytest.mark.parametrize("mode", ["per_round", "chunked", "device_loop"])
def test_accel_off_bit_identical_all_modes(mode):
    ds, n = _ds()
    # the per-round driver pays a per-round dispatch+eval cost (~0.5 s/
    # round on the CI box) — 30 rounds cross three eval boundaries, which
    # is all the two-arm bit-identity needs; the cheap drivers keep the
    # full 100 rounds of schedule evolution
    kw = dict(num_rounds=30)
    if mode == "chunked":
        kw = dict(scan_chunk=1)
    elif mode == "device_loop":
        kw = dict(device_loop=True)
    w_o, a_o, t_o = _run(ds, n, accel="off", **kw)
    w_p, a_p, t_p = _run(ds, n, **kw)
    np.testing.assert_array_equal(np.asarray(w_o), np.asarray(w_p))
    np.testing.assert_array_equal(np.asarray(a_o), np.asarray(a_p))
    assert [r.round for r in t_o.records] == [r.round for r in t_p.records]


@pytest.mark.slow
def test_accel_auto_resolution():
    """auto = on for gap-targeted CoCoA+ runs, off without a target (the
    fixed-round benchmark paths stay bit-comparable)."""
    ds, n = _ds()
    # targetless runs take the slow per-round driver — 30 rounds suffice
    # for the two-arm identity (see test_accel_off_bit_identical_all_modes)
    w_a, _, _ = _run(ds, n, accel="auto", gap_target=None, num_rounds=30)
    w_p, _, _ = _run(ds, n, gap_target=None, num_rounds=30)
    np.testing.assert_array_equal(np.asarray(w_a), np.asarray(w_p))
    # with a target, auto accelerates: the trajectory departs from plain
    w_on, _, _ = _run(ds, n, accel="on", num_rounds=60)
    w_au, _, _ = _run(ds, n, accel="auto", num_rounds=60)
    np.testing.assert_array_equal(np.asarray(w_on), np.asarray(w_au))


# --- host/device parity ------------------------------------------------------


@pytest.mark.parametrize("theta", ["fixed", "adaptive"])
def test_accel_device_loop_identical_to_host(theta):
    ds, n = _ds()
    w_h, a_h, t_h = _run(ds, n, accel="on", theta=theta)
    w_d, a_d, t_d = _run(ds, n, accel="on", theta=theta, device_loop=True)
    np.testing.assert_array_equal(np.asarray(w_h), np.asarray(w_d))
    np.testing.assert_array_equal(np.asarray(a_h), np.asarray(a_d))
    assert [r.round for r in t_h.records] == [r.round for r in t_d.records]


# --- checkpoint / resume -----------------------------------------------------


def test_accel_checkpoint_carries_hist_and_extended_sched(tmp_path):
    ds, n = _ds()
    _run(ds, n, accel="on", theta="adaptive", chkpt_dir=str(tmp_path),
         chkpt_iter=50, device_loop=True)
    path = ckpt_lib.latest(str(tmp_path), "CoCoA+")
    assert path is not None
    meta, arrays = ckpt_lib.load_full(path)
    assert "hist" in arrays
    assert arrays["hist"].shape == (2,) + arrays["alpha"].shape
    assert len(meta["sched"]) == base.SCHED_LEN + base.ACCEL_LEN


@pytest.mark.parametrize("device_loop", [False, True],
                         ids=["chunked", "deviceloop"])
def test_accel_resume_mid_momentum_bit_identical(tmp_path, device_loop):
    """Resume from a mid-run checkpoint (momentum β and Θ watch slots
    mid-flight): the restored run must reproduce the uninterrupted one
    bit for bit."""
    ds, n = _ds()
    ck = str(tmp_path)
    w0, a0, t0 = _run(ds, n, accel="on", theta="adaptive", chkpt_dir=ck,
                      chkpt_iter=50, device_loop=device_loop)
    path = os.path.join(ck, "CoCoA+-r000050.npz")
    meta, arrays = ckpt_lib.load_full(path)
    sched = np.asarray(meta["sched"], np.float32)
    assert sched.shape == (base.SCHED_LEN + base.ACCEL_LEN,)
    w_r, a_r, t_r = _run(
        ds, n, accel="on", theta="adaptive", device_loop=device_loop,
        w_init=arrays["w"], alpha_init=arrays["alpha"],
        hist_init=arrays["hist"], sched_init=sched,
        start_round=meta["round"] + 1)
    np.testing.assert_array_equal(np.asarray(w0), np.asarray(w_r))
    np.testing.assert_array_equal(np.asarray(a0), np.asarray(a_r))


def test_accel_off_resumes_accel_checkpoint(tmp_path):
    """An accel checkpoint restored into an --accel=off run keeps the σ′
    head of the sched vector and simply drops the momentum state (any
    (w, α) is a valid primal-dual pair)."""
    ds, n = _ds()
    ck = str(tmp_path)
    _run(ds, n, accel="on", chkpt_dir=ck, chkpt_iter=50)
    path = os.path.join(ck, "CoCoA+-r000050.npz")
    meta, arrays = ckpt_lib.load_full(path)
    w_r, a_r, t_r = _run(
        ds, n, accel="off", w_init=arrays["w"],
        alpha_init=arrays["alpha"], start_round=meta["round"] + 1)
    assert t_r.records, "resumed run must keep evaluating"


# --- telemetry ---------------------------------------------------------------


def _collect():
    events = []
    tele_events.get_bus().subscribe(events.append)
    return events


def _accel_event_run(device_loop):
    """A run engineered to restart at least once: λ small enough that the
    gap trajectory is non-monotone under extrapolation."""
    ds, n = _ds(n=1024, d=256, k=4, seed=0)
    return _run(ds, n, accel="on", theta="adaptive", lam=1e-4,
                num_rounds=200, debug_iter=5, gap_target=1e-5,
                device_loop=device_loop)


def test_accel_events_host_vs_device_identical():
    """momentum_restart / theta_stage events: same count, same rounds,
    same payloads on the host-chunked and device-loop paths (the
    DeviceTap decode vs the host twin's flags)."""
    def strip(events):
        return [
            {k: v for k, v in e.items() if k not in ("seq", "ts", "pid")}
            for e in events
            if e["event"] in ("momentum_restart", "theta_stage")]

    ev_h = _collect()
    _accel_event_run(device_loop=False)
    host = strip(ev_h)
    tele_events.get_bus().reset()
    ev_d = _collect()
    _accel_event_run(device_loop=True)
    dev = strip(ev_d)
    assert host == dev
    assert any(e["event"] == "theta_stage" for e in host), \
        "the fixture must exercise at least one Θ step"


def test_accel_events_schema_and_metrics(tmp_path):
    from cocoa_tpu.telemetry import schema as tele_schema
    from cocoa_tpu.telemetry.metrics import MetricsWriter

    jsonl = str(tmp_path / "events.jsonl")
    metrics_path = str(tmp_path / "metrics.prom")
    bus = tele_events.get_bus()
    bus.configure(jsonl_path=jsonl, metrics_path=metrics_path)
    _, _, traj = _accel_event_run(device_loop=True)
    assert tele_schema.check_file(jsonl) == []
    text = open(metrics_path).read()
    assert "cocoa_momentum_restarts_total" in text
    import re
    n_restarts = int(re.search(
        r"cocoa_momentum_restarts_total (\d+)", text).group(1))
    with open(jsonl) as f:
        restart_events = [ln for ln in f
                          if '"momentum_restart"' in ln]
    assert n_restarts == len(restart_events)
    if any('"theta_stage"' in ln for ln in open(jsonl)):
        assert "cocoa_theta_stage" in text


def test_accel_telemetry_on_off_bit_identical(tmp_path):
    """The tap/stream machinery is side-effect-only: an accel run with
    every sink active produces bit-identical (w, α) to a silent one."""
    w_s, a_s, _ = _accel_event_run(device_loop=True)
    bus = tele_events.get_bus()
    bus.configure(jsonl_path=str(tmp_path / "e.jsonl"),
                  metrics_path=str(tmp_path / "m.prom"))
    w_t, a_t, _ = _accel_event_run(device_loop=True)
    np.testing.assert_array_equal(np.asarray(w_s), np.asarray(w_t))
    np.testing.assert_array_equal(np.asarray(a_s), np.asarray(a_t))


# --- validations -------------------------------------------------------------


def test_accel_validations():
    ds, n = _ds()
    params = Params(n=n, num_rounds=20, local_iters=8, lam=1e-2)
    debug = DebugParams(debug_iter=5, seed=0)
    with pytest.raises(ValueError, match="auto|on|off"):
        run_cocoa(ds, params, debug, plus=True, quiet=True, accel="fast")
    with pytest.raises(ValueError, match="fixed|adaptive"):
        run_cocoa(ds, params, debug, plus=True, quiet=True, accel="on",
                  theta="warp", gap_target=1e-6)
    # theta=adaptive needs an accelerated run
    with pytest.raises(ValueError, match="accel"):
        run_cocoa(ds, params, debug, plus=True, quiet=True,
                  theta="adaptive", gap_target=1e-6)
    # the trial control stays untouched
    p_auto = dataclasses.replace(params, sigma="auto")
    with pytest.raises(ValueError, match="trial"):
        run_cocoa(ds, p_auto, debug, plus=True, quiet=True, accel="on",
                  sigma_schedule="trial", gap_target=1e-6)
    # momentum restarts ride the eval cadence
    with pytest.raises(ValueError, match="debugIter"):
        run_cocoa(ds, params, DebugParams(debug_iter=0, seed=0),
                  plus=True, quiet=True, accel="on", gap_target=1e-6)


def test_accel_combines_with_sigma_anneal():
    """accel + σ′ anneal share one device loop: the branch table is the
    (σ′ stage × Θ stage) product and both selectors ride the sched
    leaf."""
    ds, n = _ds()
    params = Params(n=n, num_rounds=100, local_iters=16, lam=1e-2,
                    sigma="auto")
    debug = DebugParams(debug_iter=10, seed=0)
    w, alpha, traj = run_cocoa(ds, params, debug, plus=True, quiet=True,
                               math="fast", rng="permuted",
                               gap_target=1e-6, accel="on",
                               theta="adaptive", device_loop=True)
    assert traj.records[-1].sigma is not None


def test_accel_with_hot_cols_hybrid_layout():
    """--accel on a hybrid (--hotCols) sparse layout: the secant jump's
    transpose-apply must scatter the hot-panel contribution as a summed
    (n_hot,) update (regression: a per-shard (K, n_hot) einsum raised a
    broadcast error at trace time, so accel+hotCols could never run)."""
    data = synth_sparse(512, 128, nnz_mean=12, seed=3)
    ds = shard_dataset(data, k=4, layout="sparse", hot_cols=16)
    w, alpha, traj = run_cocoa(
        ds, Params(n=data.n, num_rounds=60, local_iters=16, lam=1e-2),
        DebugParams(debug_iter=10, seed=0), plus=True, quiet=True,
        math="fast", rng="permuted", gap_target=1e-6, accel="on",
        device_loop=True)
    assert np.isfinite(np.asarray(w)).all()
    gaps = [r.gap for r in traj.records if r.gap is not None]
    assert gaps and np.isfinite(gaps[-1]) and gaps[-1] < gaps[0]


def _folded_arrays(d, mesh=None):
    """Dense rows, their coefficients and the fold cache beside them, as
    the dense Pallas path hands them to ``shards_axpy``."""
    import jax

    from cocoa_tpu.ops.pallas_sdca import fold_rows

    k, n_shard = 4, 24
    rng = np.random.default_rng(d)
    X = jnp.asarray(rng.normal(size=(k, n_shard, d)), jnp.float32)
    coefs = jnp.asarray(rng.normal(size=(k, n_shard)), jnp.float32)
    if mesh is not None:
        from cocoa_tpu.parallel.mesh import sharded_rows

        X = jax.device_put(X, sharded_rows(mesh, extra_dims=2))
        coefs = jax.device_put(coefs, sharded_rows(mesh, extra_dims=1))
    return coefs, {"X": X}, {"X": X, "X_folded": fold_rows(X)}


@pytest.mark.parametrize("case", ["hybrid", "folded_d64", "folded_d2001",
                                  "folded_mesh"])
def test_shards_axpy_matches_dense(case):
    """shards_axpy == the dense einsum on the same rows: on the hybrid
    split (the hot/cold split permutes per-coordinate sums only), and
    against the dense Pallas path's folded rows — d a multiple of 8, a d
    that fold_rows pads (the padding lanes are cut), and shard by shard
    under the mesh ``fanout`` as the secant jump calls it."""
    import jax

    from cocoa_tpu.ops import rows as _rows

    if case == "hybrid":
        data = synth_sparse(256, 64, nnz_mean=10, seed=7)
        dense = shard_dataset(data, k=4, layout="dense")
        hyb = shard_dataset(data, k=4, layout="sparse", hot_cols=8)
        coefs = jnp.asarray(
            np.random.default_rng(0).normal(size=(4, dense.n_shard)),
            jnp.float32)
        vec = jnp.zeros((data.num_features,), jnp.float32)
        out_d = _rows.shards_axpy(coefs, dense.shard_arrays(), vec)
        out = _rows.shards_axpy(coefs, hyb.shard_arrays(), vec)
    elif case == "folded_mesh":
        from cocoa_tpu.parallel import make_mesh
        from cocoa_tpu.parallel.mesh import primal_sharding

        mesh = make_mesh(2)         # two shards a device, as at x4
        coefs, plain, folded = _folded_arrays(64, mesh)
        vec = jax.device_put(jnp.ones((64,), jnp.float32),
                             primal_sharding(mesh))

        def shard_axpy(w_, coefs_k, shard_k):
            return (_rows.shards_axpy(
                coefs_k[None], jax.tree.map(lambda a: a[None], shard_k),
                jnp.zeros_like(w_)),)

        jump = jax.jit(lambda arrs: vec + base.fanout(
            shard_axpy, mesh, vec, coefs, arrs)[0])
        out_d, out = jump(plain), jump(folded)
    else:
        d = int(case.rsplit("_d", 1)[1])
        coefs, plain, folded = _folded_arrays(d)
        assert folded["X_folded"].shape[-1] * 8 == -(-d // 8) * 8
        vec = jnp.ones((d,), jnp.float32)
        out_d = _rows.shards_axpy(coefs, plain, vec)
        out = _rows.shards_axpy(coefs, folded, vec)
    assert out.shape == out_d.shape
    np.testing.assert_allclose(np.asarray(out_d), np.asarray(out),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("d", [2048, 2040, 2001, 2000])
def test_fold_rows_row_major_equals_plain_reshape(d):
    """The fold stored for the device loop is the plain reshape of the
    rows zero-padded to whole lane tiles, element for element — the shape
    whose row-major layout pads nothing, so the device keeps it; which
    widths are stored so is a rule on d."""
    from cocoa_tpu.ops import pallas_sdca

    X = jnp.asarray(np.random.default_rng(d).normal(size=(2, 16, d)),
                    jnp.float32)
    plain = pallas_sdca.fold_rows(X)
    stored = pallas_sdca.fold_rows(X, row_major=True)
    assert plain.shape == (2, 16, 8, -(-d // 8))
    assert stored.shape == (2, 16, 8, 256) and stored.sharding == X.sharding
    padded = np.zeros((2, 16, 2048), np.float32)
    padded[:, :, :d] = np.asarray(X)
    np.testing.assert_array_equal(np.asarray(stored),
                                  padded.reshape(2, 16, 8, 256))
    np.testing.assert_array_equal(
        np.asarray(plain).reshape(2, 16, -1)[:, :, :d], np.asarray(X))
    # 0 / 0.39% / 2.3% / 2.4% of lane padding against the 1% it may cost
    assert pallas_sdca.stores_row_major(d) == (d in (2048, 2040))
    assert pallas_sdca.stores_row_major(160000)     # imagenet: 0.48%


@pytest.mark.parametrize("mesh_devices", [0, 2])
def test_accel_device_loop_same_with_and_without_fold_cache(
        mesh_devices, monkeypatch):
    """An --accel run through the device loop on the dense Pallas path
    (interpreted here): the jump contracts against the fold cache; with
    the cache withheld from it, the same rounds and a certified pair."""
    from cocoa_tpu.data.synth import synth_dense_sharded
    from cocoa_tpu.ops import rows as _rows
    from cocoa_tpu.parallel import make_mesh

    mesh = make_mesh(mesh_devices) if mesh_devices else None
    # d = 1016: narrow enough to run interpreted, and stored lane-padded
    # to 1024 (0.79%), so kernel and jump both meet lanes past w's length
    ds = synth_dense_sharded(256, 1016, 4, seed=1, mesh=mesh)

    def run(accel="on"):
        base._DEVICE_RUNS.clear()
        params = Params(n=ds.n, num_rounds=200, local_iters=16, lam=1e-2)
        w, a, traj = run_cocoa(
            ds, params, DebugParams(debug_iter=5, seed=0), plus=True,
            quiet=True, math="fast", rng="permuted", gap_target=1e-5,
            accel=accel, device_loop=True, pallas=True, mesh=mesh)
        return np.asarray(w), np.asarray(a), traj

    w_f, a_f, t_f = run()
    assert t_f.meta["solver_path"]["rows"] == "row_major"
    assert ds._x_folded_cache.shape == (4, ds.n_shard, 8, 128)
    axpy = _rows.shards_axpy
    monkeypatch.setattr(
        _rows, "shards_axpy", lambda coefs, shards, vec: axpy(
            coefs, {k: v for k, v in shards.items() if k != "X_folded"},
            vec))
    w_x, a_x, t_x = run()
    base._DEVICE_RUNS.clear()
    assert [r.round for r in t_f.records] == [r.round for r in t_x.records]
    assert t_f.records[-1].gap <= 1e-5 and t_x.records[-1].gap <= 1e-5
    # and the jump fired: without it the iterate is another
    assert not np.array_equal(run(accel="off")[0], w_f)
    np.testing.assert_allclose(w_f, w_x, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(a_f, a_x, atol=1e-4)
    assert a_f.min() >= 0.0 and a_f.max() <= 1.0
    # the certified pair: w is its own alpha's correspondence image
    X = np.asarray(ds.X, np.float64)
    y = np.asarray(ds.labels, np.float64)
    w_ref = np.einsum("kn,knd->d", y * a_f, X) / (1e-2 * ds.n)
    np.testing.assert_allclose(w_f, w_ref[:w_f.shape[0]], atol=1e-5)


def test_theta_adaptive_degrades_when_accel_auto_resolves_off():
    """theta=adaptive rides accel=auto: on a run where auto resolves OFF
    (plain CoCoA — the CLI's run_all second leg), Θ degrades to the full-H
    schedule instead of raising mid-run; explicit accel=off still
    rejects the contradiction."""
    ds, n = _ds()
    params = Params(n=n, num_rounds=20, local_iters=8, lam=1e-2)
    debug = DebugParams(debug_iter=5, seed=0)
    w, alpha, traj = run_cocoa(ds, params, debug, plus=False, quiet=True,
                               gap_target=1e-6, accel="auto",
                               theta="adaptive")
    assert np.isfinite(np.asarray(w)).all()
    with pytest.raises(ValueError, match="accel"):
        run_cocoa(ds, params, debug, plus=True, quiet=True,
                  gap_target=1e-6, accel="off", theta="adaptive")


def test_eval_boundary_sigma_seam_caps_bank():
    """A σ′ anneal backoff at the same eval boundary is a round-map seam
    exactly like a Θ stage advance: the secant bank caps at the α just
    banked, and an already-armed jump stays armed."""
    sched = base.sched_init_values(1, accel=True)
    sched[base.A_LASTGAP] = np.float32(1.0)
    sched[base.A_HIST] = np.float32(1.0)
    # the σ′ watch one eval short of its window, its best under this gap
    sched[1:4] = (2.0, 0.1, 0.1)
    # improving eval + seam: would bank to 2, capped back to 1
    s, upd = _boundary(sched, 0.5, 1, 1e-6, n_stages=2)
    assert upd.backed and s[0] == 1.0
    assert not upd.restarted and s[base.A_HIST] == np.float32(1.0)
    assert s[base.A_JUMP] == np.float32(0.0)
    # armed jump survives the seam (hist already 0 after arming)
    sched[base.A_HIST] = np.float32(2.0)
    sched[base.A_LASTGAP] = np.float32(1.0)
    s, upd = _boundary(sched, 0.5, 1, 1e-6, n_stages=2)
    assert upd.backed
    assert s[base.A_JUMP] == np.float32(1.0)
    assert s[base.A_HIST] == np.float32(0.0)
