"""Round-6 satellite fixes (ADVICE.md round 5).

- data/sharding.py: multi-process + multiplexed dp mesh (D != K) must be
  an explicit error, not a silent fall-through to the replicated builder.
  (Round 13 lifts this for divisible K — the distributed builder stacks
  m = K/D shards per device; only a NON-divisor K stays a loud error.)
- solvers/base.py: the divergence guard is a resolvable flag
  (--divergenceGuard=auto|on|off; auto arms only below the safe K·γ σ′).
- solvers/base.py drive_on_device: a stall-guard fire on the FINAL chunk
  must still classify ``traj.stopped`` (the old n_done<n_chunks inference
  missed it).
- solvers/cocoa.py sigma=auto cleanup: only THIS run's checkpoint files
  (exact algorithm prefix, trial round range) are deleted after a
  diverged trial.
- cli.py: inferred meshes that leave devices idle print a note.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.libsvm import LibsvmData
from cocoa_tpu.data.sharding import shard_dataset
from cocoa_tpu.parallel import make_mesh
from cocoa_tpu.solvers import base, run_cocoa


def _dense_data(n=48, d=16, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d))
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    y = np.where(X @ rng.standard_normal(d) >= 0, 1.0, -1.0)
    indptr = np.arange(0, (n + 1) * d, d, dtype=np.int64)
    return LibsvmData(labels=y, indptr=indptr,
                      indices=np.tile(np.arange(d, dtype=np.int32), n),
                      values=X.reshape(-1), num_features=d)


# --- data/sharding.py: multi-process multiplexed-mesh guard ---------------


def test_multiprocess_multiplexed_mesh_accepted(monkeypatch):
    """Round 13 lifts the round-6 rejection: a multi-process multiplexed
    dp mesh (K divisible by D) routes through the distributed builder —
    with every device addressable it must reproduce the replicated
    control bit-for-bit; a non-divisor K stays a loud error."""
    data = _dense_data()
    mesh = make_mesh(2)
    ctrl = shard_dataset(data, k=4, layout="dense", dtype=jnp.float32,
                         mesh=mesh)
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    with pytest.raises(ValueError, match="divisible by the dp mesh"):
        shard_dataset(data, k=3, layout="dense", dtype=jnp.float32,
                      mesh=mesh)
    ds = shard_dataset(data, k=4, layout="dense", dtype=jnp.float32,
                       mesh=mesh)
    assert ds.k == 4
    for field, want in ctrl.shard_arrays().items():
        np.testing.assert_array_equal(np.asarray(ds.shard_arrays()[field]),
                                      np.asarray(want), err_msg=field)


def test_singleprocess_multiplexed_mesh_still_works():
    data = _dense_data()
    mesh = make_mesh(2)
    ds = shard_dataset(data, k=4, layout="dense", dtype=jnp.float32,
                       mesh=mesh)
    assert ds.k == 4  # 2 logical shards multiplex per device, as before


# --- solvers/base.py: the divergence guard flag ---------------------------


def test_resolve_divergence_guard():
    r = base.resolve_divergence_guard
    assert r("on", "cocoa", 4.0, 4, 1.0) is True
    assert r("off", "plus", 1.0, 4, 1.0) is False
    # auto: armed only for σ′ overridden below the safe K·γ bound, and
    # only for modes whose subproblem reads σ′
    assert r("auto", "plus", 2.0, 4, 1.0) is True      # σ′ < K·γ
    assert r("auto", "plus", 4.0, 4, 1.0) is False     # the safe default
    assert r("auto", "cocoa", 1.0, 4, 1.0) is False    # σ unused
    assert r("auto", "frozen", 1.0, 4, 1.0) is False
    assert r("auto", "prox", 1.0, 4, 1.0) is True
    with pytest.raises(ValueError, match="auto|on|off"):
        r("maybe", "plus", 1.0, 4, 1.0)


def test_drive_guard_off_runs_full_budget(monkeypatch):
    """A stalling gap-targeted run completes its round budget when the
    guard is disarmed (and bails out when armed) — host driver."""
    monkeypatch.setattr(base, "STALL_EVALS", 3)
    monkeypatch.setattr(base, "STALL_ROUNDS", 3)
    params = Params(n=8, num_rounds=20, local_iters=1)
    debug = DebugParams(debug_iter=1, seed=0)

    def run(guard):
        state = (jnp.zeros(4),)
        traj = base.drive_chunked(
            "t", params, debug, state, lambda t, c, s: s,
            lambda s: (1.0, 1.0, None),   # constant gap: pure stall
            quiet=True, gap_target=1e-6, divergence_guard=guard, chunk=1,
        )[1]
        return traj

    armed = run(True)
    assert armed.stopped == "diverged"
    assert armed.records[-1].round < 20
    off = run(False)
    assert off.stopped is None
    assert off.records[-1].round == 20


def test_safe_sigma_auto_guard_unarmed(monkeypatch):
    """End-to-end: with --divergenceGuard=auto (default) a SAFE-σ′ run is
    never labeled DIVERGED even when its gap stalls — the ADVICE r5
    mislabel; forcing --divergenceGuard=on restores the old behavior."""
    monkeypatch.setattr(base, "STALL_EVALS", 3)
    monkeypatch.setattr(base, "STALL_ROUNDS", 3)
    data = _dense_data(n=32, d=8, seed=1)
    ds = shard_dataset(data, k=4, layout="dense", dtype=jnp.float64)
    # H=1: one coordinate step per shard per round — the gap improves a
    # sliver per eval, far under 25% per 3-eval window (slow, NOT diverging)
    params = Params(n=data.n, num_rounds=12, local_iters=1, lam=0.01)
    debug = DebugParams(debug_iter=1, seed=0)
    kw = dict(plus=True, quiet=True, gap_target=1e-12, rng="jax")
    _, _, traj = run_cocoa(ds, params, debug, **kw)   # σ′ = K·γ (safe)
    assert traj.stopped != "diverged"
    assert traj.records[-1].round == 12
    _, _, traj_on = run_cocoa(ds, params, debug, divergence_guard="on",
                              **kw)
    assert traj_on.stopped == "diverged"


def test_sigma_auto_rejects_guard_off():
    data = _dense_data()
    ds = shard_dataset(data, k=4, layout="dense", dtype=jnp.float64)
    params = Params(n=data.n, num_rounds=4, local_iters=2, sigma="auto")
    with pytest.raises(ValueError, match="divergence guard"):
        run_cocoa(ds, params, DebugParams(debug_iter=2, seed=0), plus=True,
                  quiet=True, gap_target=1e-3, divergence_guard="off")


# --- solvers/base.py: drive_on_device final-chunk classification ----------


def _device_run(gaps, gap_target, stall_evals, divergence_guard=True):
    """Drive a toy device loop through `gaps` (one eval per chunk)."""
    gaps = jnp.asarray(gaps, jnp.float32)

    def chunk_kernel(state, chunk, shard_arrays):
        (i,) = state
        return (i + 1.0,)

    def eval_kernel(state, shard_arrays, test_arrays):
        (i,) = state
        g = gaps[jnp.int32(i) - 1]
        return jnp.stack([g, g, jnp.nan])

    idxs_all = jnp.zeros((len(gaps), 1, 1, 1), jnp.int32)
    state, traj = base.drive_on_device(
        "toy", (jnp.zeros((), jnp.float32),), chunk_kernel, eval_kernel,
        idxs_all, shard_arrays=jnp.zeros(()), quiet=True,
        gap_target=gap_target, stall_evals=stall_evals,
        divergence_guard=divergence_guard,
    )
    return traj


def test_device_loop_stall_on_final_chunk_classified():
    """The stall window trips exactly on the LAST chunk: the old
    0 < n_done < n_chunks inference saw a 'completed' run; the device-side
    flags classify it DIVERGED (ADVICE r5)."""
    traj = _device_run([1.0, 1.0, 1.0], gap_target=1e-6, stall_evals=2)
    assert len(traj.records) == 3
    assert traj.stopped == "diverged"


def test_device_loop_target_on_final_chunk_classified():
    traj = _device_run([1.0, 1.0, 1e-7], gap_target=1e-6, stall_evals=2)
    assert traj.stopped == "target"


def test_device_loop_guard_off_completes():
    traj = _device_run([1.0, 1.0, 1.0, 1.0], gap_target=1e-6,
                       stall_evals=2, divergence_guard=False)
    assert traj.stopped is None
    assert len(traj.records) == 4


def test_device_loop_full_budget_unclassified():
    """A run that simply exhausts its chunks (converging, target not yet
    reached) stays stopped=None exactly as before."""
    traj = _device_run([1.0, 0.5, 0.25], gap_target=1e-6, stall_evals=12)
    assert traj.stopped is None
    assert len(traj.records) == 3


# --- solvers/cocoa.py: sigma=auto checkpoint cleanup scoping --------------


def test_sigma_auto_cleanup_scoped_to_trial(tmp_path, monkeypatch, capsys):
    """After a diverged trial, only the TRIAL's checkpoints (exact
    'CoCoA+-r' prefix, rounds ≤ the diverged round) are removed — a
    concurrent plain-CoCoA run's files and higher-round CoCoA+ files in
    the same directory survive (ADVICE r5: the bare 'CoCoA' prefix
    deleted them all).  Pinned on the --sigmaSchedule=trial A/B control —
    the in-loop anneal default never restarts, so it has no checkpoints
    to clean up (tests/test_sigma_anneal.py)."""
    from cocoa_tpu.solvers import cocoa as cocoa_mod
    from cocoa_tpu.utils.logging import RoundRecord, Trajectory

    data = _dense_data()
    ds = shard_dataset(data, k=4, layout="dense", dtype=jnp.float64)
    trial_sigma = 4 / 2.0
    real = cocoa_mod.run_sdca_family

    def spy(ds_, params_, debug_, name_, alg, **kw):
        if alg[2] == trial_sigma:
            # the trial "wrote" checkpoints up to its diverged round; a
            # concurrent run's files appear in the same window
            (tmp_path / "CoCoA+-r000392.npz").write_bytes(b"x")
            (tmp_path / "CoCoA+-r000392.npz.json").write_text("{}")
            (tmp_path / "CoCoA-r000100.npz").write_bytes(b"x")     # CoCoA run
            (tmp_path / "CoCoA+-r000999.npz").write_bytes(b"x")    # later run
            t = Trajectory(name_, quiet=True)
            t.records.append(RoundRecord(round=392, wall_time=None, gap=5.0))
            t.stopped = "diverged"
            return None, None, t
        return real(ds_, params_, debug_, name_, alg, **kw)

    monkeypatch.setattr(cocoa_mod, "run_sdca_family", spy)
    params = Params(n=data.n, num_rounds=6, local_iters=2, lam=0.01,
                    sigma="auto")
    debug = DebugParams(debug_iter=2, seed=0, chkpt_iter=100,
                        chkpt_dir=str(tmp_path))
    run_cocoa(ds, params, debug, plus=True, quiet=False, math="fast",
              gap_target=1e-3, rng="jax", sigma_schedule="trial")
    names = sorted(p.name for p in tmp_path.iterdir())
    assert "CoCoA+-r000392.npz" not in names          # trial ckpt deleted
    assert "CoCoA+-r000392.npz.json" not in names     # and its sidecar
    assert "CoCoA-r000100.npz" in names               # concurrent CoCoA run
    assert "CoCoA+-r000999.npz" in names              # beyond trial range
    assert "restarting with the safe" in capsys.readouterr().out


# --- cli.py: inferred-mesh idle-device note -------------------------------


def test_cli_auto_mesh_note(tmp_path, capsys):
    from cocoa_tpu import cli
    from cocoa_tpu.data.synth import synth_dense, write_libsvm

    path = str(tmp_path / "train.dat")
    write_libsvm(synth_dense(48, 12, seed=0), path)
    # prime numSplits=11 on 8 devices: the largest fitting divisor is 1 —
    # all shards on one chip, 7 devices idle (the worst-case cliff)
    rc = cli.main([
        f"--trainFile={path}", "--numFeatures=12", "--numSplits=11",
        "--numRounds=2", "--localIterFrac=0.25", "--lambda=.01",
        "--justCoCoA=true", "--debugIter=2", "--rng=jax",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "note: inferred mesh uses 1 of 8 devices" in out
    assert "numSplits divisible by 8" in out

    # an explicit --mesh choice is the user's own: no note
    rc = cli.main([
        f"--trainFile={path}", "--numFeatures=12", "--numSplits=11",
        "--numRounds=2", "--localIterFrac=0.25", "--lambda=.01",
        "--justCoCoA=true", "--debugIter=2", "--rng=jax", "--mesh=1",
    ])
    assert rc == 0
    assert "note: inferred mesh" not in capsys.readouterr().out


def test_cli_divergence_guard_flag(tmp_path, capsys):
    from cocoa_tpu import cli
    from cocoa_tpu.data.synth import synth_dense, write_libsvm

    path = str(tmp_path / "train.dat")
    write_libsvm(synth_dense(24, 8, seed=0), path)
    rc = cli.main([f"--trainFile={path}", "--numFeatures=8",
                   "--divergenceGuard=maybe"])
    assert rc == 2
    assert "auto|on|off" in capsys.readouterr().err

    rc = cli.main([f"--trainFile={path}", "--numFeatures=8",
                   "--sigma=auto", "--gapTarget=1e-3",
                   "--divergenceGuard=off"])
    assert rc == 2
    assert "divergence guard" in capsys.readouterr().err
