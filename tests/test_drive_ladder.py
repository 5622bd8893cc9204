"""The drive ladder's host path for a warm job: one start program, one loop
dispatch a super-block, one read a super-block.

``Trajectory.meta`` carries what the ladder issued (``launches``: programs
between the job's call and its result, counted on the host where they are
issued) and how often it read the device (``fetches``:
``sanitize.intended_fetch`` entries), so the counts are held here, on a
CPU, beside the round counts of ``test_count_gates.py``.  What follows from
what the ladder can observe — ``sampler.device``, whether an init was
handed in, whether there is a mesh — must not change a bit of the result.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data import shard_dataset
from cocoa_tpu.data.columns import shard_columns
from cocoa_tpu.parallel import make_mesh
from cocoa_tpu.solvers import base, run_cocoa, run_prox_cocoa

from test_prox import _problem

K = 4
_DBG = DebugParams(debug_iter=5, seed=0)
_JOB = dict(quiet=True, math="fast", device_loop=True, rng="permuted")
# the arms of the SDCA family's loop carry: (w, α), + the σ′ schedule
# leaf, + the --accel window bank and schedule leaf
_ARMS = {
    "plain": dict(accel="off"),
    "accel": dict(accel="on"),
    "sched": dict(accel="off", sigma_schedule="anneal"),
}


def _svm_job(tiny_data, mesh, arm="plain", loss="hinge", dtype=jnp.float64):
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=dtype,
                       mesh=mesh)
    params = Params(n=tiny_data.n, num_rounds=40, local_iters=12, lam=1e-2,
                    loss=loss, smoothing=1.0 if loss == "logistic" else 0.0,
                    sigma="auto" if arm == "sched" else None)

    def job(debug=_DBG, params=params, **kw):
        w, alpha, traj = run_cocoa(
            ds, params, debug, plus=True, mesh=mesh,
            **{**_JOB, "gap_target": 1e-6, **_ARMS[arm], **kw})
        return (np.asarray(w), np.asarray(alpha)), traj

    zeros = dict(w_init=np.zeros(ds.num_features),
                 alpha_init=np.zeros((K, ds.n_shard)))
    return job, zeros


def _prox_job(mesh, dtype=jnp.float64):
    A, b, _, data = _problem(seed=3)
    ds = shard_columns(data, K, dtype=dtype, mesh=mesh)
    params = Params(n=data.num_features, num_rounds=40, local_iters=12,
                    lam=float(0.1 * np.max(np.abs(A.T @ b))), gamma=1.0,
                    smoothing=0.0, loss="lasso")

    def job(debug=_DBG, params=params, **kw):
        x, r, traj = run_prox_cocoa(
            ds, params, debug, mesh=mesh,
            **{**_JOB, "gap_target": 1e-9, **kw})
        return (np.asarray(r), np.asarray(x)), traj

    zeros = dict(r_init=-np.asarray(b), x_init=np.zeros((K, ds.n_shard)))
    return job, zeros


def _records(traj):
    return [(r.round, r.primal, r.gap, r.sigma) for r in traj.records], \
        traj.stopped


def _same(a, b):
    (state_a, traj_a), (state_b, traj_b) = a, b
    for x, y in zip(state_a, state_b):
        np.testing.assert_array_equal(x, y)
    assert _records(traj_a) == _records(traj_b)


@pytest.fixture(autouse=True)
def _inert_bus():
    """An active event bus is one more sanctioned read a block on the
    scheduled arms (the tap reads the schedule leaf before the dispatch):
    the counts here are a job's with the bus as a library caller has it."""
    from cocoa_tpu.telemetry import events

    events.get_bus().reset()
    yield
    events.get_bus().reset()


@pytest.fixture
def staging_threads(monkeypatch):
    """The staging threads the ladder starts, counted."""
    started = []

    class Counting(base._Prefetch):
        def __init__(self, fn, *args):
            started.append(getattr(fn, "__name__", "?"))
            super().__init__(fn, *args)

    monkeypatch.setattr(base, "_Prefetch", Counting)
    return started


@pytest.mark.parametrize("entry, arm", [
    ("svm", "plain"), ("svm", "accel"), ("svm", "sched"), ("prox", "plain")])
@pytest.mark.parametrize("devices", [None, 4], ids=["one_device", "mesh4"])
def test_warm_job_is_one_start_one_loop_one_read(tiny_data, entry, arm,
                                                 devices, staging_threads):
    """A warm ``--deviceLoop`` job that samples in-jit and starts from
    nothing: at most 3 programs (the start program, the loop; the prox
    entry's support count is the third), one read a super-block, and no
    staging thread.  With ``--sampling=host`` the tables are real work:
    the thread runs, and the job's result does not change by a bit."""
    mesh = make_mesh(devices) if devices else None
    job, _ = (_svm_job(tiny_data, mesh, arm) if entry == "svm"
              else _prox_job(mesh))
    job()                                   # warm-up: compiles
    del staging_threads[:]
    state, traj = job()
    assert len(traj.records) >= 2
    assert traj.meta["fetches"] == 1        # one super-block
    assert traj.meta["launches"] == (3 if entry == "prox" else 2)
    assert staging_threads == []
    hosted = job(sampling="host")
    assert staging_threads and set(staging_threads) == {"stage"}
    assert hosted[1].meta["fetches"] == 1
    _same((state, traj), hosted)


@pytest.mark.parametrize("entry, loss", [
    ("svm", "hinge"), ("svm", "logistic"), ("prox", "lasso")])
def test_job_from_nothing_equals_job_from_explicit_zeros(tiny_data, entry,
                                                         loss):
    """The one start program against the path an init takes (a leaf at a
    time): the same (w, α) — (r, x) for the lasso — and the same
    trajectory, on the Pallas kernels (interpreted here)."""
    job, zeros = (_svm_job(tiny_data, None, loss=loss, dtype=jnp.float32)
                  if entry == "svm" else _prox_job(None, dtype=jnp.float32))
    fresh = job(pallas=True)
    given = job(pallas=True, **zeros)
    assert fresh[1].meta["solver_path"]["kernel"] == "pallas"
    assert fresh[1].meta["launches"] < given[1].meta["launches"]
    _same(fresh, given)


@pytest.mark.parametrize("arm", ["accel", "sched"])
def test_scheduled_arms_from_nothing_equal_a_handed_alpha(tiny_data, arm):
    """The arms whose start state has more leaves than (w, α): a zero
    ``alpha_init`` alone takes the old path (a ``w_init`` would also drop
    the σ′ schedule: a resumed run has no stage to trust)."""
    job, zeros = _svm_job(tiny_data, None, arm)
    _same(job(), job(alpha_init=zeros["alpha_init"]))


@pytest.mark.parametrize("entry", ["svm", "prox"])
def test_two_super_blocks_equal_one(tiny_data, entry, tmp_path,
                                    staging_threads):
    """``chkptIter`` caps a super-block: the job is two loop dispatches
    and two reads, each block's spec built on the driving thread, and the
    result is the one-block job's."""
    job, _ = _svm_job(tiny_data, None) if entry == "svm" else _prox_job(None)
    # no target: every round of both blocks runs
    one = job(gap_target=None)
    ckpt = dataclasses.replace(_DBG, chkpt_dir=str(tmp_path), chkpt_iter=20)
    two = job(debug=ckpt, gap_target=None)
    extra = 1 if entry == "prox" else 0
    assert one[1].meta["fetches"] == 1
    assert one[1].meta["launches"] == 2 + extra
    assert two[1].meta["fetches"] == 2
    assert two[1].meta["launches"] == 3 + extra
    assert staging_threads == []
    _same(one, two)


def test_console_line_reports_launches_and_fetches(tiny_data, capsys):
    job, _ = _svm_job(tiny_data, None)
    job()
    capsys.readouterr()
    _, traj = job(quiet=False)
    out = capsys.readouterr().out
    assert "local solver:" in out
    assert (f"drive ladder: {traj.meta['launches']} programs launched, "
            f"{traj.meta['fetches']} host fetches") in out


def test_fetch_helper_reads_once_and_cuts_on_the_host():
    """The shared read of the solo loop and its fleet twin: the whole
    buffer comes back, the rows the loop wrote are cut in NumPy, and a
    scalar count (the fleet's) reads like a header vector (the solo's)."""
    from cocoa_tpu.analysis import sanitize

    buf = jnp.arange(24.0).reshape(6, 4)
    before = sanitize.intended_fetches_total
    head, rows = base.fetch_loop_result(jnp.asarray([2, 1, 0], jnp.int32),
                                        buf, "device_loop_fetch")
    assert head.tolist() == [2, 1, 0]
    np.testing.assert_array_equal(rows, np.asarray(buf)[:2])
    head, rows = base.fetch_loop_result(jnp.int32(6), buf,
                                        "fleet_loop_fetch")
    assert head.tolist() == [6] and rows.shape == (6, 4)
    assert isinstance(rows, np.ndarray)
    assert sanitize.intended_fetches_total - before == 2
