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

import jax
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


# --- the loop builder (solvers/cocoa.build_sdca_loop) ------------------------

_WARM = dataclasses.replace(
    Params(n=64, num_rounds=40, local_iters=12, lam=1e-2),
    loss="smooth_hinge", smoothing=0.5)
# an arm's static description -> the table it must make, in branch-index
# order (stage . n_phases + phase) . n_theta + theta
_DESCRIPTIONS = {
    "plain": dict(levels=(4.0,), phases=1, theta_hs=(12,), bank=False),
    "sched": dict(levels=(1.0, 2.0, 4.0), phases=1, theta_hs=(12,),
                  bank=False),
    "sched_warm": dict(levels=(4.0,), phases=2, theta_hs=(12,), bank=False),
    "accel": dict(levels=(4.0,), phases=1, theta_hs=(12,), bank=True),
    "accel_warm_theta": dict(levels=(2.0, 4.0), phases=2, theta_hs=(6, 12),
                             bank=True),
}


@pytest.mark.parametrize("arm", list(_DESCRIPTIONS))
def test_builder_table_is_levels_x_phases_x_theta(tiny_data, arm,
                                                  monkeypatch):
    """One branch table for every arm: ``len(levels) . n_phases . n_theta``
    chunk kernels, made in the order the one branch index reads them, a Θ
    stage at its own H; the plain arm's jitted step takes and returns
    exactly (w, α), every other arm's the leaves it was handed."""
    from cocoa_tpu.solvers import cocoa

    d = _DESCRIPTIONS[arm]
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float32)
    params = dataclasses.replace(_WARM, n=tiny_data.n, loss="hinge",
                                 smoothing=0.0)
    phases = [dataclasses.replace(_WARM, n=tiny_data.n), params][-d["phases"]:]
    made, real = [], cocoa._make_chunk_kernel

    def counting(mesh, p, k, alg, **kw):
        made.append((alg[2], p.loss, p.local_iters))
        return real(mesh, p, k, alg, **kw)

    monkeypatch.setattr(cocoa, "_make_chunk_kernel", counting)
    sampler = base.IndexSampler("permuted", 0, 12, ds.counts, device=True)
    kernel, step, token = cocoa.build_sdca_loop(
        None, params, K, ("plus", 1.0, d["levels"][0]), sampler,
        dict(math="fast"), levels=d["levels"], branch_params=phases,
        theta_hs=d["theta_hs"], warm_end=10 * (d["phases"] - 1),
        bank=d["bank"])
    assert made == [(lv, p.loss, hs) for lv in d["levels"] for p in phases
                    for hs in d["theta_hs"]]
    assert (token is None) == (arm == "plain")

    state = [jnp.zeros(ds.num_features, jnp.float32),
             jnp.zeros((K, ds.n_shard), jnp.float32)]
    if d["bank"]:
        state.append(jnp.zeros((2, K, ds.n_shard), jnp.float32))
    if arm != "plain":
        state.append(jnp.asarray(
            base.sched_init_values(1, accel=d["bank"])))
    idxs = sampler.chunk_indices(1, 5)
    out = jax.eval_shape(step, *state, idxs, ds.shard_arrays())
    assert [(o.shape, o.dtype) for o in out] == [(s.shape, s.dtype)
                                                 for s in state]
    assert jax.eval_shape(kernel, tuple(state), idxs,
                          ds.shard_arrays()) == out
    if arm == "plain":
        assert len(out) == 2
        with pytest.raises(TypeError):      # (w, α) and nothing else
            jax.eval_shape(step, *state, state[1], idxs, ds.shard_arrays())


@pytest.mark.parametrize("arm", ["plain", "accel", "sched"])
@pytest.mark.parametrize("loop", ["device_loop", "host_stepped"])
def test_equal_jobs_share_one_step_and_one_loop(tiny_data, arm, loop):
    """Two jobs with equal arguments: one entry of the step cache and one
    of the loop cache, and the second compiles nothing."""
    from cocoa_tpu.analysis import sanitize
    from cocoa_tpu.solvers import cocoa

    job, _ = _svm_job(tiny_data, None, arm, dtype=jnp.float32)
    kw = {} if loop == "device_loop" else dict(device_loop=False,
                                               scan_chunk=1)
    first = job(**kw)
    cached = len(cocoa._CHUNK_STEPS), len(base._DEVICE_RUNS)
    with sanitize.watch_compiles() as compiles:
        again = job(**kw)
    assert (len(cocoa._CHUNK_STEPS), len(base._DEVICE_RUNS)) == cached
    assert [c.name for c in compiles] == []
    _same(first, again)


@pytest.mark.parametrize("arm", ["plain", "accel", "sched"])
def test_host_stepped_counts(tiny_data, arm):
    """What the ONE host-stepped driver counts, at chunk = 1 as at the
    eval cadence: a launch a chunk and one an eval beside the start
    program's, one sanctioned read an eval, and the device loop's
    trajectory."""
    job, _ = _svm_job(tiny_data, None, arm)
    # (a target out of reach: the sched arm needs one, and every round runs)
    looped = job(gap_target=1e-12)
    one = job(gap_target=1e-12, device_loop=False, scan_chunk=1)
    five = job(gap_target=1e-12, device_loop=False, scan_chunk=5)
    assert one[1].meta["launches"] == 1 + 40 + 8
    assert five[1].meta["launches"] == 1 + 8 + 8
    assert one[1].meta["fetches"] == five[1].meta["fetches"] == 8
    _same(one, five)
    _same(one, looped)


def test_per_round_driver_is_the_chunked_one_at_chunk_1(tiny_data):
    """No ``scan_chunk`` and no device loop: the per-round program under
    the chunked driver — the same counts as the chunk program at chunk = 1
    and the same records (the two programs round alike here)."""
    job, _ = _svm_job(tiny_data, None)
    per_round = job(gap_target=None, device_loop=False)
    chunked = job(gap_target=None, device_loop=False, scan_chunk=1)
    assert per_round[1].meta["launches"] == chunked[1].meta["launches"] \
        == 1 + 40 + 8
    assert per_round[1].meta["solver_path"] == chunked[1].meta["solver_path"]
    assert _records(per_round[1])[1] == _records(chunked[1])[1]
    assert [r[0] for r in _records(per_round[1])[0]] == \
        [r[0] for r in _records(chunked[1])[0]]
