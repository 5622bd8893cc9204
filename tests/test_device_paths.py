"""Device-side execution paths (scan_chunk, device_loop) for the non-CoCoA
solvers: the chunked lax.scan and the fully device-resident lax.while_loop
must produce the same state and trajectory as the host-stepped per-round
driver, on both the single-chip and mesh paths.  (CoCoA's paths are covered
in test_fast_math.py / test_integration.py; mini-batch CD now shares
CoCoA's driver and gains the same paths.)"""

import jax.numpy as jnp
import numpy as np
import pytest

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import shard_dataset
from cocoa_tpu.parallel import make_mesh
from cocoa_tpu.solvers import run_dist_gd, run_minibatch_cd, run_sgd

K = 4


def _params(tiny_data, **kw):
    defaults = dict(n=tiny_data.n, num_rounds=12, local_iters=15, lam=0.01,
                    beta=1.0, gamma=1.0)
    defaults.update(kw)
    return Params(**defaults)


_DBG = DebugParams(debug_iter=4, seed=0)


def _traj_metrics(traj):
    return [(r.round, r.primal, r.gap) for r in traj.records]


@pytest.mark.parametrize("local", [True, False])
def test_sgd_chunked_matches_per_round(tiny_data, local):
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data)
    w0, traj0 = run_sgd(ds, p, _DBG, local=local, quiet=True)
    w1, traj1 = run_sgd(ds, p, _DBG, local=local, quiet=True, scan_chunk=5)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0), atol=1e-12)
    a, b = _traj_metrics(traj0), _traj_metrics(traj1)
    assert [x[0] for x in a] == [x[0] for x in b]
    np.testing.assert_allclose([x[1] for x in a], [x[1] for x in b],
                               atol=1e-12)


@pytest.mark.parametrize("local", [True, False])
def test_sgd_device_loop_matches_per_round(tiny_data, local):
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data)
    w0, traj0 = run_sgd(ds, p, _DBG, local=local, quiet=True)
    w1, traj1 = run_sgd(ds, p, _DBG, local=local, quiet=True,
                        device_loop=True)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0), atol=1e-12)
    a, b = _traj_metrics(traj0), _traj_metrics(traj1)
    assert [x[0] for x in a] == [x[0] for x in b]
    np.testing.assert_allclose([x[1] for x in a], [x[1] for x in b],
                               atol=1e-12)


def test_sgd_chunked_on_mesh_matches_local(tiny_data):
    ds_l = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data)
    w0, _ = run_sgd(ds_l, p, _DBG, local=True, quiet=True)
    mesh = make_mesh(K)
    ds_m = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64,
                         mesh=mesh)
    w1, _ = run_sgd(ds_m, p, _DBG, local=True, quiet=True, mesh=mesh,
                    scan_chunk=5)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0), atol=1e-12)


def test_dist_gd_chunked_and_device_loop_match(tiny_data):
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data)
    w0, traj0 = run_dist_gd(ds, p, _DBG, quiet=True)
    w1, traj1 = run_dist_gd(ds, p, _DBG, quiet=True, scan_chunk=5)
    w2, traj2 = run_dist_gd(ds, p, _DBG, quiet=True, device_loop=True)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0), atol=1e-12)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(w0), atol=1e-12)
    for tr in (traj1, traj2):
        np.testing.assert_allclose(
            [x[1] for x in _traj_metrics(tr)],
            [x[1] for x in _traj_metrics(traj0)], atol=1e-12)


def test_dist_gd_chunked_on_mesh_matches_local(tiny_data):
    ds_l = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data)
    w0, _ = run_dist_gd(ds_l, p, _DBG, quiet=True)
    mesh = make_mesh(K)
    ds_m = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64,
                         mesh=mesh)
    w1, _ = run_dist_gd(ds_m, p, _DBG, quiet=True, mesh=mesh, scan_chunk=4)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0), atol=1e-12)


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_mbcd_device_paths_match(tiny_data, layout):
    """Mini-batch CD through the shared SDCA driver: chunked, device-loop,
    and Pallas (interpret) paths all track the per-round exact path."""
    ds = shard_dataset(tiny_data, k=K, layout=layout, dtype=jnp.float64)
    p = _params(tiny_data)
    w0, a0, _ = run_minibatch_cd(ds, p, _DBG, quiet=True)
    w1, a1, _ = run_minibatch_cd(ds, p, _DBG, quiet=True, scan_chunk=5)
    w2, a2, _ = run_minibatch_cd(ds, p, _DBG, quiet=True, device_loop=True)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0), atol=1e-12)
    np.testing.assert_allclose(np.asarray(w2), np.asarray(w0), atol=1e-12)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a0), atol=1e-12)
    np.testing.assert_allclose(np.asarray(a2), np.asarray(a0), atol=1e-12)
    # fast-math + Pallas kernel (interpret mode on CPU), frozen mode
    w3, a3, _ = run_minibatch_cd(ds, p, _DBG, quiet=True, math="fast",
                                 pallas=True, scan_chunk=5)
    np.testing.assert_allclose(np.asarray(w3), np.asarray(w0), atol=1e-9)
    np.testing.assert_allclose(np.asarray(a3), np.asarray(a0), atol=1e-9)


def test_mbcd_gap_target_early_stop(tiny_data):
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=400, local_iters=30)
    dbg = DebugParams(debug_iter=20, seed=0)
    w, a, traj = run_minibatch_cd(ds, p, dbg, quiet=True, gap_target=0.5,
                                  scan_chunk=20)
    assert traj.records[-1].gap <= 0.5
    assert traj.records[-1].round < 400

def test_device_loop_records_block_timestamps(tiny_data, monkeypatch):
    """VERDICT r1 item 6: the device-resident driver stamps each
    super-block's host sync into the Trajectory, so benchmark-mode JSONL
    keeps monotone (round, time) pairs.  Rounds inside a block stay
    unobservable (wall_time=None) — only the sync boundaries are real."""
    from cocoa_tpu.solvers import base, run_cocoa

    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=20)
    d = DebugParams(debug_iter=2, seed=0)
    # force tiny super-blocks: each block = 1 chunk of debug_iter rounds
    monkeypatch.setattr(base, "MAX_IDX_TABLE_BYTES",
                        4 * 1 * d.debug_iter * K * p.local_iters)
    base._DEVICE_RUNS.clear()
    # sampling="host": the table-size cap (what this test shrinks to force
    # block boundaries) only governs concrete host tables — device-sampling
    # runs ship ~no table bytes and ride one block (their boundaries come
    # from chkptIter alone)
    _, _, traj = run_cocoa(ds, p, d, plus=True, quiet=True, device_loop=True,
                           sampling="host")
    base._DEVICE_RUNS.clear()
    stamps = [r.wall_time for r in traj.records if r.wall_time is not None]
    assert len(stamps) >= 2, [r.wall_time for r in traj.records]
    assert stamps == sorted(stamps)
    assert all(s > 0 for s in stamps)
    # every block boundary (here: every chunk) is stamped
    assert traj.records[-1].wall_time is not None


def test_device_loop_ckpt_round_matches_early_stop(tiny_data, tmp_path):
    """A gap-target run can stop the device while_loop mid-super-block;
    the checkpoint saved at that block's boundary must carry the round
    the run ACTUALLY executed (one eval record per executed chunk), not
    the nominal block end — a later --resume would otherwise skip rounds
    the round-keyed sampler never ran."""
    from cocoa_tpu import checkpoint as ckpt_lib
    from cocoa_tpu.solvers import run_cocoa

    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=60, local_iters=25, lam=0.001)
    # debug_iter=2, chkpt_iter=10 -> blocks of 5 chunks (10 rounds); a
    # loose gap target stops well before round 60, usually mid-block
    dbg = DebugParams(debug_iter=2, seed=0, chkpt_iter=10,
                      chkpt_dir=str(tmp_path))
    w, a, traj = run_cocoa(ds, p, dbg, plus=True, quiet=True,
                           device_loop=True, gap_target=0.15)
    last_round = traj.records[-1].round
    assert traj.records[-1].gap <= 0.15
    assert last_round < 60, "target must hit before the round cap"
    path = ckpt_lib.latest(str(tmp_path), "CoCoA+")
    assert path is not None, "device loop saved no checkpoint"
    meta, _w, _a = ckpt_lib.load(path)
    assert meta["round"] <= last_round, (
        f"checkpoint round {meta['round']} overstates executed "
        f"round {last_round}"
    )


# --- the run's record says how the dense kernel's rows are fetched (PR 42) --

@pytest.mark.parametrize("case, fetch, depth", [
    ("dense_pallas_k4", "ring", 2),         # interleaved: the kernel's ring
    ("dense_pallas_k1", "pipelined", None),     # shard-major: Pallas's
    ("dense_fori", None, None),
    ("sparse_pallas", None, None),
    ("classes_pallas", "ring", 2),
])
def test_solver_path_carries_row_fetch_and_ring_depth(tiny_data, case, fetch,
                                                      depth):
    """``SolverPath.as_dict()`` has ``row_fetch`` and ``ring_depth`` in
    every record; they say something on the dense Pallas path alone: the
    ring and the depth the VMEM fit gives where the K chains advance in
    lockstep, ``pipelined`` where a shard runs at a time, None on ``fori``
    and on every sparse path."""
    import dataclasses

    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    layout = "sparse" if case.startswith("sparse") else "dense"
    k = 1 if case.endswith("k1") else K
    ds = shard_dataset(tiny_data, k=k, layout=layout, dtype=jnp.float32)
    if case.startswith("classes"):
        ds = dataclasses.replace(
            ds, classes=jnp.zeros(ds.labels.shape, jnp.int32), num_classes=3)
    path = resolve_solver_path(ds, 8, math="fast",
                               pallas="fori" not in case).as_dict()
    assert {"row_fetch", "ring_depth"} <= set(path)
    assert (path["row_fetch"], path["ring_depth"]) == (fetch, depth)
    assert (path["form"] is None) == (fetch is None)


def test_a_dense_pallas_job_records_its_ring(tiny_data):
    """The record a job returns (``Trajectory.meta``) and its console line
    carry the mechanism: a reader of the run can tell that the ring ran,
    and how deep."""
    from cocoa_tpu.solvers import run_cocoa

    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    _, _, traj = run_cocoa(ds, _params(tiny_data, num_rounds=4), _DBG,
                           plus=True, quiet=True, math="fast", pallas=True)
    path = traj.meta["solver_path"]
    assert (path["form"], path["row_fetch"], path["ring_depth"]) == (
        "interleaved", "ring", 2)      # rounds of 15 steps: the shallowest
    _, _, fori = run_cocoa(ds, _params(tiny_data, num_rounds=4), _DBG,
                           plus=True, quiet=True, math="fast", pallas=False)
    assert fori.meta["solver_path"]["row_fetch"] is None
    assert fori.meta["solver_path"]["ring_depth"] is None
