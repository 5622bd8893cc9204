"""Shard-multiplexed mesh path: K logical shards on D < K devices.

Spark multiplexes K partitions onto fewer executors (``coalesce``,
OptUtils.scala:14: the partition count is a data property, not the worker
count).  The mesh analogue (VERDICT r4 item 7): K = m·D shards ride a
D-device dp mesh with m shards stacked per device — the shard_map body runs
its local (m, ...) block exactly like the single-chip path (inner vmap, or
the batched Pallas/block kernels) and folds the in-device shard sum into
the same ONE psum per round.  These tests pin the multiplexed trajectories
to the single-chip K-shard trajectories bit-close, across driver paths.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data import shard_dataset
from cocoa_tpu.evals import objectives
from cocoa_tpu.parallel import make_mesh
from cocoa_tpu.parallel.fanout import shards_per_device
from cocoa_tpu.solvers import run_cocoa, run_sgd

K, D = 8, 4   # 2 logical shards per device


def _params(data, num_rounds=6):
    return Params(n=data.n, num_rounds=num_rounds, local_iters=8, lam=0.01)


def _debug():
    return DebugParams(debug_iter=2, seed=0)


def test_shards_per_device_validation():
    mesh = make_mesh(D)
    assert shards_per_device(mesh, D) == 1
    assert shards_per_device(mesh, K) == 2
    assert shards_per_device(None, K) == 1
    with pytest.raises(ValueError, match="multiplex"):
        shards_per_device(mesh, D + 1)


@pytest.mark.parametrize("plus", [True, False])
def test_multiplexed_mesh_equals_local(tiny_data, plus):
    """K=8 shards on a 4-device mesh == K=8 on one chip, per-round driver."""
    p = _params(tiny_data)
    mesh = make_mesh(D)
    ds_m = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64,
                         mesh=mesh)
    ds_l = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    w_m, a_m, _ = run_cocoa(ds_m, p, _debug(), plus=plus, mesh=mesh,
                            quiet=True)
    w_l, a_l, _ = run_cocoa(ds_l, p, _debug(), plus=plus, quiet=True)
    np.testing.assert_allclose(np.asarray(w_m), np.asarray(w_l), atol=1e-12)
    np.testing.assert_allclose(np.asarray(a_m), np.asarray(a_l), atol=1e-12)


def test_multiplexed_chunked_and_device_loop(tiny_data):
    """The chunked-scan and device-resident drivers agree with the
    single-chip trajectory under multiplexing (fast math)."""
    p = _params(tiny_data)
    mesh = make_mesh(D)
    ds_m = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64,
                         mesh=mesh)
    ds_l = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    w_l, a_l, traj_l = run_cocoa(ds_l, p, _debug(), plus=True, quiet=True,
                                 math="fast")
    w_c, a_c, _ = run_cocoa(ds_m, p, _debug(), plus=True, mesh=mesh,
                            quiet=True, math="fast", scan_chunk=3)
    np.testing.assert_allclose(np.asarray(w_c), np.asarray(w_l), atol=1e-12)
    w_d, a_d, traj_d = run_cocoa(ds_m, p, _debug(), plus=True, mesh=mesh,
                                 quiet=True, math="fast", device_loop=True)
    np.testing.assert_allclose(np.asarray(w_d), np.asarray(w_l), atol=1e-12)
    np.testing.assert_allclose(np.asarray(a_d), np.asarray(a_l), atol=1e-12)
    for rl, rd in zip(traj_l.records, traj_d.records):
        assert rl.round == rd.round
        np.testing.assert_allclose(rd.gap, rl.gap, atol=1e-12)


def test_multiplexed_sparse_layout(tiny_data):
    """The padded-CSR layout multiplexes too (no column split involved)."""
    p = _params(tiny_data, num_rounds=4)
    mesh = make_mesh(D)
    ds_m = shard_dataset(tiny_data, k=K, layout="sparse", dtype=jnp.float64,
                         mesh=mesh)
    ds_l = shard_dataset(tiny_data, k=K, layout="sparse", dtype=jnp.float64)
    w_m, _, _ = run_cocoa(ds_m, p, _debug(), plus=True, mesh=mesh, quiet=True)
    w_l, _, _ = run_cocoa(ds_l, p, _debug(), plus=True, quiet=True)
    np.testing.assert_allclose(np.asarray(w_m), np.asarray(w_l), atol=1e-12)


def test_multiplexed_block_kernel_interpret(tiny_data):
    """The batched block-chain kernel runs per-device over its m local
    shards inside shard_map (the per_round_batched multiplexed path),
    matching the single-chip block trajectory."""
    p = _params(tiny_data, num_rounds=4)
    mesh = make_mesh(D)
    ds_m = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64,
                         mesh=mesh)
    ds_l = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    kw = dict(plus=True, quiet=True, math="fast", block_size=8,
              scan_chunk=2)
    w_m, a_m, _ = run_cocoa(ds_m, p, _debug(), mesh=mesh, **kw)
    w_l, a_l, _ = run_cocoa(ds_l, p, _debug(), **kw)
    np.testing.assert_allclose(np.asarray(w_m), np.asarray(w_l), atol=1e-12)


def test_multiplexed_dense_pallas_kernel_interpret(tiny_data):
    """K = 4 shards over two devices, the dense Pallas kernel: each
    device's ``per_round_batched`` call takes its two local shards and the
    kernel hands back their Δw summed, one (1, d) row, into the one psum
    a round — the w and α of the one-device run, where the same kernel
    sums all four."""
    k, devices = 4, 2
    p = _params(tiny_data, num_rounds=4)
    mesh = make_mesh(devices)
    assert shards_per_device(mesh, k) == 2
    ds_m = shard_dataset(tiny_data, k=k, layout="dense", dtype=jnp.float64,
                         mesh=mesh)
    ds_l = shard_dataset(tiny_data, k=k, layout="dense", dtype=jnp.float64)
    kw = dict(plus=True, quiet=True, math="fast", pallas=True, scan_chunk=2)
    w_m, a_m, traj = run_cocoa(ds_m, p, _debug(), mesh=mesh, **kw)
    w_l, a_l, _ = run_cocoa(ds_l, p, _debug(), **kw)
    assert traj.meta["solver_path"]["kernel"] == "pallas"
    np.testing.assert_allclose(np.asarray(w_m), np.asarray(w_l), atol=1e-12)
    np.testing.assert_allclose(np.asarray(a_m), np.asarray(a_l), atol=1e-12)
    assert float(jnp.max(jnp.abs(a_l))) > 0


def test_multiplexed_sgd(tiny_data):
    """The SGD family (TsSampler xs with a scalar t leaf) multiplexes."""
    p = _params(tiny_data)
    mesh = make_mesh(D)
    ds_m = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64,
                         mesh=mesh)
    ds_l = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    for local in (True, False):
        w_m, _ = run_sgd(ds_m, p, _debug(), local=local, mesh=mesh,
                         quiet=True)
        w_l, _ = run_sgd(ds_l, p, _debug(), local=local, quiet=True)
        np.testing.assert_allclose(np.asarray(w_m), np.asarray(w_l),
                                   atol=1e-12)


def test_multiplexed_eval_matches_local(tiny_data):
    """The fused eval fanout sums partials over m local shards before its
    one psum — same objective values as the single-chip eval."""
    mesh = make_mesh(D)
    ds_m = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64,
                         mesh=mesh)
    ds_l = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    rng = np.random.default_rng(3)
    w = rng.normal(size=ds_l.num_features)
    w_m = jnp.asarray(w)
    alpha = jnp.asarray(rng.random((K, ds_l.n_shard)))
    p_m = objectives.primal_objective(ds_m, w_m, 0.01)
    p_l = objectives.primal_objective(ds_l, jnp.asarray(w), 0.01)
    np.testing.assert_allclose(float(p_m), float(p_l), atol=1e-12)
    g_m = objectives.duality_gap(ds_m, w_m, alpha, 0.01)
    g_l = objectives.duality_gap(ds_l, jnp.asarray(w), alpha, 0.01)
    np.testing.assert_allclose(float(g_m), float(g_l), atol=1e-12)
