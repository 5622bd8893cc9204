"""ProxCoCoA+ (lasso / elastic net): literal NumPy oracle parity, execution
path equality (exact / fast / Pallas-interpret / chunked / device-loop /
mesh), duality-gap certificate properties, sparse recovery."""

import numpy as np
import jax.numpy as jnp
import pytest

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.columns import shard_columns
from cocoa_tpu.data.libsvm import LibsvmData
from cocoa_tpu.data.sharding import split_sizes
from cocoa_tpu.parallel import make_mesh
from cocoa_tpu.solvers import run_prox_cocoa
from cocoa_tpu.utils.prng import sample_indices

K = 4


def _problem(seed=0, n=96, d=48, sparsity=6, noise=0.01):
    rng = np.random.default_rng(seed)
    A = rng.normal(size=(n, d)) / np.sqrt(n)
    x_true = np.zeros(d)
    x_true[rng.choice(d, sparsity, replace=False)] = 3 * rng.normal(size=sparsity)
    b = A @ x_true + noise * rng.normal(size=n)
    indptr = np.arange(0, (n + 1) * d, d, dtype=np.int64)
    data = LibsvmData(labels=b, indptr=indptr,
                      indices=np.tile(np.arange(d, dtype=np.int32), n),
                      values=A.reshape(-1), num_features=d)
    return A, b, x_true, data


def _params(d, lam, **kw):
    defaults = dict(n=d, num_rounds=20, local_iters=10, lam=lam,
                    gamma=1.0, smoothing=0.0, loss="lasso")
    defaults.update(kw)
    return Params(**defaults)


_DBG = DebugParams(debug_iter=5, seed=0)


def _oracle_prox(A, b, lam, k, rounds, h, seed, l2=0.0, gamma=1.0):
    """Literal sequential ProxCoCoA+: column shards, per-round frozen r0,
    sigma'-corrected prox-CD steps, additive aggregation — the NumPy ground
    truth the TPU build must match in x64."""
    n, d = A.shape
    sizes = split_sizes(d, k)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    sigma = k * gamma
    x = np.zeros(d)
    r = -b.astype(np.float64).copy()
    for t in range(1, rounds + 1):
        dv_sum = np.zeros(n)
        for s in range(k):
            lo, hi = offs[s], offs[s + 1]
            cols = A[:, lo:hi]
            idxs = sample_indices(seed, range(t, t + 1), h, hi - lo)[0]
            dv = np.zeros(n)
            dx = np.zeros(hi - lo)
            for j in idxs:
                a_j = cols[:, j]
                q = sigma * (a_j @ a_j)
                z = a_j @ r + sigma * (a_j @ dv)
                a_cur = x[lo + j] + dx[j]
                denom = q + l2
                if denom <= 0:
                    continue
                u = (q * a_cur - z) / denom
                t_new = np.sign(u) * max(abs(u) - lam / denom, 0.0)
                delta = t_new - a_cur
                dx[j] += delta
                dv += a_j * delta
            x[lo:hi] += gamma * dx
            dv_sum += dv
        r = r + gamma * dv_sum
    return x, r


def test_prox_matches_oracle_exact():
    A, b, _, data = _problem()
    d = data.num_features
    ds = shard_columns(data, K, dtype=jnp.float64)
    lam = 0.1 * np.max(np.abs(A.T @ b))
    p = _params(d, float(lam))
    x, r, _ = run_prox_cocoa(ds, p, _DBG, quiet=True, math="exact")
    x_o, r_o = _oracle_prox(A, b, lam, K, p.num_rounds, p.local_iters, 0)
    xs = np.concatenate([np.asarray(x[s])[:c] for s, c in enumerate(ds.counts)])
    np.testing.assert_allclose(xs, x_o, atol=1e-12)
    np.testing.assert_allclose(np.asarray(r)[:len(b)], r_o, atol=1e-12)


@pytest.mark.slow
@pytest.mark.parametrize("l2", [0.0, 0.3])
def test_prox_fast_and_paths_match_exact(l2):
    A, b, _, data = _problem(seed=1)
    d = data.num_features
    ds = shard_columns(data, K, dtype=jnp.float64)
    lam = 0.1 * np.max(np.abs(A.T @ b))
    p = _params(d, float(lam), smoothing=l2)
    x0, r0, _ = run_prox_cocoa(ds, p, _DBG, quiet=True, math="exact", l2=l2)
    for kw in (dict(math="fast", pallas=False),
               dict(math="fast", pallas=False, scan_chunk=5),
               dict(math="fast", pallas=False, device_loop=True),
               dict(math="fast", pallas=True, scan_chunk=5)):
        x1, r1, _ = run_prox_cocoa(ds, p, _DBG, quiet=True, l2=l2, **kw)
        np.testing.assert_allclose(np.asarray(x1), np.asarray(x0), atol=1e-9,
                                   err_msg=str(kw))
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r0), atol=1e-9,
                                   err_msg=str(kw))


# rows of A (the shared vector's length) -> the form the VMEM fit alone
# picks for eight shards of two columns in x64: 72,000-long vectors, all
# eight chains' and the shallowest ring of them beside each other, pass
# the interleaved kernel's 14 MiB
@pytest.mark.parametrize("rows,form", [(96, "interleaved"),
                                       (72_000, "shard_major")])
def test_lasso_job_through_both_dense_forms_matches_fori(rows, form):
    """A lasso job through the dense Pallas kernel, whose (1, n) result is
    the eight shards' Δv summed in its epilogue, in the form the shape
    resolves to — no flag picks it — against the ``fori`` path: x, r and
    every certificate of the trajectory."""
    from cocoa_tpu.ops import pallas_sdca

    k = 8
    A, b, _, data = _problem(seed=4, n=rows, d=16, sparsity=4)
    lam = 0.1 * np.max(np.abs(A.T @ b))
    p = _params(data.num_features, float(lam), num_rounds=10, local_iters=3)
    ds = shard_columns(data, k, dtype=jnp.float64)
    assert pallas_sdca.dense_form(k, ds.n_shard, ds.num_features, 8,
                                  3) == form
    kw = dict(quiet=True, math="fast", scan_chunk=5)
    x0, r0, fori = run_prox_cocoa(ds, p, _DBG, pallas=False, **kw)
    x1, r1, traj = run_prox_cocoa(ds, p, _DBG, pallas=True, **kw)
    path = traj.meta["solver_path"]
    assert (path["kernel"], path["form"]) == ("pallas", form)
    assert fori.meta["solver_path"]["kernel"] != "pallas"
    assert np.count_nonzero(np.asarray(x0)) > 0
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x0), atol=1e-10)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r0), atol=1e-10)
    assert [rec.round for rec in traj.records] == [
        rec.round for rec in fori.records]
    np.testing.assert_allclose([rec.gap for rec in traj.records],
                               [rec.gap for rec in fori.records],
                               rtol=1e-9, atol=1e-10)


@pytest.mark.slow
def test_prox_sparse_columns_match_dense():
    """The padded-CSC column layout must produce exactly the dense column
    layout's trajectory, on both the fori paths and the sparse Pallas
    kernel (interpret)."""
    A, b, _, data = _problem(seed=7)
    d = data.num_features
    lam = 0.1 * np.max(np.abs(A.T @ b))
    p = _params(d, float(lam))
    ds_d = shard_columns(data, K, dtype=jnp.float64, layout="dense")
    ds_s = shard_columns(data, K, dtype=jnp.float64, layout="sparse")
    assert ds_s.layout == "sparse"
    x0, r0, _ = run_prox_cocoa(ds_d, p, _DBG, quiet=True, math="exact")
    for kw in (dict(math="exact"),
               dict(math="fast", pallas=False),
               dict(math="fast", pallas=True, scan_chunk=5)):
        x1, r1, _ = run_prox_cocoa(ds_s, p, _DBG, quiet=True, **kw)
        np.testing.assert_allclose(np.asarray(x1), np.asarray(x0),
                                   atol=1e-9, err_msg=str(kw))
        np.testing.assert_allclose(np.asarray(r1), np.asarray(r0),
                                   atol=1e-9, err_msg=str(kw))


def test_shard_columns_rejects_degenerate_csc():
    _, _, _, data = _problem(seed=8)
    with np.testing.assert_raises(ValueError):
        shard_columns(data, K, layout="sparse", max_col_nnz=2)


@pytest.mark.slow
def test_prox_mesh_matches_local():
    A, b, _, data = _problem(seed=2)
    d = data.num_features
    lam = 0.1 * np.max(np.abs(A.T @ b))
    p = _params(d, float(lam))
    ds_l = shard_columns(data, K, dtype=jnp.float64)
    x0, r0, _ = run_prox_cocoa(ds_l, p, _DBG, quiet=True, math="exact")
    mesh = make_mesh(K)
    ds_m = shard_columns(data, K, dtype=jnp.float64, mesh=mesh)
    x1, r1, _ = run_prox_cocoa(ds_m, p, _DBG, quiet=True, math="exact",
                               mesh=mesh)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x0), atol=1e-12)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r0), atol=1e-12)


def test_prox_gap_certificate_and_early_stop():
    A, b, _, data = _problem(seed=3)
    d = data.num_features
    ds = shard_columns(data, K, dtype=jnp.float64)
    lam = 0.2 * np.max(np.abs(A.T @ b))
    p = _params(d, float(lam), num_rounds=400, local_iters=24)
    x, r, traj = run_prox_cocoa(ds, p, _DBG, quiet=True,
                                gap_target=1e-6, math="fast")
    gaps = [rec.gap for rec in traj.records]
    assert all(g is not None and g >= -1e-12 for g in gaps)
    assert traj.records[-1].gap <= 1e-6
    assert traj.records[-1].round < 400
    # the certificate is honest: P(x) − D(u) recomputed directly
    xs = np.concatenate([np.asarray(x[s])[:c] for s, c in enumerate(ds.counts)])
    rr = np.asarray(r)[:len(b)]
    np.testing.assert_allclose(rr, A @ xs - b, atol=1e-10)
    primal = 0.5 * rr @ rr + lam * np.abs(xs).sum()
    s = min(1.0, lam / np.max(np.abs(A.T @ rr)))
    dual = -0.5 * (s * rr) @ (s * rr) - (s * rr) @ b
    assert primal - dual <= 1e-6 + 1e-12


def test_prox_elastic_net_gap_certificate_and_early_stop():
    """VERDICT r2 item 4: the l2 term smooths the L1 conjugate
    (h*(s) = ([|s|−λ]₊)²/(2η)), so elastic net certifies too — gap
    present at every eval, ≥ 0 (weak duality), honest against a direct
    NumPy recomputation, and driving gap-target early stop."""
    A, b, _, data = _problem(seed=4)
    d = data.num_features
    ds = shard_columns(data, K, dtype=jnp.float64)
    lam = 0.2 * np.max(np.abs(A.T @ b))
    l2 = 0.5
    p = _params(d, float(lam), smoothing=l2, num_rounds=400,
                local_iters=24)
    x, r, traj = run_prox_cocoa(ds, p, _DBG, quiet=True,
                                gap_target=1e-6, math="fast", l2=l2)
    gaps = [rec.gap for rec in traj.records]
    assert all(g is not None and g >= -1e-12 for g in gaps)
    assert traj.records[-1].gap <= 1e-6
    assert traj.records[-1].round < 400
    # the certificate is honest: P(x) − D(r) recomputed directly
    xs = np.concatenate([np.asarray(x[s])[:c]
                         for s, c in enumerate(ds.counts)])
    rr = np.asarray(r)[:len(b)]
    np.testing.assert_allclose(rr, A @ xs - b, atol=1e-10)
    primal = (0.5 * rr @ rr + lam * np.abs(xs).sum()
              + 0.5 * l2 * (xs @ xs))
    excess = np.maximum(np.abs(A.T @ rr) - lam, 0.0)
    dual = -0.5 * rr @ rr - rr @ b - (excess @ excess) / (2 * l2)
    np.testing.assert_allclose(traj.records[-1].gap, primal - dual,
                               rtol=1e-6, atol=1e-12)
    assert primal - dual <= 1e-6 + 1e-12


def test_prox_resume_equals_uninterrupted(tmp_path):
    """Checkpoint the (r, x) state at round 6, resume to 12 → identical to
    a straight 12-round run (round-indexed RNG makes this exact)."""
    A, b, _, data = _problem(seed=6)
    d = data.num_features
    ds = shard_columns(data, K, dtype=jnp.float64)
    lam = 0.1 * np.max(np.abs(A.T @ b))
    dbg_save = DebugParams(debug_iter=6, seed=0, chkpt_iter=6,
                           chkpt_dir=str(tmp_path))
    p_half = _params(d, float(lam), num_rounds=6)
    run_prox_cocoa(ds, p_half, dbg_save, quiet=True, math="exact")

    from cocoa_tpu import checkpoint as ckpt_lib

    path = ckpt_lib.latest(str(tmp_path), "ProxCoCoA+")
    assert path is not None
    meta, r0, x0 = ckpt_lib.load(path)
    assert meta["round"] == 6

    p_full = _params(d, float(lam), num_rounds=12)
    x_a, r_a, _ = run_prox_cocoa(ds, p_full, _DBG, quiet=True,
                                 math="exact")
    x_b, r_b, _ = run_prox_cocoa(ds, p_full, _DBG, quiet=True,
                                 math="exact", r_init=r0, x_init=x0,
                                 start_round=meta["round"] + 1)
    np.testing.assert_array_equal(np.asarray(x_b), np.asarray(x_a))
    np.testing.assert_array_equal(np.asarray(r_b), np.asarray(r_a))


def test_prox_recovers_sparse_support():
    A, b, x_true, data = _problem(seed=5, noise=0.001)
    ds = shard_columns(data, K, dtype=jnp.float64)
    lam = 0.02 * np.max(np.abs(A.T @ b))
    p = _params(data.num_features, float(lam), num_rounds=300, local_iters=24)
    x, r, traj = run_prox_cocoa(ds, p, _DBG, quiet=True,
                                gap_target=1e-8, math="fast")
    xs = np.concatenate([np.asarray(x[s])[:c] for s, c in enumerate(ds.counts)])
    support_true = np.abs(x_true) > 0
    # every true-support coordinate is recovered with the right sign
    assert np.all(np.sign(xs[support_true]) == np.sign(x_true[support_true]))

# --- the target rides the dataset; column shards from device arrays ---------

def test_target_rides_the_dataset():
    """``shard_columns`` returns the dataset alone, the regression target
    on it (zero-padded to the shared vector's length, a pytree leaf);
    ``run_prox_cocoa`` takes what every solver's entry takes and refuses a
    dataset without one."""
    import dataclasses

    import jax

    A, b, _, data = _problem(seed=9)
    ds = shard_columns(data, K, dtype=jnp.float64)
    assert ds.target.shape == (ds.num_features,)
    np.testing.assert_array_equal(np.asarray(ds.target)[:len(b)], b)
    assert not np.asarray(ds.target)[len(b):].any()
    assert "target" not in ds.shard_arrays()        # no K axis: not fanned out
    leaves, tree = jax.tree.flatten(ds)
    assert any(leaf is ds.target for leaf in leaves)
    assert jax.tree.unflatten(tree, leaves).target is ds.target
    p = _params(data.num_features, 0.1)
    with pytest.raises((TypeError, AttributeError)):
        run_prox_cocoa(ds, ds.target, p, _DBG, quiet=True)   # the old call
    with pytest.raises(TypeError, match="objective"):
        run_prox_cocoa(ds, p, _DBG, quiet=True, objective="lasso")
    # the smoothed hinge's s (Params.smoothing, 1 by default) is not the
    # elastic-net weight: that is the entry's own ``l2``
    x0, r0, _ = run_prox_cocoa(ds, p, _DBG, quiet=True)
    x1, r1, _ = run_prox_cocoa(ds, dataclasses.replace(p, smoothing=1.0),
                               _DBG, quiet=True)
    np.testing.assert_array_equal(np.asarray(x1), np.asarray(x0))


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_row_shards_are_refused(layout):
    """The SVM solvers' dataset (row shards, labels, no target) handed to
    the prox entry by mistake is an error, not a lasso on its labels."""
    from cocoa_tpu.data import shard_dataset

    _, _, _, data = _problem(seed=11)
    rows = shard_dataset(data, k=K, layout=layout)
    assert rows.target is None
    with pytest.raises(ValueError, match="column shards that carry"):
        run_prox_cocoa(rows, _params(data.n, 0.1), _DBG, quiet=True)


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("d", [48, 27])     # equal blocks; blocks of 7, 7, 7, 6
def test_device_column_build_matches_shard_columns_bit_for_bit(d, dtype):
    """``shard_dense_columns`` (A^T and b as device arrays, no host CSR)
    against ``shard_columns`` from the same matrix: every field to the last
    bit, on entries whose squares sum exactly in float32 (eighths)."""
    from cocoa_tpu.data.columns import shard_dense_columns

    rng = np.random.default_rng(d)
    n = 40
    A = rng.integers(-8, 9, size=(n, d)) / 8.0
    b = rng.normal(size=n)
    data = LibsvmData(labels=b, indptr=np.arange(0, (n + 1) * d, d,
                                                 dtype=np.int64),
                      indices=np.tile(np.arange(d, dtype=np.int32), n),
                      values=A.reshape(-1), num_features=d)
    host = shard_columns(data, K, dtype=jnp.dtype(dtype), layout="dense")
    dev = shard_dense_columns(jnp.asarray(A.T), jnp.asarray(b), K,
                              dtype=jnp.dtype(dtype))
    for name in ("labels", "mask", "sq_norms", "X", "target"):
        want, got = (np.asarray(getattr(s, name)) for s in (host, dev))
        assert want.dtype == got.dtype == np.dtype(dtype), name
        np.testing.assert_array_equal(got, want, err_msg=name)
    assert (dev.layout, dev.n, dev.num_features, list(dev.counts)) == (
        host.layout, host.n, host.num_features, list(host.counts))
    assert dev.X.shape == (K, 16, 40)       # blocks padded to 16, n to 8


def test_device_column_build_on_a_mesh_trains_like_the_host_build():
    from cocoa_tpu.data.columns import shard_dense_columns

    A, b, _, data = _problem(seed=2)
    lam = 0.1 * np.max(np.abs(A.T @ b))
    p = _params(data.num_features, float(lam), num_rounds=5)
    mesh = make_mesh(K)
    ds_h = shard_columns(data, K, dtype=jnp.float64, mesh=mesh)
    ds_d = shard_dense_columns(jnp.asarray(A.T), jnp.asarray(b), K,
                               dtype=jnp.float64, mesh=mesh)
    assert ds_d.X.sharding == ds_h.X.sharding
    assert ds_d.target.sharding == ds_h.target.sharding
    x0, r0, _ = run_prox_cocoa(ds_h, p, _DBG, mesh=mesh, quiet=True)
    x1, r1, _ = run_prox_cocoa(ds_d, p, _DBG, mesh=mesh, quiet=True)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x0), atol=1e-12)
    np.testing.assert_allclose(np.asarray(r1), np.asarray(r0), atol=1e-12)


@pytest.mark.parametrize("l2,objective", [(0.0, "lasso"),
                                          (0.3, "elastic_net")])
def test_run_records_objective_form_support_and_vector_length(l2, objective,
                                                              capsys):
    """``SolverPath.objective`` / ``form`` and ``Trajectory.meta``'s
    ``x_nnz`` / ``vector_len``, on the console line too."""
    A, b, _, data = _problem(seed=5)
    ds = shard_columns(data, K, dtype=jnp.float32)
    lam = 0.1 * np.max(np.abs(A.T @ b))
    p = _params(data.num_features, float(lam), smoothing=l2, num_rounds=10)
    x, r, traj = run_prox_cocoa(ds, p, _DBG, math="fast", pallas=True,
                                scan_chunk=5, l2=l2)
    path = traj.meta["solver_path"]
    assert (path["objective"], path["form"], path["kernel"]) == (
        objective, "interleaved", "pallas")
    assert traj.meta["vector_len"] == ds.num_features == 96
    mask = np.asarray(ds.mask)
    assert traj.meta["x_nnz"] == np.count_nonzero(np.asarray(x) * mask) > 0
    out = capsys.readouterr().out
    assert f"objective {objective}" in out and "pallas (interpreted) " \
        "interleaved" in out
    assert "the shared vector is 96 long" in out
    assert f"x has {traj.meta['x_nnz']} nonzero coordinates of 48" in out
    # off the dense Pallas kernel there is no form; the dual family is svm
    _, _, fori = run_prox_cocoa(ds, p, _DBG, quiet=True, math="fast",
                                pallas=False, l2=l2)
    assert fori.meta["solver_path"]["form"] is None
    assert fori.meta["solver_path"]["objective"] == objective


def test_solver_path_objective_is_svm_for_the_dual_family(tiny_data):
    from cocoa_tpu.data import shard_dataset
    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float32)
    path = resolve_solver_path(ds, 8, math="fast", pallas=True)
    assert path.for_mode("plus", 1.0).objective == "svm"
    assert path.for_mode("prox", 0.0).objective == "lasso"
    assert path.for_mode("prox", 0.5).objective == "elastic_net"
    assert path.form == "interleaved" and "interleaved" in path.describe()
    assert "objective" not in path.describe()
    assert resolve_solver_path(ds, 8, math="fast",
                               pallas=False).form is None


# what the fit alone resolves at the four dense deployments of the benchmark
# (k shards a device, rows a shard, the shared vector, H): the form, and
# the interleaved kernel's ring depth (PR 42: the deepest of 8 / 4 / 2
# whose rows fit, whose loop group stays under 32 chain-steps and whose
# look-ahead is under a 200th of the round; the step
# groups of 2 it replaced are gone with the grid) or the shard-major
# kernel's step group; the budgets are Mosaic's default
# scoped VMEM, kept after step 0 of PR 34 (PERF.md §6)
DENSE_SHAPES = {
    "epsilon": ((8, 50000, 2000, 5000), "interleaved", 4),
    "imagenet_x4": ((2, 4096, 160000, 409), "interleaved", 2),
    "epsilon_lasso": ((8, 256, 400000, 25), "shard_major", 1),
    "one_shard_a_device": ((1, 4096, 160000, 409), "shard_major", 4),
}


@pytest.mark.parametrize("name", list(DENSE_SHAPES))
def test_dense_form_and_group_from_the_fit_alone(name):
    from cocoa_tpu.ops import pallas_sdca

    (k, n_shard, d, h), form, group = DENSE_SHAPES[name]
    assert pallas_sdca.dense_form(k, n_shard, d, 4, h) == form
    picked = (pallas_sdca.pick_interleave(k, n_shard, d, 4, h)
              if form == "interleaved"
              else pallas_sdca.pick_unroll(n_shard, d, 4, h))
    assert picked == group
    if name == "epsilon_lasso":
        # all eight shards' 1.6 MB columns beside each other, the
        # shallowest ring of them: 2.9 x the budget
        assert pallas_sdca.interleave_vmem_estimate(k, n_shard, d, 4, 2) \
            > 2.5 * pallas_sdca.INTERLEAVE_BUDGET
        assert pallas_sdca.vmem_estimate(n_shard, d, 4, 1) \
            <= pallas_sdca.VMEM_BUDGET < pallas_sdca.vmem_estimate(
                n_shard, d, 4, 2)
