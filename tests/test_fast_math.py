"""Fast-math (margins decomposition) and Pallas kernel paths.

The fast inner loop is exactly equal in real arithmetic to the reference
order (x·w_step = margins0 + sig_eff·x·Δw — see ops/local_sdca.mode_factors);
floating point rounds differently, so trajectory equality is asserted loosely
while convergence properties are asserted exactly.  The Pallas kernel (run
in interpreter mode on CPU) must match the XLA fast path to near-machine
precision.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import shard_dataset
from cocoa_tpu.ops.local_sdca import local_sdca, local_sdca_fast
from cocoa_tpu.ops.pallas_sdca import pallas_sdca_round
from cocoa_tpu.parallel import make_mesh
from cocoa_tpu.solvers import run_cocoa
from cocoa_tpu.utils.prng import sample_indices_per_shard


def _params(tiny_data, **kw):
    defaults = dict(n=tiny_data.n, num_rounds=10, local_iters=20, lam=0.01,
                    beta=1.0, gamma=1.0)
    defaults.update(kw)
    return Params(**defaults)


_DBG = DebugParams(debug_iter=-1, seed=0)


@pytest.mark.parametrize("mode,sigma", [("cocoa", 1.0), ("plus", 4.0), ("frozen", 1.0)])
@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_fast_kernel_close_to_exact(tiny_data, mode, sigma, layout):
    ds = shard_dataset(tiny_data, k=1, layout=layout, dtype=jnp.float64)
    shard = {k: v[0] for k, v in ds.shard_arrays().items()}
    rng = np.random.default_rng(1)
    d = tiny_data.num_features
    w = jnp.asarray(rng.normal(size=d) * 0.1)
    alpha = jnp.asarray(np.clip(rng.normal(size=tiny_data.n) * 0.3 + 0.3, 0, 1))
    idxs = jnp.asarray(
        sample_indices_per_shard(7, range(1, 2), 100, [tiny_data.n])[0, 0]
    )
    da_e, dw_e = local_sdca(w, alpha, shard, idxs, 0.01, tiny_data.n,
                            mode=mode, sigma=sigma)
    from cocoa_tpu.ops.rows import shard_margins

    m0 = shard_margins(w, shard)
    da_f, dw_f = local_sdca_fast(m0, alpha, shard, idxs, 0.01, tiny_data.n,
                                 jnp.zeros(d, dtype=jnp.float64),
                                 mode=mode, sigma=sigma)
    np.testing.assert_allclose(np.asarray(da_f), np.asarray(da_e),
                               rtol=1e-8, atol=1e-10)
    np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_e),
                               rtol=1e-8, atol=1e-10)


@pytest.mark.parametrize("mode,sigma", [("cocoa", 1.0), ("plus", 4.0), ("frozen", 1.0)])
def test_pallas_interpret_matches_xla_fast(tiny_data, mode, sigma):
    k = 4
    ds = shard_dataset(tiny_data, k=k, layout="dense", dtype=jnp.float64)
    rng = np.random.default_rng(2)
    d = tiny_data.num_features
    w = jnp.asarray(rng.normal(size=d) * 0.1)
    alpha = jnp.asarray(
        np.clip(rng.normal(size=(k, ds.n_shard)) * 0.3 + 0.3, 0, 1)
    )
    idxs = jnp.asarray(
        sample_indices_per_shard(5, range(1, 2), 30, ds.counts)[:, 0, :]
    )
    dw_p, a_p = pallas_sdca_round(
        w, alpha, ds.X, ds.labels, ds.sq_norms, idxs, 0.01, tiny_data.n,
        mode=mode, sigma=sigma, interpret=True,
    )
    m0 = jnp.einsum("knd,d->kn", ds.X, w)
    dw_sum = jnp.zeros(d, dtype=jnp.float64)
    for s in range(k):
        shard = {kk: v[s] for kk, v in ds.shard_arrays().items()}
        da, dw = local_sdca_fast(
            m0[s], alpha[s], shard, idxs[s], 0.01, tiny_data.n,
            jnp.zeros(d, dtype=jnp.float64), mode=mode, sigma=sigma,
        )
        # in-kernel margins reduce x·w in a different order than the
        # einsum the fast path precomputes — x64 agreement to ~1e-13
        dw_sum = dw_sum + dw
        np.testing.assert_allclose(np.asarray(a_p[s] - alpha[s]),
                                   np.asarray(da), atol=1e-12)
    # the kernel hands back the shards' sum, one row
    np.testing.assert_allclose(np.asarray(dw_p), np.asarray(dw_sum)[None],
                               atol=1e-12)


def _round_matches_fast(tiny_data, k, h, mode, sigma, loss="logistic",
                        lam=0.01, n=None, **kernel):
    """One round of the dense Pallas kernel (f64, interpreted) against
    ``local_sdca_fast`` on every shard: α shard by shard — what still pins
    each chain by itself — and Δw as the shards' sum, the one (1, d) row
    the kernel returns."""
    n = n or tiny_data.n
    step = dict(mode=mode, sigma=sigma, loss=loss,
                smoothing=0.0 if loss == "lasso" else 1.0)
    ds = shard_dataset(tiny_data, k=k, layout="dense", dtype=jnp.float64)
    rng = np.random.default_rng(2)
    d = tiny_data.num_features
    w = jnp.asarray(rng.normal(size=d) * 0.1)
    alpha = jnp.asarray(
        np.clip(rng.normal(size=(k, ds.n_shard)) * 0.3 + 0.3, 0, 1)
    )
    idxs = jnp.asarray(
        sample_indices_per_shard(5, range(1, 2), h, ds.counts)[:, 0, :]
    )
    dw_p, a_p = pallas_sdca_round(
        w, alpha, ds.X, ds.labels, ds.sq_norms, idxs, lam, n,
        interpret=True, **step, **kernel,
    )
    assert np.all(np.isfinite(np.asarray(a_p)))
    fast = jax.jit(jax.vmap(lambda m0, a, shard, ix: local_sdca_fast(
        m0, a, shard, ix, lam, n, jnp.zeros(d, dtype=jnp.float64), **step)))
    da, dw = fast(jnp.einsum("knd,d->kn", ds.X, w), alpha,
                  ds.shard_arrays(), idxs)
    assert dw_p.shape == (1, d) and a_p.shape == alpha.shape
    np.testing.assert_allclose(np.asarray(dw_p),
                               np.asarray(dw.sum(axis=0, keepdims=True)),
                               atol=1e-12)
    np.testing.assert_allclose(np.asarray(a_p - alpha), np.asarray(da),
                               atol=1e-12)
    # every shard stepped (equality is not 0 == 0), and no shard's Δw is
    # the whole sum
    assert float(jnp.min(jnp.max(jnp.abs(da), axis=1))) > 1e-4
    if k > 1:
        assert float(jnp.max(jnp.abs(dw.sum(axis=0) - dw[0]))) > 1e-6


@pytest.mark.parametrize("k", [1, 3, 4])
@pytest.mark.parametrize("mode,sigma", [("plus", 4.0), ("cocoa", 1.0),
                                        ("frozen", 1.0)])
@pytest.mark.parametrize("interleave", [True, False])
def test_pallas_logistic_interpret_matches_xla_fast(tiny_data, interleave,
                                                    mode, sigma, k):
    """The logistic step is solved for the K lockstep chains as one vector,
    a shard a lane (pallas_sdca ``_solve_in_lanes``): K = 3 leaves filler
    lanes beside the chains, K = 1 is one lane whichever kernel runs, and
    ``interleave=False`` is the shard-major kernel's one-lane solve.
    Step groups of 2, as at epsilon."""
    _round_matches_fast(tiny_data, k, 30, mode, sigma,
                        interleave=interleave, unroll=2)


@pytest.mark.parametrize("interleave", [True, False])
def test_pallas_logistic_inert_tail(tiny_data, interleave):
    """``unroll`` ∤ H: the group past H solves its lanes like any other and
    its ``live`` mask drops the result."""
    _round_matches_fast(tiny_data, 3, 31, "plus", 3.0,
                        interleave=interleave, unroll=4)


# mode -> the step's other parameters: the SVM family's two scaling laws
# and the L1 family's prox step (solvers/prox_cocoa.py: n = 1, the lasso
# rule)
_SUMMED = {"plus": dict(sigma=4.0, loss="hinge"),
           "cocoa": dict(sigma=1.0, loss="hinge"),
           "prox": dict(sigma=4.0, loss="lasso", lam=0.05, n=1)}


@pytest.mark.parametrize("mode", list(_SUMMED))
@pytest.mark.parametrize("h", [12, 13])     # groups of 4: exact, and a tail
@pytest.mark.parametrize("k", [1, 2, 4])
@pytest.mark.parametrize("interleave", [True, False])
def test_pallas_hands_back_the_shards_summed_dw(tiny_data, interleave, k, h,
                                                mode):
    """Both forms of the dense kernel add the K shards' Δw into ONE out
    block in their epilogue, under every family's step.
    ``interleave=True`` at K = 1 is a sum of one; h = 13 leaves the last
    group of 4 an inert tail."""
    _round_matches_fast(tiny_data, k, h, mode, **_SUMMED[mode],
                        interleave=interleave, unroll=4)


@pytest.mark.slow
@pytest.mark.parametrize("mode,sigma", [("cocoa", 1.0), ("plus", 4.0), ("frozen", 1.0)])
def test_pallas_sparse_interpret_matches_xla_fast(tiny_data, mode, sigma):
    """The sparse (padded-CSR) kernel — in-kernel margins, SMEM feature
    addressing, lane-blocked w/Δw — must match the XLA fast path."""
    from cocoa_tpu.ops.pallas_sparse import pallas_sparse_sdca_round
    from cocoa_tpu.ops.rows import shard_margins

    k = 4
    ds = shard_dataset(tiny_data, k=k, layout="sparse", dtype=jnp.float64)
    rng = np.random.default_rng(4)
    d = ds.num_features
    w = jnp.asarray(rng.normal(size=d) * 0.1)
    alpha = jnp.asarray(
        np.clip(rng.normal(size=(k, ds.n_shard)) * 0.3 + 0.3, 0, 1)
    )
    idxs = jnp.asarray(
        sample_indices_per_shard(6, range(1, 2), 30, ds.counts)[:, 0, :]
    )
    dw_p, a_p = pallas_sparse_sdca_round(
        w, alpha, ds.sp_indices, ds.sp_values, ds.labels, ds.sq_norms,
        idxs, 0.01, tiny_data.n, mode=mode, sigma=sigma, interpret=True,
    )
    for s in range(k):
        shard = {kk: v[s] for kk, v in ds.shard_arrays().items()}
        m0 = shard_margins(w, shard)
        da, dw = local_sdca_fast(
            m0, alpha[s], shard, idxs[s], 0.01, tiny_data.n,
            jnp.zeros(d, dtype=jnp.float64), mode=mode, sigma=sigma,
        )
        np.testing.assert_allclose(np.asarray(dw_p[s]), np.asarray(dw),
                                   atol=1e-13)
        np.testing.assert_allclose(np.asarray(a_p[s] - alpha[s]),
                                   np.asarray(da), atol=1e-13)


@pytest.mark.slow
def test_pallas_sparse_solver_end_to_end_interpret(tiny_data):
    """Full CoCoA+ run through the sparse Pallas kernel (interpret mode,
    chunked driver) tracks the fori_loop fast path."""
    ds = shard_dataset(tiny_data, k=4, layout="sparse", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=15, local_iters=20)
    dbg = DebugParams(debug_iter=15, seed=0)
    w_f, a_f, traj_f = run_cocoa(ds, p, dbg, plus=True, quiet=True,
                                 math="fast", pallas=False, scan_chunk=5)
    w_p, a_p, traj_p = run_cocoa(ds, p, dbg, plus=True, quiet=True,
                                 math="fast", pallas=True, scan_chunk=5)
    np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_f), atol=1e-10)
    np.testing.assert_allclose(np.asarray(a_p), np.asarray(a_f), atol=1e-10)


@pytest.mark.slow
@pytest.mark.parametrize("unroll", [1, 2, 4, 8])
def test_pallas_unroll_invariant(tiny_data, unroll):
    """The step-group size S is a pure DMA-batching knob: every S must
    produce the same (dw, alpha) to machine precision — same op sequence,
    XLA may fuse the unrolled body differently — including S ∤ H (the
    clamped inert tail)."""
    k = 2
    ds = shard_dataset(tiny_data, k=k, layout="dense", dtype=jnp.float64)
    rng = np.random.default_rng(3)
    d = tiny_data.num_features
    w = jnp.asarray(rng.normal(size=d) * 0.1)
    alpha = jnp.asarray(
        np.clip(rng.normal(size=(k, ds.n_shard)) * 0.3 + 0.3, 0, 1)
    )
    h = 27  # not divisible by any S > 1
    idxs = jnp.asarray(
        sample_indices_per_shard(9, range(1, 2), h, ds.counts)[:, 0, :]
    )
    kw = dict(mode="plus", sigma=2.0, interpret=True)
    dw_1, a_1 = pallas_sdca_round(
        w, alpha, ds.X, ds.labels, ds.sq_norms, idxs, 0.01, tiny_data.n,
        unroll=1, **kw,
    )
    dw_s, a_s = pallas_sdca_round(
        w, alpha, ds.X, ds.labels, ds.sq_norms, idxs, 0.01, tiny_data.n,
        unroll=unroll, **kw,
    )
    np.testing.assert_allclose(np.asarray(dw_s), np.asarray(dw_1),
                               rtol=0, atol=1e-13)
    np.testing.assert_allclose(np.asarray(a_s), np.asarray(a_1),
                               rtol=0, atol=1e-13)


@pytest.mark.parametrize("plus", [True, False])
def test_fast_solver_converges_like_exact(tiny_data, plus):
    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=40, local_iters=30)
    dbg = DebugParams(debug_iter=40, seed=0)
    _, _, traj_e = run_cocoa(ds, p, dbg, plus=plus, quiet=True)
    _, _, traj_f = run_cocoa(ds, p, dbg, plus=plus, quiet=True,
                             math="fast", pallas=False)
    gap_e = traj_e.records[-1].gap
    gap_f = traj_f.records[-1].gap
    assert gap_f == pytest.approx(gap_e, rel=1e-3)
    assert gap_f >= -1e-12


@pytest.mark.slow
def test_pallas_solver_end_to_end_interpret(tiny_data):
    """Full CoCoA+ run through the Pallas kernel (interpret mode, chunked
    driver, single-chip path) tracks the exact solver."""
    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=20, local_iters=20)
    dbg = DebugParams(debug_iter=20, seed=0)
    _, _, traj_e = run_cocoa(ds, p, dbg, plus=True, quiet=True)
    _, _, traj_p = run_cocoa(ds, p, dbg, plus=True, quiet=True,
                             math="fast", pallas=True, scan_chunk=5)
    assert traj_p.records[-1].gap == pytest.approx(traj_e.records[-1].gap,
                                                   rel=1e-3)


@pytest.mark.parametrize("scan", [0, 4])
def test_fast_math_on_mesh_without_pallas(tiny_data, scan):
    """math='fast' must work under shard_map on a real mesh (regression:
    the dw carry needs varying provenance), per-round and chunked."""
    k = 4
    mesh = make_mesh(k)
    ds_m = shard_dataset(tiny_data, k=k, layout="dense", dtype=jnp.float64, mesh=mesh)
    ds_l = shard_dataset(tiny_data, k=k, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=8)
    dbg = DebugParams(debug_iter=8, seed=0)
    _, _, tm = run_cocoa(ds_m, p, dbg, plus=True, mesh=mesh, quiet=True,
                         math="fast", pallas=False, scan_chunk=scan)
    _, _, tl = run_cocoa(ds_l, p, dbg, plus=True, quiet=True,
                         math="fast", pallas=False, scan_chunk=scan)
    assert tm.records[-1].gap == pytest.approx(tl.records[-1].gap, abs=1e-12)


@pytest.mark.slow
def test_pallas_mesh_per_round_driver_reroutes(tiny_data):
    """pallas on a mesh with scan_chunk=0 must not crash (regression: it is
    rerouted through the chunked driver)."""
    k = 4
    mesh = make_mesh(k)
    ds = shard_dataset(tiny_data, k=k, layout="dense", dtype=jnp.float64, mesh=mesh)
    p = _params(tiny_data, num_rounds=4)
    _, _, traj = run_cocoa(ds, p, DebugParams(debug_iter=4, seed=0), plus=True,
                           mesh=mesh, quiet=True, math="fast", pallas=True)
    assert traj.records[-1].gap is not None


def test_math_flag_validated(tiny_data):
    ds = shard_dataset(tiny_data, k=2, layout="dense", dtype=jnp.float64)
    with pytest.raises(ValueError, match="math"):
        run_cocoa(ds, _params(tiny_data), _DBG, plus=True, quiet=True,
                  math="fas")


@pytest.mark.slow
def test_pallas_mesh_equals_local(tiny_data):
    """Pallas kernel inside shard_map (4-device mesh) == single-chip path."""
    k = 4
    mesh = make_mesh(k)
    ds_m = shard_dataset(tiny_data, k=k, layout="dense", dtype=jnp.float64, mesh=mesh)
    ds_l = shard_dataset(tiny_data, k=k, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=8)
    dbg = DebugParams(debug_iter=8, seed=0)
    _, _, tm = run_cocoa(ds_m, p, dbg, plus=True, mesh=mesh, quiet=True,
                         math="fast", pallas=True, scan_chunk=4)
    _, _, tl = run_cocoa(ds_l, p, dbg, plus=True, quiet=True,
                         math="fast", pallas=True, scan_chunk=4)
    assert tm.records[-1].gap == pytest.approx(tl.records[-1].gap, abs=1e-12)


def test_pallas_requires_fast_math(tiny_data):
    ds = shard_dataset(tiny_data, k=2, layout="dense", dtype=jnp.float64)
    with pytest.raises(ValueError, match="fast"):
        run_cocoa(ds, _params(tiny_data), _DBG, plus=True, quiet=True,
                  math="exact", pallas=True)
