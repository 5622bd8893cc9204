"""Block-coordinate inner solver (``--blockSize``, VERDICT r1 item 2).

``local_sdca_block`` consumes the SAME sampled index stream as the
sequential fast path and is identical to it in real arithmetic (the running
Δw dot is replaced by cached block Gram contributions — see the kernel
docstring), so the contract tested here is strict trajectory equality to fp
tolerance against ``local_sdca_fast`` / the literal oracle — not just
"convergence parity".  Coverage: all four modes, both layouts, H not a
multiple of B (masked tail), tiny shards (duplicate draws inside a block),
off-fixed-point scaling parameters, the device-loop and mesh paths, and the
CLI flag gating.
"""

import jax.numpy as jnp
import numpy as np
import pytest

import oracle
from conftest import SMALL_TRAIN  # noqa: E402
from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import shard_dataset, split_sizes
from cocoa_tpu.ops.local_sdca import local_sdca_block, local_sdca_fast
from cocoa_tpu.ops.rows import shard_margins
from cocoa_tpu.solvers import run_cocoa, run_minibatch_cd
from cocoa_tpu.utils.prng import sample_indices, sample_indices_per_shard

K = 4
H = 20


def _params(tiny_data, **kw):
    defaults = dict(n=tiny_data.n, num_rounds=10, local_iters=H, lam=0.01,
                    beta=1.0, gamma=1.0)
    defaults.update(kw)
    return Params(**defaults)


_DBG = DebugParams(debug_iter=-1, seed=0)


@pytest.mark.parametrize("mode,sigma", [("cocoa", 1.0), ("plus", 4.0),
                                        ("frozen", 1.0)])
@pytest.mark.parametrize("layout", ["dense", "sparse"])
@pytest.mark.parametrize("block", [1, 8, 37])
def test_block_kernel_matches_fast(tiny_data, mode, sigma, layout, block):
    """Kernel-level equality vs the sequential fast path.  H=37 draws from a
    96-row single shard: duplicate indices inside a block are certain at
    B=37, and B=8 exercises the masked tail (37 = 4·8 + 5)."""
    ds = shard_dataset(tiny_data, k=1, layout=layout, dtype=jnp.float64)
    shard = {k: v[0] for k, v in ds.shard_arrays().items()}
    rng = np.random.default_rng(11)
    d = tiny_data.num_features
    w = jnp.asarray(rng.normal(size=d) * 0.1)
    alpha = jnp.asarray(np.clip(rng.normal(size=tiny_data.n) * 0.3 + 0.3, 0, 1))
    idxs = jnp.asarray(
        sample_indices_per_shard(7, range(1, 2), 37, [tiny_data.n])[0, 0]
    )
    m0 = shard_margins(w, shard)
    da_f, dw_f = local_sdca_fast(m0, alpha, shard, idxs, 0.01, tiny_data.n,
                                 jnp.zeros(d), mode=mode, sigma=sigma)
    da_b, dw_b = local_sdca_block(m0, alpha, shard, idxs, 0.01, tiny_data.n,
                                  jnp.zeros(d), mode=mode, sigma=sigma,
                                  block=block)
    np.testing.assert_allclose(np.asarray(da_b), np.asarray(da_f),
                               rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(np.asarray(dw_b), np.asarray(dw_f),
                               rtol=1e-9, atol=1e-12)


def test_block_duplicates_in_block_exact(tiny_data):
    """A pathological stream — every draw the same index — makes the Gram
    self-coupling term carry the whole sequential recurrence."""
    ds = shard_dataset(tiny_data, k=1, layout="dense", dtype=jnp.float64)
    shard = {k: v[0] for k, v in ds.shard_arrays().items()}
    d = tiny_data.num_features
    w = jnp.zeros(d)
    alpha = jnp.zeros(tiny_data.n)
    idxs = jnp.full(16, 3, dtype=jnp.int32)
    m0 = shard_margins(w, shard)
    da_f, dw_f = local_sdca_fast(m0, alpha, shard, idxs, 0.01, tiny_data.n,
                                 jnp.zeros(d), mode="plus", sigma=4.0)
    da_b, dw_b = local_sdca_block(m0, alpha, shard, idxs, 0.01, tiny_data.n,
                                  jnp.zeros(d), mode="plus", sigma=4.0,
                                  block=16)
    np.testing.assert_allclose(np.asarray(da_b), np.asarray(da_f), atol=1e-12)
    np.testing.assert_allclose(np.asarray(dw_b), np.asarray(dw_f), atol=1e-12)


def _shards(tiny_data):
    X = tiny_data.to_dense()
    y = tiny_data.labels
    sizes = split_sizes(tiny_data.n, K)
    offs = np.concatenate([[0], np.cumsum(sizes)])
    return [(X[offs[i]:offs[i + 1]], y[offs[i]:offs[i + 1]])
            for i in range(K)]


def _sample_fn(seed, t, n_local):
    return sample_indices(seed, range(t, t + 1), H, n_local)[0]


@pytest.mark.parametrize("plus,beta,gamma", [
    (True, 1.0, 0.5),    # CoCoA+ off the γ=1 fixed point
    (False, 2.0, 1.0),   # CoCoA averaging off the β=1 fixed point
])
def test_block_solver_matches_oracle(tiny_data, plus, beta, gamma):
    """Full-trajectory oracle match through run_cocoa with block_size — the
    same contract the fast path carries, at off-fixed-point scalings."""
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=5, beta=beta, gamma=gamma)
    w, alpha, _ = run_cocoa(ds, p, _DBG, plus=plus, quiet=True,
                            math="fast", block_size=8)
    w_o, alphas_o = oracle.cocoa_outer(
        _shards(tiny_data), np.zeros(tiny_data.num_features),
        p.lam, p.n, p.num_rounds, H, beta, gamma, 0, plus, _sample_fn,
    )
    np.testing.assert_allclose(np.asarray(w), w_o, rtol=1e-8, atol=1e-10)
    for s in range(K):
        np.testing.assert_allclose(
            np.asarray(alpha[s, : len(alphas_o[s])]), alphas_o[s],
            rtol=1e-8, atol=1e-10,
        )


def test_block_minibatch_cd_matches_plain(tiny_data):
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=4, beta=0.5)
    w0, a0, _ = run_minibatch_cd(ds, p, _DBG, quiet=True, math="fast")
    w1, a1, _ = run_minibatch_cd(ds, p, _DBG, quiet=True, math="fast",
                                 block_size=8)
    np.testing.assert_allclose(np.asarray(w1), np.asarray(w0),
                               rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(np.asarray(a1), np.asarray(a0),
                               rtol=1e-9, atol=1e-12)


def test_block_device_loop_and_mesh_match_host(tiny_data):
    """The block kernel rides the chunked/device-loop drivers and the
    shard_map mesh path unchanged."""
    from cocoa_tpu.parallel import make_mesh

    p = _params(tiny_data, num_rounds=10)
    dbg = DebugParams(debug_iter=5, seed=0)
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    w_h, _, traj_h = run_cocoa(ds, p, dbg, plus=True, quiet=True,
                               math="fast", block_size=8)
    w_d, _, traj_d = run_cocoa(ds, p, dbg, plus=True, quiet=True,
                               math="fast", block_size=8, device_loop=True)
    np.testing.assert_allclose(np.asarray(w_d), np.asarray(w_h), atol=1e-12)
    assert [r.gap for r in traj_d.records] == pytest.approx(
        [r.gap for r in traj_h.records], rel=1e-10)

    mesh = make_mesh(K)
    ds_m = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64,
                         mesh=mesh)
    w_m, _, _ = run_cocoa(ds_m, p, dbg, plus=True, quiet=True,
                          math="fast", block_size=8, mesh=mesh,
                          device_loop=True)
    np.testing.assert_allclose(np.asarray(w_m), np.asarray(w_h),
                               rtol=1e-9, atol=1e-11)


def test_block_sparse_solver_end_to_end(tiny_data):
    ds = shard_dataset(tiny_data, k=K, layout="sparse", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=10)
    dbg = DebugParams(debug_iter=10, seed=0)
    w_f, _, traj_f = run_cocoa(ds, p, dbg, plus=True, quiet=True,
                               math="fast", pallas=False)
    w_b, _, traj_b = run_cocoa(ds, p, dbg, plus=True, quiet=True,
                               math="fast", block_size=8)
    np.testing.assert_allclose(np.asarray(w_b), np.asarray(w_f),
                               rtol=1e-9, atol=1e-12)
    assert traj_b.records[-1].gap == pytest.approx(traj_f.records[-1].gap,
                                                   rel=1e-8)


def test_block_prox_lasso_matches_plain(tiny_data):
    """The prox mode shares the σ′-scaled read structure; the block kernel
    must carry it unchanged (ProxCoCoA+ lasso end-to-end)."""
    from cocoa_tpu.data.columns import shard_columns
    from cocoa_tpu.solvers import run_prox_cocoa

    ds_c = shard_columns(tiny_data, K, dtype=jnp.float64)
    d = tiny_data.num_features
    lam = 0.1 * float(np.max(np.abs(tiny_data.to_dense().T @ tiny_data.labels)))
    p = Params(n=d, num_rounds=10, local_iters=4, lam=lam, loss="lasso",
               smoothing=0.0)
    dbg = DebugParams(debug_iter=10, seed=0)
    x0, r0, traj0 = run_prox_cocoa(ds_c, p, dbg, quiet=True, math="fast")
    x1, r1, traj1 = run_prox_cocoa(ds_c, p, dbg, quiet=True, math="fast",
                                   block_size=4)
    np.testing.assert_allclose(np.asarray(x1), np.asarray(x0),
                               rtol=1e-9, atol=1e-12)
    assert traj1.records[-1].gap == pytest.approx(traj0.records[-1].gap,
                                                  rel=1e-8)


@pytest.mark.parametrize("mode,sigma", [("cocoa", 1.0), ("plus", 4.0),
                                        ("frozen", 1.0)])
@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_batched_pallas_chain_matches_fast(tiny_data, mode, sigma, layout):
    """The TPU hot path — local_sdca_block_batched with the lockstep Pallas
    chain kernel (interpret mode on CPU) — must match K independent
    sequential fast-path runs: in-block margins, Gram coupling, additive α
    scatter, masked tail (H=37 vs B=128), duplicate draws, and a zero-norm
    row (the qii == 0 branch the compressed hinge chain special-cases)."""
    from cocoa_tpu.ops.local_sdca import local_sdca_block_batched

    ds = shard_dataset(tiny_data, k=K, layout=layout, dtype=jnp.float64)
    sa = ds.shard_arrays()
    rng = np.random.default_rng(5)
    d = tiny_data.num_features
    w = jnp.asarray(rng.normal(size=d) * 0.1)
    alpha = jnp.asarray(
        np.clip(rng.normal(size=(K, ds.n_shard)) * 0.3 + 0.3, 0, 1)
    )
    idxs = jnp.asarray(
        sample_indices_per_shard(7, range(1, 2), 37, ds.counts)[:, 0, :]
    )
    da_b, dw_b = local_sdca_block_batched(
        w, alpha, sa, idxs, 0.01, tiny_data.n, mode=mode, sigma=sigma,
        block=128, interpret=True,
    )
    for s in range(K):
        shard = {kk: v[s] for kk, v in sa.items()}
        m0 = shard_margins(w, shard)
        da_f, dw_f = local_sdca_fast(
            m0, alpha[s], shard, idxs[s], 0.01, tiny_data.n,
            jnp.zeros(d), mode=mode, sigma=sigma,
        )
        np.testing.assert_allclose(np.asarray(da_b[s]), np.asarray(da_f),
                                   rtol=1e-9, atol=1e-12)
        np.testing.assert_allclose(np.asarray(dw_b[s]), np.asarray(dw_f),
                                   rtol=1e-9, atol=1e-12)


@pytest.mark.parametrize("loss,smoothing", [("smooth_hinge", 0.5),
                                            ("logistic", 1.0)])
def test_batched_chain_generic_losses(tiny_data, loss, smoothing):
    """The non-hinge losses ride the chain kernel's generic branch (no
    algebraic collapse; losses.alpha_step runs on (K, 1) columns in the
    chain) — must match the sequential fast path."""
    from cocoa_tpu.ops.local_sdca import local_sdca_block_batched

    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    sa = ds.shard_arrays()
    rng = np.random.default_rng(9)
    d = tiny_data.num_features
    w = jnp.asarray(rng.normal(size=d) * 0.1)
    alpha = jnp.asarray(
        np.clip(rng.normal(size=(K, ds.n_shard)) * 0.3 + 0.3, 0.01, 0.99)
    )
    idxs = jnp.asarray(
        sample_indices_per_shard(7, range(1, 2), 37, ds.counts)[:, 0, :]
    )
    da_b, dw_b = local_sdca_block_batched(
        w, alpha, sa, idxs, 0.01, tiny_data.n, mode="plus", sigma=4.0,
        loss=loss, smoothing=smoothing, block=128, interpret=True,
    )
    for s in range(K):
        shard = {kk: v[s] for kk, v in sa.items()}
        m0 = shard_margins(w, shard)
        da_f, dw_f = local_sdca_fast(
            m0, alpha[s], shard, idxs[s], 0.01, tiny_data.n,
            jnp.zeros(d), mode="plus", sigma=4.0, loss=loss,
            smoothing=smoothing,
        )
        np.testing.assert_allclose(np.asarray(da_b[s]), np.asarray(da_f),
                                   rtol=1e-8, atol=1e-10)
        np.testing.assert_allclose(np.asarray(dw_b[s]), np.asarray(dw_f),
                                   rtol=1e-8, atol=1e-10)


def test_batched_chain_zero_norm_row(tiny_data):
    """qii == 0: the compressed hinge chain must reproduce alpha_step's
    projected-gradient outcome (α → 1) for a zero row in the stream."""
    from cocoa_tpu.data.libsvm import LibsvmData
    from cocoa_tpu.ops.local_sdca import local_sdca_block_batched

    rng = np.random.default_rng(3)
    n, d = 64, 16
    X = rng.normal(size=(n, d))
    X[5] = 0.0
    y = np.where(rng.random(n) > 0.5, 1.0, -1.0)
    indptr = np.arange(0, (n + 1) * d, d, dtype=np.int64)
    data = LibsvmData(labels=y, indptr=indptr,
                      indices=np.tile(np.arange(d, dtype=np.int32), n),
                      values=X.reshape(-1), num_features=d)
    ds = shard_dataset(data, k=1, layout="dense", dtype=jnp.float64)
    sa = ds.shard_arrays()
    w = jnp.zeros(d)
    alpha = jnp.zeros((1, ds.n_shard))
    idxs = jnp.asarray([[5, 2, 5, 9]], dtype=jnp.int32)
    da_b, dw_b = local_sdca_block_batched(
        w, alpha, sa, idxs, 0.01, n, mode="plus", sigma=2.0,
        block=128, interpret=True,
    )
    shard = {kk: v[0] for kk, v in sa.items()}
    da_f, dw_f = local_sdca_fast(
        shard_margins(w, shard), alpha[0], shard, idxs[0], 0.01, n,
        jnp.zeros(d), mode="plus", sigma=2.0,
    )
    assert float(da_b[0][5]) == 1.0
    np.testing.assert_allclose(np.asarray(da_b[0]), np.asarray(da_f),
                               atol=1e-12)
    np.testing.assert_allclose(np.asarray(dw_b[0]), np.asarray(dw_f),
                               atol=1e-12)


def test_block_pallas_chain_through_driver(tiny_data):
    """Driver-integrated Pallas chain (interpret on CPU): the chunked
    per_round_batched routing, scan_chunk forcing, and additive α scatter
    must reproduce the XLA-chain solver trajectory."""
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data, num_rounds=4)
    dbg = DebugParams(debug_iter=4, seed=0)
    w_x, a_x, traj_x = run_cocoa(ds, p, dbg, plus=True, quiet=True,
                                 math="fast", block_size=128)
    w_p, a_p, traj_p = run_cocoa(ds, p, dbg, plus=True, quiet=True,
                                 math="fast", block_size=128,
                                 block_chain="pallas_interpret")
    np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_x),
                               rtol=1e-9, atol=1e-12)
    np.testing.assert_allclose(np.asarray(a_p), np.asarray(a_x),
                               rtol=1e-9, atol=1e-12)


def test_block_pallas_chain_mesh_through_driver(tiny_data):
    """Same, on the shard_map mesh path (per_shard routing)."""
    from cocoa_tpu.parallel import make_mesh

    mesh = make_mesh(K)
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64,
                       mesh=mesh)
    p = _params(tiny_data, num_rounds=4)
    dbg = DebugParams(debug_iter=4, seed=0)
    ds_l = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    w_x, _, _ = run_cocoa(ds_l, p, dbg, plus=True, quiet=True,
                          math="fast", block_size=128)
    w_p, _, _ = run_cocoa(ds, p, dbg, plus=True, quiet=True,
                          math="fast", block_size=128, mesh=mesh,
                          block_chain="pallas_interpret")
    np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_x),
                               rtol=1e-9, atol=1e-12)


def test_block_chain_rejects_fp_mesh(tiny_data):
    """The Pallas block chain assumes the full feature axis per device —
    an fp mesh must be rejected exactly like the sequential Pallas path."""
    from cocoa_tpu.parallel import make_mesh

    mesh = make_mesh(4, fp=2)
    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=jnp.float64,
                       mesh=mesh)
    p = _params(tiny_data)
    with pytest.raises(ValueError, match="feature-parallel"):
        run_cocoa(ds, p, _DBG, plus=True, quiet=True, math="fast",
                  block_size=128, mesh=mesh, block_chain="pallas_interpret")


def test_chain_vmem_fit_guard():
    """Auto selection must fall back to the XLA chain when the kernel's
    VMEM working set cannot fit (it crashes Mosaic rather than degrading)."""
    from cocoa_tpu.ops.pallas_chain import chain_fits

    assert chain_fits(8, 256, 4)          # the benchmark config
    assert not chain_fits(16, 512, 4)     # 33 MB gq >> 16 MB VMEM


def test_block_requires_fast_math(tiny_data):
    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    p = _params(tiny_data)
    with pytest.raises(ValueError, match="math='fast'"):
        run_cocoa(ds, p, _DBG, plus=True, quiet=True, math="exact",
                  block_size=8)
    with pytest.raises(ValueError, match="Pallas"):
        run_cocoa(ds, p, _DBG, plus=True, quiet=True, math="fast",
                  pallas=True, block_size=8)


def test_cli_block_size_flag(tmp_path, capsys):
    """--blockSize runs the menu through the block kernel and is rejected
    without --math=fast."""
    from cocoa_tpu import cli

    rc = cli.main([
        f"--trainFile={SMALL_TRAIN}",
        "--numFeatures=9947", "--numSplits=4", "--numRounds=5",
        "--localIterFrac=0.05", "--lambda=.001", "--justCoCoA=true",
        "--debugIter=5", "--math=fast", "--blockSize=8", "--mesh=1",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "CoCoA+" in out

    rc = cli.main([
        f"--trainFile={SMALL_TRAIN}",
        "--numFeatures=9947", "--blockSize=8",
    ])
    assert rc == 2
    assert "--math=fast" in capsys.readouterr().err


@pytest.mark.parametrize("mode,sigma", [("cocoa", 1.0), ("plus", 4.0),
                                        ("frozen", 1.0)])
@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_fused_block_kernel_matches_fast(tiny_data, mode, sigma, layout):
    """The FUSED per-block kernel (ops/pallas_chain.fused_block — in-kernel
    Gram, margins, equality tile, chain, and Δw update) is the f32
    production path; the float64 parity tests above exercise only the
    legacy split path (fused_fits requires itemsize 4).  This f32
    interpret-mode run must take the fused branch and match the sequential
    fast path to f32 tolerance."""
    from cocoa_tpu.ops.local_sdca import local_sdca_block_batched
    from cocoa_tpu.ops.pallas_chain import fused_fits

    ds = shard_dataset(tiny_data, k=K, layout=layout, dtype=jnp.float32)
    sa = ds.shard_arrays()
    d = tiny_data.num_features
    assert fused_fits(K, 128, d, 4, ds.n_shard), \
        "test config must exercise the fused branch"
    rng = np.random.default_rng(5)
    w = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    alpha = jnp.asarray(
        np.clip(rng.normal(size=(K, ds.n_shard)) * 0.3 + 0.3, 0, 1),
        jnp.float32,
    )
    idxs = jnp.asarray(
        sample_indices_per_shard(7, range(1, 2), 37, ds.counts)[:, 0, :]
    )
    da_b, dw_b = local_sdca_block_batched(
        w, alpha, sa, idxs, 0.01, tiny_data.n, mode=mode, sigma=sigma,
        block=128, interpret=True,
    )
    for s in range(K):
        shard = {kk: v[s] for kk, v in sa.items()}
        m0 = shard_margins(w, shard)
        da_f, dw_f = local_sdca_fast(
            m0, alpha[s], shard, idxs[s], 0.01, tiny_data.n,
            jnp.zeros(d, jnp.float32), mode=mode, sigma=sigma,
        )
        np.testing.assert_allclose(np.asarray(da_b[s]), np.asarray(da_f),
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dw_b[s]), np.asarray(dw_f),
                                   rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("loss,smoothing", [("smooth_hinge", 0.5),
                                            ("logistic", 1.0)])
def test_fused_block_kernel_generic_losses(tiny_data, loss, smoothing):
    """The fused kernel's non-hinge branch (losses.alpha_step on (K, 1)
    columns inside the chain) — the float64 generic-loss tests above only
    pin the legacy split path."""
    from cocoa_tpu.ops.local_sdca import local_sdca_block_batched
    from cocoa_tpu.ops.pallas_chain import fused_fits

    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float32)
    sa = ds.shard_arrays()
    d = tiny_data.num_features
    assert fused_fits(K, 128, d, 4, ds.n_shard)
    rng = np.random.default_rng(9)
    w = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    alpha = jnp.asarray(
        np.clip(rng.normal(size=(K, ds.n_shard)) * 0.3 + 0.3, 0, 1),
        jnp.float32,
    )
    idxs = jnp.asarray(
        sample_indices_per_shard(3, range(1, 2), 37, ds.counts)[:, 0, :]
    )
    da_b, dw_b = local_sdca_block_batched(
        w, alpha, sa, idxs, 0.01, tiny_data.n, mode="plus", sigma=4.0,
        loss=loss, smoothing=smoothing, block=128, interpret=True,
    )
    for s in range(K):
        shard = {kk: v[s] for kk, v in sa.items()}
        m0 = shard_margins(w, shard)
        da_f, dw_f = local_sdca_fast(
            m0, alpha[s], shard, idxs[s], 0.01, tiny_data.n,
            jnp.zeros(d, jnp.float32), mode="plus", sigma=4.0,
            loss=loss, smoothing=smoothing,
        )
        np.testing.assert_allclose(np.asarray(da_b[s]), np.asarray(da_f),
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(dw_b[s]), np.asarray(dw_f),
                                   rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("mode,sigma", [("cocoa", 1.0), ("plus", 4.0),
                                        ("frozen", 1.0)])
@pytest.mark.parametrize("h", [20, 200])
def test_batched_chain_distinct_matches_per_block(tiny_data, mode, sigma, h):
    """``distinct=True`` (the permuted-mode one-scatter-per-round α update
    — round 5's glue elimination) must be BIT-identical to the per-block
    path when the round's indices really are pairwise distinct per shard:
    the hoisted α₀ gather reads values no earlier block of the round could
    have touched, and each coordinate receives exactly one add.  h=20 is
    the single-block case (masked tail); h=200 > B=128 spans TWO blocks —
    the only case where the distinct path's cross-block structure (hoisted
    α₀ for block 2, deltas-as-scan-outputs ordering, the single post-scan
    scatter) differs from the per-block path at all."""
    from cocoa_tpu.data.synth import synth_dense
    from cocoa_tpu.ops.local_sdca import local_sdca_block_batched
    from cocoa_tpu.ops.pallas_chain import fused_fits

    k = 2
    if h > 20:
        # cross-block coverage needs shards with >= h rows (distinct draws)
        data = synth_dense(640, 32, seed=3)
    else:
        data = tiny_data
    # f32: the distinct branch lives on the FUSED path only, and fused_fits
    # requires itemsize 4 — float64 would silently take the split fallback
    # where distinct is a no-op and this test would compare the per-block
    # path against itself (caught in round-5 review)
    ds = shard_dataset(data, k=k, layout="dense", dtype=jnp.float32)
    sa = ds.shard_arrays()
    d = data.num_features
    assert fused_fits(k, 128, d, 4, ds.n_shard), \
        "test config must exercise the fused branch"
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    alpha = jnp.asarray(
        np.clip(rng.normal(size=(k, ds.n_shard)) * 0.3 + 0.3, 0, 1),
        jnp.float32,
    )
    # pairwise-distinct draws: a fresh permutation prefix per shard
    idxs = jnp.asarray(np.stack([
        rng.permutation(int(c))[:h] for c in ds.counts
    ]).astype(np.int32))
    kw = dict(mode=mode, sigma=sigma, block=128, interpret=True)
    da_p, dw_p = local_sdca_block_batched(
        w, alpha, sa, idxs, 0.01, data.n, **kw)
    da_d, dw_d = local_sdca_block_batched(
        w, alpha, sa, idxs, 0.01, data.n, distinct=True, **kw)
    # bit-identity, not tolerance: same gathered values (gather commutes
    # with the elementwise qf scale), one add per coordinate either way
    np.testing.assert_array_equal(np.asarray(da_d), np.asarray(da_p))
    np.testing.assert_array_equal(np.asarray(dw_d), np.asarray(dw_p))


@pytest.mark.parametrize("distinct", [False, True])
@pytest.mark.parametrize("mode,sigma", [("cocoa", 1.0), ("plus", 4.0),
                                        ("frozen", 1.0)])
def test_pipelined_fused_matches_serial_bit_exact(mode, sigma, distinct):
    """The two-phase software-pipelined block scan (row tile for block
    b+1 gathered during block b's chain kernel, riding the scan carry)
    must be BIT-identical to the serial schedule: the prefetch reorders
    memory traffic, never math — every kernel invocation consumes a tile
    gathered from the same indices by the same gather op.  h=200 > B=128
    spans two blocks, the only case where the pipeline differs from the
    serial scan at all; f32 so the fused branch actually runs."""
    from cocoa_tpu.data.synth import synth_dense
    from cocoa_tpu.ops.local_sdca import local_sdca_block_batched
    from cocoa_tpu.ops.pallas_chain import fused_fits

    k, h = 2, 200
    data = synth_dense(640, 32, seed=3)
    ds = shard_dataset(data, k=k, layout="dense", dtype=jnp.float32)
    sa = ds.shard_arrays()
    d = data.num_features
    assert fused_fits(k, 128, d, 4, ds.n_shard), \
        "test config must exercise the fused branch"
    rng = np.random.default_rng(11)
    w = jnp.asarray(rng.normal(size=d) * 0.1, jnp.float32)
    alpha = jnp.asarray(
        np.clip(rng.normal(size=(k, ds.n_shard)) * 0.3 + 0.3, 0, 1),
        jnp.float32,
    )
    if distinct:
        # the distinct license requires pairwise-distinct draws per shard
        idxs = jnp.asarray(np.stack([
            rng.permutation(int(c))[:h] for c in ds.counts
        ]).astype(np.int32))
    else:
        idxs = jnp.asarray(
            sample_indices_per_shard(7, range(1, 2), h, ds.counts)[:, 0, :]
        )
    kw = dict(mode=mode, sigma=sigma, block=128, interpret=True,
              distinct=distinct)
    da_s, dw_s = local_sdca_block_batched(
        w, alpha, sa, idxs, 0.01, data.n, pipeline=False, **kw)
    da_p, dw_p = local_sdca_block_batched(
        w, alpha, sa, idxs, 0.01, data.n, pipeline=True, **kw)
    np.testing.assert_array_equal(np.asarray(da_p), np.asarray(da_s))
    np.testing.assert_array_equal(np.asarray(dw_p), np.asarray(dw_s))


def test_pipelined_split_matches_serial_bit_exact(tiny_data):
    """Same schedule contract on the legacy split path (float64 fails
    fused_fits's itemsize gate, so this pins the einsum+chain-kernel
    fallback): the prefetched row tile feeds identical einsums."""
    from cocoa_tpu.ops.local_sdca import local_sdca_block_batched

    ds = shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float64)
    sa = ds.shard_arrays()
    rng = np.random.default_rng(5)
    d = tiny_data.num_features
    w = jnp.asarray(rng.normal(size=d) * 0.1)
    alpha = jnp.asarray(
        np.clip(rng.normal(size=(K, ds.n_shard)) * 0.3 + 0.3, 0, 1)
    )
    idxs = jnp.asarray(
        sample_indices_per_shard(7, range(1, 2), 200, ds.counts)[:, 0, :]
    )  # 200 > B=128: two blocks, so the pipeline actually differs
    kw = dict(mode="plus", sigma=4.0, block=128, interpret=True)
    da_s, dw_s = local_sdca_block_batched(
        w, alpha, sa, idxs, 0.01, tiny_data.n, pipeline=False, **kw)
    da_p, dw_p = local_sdca_block_batched(
        w, alpha, sa, idxs, 0.01, tiny_data.n, pipeline=True, **kw)
    np.testing.assert_array_equal(np.asarray(da_p), np.asarray(da_s))
    np.testing.assert_array_equal(np.asarray(dw_p), np.asarray(dw_s))


def test_block_distinct_through_driver_permuted(tiny_data, monkeypatch):
    """End-to-end: the driver auto-enables the distinct α update for
    permuted sampling exactly when counts % H == 0 (observed via a spy on
    the kernel call — f32 so the fused path actually runs; a float64 run
    would silently take the split fallback where distinct is a no-op),
    and both selections match the no-block fast path on the same permuted
    index stream."""
    # the package re-exports a FUNCTION named local_sdca that shadows the
    # submodule attribute (import ... as resolves via getattr); take the
    # module straight from sys.modules
    import sys as _sys

    import cocoa_tpu.ops.local_sdca  # noqa: F401  (ensure imported)
    from cocoa_tpu.solvers import run_cocoa

    ls_mod = _sys.modules["cocoa_tpu.ops.local_sdca"]
    seen = []
    real = ls_mod.local_sdca_block_batched

    def spy(*args, **kw):
        seen.append(kw.get("distinct", False))
        return real(*args, **kw)

    monkeypatch.setattr(ls_mod, "local_sdca_block_batched", spy)
    # the spy fires at trace time — drop any cached executables so every
    # config in this test really rebuilds (and re-imports) the kernel
    from cocoa_tpu.solvers import cocoa as cocoa_mod

    cocoa_mod._CHUNK_STEPS.clear()
    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=jnp.float32)
    # counts = 24 per shard; H=8 divides -> distinct ON; H=7 -> OFF
    for h, want in ((8, True), (7, False)):
        seen.clear()
        p = Params(n=tiny_data.n, num_rounds=6, local_iters=h, lam=0.01)
        w_b, a_b, _ = run_cocoa(ds, p, DebugParams(debug_iter=3, seed=0),
                                plus=True, quiet=True, math="fast",
                                rng="permuted", block_size=128,
                                block_chain="pallas_interpret",
                                scan_chunk=2)
        assert seen and all(s == want for s in seen), (h, want, seen)
        # the fast path (no blocks) is the ground truth for the same
        # permuted index stream
        w_f, a_f, _ = run_cocoa(ds, p, DebugParams(debug_iter=3, seed=0),
                                plus=True, quiet=True, math="fast",
                                rng="permuted", scan_chunk=2)
        np.testing.assert_allclose(np.asarray(w_b), np.asarray(w_f),
                                   rtol=2e-4, atol=1e-6)
        np.testing.assert_allclose(np.asarray(a_b), np.asarray(a_f),
                                   rtol=2e-4, atol=1e-6)
