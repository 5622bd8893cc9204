"""Rows kept as a stream through the resolver and the whole driver, in
interpret mode on the CPU at the small size of tests/test_longrows.py (whose
``_data`` makes the rows): what ``SolverPath`` reports, what is refused and
why, and ``run_cocoa`` end to end on the Pallas path against the ``fori``
path."""

import numpy as np
import pytest

from cocoa_tpu.data.sharding import shard_dataset

from test_longrows import K, LAM, LONGEST, _data


@pytest.fixture(scope="module")
def data():
    return _data()


@pytest.fixture(scope="module")
def ds(data):
    return shard_dataset(data, k=K, layout="sparse")


def test_resolver_reports_the_stream(ds):
    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    path = resolve_solver_path(ds, 12, None, math="fast", pallas=True)
    assert (path.inner, path.kernel, path.state, path.storage) == (
        "sequential", "pallas", "vmem", "stream")
    assert path.interpret and path.longest_row == LONGEST
    assert 0.85 <= path.slot_fill <= 1.0    # (the cell's: over 1 / 1.10)
    assert "kept as a stream" in path.describe()
    # the ring's chunk (the kernels' constant) and what it moves
    assert path.chunk_pieces == 8 and 0.15 < path.chunk_fill < 0.4
    assert "fetched 8 pieces a chunk (chunk fill 0." in path.describe()
    # how the chain takes a margin follows the algorithm, which the driver
    # knows: mini-batch CD never reads dw_k, so no v gives it back
    assert path.margin is None and "margin" not in path.describe()
    for mode, want in (("plus", "combined"), ("cocoa", "combined"),
                       ("prox", "combined"), ("frozen", "split")):
        assert path.for_mode(mode).margin == want
        assert f"margin {want}" in path.for_mode(mode).describe()
    auto = resolve_solver_path(ds, 12, None, math="fast")    # a CPU: fori
    assert (auto.kernel, auto.storage) == ("fori", "stream")
    assert auto.for_mode("plus").margin is None
    with pytest.raises(ValueError, match="block"):
        resolve_solver_path(ds, 12, None, math="fast", block_size=128)


@pytest.mark.parametrize("loss", ["hinge", "smooth_hinge", "logistic"])
def test_a_stream_on_the_pallas_path_solves_its_step_on_vectors(ds, loss):
    """``step_solve`` on rows kept as a stream: ``vector`` on the Pallas
    path under every loss (one chain at a time, its step's floats (1, 1)
    vectors: ops/pallas_longrows._kernel, PR 49; the body is read in
    tests/test_losses.py), said on the console line; ``scalar`` on
    ``fori``; the margin's form changes nothing of it."""
    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    path = resolve_solver_path(ds, 12, None, math="fast", pallas=True,
                               loss=loss)
    assert (path.kernel, path.storage, path.step_solve) == (
        "pallas", "stream", "vector")
    assert "each step solved on the vector unit" in path.describe()
    assert "solved in lanes" not in path.describe()
    for mode in ("plus", "frozen"):
        assert path.for_mode(mode).step_solve == "vector"
    fori = resolve_solver_path(ds, 12, None, math="fast", pallas=False,
                               loss=loss)
    assert (fori.kernel, fori.storage, fori.step_solve) == (
        "fori", "stream", "scalar")
    assert "on the vector unit" not in fori.describe()


def test_a_row_that_outgrows_smem_is_refused_with_the_numbers():
    from cocoa_tpu.ops.pallas_sparse_hbm import hbm_refusal

    assert hbm_refusal(29890095, 64, 240801) == ""
    why = hbm_refusal(16609143, 32768, 4375)
    assert "outgrows SMEM" in why and "32768" in why and "786432" in why


@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_driver_on_the_stream_matches_the_fori_path(data, ds, loss):
    """run_cocoa end to end — rounds, the certificate's margins, the
    --accel jump's axpy — on the Pallas path against the fori path."""
    from cocoa_tpu import solvers
    from cocoa_tpu.config import DebugParams, Params

    params = Params(n=data.n, num_rounds=6, local_iters=16, lam=LAM,
                    loss=loss)
    debug = DebugParams(debug_iter=2, seed=3)
    runs = {}
    for name, kw in (("pallas", dict(pallas=True)),
                     ("fori", dict(pallas=False))):
        w, alpha, traj = solvers.run_cocoa(
            ds, params, debug, plus=True, quiet=True, math="fast",
            device_loop=True, rng="permuted", accel="auto", **kw)
        runs[name] = (np.asarray(w), np.asarray(alpha), traj)
    path = runs["pallas"][2].meta["solver_path"]
    assert (path["kernel"], path["storage"], path["margin"]) == (
        "pallas", "stream", "combined")
    assert path["step_solve"] == "vector"
    fori = runs["fori"][2].meta["solver_path"]
    assert (fori["kernel"], fori["margin"]) == ("fori", None)
    assert fori["step_solve"] == "scalar"
    np.testing.assert_allclose(runs["pallas"][0], runs["fori"][0], atol=1e-5)
    np.testing.assert_allclose(runs["pallas"][1], runs["fori"][1], atol=1e-5)
    gaps = [rec.gap for rec in runs["pallas"][2].records]
    assert gaps[-1] < gaps[0]


def test_minibatch_cd_on_the_stream_keeps_the_split_margin(data, ds, capsys):
    """Mini-batch CD (``frozen``) on the Pallas path: x . w of the round's
    rows stays a pass of its own, said on the console line and on
    ``Trajectory.meta``, and the run matches the fori path."""
    from cocoa_tpu import solvers
    from cocoa_tpu.config import DebugParams, Params

    params = Params(n=data.n, num_rounds=2, local_iters=16, lam=LAM)
    debug = DebugParams(debug_iter=2, seed=3)
    runs = {}
    for name, pallas in (("pallas", True), ("fori", False)):
        w, alpha, traj = solvers.run_minibatch_cd(
            ds, params, debug, math="fast", device_loop=True,
            rng="permuted", pallas=pallas)
        runs[name] = (np.asarray(w), np.asarray(alpha), traj)
    said = capsys.readouterr().out
    assert "rows kept as a stream" in said and ", margin split" in said
    assert runs["pallas"][2].meta["solver_path"]["margin"] == "split"
    assert runs["fori"][2].meta["solver_path"]["margin"] is None
    assert np.abs(runs["pallas"][0]).max() > 0
    np.testing.assert_allclose(runs["pallas"][0], runs["fori"][0], atol=1e-5)
    np.testing.assert_allclose(runs["pallas"][1], runs["fori"][1], atol=1e-5)


def test_the_primal_solvers_refuse_a_stream(ds):
    """They read sparse rows as the rectangle: refused, not misread."""
    from cocoa_tpu import solvers
    from cocoa_tpu.config import DebugParams, Params

    params = Params(n=ds.n, num_rounds=2, local_iters=4, lam=LAM)
    debug = DebugParams(debug_iter=1)
    with pytest.raises(ValueError, match="kept as a stream"):
        solvers.run_sgd(ds, params, debug, local=True, quiet=True)
    with pytest.raises(ValueError, match="kept as a stream"):
        solvers.run_dist_gd(ds, params, debug, quiet=True)
