"""Demo-equivalent integration tests on the bundled reference data
(run-demo-local.sh config: K=4, H=50, λ=1e-3), abbreviated to keep CI fast.
The full 100-round run reaches gap ≈ 4.7e-3 and test error 2.5%."""

import jax.numpy as jnp
import numpy as np
import pytest

from conftest import SMALL_TRAIN, SMALL_TEST  # noqa: E402
from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data import shard_dataset
from cocoa_tpu.parallel import make_mesh
from cocoa_tpu.solvers import run_cocoa


@pytest.fixture(scope="module")
def demo(small_train, small_test):
    mesh = make_mesh(4)
    ds = shard_dataset(small_train, k=4, layout="sparse", dtype=jnp.float64, mesh=mesh)
    tds = shard_dataset(small_test, k=4, layout="sparse", dtype=jnp.float64, mesh=mesh)
    params = Params(n=2000, num_rounds=30, local_iters=50, lam=0.001,
                    beta=1.0, gamma=1.0)
    return mesh, ds, tds, params


@pytest.mark.parametrize("plus", [True, False])
def test_demo_converges(demo, plus):
    mesh, ds, tds, params = demo
    debug = DebugParams(debug_iter=10, seed=0)
    w, alpha, traj = run_cocoa(
        ds, params, debug, plus=plus, mesh=mesh, test_ds=tds, quiet=True
    )
    gaps = [r.gap for r in traj.records]
    errs = [r.test_error for r in traj.records]
    # gap decreasing across checkpoints, non-negative, below .1 by round 30
    assert all(g >= 0 for g in gaps)
    assert gaps[-1] < gaps[0]
    assert gaps[-1] < 0.1
    # linear SVM on this data sits at ~2.5% test error
    assert errs[-1] < 0.06
    # alpha in box, w finite
    assert np.all(np.isfinite(np.asarray(w)))
    a = np.asarray(alpha)
    assert a.min() >= -1e-12 and a.max() <= 1 + 1e-12


def test_cli_end_to_end(capsys):
    from cocoa_tpu import cli

    rc = cli.main([
        f"--trainFile={SMALL_TRAIN}",
        f"--testFile={SMALL_TEST}",
        "--numFeatures=9947",
        "--numSplits=4",
        "--numRounds=10",
        "--localIterFrac=0.1",
        "--lambda=.001",
        "--debugIter=5",
        "--justCoCoA=true",
        "--dtype=float64",
        "--master=local[4]",  # accepted-and-ignored reference flag
    ])
    out = capsys.readouterr().out
    assert rc == 0
    assert "Running CoCoA+ on 2000 data examples" in out
    assert "Running CoCoA on 2000 data examples" in out
    assert "primal-dual gap:" in out
    assert "CoCoA+ has finished running. Summary Stats:" in out
    assert "Duality Gap:" in out
    assert "Test Error:" in out


@pytest.mark.parametrize("flag", ["--bogus=1", "--blockPipeline=off"])
def test_cli_rejects_unknown_flag(flag):
    """``--blockPipeline`` was a flag until PR 28; the block scan picks
    its schedule from the block count and the name is refused like any
    other unknown one."""
    from cocoa_tpu import cli

    name = flag.split("=")[0]
    with pytest.raises(SystemExit, match=f"Invalid argument: {name}"):
        cli.parse_args([flag])
    with pytest.raises(SystemExit, match=f"Invalid argument: {name}"):
        cli.main([flag])


def test_cli_requires_trainfile(capsys):
    from cocoa_tpu import cli

    assert cli.main(["--numFeatures=5"]) == 2
    assert "trainFile is required" in capsys.readouterr().err
