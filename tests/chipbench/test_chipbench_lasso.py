"""The lasso cell's own pieces, on the CPU: the harness finds everything
``epsilon-lasso.prox_cocoa_plus`` names; the configuration's arithmetic (H,
the bytes of a round, the bytes of the columns); the stand-in generator
makes what it says (unit rows, a planted x* of equal magnitudes, flipped
signs, lambda_max where the arithmetic puts it), the same from the same
seed; the system (the Pallas kernel in interpret mode, and ``fori``)
follows a NumPy prox-CD oracle step for step; the plain reference's
objectives agree with the oracle's; the audit passes a float32 job and
refuses one whose x was rounded once to bfloat16 or whose A^T r went
through one bf16 pass.

Tolerances: the reference works in float32 on the device and adds its K
partial sums on the host in float64, so against float64 NumPy its
objectives agree to 1e-6 relative; the float64 system against the float64
oracle to 1e-9."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import cost_model, reference_lasso, registry  # noqa: E402
from chipbench import run as harness  # noqa: E402

from owed import check_cell  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
CELL = "epsilon-lasso.prox_cocoa_plus"
SMALL = dict(name="small", n=96, d=2048, num_splits=4, local_iter_frac=0.25,
             dtype="float32", loss="lasso", layout="dense",
             generator="dense_columns_planted",
             generator_args=dict(flip=0.02, support=12))
SEED = 3000000019               # past 2**31: the driver's are large
# no entry is this cell's alone since PR 55: what its PR entered under
# ``prox_*`` names are the dense cells' readings, one entry each
# (``sparse_dw_reduce_share``: the ``cocoa_dw_reduce`` scope, here the
# summed dv)
BLOCK = []
SHARED = ["local_solve_ms", "local_solve_roofline", "eval_share",
          "sparse_dw_reduce_share", "unscoped_share", "fixed_init_s",
          "fixed_stage_s", "fixed_dispatch_s", "fixed_fetch_s",
          "fixed_unspanned_s", "round_roofline"]
GENERIC = ["device_idle_share", "fixed_s", "launches_per_job", "round_ms",
           "top_op_share", "compile_s", "compiles_in_window"]


@pytest.fixture(scope="module")
def gen():
    return registry.load_module(BENCH, "generators", "dense_columns_planted")


@pytest.fixture(scope="module")
def planted(gen):
    """``(A (d, n) float64, b, x*)`` of the small stand-in."""
    import jax

    a_t, b, x_star = gen.columns_and_target(
        jax.random.key(SEED), SMALL["n"], SMALL["d"],
        SMALL["generator_args"]["support"], SMALL["generator_args"]["flip"])
    return (np.asarray(a_t, np.float64).T, np.asarray(b, np.float64),
            np.asarray(x_star, np.float64))


@pytest.fixture(scope="module")
def small(gen):
    return gen.make(SMALL, SEED)


def small_cell(planted, **job_kwargs):
    """The cell at the small size: lambda = 0.1 lambda_max of ITS data and
    the relative target 1e-4 P(0), as the configuration and the job state
    them for the published size."""
    A, b, _ = planted
    cell = registry.resolve_cell(BENCH, CELL)
    lam = 0.1 * float(np.abs(A.T @ b).max())
    target = 1e-4 * 0.5 * float(b @ b)
    job = json.loads(json.dumps(cell["job"]))
    job["stop"]["target"] = job["kwargs"]["gap_target"] = target
    job["kwargs"].update(job_kwargs)
    return {**cell, "config": {**SMALL, "lambda": lam}, "job": job}


def oracle(A, b, lam, k, rounds, h, seed=0):
    """Literal sequential ProxCoCoA+ in float64 NumPy (gamma = 1, sigma' =
    K): column blocks, a round's frozen r, sigma'-corrected prox steps."""
    from cocoa_tpu.data.sharding import split_sizes
    from cocoa_tpu.utils.prng import sample_indices

    n, d = A.shape
    offs = np.concatenate([[0], np.cumsum(split_sizes(d, k))])
    x, r = np.zeros(d), -b.copy()
    for t in range(1, rounds + 1):
        dv_sum = np.zeros(n)
        for s in range(k):
            lo, hi = offs[s], offs[s + 1]
            dv, dx = np.zeros(n), np.zeros(hi - lo)
            for j in sample_indices(seed, range(t, t + 1), h, hi - lo)[0]:
                a_j = A[:, lo + j]
                q = k * (a_j @ a_j)
                z = a_j @ r + k * (a_j @ dv)
                cur = x[lo + j] + dx[j]
                u = (q * cur - z) / q
                new = np.sign(u) * max(abs(u) - lam / q, 0.0)
                dx[j] += new - cur
                dv += a_j * (new - cur)
            x[lo:hi] += dx
            dv_sum += dv
        r = r + dv_sum
    return x, r


def lasso_gap(A, b, x, lam):
    r = A @ x - b
    primal = 0.5 * r @ r + lam * np.abs(x).sum()
    s = min(1.0, lam / np.abs(A.T @ r).max())
    return primal, primal - (-0.5 * s * s * (r @ r) - s * (r @ b))


def test_the_harness_resolves_the_cell():
    cell = registry.resolve_cell(BENCH, CELL)
    cfg, job = cell["config"], cell["job"]
    assert (cell["chips"], cfg["name"], cfg["layout"], cfg["loss"]) == (
        1, "epsilon-lasso", "dense", "lasso")
    assert (cfg["n"], cfg["d"], cfg["num_splits"]) == (2000, 400000, 8)
    assert cfg["published"] == {"rows": 400000, "features": 2000}
    assert (job["entry"], job["check"]) == ("run_prox_cocoa",
                                            "certified_gap_lasso")
    assert job["expect_path"] == {"inner": "sequential", "kernel": "pallas",
                                  "interpret": False}
    # the target is 1e-4 of P(0) = 1/2 |b|^2, b of 400,000 signs
    assert job["stop"]["target"] == job["kwargs"]["gap_target"] == (
        1e-4 * 0.5 * cfg["published"]["rows"])
    # ISSUE 34's rule: 25, or 50 where eight seeds do not stop at one eval
    assert job["debug"]["debug_iter"] == 50
    assert f"--debugIter={job['debug']['debug_iter']}" in job["flags"]
    assert cell["traffic"] == job["name"] == "prox_cocoa_plus_relgap1e-4_e50"
    assert not {"pallas", "block_size", "unroll", "interleave", "form",
                "scan_chunk"} & set(job["kwargs"])
    gen = registry.load_module(BENCH, "generators", cfg["generator"])
    check = registry.load_module(BENCH, "checks", job["check"])
    assert callable(gen.make) and callable(check.audit)
    assert callable(check.job_problem)
    assert 1e-6 < check.R_TOL < 2e-3        # under one bf16 rounding of x


@pytest.mark.parametrize("name", SHARED + GENERIC)
def test_a_traced_line_of_the_cell_can_carry_the_metric(name):
    readers = {m["name"]: (m, read, params) for m, read, params
               in registry.layer_readers(BENCH, CELL)}
    m, read, params = readers[name]
    assert callable(read)
    assert m["moves"] == ("setup_s" if name == "compile_s" else "job_s")
    if name in SHARED:
        assert CELL in m.get("workloads", [CELL])
        spans = ["init_state", "wait_indices", "dispatch", "fetch"]
        assert params == {
            "local_solve_ms": {"scope": "cocoa_local_solve",
                               "per_round": True},
            "local_solve_roofline": {"scope": "cocoa_local_solve"},
            "eval_share": {"scope": "cocoa_eval"},
            "sparse_dw_reduce_share": {"scope": "cocoa_dw_reduce"},
            "unscoped_share": {"scope": None},
            "fixed_init_s": {"span": spans[0]},
            "fixed_stage_s": {"span": spans[1]},
            "fixed_dispatch_s": {"span": spans[2]},
            "fixed_fetch_s": {"span": spans[3]},
            "fixed_unspanned_s": {"less": spans},
            "round_roofline": {}}[name]
    else:
        assert "workloads" not in m


def test_the_cell_owes_these_metrics():
    check_cell(BENCH, CELL, BLOCK, GENERIC, SHARED)


def test_the_configurations_arithmetic():
    """H = 25 from the harness's own rule; a round reads 200 columns of 1.6
    MB = 320 MB, 0.39 ms at the HBM peak, by the dense cells' model and
    reader; the column shards are 3.28 GB."""
    from chipbench.readers import round_roofline

    cell = registry.resolve_cell(BENCH, CELL)
    params, debug, kwargs, h = harness.job_arguments(cell)
    assert h == 25 and params.local_iters == 25
    assert (params.n, params.loss, params.gamma) == (2000, "lasso", 1.0)
    # nothing restates --objective (the entry's name does) or sets --l2
    assert not {"objective", "l2"} & set(kwargs)
    assert params.lam == cell["config"]["lambda"] == 72.0
    model = cost_model.sdca_round(400000, 8, h)
    assert model["hbm_bytes"] == 320e6
    floor = round_roofline.floor_of({
        **cell, "local_iters": h, "device_kind": "TPU v5 lite",
        "solver_path": cell["job"]["expect_path"]})
    assert floor["bound"] == "hbm"
    assert abs(floor["floor_s"] - 320e6 / 819e9) < 1e-9
    assert 8 * 256 * 400000 * 4 == 3276800000


@pytest.mark.parametrize("what", ["unit_rows", "planted", "flips",
                                  "lambda_max", "layout"])
def test_generator_follows_the_stated_law(gen, planted, small, what):
    A, b, x_star = planted
    d, n = A.shape                      # solver's view: d rows, n columns
    support = SMALL["generator_args"]["support"]
    if what == "unit_rows":
        np.testing.assert_allclose((A * A).sum(axis=1), 1.0, atol=1e-5)
        assert abs((A * A).sum(axis=0).mean() - d / n) < 0.05 * d / n
    elif what == "planted":
        assert np.count_nonzero(x_star) == support
        assert set(np.unique(x_star)) == {-1.0, 0.0, 1.0}   # equal magnitude
    elif what == "flips":
        assert set(np.unique(b)) == {-1.0, 1.0}
        flipped = (np.sign(A @ x_star) != b).mean()
        assert 0.005 < flipped < 0.04                       # 2% of 2,048
    elif what == "lambda_max":
        # a support column's a_j . b, by the generator's arithmetic
        corr = np.abs(A.T @ b)
        on = corr[x_star != 0]
        want = np.sqrt(2 / np.pi) * 0.96 * d / np.sqrt(n * support)
        assert abs(on.mean() - want) < 0.15 * want
        assert corr.max() == on.max()           # lambda_max sits on x*
    else:
        k, cols = SMALL["num_splits"], n // SMALL["num_splits"]
        d_shard = -(-cols // 16) * 16
        assert small.X.shape == (k, d_shard, d) and small.n == n
        assert small.num_features == d and list(small.counts) == [cols] * k
        X = np.asarray(small.X)
        np.testing.assert_array_equal(
            X[:, :cols].reshape(n, d), A.T.astype(np.float32))
        assert not X[:, cols:].any()
        np.testing.assert_array_equal(np.asarray(small.target), b)
        np.testing.assert_array_equal(np.asarray(small.mask)[:, :cols], 1.0)
        assert not np.asarray(small.mask)[:, cols:].any()
        np.testing.assert_allclose(np.asarray(small.sq_norms),
                                   (X * X).sum(-1), rtol=1e-6)


@pytest.mark.parametrize("seed", [SEED, 8, 2147483659])
def test_generator_plants_the_steadiest_of_its_draws(gen, seed):
    """``support_draws``: of the supports a seed draws, the one planted is
    the one whose worst column lags the average one least under the pull
    of the others (by NumPy in float64, from A alone); the first draw is
    what a single draw plants, and the law of x* holds whichever is
    kept."""
    import jax

    n, d = SMALL["n"], SMALL["d"]
    support, flip = (SMALL["generator_args"][k] for k in ("support", "flip"))

    def lag(a_t, x_star):
        a, on = np.asarray(a_t, np.float64), np.flatnonzero(x_star)
        g, s = a[on] @ a[on].T, np.asarray(x_star, np.float64)[on]
        pull = np.array([s[j] * sum(s[k] * g[j, k] for k in range(len(on))
                                    if k != j) / g[j, j]
                         for j in range(len(on))])
        return pull.mean() - pull.min()

    lags = {}
    for draws in (1, 2, 16):
        a_t, b, x_star = gen.columns_and_target(
            jax.random.key(seed), n, d, support, flip, draws)
        x_star = np.asarray(x_star)
        assert np.count_nonzero(x_star) == support
        assert set(np.unique(x_star)) == {-1.0, 0.0, 1.0}
        flipped = (np.sign(np.asarray(a_t, np.float64).T @ x_star)
                   != np.asarray(b)).mean()
        assert 0.005 < flipped < 0.04
        lags[draws] = lag(a_t, x_star)
    # more draws hold the fewer among them: the least lag can only fall
    assert lags[16] <= lags[2] <= lags[1]
    assert lags[16] < lags[1]
    on = np.flatnonzero(x_star)
    g = np.asarray(a_t, np.float64)
    assert abs(float(gen.worst_lag(
        np.asarray(g @ g.T, np.float32), on,
        x_star[on].astype(np.float32))) - lags[16]) < 1e-4


def test_configuration_draws_its_support_sixteen_times(gen):
    cell = registry.resolve_cell(BENCH, CELL)
    assert cell["config"]["generator_args"] == {
        "flip": 0.02, "support": 100, "support_draws": 16}
    small = dict(SMALL, generator_args=dict(SMALL["generator_args"],
                                            support_draws=16))
    ds, one = gen.make(small, SEED), gen.make(SMALL, SEED)
    # the columns are the seed's whatever is planted in them; the target
    # follows the kept support
    np.testing.assert_array_equal(np.asarray(ds.X), np.asarray(one.X))
    assert (np.asarray(ds.target) != np.asarray(one.target)).any()


def test_generator_same_seed_same_columns(gen, small):
    again, other = gen.make(SMALL, SEED), gen.make(SMALL, 8)
    for name in ("X", "target", "mask", "sq_norms", "labels"):
        np.testing.assert_array_equal(getattr(small, name),
                                      getattr(again, name))
    assert (np.asarray(small.X) != np.asarray(other.X)).any()
    assert (np.asarray(small.target) != np.asarray(other.target)).any()
    with pytest.raises(ValueError, match="one chip"):
        gen.make(SMALL, SEED, mesh=object())


@pytest.mark.parametrize("fault", ["none", "shifted_column", "padding",
                                   "mask", "target", "shape"])
def test_generator_holds_the_shards_against_its_own_columns(gen, small,
                                                            fault):
    """The reference reads the program's shards, so the generator compares
    them, once, with the columns and target it made: a column in the wrong
    slot, a value on padding, a wrong mask or target is counted."""
    import dataclasses

    import jax

    args = SMALL["generator_args"]
    a_t, b, _ = gen.columns_and_target(
        jax.random.key(SEED), SMALL["n"], SMALL["d"], args["support"],
        args["flip"])
    cols = SMALL["n"] // SMALL["num_splits"]
    X, mask = np.array(small.X), np.array(small.mask)
    target = np.array(small.target)
    want = 0
    if fault == "shifted_column":
        X[1, :cols] = np.roll(X[1, :cols], 1, axis=0)
        want = (X[1, :cols] != np.asarray(small.X)[1, :cols]).sum()
    elif fault == "padding":
        X[2, cols, 5], want = 1.0, 1
    elif fault == "mask":
        mask[0, cols], want = 1.0, 1
    elif fault == "target":
        target[7], want = -target[7], 1
    elif fault == "shape":
        X, mask, want = X[:, :-16], mask[:, :-16], -1
    ds = dataclasses.replace(small, X=X, mask=mask, target=target)
    assert gen.layout_faults(a_t, b, ds) == want
    if fault == "shifted_column":
        assert want > 1000


def test_job_is_what_its_flag_line_runs_on_column_shards(tmp_path):
    """What ``test_chipbench_jobs.py`` holds every job to (since PR 52 this
    one too, on column shards at the job's own target), here at a target
    and a lambda scaled to the tiny file as the cell's are to epsilon: at a
    tiny shape, ``cli.main`` with the job's flag line and the harness's
    own call with the job's keyword arguments run the same trajectory to
    the same stop, on the same resolved path; row shards are refused."""
    from cocoa_tpu import cli
    from cocoa_tpu.data import load_libsvm, shard_columns
    from cocoa_tpu.data.synth import synth_dense, write_libsvm
    from cocoa_tpu.telemetry import events as tele

    rows, width, k = 192, 32, 2
    path, ev = str(tmp_path / "tiny.dat"), str(tmp_path / "ev.jsonl")
    write_libsvm(synth_dense(rows, width, seed=3), path)
    data = load_libsvm(path, width)
    cell = registry.resolve_cell(BENCH, CELL)
    # the CLI's H is the flag's share of a column shard: the config's keys
    # in the solver's view give the harness the same
    lam = 0.05 * float(np.abs(data.to_dense().T @ data.labels).max())
    target = 1e-4 * 0.5 * float(data.labels @ data.labels)
    job = json.loads(json.dumps(cell["job"]))
    job["stop"]["target"] = job["kwargs"]["gap_target"] = target
    cell = {**cell, "job": job, "config": {
        **cell["config"], "n": width, "d": rows, "num_splits": k,
        "lambda": lam}}
    ds = shard_columns(data, k, layout="dense")
    run_once, h = harness.make_job(cell, ds, None)
    got = run_once()
    flags = job["flags"].replace("--gapTarget=20", f"--gapTarget={target}")
    try:
        assert cli.main([
            f"--trainFile={path}", f"--numFeatures={width}",
            f"--numSplits={k}", f"--lambda={lam}",
            f"--localIterFrac={cell['config']['local_iter_frac']}",
            "--mesh=1", "--quiet", f"--events={ev}", *flags.split()]) == 0
    finally:
        tele.get_bus().reset()
    with open(ev) as f:
        events = [json.loads(line) for line in f if line.strip()]
    (start,) = [e for e in events if e["event"] == "run_start"]
    evals = [e for e in events if e["event"] == "round_eval"]
    (end,) = [e for e in events if e["event"] == "run_end"]
    assert start["manifest"]["solver_path"] == got["solver_path"]
    assert got["solver_path"]["objective"] == "lasso"
    assert max(1, int(start["manifest"]["config"]["local_iter_frac"]
                      * width / k)) == h
    records = got["traj"].records
    assert [e["t"] for e in evals] == [r.round for r in records]
    assert (evals[-1]["primal"], evals[-1]["gap"]) == (records[-1].primal,
                                                       records[-1].gap)
    assert end["rounds"] == got["rounds"] and got["rounds"] < 10000
    assert end["stopped"] == got["traj"].stopped
    assert records[-1].gap <= target
    # row shards, the SVM solvers' dataset, are refused
    from cocoa_tpu.data import shard_dataset

    run_rows, _ = harness.make_job(cell, shard_dataset(
        data, k=k, layout="dense"), None)
    with pytest.raises(ValueError, match="column shards"):
        run_rows()


@pytest.mark.parametrize("path", ["fori", "pallas"])
def test_the_system_follows_the_oracle_step_for_step(planted, path):
    """Twelve rounds of the library entry on float64 column shards built
    from device arrays, against the NumPy oracle: the same x and r."""
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.columns import shard_dense_columns
    from cocoa_tpu.solvers import run_prox_cocoa

    A, b, _ = planted
    k, h, rounds = SMALL["num_splits"], 6, 12
    lam = 0.1 * float(np.abs(A.T @ b).max())
    ds = shard_dense_columns(jnp.asarray(A.T), jnp.asarray(b), k,
                             dtype=jnp.float64)
    x, r, traj = run_prox_cocoa(
        ds, Params(n=A.shape[1], num_rounds=rounds, local_iters=h, lam=lam,
                   loss="lasso"),
        DebugParams(debug_iter=6, seed=0), quiet=True, math="fast",
        pallas=path == "pallas", scan_chunk=6)
    x_o, r_o = oracle(A, b, lam, k, rounds, h)
    cols = A.shape[1] // k
    np.testing.assert_allclose(np.asarray(x)[:, :cols].reshape(-1), x_o,
                               atol=1e-9)
    np.testing.assert_allclose(np.asarray(r), r_o, atol=1e-9)
    assert traj.meta["solver_path"]["kernel"] == path
    assert traj.meta["solver_path"]["objective"] == "lasso"
    # the trajectory's certificate is the oracle's, in float64
    primal, gap = lasso_gap(A, b, x_o, lam)
    assert abs(traj.records[-1].primal - primal) < 1e-9 * primal
    assert abs(traj.records[-1].gap - gap) < 1e-9 * primal


def test_reference_against_the_oracle(planted, small):
    """The plain reference, from x alone, against float64 NumPy at the
    oracle's x after 40 rounds (a gap of a few percent of P(0))."""
    A, b, _ = planted
    k = SMALL["num_splits"]
    lam = 0.1 * float(np.abs(A.T @ b).max())
    x_o, r_o = oracle(A, b, lam, k, 40, 6)
    cols, d_shard = A.shape[1] // k, small.X.shape[1]
    x = np.zeros((k, d_shard), np.float32)
    x[:, :cols] = x_o.reshape(k, cols)
    ref = reference_lasso.recompute(small, x, lam)
    x32 = x[:, :cols].reshape(-1).astype(np.float64)
    A32 = np.asarray(small.X, np.float64)[:, :cols].reshape(-1, A.shape[0]).T
    primal, gap = lasso_gap(A32, b, x32, lam)
    assert abs(ref["primal"] - primal) < 1e-6 * primal
    assert abs(ref["gap"] - gap) < 1e-6 * primal
    assert gap > 0 and ref["gap"] > 0
    np.testing.assert_allclose(ref["r_ref"], A32 @ x32 - b, atol=1e-5)
    assert ref["x_nnz"] == np.count_nonzero(x_o)
    assert ref["x_on_padding"] == 0
    x[0, d_shard - 1] = 1.0             # a value on a padding column
    assert reference_lasso.recompute(small, x, lam)["x_on_padding"] == 1
    with pytest.raises(ValueError, match="target"):
        import dataclasses

        reference_lasso.recompute(dataclasses.replace(small, target=None),
                                  x, lam)


@pytest.fixture(scope="module")
def audited(planted, small):
    """One float32 job of the small cell through the harness's own
    ``make_job`` (``fori``: this process's platform is cpu), and its
    audit."""
    cell = small_cell(planted, device_loop=False, scan_chunk=26)
    run_once, h = harness.make_job(cell, small, None)
    run = run_once()
    check = registry.load_module(BENCH, "checks", cell["job"]["check"])
    return cell, check, run, check.audit(cell, small, run)


def test_the_audit_passes_a_float32_job(audited, small):
    cell, check, run, audit = audited
    assert audit["ok"], audit["problems"]
    assert check.job_problem(cell["job"], run) is None
    assert run["rounds"] % 50 == 0 and run["rounds"] < 10000
    assert 0 <= audit["gap"] <= cell["job"]["stop"]["target"]
    assert audit["r_err"] < 0.2 * check.R_TOL
    assert audit["x_on_padding"] == 0
    assert audit["program"]["x_nnz"] == audit["x_nnz"] > 0
    assert harness.placement_problems(
        {**cell, "job": {**cell["job"], "expect_path": {
            "inner": "sequential", "kernel": "fori"}}}, small, run) == []


@pytest.mark.parametrize("fault", ["x_bf16", "atr_bf16", "r_drift",
                                   "no_certificate", "x_on_padding"])
def test_the_audit_refuses(audited, small, fault):
    """Each counter-reading fails by a limit of the audit: x rounded once
    to bfloat16 (``R_TOL``), A^T r through one bf16 pass (the gap's
    agreement), an r that left A x - b, a job that stopped on its budget,
    a value on a padding column."""
    import jax.numpy as jnp

    cell, check, run, audit = audited
    target = cell["job"]["stop"]["target"]
    if fault == "x_bf16":
        assert audit["bf16_x_fails"] and audit["r_err_bf16"] > check.R_TOL
        bad = {**run, "w": jnp.asarray(run["w"], jnp.bfloat16).astype(
            jnp.float32)}
        problems = check.audit(cell, small, bad)["problems"]
        assert any(p.startswith("r != A x - b") for p in problems)
    elif fault == "atr_bf16":
        assert audit["bf16_atr_fails"]
        assert abs(audit["gap_bf16"] - audit["gap"]) > check.GAP_TOL * target
    elif fault == "r_drift":
        bad = {**run, "alpha": run["alpha"] + 1e-2}
        problems = check.audit(cell, small, bad)["problems"]
        assert any(p.startswith("r != A x - b") for p in problems)
    elif fault == "no_certificate":
        short = json.loads(json.dumps(cell["job"]))
        short["params"]["num_rounds"] = short["debug"]["debug_iter"] = 26
        run_once, _ = harness.make_job({**cell, "job": short}, small, None)
        why = check.job_problem(short, run_once())
        assert why and why.startswith("no certificate")
    else:
        x = np.array(run["w"])
        x[0, -1] = 0.5
        problems = check.audit(cell, small, {**run, "w": x})["problems"]
        assert any("padding" in p for p in problems)


def test_config_states_every_guess():
    cfg = registry.load_json(os.path.join(ROOT, "chipbench", "configs",
                                          "epsilon-lasso.json"))
    entry = [c for c in BENCH["configs"] if c["name"] == "epsilon-lasso"][0]
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"]
    assert "arXiv:1611.02189" in entry["source"]
    for key in ("view", "published", "deployment", "guarantees", "assumed"):
        assert cfg[key]
    said = " ".join(cfg["assumed"])
    for word in ("stand-in", "EQUAL magnitude", "lambda_max", "K = 8",
                 "as remembered"):
        assert word in said
    assert "float32 throughout" in cfg["guarantees"]
    assert "gap >= 0" in cfg["guarantees"]
