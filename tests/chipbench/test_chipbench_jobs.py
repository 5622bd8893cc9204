"""A job is what a user runs: at a tiny shape, ``cli.main`` with a job's
flag line and the benchmark's own call with that job's keyword arguments
resolve to the same parameters, local solver and sampling, and run the
same trajectory to the same stop.  Nothing here names a job: what a job's
file says is held against what the CLI made of its flag line.  And the
plain reference agrees with ``tests/oracle.py`` on the demo data."""

import dataclasses
import glob
import inspect
import json
import os
import re
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import reference, registry, run  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
JOBS = sorted(os.path.basename(p)[:-5] for p in glob.glob(
    os.path.join(BENCH["_dir"], "jobs", "*.json")))
SHAPE = dict(n=192, d=32, num_splits=2, local_iter_frac=0.1, loss="hinge")
SHAPE["lambda"] = 0.01
# keyword arguments that restate no flag: which twin of an entry runs
# (the CLI runs them all, one after another) and a silent console
SELECTORS = {"plus", "local", "quiet"}
# flags this test adds to every line itself
OWN_FLAGS = {"events", "quiet", "mesh"}
# what cli.main passes where the line names no flag and the library's own
# default is another
CLI_DEFAULTS = {"accel": "auto"}


@pytest.fixture(scope="module")
def tiny_file(tmp_path_factory):
    from cocoa_tpu.data.synth import synth_dense, write_libsvm

    path = str(tmp_path_factory.mktemp("jobs") / "tiny.dat")
    write_libsvm(synth_dense(SHAPE["n"], SHAPE["d"], seed=3), path)
    return path


def cli_run(flags: str, train: str, events: str) -> list:
    from cocoa_tpu import cli
    from cocoa_tpu.telemetry import events as tele

    argv = [f"--trainFile={train}", f"--numFeatures={SHAPE['d']}",
            f"--numSplits={SHAPE['num_splits']}",
            f"--lambda={SHAPE['lambda']}",
            f"--localIterFrac={SHAPE['local_iter_frac']}", "--mesh=1",
            "--quiet", f"--events={events}", *flags.split()]
    try:
        assert cli.main(argv) == 0
    finally:
        tele.get_bus().reset()
    with open(events) as f:
        return [json.loads(line) for line in f if line.strip()]


def snake(flag: str) -> str:
    return re.sub("([A-Z])", lambda m: "_" + m.group(1).lower(), flag)


def same(text, value) -> bool:
    """A flag's text against the keyword argument that restates it."""
    if isinstance(value, bool):
        return (str(text).lower() != "false") == value
    try:
        return float(text) == float(value)
    except (TypeError, ValueError):
        return str(text).lower() == str(value).lower()


@pytest.mark.parametrize("name", JOBS)
def test_job_is_what_its_flag_line_runs(name, tiny_file, tmp_path):
    from cocoa_tpu import solvers
    from cocoa_tpu.config import RunConfig
    from cocoa_tpu.data import load_libsvm, shard_dataset

    job = {**registry.load_json(os.path.join(BENCH["_dir"], "jobs",
                                             name + ".json")), "name": name}
    ds = shard_dataset(load_libsvm(tiny_file, SHAPE["d"]),
                       k=SHAPE["num_splits"], layout="dense")
    cell = {"config": SHAPE, "job": job, "chips": 1}
    params, debug, kw, _ = run.job_arguments(cell)
    run_once, _ = run.make_job(cell, ds, None)
    got = run_once()
    algorithm = got["traj"].algorithm        # the label the program gives

    events = cli_run(job["flags"], tiny_file, str(tmp_path / "ev.jsonl"))
    (start,) = [e for e in events if e["event"] == "run_start"]
    (end,) = [e for e in events if e["event"] == "run_end"
              and e["algorithm"] == algorithm]
    manifest = start["manifest"]
    man = manifest["config"]

    # the CLI's own resolution of the line (config.RunConfig) gives the
    # parameters the job's file gives: rounds, H, lambda, loss, sigma', ...
    fields = {f.name for f in dataclasses.fields(RunConfig)}
    cfg = RunConfig(**{f: man[f] for f in fields if f != "mesh_shape"})
    assert dataclasses.asdict(cfg.to_params(ds.n, ds.k)) == \
        dataclasses.asdict(params)
    assert (man["debug_iter"], man["seed"]) == (debug.debug_iter, debug.seed)
    # every flag on the line is a keyword argument of the job, and agrees
    line = {flag: text for flag, text in man.items()
            if flag not in fields and flag not in OWN_FLAGS}
    for flag, text in line.items():
        assert snake(flag) in kw, f"--{flag} is on the line, not in kwargs"
        assert same(text, kw[snake(flag)]), (flag, text, kw[snake(flag)])
    # every keyword argument restates a flag of the line, a same-named
    # field of the CLI's configuration (--rng, --math, --sampling), or
    # what the CLI passes where the line has no flag
    on_line = {snake(flag) for flag in line}
    for key, value in kw.items():
        if key in SELECTORS or key in on_line:
            continue
        text = man[key] if key in fields else CLI_DEFAULTS.get(key)
        assert text is not None, f"{key} restates no flag"
        assert same(text, value), (key, text, value)
    # ... and leaves out nothing the CLI would pass the entry
    accepted = inspect.signature(
        getattr(solvers, job["entry"])).parameters
    takes_any = any(p.kind is p.VAR_KEYWORD for p in accepted.values())
    passed = {**{key: man[key] for key in ("rng", "sampling", "math")
                 if man[key] != getattr(RunConfig(), key)}, **CLI_DEFAULTS}
    for key, text in passed.items():
        if (takes_any or key in accepted) and key not in on_line:
            assert same(text, kw.get(key)), (key, text, kw.get(key))
    if got["solver_path"] is not None:
        assert manifest["solver_path"] == got["solver_path"]
    # ... and the same trajectory comes out, to the last bit
    last = got["traj"].records[-1]
    assert end["rounds"] == got["rounds"] == last.round
    assert end["stopped"] == got["traj"].stopped
    cli_evals = [e for e in events if e["event"] == "round_eval"
                 and e["algorithm"] == algorithm]
    assert [e["t"] for e in cli_evals] == \
        [r.round for r in got["traj"].records]
    assert cli_evals[-1]["primal"] == last.primal
    assert cli_evals[-1]["gap"] == last.gap


# --- the plain reference against tests/oracle.py ----------------------------

LAM = 1e-3


@pytest.fixture(scope="module")
def demo(small_train):
    """The demo rows as a dense float64 matrix, a random alpha in [0, 1]
    and its w(alpha)."""
    n, d = small_train.n, small_train.num_features
    X = np.zeros((n, d))
    for i in range(n):
        idx, val = small_train.row(i)
        X[i, idx] = val
    alpha = np.random.default_rng(0).random(n)
    w = (X.T @ (small_train.labels * alpha)) / (LAM * n)
    return dict(data=small_train, X=X, y=small_train.labels, alpha=alpha,
                w=w, d=d)


def on_device(demo, k: int):
    """The demo as the program lays it out over ``k`` shards, with alpha
    and w placed to match (the dense layout pads d; w follows)."""
    import jax.numpy as jnp

    from cocoa_tpu.data import shard_dataset

    ds = shard_dataset(demo["data"], k=k, layout="dense")
    alpha = np.zeros((ds.k, ds.n_shard), np.float32)
    lo = 0
    for s, m in enumerate(np.asarray(ds.counts)):
        alpha[s, :m] = demo["alpha"][lo:lo + m]
        lo += m
    w = jnp.zeros(ds.num_features, jnp.float32).at[:demo["d"]].set(
        jnp.asarray(demo["w"], jnp.float32))
    return ds, w, jnp.asarray(alpha)


@pytest.mark.parametrize("k", [1, 4])
def test_reference_agrees_with_the_oracle(demo, k):
    import oracle

    ds, w32, alpha = on_device(demo, k)
    got = reference.recompute(ds, w32, alpha, LAM)
    w64 = np.asarray(w32, np.float64)[:demo["d"]]
    primal = oracle.primal_objective(demo["X"], demo["y"], w64, LAM)
    a_sum = float(np.asarray(alpha, np.float64).sum())
    assert got["primal"] == pytest.approx(primal, rel=1e-6)
    # the reference's dual is D(alpha) with w(alpha) recomputed; the oracle
    # takes w as given — equal here because w IS w(alpha) up to float32
    assert got["dual"] == pytest.approx(
        oracle.dual_objective(w64, a_sum, ds.n, LAM), rel=1e-6)
    assert got["gap"] == pytest.approx(
        oracle.duality_gap(demo["X"], demo["y"], w64, a_sum, LAM), rel=1e-5)
    assert got["w_err"] <= 1e-6 * got["w_scale"]
    assert 0.0 <= got["alpha_min"] and got["alpha_max"] <= 1.0


def test_reference_logistic_against_float64(demo):
    """The logistic objectives against the formulas in host float64."""
    ds, w32, alpha = on_device(demo, 4)
    got = reference.recompute(ds, w32, alpha, LAM, "logistic")
    w64 = np.asarray(w32, np.float64)[:demo["d"]]
    a = np.asarray(alpha, np.float64)[np.asarray(ds.mask) > 0]
    z = demo["y"] * (demo["X"] @ w64)
    primal = np.mean(np.logaddexp(0.0, -z)) + 0.5 * LAM * (w64 @ w64)
    entropy = -(a * np.log(a) + (1 - a) * np.log1p(-a))
    dual = entropy.sum() / ds.n - 0.5 * LAM * (demo["w"] @ demo["w"])
    assert got["primal"] == pytest.approx(primal, rel=1e-6)
    assert got["dual"] == pytest.approx(dual, rel=1e-5)
    with pytest.raises(ValueError, match="no loss 'squared'"):
        reference.recompute(ds, w32, alpha, LAM, "squared")


def test_reference_sees_a_wrong_w(demo):
    import jax.numpy as jnp

    ds, bad, _ = on_device(demo, 4)          # not w(alpha) of THIS alpha
    alpha = jnp.full((ds.k, ds.n_shard), 0.5, jnp.float32) * ds.mask
    got = reference.recompute(ds, bad, alpha, LAM)
    assert got["w_err"] > 1e-2 * got["w_scale"]


def test_reference_refuses_a_layout_it_does_not_read(demo):
    from cocoa_tpu.data import shard_dataset

    ds = shard_dataset(demo["data"], k=2, layout="sparse")
    with pytest.raises(ValueError, match="reads dense rows"):
        reference.recompute(ds, None, None, LAM)
