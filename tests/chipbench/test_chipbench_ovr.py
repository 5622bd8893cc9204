"""The one-vs-rest cell's own pieces, on the CPU: the harness finds
everything ``mnist8m.ovr_cocoa_plus`` names; the configuration's arithmetic
(H, the bytes of the rows and of the kernel's state, the round's floor);
the stand-in generator makes what it says (unit rows, T exchangeable
classes, 2% relabelled), the same from the same seed; the check passes a
float32 job and refuses a w rounded once to bfloat16, an alpha outside the
box and a job that stopped with a class over the target; the job's file
restates its flag line; the whole ``run_cell`` at a tiny size."""

import dataclasses
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import cost_model, registry  # noqa: E402
from chipbench import run as harness  # noqa: E402

from owed import check_cell  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
CELL = "mnist8m.ovr_cocoa_plus"
SMALL = dict(name="small", n=1000, d=24, num_classes=5, num_splits=2,
             local_iter_frac=0.1, dtype="float32", loss="hinge",
             layout="dense", generator="dense_multiclass_planted",
             generator_args=dict(flip=0.02))
SMALL["lambda"] = 1e-2
SEED = 3800000023               # past 2**31: the driver's are large
# the one entry only this cell reads (a reader of its own), and the dense
# cells' readings it shares with them, one entry each (PR 55)
BLOCK = ["ovr_class_step_ns"]
SHARED = ["local_solve_ms", "local_solve_roofline", "round_roofline",
          "eval_share", "unscoped_share"]
GENERIC = ["device_idle_share", "fixed_s", "launches_per_job", "round_ms",
           "top_op_share", "compile_s", "compiles_in_window"]


@pytest.fixture(scope="module")
def gen():
    return registry.load_module(BENCH, "generators",
                                "dense_multiclass_planted")


@pytest.fixture(scope="module")
def small(gen):
    return gen.make(SMALL, SEED)


def small_cell(target=2e-3, **job_kwargs):
    cell = registry.resolve_cell(BENCH, CELL)
    job = json.loads(json.dumps(cell["job"]))
    job["stop"]["target"] = job["kwargs"]["gap_target"] = target
    job["kwargs"].update(job_kwargs)
    job["expect_path"] = {"inner": "sequential", "kernel": "fori"}
    return {**cell, "config": dict(SMALL), "job": job}


@pytest.fixture(scope="module")
def audited(small):
    """One job of the small cell, and its audit."""
    cell = small_cell()
    run_once, _ = harness.make_job(cell, small, None)
    run = run_once()
    check = registry.load_module(BENCH, "checks", "certified_gap_ovr")
    return cell, check, run, check.audit(cell, small, run)


def test_the_harness_resolves_the_cell():
    from cocoa_tpu import solvers

    cell = registry.resolve_cell(BENCH, CELL)
    assert (cell["chips"], cell["config"]["name"], cell["job"]["name"]) == (
        1, "mnist8m", "ovr_cocoa_plus_gap1e-4")
    assert cell["job"]["check"] == "certified_gap_ovr"
    assert cell["config"]["generator"] == "dense_multiclass_planted"
    assert callable(getattr(solvers, cell["job"]["entry"]))
    assert cell["job"]["expect_path"] == {
        "inner": "sequential", "kernel": "pallas", "interpret": False}


def test_the_cell_owes_these_metrics():
    check_cell(BENCH, CELL, BLOCK, GENERIC, SHARED)
    for m in BENCH["per_layer"]:
        if m["name"] in BLOCK + SHARED:
            assert CELL in m.get("workloads", [CELL])
            assert m["moves"] == "job_s"


@pytest.mark.parametrize("name", SHARED + BLOCK)
def test_a_metric_of_the_cell_names_a_reader_the_benchmark_has(name):
    read, params = registry.layer_reader(BENCH, name)
    assert callable(read)
    module = read.__module__.rsplit("_readers_", 1)[-1]
    assert (module, params) == {
        "local_solve_ms": ("scope_share", {"scope": "cocoa_local_solve",
                                           "per_round": True}),
        "local_solve_roofline": ("local_solve_roofline",
                                 {"scope": "cocoa_local_solve"}),
        "round_roofline": ("round_roofline", {}),
        "eval_share": ("scope_share", {"scope": "cocoa_eval"}),
        "unscoped_share": ("scope_share", {"scope": None}),
        "ovr_class_step_ns": ("ovr_class_step_ns", {}),
    }[name]


def test_the_new_reader_reads_nothing_from_a_program_without_classes():
    """On the parent's side of a comparison ``solver_path`` has no
    ``classes``: the reader returns nothing and does not raise."""
    read, _ = registry.layer_reader(BENCH, "ovr_class_step_ns")
    cell = {"solver_path": {"kernel": "pallas"}, "local_iters": 10}
    assert read(None, [], cell) is None
    assert read(None, [], {**cell, "solver_path": None}) is None


def test_the_configurations_arithmetic():
    """H = 12,656 from the harness's own rule; the eighth share's rows are
    3.18 GB; a round's floor counts K x H rows of the PUBLISHED 3,136 B
    (317.5 MB, 0.39 ms at the HBM peak), whatever T and the fold's padding
    make of them; the kernel's state is 64.8 MB of VMEM."""
    from chipbench.readers import round_roofline
    from cocoa_tpu.ops import pallas_sdca

    cell = registry.resolve_cell(BENCH, CELL)
    cfg = cell["config"]
    params, debug, kwargs, h = harness.job_arguments(cell)
    assert h == 12656 and params.local_iters == 12656
    assert (params.n, params.loss, params.lam) == (1012500, "hinge", 1e-4)
    assert (cfg["d"], cfg["num_classes"], cfg["num_splits"]) == (784, 10, 8)
    assert cfg["n"] * 8 == cfg["n_published"] == 8100000
    assert cfg["n"] * cfg["d"] * 4 == 3175200000
    assert kwargs["accel"] == "off" and debug.debug_iter == 10
    model = cost_model.sdca_round(cfg["d"], 8, h)
    assert model["hbm_bytes"] == 8 * 12656 * 3136 == 317513728
    floor = round_roofline.floor_of({
        **cell, "local_iters": h, "device_kind": "TPU v5 lite",
        "solver_path": cell["job"]["expect_path"]})
    assert floor["bound"] == "hbm"
    assert abs(floor["floor_s"] - 317513728 / 819e9) < 1e-9
    n_shard = -(-(-(-cfg["n"] // 8)) // 16) * 16
    assert n_shard == 126576
    assert 8 * -(-n_shard // 128) * pallas_sdca.class_rows(10) * 128 * 4 \
        == 64815104
    assert pallas_sdca.classes_fit(8, n_shard, 784, 10, 4)


@pytest.mark.parametrize("what", ["layout", "unit_rows", "balance",
                                  "relabelled", "labels"])
def test_generator_follows_the_stated_law(small, what):
    k, t = SMALL["num_splits"], SMALL["num_classes"]
    X, cls = np.asarray(small.X), np.asarray(small.classes)
    mask = np.asarray(small.mask)
    rows = SMALL["n"] // k
    if what == "layout":
        assert small.layout == "dense" and small.num_classes == t
        assert X.shape == (k, -(-rows // 16) * 16, SMALL["d"])
        assert cls.shape == mask.shape and cls.dtype == np.int32
        assert list(small.counts) == [rows] * k and small.n == SMALL["n"]
        assert not X[:, rows:].any() and not mask[:, rows:].any()
        assert not cls[:, rows:].any()
        np.testing.assert_allclose(np.asarray(small.sq_norms),
                                   (X * X).sum(-1), rtol=1e-6)
    elif what == "unit_rows":
        np.testing.assert_allclose((X * X).sum(-1)[mask > 0], 1.0,
                                   atol=1e-5)
    elif what == "balance":
        share = np.bincount(cls[mask > 0], minlength=t) / SMALL["n"]
        assert share.min() > 0.1 and share.max() < 0.3      # 1/T = 0.2
    elif what == "relabelled":
        # a class model that fits the planted classes misses the ~2%:
        # one-vs-rest least squares on the rows, argmax over classes
        x, c = X[mask > 0], cls[mask > 0]
        w = np.linalg.lstsq(x, np.eye(t)[c] * 2 - 1, rcond=None)[0]
        assert 0.0 < (np.argmax(x @ w, axis=1) != c).mean() < 0.35
    else:
        np.testing.assert_array_equal(
            np.asarray(small.labels), np.where(cls == 0, 1.0, -1.0) * mask)


def test_generator_same_seed_same_rows(gen, small):
    again = gen.make(SMALL, SEED)
    np.testing.assert_array_equal(np.asarray(again.X), np.asarray(small.X))
    np.testing.assert_array_equal(np.asarray(again.classes),
                                  np.asarray(small.classes))
    other = gen.make(SMALL, SEED + 1)
    assert (np.asarray(other.classes) != np.asarray(small.classes)).any()


def test_generator_refuses_a_program_without_a_class_axis(gen, monkeypatch):
    @dataclasses.dataclass
    class Parents:
        layout: str

    monkeypatch.setattr(gen, "ShardedDataset", Parents)
    with pytest.raises(RuntimeError, match="no class axis"):
        gen.make(SMALL, SEED)


def test_job_restates_its_flag_line():
    """Every flag of the line is a keyword argument of the job or one of
    its parameters, with the same value; --accel=off is on the line."""
    job = registry.resolve_cell(BENCH, CELL)["job"]
    flags = dict(f.lstrip("-").split("=") if "=" in f
                 else (f.lstrip("-"), "true") for f in job["flags"].split())
    assert flags == {"justCoCoA": "true", "math": "fast",
                     "deviceLoop": "true", "rng": "permuted", "accel": "off",
                     "gapTarget": "1e-4", "numRounds": "600",
                     "debugIter": "10"}
    kw = job["kwargs"]
    assert (kw["math"], kw["rng"], kw["accel"], kw["device_loop"]) == (
        "fast", "permuted", "off", True)
    assert kw["gap_target"] == job["stop"]["target"] == 1e-4
    assert job["params"]["num_rounds"] == job["stop"]["round_budget"] == 600
    assert job["debug"]["debug_iter"] == 10
    # the SVM cells' line, but for --accel
    twin = registry.load_json(os.path.join(
        BENCH["_dir"], "jobs", "cocoa_plus_gap1e-4.json"))
    assert job["flags"].replace(" --accel=off", "") == twin["flags"]
    assert {**kw, "accel": "auto"} == twin["kwargs"]


def test_the_audit_passes_a_float32_job(audited):
    cell, check, run, audit = audited
    assert audit["ok"], audit["problems"]
    assert check.job_problem(cell["job"], run) is None
    assert len(audit["gaps"]) == SMALL["num_classes"]
    assert max(audit["gaps"]) <= cell["job"]["stop"]["target"]
    assert max(audit["w_err"]) < check.W_TOL < min(audit["w_err_bf16"])
    assert audit["bf16_w_fails"]


@pytest.mark.parametrize("fault", ["w_bf16", "alpha_out", "class_over",
                                   "off_cadence"])
def test_the_audit_refuses(audited, small, fault):
    import jax.numpy as jnp

    cell, check, run, _ = audited
    bad = dict(run)
    if fault == "w_bf16":
        bad["w"] = run["w"].astype(jnp.bfloat16).astype(jnp.float32)
        said = "w != (1/(lam n))"
    elif fault == "alpha_out":
        bad["alpha"] = run["alpha"].at[1, 0, 0].set(1.5)
        said = "alpha left [0, 1]"
    elif fault == "class_over":
        traj = dataclasses.replace(run["traj"].records[-1])
        traj.class_gaps = [*traj.class_gaps[:-1], 1.0]
        bad["traj"] = type("T", (), dict(records=[traj], stopped="target"))
        said = "no certificate on every class"
    else:
        bad["rounds"] = run["rounds"] + 1
        said = "not at an evaluation"
    problems = check.audit(cell, small, bad)["problems"]
    assert any(said in p for p in problems), problems
    if fault in ("class_over", "off_cadence"):
        assert said in check.job_problem(cell["job"], bad)


def test_run_cell_end_to_end_at_a_tiny_size(tmp_path):
    cell = small_cell()
    result = harness.run_cell(BENCH, cell, seed=SEED, seconds=0.2,
                              trace=False, out_dir=str(tmp_path))
    assert result["correct"] and result["failed"] == 0, result["detail"]
    assert result["attempted"] >= 1
    assert {"job_s", "peak_hbm_gb", "setup_s"} <= set(result["metrics"])
    assert result["metrics"]["comm_rounds"]["value"] % 5 == 0
    detail = result["detail"]
    assert detail["solver_path"]["classes"] == SMALL["num_classes"]
    assert detail["audit"]["ok"]
    rounds = {j["rounds"] for j in detail["jobs"]}
    assert len(rounds) == 1 and rounds.pop() % 10 == 0     # one stop


def test_config_states_every_guess():
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    assert cfg["reduced"] == ["n"]
    said = " ".join(cfg["assumed"])
    for word in ("stand-in", "unit length", "lambda", "remembered",
                 "K = 8"):
        assert word in said, word
    for key in ("sizing_rule", "deployment", "guarantees", "n_published"):
        assert cfg[key]
    assert "eight" in cfg["sizing_rule"] and "four" in cfg["sizing_rule"]
