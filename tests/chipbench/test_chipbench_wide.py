"""The wide-class cell's own pieces, on the CPU: the harness finds
everything ``ilsvrc1k.ovr_cocoa_plus`` names; it owes at least the six
``wide_*`` metrics, the seven generic ones, the twelve shared readings
(the cold account's nine among them) and three end-to-end ones; the configuration's arithmetic (H,
the block, the bytes of the rows and of alpha, the two floors); the
stand-in generator makes what it says (unit rows, exchangeable classes, 2%
relabelled), the same from the same seed, and asks the program before it
makes anything; the new readers read nothing where the run's record states
no block solve; the check passes a float32 job and refuses a W rounded once
to bfloat16, an alpha off the box, a class over the target, a stop off the
cadence and a model on the lanes past T; the job's file restates its flag
line; the whole ``run_cell`` at a tiny size."""

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

from chipbench import cost_model, cost_model_wide, registry  # noqa: E402
from chipbench import run as harness  # noqa: E402

from owed import COLD, GENERIC, check_cell  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
CELL = "ilsvrc1k.ovr_cocoa_plus"
SMALL = dict(name="small", n=3001, d=70, num_classes=24, num_splits=2,
             local_iter_frac=0.2, dtype="float32", loss="hinge",
             layout="dense", generator="dense_multiclass_wide",
             generator_args=dict(flip=0.02))
SMALL["lambda"] = 1e-2
SEED = 5300000041               # past 2**31: the driver's are large
SCOPED = {"wide_products_share": "cocoa_wide_products",
          "wide_replay_share": "cocoa_wide_replay"}
SOLVE = {"wide_solve_ms": "ms", "wide_class_step_ns": "class_step_ns",
         "wide_solve_roofline": "roofline"}
BLOCK = ["wide_solve_ms", "wide_products_share", "wide_replay_share",
         "wide_class_step_ns", "wide_solve_roofline", "wide_eval_roofline"]
# the readings the cell shares with every other cell, one entry each: the
# two scope shares and, since PR 55, the nine parts of the cold account
# (no ``workloads`` key), and ``indices_share``, whose list it stands in
SHARED = ["eval_share", "unscoped_share", "indices_share"] + COLD


@pytest.fixture(scope="module")
def gen():
    return registry.load_module(BENCH, "generators", "dense_multiclass_wide")


@pytest.fixture(scope="module")
def small(gen):
    """``gen.make`` with the pre-flight answered yes (at this size the
    sublane kernel would hold the set)."""
    real = gen.preflight
    gen.preflight = lambda config, resolve=None: {}
    try:
        return gen.make(SMALL, SEED)
    finally:
        gen.preflight = real


@pytest.fixture
def wide(monkeypatch):
    """The sublane kernel holds nothing, as at the cell's size."""
    from cocoa_tpu.ops import pallas_sdca

    monkeypatch.setattr(pallas_sdca, "CLASS_VMEM_BUDGET", 0)


def small_cell(target=5e-3, **expect):
    cell = registry.resolve_cell(BENCH, CELL)
    job = json.loads(json.dumps(cell["job"]))
    job["stop"]["target"] = job["kwargs"]["gap_target"] = target
    job["expect_path"] = {"inner": "block", "kernel": "products",
                          "class_axis": "lanes", "chain": "xla", **expect}
    return {**cell, "config": dict(SMALL), "job": job}


@pytest.fixture(scope="module")
def audited(small):
    """One job of the small cell (XLA's replay), and its audit."""
    from cocoa_tpu.ops import pallas_sdca

    budget = pallas_sdca.CLASS_VMEM_BUDGET
    pallas_sdca.CLASS_VMEM_BUDGET = 0
    try:
        cell = small_cell()
        run_once, _ = harness.make_job(cell, small, None)
        run = run_once()
    finally:
        pallas_sdca.CLASS_VMEM_BUDGET = budget
    check = registry.load_module(BENCH, "checks", "certified_gap_wide")
    return cell, check, run, check.audit(cell, small, run)


def test_the_harness_resolves_the_cell():
    from cocoa_tpu import solvers

    cell = registry.resolve_cell(BENCH, CELL)
    cfg, job = cell["config"], cell["job"]
    assert (cell["chips"], cfg["name"], cell["traffic"]) == (
        1, "ilsvrc1k", job["name"])
    assert job["name"].startswith("ovr_cocoa_plus_gap") and \
        job["name"].endswith("_wide")
    assert job["check"] == "certified_gap_wide"
    assert cfg["generator"] == "dense_multiclass_wide"
    assert callable(getattr(solvers, job["entry"]))
    assert job["expect_path"] == {
        "inner": "block", "kernel": "products", "class_axis": "lanes",
        "chain": "pallas", "interpret": False}
    # nothing on the line or in the call picks a kernel, a form or a block
    assert not {"pallas", "block_size", "block_chain"} & set(job["kwargs"])
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "ilsvrc1k"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) == 196
    assert entry["reduced"] == cfg["reduced"] == ["n"]
    assert cfg["architecture"] is None          # a deployment, not a model
    assert set(job["audit"]) == {"w_tol", "gap_tol"}
    assert len(cell["why"]) <= 200 and len(entry["why"]) <= 200


def test_the_cell_owes_these_metrics():
    check_cell(BENCH, CELL, BLOCK, GENERIC, SHARED)
    for m in BENCH["per_layer"]:
        if m["name"] in BLOCK:
            assert m["workloads"] == [CELL] and m["moves"] == "job_s"
        if m["name"] in SHARED:
            assert CELL in m.get("workloads", [CELL])


@pytest.mark.parametrize("name", BLOCK)
def test_a_new_metric_names_its_reader(name):
    read, params = registry.layer_reader(BENCH, name)
    module = read.__module__.rsplit("_readers_", 1)[-1]
    if name in SCOPED:
        want = ("scope_share", {"scope": SCOPED[name]})
    elif name in SOLVE:
        want = ("wide_solve", {"part": SOLVE[name]})
    else:
        want = (name, {})
    assert (module, params) == want


@pytest.mark.parametrize("name", list(SOLVE) + ["wide_eval_roofline"])
def test_a_new_reader_reads_nothing_without_a_block_solve_on_the_lanes(name):
    """On a program whose record states no block solve on the lanes (every
    other cell, a tree from before the path) the reader returns nothing and
    does not raise."""
    read, params = registry.layer_reader(BENCH, name)
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    cell = {"name": "nowhere", "config": cfg,
            "job": {"debug": {"debug_iter": 10}}, "local_iters": 10,
            "solver_path": {"inner": "sequential", "kernel": "pallas",
                            "classes": 10, "class_axis": "sublanes"}}
    jobs = [{"rounds": 20}]
    assert read(None, jobs, cell, **params) is None
    assert read(None, jobs, {**cell, "solver_path": None}, **params) is None
    assert read(None, jobs, {**cell, "solver_path": {
        "inner": "sequential", "class_axis": "lanes"}}, **params) is None


def test_the_solve_reader_adds_up_the_nested_scopes(monkeypatch):
    """``wide_solve`` reads every op whose scope path holds the solve's
    scope: its own and those of the two halves nested in it, which
    ``scope_share`` puts under their own names alone."""
    import types

    from chipbench import phases

    read, _ = registry.layer_reader(BENCH, "wide_solve_ms")
    paths = {"gather.1": "jit(run)/while/body/cocoa_local_solve/gather",
             "dot.2": "jit(run)/cocoa_local_solve/while/body/"
                      "cocoa_wide_products/dot_general",
             "replay.3": "jit(run)/cocoa_local_solve/while/body/"
                         "cocoa_wide_replay/pallas_call",
             "eval.4": "jit(run)/cocoa_eval/dot_general", "copy.5": ""}
    ph = types.SimpleNamespace(scoped=True, paths=paths)
    monkeypatch.setattr(phases, "load", lambda cell: ph)
    trace = types.SimpleNamespace(ops={"gather.1": 0.010, "dot.2": 0.100,
                                       "replay.3": 0.050, "eval.4": 0.500,
                                       "copy.5": 0.020}, busy_s=0.68)
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    cell = {"name": CELL, "config": cfg, "local_iters": 4003, "chips": 1,
            "device_kind": "TPU v5 lite",
            "job": {"debug": {"debug_iter": 10}},
            "solver_path": {"inner": "block", "class_axis": "lanes",
                            "classes": 1000}}
    jobs = [{"rounds": 10}, {"rounds": 10}]
    per_round = 0.160 / 20
    assert read(trace, jobs, cell, part="ms") == pytest.approx(8.0)
    assert read(trace, jobs, cell, part="class_step_ns") == pytest.approx(
        1e9 * per_round / (8 * 4003 * 1000))
    floor = 4.0 * 8 * 4003 * 4096 * 1000 / 197e12
    assert read(trace, jobs, cell, part="roofline") == pytest.approx(
        100 * floor / per_round)
    with pytest.raises(ValueError, match="not 'other'"):
        read(trace, jobs, cell, part="other")


def test_the_configurations_arithmetic():
    """H = 4,003 from the harness's own rule and 32,024 row-steps a round,
    each a thousand class-steps, in 16 blocks of 256 a shard; the rows are
    5.25 GB and alpha 1.31 GB at T_pad = 1,024; a round's floor is 5.2e11
    operations (2.7 ms at the bfloat16 peak) over 0.81 GB (0.99 ms), an
    evaluation's 2.6e12 (13.3 ms) over 6.55 GB (8.0 ms): both
    compute-bound."""
    from cocoa_tpu.data.sharding import class_pad, pad_rows, split_sizes
    from cocoa_tpu.ops.block_lanes import block_lanes_plan

    cell = registry.resolve_cell(BENCH, CELL)
    cfg = cell["config"]
    params, debug, kwargs, h = harness.job_arguments(cell)
    assert h == 4003 and params.local_iters == 4003
    assert (params.n, params.loss, params.lam) == (320292, "hinge", 1e-4)
    assert (cfg["d"], cfg["num_classes"], cfg["n_published"],
            cfg["num_splits"]) == (4096, 1000, 1281167, 8)
    assert cfg["n"] == -(-cfg["n_published"] // 4)     # a quarter, rounded up
    assert cfg["num_splits"] * h == 32024
    assert abs(params.lam * params.n - 32.0292) < 1e-9       # lambda n = 32
    assert kwargs["accel"] == "off"
    assert debug.debug_iter == cell["job"]["debug"]["debug_iter"]
    t_pad = class_pad(cfg["num_classes"])
    n_shard = pad_rows(int(split_sizes(cfg["n"], 8).max()))
    assert (t_pad, n_shard) == (1024, 40048)
    assert round(8 * n_shard * cfg["d"] * 4 / 1e9, 2) == 5.25   # the rows
    assert round(8 * n_shard * t_pad * 4 / 1e9, 2) == 1.31      # alpha
    assert round(cfg["d"] * t_pad * 4 / 1e6, 1) == 16.8         # W; 8 V_k
    plan = block_lanes_plan(h, t_pad)
    assert (plan.block, plan.blocks) == (256, 16)
    peaks = cost_model.peaks_for("TPU v5 lite")
    solve = cost_model_wide.solve_round(8, h, cfg["d"], 1000)
    assert solve["flops"] == 4.0 * 32024 * 4096 * 1000
    assert round(solve["flops"] / 1e11, 1) == 5.2
    assert round(solve["hbm_bytes"] / 1e9, 2) == 0.81
    floor = cost_model.round_floor_s(solve, peaks)
    assert floor["bound"] == "flops"
    assert round(1e3 * floor["flop_s"], 1) == 2.7
    assert round(1e3 * floor["hbm_s"], 2) == 0.99
    evals = cost_model_wide.eval_pass(cfg["n"], cfg["d"], 1000)
    assert round(evals["flops"] / 1e12, 1) == 2.6
    assert round(evals["hbm_bytes"] / 1e9, 2) == 6.55
    floor = cost_model.round_floor_s(evals, peaks)
    assert floor["bound"] == "flops"
    assert round(1e3 * floor["flop_s"], 1) == 13.3
    assert round(1e3 * floor["hbm_s"], 1) == 8.0


@pytest.mark.parametrize("what", ["layout", "unit_rows", "classes",
                                  "labels"])
def test_generator_follows_the_stated_law(small, what):
    k, t, d = SMALL["num_splits"], SMALL["num_classes"], SMALL["d"]
    x, ids = np.asarray(small.X), np.asarray(small.classes)
    mask = np.asarray(small.mask) > 0
    if what == "layout":
        assert small.layout == "dense" and small.num_classes == t
        assert x.shape == (k, small.n_shard, d) and ids.shape == mask.shape
        assert ids.dtype == np.int32 and x.dtype == np.float32
        assert list(small.counts) == [1501, 1500] and small.n == SMALL["n"]
        assert mask.sum(1).tolist() == [1501, 1500]
        assert not x[~mask].any() and not ids[~mask].any()
        np.testing.assert_allclose(np.asarray(small.sq_norms),
                                   (x * x).sum(-1), rtol=1e-6)
    elif what == "unit_rows":
        np.testing.assert_allclose((x * x).sum(-1)[mask], 1.0, atol=1e-5)
    elif what == "classes":
        # exchangeable: every class about 1 / T of the rows
        counts = np.bincount(ids[mask], minlength=t)
        assert counts.min() > 0.5 * SMALL["n"] / t
        assert counts.max() < 1.6 * SMALL["n"] / t
    else:
        np.testing.assert_array_equal(
            np.asarray(small.labels), np.where(ids == 0, 1.0, -1.0) * mask)


def test_generator_same_seed_same_rows(gen, small, monkeypatch):
    monkeypatch.setattr(gen, "preflight", lambda config, resolve=None: {})
    again = gen.make(SMALL, SEED)
    for f in ("X", "classes", "labels"):
        np.testing.assert_array_equal(np.asarray(getattr(again, f)),
                                      np.asarray(getattr(small, f)))
    other = gen.make(SMALL, SEED + 1)
    assert (np.asarray(other.classes) != np.asarray(small.classes)).any()


def test_generator_makes_the_rows_block_by_block(gen, monkeypatch):
    """Several row blocks a shard, the last one sharing rows with its
    neighbour: every real row is a unit row with a class of its own."""
    monkeypatch.setattr(gen, "preflight", lambda config, resolve=None: {})
    monkeypatch.setattr(gen, "ROW_BLOCK", 512)
    ds = gen.make(SMALL, SEED)
    assert ds.n_shard % 512         # 1,504 rows: the last block starts early
    x, mask = np.asarray(ds.X), np.asarray(ds.mask) > 0
    np.testing.assert_allclose((x * x).sum(-1)[mask], 1.0, atol=1e-5)
    assert not x[~mask].any()
    assert np.unique(x[mask][:, 0]).size == SMALL["n"]    # no row twice


def test_preflight_asks_the_program_first(gen, wide):
    """Only a program that would take a block of rows a step with the
    class axis on the lanes goes on: the parent's answer (``fori``, a class
    a sublane) is refused before anything is made, with what it resolved
    to."""
    class Path:
        def __init__(self, **kw):
            self.kw = kw

        def as_dict(self):
            return self.kw

    seen = []

    def parents(ds, h, mesh, math):
        seen.append((ds.n, ds.num_features, ds.X.shape, ds.classes.shape,
                     ds.num_classes, h, math))
        return Path(inner="sequential", kernel="fori",
                    class_axis="sublanes")

    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    with pytest.raises(RuntimeError, match="kernel='fori'.*not a block"):
        gen.preflight(cfg, parents)
    assert seen == [(320292, 4096, (8, 40048, 4096), (8, 40048), 1000, 4003,
                     "fast")]
    ok = gen.preflight(cfg, lambda *a, **k: Path(inner="block",
                                                 class_axis="lanes"))
    assert ok == {"inner": "block", "class_axis": "lanes"}
    # the program's own answer on these shapes, from the shapes alone
    path = gen.preflight(cfg)
    assert (path["inner"], path["kernel"], path["class_axis"],
            path["plan"]["block"]) == ("block", "products", "lanes", 256)


def test_job_restates_its_flag_line():
    """mnist8m's line at this job's target, cadence and budget, and no flag
    that picks a kernel; the target and the cadence are the traffic's
    name's, and the sizing rules' readings are in the job's ``sizing``."""
    job = registry.resolve_cell(BENCH, CELL)["job"]
    flags = dict(f.lstrip("-").split("=") if "=" in f
                 else (f.lstrip("-"), "true") for f in job["flags"].split())
    target, every = flags["gapTarget"], flags["debugIter"]
    assert flags == {"justCoCoA": "true", "math": "fast",
                     "deviceLoop": "true", "rng": "permuted", "accel": "off",
                     "gapTarget": target, "numRounds": "600",
                     "debugIter": every}
    assert target in ("1e-3", "1e-2") and every in ("10", "25", "50")
    kw = job["kwargs"]
    assert kw["gap_target"] == job["stop"]["target"] == float(target)
    assert job["params"]["num_rounds"] == job["stop"]["round_budget"] == 600
    assert job["debug"]["debug_iter"] == int(every)
    assert job["name"] == f"ovr_cocoa_plus_gap{target}_e{every}_wide"
    assert "15 s" in job["sizing"] and "eight seeds" in job["sizing"]
    assert target in registry.resolve_cell(BENCH, CELL)["why"]
    # mnist8m's line, but for the target, the cadence
    twin = registry.load_json(os.path.join(
        BENCH["_dir"], "jobs", "ovr_cocoa_plus_gap1e-4.json"))
    assert job["flags"].replace(f"--gapTarget={target}", "--gapTarget=1e-4")\
        .replace(f"--debugIter={every}", "--debugIter=10") == twin["flags"]
    assert {**kw, "gap_target": 1e-4} == twin["kwargs"]


def test_the_audit_passes_a_float32_job(audited):
    cell, check, run, audit = audited
    assert audit["ok"], audit["problems"]
    # a timed job is judged by its records and its (W, alpha) let go of
    timed = dict(run)
    assert check.job_problem(cell["job"], timed) is None
    assert timed["w"] is None and timed["alpha"] is None
    assert len(audit["gaps"]) == SMALL["num_classes"]
    assert max(audit["gaps"]) <= cell["job"]["stop"]["target"]
    assert audit["w_err_max"] < 2e-6 < 1e-4 < audit["w_err_bf16_least"]
    assert audit["bf16_w_fails"] and audit["pad_lanes_max"] == 0.0
    assert audit["gap_off_max"] < 1e-6
    assert run["w"].shape == (SMALL["d"], 8, 128)
    assert run["alpha"].shape == (2, 1504, 8, 128)
    assert run["solver_path"]["plan"]["blocks"] >= 2


@pytest.mark.parametrize("fault", ["w_bf16", "alpha_out", "class_over",
                                   "off_cadence", "pad_lane"])
def test_the_audit_refuses(audited, small, fault):
    import jax.numpy as jnp

    cell, check, run, _ = audited
    bad = dict(run)
    if fault == "w_bf16":
        bad["w"] = run["w"].astype(jnp.bfloat16).astype(jnp.float32)
        said = "w != (1/(lam n))"
    elif fault == "alpha_out":
        bad["alpha"] = run["alpha"].at[1, 0, 0, 0].set(1.5)
        said = "alpha left [0, 1]"
    elif fault == "class_over":
        traj = dataclasses.replace(run["traj"].records[-1])
        traj.class_gaps = [*traj.class_gaps[:-1], 1.0]
        bad["traj"] = type("T", (), dict(records=[traj], stopped="target"))
        said = "no certificate on every class"
    elif fault == "pad_lane":
        bad["w"] = run["w"].at[0, 7, 127].set(0.5)
        said = "lanes past T"
    else:
        bad["rounds"] = run["rounds"] + 1
        said = "not at an evaluation"
    problems = check.audit(cell, small, bad)["problems"]
    assert any(said in p for p in problems), problems
    if fault in ("class_over", "off_cadence"):
        assert said in check.job_problem(cell["job"], bad)


def test_a_limit_a_bfloat16_w_passes_is_a_problem(audited, small):
    cell, check, run, _ = audited
    loose = json.loads(json.dumps(cell["job"]))
    loose["audit"]["w_tol"] = 0.5
    problems = check.audit({**cell, "job": loose}, small, run)["problems"]
    assert any("passes a bfloat16 W" in p for p in problems), problems


def test_the_reference_is_the_float64_sum(small, audited):
    """``reference_wide``'s w(alpha), two float32 levels, against a float64
    sum on the host, from the same alpha and class ids."""
    from chipbench import reference_wide

    cell, _, run, audit = audited
    lam, n, t = SMALL["lambda"], SMALL["n"], SMALL["num_classes"]
    x = np.asarray(small.X, np.float64).reshape(-1, SMALL["d"])
    ids = np.asarray(small.classes).reshape(-1)
    m = np.asarray(small.mask).reshape(-1)
    a = np.asarray(run["alpha"], np.float64).reshape(len(ids), -1)[:, :t]
    y = np.where(ids[:, None] == np.arange(t)[None], 1.0, -1.0) * m[:, None]
    w_ref = ((a * y).T @ x) / (lam * n)
    w = np.asarray(run["w"], np.float64).reshape(SMALL["d"], -1)[:, :t].T
    err = np.abs(w - w_ref).max(1) / np.maximum(1, np.abs(w_ref).max(1))
    np.testing.assert_allclose(audit["w_err"], err, atol=2e-7)
    again = reference_wide.recompute(small, run["w"], run["alpha"], lam,
                                     row_block=200)     # several blocks
    np.testing.assert_allclose(again["w_err"], audit["w_err"], atol=2e-7)
    np.testing.assert_allclose(again["gaps"], audit["gaps"], atol=1e-6)


def test_run_cell_end_to_end_at_a_tiny_size(tmp_path, wide, gen,
                                            monkeypatch):
    """The whole harness on XLA's replay (this platform's own answer once
    the sublane kernel holds nothing)."""
    cell = small_cell()
    result = harness.run_cell(BENCH, cell, seed=SEED, seconds=0.2,
                              trace=False, out_dir=str(tmp_path))
    assert result["correct"] and result["failed"] == 0, result["detail"]
    assert result["attempted"] >= 1
    assert {"job_s", "comm_rounds", "peak_hbm_gb", "setup_s"} <= set(
        result["metrics"])
    every = cell["job"]["debug"]["debug_iter"]
    assert result["metrics"]["comm_rounds"]["value"] % every == 0
    detail = result["detail"]
    path = detail["solver_path"]
    assert (path["classes"], path["class_axis"], path["class_tiles"],
            path["inner"], path["kernel"], path["chain"]) == (
        24, "lanes", 1, "block", "products", "xla")
    assert detail["audit"]["ok"] and not detail["problems"]
