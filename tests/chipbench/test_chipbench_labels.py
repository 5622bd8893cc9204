"""The label-set cell's own pieces, on the CPU: the harness finds everything
``amazoncat13k.ovr_cocoa_plus`` names; it owes at least its three
``xmc_*`` entries, the shared readings it stands listed in, the seven
generic ones and three end-to-end ones, each over a reader the benchmark
has; the configuration's arithmetic (H, the
steps a round, the bytes of alpha and W, the two floors); the stand-in
generator makes what it says (unit rows, a bias column in every row, label
sets whose frequencies follow the rank law), the same from the same seed,
and asks the program before it makes anything; the check passes a float32
job and refuses a W rounded once to bfloat16, an alpha off the box, a class
over the target, a stop off the cadence and a model on the lanes past T;
the job's file restates its flag line; the whole ``run_cell`` at a tiny
size on the interpreted kernel."""

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

from chipbench import cost_model_labels, registry  # noqa: E402
from chipbench import run as harness  # noqa: E402

from owed import COLD, check_cell  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
CELL = "amazoncat13k.ovr_cocoa_plus"
SMALL = dict(name="small", n=1024, d=300, mean_nnz=10.0, num_classes=24,
             published_labels=320, num_splits=2, local_iter_frac=0.1,
             dtype="float32", loss="hinge", layout="sparse",
             generator="sparse_multilabel",
             generator_args=dict(mean_nnz=10.0, sigma_nnz=0.5, max_nnz=24,
                                 labels_per_row=5.04, first_rank=4,
                                 label_slots=8, flip=0.02, planted_density_inv=2,
                                 planted_hot_cut=8))
SMALL["lambda"] = 1e-2
SEED = 4800000029               # past 2**31: the driver's are large
# the scope readings the cell shares with other cells, one entry each
# (PR 55): {entry: (scope, per round)}
SCOPED = {"local_solve_ms": ("cocoa_local_solve", True),
          "sparse_gather_share": ("cocoa_sparse_gather", False),
          "eval_share": ("cocoa_eval", False),
          "sparse_dw_reduce_share": ("cocoa_dw_reduce", False),
          "unscoped_share": (None, False)}
# the three entries only this cell reads (readers of its own)
OWN_READERS = ["xmc_class_step_ns", "xmc_solve_roofline",
               "xmc_eval_roofline"]
BLOCK = OWN_READERS
SHARED = list(SCOPED) + ["ctr_step_ns"] + COLD
GENERIC = ["device_idle_share", "fixed_s", "launches_per_job", "round_ms",
           "top_op_share", "compile_s", "compiles_in_window"]


@pytest.fixture(scope="module")
def gen():
    return registry.load_module(BENCH, "generators", "sparse_multilabel")


@pytest.fixture(scope="module")
def small(gen):
    """``gen.make`` with the pre-flight answered yes (this process's
    platform is cpu, where the program's own answer is ``fori``)."""
    real = gen.preflight
    gen.preflight = lambda config, resolve=None: {}
    try:
        return gen.make(SMALL, SEED)
    finally:
        gen.preflight = real


def small_cell(target=5e-3, **expect):
    cell = registry.resolve_cell(BENCH, CELL)
    job = json.loads(json.dumps(cell["job"]))
    job["stop"]["target"] = job["kwargs"]["gap_target"] = target
    job["expect_path"] = {"inner": "sequential", "kernel": "fori",
                          "class_axis": "lanes", **expect}
    return {**cell, "config": dict(SMALL), "job": job}


@pytest.fixture(scope="module")
def audited(small):
    """One job of the small cell (the plain-XLA round), and its audit."""
    cell = small_cell()
    run_once, _ = harness.make_job(cell, small, None)
    run = run_once()
    check = registry.load_module(BENCH, "checks", "certified_gap_labels")
    return cell, check, run, check.audit(cell, small, run)


def test_the_harness_resolves_the_cell():
    from cocoa_tpu import solvers

    cell = registry.resolve_cell(BENCH, CELL)
    cfg, job = cell["config"], cell["job"]
    assert (cell["chips"], cfg["name"], job["name"]) == (
        1, "amazoncat13k", "ovr_cocoa_plus_gap1e-2_e5_labels")
    assert job["check"] == "certified_gap_labels"
    assert cfg["generator"] == "sparse_multilabel"
    assert callable(getattr(solvers, job["entry"]))
    assert job["expect_path"] == {
        "inner": "sequential", "kernel": "pallas", "state": "hbm",
        "class_axis": "lanes", "interpret": False}
    # nothing on the line or in the call picks a kernel, a layout or a plan
    assert not {"pallas", "block_size", "hot_cols"} & set(job["kwargs"])
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "amazoncat13k"]
    assert entry["source"] == cfg["source"] and len(entry["source"]) <= 200
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["architecture"] is None          # a deployment, not a model
    assert set(job["audit"]) == {"w_tol", "gap_tol"}


def test_the_cell_owes_these_metrics():
    check_cell(BENCH, CELL, BLOCK, GENERIC, SHARED)
    moves = {"cold": "setup_s", "hbm": "peak_hbm_gb"}
    for m in BENCH["per_layer"]:
        if m["name"] in BLOCK:
            assert m["workloads"] == [CELL]
        if m["name"] in BLOCK + SHARED:
            assert CELL in m.get("workloads", [CELL])
            assert m["moves"] == moves.get(m["name"].split("_")[0], "job_s")


@pytest.mark.parametrize("name", BLOCK + SHARED)
def test_a_metric_of_the_cell_names_a_reader_the_benchmark_has(name):
    read, params = registry.layer_reader(BENCH, name)
    assert callable(read)
    module = read.__module__.rsplit("_readers_", 1)[-1]
    if name in SCOPED:
        scope, per_round = SCOPED[name]
        want = ("scope_share", {"scope": scope, **(
            {"per_round": True} if per_round else {})})
    elif name in COLD:
        want = ("cold_account", {"part": name})
    else:
        want = (name, {})
    assert (module, params) == want


@pytest.mark.parametrize("name", OWN_READERS)
def test_a_new_reader_reads_nothing_without_a_class_axis_on_the_lanes(name):
    """On a program whose record states no class axis (every T = 1 cell, a
    tree from before the field) the reader returns nothing and does not
    raise."""
    read, _ = registry.layer_reader(BENCH, name)
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    cell = {"config": cfg, "job": {"debug": {"debug_iter": 5}},
            "solver_path": {"kernel": "pallas", "classes": 10,
                            "class_axis": "sublanes"}, "local_iters": 10}
    assert read(None, [], cell) is None
    assert read(None, [], {**cell, "solver_path": None}) is None
    assert read(None, [], {**cell, "solver_path": {"kernel": "pallas"}}) \
        is None


def test_the_configurations_arithmetic():
    """H = 14,827 from the harness's own rule and 118,616 steps a round;
    alpha is 4.86 GB and W 0.84 GB at T_pad = 1,024; a round's floor is
    1.12 GB (1.37 ms at the HBM peak) and an evaluation's 6.26 GB (7.6
    ms)."""
    from chipbench import cost_model
    from cocoa_tpu.data.sharding import class_pad, pad_rows, split_sizes
    from cocoa_tpu.ops.pallas_sparse_lanes import lanes_plan

    cell = registry.resolve_cell(BENCH, CELL)
    cfg = cell["config"]
    params, debug, kwargs, h = harness.job_arguments(cell)
    assert h == 14827 and params.local_iters == 14827
    assert (params.n, params.loss, params.lam) == (1186239, "hinge", 1e-4)
    assert (cfg["d"], cfg["num_classes"], cfg["published_labels"],
            cfg["num_splits"], cfg["mean_nnz"]) == (203882, 1000, 13330, 8,
                                                    71.2)
    assert cfg["num_splits"] * h == 118616
    assert abs(params.lam * params.n - 118.6239) < 1e-9      # lambda n = 119
    assert kwargs["accel"] == "off" and debug.debug_iter == 5
    t_pad = class_pad(cfg["num_classes"])
    n_shard = pad_rows(int(split_sizes(cfg["n"], 8).max()))
    assert (t_pad, n_shard) == (1024, 148288)
    assert round(8 * n_shard * t_pad * 4 / 1e9, 2) == 4.86    # alpha
    assert round(cfg["d"] * t_pad * 4 / 1e9, 2) == 0.84       # W
    width = cfg["generator_args"]["max_nnz"]
    assert round(2 * 8 * n_shard * width * 4 / 1e9, 2) == 2.43    # the rows
    plan = lanes_plan(width, h, 4, t_pad,
                      cfg["generator_args"]["label_slots"])
    assert (plan.t, plan.s, plan.m, plan.w_r, plan.direct, plan.t_pad) == (
        1, 14848, 256, 256, True, 1024)
    peak = cost_model.peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    solve = cost_model_labels.solve_round_bytes(8, h, cfg["mean_nnz"], 1000)
    assert solve == 118616 * 71.2 * 20 + 118616 * (16 + 8000)
    assert round(solve / 1e9, 2) == 1.12
    assert round(1e3 * solve / peak, 2) == 1.37
    evals = cost_model_labels.eval_pass_bytes(cfg["n"], cfg["d"],
                                              cfg["mean_nnz"], 1000)
    assert round(evals / 1e9, 2) == 6.26
    assert round(1e3 * evals / peak, 1) == 7.6


@pytest.mark.parametrize("what", ["layout", "unit_rows", "bias", "columns",
                                  "shares", "sets", "labels"])
def test_generator_follows_the_stated_law(gen, small, what):
    k, t = SMALL["num_splits"], SMALL["num_classes"]
    args = SMALL["generator_args"]
    cols, vals = np.asarray(small.sp_indices), np.asarray(small.sp_values)
    ids, mask = np.asarray(small.classes), np.asarray(small.mask) > 0
    rows, lens = SMALL["n"] // k, (vals != 0).sum(-1)
    if what == "layout":
        assert small.layout == "sparse" and small.num_classes == t
        assert cols.shape == vals.shape == (k, rows, args["max_nnz"])
        assert ids.shape == (k, rows, args["label_slots"])
        assert ids.dtype == np.int32 and small.label_slots == 8
        assert list(small.counts) == [rows] * k and small.n == SMALL["n"]
        assert lens[mask].min() >= 1 and lens.max() <= args["max_nnz"]
        assert abs(lens[mask].mean() - args["mean_nnz"]) < 1.0
        np.testing.assert_allclose(np.asarray(small.sq_norms),
                                   (vals * vals).sum(-1), rtol=1e-6)
    elif what == "unit_rows":
        np.testing.assert_allclose((vals * vals).sum(-1)[mask], 1.0,
                                   atol=1e-5)
    elif what == "bias":
        # a row's last nonzero is column d - 1, and no other slot is
        last = np.take_along_axis(cols, (lens - 1)[..., None], -1)[..., 0]
        assert (last[mask] == SMALL["d"] - 1).all()
        assert ((cols == SMALL["d"] - 1).sum(-1)[mask] == 1).all()
    elif what == "columns":
        # ascending, no column twice, padding slots hold column 0, value 0
        live = np.arange(args["max_nnz"]) < lens[..., None]
        assert (np.diff(cols, axis=-1)[live[..., 1:]] > 0).all()
        assert not cols[~live].any() and not vals[~live].any()
    elif what == "shares":
        # the rank law, the batch every (320 / 24)-th rank from rank
        # ``first_rank`` = 4 on: the stride that holds what the MEAN batch
        # holds, 5.04 * 24 / 320 = 0.378 labels a row, not the one stride
        # with the head label's 0.79 in it
        share = gen.label_shares(SMALL)
        law = 5.04 / np.sum(1 / np.arange(1, 321))
        assert abs(share[0] - law / 4) < 1e-12
        assert abs(share[1] - law / (4 + 320 // 24)) < 1e-12
        assert abs(share.sum() - 5.04 * 24 / 320) < 0.03
        counts = np.bincount(ids[mask][ids[mask] >= 0], minlength=t)
        assert abs(counts[0] / SMALL["n"] - share[0]) < 0.05
        assert abs(counts.sum() / SMALL["n"] - share.sum()) < 0.1
        assert counts[0] > 2 * counts[1:].max()         # a head and a tail
        # the cell's own batch: ISSUE 48's ~0.38 positives a row, its most
        # frequent label a tenth of the rows and its rarest 45 of them
        cfg = registry.resolve_cell(BENCH, CELL)["config"]
        real = gen.label_shares(cfg)
        assert cfg["generator_args"]["first_rank"] == 5
        assert abs(real.sum() - 5.04 * 1000 / 13330) < 0.02
        assert abs(real[0] - 0.100) < 1e-3 and real[0] == real.max()
        assert 44 < real[-1] * cfg["n"] < 46
        with pytest.raises(ValueError, match="past 13330"):
            gen.label_shares({**cfg, "generator_args": {
                **cfg["generator_args"], "first_rank": 16}})
    elif what == "sets":
        # ascending ids, -1 past the set and on every padding row; some row
        # in no label's set and some row in several
        held = (ids >= 0).sum(-1)
        filled = np.arange(ids.shape[-1]) < held[..., None]
        assert (ids[filled] >= 0).all() and (ids[~filled] == -1).all()
        assert (ids[filled] < t).all()
        pairs = filled[..., 1:]
        assert (np.diff(ids, axis=-1)[pairs] > 0).all()
        assert (held[mask] == 0).any() and (held[mask] > 1).any()
    else:
        np.testing.assert_array_equal(
            np.asarray(small.labels),
            np.where((ids == 0).any(-1), 1.0, -1.0) * mask)


def test_generator_same_seed_same_rows(gen, small, monkeypatch):
    monkeypatch.setattr(gen, "preflight", lambda config, resolve=None: {})
    again = gen.make(SMALL, SEED)
    for f in ("sp_indices", "sp_values", "classes", "labels"):
        np.testing.assert_array_equal(np.asarray(getattr(again, f)),
                                      np.asarray(getattr(small, f)))
    other = gen.make(SMALL, SEED + 1)
    assert (np.asarray(other.classes) != np.asarray(small.classes)).any()


def test_preflight_asks_the_program_first(gen):
    """A program whose resolver refuses a class axis on sparse rows fails
    the cell with the resolver's own words, before anything is made; one
    that answers ``fori`` (this platform's own answer) is refused here."""
    class Path:
        def __init__(self, **kw):
            self.kw = kw

        def as_dict(self):
            return self.kw

    seen = []

    def parents(ds, h, mesh, math):
        seen.append((ds.n, ds.num_features, ds.sp_indices.shape,
                     ds.classes.shape, ds.num_classes, h, math))
        raise ValueError("a set of 1000 classes trains one-vs-rest on dense "
                         "rows only")

    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    with pytest.raises(ValueError, match="dense rows only"):
        gen.preflight(cfg, parents)
    assert seen == [(1186239, 203882, (8, 148288, 256), (8, 148288, 8),
                     1000, 14827, "fast")]
    with pytest.raises(RuntimeError, match="kernel='fori'"):
        gen.preflight(cfg, lambda *a, **k: Path(kernel="fori"))
    ok = gen.preflight(cfg, lambda *a, **k: Path(kernel="pallas",
                                                 class_axis="lanes"))
    assert ok == {"kernel": "pallas", "class_axis": "lanes"}
    with pytest.raises(RuntimeError, match="kernel='fori'"):
        gen.make(SMALL, 1)


def test_job_restates_its_flag_line():
    """kddb's line with --accel=off on it, and no flag the frozen jobs test
    cannot give its tiny file.  The target is the traffic's name's, 1e-2:
    the sizing rules' readings (a job to 1e-2 is under 15 s, and at 1e-3
    the seeds split between two stops at either cadence the rules allow)
    are in the job's ``sizing``."""
    job = registry.resolve_cell(BENCH, CELL)["job"]
    flags = dict(f.lstrip("-").split("=") if "=" in f
                 else (f.lstrip("-"), "true") for f in job["flags"].split())
    assert flags == {"justCoCoA": "true", "math": "fast",
                     "deviceLoop": "true", "rng": "permuted", "accel": "off",
                     "gapTarget": "1e-2", "numRounds": "300",
                     "debugIter": "5"}
    kw = job["kwargs"]
    assert kw["gap_target"] == job["stop"]["target"] == 1e-2
    assert job["params"]["num_rounds"] == job["stop"]["round_budget"] == 300
    assert job["debug"]["debug_iter"] == 5
    assert "1e-3" in job["sizing"] and "15 s" in job["sizing"]
    assert "1e-2" in job["name"] and "1e-2" in job["what"]
    assert "1e-2" in registry.resolve_cell(BENCH, CELL)["why"]
    # kddb's line, but for --accel
    twin = registry.load_json(os.path.join(
        BENCH["_dir"], "jobs", "cocoa_plus_gap1e-2_e5.json"))
    assert job["flags"].replace(" --accel=off", "") == twin["flags"]
    assert {**kw, "accel": "auto"} == twin["kwargs"]


def test_the_audit_passes_a_float32_job(audited):
    cell, check, run, audit = audited
    tol = cell["job"]["audit"]
    assert audit["ok"], audit["problems"]
    # a timed job is judged by its records and its (W, alpha) let go of:
    # the harness keeps ``run`` bound while the next job starts
    timed = dict(run)
    assert check.job_problem(cell["job"], timed) is None
    assert timed["w"] is None and timed["alpha"] is None
    assert len(audit["gaps"]) == SMALL["num_classes"]
    assert max(audit["gaps"]) <= cell["job"]["stop"]["target"]
    assert audit["w_err_max"] < tol["w_tol"] < audit["w_err_bf16_least"]
    assert audit["bf16_w_fails"] and audit["pad_lanes_max"] == 0.0
    assert audit["gap_off_max"] < 1e-5
    assert run["w"].shape == (SMALL["d"], 8, 128)
    assert run["alpha"].shape == (2, 512, 8, 128)


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
    wide = json.loads(json.dumps(cell["job"]))
    wide["audit"]["w_tol"] = 0.5
    problems = check.audit({**cell, "job": wide}, small, run)["problems"]
    assert any("passes a bfloat16 W" in p for p in problems), problems


def test_run_cell_end_to_end_at_a_tiny_size(tmp_path, monkeypatch):
    """The whole harness on the interpreted kernel: the resolver is told
    ``pallas`` (its own answer on a cpu is ``fori``), nothing else."""
    from cocoa_tpu.solvers import cocoa

    resolve = cocoa.resolve_solver_path
    monkeypatch.setattr(
        cocoa, "resolve_solver_path",
        lambda *a, **kw: resolve(*a, **{**kw, "pallas": True}))
    cell = small_cell(kernel="pallas", state="hbm", interpret=True)
    result = harness.run_cell(BENCH, cell, seed=SEED, seconds=0.2,
                              trace=False, out_dir=str(tmp_path))
    assert result["correct"] and result["failed"] == 0, result["detail"]
    assert result["attempted"] >= 1
    assert {"job_s", "peak_hbm_gb", "setup_s"} <= set(result["metrics"])
    assert result["metrics"]["comm_rounds"]["value"] % 5 == 0
    detail = result["detail"]
    path = detail["solver_path"]
    assert (path["classes"], path["class_axis"], path["class_tiles"],
            path["label_slots"], path["local_ids"], path["segments"]) == (
        24, "lanes", 1, 8, "direct", 1)
    assert path["ids_per_segment"] == path["table_width"] == 24
    assert detail["audit"]["ok"]
    rounds = {j["rounds"] for j in detail["jobs"]}
    assert len(rounds) == 1 and rounds.pop() % 5 == 0      # one stop


def test_config_states_every_guess():
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    assert cfg["reduced"] == ["labels"]
    said = " ".join(cfg["assumed"])
    for word in ("stand-in", "remembered", "bias column", "squared hinge",
                 "lambda", "K = 8", "log-normal", "test split",
                 "power law"):
        assert word in said, word
    for key in ("sizing_rule", "deployment", "guarantees",
                "published_labels"):
        assert cfg[key]
    assert "fourteen" in cfg["deployment"].lower()
    assert "PLACEHOLDER" not in json.dumps(
        registry.resolve_cell(BENCH, CELL))
