"""The label-stream cell's own pieces, on the CPU: the harness finds
everything ``delicious200k.ovr_cocoa_plus`` names; it owes at least its
three ``labelstream_*`` entries, the shared readings it stands listed in,
the seven generic ones and three end-to-end ones, each over a reader the
benchmark has; the configuration's arithmetic (H, the steps a round, the
bytes of alpha, W and the stream, the two floors); the stand-in generator
makes what it says (a stream of unit rows with a long tail, a bias column
in every row, label sets whose frequencies follow the rank law), the same
from the same seed, and asks the program before it makes anything; the
check passes a float32 job and refuses a W rounded once to bfloat16, an
alpha off the box, a class over the target, a stop off the cadence and a
model on the lanes past T; the job's file restates its flag line; the
whole ``run_cell`` at a tiny size on the interpreted kernel."""

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

from chipbench import cost_model_labelstream, registry  # noqa: E402
from chipbench import run as harness  # noqa: E402

from owed import COLD, check_cell  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
CELL = "delicious200k.ovr_cocoa_plus"
SMALL = dict(name="small", n=512, d=700, mean_nnz=40.0, num_classes=24,
             published_labels=320, num_splits=2, local_iter_frac=0.1,
             dtype="float32", loss="hinge", layout="sparse",
             generator="longrows_multilabel",
             generator_args=dict(mean_nnz=40.0, sigma_nnz=1.0, max_nnz=256,
                                 labels_per_row=5.04, first_rank=4,
                                 label_slots=8, flip=0.02, planted_density_inv=2,
                                 planted_hot_cut=8, window_slots=1 << 15))
SMALL["lambda"] = 1e-2
SEED = 5700000029               # past 2**31: the driver's are large
# the scope readings the cell shares with other cells, one entry each
# (PR 55): {entry: (scope, per round)}
SCOPED = {"local_solve_ms": ("cocoa_local_solve", True),
          "sparse_gather_share": ("cocoa_sparse_gather", False),
          "eval_share": ("cocoa_eval", False),
          "sparse_dw_reduce_share": ("cocoa_dw_reduce", False),
          "indices_share": ("cocoa_indices", False),
          "unscoped_share": (None, False)}
# the two entries only this cell reads (readers of its own); what the chain
# pays a sampled nonzero is ``ctr_step_ns`` over the mean nonzeros a row
OWN_READERS = ["labelstream_solve_roofline", "labelstream_eval_roofline"]
BLOCK = OWN_READERS
SHARED = list(SCOPED) + ["ctr_step_ns"] + COLD
GENERIC = ["device_idle_share", "fixed_s", "launches_per_job", "round_ms",
           "top_op_share", "compile_s", "compiles_in_window"]


@pytest.fixture(scope="module")
def gen():
    return registry.load_module(BENCH, "generators", "longrows_multilabel")


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
                          "class_axis": "lanes", "storage": "stream",
                          **expect}
    return {**cell, "config": dict(SMALL), "job": job}


@pytest.fixture(scope="module")
def audited(small):
    """One job of the small cell (the plain-XLA round), and its audit."""
    cell = small_cell()
    run_once, _ = harness.make_job(cell, small, None)
    run = run_once()
    check = registry.load_module(BENCH, "checks", "certified_gap_labelstream")
    return cell, check, run, check.audit(cell, small, run)


def test_the_harness_resolves_the_cell():
    from cocoa_tpu import solvers

    cell = registry.resolve_cell(BENCH, CELL)
    cfg, job = cell["config"], cell["job"]
    assert (cell["chips"], cfg["name"], job["name"]) == (
        1, "delicious200k", "ovr_cocoa_plus_gap1e-2_e5_labelstream")
    assert job["check"] == "certified_gap_labelstream"
    assert cfg["generator"] == "longrows_multilabel"
    assert callable(getattr(solvers, job["entry"]))
    assert job["expect_path"] == {
        "inner": "sequential", "kernel": "pallas", "state": "hbm",
        "class_axis": "lanes", "storage": "stream", "interpret": False}
    # nothing on the line or in the call picks a kernel, a layout or a plan
    assert not {"pallas", "block_size", "hot_cols"} & set(job["kwargs"])
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "delicious200k"]
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
    """H = 2,457 from the harness's own rule and 19,656 steps a round over
    ~5.92e6 sampled nonzeros; W is 3.21 GB and alpha 0.81 GB at T_pad =
    1,024, the stream 0.54 GB as stored; a round's floor is 0.20 GB (0.25
    ms at the HBM peak) and an evaluation's 4.39 GB (5.4 ms)."""
    from chipbench import cost_model
    from cocoa_tpu.data.sharding import class_pad, pad_rows, split_sizes
    from cocoa_tpu.ops.pallas_longrows_lanes import stream_lanes_plan

    cell = registry.resolve_cell(BENCH, CELL)
    cfg = cell["config"]
    params, debug, kwargs, h = harness.job_arguments(cell)
    assert h == 2457 and params.local_iters == 2457
    assert (params.n, params.loss, params.lam) == (196606, "hinge", 1e-4)
    assert (cfg["d"], cfg["num_classes"], cfg["published_labels"],
            cfg["num_splits"], cfg["mean_nnz"]) == (782585, 1000, 205443, 8,
                                                    301.17)
    assert cfg["num_splits"] * h == 19656
    assert round(19656 * cfg["mean_nnz"] / 1e6, 2) == 5.92
    assert abs(params.lam * params.n - 19.6606) < 1e-9      # lambda n = 19.7
    assert kwargs["accel"] == "off" and debug.debug_iter == 5
    t_pad = class_pad(cfg["num_classes"])
    n_shard = pad_rows(int(split_sizes(cfg["n"], 8).max()))
    assert (t_pad, n_shard) == (1024, 24576)
    assert round(8 * n_shard * t_pad * 4 / 1e9, 2) == 0.81    # alpha
    assert round(cfg["d"] * t_pad * 4 / 1e9, 2) == 3.21       # W
    gen = registry.load_module(BENCH, "generators", "longrows_multilabel")
    pieces = gen.shapes_only(cfg).sp_indices.shape[1]
    assert gen.stream_windows(cfg) == 8 and pieces == 65536
    assert round(2 * 8 * pieces * 128 * 4 / 1e9, 2) == 0.54   # the stream
    # as a rectangle at the longest row: 12.9 GB for 0.47 GB of nonzeros
    width = cfg["generator_args"]["max_nnz"]
    assert round(cfg["n"] * width * 8 / 1e9, 1) == 12.9
    assert round(cfg["n"] * cfg["mean_nnz"] * 8 / 1e9, 2) == 0.47
    plan = stream_lanes_plan(h, 4, t_pad,
                             cfg["generator_args"]["label_slots"])
    assert (plan.steps, plan.ring, plan.row_block, plan.t_pad,
            plan.label_slots) == (2560, 512, 256, 1024, 8)
    peak = cost_model.peaks_for("TPU v5 lite")["hbm_bytes_per_s"]
    solve = cost_model_labelstream.solve_round_bytes(8, h, cfg["mean_nnz"],
                                                     1000)
    assert solve == 19656 * 301.17 * 8 + 19656 * (16 + 8000)
    assert round(solve / 1e9, 2) == 0.20
    assert round(1e3 * solve / peak, 2) == 0.25
    evals = cost_model_labelstream.eval_pass_bytes(cfg["n"], cfg["d"],
                                                   cfg["mean_nnz"], 1000)
    assert round(evals / 1e9, 2) == 4.39
    assert round(1e3 * evals / peak, 1) == 5.4


def _rows_of(ds):
    """``[(columns, values)]`` of every stored row of a stream dataset, by
    (shard, position), read off the flat stream on the host."""
    cols, vals = np.asarray(ds.sp_indices), np.asarray(ds.sp_values)
    ptr, lens = np.asarray(ds.sp_row_ptr), np.asarray(ds.sp_row_len)
    out = {}
    for s in range(ptr.shape[0]):
        fc, fv = cols[s].reshape(-1), vals[s].reshape(-1)
        for i in range(ptr.shape[1]):
            a = 8 * ptr[s, i]
            out[s, i] = (fc[a:a + lens[s, i]], fv[a:a + lens[s, i]])
    return out


@pytest.mark.parametrize("what", ["layout", "unit_rows", "bias", "columns",
                                  "tail", "shares", "sets", "labels"])
def test_generator_follows_the_stated_law(gen, small, what):
    k, t = SMALL["num_splits"], SMALL["num_classes"]
    args = SMALL["generator_args"]
    ids, mask = np.asarray(small.classes), np.asarray(small.mask) > 0
    ptr, lens = np.asarray(small.sp_row_ptr), np.asarray(small.sp_row_len)
    rows = SMALL["n"] // k
    stored = _rows_of(small)
    if what == "layout":
        assert small.layout == "sparse" and small.num_classes == t
        pieces = args["window_slots"] // 128 * gen.stream_windows(SMALL)
        assert np.asarray(small.sp_indices).shape == (k, pieces, 128)
        assert np.asarray(small.sp_values).shape == (k, pieces, 128)
        assert ptr.shape == lens.shape == (k, rows)
        assert small.sp_row_iota.shape == (k, args["max_nnz"])
        assert ids.shape == (k, rows, args["label_slots"])
        assert ids.dtype == np.int32 and small.label_slots == 8
        assert list(small.counts) == [rows] * k and small.n == SMALL["n"]
        assert lens[mask].min() >= 1 and lens.max() <= args["max_nnz"]
        assert abs(lens[mask].mean() - args["mean_nnz"]) < 4.0
        # a row starts on a group boundary, right behind the row before it
        slots = -(-lens // 8)
        np.testing.assert_array_equal(
            ptr, np.cumsum(slots, axis=1) - slots)
        # what lies between the rows, and past the last, is column 0, value 0
        live = np.zeros((k, pieces * 128), bool)
        for (s_, i), (c, _) in stored.items():
            live[s_, 8 * ptr[s_, i]:8 * ptr[s_, i] + len(c)] = True
        assert not np.asarray(small.sp_indices).reshape(k, -1)[~live].any()
        assert not np.asarray(small.sp_values).reshape(k, -1)[~live].any()
    elif what == "unit_rows":
        for c, v in stored.values():
            np.testing.assert_allclose((v * v).sum(), 1.0, atol=1e-5)
        np.testing.assert_allclose(np.asarray(small.sq_norms)[mask], 1.0,
                                   atol=1e-5)
    elif what == "bias":
        # a row's last nonzero is column d - 1, and no other slot is
        for c, _ in stored.values():
            assert c[-1] == SMALL["d"] - 1 and (c[:-1] < SMALL["d"] - 1).all()
    elif what == "columns":
        # ascending, no column twice
        for c, v in stored.values():
            assert (np.diff(c) > 0).all() and (v > 0).all()
    elif what == "tail":
        # log-normal at sigma 1: the longest row several times the mean,
        # and the loader's own rule would keep these rows as a stream
        from cocoa_tpu.data.sharding import stream_suits

        real = lens[mask]
        assert real.max() > 4 * real.mean() and np.median(real) < real.mean()
        assert stream_suits(np.maximum(real * 3, 1))     # at three times
    elif what == "shares":
        share = gen.label_shares(SMALL)
        law = 5.04 / np.sum(1 / np.arange(1, 321))
        assert abs(share[0] - law / 4) < 1e-12
        counts = np.bincount(ids[mask][ids[mask] >= 0], minlength=t)
        assert abs(counts[0] / SMALL["n"] - share[0]) < 0.06
        assert abs(counts.sum() / SMALL["n"] - share.sum()) < 0.12
        assert counts[0] > 2 * counts[1:].max()         # a head and a tail
        # the cell's own batch: every 205.4th rank from rank 37 holds what
        # the MEAN batch of 1,000 of the 205,443 holds, 0.368 labels a row
        cfg = registry.resolve_cell(BENCH, CELL)["config"]
        real = gen.label_shares(cfg)
        assert cfg["generator_args"]["first_rank"] == 37
        assert abs(real.sum() - 75.54 * 1000 / 205443) < 0.002
        assert abs(real[0] - 0.159) < 1e-3 and real[0] == real.max()
        assert 5 < real[-1] * cfg["n"] < 6
    elif what == "sets":
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
    for f in ("sp_indices", "sp_values", "sp_row_ptr", "sp_row_len",
              "classes", "labels"):
        np.testing.assert_array_equal(np.asarray(getattr(again, f)),
                                      np.asarray(getattr(small, f)))
    other = gen.make(SMALL, SEED + 1)
    assert (np.asarray(other.classes) != np.asarray(small.classes)).any()


def test_preflight_asks_the_program_first(gen):
    """A program whose resolver refuses a class axis on rows kept as a
    stream (the parent of PR 57) fails the cell with the resolver's own
    words, before anything is made; one that answers ``fori`` (this
    platform's own answer) is refused here."""
    class Path:
        def __init__(self, **kw):
            self.kw = kw

        def as_dict(self):
            return self.kw

    seen = []

    def parents(ds, h, mesh, math):
        seen.append((ds.n, ds.num_features, ds.sp_indices.shape,
                     ds.sp_row_ptr.shape, ds.sp_row_iota.shape,
                     ds.classes.shape, ds.num_classes, h, math))
        raise ValueError("no kernel carries the class axis on rows kept as "
                         "a stream yet")

    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    with pytest.raises(ValueError, match="rows kept as a stream yet"):
        gen.preflight(cfg, parents)
    assert seen == [(196606, 782585, (8, 65536, 128), (8, 24576), (8, 8192),
                     (8, 24576, 8), 1000, 2457, "fast")]
    with pytest.raises(RuntimeError, match="kernel='fori'"):
        gen.preflight(cfg, lambda *a, **k: Path(kernel="fori"))
    ok = gen.preflight(cfg, lambda *a, **k: Path(kernel="pallas",
                                                 class_axis="lanes"))
    assert ok == {"kernel": "pallas", "class_axis": "lanes"}
    with pytest.raises(RuntimeError, match="kernel='fori'"):
        gen.make(SMALL, 1)


def test_job_restates_its_flag_line():
    """amazoncat13k's line, flag for flag (kddb's with --accel=off on it):
    ISSUE 57's traffic, an evaluation every 5 rounds.  The job's ``sizing``
    has the rules' readings: one stop at every seed, the median job far
    under 60 s, a run inside 1,200 s."""
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
    assert job["debug"]["debug_iter"] == 5 and "_e5_" in job["name"]
    assert "60 s" in job["sizing"] and "1,200 s" in job["sizing"]
    assert "w_tol" in job["audit_why"] and "gap_tol" in job["audit_why"]
    assert "1e-2" in job["name"] and "1e-2" in job["what"]
    assert "1e-2" in registry.resolve_cell(BENCH, CELL)["why"]
    # kddb's line, but for --accel
    twin = registry.load_json(os.path.join(
        BENCH["_dir"], "jobs", "cocoa_plus_gap1e-2_e5.json"))
    assert job["flags"].replace(" --accel=off", "") == twin["flags"]
    assert {**kw, "accel": "auto"} == twin["kwargs"]
    labels = registry.load_json(os.path.join(
        BENCH["_dir"], "jobs", "ovr_cocoa_plus_gap1e-2_e5_labels.json"))
    assert (job["flags"], kw) == (labels["flags"], labels["kwargs"])


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
    assert run["alpha"].shape == (2, 256, 8, 128)


@pytest.mark.parametrize("tiles", [1, 2, 3, 8])
def test_the_reference_reads_the_same_however_many_tiles_a_call_takes(
        audited, small, tiles):
    """A call of the reference takes ``TILES`` class tiles so that a slot
    of the stream moves one row of W for all of them; a class's arithmetic
    is its lane's alone, so every reading is the same to the bit at 1, 2
    and 8 tiles a call (3 does not divide R = 8: the call takes 2)."""
    from chipbench import reference_labelstream as ref

    cell, _, run, audit = audited
    assert ref.TILES == 4               # what the audit above ran with
    got, at_four = (ref.recompute(small, run["w"], run["alpha"],
                                  cell["config"]["lambda"], "hinge", **kw)
                    for kw in (dict(tiles=tiles), {}))
    assert got["gaps"] == audit["gaps"]
    for key in ("primal", "dual", "gaps_bf16", "w_err", "w_err_bf16",
                "w_scale", "pad_lanes_max"):
        assert got[key] == at_four[key], key


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
            path["label_slots"], path["storage"], path["margin"],
            path["step_solve"], path["chunk_pieces"]) == (
        24, "lanes", 1, 8, "stream", "split", "lanes", 8)
    assert path["plan"] == {"t_pad": 1024, "ring": 512, "row_block": 256,
                            "steps": 256, "label_slots": 8}
    assert 0 < path["pass_slot_share"] <= 1 and 0 < path["slot_fill"] < 1
    assert path["longest_row"] <= 256 and 0 < path["chunk_fill"] < 1
    assert detail["audit"]["ok"]
    rounds = {j["rounds"] for j in detail["jobs"]}
    assert len(rounds) == 1 and rounds.pop() % 5 == 0     # one stop


def test_config_states_every_guess():
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    assert cfg["reduced"] == ["labels"]
    said = " ".join(cfg["assumed"])
    for word in ("stand-in", "remembered", "bias column", "squared hinge",
                 "lambda", "K = 8", "log-normal", "test split",
                 "power law", "sigma", "8,192"):
        assert word in said, word
    for key in ("sizing_rule", "deployment", "guarantees",
                "published_labels"):
        assert cfg[key]
    assert "206" in cfg["deployment"]
    assert "PLACEHOLDER" not in json.dumps(
        registry.resolve_cell(BENCH, CELL))
