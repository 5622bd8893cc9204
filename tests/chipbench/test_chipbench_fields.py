"""The click-log cell's own pieces, on the CPU: the harness finds everything
``criteo.logistic`` names; the configuration is one of four chips' share of
the published file (``reduced: ["n"]``) and states every guess; the job is
kddb's but for its loss, its check, the two numbers its check takes from it
and one more key of the expected path; the cell owes its metrics by name
and each has a reader; the generator's pre-flight refuses a program that
would not run the ``direct`` plan; the check passes a float32 pair and
refuses a gap over the target, an alpha outside [eps, 1 - eps] and a w
rounded once to bfloat16; the step reader divides what it should.

The stand-in itself (39 a row, one a field, 26% clicks) and the job on the
program's kernels are held by tests/test_fields.py."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import cost_model_sparse, reference_sparse, registry  # noqa: E402

from owed import check_cell  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
CELL = "criteo.logistic"
# no entry is this cell's alone since PR 55: the scope readings it shares
# with the other sparse cells, one entry each; ``ctr_step_ns`` is read at
# amazoncat13k too
BLOCK = []
SCOPES = ["local_solve_ms", "sparse_gather_share", "eval_share",
          "accel_jump_share", "unscoped_share", "sparse_solve_roofline",
          "ctr_step_ns"]
# peak_hbm_gb and setup_s by part: the dense cells' account
# (chipbench/readers/cold_account.py), read in this cell under the
# account's own entries
ACCOUNTS = {name: moves for moves, names in {
    "peak_hbm_gb": ["hbm_entry_gb", "hbm_rise_layout_gb", "hbm_rise_job_gb",
                    "hbm_rise_after_gb", "hbm_resident_gb",
                    "hbm_program_temp_gb"],
    "setup_s": ["cold_layout_s", "cold_build_s", "cold_job_s"],
}.items() for name in names}
SHARED = SCOPES + list(ACCOUNTS)
GENERIC = ["device_idle_share", "fixed_s", "launches_per_job", "round_ms",
           "top_op_share", "compile_s", "compiles_in_window"]
SMALL = dict(name="small", n=3000, d=2048, num_splits=4,
             local_iter_frac=0.1, dtype="float32", loss="logistic",
             layout="sparse", mean_nnz=39.0,
             generator_args=dict(counter_fields=13, categorical_fields=26,
                                 smallest_field=4, click_share=0.26,
                                 flip=0.02, planted_density_inv=2))
SMALL["lambda"] = 1e-3


@pytest.fixture(scope="module")
def gen():
    return registry.load_module(BENCH, "generators", "sparse_fields")


@pytest.fixture(scope="module")
def small(gen):
    """``gen.make`` with the pre-flight answered yes (this process's
    platform is cpu, where the program's own answer is ``fori``)."""
    real = gen.preflight
    gen.preflight = lambda config, resolve=None: {}
    try:
        return gen.make(SMALL, 4500000053)
    finally:
        gen.preflight = real


def test_the_harness_resolves_the_cell():
    cell = registry.resolve_cell(BENCH, CELL)
    cfg, job = cell["config"], cell["job"]
    assert (cell["chips"], cfg["name"], cfg["layout"]) == (1, "criteo",
                                                           "sparse")
    assert cell["traffic"] == "cocoa_plus_logistic_gap1e-2_e5_fields"
    assert cfg["generator"] == "sparse_fields"
    assert job["check"] == "certified_gap_fields"
    assert registry.loss_of(cell) == cfg["loss"] == "logistic"
    # kddb's job but for the loss, the check and what the check is told
    kddb = registry.resolve_cell(BENCH, "kddb.cocoa_plus")["job"]
    assert job["flags"] == kddb["flags"] + " --loss=logistic"
    assert job["params"] == {**kddb["params"], "loss": "logistic"}
    for key in ("entry", "debug", "kwargs", "stop"):
        assert job[key] == kddb[key]
    assert job["expect_path"] == {**kddb["expect_path"],
                                  "local_ids": "direct"}
    assert not {"pallas", "block_size", "hot_cols"} & set(job["kwargs"])
    gen = registry.load_module(BENCH, "generators", cfg["generator"])
    check = registry.load_module(BENCH, "checks", job["check"])
    assert callable(gen.make) and callable(gen.preflight)
    assert callable(check.audit) and callable(check.job_problem)
    # the check's two numbers are the job's, each with its argument
    assert set(job["audit"]) == {"w_tol", "alpha_eps"}
    assert 1e-5 < job["audit"]["w_tol"] <= 2e-4  # a twentieth of bf16's 4e-3
    assert 0.0 <= job["audit"]["alpha_eps"] < 1e-6
    for word in ("w_tol:", "alpha_eps:", "bfloat16", "columns 0-12",
                 "seeds"):
        assert word in job["audit_why"]


def test_the_configuration_is_a_quarter_of_the_published_file():
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "criteo"]
    assert entry["reduced"] == cfg["reduced"] == ["n"]
    assert entry["source"] == cfg["source"] and "#criteo" in cfg["source"]
    assert cfg["architecture"] is None          # a deployment, not a model
    assert (cfg["n"], cfg["d"], cfg["num_splits"]) == (11460154, 1000000, 8)
    assert cfg["published_n"] == 45840617
    assert cfg["published_n"] - 4 * cfg["n"] in range(0, 4)
    assert (cfg["dtype"], cfg["loss"], cfg["local_iter_frac"],
            cfg["mean_nnz"]) == ("float32", "logistic", 0.1, 39.0)
    assert int(cfg["local_iter_frac"] * cfg["n"] / 8) == 143251
    args = cfg["generator_args"]
    assert args["counter_fields"] + args["categorical_fields"] == 39
    assert (args["click_share"], args["flip"]) == (0.26, 0.02)
    assert cfg["lambda"] in (1e-5, 1e-4)        # ISSUE 45's sizing rule (3)
    text = json.dumps(cfg["assumed"])
    for word in ("stand-in", "39 nonzeros", "lambda", "K = 8", "remembered",
                 "Zipf", "test split", "26%", "unit length"):
        assert word in text
    for word in ("float32", "certificate", "entropy", "[0, 1]"):
        assert word in cfg["guarantees"]
    for word in ("four chips", "K = 32", "143,251", "direct"):
        assert word in cfg["deployment"]
    for word in ("15 s", "120 s", "1e-3", "reduced = [n]"):
        assert word in cfg["sizing_rule"]
    assert "1803.06333" in cfg["what"]


@pytest.mark.parametrize("name", SHARED + GENERIC)
def test_a_traced_line_of_the_cell_can_carry_the_metric(name):
    readers = {m["name"]: (m, read, params) for m, read, params
               in registry.layer_readers(BENCH, CELL)}
    m, read, params = readers[name]
    assert callable(read)
    assert m["moves"] == ACCOUNTS.get(
        name, "setup_s" if name == "compile_s" else "job_s")
    if name in SHARED:
        assert CELL in m.get("workloads", [CELL])
    else:
        assert "workloads" not in m
    module = read.__module__.rsplit("_readers_", 1)[-1]
    want = {"local_solve_ms": ("scope_share", {"scope": "cocoa_local_solve",
                                               "per_round": True}),
            "sparse_gather_share": ("scope_share",
                                    {"scope": "cocoa_sparse_gather"}),
            "eval_share": ("scope_share", {"scope": "cocoa_eval"}),
            "accel_jump_share": ("scope_share",
                                 {"scope": "cocoa_accel_jump"}),
            "unscoped_share": ("scope_share", {"scope": None}),
            "sparse_solve_roofline": ("sparse_solve_roofline", {}),
            "ctr_step_ns": ("ctr_step_ns", {}),
            **{a: ("cold_account", {"part": a}) for a in ACCOUNTS}}
    if name in want:
        assert (module, params) == want[name]
    if name == "ctr_step_ns":
        # the unit, source and direction of mnist8m's step cost
        (entry,) = [e for e in BENCH["per_layer"]
                    if e["name"] == "ovr_class_step_ns"]
        assert (m["layer"], m["unit"], m["source"], m["better"]) == (
            entry["layer"], entry["unit"], entry["source"], entry["better"])


def test_the_cell_owes_these_metrics():
    check_cell(BENCH, CELL, BLOCK, GENERIC, SHARED)
    assert len(SHARED) == 16
    # ``ctr_step_ns`` is read where a chain's step is worth its own cost:
    # here and at amazoncat13k, and nowhere older
    (entry,) = [e for e in BENCH["per_layer"] if e["name"] == "ctr_step_ns"]
    assert entry["workloads"][:2] == [CELL, "amazoncat13k.ovr_cocoa_plus"]


def test_the_readers_read_nothing_where_there_is_nothing():
    """A tree without cold records, and a job off the padded-CSR Pallas
    path: nothing, and no error."""
    read, params = registry.layer_reader(BENCH, "hbm_program_temp_gb")
    assert read(None, [], {"cold_account": None}, **params) is None
    assert read(None, [], {"cold_account": {"hbm_program_temp_gb": 0.33}},
                **params) == 0.33
    cell = {**registry.resolve_cell(BENCH, CELL), "local_iters": 143251,
            "device_kind": "TPU v5 lite",
            "solver_path": {"inner": "sequential", "kernel": "fori"}}
    read, _ = registry.layer_reader(BENCH, "ctr_step_ns")
    assert read(None, [], cell) is None
    assert read(None, [], {**cell, "solver_path": None}) is None


def test_the_step_reader_divides_a_rounds_scope_by_its_steps(monkeypatch):
    from chipbench.readers import scope_share

    read, _ = registry.layer_reader(BENCH, "ctr_step_ns")
    cell = {**registry.resolve_cell(BENCH, CELL), "local_iters": 143251,
            "solver_path": {"inner": "sequential", "kernel": "pallas"}}
    seen = []

    def round_s(trace, jobs, cell_, scope):
        seen.append(scope)
        return 3.5

    monkeypatch.setattr(scope_share, "round_s", round_s)
    # 3.5 s a round over 8 x 143,251 steps
    assert read(None, [], cell) == pytest.approx(3.5e9 / 1146008)
    assert seen == ["cocoa_local_solve"]
    monkeypatch.setattr(scope_share, "round_s", lambda *a: None)
    assert read(None, [], cell) is None


def test_the_floor_is_the_sparse_cells_at_these_sizes():
    model = cost_model_sparse.sparse_round(8, 143251, 39.0)
    assert model["steps"] == 1146008
    assert model["hbm_bytes"] == pytest.approx(1146008 * (39 * 20 + 16))
    floor_s = registry.load_module(BENCH, "readers",
                                   "sparse_solve_roofline").floor_s
    cell = {**registry.resolve_cell(BENCH, CELL), "local_iters": 143251,
            "device_kind": "TPU v5 lite",
            "solver_path": {"inner": "sequential", "kernel": "pallas"}}
    assert floor_s(cell) == pytest.approx(model["hbm_bytes"] / 819e9)
    assert 1.1e-3 < floor_s(cell) < 1.12e-3             # 1.11 ms a round


def test_preflight_asks_for_the_direct_plan(gen):
    class Path:
        def __init__(self, **kw):
            self.kw = kw

        def as_dict(self):
            return self.kw

    seen = []

    def resolver(**answer):
        def resolve(ds, h, mesh, math):
            seen.append((ds.n, ds.num_features, ds.sp_indices.shape, h,
                         math))
            return Path(**answer)
        return resolve

    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    with pytest.raises(RuntimeError, match="kernel='fori'"):
        gen.preflight(cfg, resolver(kernel="fori", local_ids=None))
    # a program from before SolverPath said which plan ran (the parent of
    # the PR that brought the cell): refused in seconds, nothing made
    with pytest.raises(RuntimeError, match="local_ids = None"):
        gen.preflight(cfg, resolver(kernel="pallas", state="hbm"))
    with pytest.raises(RuntimeError, match="local_ids = 'sorted'"):
        gen.preflight(cfg, resolver(kernel="pallas", local_ids="sorted"))
    ok = gen.preflight(cfg, resolver(kernel="pallas", local_ids="direct"))
    assert ok == {"kernel": "pallas", "local_ids": "direct"}
    # shapes only: the quarter's sizes as the loader would store 39 a row
    assert seen[0] == (11460154, 1000000, (8, 1432528, 40), 143251, "fast")
    # and make() asks before it makes anything: on this platform (cpu) the
    # program's own resolver answers fori
    with pytest.raises(RuntimeError, match="kernel='fori'"):
        gen.make(SMALL, 1)


def test_the_checks_refusals(small):
    """A (w, alpha) with w = w(alpha) in float32 and alpha inside the box
    passes; a gap over the target, an alpha outside [eps, 1 - eps] and the
    same w rounded once to bfloat16 are each refused by name; a limit so
    wide that a bfloat16 w passes is itself a problem."""
    import jax.numpy as jnp

    from cocoa_tpu.evals import objectives

    ds, lam = small, SMALL["lambda"]
    check = registry.load_module(BENCH, "checks", "certified_gap_fields")
    job = registry.resolve_cell(BENCH, CELL)["job"]
    r = np.random.RandomState(0)
    cols, vals, y, mask = (np.asarray(a) for a in (
        ds.sp_indices, ds.sp_values, ds.labels, ds.mask))
    alpha = ((0.05 + 0.9 * r.rand(*y.shape)) * mask).astype(np.float32)
    wsum = np.zeros(SMALL["d"])
    np.add.at(wsum, cols.reshape(-1),
              (vals * (y * alpha)[..., None]).reshape(-1).astype(np.float64))
    w = (wsum / (lam * ds.n)).astype(np.float32)
    out = reference_sparse.recompute(ds, jnp.asarray(w), jnp.asarray(alpha),
                                     lam, "logistic")
    p, g, _ = objectives.evaluate(ds, jnp.asarray(w), jnp.asarray(alpha),
                                  lam, loss="logistic")
    assert p == pytest.approx(out["primal"], rel=2e-6)
    assert g == pytest.approx(out["gap"], rel=5e-6)

    class Rec:
        round, primal, gap = 5, out["primal"], out["gap"]

    class Traj:
        records, stopped = [Rec], "target"

    audit = {**job["audit"], "alpha_eps": 1e-3}
    cell = {"job": {"stop": {"rule": "certified_gap",
                             "target": out["gap"] * 1.01,
                             "round_budget": 300}, "audit": audit},
            "config": {"lambda": lam, "loss": "logistic"}}
    run = dict(w=jnp.asarray(w), alpha=jnp.asarray(alpha), traj=Traj,
               rounds=5, wall_s=1.0)
    good = check.audit(cell, ds, run)
    assert good["ok"], good["problems"]
    limit = job["audit"]["w_tol"] * max(1.0, out["w_scale"])
    assert good["w_limit"] == limit and good["w_err"] <= 2e-6 * out["w_scale"]
    assert good["w_err_bf16_least"] > limit
    assert 0.05 <= good["alpha_min_real"] <= good["alpha_max_real"] <= 0.95

    def problems(**change):
        c = {**cell, "job": {**cell["job"], **change.pop("job", {})}}
        return check.audit(c, ds, {**run, **change})["problems"]

    # a gap over the target: no certificate
    over = problems(job={"stop": {**cell["job"]["stop"],
                                  "target": out["gap"] * 0.5}})
    assert any("no certificate" in p for p in over)
    assert any("reference gap" in p for p in over)
    # an alpha outside [eps, 1 - eps], on either side, of a real row
    i = tuple(np.argwhere(mask > 0)[0])
    for bad in (1e-4, 1.0 - 1e-4):
        a = alpha.copy()
        a[i] = bad
        (p,) = [p for p in problems(alpha=jnp.asarray(a))
                if "alpha left" in p]
        assert "[0.001, 1 - 0.001]" in p
    # a padding row's alpha (0) is no real row's
    assert float(alpha[mask == 0].max(initial=0.0)) == 0.0
    # the same w rounded once to bfloat16: refused by the comparison of w
    rounded = jnp.asarray(w).astype(jnp.bfloat16).astype(jnp.float32)
    assert any("w != (1/(lam n))" in p for p in problems(w=rounded))
    # a limit a bfloat16 w passes is itself a problem
    assert any("passes a bfloat16 w" in p for p in problems(
        job={"audit": {**audit, "w_tol": 1e-2}}))
