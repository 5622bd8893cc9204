"""The sparse cell's own pieces, on the CPU: the harness finds everything
``kddb.cocoa_plus`` names; the stand-in generator makes what it says, the
same from the same seed, and refuses a program that would run the XLA
``fori`` chain; the plain sparse reference agrees with a NumPy float64
recomputation and with the program's objectives; the roofline's byte
count equals a hand count.

Tolerances: the reference works in float32 on the device and adds its
block and shard sums on the host in float64, so against float64 NumPy its
objectives (order 1-10) agree to 1e-6 relative and w(alpha) to 1e-6 of
|w|_inf; against the program's float32 objectives to 2e-6 relative."""

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

from owed import GENERIC, check_cell  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
CELL = "kddb.cocoa_plus"
SMALL = dict(name="small", n=3000, d=2000, num_splits=4, local_iter_frac=0.1,
             dtype="float32", loss="hinge", layout="sparse",
             generator_args=dict(max_nnz=16, mean_nnz=6.0, sigma_nnz=0.5,
                                 flip=0.02, planted_hot_cut=16))
SMALL["lambda"] = 1e-3
# no entry is this cell's alone since PR 55: what its PR entered under
# ``sparse_*`` names are readings later cells share, one entry each
BLOCK = []
SHARED = ["local_solve_ms", "sparse_gather_share", "eval_share",
          "unscoped_share", "sparse_dw_reduce_share", "sparse_solve_roofline"]


@pytest.fixture(scope="module")
def gen():
    return registry.load_module(BENCH, "generators", "sparse_zipf")


def make(gen, config, seed):
    """``gen.make`` with the pre-flight answered by a resolver that has a
    sparse solve for every size (this process's platform is cpu, where the
    program's own answer is ``fori``)."""
    real = gen.preflight
    gen.preflight = lambda config, resolve=None: {}
    try:
        return gen.make(config, seed)
    finally:
        gen.preflight = real


def test_the_harness_resolves_the_cell():
    cell = registry.resolve_cell(BENCH, CELL)
    cfg, job = cell["config"], cell["job"]
    assert (cell["chips"], cfg["name"], cfg["layout"]) == (1, "kddb",
                                                           "sparse")
    assert (cfg["n"], cfg["d"], cfg["reduced"]) == (19264097, 29890095, [])
    assert cfg["generator"] == "sparse_zipf"
    assert job["check"] == "certified_gap_sparse"
    assert job["expect_path"] == {"inner": "sequential", "kernel": "pallas",
                                  "state": "hbm", "interpret": False}
    assert job["stop"]["target"] == job["kwargs"]["gap_target"]
    assert job["debug"]["debug_iter"] == 5
    assert not {"pallas", "block_size", "hot_cols"} & set(job["kwargs"])
    gen = registry.load_module(BENCH, "generators", cfg["generator"])
    check = registry.load_module(BENCH, "checks", job["check"])
    assert callable(gen.make) and callable(gen.preflight)
    assert callable(check.audit) and callable(check.job_problem)
    readers = {m["name"]: (read, params) for m, read, params
               in registry.layer_readers(BENCH, CELL)}
    assert set(SHARED) <= set(readers)
    assert readers["sparse_gather_share"][1] == {
        "scope": "cocoa_sparse_gather"}
    assert readers["local_solve_ms"][1] == {"scope": "cocoa_local_solve",
                                            "per_round": True}
    for name in SHARED:     # each under one entry, which lists the cell
        (m,) = [m for m in BENCH["per_layer"] if m["name"] == name]
        assert CELL in m.get("workloads", [CELL])


def test_a_traced_line_of_the_cell_can_carry_every_metric_it_owes():
    """A traced run is refused if its line lacks a per-layer metric that
    exists in the cell.  Off the dense path ``round_roofline`` has no floor
    (``floor_of`` gives None), so its entry names the dense cells; every
    metric left in this cell reads what a sparse job's trace holds."""
    from chipbench.readers import round_roofline
    cell = registry.resolve_cell(BENCH, CELL)
    state = dict(local_iters=240801, device_kind="TPU v5 lite",
                 solver_path={**cell["job"]["expect_path"],
                              "layout": "sparse"})
    assert round_roofline.floor_of({**cell, **state}) is None
    check_cell(BENCH, CELL, BLOCK, GENERIC, SHARED)


def test_generator_makes_what_it_says(gen):
    ds = make(gen, SMALL, 2500000029)
    cols, vals, y, mask, sq = (np.asarray(a) for a in (
        ds.sp_indices, ds.sp_values, ds.labels, ds.mask, ds.sq_norms))
    assert ds.layout == "sparse" and ds.n == 3000 and ds.num_features == 2000
    assert cols.shape == vals.shape == (4, ds.n_shard, 16)
    assert mask.sum() == 3000 and list(ds.counts) == [750] * 4
    real = mask > 0
    length = (vals != 0).sum(-1)
    assert length[real].min() >= 1 and length[real].max() <= 16
    assert abs(length[real].mean() - 6.0) < 0.15
    assert (length[~real] == 0).all() and (y[~real] == 0).all()
    slot = np.arange(16)
    assert ((vals != 0) == (slot < length[..., None])).all()   # a prefix
    live = slot < length[..., None]
    assert (np.diff(cols, axis=-1)[live[..., 1:]] > 0).all()   # no repeats
    assert cols[live].min() >= 0 and cols[live].max() < 2000
    np.testing.assert_allclose(sq[real], 1.0, atol=1e-6)       # unit rows
    np.testing.assert_allclose((vals.astype(np.float64) ** 2).sum(-1)[real],
                               1.0, atol=1e-6)
    assert set(np.unique(y[real])) == {-1.0, 1.0}
    assert abs(y[real].mean()) < 0.1        # no hot column sets the balance
    # Zipf columns: the low decile of columns holds most nonzeros
    assert (cols[live] < 200).mean() > 0.5


def test_generator_same_seed_same_shards(gen):
    a, b, c = (make(gen, SMALL, s) for s in (7, 7, 8))
    for name in ("sp_indices", "sp_values", "labels", "mask", "sq_norms"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name))
    assert (np.asarray(a.sp_indices) != np.asarray(c.sp_indices)).any()
    assert (np.asarray(a.labels) != np.asarray(c.labels)).any()


def test_generator_blocks_tile_the_shard(gen, monkeypatch):
    """Row blocks smaller than the shard, the last pulled back to end on
    the last row: every real row is made, none left at zero."""
    monkeypatch.setattr(gen, "ROW_BLOCK", 200)
    ds = make(gen, SMALL, 3)
    length = (np.asarray(ds.sp_values) != 0).sum(-1)
    assert (length[np.asarray(ds.mask) > 0] >= 1).all()
    assert np.asarray(ds.mask).sum() == 3000


def test_preflight_refuses_the_fori_chain(gen):
    class Path:
        def __init__(self, kernel):
            self.kernel = kernel

        def as_dict(self):
            return {"kernel": self.kernel}

    seen = []

    def resolver(kernel):
        def resolve(ds, h, mesh, math):
            seen.append((ds.n, ds.num_features, ds.sp_indices.shape, h,
                         math))
            return Path(kernel)
        return resolve

    kddb = registry.resolve_cell(BENCH, CELL)["config"]
    with pytest.raises(RuntimeError, match="kernel='fori'"):
        gen.preflight(kddb, resolver("fori"))
    assert gen.preflight(kddb, resolver("pallas")) == {"kernel": "pallas"}
    # shapes only: the published sizes, nothing made
    assert seen[0] == (19264097, 29890095, (8, 2408016, 64), 240801, "fast")
    # and make() asks before it makes anything: on this platform (cpu) the
    # program's own resolver answers fori
    with pytest.raises(RuntimeError, match="kernel='fori'"):
        gen.make(SMALL, 1)


@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_sparse_reference_against_numpy_and_the_program(gen, loss):
    import jax.numpy as jnp

    from cocoa_tpu.evals import objectives

    ds = make(gen, SMALL, 5)
    lam = SMALL["lambda"]
    r = np.random.RandomState(0)
    cols, vals, y, mask = (np.asarray(a) for a in (
        ds.sp_indices, ds.sp_values, ds.labels, ds.mask))
    alpha = (r.rand(*y.shape) * mask).astype(np.float32)
    wsum = np.zeros(2000)
    np.add.at(wsum, cols.reshape(-1),
              (vals * (y * alpha)[..., None]).reshape(-1).astype(np.float64))
    w64 = wsum / (lam * ds.n)
    w = w64.astype(np.float32)
    m = (vals.astype(np.float64) * w.astype(np.float64)[cols]).sum(-1)
    a64 = alpha.astype(np.float64)
    if loss == "hinge":
        ploss, dloss = np.maximum(0, 1 - y * m), a64
    else:
        ploss = np.logaddexp(0, -y * m)
        with np.errstate(divide="ignore", invalid="ignore"):
            dloss = -(np.where(a64 > 0, a64 * np.log(a64), 0)
                      + np.where(a64 < 1, (1 - a64) * np.log(1 - a64), 0))
    wf = w.astype(np.float64)
    primal = (ploss * mask).sum() / ds.n + 0.5 * lam * (wf @ wf)
    dual = (dloss * mask).sum() / ds.n - 0.5 * lam * (w64 @ w64)
    for block_slots in (1 << 24, 16 * 100):     # one block; overlapping blocks
        out = reference_sparse.recompute(ds, jnp.asarray(w),
                                         jnp.asarray(alpha), lam, loss,
                                         block_slots=block_slots)
        assert out["primal"] == pytest.approx(primal, rel=1e-6)
        assert out["dual"] == pytest.approx(dual, rel=1e-6, abs=1e-6)
        assert out["w_err"] <= 1e-6 * out["w_scale"]
        assert out["w_scale"] == pytest.approx(np.abs(w64).max(), rel=1e-6)
        assert (out["alpha_min"], out["alpha_max"]) == (alpha.min(),
                                                        alpha.max())
    p, g, _ = objectives.evaluate(ds, jnp.asarray(w), jnp.asarray(alpha),
                                  lam, loss=loss)
    assert p == pytest.approx(out["primal"], rel=2e-6)
    assert g == pytest.approx(out["gap"], rel=2e-6)
    src = open(reference_sparse.__file__).read()
    assert "cocoa_tpu" not in src.split('"""', 2)[2]    # no program import


def test_roofline_bytes_equal_a_hand_count():
    # three steps on rows of 2, 3 and 1 nonzeros: per nonzero a column and
    # a value (8), w (4), dw read and written (8); per step y, |x|^2, alpha
    # in and alpha out (16)
    assert cost_model_sparse.round_bytes(3, 2 + 3 + 1) == \
        6 * (8 + 4 + 8) + 3 * 16 == 168
    model = cost_model_sparse.sparse_round(8, 240801, 29.4)
    assert model["steps"] == 1926408
    assert model["hbm_bytes"] == pytest.approx(
        1926408 * (29.4 * 20 + 16), rel=1e-12)
    reader = registry.load_module(BENCH, "readers", "sparse_solve_roofline")
    cell = {**registry.resolve_cell(BENCH, CELL), "local_iters": 240801,
            "device_kind": "TPU v5 lite",
            "solver_path": {"inner": "sequential", "kernel": "pallas"}}
    assert reader.floor_s(cell) == pytest.approx(
        model["hbm_bytes"] / 819e9)                     # 1.42 ms a round
    assert reader.floor_s({**cell, "solver_path": {"kernel": "fori"}}) \
        is None
    dense = registry.resolve_cell(BENCH, "epsilon.cocoa_plus")
    assert reader.floor_s({**dense, "local_iters": 5000,
                           "device_kind": "TPU v5 lite", "solver_path": {
                               "inner": "sequential",
                               "kernel": "pallas"}}) is None


def test_config_states_every_guess():
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    text = json.dumps(cfg["assumed"])
    for word in ("stand-in", "max_nnz", "lambda", "K = 8", "remembered",
                 "hottest"):
        assert word in text
    assert cfg["generator_args"]["max_nnz"] == 64
    assert cfg["mean_nnz"] == cfg["generator_args"]["mean_nnz"] == 29.4
