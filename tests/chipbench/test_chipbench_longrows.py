"""The long-row cell's own pieces, on the CPU: the harness finds everything
``webspam.cocoa_plus`` names; the stand-in generator makes what it says
(length law, mean, longest, no column twice, windows that tile a shard), the
same from the same seed, and refuses a program that would run the XLA
``fori`` chain; the plain long-row reference agrees with a NumPy float64
recomputation and with the program's objectives; the roofline's byte count
equals a hand count; the configuration states every guess.

Tolerances: the reference works in float32 on the device and adds its block
and shard sums on the host in float64, so against float64 NumPy its
objectives (order 1) agree to 1e-6 relative and w(alpha) to 1e-6 of
|w|_inf; against the program's float32 objectives (the stream kernels in
interpret mode) to 2e-6 relative (the gap: 5e-6)."""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import cost_model_sparse, reference_longrows, registry  # noqa: E402

from owed import check_cell  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
CELL = "webspam.cocoa_plus"
SMALL = dict(name="small", n=1024, d=65536, num_splits=4,
             local_iter_frac=0.1, dtype="float32", loss="hinge",
             layout="sparse",
             generator_args=dict(max_nnz=3000, mean_nnz=300, sigma_nnz=0.6,
                                 flip=0.02, planted_hot_cut=256))
SMALL["lambda"] = 1e-3
# no entry is this cell's alone since PR 55: what its PR entered under
# ``longrow_*`` names are readings other cells share, one entry each
BLOCK = []
SHARED = ["local_solve_ms", "sparse_gather_share", "eval_share",
          "unscoped_share", "sparse_solve_roofline"]
GENERIC = ["device_idle_share", "fixed_s", "launches_per_job", "round_ms",
           "top_op_share", "compile_s", "compiles_in_window"]
ALIGN = 8


@pytest.fixture(scope="module")
def gen():
    mod = registry.load_module(BENCH, "generators", "longrows_zipf")
    mod.WINDOW = 1 << 16        # a shard of this size spans two windows
    return mod


def make(gen, config, seed):
    """``gen.make`` with the pre-flight answered by a resolver that has a
    solve for every size (this process's platform is cpu, where the
    program's own answer is ``fori``)."""
    real = gen.preflight
    gen.preflight = lambda config, resolve=None: {}
    try:
        return gen.make(config, seed)
    finally:
        gen.preflight = real


@pytest.fixture(scope="module")
def small(gen):
    return make(gen, SMALL, 2500000029)


def rows_of(ds):
    """[(shard, row, columns, values)] of the real rows, read from the
    stream as the storage says."""
    cols = np.asarray(ds.sp_indices).reshape(ds.k, -1)
    vals = np.asarray(ds.sp_values).reshape(ds.k, -1)
    ptr, length = np.asarray(ds.sp_row_ptr), np.asarray(ds.sp_row_len)
    return [(a, i, cols[a, ALIGN * ptr[a, i]:ALIGN * ptr[a, i] + length[a, i]],
             vals[a, ALIGN * ptr[a, i]:ALIGN * ptr[a, i] + length[a, i]])
            for a in range(ds.k) for i in range(int(ds.counts[a]))]


def test_the_harness_resolves_the_cell():
    cell = registry.resolve_cell(BENCH, CELL)
    cfg, job = cell["config"], cell["job"]
    assert (cell["chips"], cfg["name"], cfg["layout"]) == (1, "webspam",
                                                           "sparse")
    assert (cfg["d"], cfg["mean_nnz"]) == (16609143, 3727)
    assert cfg["generator"] == "longrows_zipf"
    assert job["check"] == "certified_gap_longrows"
    assert job["expect_path"] == {"inner": "sequential", "kernel": "pallas",
                                  "storage": "stream", "interpret": False}
    # the same job as kddb's, but for its check and its path
    kddb = registry.resolve_cell(BENCH, "kddb.cocoa_plus")["job"]
    for key in ("entry", "flags", "params", "debug", "kwargs", "stop"):
        assert job[key] == kddb[key]
    assert not {"pallas", "block_size", "hot_cols"} & set(job["kwargs"])
    gen = registry.load_module(BENCH, "generators", cfg["generator"])
    check = registry.load_module(BENCH, "checks", job["check"])
    assert callable(gen.make) and callable(gen.preflight)
    assert callable(check.audit) and callable(check.job_problem)
    assert 1e-5 < check.W_TOL < 2e-3        # under one bf16 rounding of w


@pytest.mark.parametrize("name", SHARED + GENERIC)
def test_a_traced_line_of_the_cell_can_carry_the_metric(name):
    """A traced run is refused if its line lacks a per-layer metric that
    exists in the cell: each has a reader the harness finds, the shared
    ones under the one entry of their reading."""
    readers = {m["name"]: (m, read, params) for m, read, params
               in registry.layer_readers(BENCH, CELL)}
    m, read, params = readers[name]
    assert callable(read)
    assert m["moves"] == ("setup_s" if name == "compile_s" else "job_s")
    if name in SHARED:
        assert CELL in m.get("workloads", [CELL])
    else:
        assert "workloads" not in m
    want = {"local_solve_ms": {"scope": "cocoa_local_solve",
                               "per_round": True},
            "sparse_gather_share": {"scope": "cocoa_sparse_gather"},
            "eval_share": {"scope": "cocoa_eval"},
            "unscoped_share": {"scope": None}}
    if name in want:
        assert params == want[name]


def test_the_cell_owes_these_metrics():
    check_cell(BENCH, CELL, BLOCK, GENERIC, SHARED)
    from chipbench.readers import round_roofline
    cell = registry.resolve_cell(BENCH, CELL)
    assert round_roofline.floor_of({
        **cell, "local_iters": 4375, "device_kind": "TPU v5 lite",
        "solver_path": {**cell["job"]["expect_path"], "layout": "sparse"}
    }) is None


def test_generator_makes_a_stream(gen, small):
    ds = small
    assert ds.layout == "sparse" and ds.n == 1024 and ds.num_features == 65536
    assert ds.sp_row_ptr is not None and list(ds.counts) == [256] * 4
    n_win = gen.stream_windows(SMALL)
    assert n_win == 2
    assert ds.sp_indices.shape == ds.sp_values.shape == (
        4, n_win * gen.WINDOW // 128, 128)
    assert np.asarray(ds.sp_row_iota).shape == (4, 3000)
    mask, y, sq = (np.asarray(a) for a in (ds.mask, ds.labels, ds.sq_norms))
    real = mask > 0
    assert mask.sum() == 1024
    ptr, length = np.asarray(ds.sp_row_ptr), np.asarray(ds.sp_row_len)
    assert (length[~real] == 0).all() and (y[~real] == 0).all()
    for a in range(4):          # rows end to end, on 8-slot boundaries
        m = int(ds.counts[a])
        np.testing.assert_array_equal(
            ptr[a, 1:m], np.cumsum(-(-length[a, :m] // ALIGN))[:-1])
        assert ptr[a, 0] == 0
    # every value sits inside a row: the slots between rows hold nothing
    assert int((np.asarray(ds.sp_values) != 0).sum()) == int(length.sum())
    stored = ds.sp_indices.shape[1] * 128
    used = ALIGN * ptr[:, 255] + length[:, 255]
    assert (used + 8 * 128 <= stored).all()       # the kernels' spare
    np.testing.assert_allclose(sq[real], 1.0, atol=1e-6)       # unit rows
    assert set(np.unique(y[real])) == {-1.0, 1.0}
    assert abs(y[real].mean()) < 0.15


@pytest.mark.parametrize("what", ["length_law", "columns", "values",
                                  "hot_head"])
def test_generator_rows_follow_the_stated_laws(gen, small, what):
    rows = rows_of(small)
    length = np.array([len(c) for _, _, c, _ in rows])
    if what == "length_law":
        assert length.min() >= 1 and length.max() <= 3000
        assert abs(length.mean() - 300) < 25           # 1,024 rows
        logs = np.log(length[length < 3000])
        assert abs(logs.std() - 0.6) < 0.06            # log-normal, sigma .6
        assert (length > 2 * 300).mean() > 0.03        # a long tail
        mu = gen.length_mu(3727, 0.6, 32768)
        assert abs(mu - (np.log(3727) - 0.18)) < 0.02
    elif what == "columns":
        for _, _, c, _ in rows:     # ascending, none twice, inside d
            assert (np.diff(c) > 0).all()
            assert c[0] >= 0 and c[-1] < 65536
    elif what == "values":
        for _, _, c, v in rows[::37]:
            np.testing.assert_allclose(v, 1 / np.sqrt(len(c)), rtol=1e-6)
    else:
        # Zipf(1) with repeats moved up: a row's head is the dense run
        # 0, 1, 2, ..., and the hottest columns hold most nonzeros
        long_rows = [c for _, _, c, _ in rows if len(c) >= 200]
        assert all((c[:20] == np.arange(20)).all() for c in long_rows)
        everything = np.concatenate([c for _, _, c, _ in rows])
        share = (everything < 256).mean()       # ln 257 / ln 62536 = 0.50
        assert 0.40 < share < 0.60


def test_generator_same_seed_same_shards(gen, small):
    b, c = make(gen, SMALL, 2500000029), make(gen, SMALL, 8)
    for name in ("sp_indices", "sp_values", "labels", "mask", "sq_norms",
                 "sp_row_ptr", "sp_row_len"):
        np.testing.assert_array_equal(getattr(small, name), getattr(b, name))
    assert (np.asarray(small.sp_row_len) != np.asarray(c.sp_row_len)).any()
    assert (np.asarray(small.labels) != np.asarray(c.labels)).any()


def test_generator_windows_tile_the_shard(gen, small):
    """A shard spans two windows and a row straddles their border: the
    same rows come out of one window that holds the whole shard."""
    length = np.asarray(small.sp_row_len)
    first = ALIGN * np.asarray(small.sp_row_ptr)
    assert ((first < gen.WINDOW) & (first + length > gen.WINDOW)).any()
    old = gen.WINDOW
    gen.WINDOW = 1 << 17
    try:
        whole = make(gen, SMALL, 2500000029)
    finally:
        gen.WINDOW = old
    assert whole.sp_indices.shape == small.sp_indices.shape
    np.testing.assert_array_equal(whole.sp_row_len, small.sp_row_len)
    np.testing.assert_array_equal(whole.labels != 0, small.labels != 0)
    # the columns' uniforms are drawn per window, so only the laws agree
    for (_, _, c, _), (_, _, c2, _) in zip(rows_of(whole)[::29],
                                           rows_of(small)[::29]):
        assert len(c) == len(c2) and (np.diff(c) > 0).all()


def test_generator_cuts_the_last_rows_where_a_shard_overflows(gen):
    import jax.numpy as jnp

    length = jnp.asarray([100, 100, 100, 50, 0, 0], jnp.int32)
    real = jnp.asarray([1, 1, 1, 1, 0, 0], bool)
    first, cut = gen.fit_rows(length, real, 10000)
    assert cut.tolist() == [100, 100, 100, 50, 0, 0]
    assert first.tolist()[:4] == [0, 104, 208, 312]
    first, cut = gen.fit_rows(length, real, 250)     # every row keeps a slot
    assert cut.tolist() == [100, 100, 34, 1, 0, 0]
    assert first.tolist()[:4] == [0, 104, 208, 248]
    assert int(first[3] + cut[3]) <= 250


def test_preflight_refuses_the_fori_chain(gen):
    class Path:
        def __init__(self, kernel):
            self.kernel = kernel

        def as_dict(self):
            return {"kernel": self.kernel}

    seen = []

    def resolver(kernel):
        def resolve(ds, h, mesh, math):
            seen.append((ds.n, ds.num_features, ds.sp_indices.shape[0],
                         ds.sp_indices.shape[2], ds.sp_row_ptr.shape,
                         ds.sp_row_iota.shape[1], h, math))
            return Path(kernel)
        return resolve

    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    with pytest.raises(RuntimeError, match="kernel='fori'"):
        gen.preflight(cfg, resolver("fori"))
    assert gen.preflight(cfg, resolver("pallas")) == {"kernel": "pallas"}
    n_shard = -(-(cfg["n"] // 8) // 16) * 16
    h = int(0.1 * cfg["n"] / 8)
    # shapes only: the published sizes, nothing made
    assert seen[0] == (cfg["n"], 16609143, 8, 128, (8, n_shard), 32768, h,
                       "fast")
    # and make() asks before it makes anything: on this platform (cpu) the
    # program's own resolver answers fori for a stream as well
    with pytest.raises(RuntimeError, match="kernel='fori'"):
        gen.make(SMALL, 1)


@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_longrow_reference_against_numpy_and_the_program(gen, small, loss):
    import jax.numpy as jnp

    from cocoa_tpu.evals import objectives

    ds, lam, d = small, SMALL["lambda"], 65536
    r = np.random.RandomState(0)
    y, mask = np.asarray(ds.labels), np.asarray(ds.mask)
    alpha = (r.rand(*y.shape) * mask).astype(np.float32)
    rows = rows_of(ds)
    wsum = np.zeros(d)
    for a, i, c, v in rows:
        wsum[c] += v.astype(np.float64) * float(y[a, i]) * float(alpha[a, i])
    w64 = wsum / (lam * ds.n)
    w = w64.astype(np.float32)
    wf = w.astype(np.float64)
    m = np.zeros(y.shape)
    for a, i, c, v in rows:
        m[a, i] = v.astype(np.float64) @ wf[c]
    a64 = alpha.astype(np.float64)
    if loss == "hinge":
        ploss, dloss = np.maximum(0, 1 - y * m), a64
    else:
        ploss = np.logaddexp(0, -y * m)
        with np.errstate(divide="ignore", invalid="ignore"):
            dloss = -(np.where(a64 > 0, a64 * np.log(a64), 0)
                      + np.where(a64 < 1, (1 - a64) * np.log(1 - a64), 0))
    primal = (ploss * mask).sum() / ds.n + 0.5 * lam * (wf @ wf)
    dual = (dloss * mask).sum() / ds.n - 0.5 * lam * (w64 @ w64)
    slots = ds.sp_indices.shape[1] * 128
    # one block; blocks that cut rows; a last block pulled back
    for block_slots in (1 << 24, 1 << 13, slots // 3 // 8 * 8):
        out = reference_longrows.recompute(ds, jnp.asarray(w),
                                           jnp.asarray(alpha), lam, loss,
                                           block_slots=block_slots)
        assert out["primal"] == pytest.approx(primal, rel=1e-6)
        assert out["dual"] == pytest.approx(dual, rel=1e-6, abs=1e-6)
        assert out["w_err"] <= 1e-6 * out["w_scale"]
        assert out["w_scale"] == pytest.approx(np.abs(w64).max(), rel=1e-6)
        assert out["stray_values"] == 0
        # one rounding of w to bfloat16 is far outside the w tolerance
        assert out["w_err_bf16"] > 5e-4 * out["w_scale"]
        assert (out["alpha_min"], out["alpha_max"]) == (alpha.min(),
                                                        alpha.max())
    p, g, _ = objectives.evaluate(ds, jnp.asarray(w), jnp.asarray(alpha),
                                  lam, loss=loss)
    assert p == pytest.approx(out["primal"], rel=2e-6)
    # the program's dual reads |w|^2 of the float32 w it is handed, the
    # reference's |w(alpha)|^2 in float64: 3e-6 of a gap of 2.9 here
    assert g == pytest.approx(out["gap"], rel=5e-6)
    src = open(reference_longrows.__file__).read().split('"""', 2)[2]
    assert "cocoa_tpu.ops" not in src and "cocoa_tpu.evals" not in src
    assert "pallas" not in src


def test_reference_counts_a_value_outside_every_row(gen):
    import jax.numpy as jnp

    ds = make(gen, SMALL, 11)
    last = int(np.asarray(ds.sp_row_ptr)[0].max()) * ALIGN + 4000
    ds.sp_values = ds.sp_values.at[0, last // 128, last % 128].set(0.5)
    out = reference_longrows.recompute(
        ds, jnp.zeros(65536, jnp.float32), jnp.zeros_like(ds.labels), 1e-3)
    assert out["stray_values"] == 1


def test_roofline_bytes_equal_a_hand_count():
    # per nonzero a column and a value (8), w (4), dw read and written (8);
    # per step y, |x|^2, alpha in and alpha out (16): ISSUE 30's 2.61 GB
    model = cost_model_sparse.sparse_round(8, 4375, 3727)
    assert model["steps"] == 35000 and model["nonzeros"] == 130445000
    assert model["hbm_bytes"] == 130445000 * 20 + 35000 * 16 == 2609460000
    read, params = registry.layer_reader(BENCH, "sparse_solve_roofline")
    assert params == {} and "sparse_solve_roofline" in read.__module__
    floor_s = registry.load_module(BENCH, "readers",
                                   "sparse_solve_roofline").floor_s
    cell = {**registry.resolve_cell(BENCH, CELL),
            "device_kind": "TPU v5 lite",
            "solver_path": {"inner": "sequential", "kernel": "pallas",
                            "storage": "stream"}}
    h = int(0.1 * cell["config"]["n"] / 8)
    assert floor_s({**cell, "local_iters": h}) == pytest.approx(
        (8 * h * 3727 * 20 + 8 * h * 16) / 819e9)
    assert floor_s({**cell, "local_iters": 4375}) == pytest.approx(
        2609460000 / 819e9)                             # 3.19 ms a round
    assert floor_s({**cell, "local_iters": h,
                    "solver_path": {"kernel": "fori"}}) is None


def test_config_states_every_guess():
    cfg = registry.resolve_cell(BENCH, CELL)["config"]
    text = json.dumps(cfg["assumed"])
    for word in ("stand-in", "32,768", "lambda", "K = 8", "remembered",
                 "hottest", "log-normal", "Zipf", "test split"):
        assert word in text
    assert cfg["generator_args"]["max_nnz"] == 32768
    assert cfg["generator_args"]["sigma_nnz"] == 0.6
    assert cfg["mean_nnz"] == cfg["generator_args"]["mean_nnz"] == 3727
    assert (cfg["d"], cfg["lambda"], cfg["num_splits"]) == (16609143, 1e-4, 8)
    (entry,) = [c for c in BENCH["configs"] if c["name"] == "webspam"]
    assert entry["reduced"] == cfg["reduced"]
    assert cfg["reduced"] in ([], ["n"])
    assert cfg["n"] == (350000 if not cfg["reduced"] else 175000)
    assert "60 s" in cfg["sizing_rule"] and "16.9" in cfg["sizing_rule"]
    for word in ("float32", "certificate", "[0, 1]"):
        assert word in cfg["guarantees"]
