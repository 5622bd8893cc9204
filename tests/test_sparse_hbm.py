"""The sparse local solve whose state stays in HBM
(ops/pallas_sparse_hbm.py), in interpret mode on the CPU: the round
against ``tests/oracle.py`` and against the ``fori`` path on seeded
padded-CSR shards small enough to run in seconds, forced through the new
path by calling it directly with a plan (the resolver would choose the
VMEM-resident kernel at these sizes); the resolver on shapes alone; and
the whole driver on the new path against the ``fori`` path.

Tolerances.  Everything here is float32 (the kernel's only dtype) while the
suite runs with x64 on.  Against the ``fori`` path (the same float32 step
math, the per-row dots summed in another order: a lane vector against a
gather-sum) dw and alpha agree to a few float32 ulps of values of order 1:
2e-6.  Against the float64 oracle the float32 chain of up to 90 dependent
steps is held to 2e-5.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cocoa_tpu.ops import pallas_sparse_hbm as ph
from cocoa_tpu.ops.local_sdca import local_sdca_fast
from cocoa_tpu.ops.rows import shard_margins

import oracle

F32 = np.float32
LAM = 1e-3


def _shards(k, n_shard, d, width, seed, lengths="mixed"):
    """Seeded padded-CSR shards: columns Zipf-like (low columns hot, so
    consecutive steps share them), no column twice in a row."""
    r = np.random.RandomState(seed)
    cols = np.zeros((k, n_shard, width), np.int32)
    vals = np.zeros((k, n_shard, width), F32)
    for a in range(k):
        for i in range(n_shard):
            want = {"one": 1, "full": width}.get(
                lengths, r.randint(1, width + 1))
            c = np.unique(np.minimum((d ** r.rand(4 * want)).astype(int),
                                     d - 1))
            if len(c) < want:       # top up with distinct columns
                c = np.unique(np.concatenate(
                    [c, r.choice(d, 2 * want, replace=False)]))
            c = np.sort(r.permutation(c)[:want])
            cols[a, i, :len(c)] = c
            vals[a, i, :len(c)] = r.randn(len(c)) / np.sqrt(len(c))
    y = np.where(r.randn(k, n_shard) >= 0, 1.0, -1.0).astype(F32)
    return cols, vals, y, (vals.astype(np.float64) ** 2).sum(-1).astype(F32)


def _fori(w, alpha, cols, vals, y, sq, idxs, n, mode, sigma, loss,
          smoothing=1.0):
    """The portable path: shard_margins + local_sdca_fast per shard."""
    dws, alphas = [], []
    for a in range(cols.shape[0]):
        shard = dict(sp_indices=cols[a], sp_values=vals[a], labels=y[a],
                     sq_norms=sq[a])
        da, dw = local_sdca_fast(
            shard_margins(w, shard), alpha[a], shard, idxs[a], LAM, n,
            jnp.zeros_like(w), mode=mode, sigma=sigma, loss=loss,
            smoothing=smoothing)
        dws.append(dw)
        alphas.append(alpha[a] + da)
    return sum(dws), jnp.stack(alphas)


def _dense(cols, vals, d):
    x = np.zeros(cols.shape[:2] + (d,), np.float64)
    for a in range(cols.shape[0]):
        for i in range(cols.shape[1]):
            np.add.at(x[a, i], cols[a, i], vals[a, i].astype(np.float64))
    return x


PLAN = ph.HbmPlan


def _case(k=2, n_shard=96, d=5000, width=8, h=40, lengths="mixed",
          repeats=False, plan=None):
    return dict(k=k, n_shard=n_shard, d=d, width=width, h=h,
                lengths=lengths, repeats=repeats, plan=plan)


CASES = {
    # the local id is the column (M = d): no remap
    "direct": _case(d=300),
    # ranks by sorting; one segment
    "remap": _case(plan=PLAN(t=1, s=64, m=1024, w_r=8, chunk=32,
                             direct=False)),
    # a round that spans three segments: dw carried between them in HBM
    "segments": _case(h=90, plan=PLAN(t=3, s=32, m=1024, w_r=8, chunk=32,
                                      direct=False)),
    # rows of one nonzero, and rows that fill every slot
    "length_1": _case(lengths="one", plan=PLAN(
        t=2, s=32, m=1024, w_r=8, chunk=16, direct=False)),
    "length_W": _case(lengths="full", plan=PLAN(
        t=2, s=32, m=1024, w_r=8, chunk=32, direct=False)),
    # with-replacement draws: a row stepped on twice in a segment reads the
    # earlier step's alpha (and hot columns carry from step to step)
    "repeats": _case(k=3, n_shard=64, width=40, h=50, repeats=True,
                     plan=PLAN(t=2, s=32, m=2048, w_r=64, chunk=32,
                               direct=False)),
    # the same on the ``direct`` plan (criteo's), the whole round one
    # segment: most steps read the alpha an earlier step wrote, a (1, 1)
    # pick out of the kernel's own output block
    "repeats_direct": _case(n_shard=48, d=300, h=64, repeats=True),
    # W a multiple of 128: the rows are stored row-major, a plain gather
    # of rows fetches them
    "row_major": _case(n_shard=64, width=128, plan=PLAN(
        t=2, s=32, m=4096, w_r=128, chunk=32, direct=False)),
}
ALGS = [("plus", "hinge"), ("cocoa", "hinge"), ("plus", "logistic"),
        ("cocoa", "logistic"), ("frozen", "hinge")]


@pytest.mark.parametrize("mode,loss", ALGS,
                         ids=[f"{m}-{lo}" for m, lo in ALGS])
@pytest.mark.parametrize("case", list(CASES))
def test_round_matches_the_fori_path_and_the_oracle(case, mode, loss):
    c = CASES[case]
    k, n_shard, d, h = c["k"], c["n_shard"], c["d"], c["h"]
    cols, vals, y, sq = _shards(k, n_shard, d, c["width"], seed=3,
                                lengths=c["lengths"])
    r = np.random.RandomState(4)
    if c["repeats"]:
        idxs = r.randint(0, n_shard, size=(k, h)).astype(np.int32)
    else:
        idxs = np.stack([r.permutation(n_shard)[:h]
                         for _ in range(k)]).astype(np.int32)
    w = (r.randn(d) * 0.1).astype(F32)
    alpha = r.rand(k, n_shard).astype(F32)
    n, sigma = k * n_shard, (float(k) if mode == "plus" else 1.0)
    plan = c["plan"] or ph.hbm_plan(d, c["width"], h, 4)
    assert plan.direct == (c["plan"] is None)
    if c["repeats"]:        # some segment does step on a row twice
        pad = np.pad(idxs, ((0, 0), (0, plan.t * plan.s - h)),
                     constant_values=-1).reshape(k * plan.t, plan.s)
        assert max(np.unique(seg[seg >= 0], return_counts=True)[1].max()
                   for seg in pad) >= 2
    args = [jnp.asarray(a) for a in (w, alpha, cols, vals, y, sq, idxs)]
    dw, a_new = ph.pallas_sparse_hbm_round(
        *args, LAM, n, mode=mode, sigma=sigma, interpret=True, loss=loss,
        plan=plan)
    assert dw.dtype == a_new.dtype == jnp.float32
    dw_f, a_f = _fori(*args, n, mode, sigma, loss)
    assert float(jnp.abs(a_f - args[1]).max()) > 0.1    # the round moved
    np.testing.assert_allclose(dw, dw_f, atol=2e-6, rtol=0)
    np.testing.assert_allclose(a_new, a_f, atol=2e-6, rtol=0)
    assert float(a_new.min()) >= 0.0 and float(a_new.max()) <= 1.0
    if loss == "hinge" and mode != "frozen":
        x = _dense(cols, vals, d)
        dw_o = np.zeros(d)
        for a in range(k):
            da, dwk = oracle.local_sdca(
                x[a], y[a].astype(np.float64), w.astype(np.float64),
                alpha[a].astype(np.float64), idxs[a], LAM, n,
                mode == "plus", sigma)
            dw_o += dwk
            np.testing.assert_allclose(a_new[a], alpha[a] + da, atol=2e-5,
                                       rtol=0)
        np.testing.assert_allclose(dw, dw_o, atol=2e-5, rtol=0)


def test_local_ids_rank_the_distinct_columns():
    r = np.random.RandomState(0)
    cols = np.sort(r.randint(0, 900, (64, 8)), axis=1).T.astype(np.int32)
    used = np.arange(8)[:, None] < r.randint(1, 9, 64)[None, :]
    lid, ucols, n_cols = ph._local_ids(jnp.asarray(cols), jnp.asarray(used))
    distinct = np.unique(cols[used])
    assert int(n_cols) == len(distinct)
    np.testing.assert_array_equal(np.asarray(ucols)[:len(distinct)],
                                  distinct)
    assert (np.asarray(ucols)[len(distinct):] == ph._NONE).all()
    np.testing.assert_array_equal(
        np.asarray(ucols)[np.asarray(lid)][used], cols[used])


def test_fetch_rows_reads_the_rows_as_stored():
    """Rows on the lanes (W = 8 against 200 rows) go through the tile
    kernel, W = 128 through the plain gather: both give (W, S) tables."""
    for width in (8, 128):
        cols, vals, _, _ = _shards(2, 200, 3000, width, seed=1)
        assert ph.rows_on_lanes(200, width) == (width == 8)
        idx = np.random.RandomState(2).permutation(200)[:70].astype(np.int32)
        c, v = ph._fetch_rows(jnp.asarray(cols), jnp.asarray(vals),
                              jnp.int32(1), jnp.asarray(idx), True)
        np.testing.assert_array_equal(np.asarray(c), cols[1][idx].T)
        np.testing.assert_array_equal(np.asarray(v), vals[1][idx].T)


def test_plan_fits_its_budgets():
    kddb = ph.hbm_plan(29890095, 64, 240801)
    assert (kddb.t, kddb.direct) == (2, False)
    assert kddb.t * kddb.s >= 240801 and kddb.s % kddb.chunk == 0
    assert kddb.m % 1024 == 0 and kddb.m % kddb.column_chunk == 0
    assert kddb.m >= kddb.s * kddb.w_r
    assert ph.hbm_vmem_estimate(kddb.s, kddb.m, 4) <= ph.HBM_VMEM_BUDGET
    # a whole epoch a round (localIterFrac = 1): more segments, same budget
    epoch = ph.hbm_plan(29890095, 64, 2408013)
    assert epoch.t > kddb.t
    assert ph.hbm_vmem_estimate(epoch.s, epoch.m, 4) <= ph.HBM_VMEM_BUDGET
    # a small d is addressed directly
    assert ph.hbm_plan(47236, 548, 253).direct
    assert ph.sparse_hbm_fits(29890095, 64, 240801, 4)


# --- the resolver, on shapes alone ------------------------------------------


def _shapes(n, d, k, width):
    from cocoa_tpu.data.sharding import (ShardedDataset, pad_rows,
                                         split_sizes)

    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    here = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    rows = jax.ShapeDtypeStruct((k, n_shard), jnp.float32, sharding=here)
    wide = lambda dt: jax.ShapeDtypeStruct(  # noqa: E731
        (k, n_shard, width), dt, sharding=here)
    return ShardedDataset(
        layout="sparse", n=n, num_features=d, counts=sizes.astype(np.int64),
        labels=rows, mask=rows, sq_norms=rows, sp_indices=wide(jnp.int32),
        sp_values=wide(jnp.float32))


SHAPES = {
    # name: (n, d, K, W, localIterFrac, the state the resolver must pick)
    "rcv1": (20242, 47236, 8, 548, 0.1, "vmem"),
    "rcv1_k4": (20242, 47236, 4, 548, 1.0, "vmem"),
    "kddb": (19264097, 29890095, 8, 64, 0.1, "hbm"),
    "kddb_epoch_rounds": (19264097, 29890095, 8, 64, 1.0, "hbm"),
    "rcv1_full": (677399, 47236, 8, 548, 0.1, "hbm"),
    "url": (2396130, 3231961, 8, 414, 0.1, "hbm"),
    "wide_rows": (350000, 16609143, 16, 4096, 0.1, "hbm"),
}


@pytest.mark.parametrize("name", list(SHAPES))
def test_resolver_never_leaves_a_sparse_set_on_fori_on_a_tpu(name,
                                                             monkeypatch):
    """From shapes alone: today's VMEM-resident kernel where it fits (rcv1),
    the HBM-state kernel past it (kddb), and never the XLA ``fori`` chain
    for a sparse set that fits a chip's HBM."""
    from cocoa_tpu.solvers import cocoa as cocoa_mod

    n, d, k, width, frac, state = SHAPES[name]
    assert 8 * n * width <= 16e9            # the rows fit one chip's HBM
    ds = _shapes(n, d, k, width)
    h = max(1, int(frac * n / k))
    tpu = type("Device", (), {"platform": "tpu"})()
    monkeypatch.setattr(cocoa_mod.jax, "devices", lambda *a: [tpu])
    path = cocoa_mod.resolve_solver_path(ds, h, None, math="fast")
    assert (path.inner, path.kernel, path.state) == ("sequential", "pallas",
                                                     state)
    assert path.interpret is False and path.as_dict()["state"] == state
    monkeypatch.undo()
    cpu = cocoa_mod.resolve_solver_path(ds, h, None, math="fast")
    assert (cpu.kernel, cpu.state) == ("fori", "hbm")   # the CPU reference


@pytest.mark.parametrize("name, plan", [
    # a quarter of criteo (chipbench/configs/criteo.json), as the loader
    # stores rows of 39 and at 39 itself: d = 10^6 fits the budget whole
    ("criteo_quarter", (11460154, 1000000, 8, 40, "direct", 1, 40)),
    ("criteo_quarter_at_39", (11460154, 1000000, 8, 39, "direct", 1, 40)),
    ("kddb", (19264097, 29890095, 8, 64, "sorted", 2, 64)),
])
def test_solver_path_says_which_plan_the_hbm_kernel_runs(name, plan,
                                                         monkeypatch):
    """``hbm_plan`` at a cell's sizes, and the fields of the run's record
    that repeat it: ``direct`` (the local id is the column, [w | dw] whole
    in VMEM, ``t`` = 1) at d = 10^6, ``sorted`` in two segments at kddb;
    the tables as wide as the rectangle's own 8-slot groups (40 for rows
    of 39, stored at 40 or forced to 39); the walk ``unrolled`` over all of
    them where the loader saw rows of one length and ``grouped`` otherwise
    (shapes alone carry no lengths: ``slots_walked`` None); None off the
    HBM-state kernel."""
    from cocoa_tpu.solvers import cocoa as cocoa_mod

    n, d, k, width, ids, segments, table = plan
    h = int(0.1 * n / k)
    p = ph.hbm_plan(d, width, h)
    assert (p.direct, p.t, p.w_r, p.unrolled) == (ids == "direct", segments,
                                                  table, False)
    assert ph._table_width(p.w_r) == (48 if table == 40 else 80)
    if ids == "direct":
        assert (p.s, p.m) == (143264, 1000448)      # [w | dw]: 8 MB
        assert ph.hbm_vmem_estimate(p.s, p.m, 4) < 10 << 20
        assert ph.hbm_plan(d, width, h, one_length=True) == \
            dataclasses.replace(p, unrolled=True)
    ds = _shapes(n, d, k, width)
    tpu = type("Device", (), {"platform": "tpu"})()
    monkeypatch.setattr(cocoa_mod.jax, "devices", lambda *a: [tpu])
    path = cocoa_mod.resolve_solver_path(ds, h, None, math="fast",
                                         loss="logistic")
    # the chain solves its step on (1, 1) vectors under any loss; the
    # VMEM-resident sparse kernel and the fori path on a coordinate's scalars
    assert (path.state, path.step_solve) == ("hbm", "vector")
    assert "each step solved on the vector unit" in path.describe()
    assert (path.local_ids, path.segments, path.table_width) == (
        ids, segments, table)
    assert (path.slot_walk, path.slots_walked) == ("grouped", None)
    assert f"{ids} local ids, {segments} segment(s) a shard, tables " \
        f"{table} slots wide, a row's slots walked, the first 32 written " \
        f"out, then in groups of 32 and 8)" in path.describe()
    ds._one_length = True               # data/sharding.note_row_lengths
    one = cocoa_mod.resolve_solver_path(ds, h, None, math="fast")
    assert (one.slot_walk, one.slots_walked, one.table_width) == (
        "unrolled", float(table), table)
    assert f"tables {table} slots wide, walks {table}.0 of {table} slots " \
        f"a step, unrolled)" in one.describe()
    monkeypatch.undo()
    cpu = cocoa_mod.resolve_solver_path(ds, h, None, math="fast")
    assert (cpu.kernel, cpu.local_ids, cpu.segments, cpu.table_width,
            cpu.slot_walk, cpu.slots_walked) == ("fori",) + (None,) * 5
    assert cpu.step_solve == "scalar"
    assert "local ids" not in cpu.describe()
    rcv1 = _shapes(*SHAPES["rcv1"][:4])
    monkeypatch.setattr(cocoa_mod.jax, "devices", lambda *a: [tpu])
    vmem = cocoa_mod.resolve_solver_path(rcv1, 253, None, math="fast")
    assert (vmem.state, vmem.local_ids, vmem.step_solve) == (
        "vmem", None, "scalar")


# --- the whole driver on the new path ---------------------------------------


@pytest.fixture
def sparse_ds():
    from cocoa_tpu.data import shard_dataset
    from cocoa_tpu.data.synth import synth_sparse

    return shard_dataset(synth_sparse(480, 400, nnz_mean=6, seed=2), k=4,
                         layout="sparse", dtype=jnp.float32)


@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_driver_on_the_hbm_path_follows_the_fori_path(sparse_ds, loss,
                                                      monkeypatch):
    """``run_cocoa`` with the cell's flags: the resolver, told that the
    VMEM-resident kernel does not fit, takes the HBM-state kernel
    (interpreted here), reports it, and certifies the same gap in the same
    rounds as the ``fori`` path."""
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.ops import pallas_sparse
    from cocoa_tpu.solvers import run_cocoa

    ds = sparse_ds
    params = Params(n=ds.n, num_rounds=60, local_iters=12, lam=1e-2,
                    loss=loss)
    kw = dict(plus=True, quiet=True, math="fast", device_loop=True,
              rng="permuted", gap_target=2e-2, accel="auto")
    debug = DebugParams(debug_iter=5, seed=0)
    w0, a0, t0 = run_cocoa(ds, params, debug, **kw)
    assert t0.meta["solver_path"]["kernel"] == "fori"
    monkeypatch.setattr(pallas_sparse, "sparse_kernel_fits",
                        lambda *a, **k: False)
    w1, a1, t1 = run_cocoa(ds, params, debug, pallas=True, **kw)
    path = t1.meta["solver_path"]
    assert (path["kernel"], path["state"], path["interpret"]) == (
        "pallas", "hbm", True)
    assert t1.stopped == t0.stopped == "target"
    assert [r.round for r in t1.records] == [r.round for r in t0.records]
    np.testing.assert_allclose([r.gap for r in t1.records],
                               [r.gap for r in t0.records], atol=1e-5)
    np.testing.assert_allclose(w1, w0, atol=1e-5)
    np.testing.assert_allclose(a1, a0, atol=1e-5)


# --- all-rows passes in blocks of rows (ops/rows.py) ------------------------


@pytest.mark.parametrize("n_rows", [1000, 1024])
def test_row_blocked_margins_and_axpy_equal_the_whole_pass(n_rows,
                                                           monkeypatch):
    """Past ``GATHER_BLOCK_SLOTS`` the gather eval and the w(alpha) scatter
    run in blocks of rows (the last pulled back to end on the last row):
    the same margins to the bit (each row's sum is its own), the same
    scatter-add to float32 rounding (1e-5 of values of order 10: the order
    of the adds into a column differs)."""
    from cocoa_tpu.ops import rows

    r = np.random.RandomState(0)
    k, width, d = 3, 8, 500
    idx = jnp.asarray(r.randint(0, d, (k, n_rows, width)).astype(np.int32))
    val = jnp.asarray(r.randn(k, n_rows, width).astype(F32))
    w = jnp.asarray(r.randn(d).astype(F32))
    coefs = jnp.asarray(r.randn(k, n_rows).astype(F32))
    shards = dict(sp_indices=idx, sp_values=val)
    margins = jax.jit(jax.vmap(lambda i, v: rows.shard_margins(
        w, dict(sp_indices=i, sp_values=v))))
    axpy = jax.jit(lambda c, s, v: rows.shards_axpy(c, s, v))
    m_whole, a_whole = margins(idx, val), axpy(coefs, shards, w)
    monkeypatch.setattr(rows, "GATHER_BLOCK_SLOTS", width * 300)
    assert rows.row_block(n_rows, width) == 256         # 4 blocks
    m_blocks = jax.jit(jax.vmap(lambda i, v: rows.shard_margins(
        w, dict(sp_indices=i, sp_values=v))))(idx, val)
    a_blocks = jax.jit(lambda c, s, v: rows.shards_axpy(c, s, v))(
        coefs, shards, w)
    np.testing.assert_array_equal(m_blocks, m_whole)
    np.testing.assert_allclose(a_blocks, a_whole, atol=1e-4, rtol=0)


def _rows_of(lengths, width, d, seed):
    """(K, n, W) padded-CSR rows of the given (K, n) lengths."""
    r = np.random.RandomState(seed)
    lengths = np.asarray(lengths)
    live = np.arange(width)[None, None, :] < lengths[..., None]
    idx = np.where(live, r.randint(0, d, lengths.shape + (width,)), 0)
    val = np.where(live, r.randn(*lengths.shape, width), 0.0)
    return idx.astype(np.int32), val.astype(F32)


LENGTH_LAWS = {
    # n = 300 rows a shard in blocks of 128: two blocks and a tail block
    # that starts 84 rows early (it overlaps its neighbour)
    "0_1_8_9_64": lambda r, k, n, w: np.sort(
        r.choice([0, 1, 8, 9, w], (k, n)), axis=1)[:, ::-1],
    "all_equal": lambda r, k, n, w: np.full((k, n), 9),
    "all_empty": lambda r, k, n, w: np.zeros((k, n), int),
    "unordered": lambda r, k, n, w: r.randint(0, w + 1, (k, n)),
    # K shards whose blocks' longest rows differ: under vmap the loop runs
    # to the longest of them
    "shards_differ": lambda r, k, n, w: np.sort(np.stack(
        [r.randint(0, min(m, w) + 1, n) for m in (3, w, 17)[:k]]), axis=1)[:, ::-1],
}


@pytest.mark.parametrize("width", [64, 12])
@pytest.mark.parametrize("law", list(LENGTH_LAWS))
def test_length_aware_block_passes_equal_the_whole_pass(law, width,
                                                        monkeypatch):
    """The block passes stop at a block's longest row, a slot group at a
    time: the same margins and the same scatter-add as one pass over every
    slot, to float32 rounding (a row's sum is made group by group), with
    the lengths and without them, at a width that is no multiple of the
    group too."""
    from cocoa_tpu.ops import rows

    r = np.random.RandomState(4)
    k, n, d = 3, 300, 700
    lengths = LENGTH_LAWS[law](r, k, n, width)
    idx, val = _rows_of(lengths, width, d, seed=5)
    w = jnp.asarray(r.randn(d).astype(F32))
    coefs = jnp.asarray(r.randn(k, n).astype(F32))
    whole = dict(sp_indices=jnp.asarray(idx), sp_values=jnp.asarray(val))
    known = {**whole, "sp_row_len": jnp.asarray(lengths, jnp.int32)}

    def both(shards):
        m = jax.jit(jax.vmap(rows.shard_margins, in_axes=(None, 0)))(
            w, shards)
        return m, jax.jit(rows.shards_axpy)(coefs, shards, w)

    m_whole, a_whole = both(whole)
    monkeypatch.setattr(rows, "GATHER_BLOCK_SLOTS", width * 128)
    assert rows.row_block(n, width) == 128
    for shards in (known, whole):
        m, a = both(shards)
        np.testing.assert_allclose(m, m_whole, atol=2e-6, rtol=2e-6)
        np.testing.assert_allclose(a, a_whole, atol=1e-4, rtol=0)
    # one shard alone (no vmap), as a mesh's shard_map body sees it
    one = {name: a[1] for name, a in known.items()}
    np.testing.assert_allclose(jax.jit(rows.shard_margins)(w, one),
                               m_whole[1], atol=2e-6, rtol=2e-6)


def test_pass_slots_counts_what_the_block_passes_touch(monkeypatch):
    """``rows.pass_slots`` against a count by hand: blocks of 128 rows, the
    tail block pulled back (it goes as far as the longest row it reads, and
    counts the 44 rows that are its own), groups of 8 slots, every shard
    that shares a loop going as far as the longest of them."""
    from cocoa_tpu.ops import rows

    k, n, width = 2, 300, 64
    lengths = np.zeros((k, n), int)
    lengths[0, :128] = 64       # block 0: 8 groups (shard 1: 20 -> 3)
    lengths[1, :128] = 20
    lengths[0, 128:256] = 9     # block 1: 2 groups
    lengths[1, 128:256] = 16
    lengths[:, 256:] = 1        # tail block = rows 172..299: it sees the 16
    assert rows.pass_slots(lengths, width) == k * n * width   # one block
    monkeypatch.setattr(rows, "GATHER_BLOCK_SLOTS", width * 128)
    together = (8 * 128 + 2 * 128 + 2 * 44) * 8 * k
    assert rows.pass_slots(lengths, width) == together
    # each shard with a loop of its own (one shard a device)
    apart = ((8 + 3) * 128 + (2 + 2) * 128 + (2 + 2) * 44) * 8
    assert rows.pass_slots(lengths, width, together=1) == apart
    # rows of kddb's length law, in order: about a half
    r = np.random.RandomState(0)
    law = np.clip(np.round(np.exp(3.27 + 0.5 * r.randn(1, 128 * 200))), 1,
                  64).astype(int)
    share = rows.pass_slots(-np.sort(-law, axis=1), 64) / law.size / 64
    assert 0.48 < share < 0.56
    assert rows.pass_slots(law, 64) / law.size / 64 > 0.99   # unordered


def test_job_from_an_unordered_sparse_set_orders_it_once(monkeypatch):
    """One interpret-mode job on the HBM-state kernel from a sparse dataset
    in built order whose all-rows passes run in row blocks: the entry puts
    its rows in length order (once: a second job finds the order), the job
    certifies, says what share of the slots its passes touch, and its α,
    taken back to the rows as built, is the w it returns: w = w(α) against
    ``tests/oracle.py``'s objectives on the original rows."""
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data import shard_dataset, sharding
    from cocoa_tpu.data.synth import synth_sparse
    from cocoa_tpu.ops import pallas_sparse, rows
    from cocoa_tpu.solvers import run_cocoa

    data = synth_sparse(1200, 400, nnz_mean=6, seed=2)
    ds = shard_dataset(data, k=4, layout="sparse", dtype=jnp.float32)
    width = ds.sp_indices.shape[-1]
    assert ds.row_order is None
    monkeypatch.setattr(rows, "GATHER_BLOCK_SLOTS", width * 128)
    monkeypatch.setattr(pallas_sparse, "sparse_kernel_fits",
                        lambda *a, **k: False)
    calls = []
    order = sharding.order_rows_by_length
    monkeypatch.setattr(sharding, "order_rows_by_length",
                        lambda ds: calls.append(1) or order(ds))
    params = Params(n=ds.n, num_rounds=60, local_iters=30, lam=1e-2)
    kw = dict(plus=True, quiet=True, math="fast", device_loop=True,
              rng="permuted", gap_target=2e-2, accel="auto", pallas=True)
    debug = DebugParams(debug_iter=5, seed=0)
    w, alpha, traj = run_cocoa(ds, params, debug, **kw)
    assert calls == [1] and ds.row_order is not None
    path = traj.meta["solver_path"]
    assert (path["kernel"], path["state"], path["interpret"]) == (
        "pallas", "hbm", True)
    lens = np.asarray(ds._row_len_cache)
    assert path["pass_slot_share"] == pytest.approx(
        rows.pass_slots(lens, width) / lens.size / width)
    assert 0.2 < path["pass_slot_share"] < 0.8
    assert traj.stopped == "target" and traj.records[-1].gap <= 2e-2
    w2, alpha2, traj2 = run_cocoa(ds, params, debug, **kw)
    assert calls == [1]                             # found in order
    np.testing.assert_array_equal(np.asarray(w2), np.asarray(w))
    # on the rows as built, through row_order
    X, y = data.to_dense(), data.labels
    sizes = ds.counts
    a_built = sharding.rows_as_built(ds, alpha)
    a_rows = np.concatenate([a_built[k, :sizes[k]] for k in range(ds.k)])
    assert a_rows.min() >= 0.0 and a_rows.max() <= 1.0
    w_of_alpha = (y * a_rows) @ X / (params.lam * ds.n)
    np.testing.assert_allclose(np.asarray(w), w_of_alpha, atol=2e-5)
    gap = oracle.duality_gap(X, y, np.asarray(w, np.float64), a_rows.sum(),
                             params.lam)
    assert gap == pytest.approx(traj.records[-1].gap, abs=2e-5)
