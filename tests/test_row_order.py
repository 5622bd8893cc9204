"""A sparse dataset's rows in length order (data/sharding.py
``order_rows_by_length``): what the order is, what it leaves alone, who
asks for it, and what crosses it by row (checkpoints)."""

import numpy as np
import pytest

import jax.numpy as jnp

from cocoa_tpu import checkpoint as ckpt_lib
from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data import sharding
from cocoa_tpu.data.sharding import (order_rows_by_length,
                                     order_rows_for_passes, rows_as_built,
                                     rows_as_ordered, shard_dataset)
from cocoa_tpu.data.synth import synth_sparse
from cocoa_tpu.evals import objectives
from cocoa_tpu.ops import rows
from cocoa_tpu.ops.pallas_sparse import row_lengths

LAM = 1e-2


def _data():
    return synth_sparse(1102, 300, nnz_mean=6, seed=3)


def _fresh(**kw):
    # 1,102 rows over 4 shards: 276, 276, 275, 275 real rows in 288
    return shard_dataset(_data(), k=4, layout="sparse", dtype=jnp.float32,
                         **kw)


def _row_sets(ds):
    """Per shard, its real rows as a sorted list of (label, columns,
    values) — what a shard IS to CoCoA, whatever order it keeps them in."""
    out = []
    for k in range(ds.k):
        m = int(ds.counts[k])
        out.append(sorted(
            (float(ds.labels[k, i]), tuple(np.asarray(ds.sp_indices[k, i])),
             tuple(np.asarray(ds.sp_values[k, i]))) for i in range(m)))
    return out


@pytest.mark.parametrize("at_once", [True, False],
                         ids=["all_shards_at_once", "a_shard_at_a_time"])
def test_order_is_a_stable_descending_permutation_within_each_shard(
        at_once, monkeypatch):
    if not at_once:
        monkeypatch.setattr(sharding, "ORDER_AT_ONCE_BYTES", 0)
    built, ds = _fresh(), _fresh()
    assert ds.row_order is None
    assert order_rows_by_length(ds) is ds
    order = np.asarray(ds.row_order)
    assert order.dtype == np.int32 and order.shape == (ds.k, ds.n_shard)
    # a permutation of each shard's positions; the shards' row sets and
    # their padding stay what they were
    assert (np.sort(order, axis=1) == np.arange(ds.n_shard)).all()
    assert _row_sets(ds) == _row_sets(built)
    assert ds.counts.tolist() == built.counts.tolist()
    for k in range(ds.k):
        m = int(ds.counts[k])
        assert np.asarray(ds.mask[k]).tolist() == [1.0] * m + [0.0] * (
            ds.n_shard - m)                 # padding rows stay last
        assert (order[k, m:] == np.arange(m, ds.n_shard)).all()
    # descending lengths, ties in built order (stable), and every by-row
    # field moved with its row
    lens = np.asarray(row_lengths(built.sp_values))
    new_lens = np.asarray(row_lengths(ds.sp_values))
    assert (np.diff(new_lens, axis=1) <= 0).all()
    assert (new_lens == np.asarray(ds._row_len_cache)).all()
    for k in range(ds.k):
        assert order[k].tolist() == sorted(
            range(ds.n_shard), key=lambda i: (-lens[k, i], i))
    for name in ("labels", "mask", "sq_norms", "sp_indices", "sp_values"):
        before = np.asarray(getattr(built, name))
        took = np.take_along_axis(
            before, order.reshape(order.shape + (1,) * (before.ndim - 2)),
            axis=1)
        np.testing.assert_array_equal(np.asarray(getattr(ds, name)), took)
    # the shard dict says how long the rows are, for the block passes
    assert (np.asarray(ds.shard_arrays()["sp_row_len"]) == new_lens).all()
    assert "sp_row_len" not in built.shard_arrays()


def test_order_is_idempotent_and_row_order_inverts_it():
    ds = order_rows_by_length(_fresh())
    held = (ds.sp_indices, ds.sp_values, ds.labels, ds.row_order)
    order_rows_by_length(ds)
    assert all(a is b for a, b in zip(
        held, (ds.sp_indices, ds.sp_values, ds.labels, ds.row_order)))
    by_row = np.random.RandomState(0).rand(2, ds.k, ds.n_shard)
    np.testing.assert_array_equal(
        rows_as_ordered(ds, rows_as_built(ds, by_row)), by_row)
    np.testing.assert_array_equal(
        rows_as_built(ds, rows_as_ordered(ds, by_row)), by_row)
    built = _fresh()
    np.testing.assert_array_equal(        # labels, by the rows as built
        rows_as_built(ds, ds.labels), np.asarray(built.labels))
    # an array of a dataset in built order passes through untouched
    assert rows_as_built(built, by_row) is not None
    np.testing.assert_array_equal(rows_as_ordered(built, by_row), by_row)
    # a dense dataset has no length order
    dense = shard_dataset(_data(), k=4, layout="dense", dtype=jnp.float32)
    assert order_rows_by_length(dense).row_order is None


def test_hybrid_panel_and_eval_twin_move_with_their_rows():
    built = _fresh(hot_cols=8, eval_dense=True)
    ds = order_rows_by_length(_fresh(hot_cols=8, eval_dense=True))
    order = np.asarray(ds.row_order)[:, :, None]
    for name in ("X_hot", "X_eval"):
        np.testing.assert_array_equal(
            np.asarray(getattr(ds, name)),
            np.take_along_axis(np.asarray(getattr(built, name)), order, 1))
    np.testing.assert_array_equal(np.asarray(ds.hot_cols),
                                  np.asarray(built.hot_cols))


def test_objectives_of_a_fixed_pair_do_not_see_the_order():
    """Primal, dual and gap of one (w, α) on the dataset as built and in
    length order (α moved with its rows): equal to float32 rounding."""
    built, ds = _fresh(), order_rows_by_length(_fresh())
    r = np.random.RandomState(1)
    w = jnp.asarray(r.randn(built.num_features).astype(np.float32) * 0.1)
    alpha = r.rand(built.k, built.n_shard).astype(np.float32) \
        * np.asarray(built.mask)
    was = objectives.evaluate(built, w, jnp.asarray(alpha), LAM)
    now = objectives.evaluate(ds, w, jnp.asarray(rows_as_ordered(ds, alpha)),
                              LAM)
    np.testing.assert_allclose(now[:2], was[:2], rtol=2e-6)
    assert objectives.dual_objective(
        ds, w, jnp.asarray(rows_as_ordered(ds, alpha)), LAM) == \
        pytest.approx(objectives.dual_objective(built, w, jnp.asarray(alpha),
                                                LAM), rel=2e-6)


def test_only_a_set_whose_passes_run_in_row_blocks_is_ordered(monkeypatch):
    """Who asks for the order: ``shard_dataset`` and ``run_sdca_family``,
    by the shape test the all-rows passes branch on.  A set one block holds
    keeps its rows as built."""
    small = _fresh()
    assert order_rows_for_passes(small).row_order is None
    width = small.sp_indices.shape[-1]
    monkeypatch.setattr(rows, "GATHER_BLOCK_SLOTS", width * 40)
    assert rows.row_block(small.n_shard, width) < small.n_shard
    at_ingest = _fresh()
    assert at_ingest.row_order is not None
    assert _row_sets(at_ingest) == _row_sets(small)
    assert order_rows_for_passes(small).row_order is not None
    np.testing.assert_array_equal(np.asarray(small.row_order),
                                  np.asarray(at_ingest.row_order))


def _run(ds, **kw):
    from cocoa_tpu.solvers import run_cocoa

    return run_cocoa(
        ds, Params(n=ds.n, num_rounds=20, local_iters=10, lam=LAM),
        DebugParams(debug_iter=5, seed=0, **{
            k: kw.pop(k) for k in ("chkpt_dir", "chkpt_iter") if k in kw}),
        plus=True, quiet=True, math="fast", rng="permuted", **kw)


@pytest.mark.parametrize("accel", ["off", "on"])
def test_checkpoint_keeps_alpha_by_the_rows_as_built(tmp_path, accel,
                                                     monkeypatch):
    """A checkpoint written under a dataset in length order holds α (and
    the ``--accel`` window bank) by the rows' positions as built: loaded
    under a freshly made dataset — which this run orders itself, on entry —
    it resumes to the uninterrupted run's result, and read by hand it pairs
    every α with its own row."""
    width = _fresh().sp_indices.shape[-1]
    monkeypatch.setattr(rows, "GATHER_BLOCK_SLOTS", width * 40)
    ds = _fresh()
    assert ds.row_order is not None                 # ordered at ingest
    w0, a0, t0 = _run(ds, accel=accel, scan_chunk=5, chkpt_dir=str(tmp_path),
                      chkpt_iter=10)
    path = str(tmp_path / "CoCoA+-r000010.npz")
    meta, arrays = ckpt_lib.load_full(path)
    # by hand: w = (1/(λn)) Σ y α x on the rows AS BUILT
    monkeypatch.setattr(rows, "GATHER_BLOCK_SLOTS", 1 << 20)
    built = _fresh()
    assert built.row_order is None
    idx, val = np.asarray(built.sp_indices), np.asarray(built.sp_values)
    w_of_alpha = np.zeros(built.num_features)
    np.add.at(w_of_alpha, idx, (np.asarray(built.labels) * arrays["alpha"]
                                )[..., None] * val / (LAM * built.n))
    np.testing.assert_allclose(arrays["w"], w_of_alpha, atol=2e-5)
    # resumed under a dataset handed over in built order
    monkeypatch.setattr(rows, "GATHER_BLOCK_SLOTS", width * 40)
    sched = (None if meta.get("sched") is None
             else np.asarray(meta["sched"], np.float32))
    w1, a1, t1 = _run(built, accel=accel, scan_chunk=5, w_init=arrays["w"],
                      alpha_init=arrays["alpha"],
                      hist_init=arrays.get("hist"), sched_init=sched,
                      start_round=meta["round"] + 1)
    assert built.row_order is not None              # ordered on entry
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w0))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a0))
    np.testing.assert_array_equal(rows_as_built(built, a1),
                                  rows_as_built(ds, a0))
