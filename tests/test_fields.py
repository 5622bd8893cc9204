"""Click-log rows under logistic regression, on the CPU: the stand-in the
benchmark's ``criteo.logistic`` cell trains on (every row 39 nonzeros, one
a field), the job's flags on the HBM-state kernel's ``direct`` plan
(interpreted) against the ``fori`` path and the plain reference, the
ordering rule for rows of one length, and the loader's rule for a
rectangle's width (data/sharding.rectangle_width) against the width it
built until PR 45.

Tolerances: the interpreted kernel against ``fori`` as
tests/test_sparse_hbm.py (1e-5 on w and alpha: two orders of the same
float32 sums); the reference against the program's float32 objectives as
tests/chipbench/test_chipbench_sparse.py (2e-6 relative); a rectangle
stored wider against the same rows stored at their own width: 1e-6 on w
and alpha (the slots past a row hold column 0 and value 0: the chain adds
exact zeros, an all-rows pass adds the same terms in XLA's order for the
other width, a last bit)."""

import os
import sys

import numpy as np
import pytest

import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chipbench import reference_sparse, registry  # noqa: E402
from cocoa_tpu.config import DebugParams, Params  # noqa: E402
from cocoa_tpu.data import sharding  # noqa: E402
from cocoa_tpu.data.libsvm import LibsvmData  # noqa: E402
from cocoa_tpu.data.sharding import (order_rows_by_length,  # noqa: E402
                                     rectangle_width, rows_of_one_length,
                                     shard_dataset)
from cocoa_tpu.ops import pallas_sparse, rows  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
ARGS = dict(counter_fields=13, categorical_fields=26, smallest_field=4,
            click_share=0.26, flip=0.02, planted_density_inv=2)
SMALL = dict(name="small", n=6000, d=4096, num_splits=4,
             local_iter_frac=0.1, dtype="float32", loss="logistic",
             layout="sparse", mean_nnz=39.0, generator_args=ARGS)
SMALL["lambda"] = 1e-3
FLAGS = dict(plus=True, quiet=True, math="fast", device_loop=True,
             rng="permuted", accel="auto")


@pytest.fixture(scope="module")
def gen():
    mod = registry.load_module(BENCH, "generators", "sparse_fields")
    # this process's platform is cpu, where the program's own answer is
    # ``fori``: the pre-flight is held by tests/chipbench/
    # test_chipbench_fields.py
    mod.preflight = lambda config, resolve=None: {}
    return mod


def _fixed_rows(n, d, lengths, seed):
    """A LIBSVM set whose row i has ``lengths[i % len(lengths)]`` nonzeros
    at ascending random columns, unit rows, labels from a planted w."""
    r = np.random.RandomState(seed)
    lens = np.resize(np.asarray(lengths), n)
    indptr = np.concatenate([[0], np.cumsum(lens)])
    indices = np.concatenate([np.sort(r.choice(d, m, replace=False))
                              for m in lens]).astype(np.int32)
    values = np.abs(r.randn(indptr[-1])) + 0.1
    values /= np.sqrt(np.add.reduceat(values ** 2, indptr[:-1]))[
        np.repeat(np.arange(n), lens)]
    w = r.randn(d)
    score = np.add.reduceat(values * w[indices], indptr[:-1])
    y = np.where(score + 0.3 * r.randn(n) > 0, 1.0, -1.0)
    return LibsvmData(labels=y, indptr=indptr.astype(np.int64),
                      indices=indices, values=values, num_features=d)


def _job(ds, loss="logistic", rounds=40, target=2e-2, h=16, **kw):
    from cocoa_tpu.solvers import run_cocoa

    return run_cocoa(
        ds, Params(n=ds.n, num_rounds=rounds, local_iters=h, lam=1e-2,
                   loss=loss),
        DebugParams(debug_iter=5, seed=0), gap_target=target,
        **{**FLAGS, **kw})


def _on_the_hbm_kernel(monkeypatch):
    """The resolver told that the VMEM-resident kernel does not fit: with
    ``pallas=True`` a sparse job then runs the HBM-state kernel,
    interpreted."""
    monkeypatch.setattr(pallas_sparse, "sparse_kernel_fits",
                        lambda *a, **k: False)


# --- the stand-in -----------------------------------------------------------

def test_generator_makes_39_a_row_one_a_field(gen):
    ds = gen.make(SMALL, 4500000029)
    cols, vals, y, mask, sq = (np.asarray(a) for a in (
        ds.sp_indices, ds.sp_values, ds.labels, ds.mask, ds.sq_norms))
    width = rectangle_width(39)
    assert width == 40 and cols.shape == vals.shape == (4, ds.n_shard, width)
    assert ds.layout == "sparse" and (ds.n, ds.num_features) == (6000, 4096)
    assert mask.sum() == 6000 and list(ds.counts) == [1500] * 4
    real = mask > 0
    assert ((vals != 0).sum(-1)[real] == 39).all()      # every row 39
    assert (vals[..., 39:] == 0).all() and (cols[..., 39:] == 0).all()
    assert (vals[~real] == 0).all() and (y[~real] == 0).all()
    # the counters: columns 0-12 in every row
    assert (cols[real][:, :13] == np.arange(13)).all()
    # one nonzero in each categorical field's range, value 1 before the row
    # is scaled: the 26 categorical values of a row are equal
    starts, sizes = gen.field_ranges(4096, ARGS)
    assert starts[0] == 13 and starts[-1] + sizes[-1] == 4096
    assert (sizes[:-1] <= sizes[1:]).all() and sizes[0] == 4
    cat = cols[real][:, 13:39]
    assert ((cat >= starts) & (cat < starts + sizes)).all()
    assert (np.diff(cols[real][:, :39], axis=1) > 0).all()  # ascending
    cv = vals[real][:, 13:39]
    assert (cv == cv[:, :1]).all()
    np.testing.assert_allclose(sq[real], 1.0, atol=1e-6)    # unit rows
    np.testing.assert_allclose((vals.astype(np.float64) ** 2).sum(-1)[real],
                               1.0, atol=1e-6)
    # Zipf inside a range: the low half of the widest range holds most
    last = cat[:, -1] - starts[-1]
    assert (last < sizes[-1] // 2).mean() > 0.75
    assert set(np.unique(y[real])) == {-1.0, 1.0}
    assert abs((y[real] > 0).mean() - 0.26) < 0.01          # 26% clicks
    assert rows_of_one_length(ds)


def test_generator_same_seed_same_shards_and_blocks_tile(gen, monkeypatch):
    a, b, c = (gen.make(SMALL, s) for s in (7, 7, 8))
    for name in ("sp_indices", "sp_values", "labels", "mask", "sq_norms"):
        np.testing.assert_array_equal(getattr(a, name), getattr(b, name))
    assert (np.asarray(a.sp_indices) != np.asarray(c.sp_indices)).any()
    assert (np.asarray(a.labels) != np.asarray(c.labels)).any()
    # row blocks smaller than the shard, the last pulled back to end on the
    # last row: every real row is made
    monkeypatch.setattr(gen, "ROW_BLOCK", 400)
    ds = gen.make(SMALL, 3)
    real = np.asarray(ds.mask) > 0
    assert ((np.asarray(ds.sp_values) != 0).sum(-1)[real] == 39).all()
    assert abs((np.asarray(ds.labels)[real] > 0).mean() - 0.26) < 0.01


# --- the cell's job on the direct plan --------------------------------------

def test_job_on_the_direct_plan_follows_fori_and_the_reference(gen,
                                                               monkeypatch):
    """``run_cocoa`` with the cell's flags under ``--loss=logistic``: the
    HBM-state kernel (interpreted) on its ``direct`` plan certifies the
    gap in the rounds the ``fori`` path takes, with its (w, alpha); and the
    plain sparse reference recomputes the certificate from the returned
    pair."""
    small = {**SMALL, "n": 960, "d": 1024}
    ds = gen.make(small, 4500000041)
    h = int(0.1 * 960 / 4)
    w0, a0, t0 = _job(ds, h=h)
    assert t0.meta["solver_path"]["kernel"] == "fori"
    assert t0.meta["solver_path"]["local_ids"] is None
    _on_the_hbm_kernel(monkeypatch)
    w1, a1, t1 = _job(ds, h=h, pallas=True)
    path = t1.meta["solver_path"]
    assert (path["kernel"], path["state"], path["interpret"],
            path["step_solve"]) == ("pallas", "hbm", True, "vector")
    assert (path["local_ids"], path["segments"], path["table_width"]) == (
        "direct", 1, 40)
    # rows of one length: both slot loops written out, 40 slots for 39
    assert (path["slot_walk"], path["slots_walked"]) == ("unrolled", 40.0)
    assert path["longest_row"] == 39 and ds.sp_indices.shape[-1] == 40
    assert ds.row_order is None
    assert t1.stopped == t0.stopped == "target"
    assert [r.round for r in t1.records] == [r.round for r in t0.records]
    np.testing.assert_allclose([r.gap for r in t1.records],
                               [r.gap for r in t0.records], atol=1e-5)
    np.testing.assert_allclose(w1, w0, atol=1e-5)
    np.testing.assert_allclose(a1, a0, atol=1e-5)
    ref = reference_sparse.recompute(ds, w1, a1, 1e-2, "logistic")
    last = t1.records[-1]
    assert last.primal == pytest.approx(ref["primal"], rel=2e-6)
    assert last.gap == pytest.approx(ref["gap"], abs=5e-6)
    assert ref["gap"] <= 2e-2 and ref["w_err"] <= 1e-5 * ref["w_scale"]
    real = np.asarray(ds.mask) > 0
    assert 0.0 < np.asarray(a1)[real].min()     # strictly inside the box
    assert np.asarray(a1)[real].max() < 1.0


# --- rows of one length are not ordered -------------------------------------

def test_one_length_is_not_ordered_and_two_lengths_are(monkeypatch):
    """``passes_want_order`` answers no where the loader saw every real row
    with the same count of nonzeros: the stable sort is the identity.
    Made to run all the same, it leaves (w, alpha) where they were, to the
    bit.  A dataset nobody noted anything on is ordered as ever."""
    monkeypatch.setattr(rows, "GATHER_BLOCK_SLOTS", 40 * 64)

    def fresh(lengths):
        return shard_dataset(_fixed_rows(1100, 300, lengths, 5), k=4,
                             layout="sparse", dtype=jnp.float32)

    one, two = fresh([39]), fresh([39, 38])
    assert rows.row_block(one.n_shard, 40) < one.n_shard
    assert rows_of_one_length(one) and not rows_of_one_length(two)
    assert one.row_order is None and two.row_order is not None
    assert (one._longest_row, two._longest_row) == (39, 39)
    w0, a0, t0 = _job(one, rounds=10, target=None)
    assert one.row_order is None                # nor on a job's entry
    assert t0.meta["solver_path"]["pass_slot_share"] == 1.0
    ordered = order_rows_by_length(fresh([39]))
    np.testing.assert_array_equal(
        np.asarray(ordered.row_order),
        np.broadcast_to(np.arange(one.n_shard), (4, one.n_shard)))
    w1, a1, _ = _job(ordered, rounds=10, target=None)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w0))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a0))
    # the same rows with the note taken off: ordered, as before PR 45
    unsaid = fresh([39])
    del unsaid._one_length
    assert not rows_of_one_length(unsaid)
    assert sharding.order_rows_for_passes(unsaid).row_order is not None
    # a loader that knows the longest row alone says nothing of the rest
    noted = sharding.note_row_lengths(fresh([39]), longest=39)
    assert noted._longest_row == 39 and not rows_of_one_length(noted)


# --- the rectangle's width ---------------------------------------------------

def test_rectangle_width_is_whole_slot_groups():
    assert [rectangle_width(w) for w in (0, 1, 8, 9, 13, 39, 40, 64, 65)] \
        == [8, 8, 8, 16, 16, 40, 40, 64, 72]
    assert sharding.RECTANGLE_GROUP == rows.SLOT_GROUP
    data = _fixed_rows(200, 300, [13], 1)
    assert shard_dataset(data, k=2, layout="sparse").sp_indices.shape[-1] \
        == 16
    # an explicit width is kept as given
    assert shard_dataset(data, k=2, layout="sparse",
                         max_nnz=13).sp_indices.shape[-1] == 13


@pytest.mark.parametrize("nnz", [39, 13])
def test_wider_rectangle_gives_the_narrow_ones_w_and_alpha(nnz, monkeypatch):
    """K = 8 shards of rows of exactly ``nnz`` nonzeros, stored as the
    loader stores them now (40 / 16 slots) and at their own width (39 / 13:
    what it built until PR 45, asked for by ``max_nnz``): a logistic job on
    the HBM-state kernel returns the same (w, alpha) to float32 rounding
    (seen: equal to the bit at 39, 7e-9 apart at 13), and ``longest_row``
    says ``nnz`` either way."""
    _on_the_hbm_kernel(monkeypatch)
    data = _fixed_rows(1536, 512, [nnz], 11)
    out = {}
    for name, kw in (("now", {}), ("until_pr45", {"max_nnz": nnz})):
        ds = shard_dataset(data, k=8, layout="sparse", dtype=jnp.float32,
                           **kw)
        w, a, t = _job(ds, rounds=10, target=None, h=19, pallas=True)
        path = t.meta["solver_path"]
        assert (path["state"], path["local_ids"], path["longest_row"]) == (
            "hbm", "direct", nnz)
        out[name] = (ds.sp_indices.shape[-1], np.asarray(w), np.asarray(a),
                     [r.gap for r in t.records])
    assert (out["now"][0], out["until_pr45"][0]) == (rectangle_width(nnz),
                                                     nnz)
    np.testing.assert_allclose(out["now"][1], out["until_pr45"][1],
                               atol=1e-6, rtol=0)
    np.testing.assert_allclose(out["now"][2], out["until_pr45"][2],
                               atol=1e-6, rtol=0)
    np.testing.assert_allclose(out["now"][3], out["until_pr45"][3],
                               rtol=1e-5)
