"""Rows of thousands of nonzeros kept as a stream (data/sharding.py
``stream_suits``) and the kernels that read it (ops/pallas_longrows.py), in
interpret mode on the CPU at a small size — n = 512, d = 65,536, mean 300
nonzeros a row, the longest 3,000, K = 4: the storage round trip, margins
and axpy against a dense matrix, the round against ``tests/oracle.py`` step
for step and against the portable ``fori`` path (the resolver and the whole
driver: tests/test_longrows_driver.py).

Tolerances.  float32 (the kernels' only dtype) under a suite that runs with
x64 on.  A margin is a float32 sum of ~300 products in another order than
the dense float64 one: 5e-6 at margins of order 1.  A round's chain of up to
60 dependent float32 steps against the float64 oracle: 2e-5, as the
rectangle's kernel is held to (tests/test_sparse_hbm.py).  The chain of
``plus`` / ``cocoa`` holds v = w + sigma' dw_k, so a column's update rounds
at |v| and not at |dw|: the recovered dw within 4 eps |w|_inf sqrt(steps on
the column) of the float64 replay, exactly 0 on a column no step touched.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cocoa_tpu.data.libsvm import LibsvmData
from cocoa_tpu.data.sharding import (STREAM_ALIGN, STREAM_PIECE,
                                     shard_dataset, stream_row_slots,
                                     stream_suits)
from cocoa_tpu.ops import pallas_longrows as plr
from cocoa_tpu.ops import rows as rows_ops
from cocoa_tpu.ops.local_sdca import local_sdca_fast

import oracle

F32 = np.float32
LAM = 1e-3
N, D, MEAN, LONGEST, K = 512, 65536, 300, 3000, 4


def _data(n=N, d=D, mean=MEAN, longest=LONGEST, seed=0, extra_row=0):
    """Seeded rows: lengths log-normal (sigma 0.6) clipped to [1, longest]
    with one row of exactly ``longest``; columns Zipf-like, ascending, none
    twice in a row; ``extra_row``: one more row of that many nonzeros."""
    r = np.random.RandomState(seed)
    lens = np.clip(np.round(np.exp(np.log(mean) - 0.18
                                   + 0.6 * r.randn(n))), 1, longest)
    lens = lens.astype(np.int64)
    lens[r.randint(n)] = longest
    if extra_row:
        lens = np.concatenate([lens, [extra_row]])
    indptr = np.concatenate([[0], np.cumsum(lens)])
    indices = np.zeros(indptr[-1], np.int32)
    for i, length in enumerate(lens):
        c = np.unique(np.minimum((d ** r.rand(3 * length)).astype(np.int64),
                                 d - 1))
        if len(c) < length:
            c = np.unique(np.concatenate(
                [c, r.choice(d, 2 * length, replace=False)]))
        indices[indptr[i]:indptr[i + 1]] = np.sort(
            r.permutation(c)[:length])
    values = r.randn(indptr[-1]) / np.sqrt(np.repeat(lens, lens))
    labels = np.where(r.randn(len(lens)) >= 0, 1.0, -1.0)
    return LibsvmData(labels=labels, indptr=indptr, indices=indices,
                      values=values, num_features=d)


@pytest.fixture(scope="module")
def data():
    return _data()


@pytest.fixture(scope="module")
def ds(data):
    return shard_dataset(data, k=K, layout="sparse")


def _dense_shards(data, ds):
    """(K, n_shard, d) float64 rows as the shards hold them."""
    x = data.to_dense()
    out = np.zeros((ds.k, ds.n_shard, data.num_features))
    lo = 0
    for a, m in enumerate(ds.counts):
        out[a, :m] = x[lo:lo + m]
        lo += m
    return out


# --- the storage ----------------------------------------------------------------


def test_the_loader_keeps_long_rows_as_a_stream(data, ds):
    """Every nonzero once, rows in the file's order, slots within 1.10 x
    the nonzeros."""
    assert ds.layout == "sparse" and ds.sp_row_ptr is not None
    assert ds.sp_indices.shape == ds.sp_values.shape
    assert ds.sp_indices.shape[0] == K and ds.sp_indices.shape[2] == STREAM_PIECE
    assert ds.sp_indices.shape[1] % 8 == 0
    nnz = int(data.indptr[-1])
    lens = np.diff(data.indptr)
    assert stream_row_slots(lens).sum() <= 1.02 * nnz
    # the arrays: the fullest shard's slots (at this size a shard is a
    # sixth fuller than the mean; at webspam's, 0.3%) and the spare pieces
    fullest = max(stream_row_slots(lens[a * 128:(a + 1) * 128]).sum()
                  for a in range(K))
    assert ds.sp_indices.size <= K * (fullest + 16 * STREAM_PIECE)
    cols = np.asarray(ds.sp_indices).reshape(K, -1)
    vals = np.asarray(ds.sp_values).reshape(K, -1)
    ptr, length = np.asarray(ds.sp_row_ptr), np.asarray(ds.sp_row_len)
    assert int((vals != 0).sum()) == int((data.values != 0).sum())
    row = 0
    for a, m in enumerate(ds.counts):
        assert (np.diff(ptr[a, :m]) > 0).all()          # file order
        for i in range(m):
            at = ptr[a, i] * STREAM_ALIGN
            want_c, want_v = data.row(row)
            assert length[a, i] == len(want_c)
            np.testing.assert_array_equal(cols[a, at:at + len(want_c)],
                                          want_c)
            np.testing.assert_array_equal(vals[a, at:at + len(want_c)],
                                          want_v.astype(F32))
            row += 1
        assert (length[a, m:] == 0).all()
    sq = np.asarray(ds.sq_norms)
    np.testing.assert_allclose(
        sq[0, :4], [(data.row(i)[1] ** 2).sum() for i in range(4)],
        rtol=1e-6)
    assert np.asarray(ds.sp_row_iota).shape == (K, LONGEST)


def test_stored_slots_do_not_follow_the_longest_row():
    """The same nonzeros with one 32,768-long row added: the slot count
    grows by that row's own slots (and whole pieces), not by n x longest."""
    base = _data(n=256, seed=1)
    more = _data(n=256, seed=1, extra_row=32768, d=D)
    a = shard_dataset(base, k=1, layout="sparse")
    b = shard_dataset(more, k=1, layout="sparse")
    assert b.sp_row_ptr is not None
    grown = b.sp_indices.size - a.sp_indices.size
    assert 32768 <= grown <= 32768 + 8 * STREAM_PIECE
    assert int(np.asarray(b.sp_row_len).max()) == 32768


@pytest.mark.parametrize("name,lens,want", [
    ("webspam", np.r_[np.full(999, 3727), 32768], True),
    ("small_test_size", np.r_[np.full(511, 290), 3000], True),
    ("kddb", np.r_[np.full(999, 29), 64], False),
    ("rcv1", np.r_[np.full(999, 73), 548], False),
    ("long_but_even", np.full(1000, 4000), False),
    # url: a hundred-odd nonzeros a row, the longest 4.4 x that (PR 41)
    ("url", np.r_[np.full(999, 116), 512], True),
    ("just_under_the_gate", np.r_[np.full(999, 95), 512], False),
    ("at_the_gate", np.r_[np.full(999, 96), 512], True),
    ("url_but_even", np.r_[np.full(999, 116), 200], False),
])
def test_the_rectangle_stays_for_sets_it_suits(name, lens, want):
    assert stream_suits(lens) == want
    assert not stream_suits(lens, itemsize=2)
    assert stream_row_slots([1, 8, 9]).tolist() == [8, 8, 16]


def test_short_rows_keep_the_rectangle_and_its_bytes():
    small = _data(n=64, mean=20, longest=60, seed=2)
    got = shard_dataset(small, k=2, layout="sparse")
    assert got.sp_row_ptr is None
    # (60 nonzeros: whole 8-slot groups, data/sharding.rectangle_width)
    assert got.sp_indices.shape == (2, got.n_shard, 64)
    assert "sp_row_ptr" not in got.shard_arrays()


# --- the passes over all rows ---------------------------------------------------


def test_margins_match_a_dense_matrix(data, ds):
    x = _dense_shards(data, ds)
    w = (np.random.RandomState(5).randn(D) * 0.3).astype(F32)
    got = plr.shard_margins(jnp.asarray(w), ds.shard_arrays(), True)
    np.testing.assert_allclose(got, x @ w.astype(np.float64), atol=5e-6,
                               rtol=0)
    # the per-shard form the eval's fan-out vmaps is the same kernel
    one = jax.vmap(rows_ops.shard_margins, in_axes=(None, 0))(
        jnp.asarray(w), ds.shard_arrays())
    np.testing.assert_array_equal(np.asarray(one), np.asarray(got))
    alone = rows_ops.shard_margins(
        jnp.asarray(w), jax.tree.map(lambda a: a[1], ds.shard_arrays()))
    np.testing.assert_array_equal(np.asarray(alone), np.asarray(got[1]))


def test_axpy_matches_a_dense_matrix(data, ds):
    x = _dense_shards(data, ds)
    r = np.random.RandomState(6)
    coefs = (r.randn(K, ds.n_shard) * np.asarray(ds.mask)).astype(F32)
    vec = r.randn(D).astype(F32)
    got = rows_ops.shards_axpy(jnp.asarray(coefs), ds.shard_arrays(),
                               jnp.asarray(vec))
    want = vec + np.einsum("kn,knd->d", coefs.astype(np.float64), x)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=0)


def test_get_row_windows_a_row_of_the_stream(data, ds):
    shard = jax.tree.map(lambda a: a[K - 1], ds.shard_arrays())
    first = int(np.sum(ds.counts[:K - 1]))
    for i in (0, 7, int(ds.counts[K - 1]) - 1):      # the last: rotated back
        row = rows_ops.get_row(shard, i)
        c, v = data.row(first + i)
        assert row.idx.shape == (LONGEST,)
        np.testing.assert_array_equal(np.asarray(row.idx)[:len(c)], c)
        np.testing.assert_array_equal(np.asarray(row.val)[:len(c)],
                                      v.astype(F32))
        assert not np.asarray(row.val)[len(c):].any()
        assert not np.asarray(row.idx)[len(c):].any()


# --- the round ------------------------------------------------------------------


def _fori(w, alpha, shards, idxs, n, mode, sigma, loss):
    """The portable path: the stream's margins + local_sdca_fast on rows
    windowed out of the stream (ops/rows.get_row)."""
    dws, alphas = [], []
    for a in range(alpha.shape[0]):
        shard = jax.tree.map(lambda x: x[a], shards)
        da, dw = local_sdca_fast(
            rows_ops.shard_margins(w, shard), alpha[a], shard, idxs[a], LAM,
            n, jnp.zeros_like(w), mode=mode, sigma=sigma, loss=loss)
        dws.append(dw)
        alphas.append(alpha[a] + da)
    return sum(dws), jnp.stack(alphas)


def _round(ds, w, alpha, idxs, n, mode="plus", sigma=float(K),
           loss="hinge"):
    sh = ds.shard_arrays()
    return plr.pallas_longrows_round(
        jnp.asarray(w), jnp.asarray(alpha), sh["sp_indices"],
        sh["sp_values"], sh["sp_row_ptr"], sh["sp_row_len"], sh["labels"],
        sh["sq_norms"], jnp.asarray(idxs), LAM, n, mode=mode, sigma=sigma,
        interpret=True, loss=loss)


def _oracle_round(data, ds, w, alpha, idxs, plus=True, sigma=float(K)):
    """The hinge round of ``tests/oracle.py`` in float64, shard by shard:
    (dw, alpha after)."""
    x = _dense_shards(data, ds)
    y = np.asarray(ds.labels, np.float64)
    dw, after = np.zeros(D), []
    for a in range(K):
        da, dwk = oracle.local_sdca(
            x[a], y[a], w.astype(np.float64), alpha[a].astype(np.float64),
            idxs[a], LAM, data.n, plus, sigma)
        dw += dwk
        after.append(alpha[a] + da)
    return dw, np.stack(after)


# (mode, loss, draws with replacement)
CASES = [("plus", "hinge", False), ("plus", "logistic", False),
         ("plus", "hinge", True), ("cocoa", "hinge", False),
         ("cocoa", "logistic", True), ("frozen", "hinge", False)]


@pytest.mark.parametrize(
    "mode,loss,repeats", CASES,
    ids=[f"{m}-{lo}-{'repeats' if r else 'distinct'}" for m, lo, r in CASES])
def test_round_matches_the_oracle_step_for_step(data, ds, mode, loss,
                                                repeats):
    h = 60
    # frozen's margin never reads dw_k, so no v = w + 0 dw_k gives dw_k
    # back: it alone keeps x . w as a pass of its own before the chain
    assert plr.margin_form(mode) == ("split" if mode == "frozen"
                                     else "combined")
    r = np.random.RandomState(7)
    m = int(ds.counts.min())
    if repeats:     # with-replacement draws: a row stepped on twice reads
        idxs = r.randint(0, 24, size=(K, h))      # the earlier step's alpha
    else:
        idxs = np.stack([r.permutation(m)[:h] for _ in range(K)])
    idxs = idxs.astype(np.int32)
    # the longest row is stepped on: several chunks, a start inside a piece
    shard_of_longest, longest_at = np.unravel_index(
        np.argmax(np.asarray(ds.sp_row_len)), (K, ds.n_shard))
    idxs[shard_of_longest, 3] = longest_at
    if not repeats:
        dup = np.flatnonzero(idxs[shard_of_longest] == longest_at)
        idxs[shard_of_longest, dup[dup != 3]] = (longest_at + 1) % m
    w = (r.randn(D) * 0.1).astype(F32)
    alpha = r.rand(K, ds.n_shard).astype(F32) * np.asarray(ds.mask)
    n, sigma = data.n, (float(K) if mode == "plus" else 1.0)
    sh = ds.shard_arrays()
    dw, a_new = _round(ds, w, alpha, idxs, n, mode, sigma, loss)
    assert dw.dtype == a_new.dtype == jnp.float32
    assert float(jnp.abs(a_new - alpha).max()) > 0.1    # the round moved
    passes = str(jax.make_jaxpr(
        lambda w, a: _round(ds, w, a, idxs, n, mode, sigma, loss))(w, alpha))
    assert "pallas_longrows_chain" in passes
    assert ("pallas_longrows_dots" in passes) == (mode == "frozen")
    assert float(a_new.min()) >= 0.0 and float(a_new.max()) <= 1.0
    dw_f, a_f = _fori(jnp.asarray(w), jnp.asarray(alpha), sh,
                      jnp.asarray(idxs), n, mode, sigma, loss)
    np.testing.assert_allclose(dw, dw_f, atol=3e-6, rtol=0)
    np.testing.assert_allclose(a_new, a_f, atol=3e-6, rtol=0)
    if loss == "hinge" and mode != "frozen":
        dw_o, a_o = _oracle_round(data, ds, w, alpha, idxs, mode == "plus",
                                  sigma)
        np.testing.assert_allclose(a_new, a_o, atol=2e-5, rtol=0)
        np.testing.assert_allclose(dw, dw_o, atol=2e-5, rtol=0)


# --- the chain against v = w + sigma' dw_k --------------------------------------


@pytest.fixture(scope="module")
def round_at_a_large_w(data, ds):
    """One CoCoA+ round from a w of |w|_inf = 1.5 (webspam's reads 0.44 to
    1.56), 60 distinct draws a shard."""
    r = np.random.RandomState(11)
    w = r.randn(D)
    w = (w * 1.5 / np.abs(w).max()).astype(F32)
    alpha = r.rand(K, ds.n_shard).astype(F32) * np.asarray(ds.mask)
    m = int(ds.counts.min())
    idxs = np.stack([r.permutation(m)[:60] for _ in range(K)]).astype(
        np.int32)
    dw, _ = _round(ds, w, alpha, idxs, data.n)
    x = _dense_shards(data, ds)
    steps = sum((x[a][idxs[a]] != 0).sum(0) for a in range(K))
    return w, np.asarray(dw), steps, _oracle_round(data, ds, w, alpha,
                                                   idxs)[0]


def test_dw_is_exactly_zero_on_columns_no_sampled_row_holds(
        round_at_a_large_w):
    """v - w on a column no step stored to is w's own bits less w."""
    w, dw, steps, _ = round_at_a_large_w
    untouched = steps == 0
    assert untouched.sum() > D // 2 and (w[untouched] != 0).all()
    assert not dw[untouched].any()
    assert np.abs(dw[~untouched]).max() > 1e-3


def test_recovered_dw_is_within_the_rounding_of_v_of_a_float64_replay(
        round_at_a_large_w):
    """Each step on a column rounds v there once, at |v| <= ~|w|_inf, and
    the errors add as a walk: the bound ISSUE 31 states for the combined
    form (the form that held dw alone read ~4e-10 here)."""
    w, dw, steps, dw_64 = round_at_a_large_w
    bound = 4 * np.finfo(F32).eps * np.abs(w).max() * np.sqrt(steps)
    assert (np.abs(dw - dw_64) <= bound).all()
    assert bound.max() < 1e-5


def test_a_row_sampled_twice_reads_its_alpha_from_the_earlier_step(data, ds):
    """With-replacement draws: the second step on a row starts from the
    first one's alpha (held in the kernel's output block), not from the
    round's."""
    r = np.random.RandomState(13)
    m = int(ds.counts.min())
    idxs = np.stack([r.permutation(m)[:12] for _ in range(K)]).astype(
        np.int32)
    idxs[:, 9] = idxs[:, 2]
    w = (r.randn(D) * 0.1).astype(F32)
    alpha = np.full((K, ds.n_shard), 0.5, F32) * np.asarray(ds.mask)
    _, a_new = _round(ds, w, alpha, idxs, data.n)
    _, twice = _oracle_round(data, ds, w, alpha, idxs)
    _, once = _oracle_round(data, ds, w, alpha, idxs[:, :9])
    rows = (np.arange(K), idxs[:, 2])
    np.testing.assert_allclose(np.asarray(a_new)[rows], twice[rows],
                               atol=2e-5, rtol=0)
    # and the second step moved it: a stale read would not land here
    assert np.abs(twice[rows] - once[rows]).max() > 1e-3
