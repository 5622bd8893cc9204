"""Rows of a hundred-odd nonzeros kept as a stream (url's shape: PR 41) and
the DMA ring that runs across them (ops/pallas_longrows.py), in interpret
mode on the CPU at a small size — n = 96, d = 8,192, mean ~116 nonzeros a
row, K = 2: what the ring moves, counted on the host; the three kernels
against ``tests/oracle.py`` on rows that start inside a piece, end on a
chunk's last slot, hold 0 and 1 nonzeros, take two and three chunks between
rows of one, and are sampled twice in a round; the round bit-equal to the
kernel of before the ring crossed rows; a url-shaped LIBSVM file through
``load_libsvm`` -> ``shard_dataset`` -> ``run_cocoa`` against the oracle;
and what the CLI's run of all six solvers does with such a file.

Tolerances as tests/test_longrows.py: a margin 5e-6, a round's chain against
the float64 oracle 2e-5.  Against the kernel of before nothing may differ at
all: the ring decides when a chunk is fetched, not what is computed or in
which order.
"""

import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cocoa_tpu.data.libsvm import LibsvmData
from cocoa_tpu.data.sharding import (STREAM_ALIGN, STREAM_PIECE,
                                     rectangle_width, shard_dataset,
                                     stream_suits)
from cocoa_tpu.ops import pallas_longrows as plr
from cocoa_tpu.ops import rows as rows_ops

import oracle

F32 = np.float32
LAM = 1e-3
N, D, K = 96, 8192, 2
# rows the ring has to get right, at the head of shard 0 (row 0 starts on a
# piece, so the rows after it start where these lengths leave them): one
# that ends on the last slot of its one chunk (1,024 = 8 pieces), an empty
# one, one of a single nonzero, one that starts inside a piece (slot 8 of
# its piece), one that straddles a piece, one of two chunks by a single
# slot (it starts at slot 8 of its piece: 8 + 1,017 = 1,025), one of
# three, and a short one behind them
HEAD = [1024, 0, 1, 248, 130, 1017, 2100, 5]


def _data(seed=0, n=N, d=D, head=True):
    """Seeded rows of url's length law (log-normal, sigma 0.5, mean 116,
    clipped to [1, 512]); ``head``: the rows of HEAD first."""
    r = np.random.RandomState(seed)
    lens = np.clip(np.round(np.exp(np.log(116) - 0.125
                                   + 0.5 * r.randn(n))), 1, 512)
    lens = lens.astype(np.int64)
    lens[-1] = 512                  # one row at the clip, as the law has
    if head:
        lens[:len(HEAD)] = HEAD
    indptr = np.concatenate([[0], np.cumsum(lens)])
    indices = np.concatenate([np.sort(r.choice(d, m, replace=False))
                              for m in lens]).astype(np.int32)
    values = r.randn(indptr[-1]) / np.sqrt(np.maximum(
        np.repeat(lens, lens), 1))
    labels = np.where(r.randn(n) >= 0, 1.0, -1.0)
    return LibsvmData(labels=labels, indptr=indptr, indices=indices,
                      values=values, num_features=d)


@pytest.fixture(scope="module")
def data():
    return _data()


@pytest.fixture(scope="module")
def ds(data):
    return shard_dataset(data, k=K, layout="sparse")


def _dense(data, ds):
    x = data.to_dense()
    out = np.zeros((ds.k, ds.n_shard, data.num_features))
    lo = 0
    for a, m in enumerate(ds.counts):
        out[a, :m] = x[lo:lo + m]
        lo += m
    return out


# --- what the ring moves ------------------------------------------------------


def test_chunk_fill_is_a_hand_count():
    """Three rows at slot groups 0, 15 and 30: the first fills a piece to
    its last slot, the second starts 8 slots before a piece's end and runs
    20 into the next, the third is empty: one chunk each.  Then a row that
    starts 8 slots before a piece's end and needs a second chunk for its
    last slot."""
    assert plr.CHUNK == 8 * STREAM_PIECE == 1024
    ptr, lens = np.array([[0, 15, 30]]), np.array([[128, 28, 0]])
    assert plr.chunk_fill(ptr, lens) == 156 / (3 * 1024)
    assert plr.chunk_fill([[15]], [[905]]) == 905 / 2048
    assert plr.chunk_fill([[15]], [[904]]) == 904 / 1024
    assert plr.chunk_fill(np.zeros((1, 0)), np.zeros((1, 0))) == 1.0


def test_the_loader_keeps_url_shaped_rows_as_a_stream(data, ds):
    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    lens = np.diff(data.indptr)
    assert 96 <= lens.mean() < 256 and stream_suits(lens)
    assert ds.sp_row_ptr is not None
    ptr, length = np.asarray(ds.sp_row_ptr), np.asarray(ds.sp_row_len)
    assert length[0, :len(HEAD)].tolist() == HEAD
    per = STREAM_PIECE // STREAM_ALIGN
    # the head rows sit where the comment at HEAD says
    assert (ptr[0, :len(HEAD)] % per * STREAM_ALIGN).tolist() == [
        0, 0, 0, 8, 0, 8, 8, 64]
    path = resolve_solver_path(ds, 8, math="fast", pallas=True)
    assert (path.storage, path.chunk_pieces) == ("stream", plr.CHUNK_PIECES)
    assert path.chunk_fill == plr.chunk_fill(ptr, length)
    assert 0.08 < path.chunk_fill < 0.2
    # no leaf of the shards carries the ring: it is the kernels' constant
    assert set(ds.shard_arrays()) == {
        "labels", "sq_norms", "mask", "sp_indices", "sp_values",
        "sp_row_ptr", "sp_row_len", "sp_row_iota"}


def test_a_caller_that_reads_rectangles_gets_one(data):
    """``rectangle=True`` keeps the rows padded to the longest whatever
    their lengths, and the primal solvers, which read nothing else, say
    how to get it when handed a stream."""
    from cocoa_tpu import solvers
    from cocoa_tpu.config import DebugParams, Params

    rect = shard_dataset(data, k=K, layout="sparse", rectangle=True)
    assert rect.sp_row_ptr is None
    assert rect.sp_indices.shape == (K, rect.n_shard,
                                     rectangle_width(max(HEAD)))
    stream = shard_dataset(data, k=K, layout="sparse")
    params = Params(n=data.n, num_rounds=1, local_iters=4, lam=LAM)
    with pytest.raises(ValueError, match="rectangle=True"):
        solvers.run_sgd(stream, params, DebugParams(debug_iter=1, seed=0),
                        local=False, quiet=True)


# --- the three kernels --------------------------------------------------------


@pytest.fixture(scope="module")
def passes(data, ds):
    r = np.random.RandomState(5)
    w = (r.randn(D) * 0.3).astype(F32)
    coefs = (r.randn(K, ds.n_shard) * np.asarray(ds.mask)).astype(F32)
    sh = ds.shard_arrays()
    margins = plr.shard_margins(jnp.asarray(w), sh, True)
    after = plr.shards_axpy(jnp.asarray(coefs), sh, jnp.asarray(w), True)
    return w, coefs, np.asarray(margins), np.asarray(after)


def test_the_passes_match_the_dense_rows(data, ds, passes):
    w, coefs, margins, after = passes
    x = _dense(data, ds)
    np.testing.assert_allclose(margins, x @ w.astype(np.float64), atol=5e-6,
                               rtol=0)
    np.testing.assert_allclose(
        after, w + np.einsum("kn,knd->d", coefs.astype(np.float64), x),
        atol=2e-5, rtol=0)
    # an empty row's margin is 0 and its coefficient moves nothing
    assert margins[0, 1] == 0.0


def test_the_eval_and_the_jump_are_the_same_passes(ds, passes):
    """ops/rows.py's entry points (the certificate's margins under the
    fan-out's vmap, the --accel jump's axpy) run the same two kernels."""
    w, coefs, margins, after = passes
    sh = ds.shard_arrays()
    vm = jax.vmap(rows_ops.shard_margins, in_axes=(None, 0))
    np.testing.assert_array_equal(np.asarray(vm(jnp.asarray(w), sh)),
                                  margins)
    np.testing.assert_array_equal(np.asarray(rows_ops.shards_axpy(
        jnp.asarray(coefs), sh, jnp.asarray(w))), after)


def _round(ds, w, alpha, idxs, n, mode="plus", loss="hinge"):
    sh = ds.shard_arrays()
    return plr.pallas_longrows_round(
        jnp.asarray(w), jnp.asarray(alpha), sh["sp_indices"],
        sh["sp_values"], sh["sp_row_ptr"], sh["sp_row_len"], sh["labels"],
        sh["sq_norms"], jnp.asarray(idxs), LAM, n, mode=mode,
        sigma=float(K) if mode == "plus" else 1.0, interpret=True,
        loss=loss)


def _idxs(ds, h=20, seed=7):
    """Distinct draws, but: shard 0 steps on every head row — from one
    chunk to two, two to three, three to one, the three-chunk row twice in
    a row and the 1,024-long one twice (a row sampled twice in a round
    reads the earlier step's alpha) — and ends on a row of three chunks."""
    r = np.random.RandomState(seed)
    m = int(ds.counts.min())
    idxs = np.stack([r.permutation(np.arange(len(HEAD), m))[:h]
                     for _ in range(K)]).astype(np.int32)
    idxs[0, :12] = [0, 1, 2, 3, 4, 5, 6, 7, 6, 6, 0, 1]
    idxs[0, -1] = 6
    return idxs


MODES = [("plus", "hinge"), ("plus", "logistic"), ("frozen", "hinge"),
         ("cocoa", "hinge"), ("frozen", "logistic"), ("plus", "smooth_hinge")]


@pytest.fixture(scope="module")
def rounds(data, ds):
    r = np.random.RandomState(9)
    w = (r.randn(D) * 0.1).astype(F32)
    alpha = r.rand(K, ds.n_shard).astype(F32) * np.asarray(ds.mask)
    idxs = _idxs(ds)
    out = {}
    for mode, loss in MODES:
        dw, a_new = _round(ds, w, alpha, idxs, data.n, mode, loss)
        out[mode, loss] = (np.asarray(dw), np.asarray(a_new))
    return w, alpha, idxs, out


def test_the_round_matches_the_oracle(data, ds, rounds):
    w, alpha, idxs, out = rounds
    x, y = _dense(data, ds), np.asarray(ds.labels, np.float64)
    dw, after = np.zeros(D), []
    for a in range(K):
        da, dwk = oracle.local_sdca(
            x[a], y[a], w.astype(np.float64), alpha[a].astype(np.float64),
            idxs[a], LAM, data.n, True, float(K))
        dw += dwk
        after.append(alpha[a] + da)
    got_dw, got_a = out["plus", "hinge"]
    np.testing.assert_allclose(got_a, np.stack(after), atol=2e-5, rtol=0)
    np.testing.assert_allclose(got_dw, dw, atol=2e-5, rtol=0)
    assert np.abs(got_a - alpha).max() > 0.1            # the round moved
    # the empty row's step moved its alpha alone (qii = 0: the step's own
    # rule), and no column
    assert got_a.min() >= 0.0 and got_a.max() <= 1.0


# sha256 of the round's (dw, alpha) bytes as the kernel of PR 41's parent
# commit gives them on these inputs (the ring inside a row: prime a row's
# first chunk, wait for it at once, twice a step), made by running that
# commit's ops/pallas_longrows.py on this file's fixtures in interpret mode;
# the last three and PASSES_BEFORE by PR 49's parent commit, whose step
# held its floats 0-d (the first three are the same on both)
BEFORE = {
    ("cocoa", "hinge"):
        "f2469da8961d9300cb9ffa9f8c18b3bb4841e2656e251e5397067920fa2dbbb7",
    ("frozen", "logistic"):
        "6d9985fbe69780ff41a3528201b9850278cdfe2080f6e38df6292f9c6e86d182",
    ("plus", "smooth_hinge"):
        "5c76fbdb2da4eabaec3a8cc7e3b92b216ab12cc8ccf5462c5a3a92dc518484e4",
    ("plus", "hinge"):
        "8548fd68c0b99e4089cd6337683db35dd4bd00537a790cab5dca5563132b0eb8",
    ("plus", "logistic"):
        "b088b2d4010535e7119741e04b57bcdb0782ba9c4b1700b36343ae98744d807a",
    ("frozen", "hinge"):
        "697905c0e9ced3c390cc9796bce5c290b70e6dc2e1da43c72637b77305fc45f8",
}


@pytest.mark.parametrize("mode,loss", MODES)
def test_the_round_is_bit_equal_to_the_kernel_of_before(rounds, mode, loss):
    """The same operations in the same order: a row of one chunk is
    fetched once and its update reads the chunk its dot left in the ring;
    a longer row's chunks come round twice; nothing computed differs."""
    dw, a_new = rounds[3][mode, loss]
    assert hashlib.sha256(dw.tobytes() + a_new.tobytes()).hexdigest() \
        == BEFORE[mode, loss]


PASSES_BEFORE = {
    "dots":
        "dd28113b45b0870b8c87f24b75bb7937dd6ba1dc87865ab31b65cf7526a06c2a",
    "axpy":
        "fe60f135cf776233541188007e6b9cb03b87b180b56a8015bb03eba197e26213",
}


@pytest.mark.parametrize("what", ["dots", "axpy"])
def test_the_passes_are_bit_equal_to_the_kernel_of_before(passes, what):
    """A row's total kept on the vector side is the sum it was (a reduce
    that keeps its axes adds the same lanes), and a coefficient splatted
    before its product with a nonzero's value is the same float32 product:
    every margin and every column of the jump, to the bit (PR 49)."""
    got = passes[2] if what == "dots" else passes[3]
    assert hashlib.sha256(got.tobytes()).hexdigest() == PASSES_BEFORE[what]


# --- a url-shaped file through the loader and the driver ---------------------


def test_a_url_shaped_file_runs_the_stream_end_to_end(tmp_path, capsys):
    """load_libsvm -> shard_dataset -> run_cocoa: the file's lengths send
    it to the stream, the resolver reports the ring's chunk and its fill
    on the console line and ``Trajectory.meta``, and the Pallas path's rounds
    agree with ``tests/oracle.py``'s CoCoA+."""
    from cocoa_tpu import solvers
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data import load_libsvm
    from cocoa_tpu.data.synth import write_libsvm
    from cocoa_tpu.solvers.base import IndexSampler

    made = _data(seed=3, n=64, d=2048, head=False)
    path = str(tmp_path / "url_shaped.dat")
    write_libsvm(made, path)
    data = load_libsvm(path, 2048)
    ds = shard_dataset(data, k=K, layout="auto")
    assert ds.layout == "sparse" and ds.sp_row_ptr is not None
    h, rounds = 8, 3
    params = Params(n=data.n, num_rounds=rounds, local_iters=h, lam=LAM)
    debug = DebugParams(debug_iter=rounds, seed=3)
    w, alpha, traj = solvers.run_cocoa(
        ds, params, debug, plus=True, quiet=False, math="fast",
        device_loop=True, rng="permuted", pallas=True)
    said = capsys.readouterr().out
    meta = traj.meta["solver_path"]
    assert (meta["kernel"], meta["storage"], meta["chunk_pieces"]) == (
        "pallas", "stream", 8)
    assert meta["step_solve"] == "vector"
    assert "each step solved on the vector unit" in said
    assert meta["chunk_fill"] == plr.chunk_fill(ds.sp_row_ptr,
                                                ds.sp_row_len)
    assert "fetched 8 pieces a chunk (chunk fill 0.1" in said
    sampler = IndexSampler("permuted", 3, h, ds.counts)
    x, y = _dense(data, ds), np.asarray(ds.labels, np.float64)
    w64, a64 = np.zeros(2048), np.zeros((K, ds.n_shard))
    for t in range(1, rounds + 1):
        idxs = np.asarray(sampler.round_indices(t))
        dw = np.zeros(2048)
        for a in range(K):
            da, dwk = oracle.local_sdca(x[a], y[a], w64, a64[a], idxs[a],
                                        LAM, data.n, True, float(K))
            a64[a] += da
            dw += dwk
        w64 += dw
    np.testing.assert_allclose(np.asarray(alpha), a64, atol=2e-5, rtol=0)
    np.testing.assert_allclose(np.asarray(w), w64, atol=2e-5, rtol=0)


def test_the_cli_states_the_chunk_in_run_start(tmp_path):
    """``python -m cocoa_tpu.cli`` on a url-shaped file with no flag that
    names a layout or a kernel: ``run_start``'s manifest carries
    ``storage: stream``, ``chunk_pieces`` and ``chunk_fill`` (on a CPU the
    solve itself resolves to ``fori``: the storage and the ring are the
    loader's, from the lengths alone)."""
    import json

    from cocoa_tpu import cli
    from cocoa_tpu.data.synth import write_libsvm
    from cocoa_tpu.telemetry import events as tele

    path, events = str(tmp_path / "u.dat"), str(tmp_path / "ev.jsonl")
    write_libsvm(_data(seed=4, n=64, d=2048, head=False), path)
    try:
        assert cli.main([f"--trainFile={path}", "--numFeatures=2048",
                         "--numSplits=2", "--lambda=.001", "--numRounds=2",
                         "--localIterFrac=0.1", "--justCoCoA=true",
                         "--mesh=1", "--quiet", f"--events={events}"]) == 0
    finally:
        tele.get_bus().reset()
    with open(events) as f:
        (start,) = [e for e in map(json.loads, f)
                    if e["event"] == "run_start"]
    said = start["manifest"]["solver_path"]
    assert (said["storage"], said["kernel"], said["chunk_pieces"]) == (
        "stream", "fori", 8)
    assert 0.08 < said["chunk_fill"] < 0.16


def test_the_cli_runs_all_six_solvers_on_a_url_shaped_file(tmp_path, capsys):
    """``--justCoCoA=false`` adds the primal baselines, which read
    rectangles alone: the CLI asks the loader for one, so the same file
    that trains as a stream by default still finishes every solver, as it
    did when the rule's mean gate stood at 256."""
    from cocoa_tpu import cli
    from cocoa_tpu.data.synth import write_libsvm

    path = str(tmp_path / "u.dat")
    write_libsvm(_data(seed=4, n=64, d=2048, head=False), path)
    assert cli.main([f"--trainFile={path}", "--numFeatures=2048",
                     "--numSplits=2", "--lambda=.001", "--numRounds=2",
                     "--localIterFrac=0.1", "--mesh=1",
                     "--justCoCoA=false"]) == 0
    said = capsys.readouterr().out
    assert "kept as a stream" not in said
    for name in ("CoCoA+", "CoCoA", "Mini-batch CD", "Mini-batch SGD",
                 "Local SGD", "Dist"):
        assert name in said
