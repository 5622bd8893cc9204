"""Sparse rows of thousands of nonzeros over Zipf columns with a planted
separator, made on the device: the stand-in for a LIBSVM file of byte
n-gram counts (webspam, the trigram version).

One jitted call from the seed, never the host and never a per-row loop; the
result is the program's **stream** ``ShardedDataset`` (what
``data.sharding.shard_dataset`` builds from a file whose rows are long and
uneven): each shard's nonzeros as one run of (column, value) slots, row
after row, a row starting on a ``STREAM_ALIGN``-slot boundary, the run cut
into ``STREAM_PIECE``-slot pieces.  It is made a window of ``WINDOW`` slots
at a time and written in place, so no temporary is a shard's size.  The
seed is an argument of the jitted call: every seed runs the one compiled
program, and the same ``(config, seed)`` gives the same shards.

A row: its length L is log-normal (``sigma_nnz``), rounded and clipped to
[1, ``max_nnz``], with the mean ``mean_nnz`` (``sparse_zipf.length_mu``).  Its columns
are L draws from Zipf(s = 1) over the d columns by the inverse CDF of the
continuous law, one draw from each of L equal strata of the unit interval
(slot j takes u = (j + xi) / L, xi uniform: the draws come out ascending,
as a LIBSVM row is written), and a column drawn twice is moved up to the
next free one — in closed form, so that a slot is made from (j, L, xi)
alone: column = j + floor(max(0, D^u - 1 - L u)), D = d - max_nnz.  Where
the law puts more than one draw on a column (the hot head: u below ~0.45
for the mean row) that is the dense run 0, 1, 2, ...; past it, D^u - 1.
A byte-trigram row has such a head: the trigrams every page holds.  Values
are 1 / sqrt(L): unit rows.  Labels are sign(x . w*) with ``flip`` label
noise, for ``sparse_zipf.planted_w``, a hash of the column: zero on the
``planted_hot_cut`` hottest columns and on all but one column in
``planted_density_inv`` of the rest (``sparse_zipf.py`` says why the head is
left out).  A shard holds ``stream_windows`` windows; should a seed's rows
outgrow them (six standard deviations of the shard's total away) the last
rows are cut short, never dropped.

**Pre-flight.**  Before it makes anything, ``make`` asks the program which
local solver a job on these shapes would run
(``solvers.cocoa.resolve_solver_path`` on a dataset of shapes only) and
raises if the answer is ``kernel="fori"``; a program whose dataset has no
stream storage fails there too, in seconds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.generators.sparse_zipf import length_mu, planted_w
from cocoa_tpu.data.sharding import (STREAM_ALIGN, STREAM_PIECE,
                                     ShardedDataset, pad_rows, split_sizes)

WINDOW = 1 << 24                # slots made per step of the loop


def stream_windows(config: dict) -> int:
    """Windows a shard's stream holds: the expected slots of its rows (each
    under STREAM_ALIGN slots of padding), 2% over, the spare the kernels'
    last chunk may read, in whole windows."""
    n_rows = int(split_sizes(config["n"], config["num_splits"]).max())
    slots = n_rows * (config["generator_args"]["mean_nnz"] + STREAM_ALIGN / 2)
    return -(-int(1.02 * slots + 8 * STREAM_PIECE) // WINDOW)


def row_lengths(key, rows: int, mu: float, sigma: float, width: int):
    """(rows,) int32 lengths."""
    return jnp.clip(jnp.round(jnp.exp(
        mu + sigma * jax.random.normal(key, (rows,), jnp.float32))),
        1, width).astype(jnp.int32)


def fit_rows(length, real, capacity: int):
    """Row starts (in slots) for ``length`` (rows,) with ``real`` marking
    the shard's own rows: ``(first, length)``, the lengths cut so that
    every row keeps a slot and all end within ``capacity`` slots."""
    length = jnp.where(real, length, 0)
    slots = -(-length // STREAM_ALIGN) * STREAM_ALIGN
    first = jnp.cumsum(slots) - slots
    # rows after row i still need STREAM_ALIGN slots each
    after = (jnp.sum(real) - jnp.cumsum(real)) * STREAM_ALIGN
    length = jnp.where(real, jnp.clip(capacity - after - first, 1, length), 0)
    slots = -(-length // STREAM_ALIGN) * STREAM_ALIGN
    return ((jnp.cumsum(slots) - slots).astype(jnp.int32),
            length.astype(jnp.int32))


def _window(start, first, length, key, d_eff: int, seed_bits,
            density_inv: int, hot_cut: int):
    """Slots [start, start + WINDOW) of one shard's stream: ``(columns,
    values, per-row score parts)``.  A slot learns its row's start and
    length from two int32 running sums of the differences scattered at the
    row starts; no per-slot gather."""
    rel = first - start
    inside = (rel >= 0) & (rel < WINDOW) & (length > 0)
    at = jnp.where(inside, rel, WINDOW)
    # the row that holds the window's first slot, if it began before it
    r0 = jnp.sum((first < start) & (length > 0)) - 1
    prev = lambda a: jnp.concatenate([a[:1] * 0, a[:-1]])  # noqa: E731
    # the previous LIVE row's value: rows past the shard's own have length 0
    # and sit at the end, so the previous row of a live row is live
    spread = lambda a: (jnp.where(r0 >= 0, a[jnp.maximum(r0, 0)], 0) + (  # noqa: E731
        jnp.cumsum(jnp.zeros((WINDOW,), jnp.int32).at[at].add(
            a - prev(a), mode="drop")))).astype(jnp.int32)
    row_first, row_len = spread(first), spread(length)
    pos = start + jnp.arange(WINDOW, dtype=jnp.int32)
    j = pos - row_first
    live = (j < row_len) & (row_len > 0)
    lf = jnp.maximum(row_len, 1).astype(jnp.float32)
    xi = jax.random.uniform(key, (WINDOW,), jnp.float32)
    u = (j.astype(jnp.float32) + xi) / lf
    tail = jnp.exp(u * math.log(d_eff)) - 1.0 - lf * u
    col = j + jnp.floor(jnp.clip(tail, 0.0, float(d_eff))).astype(jnp.int32)
    cols = jnp.where(live, col, 0).astype(jnp.int32)
    vals = jnp.where(live, jax.lax.rsqrt(lf), 0.0)
    # x . w* by row: the running sum of the slots' parts, read at the ends
    # of the rows' runs within this window (a row that straddles windows is
    # added up over them)
    run = jnp.cumsum(vals * planted_w(cols, seed_bits, density_inv, hot_cut))
    lo = jnp.clip(rel, 0, WINDOW)
    hi = jnp.clip(rel + length, 0, WINDOW)
    take = lambda i: jnp.where(i > 0, run[jnp.maximum(i - 1, 0)], 0.0)  # noqa: E731
    score = jnp.where((hi > lo) & (length > 0), take(hi) - take(lo), 0.0)
    return cols, vals, score


def shapes_only(config: dict) -> ShardedDataset:
    """The dataset ``make`` would return, as shapes on the first device:
    what the pre-flight hands the program's resolver."""
    n, d, k = config["n"], config["d"], config["num_splits"]
    dtype = jnp.dtype(config.get("dtype", "float32"))
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    pieces = stream_windows(config) * (WINDOW // STREAM_PIECE)
    here = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=here)

    rows, irows = sds((k, n_shard), dtype), sds((k, n_shard), jnp.int32)
    return ShardedDataset(
        layout="sparse", n=n, num_features=d, counts=sizes.astype(np.int64),
        labels=rows, mask=rows, sq_norms=rows,
        sp_indices=sds((k, pieces, STREAM_PIECE), jnp.int32),
        sp_values=sds((k, pieces, STREAM_PIECE), dtype),
        sp_row_ptr=irows, sp_row_len=irows,
        sp_row_iota=sds((k, config["generator_args"]["max_nnz"]), jnp.int32))


def preflight(config: dict, resolve=None) -> dict:
    """Which local solver the program would run on these shapes; raises if
    it is the XLA ``fori`` chain (module docstring)."""
    if resolve is None:
        from cocoa_tpu.solvers.cocoa import resolve_solver_path as resolve
    ds = shapes_only(config)
    h = max(1, int(config["local_iter_frac"] * config["n"]
                   / config["num_splits"]))
    path = resolve(ds, h, None, math="fast").as_dict()
    if path.get("kernel") == "fori":
        raise RuntimeError(
            f"the program would run the sparse local solve of "
            f"{config['name']} (n = {config['n']}, d = {config['d']}, H = "
            f"{h}, rows up to {config['generator_args']['max_nnz']} "
            f"nonzeros) as kernel='fori', an XLA gather and scatter per "
            f"nonzero: it has no sparse solve for rows this long.  "
            f"Resolved path: {path}")
    return path


def make(config: dict, seed: int, mesh=None) -> ShardedDataset:
    if mesh is not None:
        raise ValueError("longrows_zipf makes its shards on one chip")
    preflight(config)
    n, d, k = config["n"], config["d"], config["num_splits"]
    args = config["generator_args"]
    width, sigma = args["max_nnz"], args.get("sigma_nnz", 0.6)
    flip = args.get("flip", 0.02)
    density_inv = args.get("planted_density_inv", 2)
    hot_cut = args.get("planted_hot_cut", 4096)
    dtype = jnp.dtype(config.get("dtype", "float32"))
    mu = length_mu(args["mean_nnz"], sigma, width)
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    n_win = stream_windows(config)
    win_pieces = WINDOW // STREAM_PIECE
    capacity = n_win * WINDOW - 8 * STREAM_PIECE
    if width % STREAM_ALIGN or k * n_win * WINDOW // STREAM_ALIGN >= 1 << 31:
        raise ValueError("max_nnz must be whole slot groups and the stream "
                         "must index by int32")

    def gen(key, counts):
        seed_bits = jax.random.bits(jax.random.fold_in(key, k), (),
                                    jnp.uint32)

        def shard_rows(s):
            ks = jax.random.fold_in(key, s)
            real = jnp.arange(n_shard) < counts[s]
            return fit_rows(
                row_lengths(jax.random.fold_in(ks, 0), n_shard, mu, sigma,
                            width), real, capacity)

        first, length = jax.lax.map(shard_rows, jnp.arange(k))

        def put(i, bufs):
            cols_buf, vals_buf, score = bufs
            s, b = i // n_win, i % n_win
            cols, vals, part = _window(
                b * WINDOW, first[s], length[s], jax.random.fold_in(
                    jax.random.fold_in(jax.random.fold_in(key, s), 1), b),
                d - width, seed_bits, density_inv, hot_cut)
            cut = lambda a: a.reshape(1, win_pieces, STREAM_PIECE)  # noqa: E731
            at = (s, b * win_pieces, 0)
            return (jax.lax.dynamic_update_slice(cols_buf, cut(cols), at),
                    jax.lax.dynamic_update_slice(
                        vals_buf, cut(vals).astype(dtype), at),
                    score.at[s].add(part))

        wide = (k, n_win * win_pieces, STREAM_PIECE)
        cols, vals, score = jax.lax.fori_loop(
            0, k * n_win, put,
            (jnp.zeros(wide, jnp.int32), jnp.zeros(wide, dtype),
             jnp.zeros((k, n_shard), jnp.float32)))
        k_flip, k_coin = jax.random.split(jax.random.fold_in(key, k + 1))
        real = (length > 0)
        coin = jax.random.bernoulli(k_coin, 0.5, score.shape)
        y = jnp.where((score > 0) | ((score == 0) & coin), 1.0, -1.0)
        y = jnp.where(jax.random.bernoulli(k_flip, flip, score.shape), -y, y)
        m = real.astype(dtype)
        lf = jnp.maximum(length, 1).astype(jnp.float32)
        sq = lf * jnp.square(jax.lax.rsqrt(lf))
        return (cols, vals, y.astype(dtype) * m, m, (sq * m).astype(dtype),
                (first // STREAM_ALIGN).astype(jnp.int32), length)

    cols, vals, labels, mask, sq_norms, ptr, length = jax.jit(gen)(
        jax.random.key(seed), jnp.asarray(sizes, dtype=jnp.int32))
    iota = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32)[None],
                            (k, width))
    return ShardedDataset(layout="sparse", n=n, num_features=d,
                          counts=sizes.astype(np.int64), labels=labels,
                          mask=mask, sq_norms=sq_norms, sp_indices=cols,
                          sp_values=vals, sp_row_ptr=ptr, sp_row_len=length,
                          sp_row_iota=iota)
