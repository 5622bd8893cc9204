"""Long-tailed sparse unit rows kept as a STREAM, a bias column in every
row, and a SET of labels a row whose frequencies follow a power law by
rank: the stand-in for a tf-idf extreme-classification file with real
documents for rows (Delicious-200K of the Extreme Classification
Repository), of which one chip trains a BATCH of the labels, one-vs-rest.

Two generators beside this file, joined: ``longrows_zipf``'s rows
(webspam's: the stream storage, the column law by strata, ``fit_rows``,
``row_lengths``) and ``sparse_multilabel``'s labels (amazoncat13k's: the
rank law ``label_shares``, the batch every (published / T)-th rank from
``first_rank``, a hashed planted direction a label ``_label_hash``, the
quantile rule, ``flip``).  Made on the device by one jitted call from the
seed, a shard after another; the result is the program's stream
``ShardedDataset`` with ``classes`` (K, n_shard, L): a row's label ids
within the batch, ascending, -1 past its own.

A row: its length L is log-normal (``sigma_nnz``), rounded and clipped to
[1, ``max_nnz``], with the mean ``mean_nnz``.  Its LAST nonzero is the bias
column d - 1 (LIBLINEAR's ``-B 1``, which DiSMEC trains with); the L - 1
before it are one draw from each of L - 1 equal strata of the unit interval
under Zipf(s = 1) over the other columns, ascending, a column drawn twice
moved up to the next free one in closed form (``longrows_zipf``'s law:
column = j + floor(max(0, D^u - 1 - (L - 1) u)), D = d - 1 - max_nnz).
Values are 1 / sqrt(L): unit rows.  The stream is made ``WINDOW`` slots at
a time and written in place.

Labels: label t of the batch takes the share of rows the rank law gives
its rank (``sparse_multilabel.label_shares``).  Which rows is PLANTED: a
row's score under label t's hashed direction (zero on the
``planted_hot_cut`` hottest columns, on the bias and on all but one column
in ``planted_density_inv``) over the score's standard deviation under the
hash passes the normal quantile of 1 - p_t; ``flip`` of a label's rows
lose it and as many gain it.  The T scores of a row are sums over its
nonzeros of a hash of (column, label): ``SUB`` slots at a time the (SUB,
T) hashes are made, a slot group's eight summed (a row starts on a group
boundary) and the groups added to their rows by one 0/1 matrix product at
``highest`` precision, a row's sum growing over the steps its slots span;
nothing is ever (row, widest row, T).  A row's ids are its labels in
ascending order, the first ``label_slots`` of them.  ``labels`` is class 0
against the rest.

**Pre-flight.**  Before it makes anything, ``make`` asks the program which
local solver a job on these shapes would run: a program whose resolver
refuses a class axis on rows kept as a stream fails there, in seconds,
with the resolver's own message; one that would run the XLA ``fori`` chain
is refused here."""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtri

from chipbench.generators import longrows_zipf, sparse_multilabel
from chipbench.generators.sparse_zipf import length_mu
from cocoa_tpu.data.sharding import (STREAM_ALIGN, STREAM_PIECE,
                                     ShardedDataset, pad_rows, split_sizes)

WINDOW = 1 << 20                # slots of the stream made per step
SUB = 1 << 12                   # slots whose (SUB, T) label hashes are made
                                # at a time
GROUP = STREAM_ALIGN

label_shares = sparse_multilabel.label_shares


def window_slots(config: dict) -> int:
    return int(config["generator_args"].get("window_slots", WINDOW))


def stream_windows(config: dict) -> int:
    """Windows a shard's stream holds: the expected slots of its rows (each
    under STREAM_ALIGN slots of padding), 2% over, the spare the kernels'
    last chunk may read, in whole windows."""
    n_rows = int(split_sizes(config["n"], config["num_splits"]).max())
    slots = n_rows * (config["generator_args"]["mean_nnz"] + STREAM_ALIGN / 2)
    return -(-int(1.02 * slots + 8 * STREAM_PIECE) // window_slots(config))


def _window(start, window: int, first, length, key, d_eff: int, d: int):
    """Slots [start, start + window) of one shard's stream: ``(columns,
    values, the slot's row, whether it is one of the row's Zipf draws)``.
    A slot learns its row from int32 running sums of the differences
    scattered at the row starts (``longrows_zipf._window``)."""
    rel = first - start
    inside = (rel >= 0) & (rel < window) & (length > 0)
    at = jnp.where(inside, rel, window)
    r0 = jnp.sum((first < start) & (length > 0)) - 1
    prev = lambda a: jnp.concatenate([a[:1] * 0, a[:-1]])  # noqa: E731
    spread = lambda a: (jnp.where(r0 >= 0, a[jnp.maximum(r0, 0)], 0) + (  # noqa: E731
        jnp.cumsum(jnp.zeros((window,), jnp.int32).at[at].add(
            a - prev(a), mode="drop")))).astype(jnp.int32)
    row = spread(jnp.arange(first.shape[0], dtype=jnp.int32))
    row_first, row_len = spread(first), spread(length)
    j = start + jnp.arange(window, dtype=jnp.int32) - row_first
    live = (j < row_len) & (row_len > 0)
    drawn = row_len - 1                 # Zipf draws; the bias is the last
    is_draw = live & (j < drawn)
    lf = jnp.maximum(drawn, 1).astype(jnp.float32)
    xi = jax.random.uniform(key, (window,), jnp.float32)
    u = (j.astype(jnp.float32) + xi) / lf
    tail = jnp.exp(u * math.log(d_eff)) - 1.0 - lf * u
    col = j + jnp.floor(jnp.clip(tail, 0.0, float(d_eff))).astype(jnp.int32)
    cols = jnp.where(is_draw, col, jnp.where(live, d - 1, 0)).astype(
        jnp.int32)
    vals = jnp.where(live, jax.lax.rsqrt(
        jnp.maximum(row_len, 1).astype(jnp.float32)), 0.0)
    return cols, vals, row, is_draw


def _scores(score, count, cols, row, is_draw, seed_bits, classes: int,
            density_inv: int, hot_cut: int, sub: int):
    """``(score, count)`` with what a window's slots add: ``score`` (rows +
    sub / GROUP, T) the rows' sums of the labels' planted weights over
    their carrying columns, ``count`` the carrying columns of a row."""
    groups = sub // GROUP
    t = jnp.arange(classes, dtype=jnp.int32)
    lanes = jnp.arange(groups, dtype=jnp.int32)

    def step(i, carry):
        score, count = carry
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, i * sub, sub)  # noqa: E731
        c, r = take(cols), take(row)
        carries = take(is_draw) & (c >= hot_cut)
        h = sparse_multilabel._label_hash(c[:, None], t[None, :], seed_bits)
        value = (h >> 8).astype(jnp.float32) * (2.0 / (1 << 24)) - 1.0
        planted = jnp.where(
            carries[:, None] & (h % jnp.uint32(density_inv) == 0), value, 0.0)
        by_group = planted.reshape(groups, GROUP, classes).sum(1)
        n_group = carries.reshape(groups, GROUP).sum(1).astype(jnp.float32)
        # a group is one row's: the row of its first slot
        g_row = r.reshape(groups, GROUP)[:, 0]
        r0 = g_row[0]
        owns = ((g_row - r0)[None, :] == lanes[:, None]).astype(jnp.float32)
        part = jnp.dot(owns, by_group, precision=jax.lax.Precision.HIGHEST)
        at = (r0, jnp.zeros_like(r0))
        had = jax.lax.dynamic_slice(score, at, (groups, classes))
        score = jax.lax.dynamic_update_slice(score, had + part, at)
        had = jax.lax.dynamic_slice_in_dim(count, r0, groups)
        count = jax.lax.dynamic_update_slice_in_dim(
            count, had + owns @ n_group, r0, 0)
        return score, count

    return jax.lax.fori_loop(0, cols.shape[0] // sub, step, (score, count))


def _label_sets(score, count, key, quantile, share, flip: float,
                density_inv: int, slots: int):
    """``(ids (rows, slots), class-0 labels)`` of rows with these scores."""
    # a planted weight is uniform on [-1, 1) one column in density_inv
    z = score * jax.lax.rsqrt(jnp.maximum(count / (3.0 * density_inv),
                                          1e-12))[:, None]
    taken = (z > quantile[None, :]) & (count > 0)[:, None]
    noise = jax.random.uniform(key, z.shape, jnp.float32)
    taken = jnp.where(taken, noise >= flip, noise < flip * share[None, :])
    none = share.shape[0]
    t = jnp.arange(none, dtype=jnp.int32)
    ids = -jax.lax.top_k(-jnp.where(taken, t[None, :], none), slots)[0]
    return jnp.where(ids == none, -1, ids), jnp.where(taken[:, 0], 1.0, -1.0)


def shapes_only(config: dict) -> ShardedDataset:
    """The dataset ``make`` would return, as shapes on the first device:
    what the pre-flight hands the program's resolver."""
    n, d, k = config["n"], config["d"], config["num_splits"]
    args = config["generator_args"]
    dtype = jnp.dtype(config.get("dtype", "float32"))
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    pieces = stream_windows(config) * (window_slots(config) // STREAM_PIECE)
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
        sp_row_iota=sds((k, args["max_nnz"]), jnp.int32),
        classes=sds((k, n_shard, args["label_slots"]), jnp.int32),
        num_classes=config["num_classes"])


def preflight(config: dict, resolve=None) -> dict:
    """Which local solver the program would run on these shapes; raises
    where no kernel of it carries T class models over rows kept as a
    stream (the resolver's own refusal, or the ``fori`` chain)."""
    if resolve is None:
        from cocoa_tpu.solvers.cocoa import resolve_solver_path as resolve
    h = max(1, int(config["local_iter_frac"] * config["n"]
                   / config["num_splits"]))
    path = resolve(shapes_only(config), h, None, math="fast").as_dict()
    if path.get("kernel") == "fori":
        raise RuntimeError(
            f"the program would run the local solve of {config['name']} "
            f"(n = {config['n']}, d = {config['d']}, T = "
            f"{config['num_classes']}, H = {h}, rows up to "
            f"{config['generator_args']['max_nnz']} nonzeros kept as a "
            f"stream) as kernel='fori': a gather and a scatter-add of a "
            f"{config['num_classes']}-wide row of W a nonzero in XLA.  "
            f"Resolved path: {path}")
    return path


def make(config: dict, seed: int, mesh=None) -> ShardedDataset:
    if mesh is not None:
        raise ValueError("longrows_multilabel makes its shards on one chip")
    preflight(config)
    n, d, k = config["n"], config["d"], config["num_splits"]
    args, classes = config["generator_args"], config["num_classes"]
    width, sigma = args["max_nnz"], args.get("sigma_nnz", 1.0)
    flip, slots = args.get("flip", 0.02), args["label_slots"]
    density_inv = args.get("planted_density_inv", 2)
    hot_cut = args.get("planted_hot_cut", 4096)
    dtype = jnp.dtype(config.get("dtype", "float32"))
    mu = length_mu(args["mean_nnz"], sigma, width)
    share = jnp.asarray(label_shares(config), jnp.float32)
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    window, n_win = window_slots(config), stream_windows(config)
    sub = min(SUB, window)
    win_pieces = window // STREAM_PIECE
    capacity = n_win * window - 8 * STREAM_PIECE
    if (width % STREAM_ALIGN or window % sub or sub % STREAM_PIECE
            or k * n_win * window // STREAM_ALIGN >= 1 << 31):
        raise ValueError("max_nnz must be whole slot groups, a window whole "
                         "label steps, and the stream must index by int32")

    def gen(key, counts, share):
        seed_bits = jax.random.bits(jax.random.fold_in(key, k), (),
                                    jnp.uint32)
        quantile = -ndtri(share)

        def one_shard(s, bufs):
            cols_buf, vals_buf, ids_buf, y_buf, ptr_buf, len_buf = bufs
            ks = jax.random.fold_in(key, s)
            real = jnp.arange(n_shard) < counts[s]
            first, length = longrows_zipf.fit_rows(
                longrows_zipf.row_lengths(jax.random.fold_in(ks, 0), n_shard,
                                          mu, sigma, width), real, capacity)

            def one_window(b, carry):
                cols_buf, vals_buf, score, count = carry
                cols, vals, row, is_draw = _window(
                    b * window, window, first, length, jax.random.fold_in(
                        jax.random.fold_in(ks, 1), b), d - 1 - width, d)
                cut = lambda a: a.reshape(1, win_pieces, STREAM_PIECE)  # noqa: E731
                at = (s, b * win_pieces, jnp.zeros_like(b))
                score, count = _scores(score, count, cols, row, is_draw,
                                       seed_bits, classes, density_inv,
                                       hot_cut, sub)
                return (jax.lax.dynamic_update_slice(cols_buf, cut(cols), at),
                        jax.lax.dynamic_update_slice(
                            vals_buf, cut(vals).astype(dtype), at),
                        score, count)

            spare = sub // GROUP
            cols_buf, vals_buf, score, count = jax.lax.fori_loop(
                0, n_win, one_window,
                (cols_buf, vals_buf,
                 jnp.zeros((n_shard + spare, classes), jnp.float32),
                 jnp.zeros((n_shard + spare,), jnp.float32)))
            ids, y = _label_sets(score[:n_shard], count[:n_shard],
                                 jax.random.fold_in(ks, 2), quantile, share,
                                 flip, density_inv, slots)
            live = length > 0
            put = lambda buf, a: jax.lax.dynamic_update_slice(  # noqa: E731
                buf, a[None].astype(buf.dtype),
                (s,) + (jnp.zeros_like(s),) * a.ndim)
            return (cols_buf, vals_buf,
                    put(ids_buf, jnp.where(live[:, None], ids, -1)),
                    put(y_buf, jnp.where(live, y, 0.0)),
                    put(ptr_buf, first // STREAM_ALIGN), put(len_buf, length))

        wide = (k, n_win * win_pieces, STREAM_PIECE)
        rows = jnp.zeros((k, n_shard), dtype)
        irows = jnp.zeros((k, n_shard), jnp.int32)
        cols, vals, ids, y, ptr, length = jax.lax.fori_loop(
            0, k, one_shard,
            (jnp.zeros(wide, jnp.int32), jnp.zeros(wide, dtype),
             jnp.full((k, n_shard, slots), -1, jnp.int32), rows, irows,
             irows))
        m = (length > 0).astype(dtype)
        # a unit row: L values of 1 / sqrt(L)
        lf = jnp.maximum(length, 1).astype(jnp.float32)
        sq = lf * jnp.square(jax.lax.rsqrt(lf))
        return cols, vals, ids, y, m, (sq * m).astype(dtype), ptr, length

    cols, vals, ids, labels, mask, sq_norms, ptr, length = jax.jit(gen)(
        jax.random.key(seed), jnp.asarray(sizes, dtype=jnp.int32), share)
    iota = jnp.broadcast_to(jnp.arange(width, dtype=jnp.int32)[None],
                            (k, width))
    return ShardedDataset(layout="sparse", n=n, num_features=d,
                          counts=sizes.astype(np.int64), labels=labels,
                          mask=mask, sq_norms=sq_norms, sp_indices=cols,
                          sp_values=vals, sp_row_ptr=ptr, sp_row_len=length,
                          sp_row_iota=iota, classes=ids,
                          num_classes=config["num_classes"])
