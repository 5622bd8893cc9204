"""Sparse unit rows over Zipf columns, a bias column in every row, and a SET
of labels a row whose frequencies follow a power law by rank: the stand-in
for a bag-of-words extreme-classification file (AmazonCat-13K of the
Extreme Classification Repository) of which one chip trains a BATCH of the
labels, one-vs-rest.

Made on the device by one jitted call from the seed, block of rows by
block, as ``sparse_zipf`` (the generator beside this file) makes kddb's;
its length law and its hashed planted weights are used as they are
(``length_mu``, ``planted_w``).  The result is the program's padded-CSR
``ShardedDataset`` with ``classes`` (K, n_shard, L): a row's label ids
within the batch, ascending, -1 past its own.

A row: its length is log-normal, rounded and clipped to [1, W]
(``generator_args``: ``mean_nnz``, ``sigma_nnz``, ``max_nnz`` = W).  Its
last nonzero is the BIAS column d - 1 (LIBLINEAR's ``-B 1``: DiSMEC trains
with a bias term), the others are ascending draws without repeats from
Zipf(s = 1) over the other d - 1 columns, as ``sparse_zipf`` draws them.
Values are 1 / sqrt(length): unit rows.  Slots past the length carry
column 0 and value 0.

Labels.  The published file has ``published_labels`` = 13,330 labels, 448.6
rows a label in the mean; label r of them (rank 1 the most frequent) is
given the share of rows p_r = ``labels_per_row`` / H(13,330) / r, a power
law of exponent 1 whose mean over the ranks is the published one (p_1 =
0.50, p_13330 = 3.8e-5: 45 rows).  The chip's batch is ``num_classes`` =
T of them, every (13,330 / T)-th rank from rank ``first_rank`` on, r_t =
``first_rank`` + floor(t 13,330 / T): head and tail are both in it.  The
mean batch of T of the 13,330 holds ``labels_per_row`` T / 13,330 = 0.378
labels a row, and ``first_rank`` = 5 is the stride whose batch holds that
as made (0.364 by the law; the planted scores' tails are a little heavier
than a normal's, so the labels take 3 to 9% more rows than their
shares; from rank 4 the law gives 0.392 and 0.40 to 0.42 are made): its
most frequent label takes a tenth of the rows.  The ONE stride in 13.33
that starts at rank 1 holds the head label's 0.50 a row by itself, 0.78
in all: one of fourteen nodes trains that batch, and it is not the one
made here.  Which rows a label takes is PLANTED, as
kddb's separator is: label t has a hashed direction of its own over the
columns (zero on the ``planted_hot_cut`` hottest columns, on the bias and
on all but one column in ``planted_density_inv``), a row's score under it
is x . w*_t over the row's standard deviation under the hash, near enough
a unit normal, and the row takes the label where the score passes the
normal quantile of 1 - p_t: the frequencies are fixed by rank, never
drawn, and a label's rows are linearly separated from the rest up to the
noise — ``flip`` of a label's rows lose it, and as many other rows gain
it.  A row's ids are its labels in ascending order, the first
``label_slots`` of them (at 0.38 a row in the mean, more than 8 is no row
in 10^9).  ``labels`` is class 0 against the rest.

**Pre-flight.**  Before it makes anything, ``make`` asks the program which
local solver a job on these shapes would run: a program whose resolver
refuses a class axis on sparse rows fails there, in seconds, with the
resolver's own message; one that would run the XLA ``fori`` chain (a W row
gather and a scatter-add a nonzero) is refused here."""

from __future__ import annotations

import math
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import ndtri

from chipbench import registry
from cocoa_tpu.data.sharding import ShardedDataset, pad_rows, split_sizes

_zipf = registry.load_module(
    {"_dir": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))},
    "generators", "sparse_zipf")

ROW_BLOCK = 128                 # rows made per step: (block, W, T) hashes


def label_shares(config: dict) -> np.ndarray:
    """p_t, t = 0 .. T-1: the share of rows label t of the batch takes."""
    args = config["generator_args"]
    published, t = config["published_labels"], config["num_classes"]
    harmonic = float(np.sum(1.0 / np.arange(1, published + 1)))
    ranks = args["first_rank"] + np.floor(np.arange(t) * published / t)
    if ranks[-1] > published:
        raise ValueError(f"first_rank {args['first_rank']}: the batch's last "
                         f"rank {int(ranks[-1])} is past {published}")
    return args["labels_per_row"] / harmonic / ranks


def _label_hash(cols, t, seed_bits):
    """A 32-bit hash of (column, label)."""
    h = (cols.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
         + t.astype(jnp.uint32) * jnp.uint32(0x7FEB352D) + seed_bits)
    h = (h ^ (h >> 15)) * jnp.uint32(0x85EBCA77)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE3D)
    return h ^ (h >> 16)


def _rows(key, rows: int, d: int, width: int, mu: float, sigma: float,
          flip: float, density_inv: int, hot_cut: int, slots: int,
          quantile, share, seed_bits):
    """``rows`` rows: (columns, values, label ids, class-0 labels, squared
    norms)."""
    k_len, k_exp, k_flip = jax.random.split(key, 3)
    length = jnp.clip(jnp.round(jnp.exp(
        mu + sigma * jax.random.normal(k_len, (rows,), jnp.float32))),
        1, width)
    slot = jnp.arange(width, dtype=jnp.int32)
    drawn = length - 1                  # Zipf draws; the bias is the last
    e = jax.random.exponential(k_exp, (rows, width + 1), jnp.float32)
    e = jnp.where(jnp.arange(width + 1)[None, :] <= drawn[:, None], e, 0.0)
    u = jnp.cumsum(e[:, :width], axis=1) / jnp.sum(e, axis=1, keepdims=True)
    span = math.log(d - 1 - width + 1)
    c = jnp.floor(jnp.exp(jnp.minimum(u, 1.0) * span)).astype(jnp.int32) - 1
    c = jnp.clip(c, 0, d - 1 - width)
    c = jax.lax.cummax(c - slot[None, :], axis=1) + slot[None, :]
    is_draw = slot[None, :] < drawn[:, None]
    is_bias = slot[None, :] == drawn[:, None]
    cols = jnp.where(is_draw, c, jnp.where(is_bias, d - 1, 0))
    vals = jnp.where(is_draw | is_bias, jax.lax.rsqrt(length)[:, None], 0.0)
    # the labels' hashed directions over the row's drawn columns
    t = jnp.arange(share.shape[0], dtype=jnp.int32)
    h = _label_hash(cols[:, :, None], t[None, None, :], seed_bits)
    carries = (is_draw & (cols >= hot_cut))[:, :, None]
    value = (h >> 8).astype(jnp.float32) * (2.0 / (1 << 24)) - 1.0
    planted = jnp.where(carries & (h % jnp.uint32(density_inv) == 0),
                        value, 0.0)
    score = jnp.sum(vals[:, :, None] * planted, axis=1)      # (rows, T)
    # a planted weight is uniform on [-1, 1) one column in density_inv
    var = jnp.sum(jnp.where(carries[..., 0], vals * vals, 0.0),
                  axis=1) / (3.0 * density_inv)
    z = score * jax.lax.rsqrt(jnp.maximum(var, 1e-12))[:, None]
    taken = (z > quantile[None, :]) & (var > 0)[:, None]
    noise = jax.random.uniform(k_flip, z.shape, jnp.float32)
    taken = jnp.where(taken, noise >= flip, noise < flip * share[None, :])
    none = share.shape[0]
    ids = -jax.lax.top_k(-jnp.where(taken, t[None, :], none), slots)[0]
    ids = jnp.where(ids == none, -1, ids)
    y = jnp.where(taken[:, 0], 1.0, -1.0)
    return cols, vals, ids, y, jnp.sum(vals * vals, axis=1)


def shapes_only(config: dict) -> ShardedDataset:
    """The dataset ``make`` would return, as shapes on the first device:
    what the pre-flight hands the program's resolver."""
    n, d, k = config["n"], config["d"], config["num_splits"]
    args = config["generator_args"]
    dtype = jnp.dtype(config.get("dtype", "float32"))
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    here = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=here)

    rows = sds((k, n_shard), dtype)
    return ShardedDataset(
        layout="sparse", n=n, num_features=d, counts=sizes.astype(np.int64),
        labels=rows, mask=rows, sq_norms=rows,
        sp_indices=sds((k, n_shard, args["max_nnz"]), jnp.int32),
        sp_values=sds((k, n_shard, args["max_nnz"]), dtype),
        classes=sds((k, n_shard, args["label_slots"]), jnp.int32),
        num_classes=config["num_classes"])


def preflight(config: dict, resolve=None) -> dict:
    """Which local solver the program would run on these shapes; raises
    where no kernel of it carries T class models over sparse rows (the
    resolver's own refusal, or the ``fori`` chain)."""
    if resolve is None:
        from cocoa_tpu.solvers.cocoa import resolve_solver_path as resolve
    h = max(1, int(config["local_iter_frac"] * config["n"]
                   / config["num_splits"]))
    path = resolve(shapes_only(config), h, None, math="fast").as_dict()
    if path.get("kernel") == "fori":
        raise RuntimeError(
            f"the program would run the local solve of {config['name']} "
            f"(n = {config['n']}, d = {config['d']}, T = "
            f"{config['num_classes']}, H = {h}) as kernel='fori': a gather "
            f"and a scatter-add of a {config['num_classes']}-wide row of W "
            f"a nonzero in XLA.  Resolved path: {path}")
    return path


def make(config: dict, seed: int, mesh=None) -> ShardedDataset:
    if mesh is not None:
        raise ValueError("sparse_multilabel makes its shards on one chip")
    preflight(config)
    n, d, k = config["n"], config["d"], config["num_splits"]
    args = config["generator_args"]
    width, sigma = args["max_nnz"], args.get("sigma_nnz", 0.5)
    flip, slots = args.get("flip", 0.02), args["label_slots"]
    density_inv = args.get("planted_density_inv", 2)
    hot_cut = args.get("planted_hot_cut", 1024)
    dtype = jnp.dtype(config.get("dtype", "float32"))
    mu = _zipf.length_mu(args["mean_nnz"], sigma, width)
    share = jnp.asarray(label_shares(config), jnp.float32)
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    block = min(ROW_BLOCK, n_shard)
    n_blocks = -(-n_shard // block)

    def gen(key, counts, share):
        seed_bits = jax.random.bits(jax.random.fold_in(key, k), (),
                                    jnp.uint32)
        quantile = -ndtri(share)

        def put(i, bufs):
            # block b of shard s, written in place (sparse_zipf's note); the
            # last block starts early enough to end on the shard's last row
            s, b = i // n_blocks, i % n_blocks
            start = jnp.minimum(b * block, n_shard - block)
            cols, vals, ids, y, sq = _rows(
                jax.random.fold_in(jax.random.fold_in(key, s), b), block, d,
                width, mu, sigma, flip, density_inv, hot_cut, slots,
                quantile, share, seed_bits)
            m = start + jnp.arange(block) < counts[s]
            new = (jnp.where(m[:, None], cols, 0),
                   jnp.where(m[:, None], vals, 0).astype(dtype),
                   jnp.where(m[:, None], ids, -1),
                   jnp.where(m, y, 0).astype(dtype), m.astype(dtype),
                   jnp.where(m, sq, 0).astype(dtype))
            return tuple(
                jax.lax.dynamic_update_slice(
                    buf, a[None].astype(buf.dtype),
                    (s, start) + (0,) * (a.ndim - 1))
                for buf, a in zip(bufs, new))

        rows = jnp.zeros((k, n_shard), dtype)
        wide = (k, n_shard, width)
        return jax.lax.fori_loop(
            0, k * n_blocks, put,
            (jnp.zeros(wide, jnp.int32), jnp.zeros(wide, dtype),
             jnp.full((k, n_shard, slots), -1, jnp.int32), rows, rows,
             rows))

    cols, vals, ids, labels, mask, sq_norms = jax.jit(gen)(
        jax.random.key(seed), jnp.asarray(sizes, dtype=jnp.int32), share)
    return ShardedDataset(layout="sparse", n=n, num_features=d,
                          counts=sizes.astype(np.int64), labels=labels,
                          mask=mask, sq_norms=sq_norms, sp_indices=cols,
                          sp_values=vals, classes=ids,
                          num_classes=config["num_classes"])
