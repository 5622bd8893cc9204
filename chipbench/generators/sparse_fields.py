"""Click-log rows made on the device: every row the same 39 nonzeros, one a
field — the stand-in for a LIBSVM file of hashed display-advertising
impressions (criteo: the Kaggle Display Advertising Challenge as the LIBSVM
page distributes it).

One jitted call from the seed, a loop over row blocks that writes each
block in place, never the host and never a per-row loop; the result is the
program's padded-CSR ``ShardedDataset`` (what ``data.sharding.shard_dataset``
builds from a file), as ``sparse_zipf`` returns kddb's.  The seed is an
argument of the jitted call, so every seed runs the one compiled program,
and the same ``(config, seed)`` gives the same shards.

A row has ``counter_fields`` (13) integer counters and ``categorical_fields``
(26) categorical fields (``generator_args``).  A counter field is ONE column
(columns 0-12), present in every row, its value |N(0, 1)|.  A categorical
field owns a range of the other d - 13 columns, the ranges disjoint and
ascending, their sizes geometric from ``smallest_field`` (4) up (the real
fields' cardinalities run from a handful to millions before they are
hashed into 10^6 columns: :func:`field_ranges`); its one nonzero is a draw
from Zipf(s = 1) inside the range by the inverse CDF of the continuous law,
with value 1.  The row is then scaled to unit length, as the page's file
is.  Columns ascend and never repeat by construction: no sort, no move-up.
The rectangle is stored as wide as the program's loader would store 39
nonzeros (``data.sharding.rectangle_width`` where the program has that
rule: a whole number of 8-slot groups, so 40; the slot past the row holds
column 0 and value 0, the padded-CSR convention).

Labels: the row's score is the sum of its categorical values times a
planted w* that is a hash of the column (``sparse_zipf.planted_w``: no
d-sized table; zero on all but one column in ``planted_density_inv``), the
label +1 where the score is over a quantile of the dataset's scores (one
sort of them on the device), then ``flip`` label noise: the quantile is the
one that leaves ``click_share`` (0.26, the Kaggle file's) of the rows
clicks after the flips, at every seed.

**Pre-flight.**  Before it makes anything, ``make`` asks the program which
local solver a job on these shapes would run
(``solvers.cocoa.resolve_solver_path`` on a dataset of shapes only) and
raises unless the answer is the HBM-state kernel on its ``direct`` plan
(``local_ids``: the local id IS the column, [w | dw] whole in VMEM): the
XLA ``fori`` chain is ~44 us a step, and a program that cannot say which
plan it runs predates the cell.  Such a program fails here in seconds,
before any data is made.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import registry
from cocoa_tpu.data import sharding
from cocoa_tpu.data.sharding import ShardedDataset, pad_rows, split_sizes

ROW_BLOCK = 1 << 16             # rows made per step of the loop

# kddb's generator (the one beside this file, wherever the benchmark's copy
# lives): its planted separator, its shapes-only dataset and its pre-flight
_zipf = registry.load_module(
    {"_dir": os.path.dirname(os.path.dirname(os.path.abspath(__file__)))},
    "generators", "sparse_zipf")
planted_w = _zipf.planted_w


def row_nnz(args: dict) -> int:
    return args["counter_fields"] + args["categorical_fields"]


def stored_width(args: dict) -> int:
    """Slots a row takes in the rectangle: the loader's rule for rows of
    this many nonzeros, where the program has one."""
    rule = getattr(sharding, "rectangle_width", lambda w: w)
    return int(rule(row_nnz(args)))


def field_ranges(d: int, args: dict):
    """``(starts, sizes)`` of the categorical fields' column ranges: sizes
    geometric from ``smallest_field``, the ratio such that they fill the
    d - counter_fields columns past the counters (the last takes what the
    rounding leaves)."""
    n_int, n_cat = args["counter_fields"], args["categorical_fields"]
    first, total = args.get("smallest_field", 4), d - n_int
    if total < first * n_cat:
        raise ValueError(f"d = {d} holds no {n_cat} ranges of {first}")
    power = np.arange(n_cat)
    lo, hi = 1.0, float(total)
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        lo, hi = ((mid, hi) if (first * mid ** power).sum() < total
                  else (lo, mid))
    sizes = np.floor(first * lo ** power).astype(np.int64)
    sizes[-1] += total - sizes.sum()
    starts = n_int + np.concatenate([[0], np.cumsum(sizes)[:-1]])
    return starts, sizes


def _rows(key, rows: int, n_int: int, starts, sizes, width: int,
          density_inv: int, seed_bits):
    """``rows`` rows: (columns, values, scores, squared norms)."""
    k_cnt, k_cat = jax.random.split(key)
    counters = jnp.abs(jax.random.normal(k_cnt, (rows, n_int), jnp.float32))
    u = jax.random.uniform(k_cat, (rows, len(sizes)), jnp.float32)
    size = jnp.asarray(sizes, jnp.float32)
    at = jnp.floor(jnp.exp(u * jnp.log(size + 1.0))).astype(jnp.int32) - 1
    cat = jnp.asarray(starts, jnp.int32) + jnp.clip(
        at, 0, jnp.asarray(sizes - 1, jnp.int32))
    scale = jax.lax.rsqrt(jnp.sum(counters * counters, axis=1,
                                  keepdims=True) + len(sizes))
    ones = jnp.broadcast_to(scale, cat.shape)
    score = jnp.sum(ones * planted_w(cat, seed_bits, density_inv, 0), axis=1)
    pad = ((0, 0), (0, width - n_int - len(sizes)))
    cols = jnp.pad(jnp.concatenate([jnp.broadcast_to(
        jnp.arange(n_int, dtype=jnp.int32), (rows, n_int)), cat], axis=1),
        pad)
    vals = jnp.pad(jnp.concatenate([counters * scale, ones], axis=1), pad)
    return cols, vals, score, jnp.sum(vals * vals, axis=1)


def preflight(config: dict, resolve=None) -> dict:
    """Which local solver the program would run on these shapes
    (``sparse_zipf.preflight`` at the width this rectangle is stored at,
    which refuses the ``fori`` chain); raises unless it is the HBM-state
    kernel's ``direct`` plan (module docstring)."""
    args = config["generator_args"]
    path = _zipf.preflight(
        {**config, "generator_args": {**args, "max_nnz": stored_width(args)}},
        resolve)
    if path.get("local_ids") != "direct":
        raise RuntimeError(
            f"the program would not run the sparse local solve of "
            f"{config['name']} (n = {config['n']}, d = {config['d']}) with "
            f"[w | dw] whole in VMEM and the column as the local id "
            f"(solver_path.local_ids = 'direct'): it says local_ids = "
            f"{path.get('local_ids')!r} (None: a program from before it said "
            f"which plan its HBM-state kernel runs).  Resolved path: {path}")
    return path


def program(config: dict):
    """``(gen, sizes)``: the function of (key, the shards' row counts) that
    makes the five arrays, to be jitted, and the counts it takes."""
    n, d, k = config["n"], config["d"], config["num_splits"]
    args = config["generator_args"]
    n_int, width = args["counter_fields"], stored_width(args)
    flip, clicks = args.get("flip", 0.02), args.get("click_share", 0.26)
    density_inv = args.get("planted_density_inv", 2)
    dtype = jnp.dtype(config.get("dtype", "float32"))
    starts, field_sizes = field_ranges(d, args)
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    block = min(ROW_BLOCK, n_shard)
    n_blocks = -(-n_shard // block)

    def gen(key, counts):
        seed_bits = jax.random.bits(jax.random.fold_in(key, k), (),
                                    jnp.uint32)

        def put(i, bufs):
            # block b of shard s, written in place (sparse_zipf's reason:
            # stacking the blocks costs a relayout of everything).  The
            # last block starts early enough to end on the shard's last
            # row, so it makes the rows it shares with its neighbour anew.
            s, b = i // n_blocks, i % n_blocks
            start = jnp.minimum(b * block, n_shard - block)
            cols, vals, score, sq = _rows(
                jax.random.fold_in(jax.random.fold_in(key, s), b), block,
                n_int, starts, field_sizes, width, density_inv, seed_bits)
            m = (start + jnp.arange(block) < counts[s]).astype(dtype)
            new = (cols * m[:, None].astype(jnp.int32),
                   (vals * m[:, None]).astype(dtype), score, m,
                   (sq * m).astype(dtype))
            return tuple(
                jax.lax.dynamic_update_slice(
                    buf, a[None].astype(buf.dtype),
                    (s, start) + (0,) * (a.ndim - 1))
                for buf, a in zip(bufs, new))

        rows = jnp.zeros((k, n_shard), dtype)
        wide = (k, n_shard, width)
        cols, vals, score, mask, sq = jax.lax.fori_loop(
            0, k * n_blocks, put,
            (jnp.zeros(wide, jnp.int32), jnp.zeros(wide, dtype),
             jnp.zeros((k, n_shard), jnp.float32), rows, rows))
        # the share of the real rows that score over the cut is the one
        # that leaves ``clicks`` of them positive after the flips; the
        # padding rows sort last
        planted = (clicks - flip) / (1.0 - 2.0 * flip)
        cut = jnp.sort(jnp.where(mask > 0, score, jnp.inf).reshape(-1))[
            min(n - 1, int(round((1.0 - planted) * n)))]
        y = jnp.where(score > cut, 1.0, -1.0)
        y = jnp.where(jax.random.bernoulli(jax.random.fold_in(key, k + 1),
                                           flip, y.shape), -y, y)
        return cols, vals, (y * mask).astype(dtype), mask, sq

    return gen, sizes


def make(config: dict, seed: int, mesh=None) -> ShardedDataset:
    if mesh is not None:
        raise ValueError("sparse_fields makes its shards on one chip")
    preflight(config)
    gen, sizes = program(config)
    cols, vals, labels, mask, sq_norms = jax.jit(gen)(
        jax.random.key(seed), jnp.asarray(sizes, dtype=jnp.int32))
    ds = ShardedDataset(layout="sparse", n=config["n"],
                        num_features=config["d"],
                        counts=sizes.astype(np.int64), labels=labels,
                        mask=mask, sq_norms=sq_norms, sp_indices=cols,
                        sp_values=vals)
    # as the program's loader does for a file: the rows' lengths, here one
    return sharding.note_row_lengths(ds, [row_nnz(config["generator_args"])])
