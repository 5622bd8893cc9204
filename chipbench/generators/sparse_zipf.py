"""Sparse unit rows over Zipf columns with a planted separator, made on
the device: the stand-in for a LIBSVM file of categorical indicator
features (kddb: KDD Cup 2010 "bridge to algebra").

One jitted call from the seed, ``lax.map`` over row blocks, never the host
and never a per-row loop; the result is the program's padded-CSR
``ShardedDataset`` (what ``data.sharding.shard_dataset`` builds from a
file), as ``dense_planted`` returns the dense one.  The seed is an argument
of the jitted call, so every seed runs the one compiled program, and the
same ``(config, seed)`` gives the same shards.

A row: its length is log-normal, rounded and clipped to [1, W]
(``generator_args``: ``mean_nnz``, ``sigma_nnz``, ``max_nnz`` = W); its
columns are draws from Zipf(s = 1) over the d columns by the inverse CDF
of the continuous law, column = floor((d - W + 1) ** u) - 1 for u uniform
on [0, 1).  The row's uniforms are made already sorted (the order
statistics of L uniforms are the normalised partial sums of L + 1
exponentials), so the columns come out ascending, and a duplicate is
moved up to the next free column (``cummax(c_j - j) + j``): no column
twice in a row, no sort.  Values are 1 / sqrt(length): unit rows of
indicator features.  Slots past the length carry column 0 and value 0,
the padded-CSR convention.  Labels are sign(x . w*) with ``flip`` label
noise, for a planted w* that is a hash of the column, so no d-sized table
is gathered: zero on the ``planted_hot_cut`` hottest columns and on all but
one column in ``planted_density_inv`` of the rest (a row whose score is
exactly 0 gets a coin).  The hot head is left out because a Zipf law puts
column 0 in three rows of four: with w* non-zero there, its one value set
the label balance and the problem's difficulty for the whole dataset, and
they swung with the seed (label mean -0.65 to +0.42, the gap after ten
rounds 0.008 to 0.026: jobs of 10 or 15 rounds; PERF.md §6, PR 26).  Past
the head every label is a sum over several of millions of columns, and
seeds differ only as samples of one law do.

**Pre-flight.**  Before it makes anything, ``make`` asks the program which
local solver a job on these shapes would run
(``solvers.cocoa.resolve_solver_path`` on a dataset of shapes only) and
raises if a sparse set of this size would run ``kernel="fori"``: the XLA
gather-and-scatter chain is ~44 us a step, an hour a job at kddb.  A
program without a sparse solve for this size fails here in seconds.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from cocoa_tpu.data.sharding import ShardedDataset, pad_rows, split_sizes

ROW_BLOCK = 1 << 16             # rows made per step of the ``lax.map``


def length_mu(mean_nnz: float, sigma: float, width: int) -> float:
    """The log-normal's mu for which the rounded, clipped length has mean
    ``mean_nnz`` (bisection on the exact sum; the clip at ``width`` eats
    part of the tail, so mu sits a little above ln(mean) - sigma^2 / 2)."""
    edges = np.arange(1, width) + 0.5          # length l <=> (l-.5, l+.5]

    def mean_at(mu):
        cdf = np.array([0.5 * (1 + math.erf((math.log(e) - mu)
                                            / (sigma * math.sqrt(2))))
                        for e in edges])
        p = np.diff(np.concatenate([[0.0], cdf, [1.0]]))
        return float(p @ np.arange(1, width + 1))

    lo, hi = 0.0, math.log(width)
    for _ in range(60):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if mean_at(mid) < mean_nnz else (lo, mid)
    return 0.5 * (lo + hi)


def planted_w(cols, seed_bits, density_inv: int, hot_cut: int):
    """w*[col] as a hash of the column: uniform on [-1, 1) where the hash
    falls on one residue of ``density_inv`` and the column is past the
    ``hot_cut`` hottest, else 0."""
    h = (cols.astype(jnp.uint32) + seed_bits) * jnp.uint32(0x9E3779B1)
    h = (h ^ (h >> 15)) * jnp.uint32(0x85EBCA77)
    h = (h ^ (h >> 13)) * jnp.uint32(0xC2B2AE3D)
    h = h ^ (h >> 16)
    value = (h >> 8).astype(jnp.float32) * (2.0 / (1 << 24)) - 1.0
    return jnp.where((h % jnp.uint32(density_inv) == 0) & (cols >= hot_cut),
                     value, 0.0)


def _rows(key, rows: int, d: int, width: int, mu: float, sigma: float,
          flip: float, density_inv: int, hot_cut: int, seed_bits):
    """``rows`` rows: (columns, values, labels, squared norms)."""
    k_len, k_exp, k_flip, k_coin = jax.random.split(key, 4)
    length = jnp.clip(jnp.round(jnp.exp(
        mu + sigma * jax.random.normal(k_len, (rows,), jnp.float32))),
        1, width)
    slot = jnp.arange(width, dtype=jnp.int32)
    live = slot[None, :] < length[:, None]
    # sorted uniforms: partial sums of exponentials over the row's total
    e = jax.random.exponential(k_exp, (rows, width + 1), jnp.float32)
    e = jnp.where(jnp.arange(width + 1)[None, :] <= length[:, None], e, 0.0)
    u = jnp.cumsum(e[:, :width], axis=1) / jnp.sum(e, axis=1, keepdims=True)
    span = math.log(d - width + 1)
    c = jnp.floor(jnp.exp(jnp.minimum(u, 1.0) * span)).astype(jnp.int32) - 1
    c = jnp.clip(c, 0, d - width)
    c = jax.lax.cummax(c - slot[None, :], axis=1) + slot[None, :]
    value = jax.lax.rsqrt(length)[:, None]
    cols = jnp.where(live, c, 0)
    vals = jnp.where(live, value, 0.0)
    score = jnp.sum(vals * planted_w(cols, seed_bits, density_inv, hot_cut),
                    axis=1)
    coin = jax.random.bernoulli(k_coin, 0.5, (rows,))
    y = jnp.where((score > 0) | ((score == 0) & coin), 1.0, -1.0)
    y = jnp.where(jax.random.bernoulli(k_flip, flip, (rows,)), -y, y)
    return cols, vals, y, jnp.sum(vals * vals, axis=1)


def shapes_only(config: dict) -> ShardedDataset:
    """The dataset ``make`` would return, as shapes on the first device:
    what the pre-flight hands the program's resolver."""
    n, d, k = config["n"], config["d"], config["num_splits"]
    width = config["generator_args"]["max_nnz"]
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
        sp_indices=sds((k, n_shard, width), jnp.int32),
        sp_values=sds((k, n_shard, width), dtype))


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
            f"{h}) as kernel='fori', an XLA gather and scatter per step "
            f"(~44 us a step: {h * 44e-6:.0f} s a round): it has no sparse "
            f"solve whose state leaves VMEM.  Resolved path: {path}")
    return path


def make(config: dict, seed: int, mesh=None) -> ShardedDataset:
    if mesh is not None:
        raise ValueError("sparse_zipf makes its shards on one chip")
    preflight(config)
    n, d, k = config["n"], config["d"], config["num_splits"]
    args = config["generator_args"]
    width, sigma = args["max_nnz"], args.get("sigma_nnz", 0.5)
    flip = args.get("flip", 0.02)
    density_inv = args.get("planted_density_inv", 2)
    hot_cut = args.get("planted_hot_cut", 4096)
    dtype = jnp.dtype(config.get("dtype", "float32"))
    mu = length_mu(args["mean_nnz"], sigma, width)
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    block = min(ROW_BLOCK, n_shard)
    n_blocks = -(-n_shard // block)

    def gen(key, counts):
        seed_bits = jax.random.bits(jax.random.fold_in(key, k), (),
                                    jnp.uint32)

        def put(i, bufs):
            # block b of shard s, written in place into the (K, n_shard, ..)
            # results: stacking the blocks of a ``lax.map`` and reshaping
            # costs a relayout copy of all 10 GB, since the device keeps
            # (K, n_shard, W) with the rows on the lanes (tried: 19.7 GB).
            # The last block starts early enough to end on the shard's last
            # row, so it makes the rows it shares with its neighbour anew.
            s, b = i // n_blocks, i % n_blocks
            start = jnp.minimum(b * block, n_shard - block)
            cols, vals, y, sq = _rows(
                jax.random.fold_in(jax.random.fold_in(key, s), b), block, d,
                width, mu, sigma, flip, density_inv, hot_cut, seed_bits)
            m = (start + jnp.arange(block) < counts[s]).astype(dtype)
            new = (cols * m[:, None].astype(jnp.int32),
                   (vals * m[:, None]).astype(dtype), y.astype(dtype) * m,
                   m, (sq * m).astype(dtype))
            return tuple(
                jax.lax.dynamic_update_slice(
                    buf, a[None].astype(buf.dtype),
                    (s, start) + (0,) * (a.ndim - 1))
                for buf, a in zip(bufs, new))

        rows = jnp.zeros((k, n_shard), dtype)
        wide = (k, n_shard, width)
        return jax.lax.fori_loop(
            0, k * n_blocks, put,
            (jnp.zeros(wide, jnp.int32), jnp.zeros(wide, dtype), rows, rows,
             rows))

    cols, vals, labels, mask, sq_norms = jax.jit(gen)(
        jax.random.key(seed), jnp.asarray(sizes, dtype=jnp.int32))
    return ShardedDataset(layout="sparse", n=n, num_features=d,
                          counts=sizes.astype(np.int64), labels=labels,
                          mask=mask, sq_norms=sq_norms, sp_indices=cols,
                          sp_values=vals)
