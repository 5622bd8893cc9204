"""Synthetic benchmark datasets — epsilon-like dense, rcv1-like sparse.

The north-star baseline configs (BASELINE.md, /root/repo/BASELINE.json) are
LIBSVM's epsilon (400K x 2000, dense, unit-normalized rows) and rcv1.binary
(~20K train x 47236, sparse ~0.16% density, tf-idf values).  Neither file can
be downloaded in this environment, so these generators produce
shape-and-statistics-faithful stand-ins from a fixed seed: a planted
ground-truth separator with label-flip noise, so every solver has a
well-conditioned problem whose duality gap actually closes.

Two paths:

- :func:`synth_dense_sharded` generates the dataset *on device, already
  sharded* — a (K, n_shard, d) normal matrix with unit-normalized rows never
  exists on the host at all.  At epsilon scale that skips a 3.2 GB
  host->device transfer and is the TPU-native way to build a benchmark
  input.
- :func:`synth_dense` / :func:`synth_sparse` build host-side
  :class:`LibsvmData` (tests, small runs, parser round-trips via
  :func:`write_libsvm`).

The reference has no synthetic-data story (its only data is the bundled
``data/small_*.dat``, README.md:19-22); this is net-new capability required
to *generate* the baseline numbers the reference never published
(SURVEY.md #6).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from cocoa_tpu.data.libsvm import LibsvmData
from cocoa_tpu.data.sharding import ShardedDataset, pad_rows, split_sizes
from cocoa_tpu.parallel import mesh as mesh_lib


def _plant_labels(margins: np.ndarray, flip: float, rng) -> np.ndarray:
    """sign(x . w*) labels with probability-``flip`` label noise, in {-1,+1}."""
    y = np.where(margins >= 0, 1.0, -1.0)
    if flip > 0:
        y = np.where(rng.random(y.shape) < flip, -y, y)
    return y


def synth_dense(
    n: int, d: int, *, seed: int = 0, flip: float = 0.02
) -> LibsvmData:
    """Host-side epsilon-like dense data as :class:`LibsvmData` (small n*d
    only — the CSR encoding of a dense matrix is deliberate here: it feeds
    the exact same ingestion path real LIBSVM files do)."""
    rng = np.random.default_rng(seed)
    X = rng.standard_normal((n, d))
    X /= np.linalg.norm(X, axis=1, keepdims=True)
    w_star = rng.standard_normal(d) / np.sqrt(d)
    y = _plant_labels(X @ w_star, flip, rng)
    indptr = np.arange(0, (n + 1) * d, d, dtype=np.int64)
    indices = np.tile(np.arange(d, dtype=np.int32), n)
    return LibsvmData(
        labels=y.astype(np.float64),
        indptr=indptr,
        indices=indices,
        values=X.reshape(-1).astype(np.float64),
        num_features=d,
    )


def synth_sparse(
    n: int,
    d: int,
    *,
    nnz_mean: int = 75,
    seed: int = 0,
    flip: float = 0.02,
    nnz_sigma: float = 0.7,
) -> LibsvmData:
    """rcv1-like sparse data, distribution-faithful where the kernels and
    the gap trajectory are sensitive (VERDICT r3 item 5 — round 3 was
    shape-faithful only):

    - **nnz/row ~ log-normal** with log-sd ``nnz_sigma`` and mean
      ``nnz_mean`` — document lengths are heavy-tailed (RCV1-v2's token
      counts famously so), where round 3's Poisson was nearly constant
      (sd ~sqrt(75) vs the real spread of ~0.7 in the log).  The padded-CSR
      layout pads every row to the MAX row nnz, so this tail is exactly
      what that kernel pays for.
    - **tf-idf values**: tf = the column's repeat count within the row's
      token draws (popular columns repeat — that IS term frequency),
      value = (1 + log tf) * idf(df(col)) with Zipf column popularity
      (df ∝ 1/rank), then L2-normalized rows — RCV1-v2's published ltc
      weighting (Lewis et al. 2004), matching both the value distribution
      and the value↔popularity correlation (common words carry small
      weights) that round 3's iid log-normal values lacked.

    ``nnz_mean`` targets the post-dedup (unique terms per row) mean — the
    token draws are inflated by the empirical dedup factor at rcv1 scale.
    """
    rng = np.random.default_rng(seed)
    # column popularity ~ 1/rank: sample columns by inverse-CDF of a Zipf-ish
    # weight vector so low feature ids are hot, mimicking sorted-by-df tf-idf
    weights = 1.0 / np.arange(1, d + 1)
    probs = weights / weights.sum()
    cdf = np.cumsum(probs)
    # log-normal TOKEN counts whose post-dedup unique mean lands on
    # nnz_mean: mu = ln(mean·inflate) - sigma^2/2, inflate = the measured
    # dedup shrinkage of Zipf draws at rcv1 scale (~0.79 unique/draw)
    mu = np.log(nnz_mean * 1.27) - 0.5 * nnz_sigma ** 2
    row_nnz = np.clip(
        np.round(rng.lognormal(mu, nnz_sigma, size=n)), 1,
        min(d, 12 * nnz_mean),
    ).astype(np.int64)
    indptr = np.concatenate([[0], np.cumsum(row_nnz)])
    total = int(indptr[-1])
    cols = np.searchsorted(cdf, rng.random(total)).astype(np.int32)
    # idf against the sampling distribution itself: df(col) = n * p(col)
    idf = np.log(1.0 / np.maximum(probs, 1.0 / (50.0 * n)))
    # dedupe within each row (duplicate idx:val pairs are legal LIBSVM-wise
    # but the dense/padded layouts would sum them differently than last-wins)
    indices_list = []
    values_list = []
    w_star = rng.standard_normal(d) / np.sqrt(nnz_mean)
    labels = np.empty(n)
    out_ptr = [0]
    for i in range(n):
        c, tf = np.unique(cols[indptr[i]:indptr[i + 1]],
                          return_counts=True)
        v = (1.0 + np.log(tf)) * idf[c]
        nrm = np.linalg.norm(v)
        v = v / (nrm if nrm > 0 else 1.0)
        indices_list.append(c)
        values_list.append(v)
        out_ptr.append(out_ptr[-1] + c.size)
        labels[i] = v @ w_star[c]
    y = _plant_labels(labels, flip, rng)
    return LibsvmData(
        labels=y.astype(np.float64),
        indptr=np.asarray(out_ptr, dtype=np.int64),
        indices=np.concatenate(indices_list).astype(np.int32),
        values=np.concatenate(values_list).astype(np.float64),
        num_features=d,
    )


def write_libsvm(data: LibsvmData, path: str, precision: int = 8) -> None:
    """Serialize to LIBSVM text (1-based indices, ``+1``/``-1`` labels) —
    round-trip fodder for the parsers and for generating big on-disk
    benchmark files."""
    with open(path, "w") as f:
        for i in range(data.n):
            idx, val = data.row(i)
            lab = "+1" if data.labels[i] > 0 else "-1"
            pairs = " ".join(
                f"{j + 1}:{v:.{precision}g}" for j, v in zip(idx, val)
            )
            f.write(f"{lab} {pairs}\n" if pairs else f"{lab}\n")


def synth_dense_sharded(
    n: int,
    d: int,
    k: int,
    *,
    seed: int = 0,
    flip: float = 0.02,
    dtype=jnp.float32,
    mesh: Optional[jax.sharding.Mesh] = None,
) -> ShardedDataset:
    """Generate an epsilon-like dense dataset directly on device, already in
    the (K, n_shard, d) sharded layout of :func:`shard_dataset` — the data
    never exists on the host.  Deterministic in ``(n, d, k, seed, flip)``
    and independent of the mesh (same shard contents on 1 device or K).

    Rows are unit-normalized (as epsilon is), labels are a planted separator
    with ``flip`` label noise, padded rows are zeroed exactly as
    :func:`shard_dataset` does.
    """
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    d_pad = mesh_lib.pad_features(d, mesh)

    counts_dev = jnp.asarray(sizes, dtype=jnp.int32)
    key = jax.random.key(seed)
    k_w, k_x, k_f = jax.random.split(key, 3)

    def gen_shard(s, count):
        # per-shard fold keeps contents independent of K's device placement
        kx = jax.random.fold_in(k_x, s)
        kf = jax.random.fold_in(k_f, s)
        X = jax.random.normal(kx, (n_shard, d), dtype=jnp.float32)
        X = X / jnp.linalg.norm(X, axis=1, keepdims=True)
        w_star = jax.random.normal(k_w, (d,), dtype=jnp.float32) / np.sqrt(d)
        margins = X @ w_star
        flips = jax.random.bernoulli(kf, flip, (n_shard,))
        y = jnp.where(margins >= 0, 1.0, -1.0)
        y = jnp.where(flips, -y, y)
        m = (jnp.arange(n_shard) < count).astype(dtype)
        X = (X * m[:, None]).astype(dtype)
        if d_pad != d:
            X = jnp.pad(X, ((0, 0), (0, d_pad - d)))
        sq = jnp.sum(X * X, axis=-1)
        return X, (y.astype(dtype) * m), m, sq

    if mesh is not None:
        rows = mesh_lib.sharded_rows(mesh, extra_dims=1)
        out_shardings = (mesh_lib.x_sharding(mesh), rows, rows, rows)
        gen = jax.jit(
            jax.vmap(gen_shard), out_shardings=out_shardings
        )
    else:
        gen = jax.jit(jax.vmap(gen_shard))
    X, labels, mask, sq_norms = gen(jnp.arange(k), counts_dev)
    return ShardedDataset(
        layout="dense",
        n=n,
        num_features=d_pad,
        counts=sizes.astype(np.int64),
        labels=labels,
        mask=mask,
        sq_norms=sq_norms,
        X=X,
    )
