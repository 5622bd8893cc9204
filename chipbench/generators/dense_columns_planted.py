"""Dense unit-row data with a planted sparse regressor, made on the device
by COLUMNS: the design matrix of an L1 (lasso / elastic net) deployment,
whose shards are blocks of A's columns and whose shared vector is the
residual r = A x - b.

The configuration's sizes are in the solver's view (``n`` coordinates =
A's columns, ``d`` = the shared vector's length = A's rows): A is d x n.
One jitted call from the seed makes A^T, (n, d): unit-normal entries, every
ROW of A scaled to unit length (``dense_planted``'s law; a column's squared
norm is then ~ d / n), and the target

    b = sign(A x*) with ``flip`` of the signs flipped,

x* with ``support`` coordinates of equal magnitude, random sign and place:
equal, so that lambda_max = |A^T b|_inf and the number of rounds to a
relative gap do not hang on a seed's largest draw (a support coordinate's
a_j . b is ~ sqrt(2 / pi) (1 - 2 flip) d / sqrt(n support)).

The rounds hang on a second largest draw, which ``draws`` takes out.  The
lasso's certificate scales the dual point by lambda / |A^T r|_inf, so at
an iterate whose support is x*'s its gap reads |x|_1 (max_j e_j - mean_j
e_j), e_j = |a_j . r| - lambda: how far the WORST support column lags the
average one.  With equal magnitudes a column's lag follows the pull of
the other support columns on it,

    f_j = s_j sum_{k != j} s_k a_j . a_k / |a_j|^2        (s = sign x*),

and max_j (mean f - f_j) is the largest of ``support`` nearly normal
draws: over seeds it spreads the gap by 15% (one s.d.) at every
evaluation, while a job's gap only falls by 0.53 from one evaluation to
the next, so a fifth of all seeds need one evaluation more than the rest
(PERF.md section 6, PR 34).  So the seed draws ``draws`` supports, sign
and place both, and plants the one whose worst column lags least: every
candidate is a uniform draw, and which is kept is read from A alone, by
no solver's arithmetic.  ``draws`` = 1 keeps the first.

The column
shards are the program's own builder's (``data/columns.shard_dense_columns``:
no host copy, no CSR); the target rides the dataset.  Because the plain
reference (``reference_lasso.py``) then reads those shards, what the
builder laid out is held here, once, against the arrays it was handed
(:func:`layout_faults`): a wrong block, a shifted column or padding that is
not zero would otherwise read the same on both sides of the audit.  The
seed is an argument of the jitted call, so every seed runs the one
compiled program.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from cocoa_tpu.data.columns import shard_dense_columns


def worst_lag(gram, place, sign):
    """max_j (mean f - f_j) of one candidate support (module docstring):
    ``gram`` is A^T A, ``place`` the support's columns, ``sign`` x*'s
    signs there."""
    g = gram[place][:, place]
    pull = sign * (g @ sign) / jnp.diagonal(g) - 1.0    # k = j adds 1
    return jnp.mean(pull) - jnp.min(pull)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def columns_and_target(key, n: int, d: int, support: int, flip: float,
                       draws: int = 1):
    """``(A^T (n, d), b (d,), x* (n,))`` from one key."""
    k_a, k_place, k_sign, k_flip = jax.random.split(key, 4)
    a_t = jax.random.normal(k_a, (n, d), dtype=jnp.float32)
    a_t = a_t / jnp.linalg.norm(a_t, axis=0, keepdims=True)     # unit ROWS
    places = jax.vmap(lambda k: jax.random.permutation(k, n)[:support])(
        jax.random.split(k_place, draws))
    signs = jnp.where(jax.random.bernoulli(k_sign, 0.5, (draws, support)),
                      1.0, -1.0).astype(jnp.float32)
    kept = 0
    if draws > 1:
        gram = jnp.dot(a_t, a_t.T, precision=jax.lax.Precision.HIGHEST)
        kept = jnp.argmin(jax.vmap(worst_lag, (None, 0, 0))(
            gram, places, signs))
    x_star = jnp.zeros((n,), jnp.float32).at[places[kept]].set(signs[kept])
    # multiply-and-sum on the vector unit: no matmul pass rounds A
    y = jnp.where(jnp.sum(a_t * x_star[:, None], axis=0) >= 0, 1.0, -1.0)
    flips = jax.random.bernoulli(k_flip, flip, (d,))
    return a_t, jnp.where(flips, -y, y), x_star


@jax.jit
def _faults(a_t, b, X, mask, target):
    """Entries of the shards that are not what the generator made, counted
    in one program: shard s holds rows lo..lo+m of ``a_t`` to the bit (m =
    n/K, one more in the first n mod K shards), every other entry of its
    block is zero, the mask is one on exactly those m slots, the target is
    b and then zeros.  Slices of static bounds compared where they lie:
    nothing of A's size is copied."""
    n, d = a_t.shape
    k, d_shard, _ = X.shape
    faults = jnp.sum(target[:d] != b) + jnp.sum(target[d:] != 0)
    for s in range(k):
        m, lo = n // k + (s < n % k), s * (n // k) + min(s, n % k)
        faults += (jnp.sum(X[s, :m, :d] != a_t[lo:lo + m])
                   + jnp.sum(X[s, m:, :] != 0) + jnp.sum(X[s, :m, d:] != 0)
                   + jnp.sum(mask[s] != (jnp.arange(d_shard) < m)))
    return faults


def layout_faults(a_t, b, ds) -> int:
    """How many entries of the program's column shards, mask and target
    differ from the generator's ``(a_t, b)``; -1 where the shapes do."""
    n, d = a_t.shape
    longest = -(-n // ds.k)                 # columns in the fullest shard
    want = (ds.k, -(-longest // 16) * 16)   # padded to whole sublane tiles
    if ds.X.shape[:2] != want or ds.X.shape[2] < d or \
            ds.target.shape != ds.X.shape[2:] or ds.mask.shape != want:
        return -1
    return int(_faults(a_t, b, ds.X, ds.mask, ds.target))


def make(config: dict, seed: int, mesh=None):
    if mesh is not None:
        raise ValueError("dense_columns_planted makes one chip's columns: "
                         "a cell across chips brings a generator that "
                         "makes each device's shards where they live")
    args = config.get("generator_args", {})
    a_t, b, _ = columns_and_target(
        jax.random.key(seed), config["n"], config["d"],
        args.get("support", 100), args.get("flip", 0.02),
        args.get("support_draws", 1))
    ds = shard_dense_columns(a_t, b, config["num_splits"],
                             dtype=jnp.dtype(config.get("dtype",
                                                        "float32")))
    faults = layout_faults(a_t, b, ds)
    if faults:
        raise RuntimeError(
            f"the program's column shards are not the generator's columns: "
            f"{faults} entries of X, mask or target differ (-1: the shapes)")
    return ds
