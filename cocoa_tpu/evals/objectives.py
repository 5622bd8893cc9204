"""Objectives and the duality-gap convergence certificate.

Math from OptUtils.scala:57-98:

- hinge loss           max(1 − y·(x·w), 0)                      (:57-61)
- primal objective     avg hinge + (λ/2)‖w‖²                    (:73-75)
- dual objective       −(λ/2)‖w‖² + Σα/n                        (:80-84)
- duality gap          primal − dual                            (:89-91)
- classification error mean over examples of [y·(x·w) ≤ 0]      (:95-98)

These cost a full data pass (the reference gates them to every ``debugIter``
rounds — CoCoA.scala:51); same policy here.  Each reduction runs through the
same fan-out machinery as the solvers (parallel/fanout.py): per-shard partial
sums, one scalar ``lax.psum`` — the TPU equivalent of
``data.map(...).reduce(_ + _)`` (OptUtils.scala:67).  Padded rows are
excluded via the mask.  The dp mesh is inferred from array placement, so the
same code serves the multi-device and single-chip paths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from cocoa_tpu.data.sharding import ShardedDataset
from cocoa_tpu.ops import losses
from cocoa_tpu.ops.rows import eval_margins
from cocoa_tpu.parallel.fanout import fanout, mesh_of
from cocoa_tpu.telemetry.tracing import SCOPE_EVAL


@functools.lru_cache(maxsize=None)
def _loss_sum_fn(mesh, loss, smoothing):
    def per_shard(w, shard):
        vals = losses.primal(loss, shard["labels"] * eval_margins(w, shard),
                             smoothing=smoothing)
        return (jnp.sum(vals * shard["mask"]),)

    @jax.jit
    def f(w, shard_arrays):
        (total,) = fanout(per_shard, mesh, w, shard_arrays)
        return total

    return f


@functools.lru_cache(maxsize=None)
def _dual_sum_fn(mesh, loss, smoothing):
    def per_shard(w, alpha_k, shard):
        return (jnp.sum(losses.dual_term(loss, alpha_k, smoothing=smoothing)
                        * shard["mask"]),)

    @jax.jit
    def f(w, alpha, shard_arrays):
        (total,) = fanout(per_shard, mesh, w, alpha, shard_arrays)
        return total

    return f


@functools.lru_cache(maxsize=None)
def _error_sum_fn(mesh):
    def per_shard(w, shard):
        correct = (eval_margins(w, shard) * shard["labels"]) > 0.0
        return (jnp.sum(jnp.where(correct, 0.0, 1.0) * shard["mask"]),)

    @jax.jit
    def f(w, shard_arrays):
        (total,) = fanout(per_shard, mesh, w, shard_arrays)
        return total

    return f


@jax.named_scope(SCOPE_EVAL)
def eval_metrics(
    w, alpha, shard_arrays, lam, n, mesh=None,
    test_shard_arrays=None, test_n: int = 0,
    loss: str = "hinge", smoothing: float = 1.0,
    inv_n=None, classes: int = 0,
):
    """Jit-traceable fused evaluation: (primal, gap, test_error) as one
    stacked device array — a single fan-out over the training data (plus one
    over the test data when given) and ZERO host syncs.  The building block
    for both the fused host-side ``evaluate`` (one fetch per eval instead of
    four) and the fully device-resident driver (solvers/base.py
    ``drive_on_device``), where a blocking host round trip costs far more
    than the eval compute itself.

    ``test_error`` is NaN when no test set is given; ``gap`` is NaN for
    primal-only solvers (``alpha=None`` — SGD / DistGD have no dual state).

    ``inv_n`` (the fleet path, solvers/fleet.py): a precomputed — possibly
    TRACED, per-tenant — 1/n scalar replacing the ``/ n`` division.  The
    static path's jit folds division by the constant n into one f32
    reciprocal multiply; a traced n cannot be folded, so the fleet passes
    the same f32 reciprocal explicitly — which is what keeps a T=1 fleet
    eval bit-identical to the solo certificate (tests/test_fleet.py).

    ``classes`` = T: a one-vs-rest job whose class axis rides the lanes
    (w (d, R, 128): sparse rows, ops/pallas_sparse_lanes.py, and dense rows
    whose T models outgrew the sublane kernel, ops/block_lanes.py) says how
    many of its T_pad lanes are models; a dense one's w (T, d) says so
    itself.
    """
    if w.ndim == 3:
        return _eval_metrics_lanes(
            w, alpha, shard_arrays, lam, n, mesh, test_shard_arrays,
            test_n, loss, smoothing, classes)
    if w.ndim == 2:
        return _eval_metrics_classes(
            w, alpha, shard_arrays, lam, n, mesh, test_shard_arrays,
            test_n, loss, smoothing)

    def over_n(x):
        return x / n if inv_n is None else x * inv_n

    w_norm_sq = w @ w
    if alpha is not None:

        def per_shard(w, alpha_k, shard):
            margins = eval_margins(w, shard)
            vals = losses.primal(loss, shard["labels"] * margins,
                                 smoothing=smoothing)
            dual_vals = losses.dual_term(loss, alpha_k, smoothing=smoothing)
            mask = shard["mask"]
            return (jnp.stack([jnp.sum(vals * mask),
                               jnp.sum(dual_vals * mask)]),)

        (sums,) = fanout(per_shard, mesh, w, alpha, shard_arrays)
        primal = over_n(sums[0]) + 0.5 * lam * w_norm_sq
        dual = -0.5 * lam * w_norm_sq + over_n(sums[1])
        gap = primal - dual
    else:

        def per_shard(w, shard):
            margins = eval_margins(w, shard)
            vals = losses.primal(loss, shard["labels"] * margins,
                                 smoothing=smoothing)
            return (jnp.sum(vals * shard["mask"]),)

        (loss_sum,) = fanout(per_shard, mesh, w, shard_arrays)
        primal = over_n(loss_sum) + 0.5 * lam * w_norm_sq
        gap = jnp.asarray(jnp.nan, primal.dtype)

    if test_shard_arrays is not None:

        def per_test_shard(w, shard):
            wrong = (eval_margins(w, shard) * shard["labels"]) <= 0.0
            return (jnp.sum(jnp.where(wrong, 1.0, 0.0) * shard["mask"]),)

        (errors,) = fanout(per_test_shard, mesh, w, test_shard_arrays)
        test_err = errors / test_n
    else:
        test_err = jnp.asarray(jnp.nan, primal.dtype)
    return jnp.stack([primal, gap, test_err])


def _class_margins(w, X):
    """X . W^T for every row and every class, (T, K, n_shard): one pass
    over the rows for all T models.  float32 at ``highest`` precision: the
    default on a TPU is one bfloat16 pass, which moves a margin by ~1e-3
    and the certificate with it (PERF.md section 6, PR 38)."""
    return jnp.einsum("td,knd->tkn", w, X,
                      precision=jax.lax.Precision.HIGHEST)


def _eval_metrics_classes(w, alpha, shard_arrays, lam, n, mesh,
                          test_shard_arrays, test_n, loss, smoothing):
    """:func:`eval_metrics` of a one-vs-rest job: w (T, d), alpha
    (T, K, n_shard) over rows that carry class ids.  T primal / dual / gap
    values from ONE pass over the rows; returned as ``[primal, gap,
    test_error, gap_0 .. gap_{T-1}]`` with ``gap`` the WORST class's (the
    stop rule, the budget and the divergence watch read it: a job ends
    when every class holds its certificate) and ``primal`` that class's.
    The test error is the multi-class one: a row counts as wrong where the
    class of the largest margin is not its own."""
    if mesh is not None or "X" not in shard_arrays:
        raise ValueError("the class axis is evaluated on dense rows on one "
                         "chip (docs/DESIGN.md, one-vs-rest)")
    from cocoa_tpu.data.sharding import class_labels

    t = w.shape[0]
    ids = jnp.arange(t, dtype=shard_arrays["classes"].dtype)[:, None, None]
    mask = shard_arrays["mask"]
    y = class_labels(shard_arrays["classes"], mask, ids)     # (T, K, n_shard)
    margins = _class_margins(w, shard_arrays["X"])
    vals = losses.primal(loss, y * margins, smoothing=smoothing)
    w_norm_sq = jnp.sum(w * w, axis=1)
    primal = jnp.sum(vals * mask, axis=(1, 2)) / n + 0.5 * lam * w_norm_sq
    dual_vals = losses.dual_term(loss, alpha, smoothing=smoothing)
    dual = jnp.sum(dual_vals * mask, axis=(1, 2)) / n - 0.5 * lam * w_norm_sq
    gaps = primal - dual
    worst = jnp.argmax(gaps)
    if test_shard_arrays is not None:
        guess = jnp.argmax(_class_margins(w, test_shard_arrays["X"]), axis=0)
        wrong = (guess != test_shard_arrays["classes"])
        test_err = jnp.sum(wrong * test_shard_arrays["mask"]) / test_n
    else:
        test_err = jnp.asarray(jnp.nan, primal.dtype)
    head = jnp.stack([primal[worst], gaps[worst],
                      test_err.astype(primal.dtype)])
    return jnp.concatenate([head, gaps])


def _eval_metrics_lanes(w, alpha, shard_arrays, lam, n, mesh,
                        test_shard_arrays, test_n, loss, smoothing, classes):
    """:func:`_eval_metrics_classes` with the class axis on the lanes: w
    (d, R, 128), alpha (K, n_shard, R, 128), ``classes`` = T of the R·128
    lanes models, over sparse rows that carry label sets (a padded-CSR
    rectangle or a stream) or over dense rows that carry one class id each.
    One blocked pass over the rows, a shard after another, gives all T
    primal / dual / gap values — on sparse rows ops/rows.class_loss_sums (a
    W row a nonzero, T margins a row; on a stream
    ops/pallas_longrows_lanes.stream_class_loss_sums, the blocks cut by
    the rows' starts), on dense rows ops/rows.dense_class_loss_sums (a
    block's margins
    one product at ``highest`` precision; a block is a whole shard until
    its temporaries would pass ops/rows.DENSE_CLASS_BLOCK_BYTES) — and the
    same vector comes back, ``[primal, gap, test_error, gap_0 ..
    gap_{T-1}]`` on the worst class.  The test error of a multi-label set
    is label-wise, the share of (row, class) pairs with the wrong sign; of
    a dense multi-class set it is :func:`_eval_metrics_classes`'s, the
    share of rows whose largest margin is not their own class's."""
    dense = "X" in shard_arrays
    if (mesh is not None or classes < 1
            or not dense and "sp_indices" not in shard_arrays):
        raise ValueError("the class axis on the lanes is evaluated on "
                         "dense rows or sparse rows (a padded-CSR "
                         "rectangle, a stream) on one chip, T stated "
                         "(docs/DESIGN.md, one-vs-rest)")
    from cocoa_tpu.data.sharding import class_vector
    from cocoa_tpu.ops.rows import class_loss_sums, dense_class_loss_sums

    def shard_sums(arrays, alpha):
        """((2+, T) per-class sums, the wrong answers' count)."""
        if dense:
            sums, wrong = dense_class_loss_sums(w, alpha, arrays, classes,
                                                loss, smoothing)
            return class_vector(sums, classes), wrong
        sparse_sums = class_loss_sums
        if "sp_row_ptr" in arrays:
            # rows kept as a stream: the blocks are cut by the rows' starts
            from cocoa_tpu.ops.pallas_longrows_lanes import (
                stream_class_loss_sums as sparse_sums)
        sums = class_vector(sparse_sums(w, alpha, arrays, classes, loss,
                                        smoothing), classes)       # (3, T)
        return sums, sums[2].sum()

    sums, _ = shard_sums(shard_arrays, alpha)
    w_norm_sq = class_vector(jnp.sum(w * w, axis=0), classes)
    primal = sums[0] / n + 0.5 * lam * w_norm_sq
    gaps = primal - (sums[1] / n - 0.5 * lam * w_norm_sq)
    worst = jnp.argmax(gaps)
    if test_shard_arrays is not None:
        # (answers: a (row, class) pair on sparse rows, a row on dense)
        test_err = (shard_sums(test_shard_arrays, None)[1]
                    / (test_n * (1 if dense else classes)))
    else:
        test_err = jnp.asarray(jnp.nan, primal.dtype)
    head = jnp.stack([primal[worst], gaps[worst],
                      test_err.astype(primal.dtype)])
    return jnp.concatenate([head, gaps])


@functools.lru_cache(maxsize=None)
def _eval_metrics_fn(mesh, lam, n, test_n, loss, smoothing, classes=0):
    # None arguments (no dual state / no test set) are empty pytrees — jit
    # specializes on the pytree structure, no separate static flags needed
    @jax.jit
    def f(w, alpha, shard_arrays, test_shard_arrays):
        return eval_metrics(
            w, alpha, shard_arrays, lam, n, mesh=mesh,
            test_shard_arrays=test_shard_arrays, test_n=test_n,
            loss=loss, smoothing=smoothing, classes=classes,
        )

    return f


def evaluate(ds: ShardedDataset, w, alpha, lam, test_ds=None,
             loss: str = "hinge", smoothing: float = 1.0):
    """Fused host-side eval: returns (primal, gap_or_None,
    test_error_or_None) with exactly ONE device→host transfer (each fetch
    is a blocking round trip; the unfused path pays four).
    ``alpha=None`` for primal-only solvers → gap is None.  A one-vs-rest
    job (w (T, d)) gets a fourth element, the list of every class's gap;
    the first three are then the worst class's (``eval_metrics``)."""
    import numpy as np

    from cocoa_tpu.analysis import sanitize

    f = _eval_metrics_fn(
        mesh_of(ds.labels), float(lam), ds.n,
        test_ds.n if test_ds is not None else 0,
        loss, float(smoothing),
        # (the class axis on the lanes: T of the T_pad lanes are models)
        *((ds.num_classes,) if w.ndim == 3 else ()),
    )
    out = f(
        w, alpha, ds.shard_arrays(),
        None if test_ds is None else test_ds.shard_arrays(),
    )
    # the one sanctioned device→host fetch of the host-stepped eval
    # cadence (the transfer-guard sanitizer disallows any other)
    with sanitize.intended_fetch("eval_fetch"):
        out = np.asarray(out)
        primal, gap, test_err = (float(v) for v in out[:3])
    return (
        primal,
        None if np.isnan(gap) else gap,
        None if np.isnan(test_err) else test_err,
        # a one-vs-rest job: every class's gap, past the three
        *((out[3:].tolist(),) if out.shape[0] > 3 else ()),
    )


def primal_objective(ds: ShardedDataset, w, lam, loss: str = "hinge",
                     smoothing: float = 1.0) -> float:
    loss_sum = _loss_sum_fn(mesh_of(ds.labels), loss, float(smoothing))(
        w, ds.shard_arrays()
    )
    return float(loss_sum) / ds.n + 0.5 * lam * float(w @ w)


def dual_objective(ds: ShardedDataset, w, alpha, lam, loss: str = "hinge",
                   smoothing: float = 1.0) -> float:
    """alpha: (K, n_shard) sharded dual variables."""
    dual_sum = _dual_sum_fn(mesh_of(ds.labels), loss, float(smoothing))(
        w, alpha, ds.shard_arrays()
    )
    return -0.5 * lam * float(w @ w) + float(dual_sum) / ds.n


def duality_gap(ds: ShardedDataset, w, alpha, lam, loss: str = "hinge",
                smoothing: float = 1.0) -> float:
    return (primal_objective(ds, w, lam, loss, smoothing)
            - dual_objective(ds, w, alpha, lam, loss, smoothing))


def classification_error(ds: ShardedDataset, w) -> float:
    errors = _error_sum_fn(mesh_of(ds.labels))(w, ds.shard_arrays())
    return float(errors) / ds.n
