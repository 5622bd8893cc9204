"""The plain reference for one-vs-rest: T L2-regularised linear classifiers
over one set of rows, written out.

Independent of ``cocoa_tpu.evals.objectives``, ``ops/losses.py`` and every
kernel: from the returned alpha (T, K, n_shard) and the rows' class ids
ALONE it recomputes, class by class and shard by shard, in straight
``jax.numpy`` float32,

    y_ti        = +1 where class_i = t, else -1
    w_t(alpha_t) = (1/(lam n)) sum_i alpha_ti y_ti x_i
    P_t(w_t)    = (1/n) sum_i loss(y_ti x_i . w_t) + (lam/2) |w_t|^2
    D_t(alpha_t) = (1/n) sum_i -loss*(-alpha_ti) - (lam/2) |w_t(alpha_t)|^2

(the losses of ``reference.py``) with P at the RETURNED w_t, as the other
cells' references take it.  Row dots and the sums over rows are
multiply-and-sum on the vector unit, never a matmul, so no bf16 pass can
enter (``jax.default_matmul_precision("highest")`` is set all the same);
a call touches one shard and one class, so no temporary is larger than a
shard, and the K partial sums are added on the host in float64.

Two counter-readings ride along, for the check's limits to sit between
(checks/certified_gap_ovr.py): every class's gap with the margins as ONE
bfloat16 pass of the matrix unit would take them — rows and w rounded once
to bfloat16, on the bits, products and sums in float32 (``gaps_bf16``) —
and the returned w rounded once to bfloat16 against w(alpha)
(``w_err_bf16``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.scipy.special import xlogy


def _losses(loss: str, z, alpha):
    """(loss(z), -loss*(-alpha)) elementwise."""
    if loss == "hinge":
        return jnp.maximum(0.0, 1.0 - z), alpha
    if loss == "logistic":
        a = jnp.clip(alpha, 0.0, 1.0)
        return jnp.logaddexp(0.0, -z), -(xlogy(a, a) + xlogy(1 - a, 1 - a))
    raise ValueError(f"the plain reference has no loss {loss!r}")


def _as_bf16(a):
    """float32 values rounded once to bfloat16 (to nearest, ties to even),
    kept as float32: on the bits, since a cast there and back inside a
    jitted program may be elided (reference_lasso.py)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)) & jnp.uint32(
        0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


@functools.partial(jax.jit, static_argnums=0)
def _class_parts(loss, x, classes, mask, t, w_t, alpha_t):
    """One shard, one class: (primal loss sum, the same through a bf16
    pass, dual sum, sum_i alpha_ti y_ti x_i)."""
    y = jnp.where(classes == t, 1.0, -1.0) * mask
    primal, dual = _losses(loss, y * jnp.sum(x * w_t, axis=-1), alpha_t)
    primal_bf16, _ = _losses(
        loss, y * jnp.sum(_as_bf16(x) * _as_bf16(w_t), axis=-1), alpha_t)
    return (jnp.sum(primal * mask), jnp.sum(primal_bf16 * mask),
            jnp.sum(dual * mask),
            jnp.sum(x * (y * alpha_t * mask)[:, None], axis=0))


def recompute(ds, w, alpha, lam: float, loss: str = "hinge") -> dict:
    """Every class's objectives and w_t(alpha_t) on ``ds`` (a dense
    ``ShardedDataset`` that carries class ids, read as plain arrays).
    Lists are by class id."""
    if ds.layout != "dense" or ds.classes is None:
        raise ValueError("the one-vs-rest reference reads dense rows that "
                         "carry class ids")
    t_count = ds.num_classes
    w32 = jnp.asarray(w, jnp.float32)
    psum, psum_bf16, asum = (np.zeros(t_count) for _ in range(3))
    wsum = np.zeros((t_count, ds.num_features))
    with jax.default_matmul_precision("highest"):
        for s in range(ds.k):
            x, cls, mask = ds.X[s], ds.classes[s], ds.mask[s]
            for t in range(t_count):
                p, pb, a, wp = _class_parts(loss, x, cls, mask, t, w32[t],
                                            alpha[t, s])
                psum[t] += float(p)
                psum_bf16[t] += float(pb)
                asum[t] += float(a)
                wsum[t] += np.asarray(wp, np.float64)
    w64 = np.asarray(w, np.float64)
    w_ref = wsum / (lam * ds.n)
    reg = 0.5 * lam * np.sum(w64 * w64, axis=1)
    dual = asum / ds.n - 0.5 * lam * np.sum(w_ref * w_ref, axis=1)
    primal = psum / ds.n + reg
    scale = np.maximum(1.0, np.abs(w_ref).max(axis=1))
    w_bf16 = np.asarray(_as_bf16(w32), np.float64)
    a_host = np.asarray(alpha)
    return dict(
        primal=primal.tolist(), dual=dual.tolist(),
        gaps=(primal - dual).tolist(),
        gaps_bf16=(psum_bf16 / ds.n + reg - dual).tolist(),
        w_err=(np.abs(w64 - w_ref).max(axis=1) / scale).tolist(),
        w_err_bf16=(np.abs(w_bf16 - w_ref).max(axis=1) / scale).tolist(),
        w_scale=np.abs(w_ref).max(axis=1).tolist(),
        alpha_min=float(a_host.min()), alpha_max=float(a_host.max()),
        class_share=(np.bincount(
            np.asarray(ds.classes)[np.asarray(ds.mask) > 0],
            minlength=t_count) / ds.n).tolist())
