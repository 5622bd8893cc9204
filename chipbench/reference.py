"""The plain reference: the L2-regularised linear classifier, written out.

Independent of ``cocoa_tpu.evals.objectives``, ``ops/losses.py`` and every
kernel: for a returned pair (w, alpha) it recomputes, in straight
``jax.numpy`` float32,

    primal  P(w)     = (1/n) sum_i loss(y_i x_i.w) + (lam/2) |w|^2
    dual    D(alpha) = (1/n) sum_i -loss*(-alpha_i) - (lam/2) |w(alpha)|^2
    w(alpha)         = (1/(lam n)) sum_i y_i alpha_i x_i

    hinge     loss(z) = max(0, 1 - z)      -loss*(-a) = a
    logistic  loss(z) = log(1 + e^-z)      -loss*(-a) = -a ln a - (1-a) ln(1-a)

from the program's own dense (K, n_shard, d) rows (a cell with another
layout brings a reference of its own).  Row dots are multiply-and-sum on
the vector unit, never a matmul, so no bf16 pass can enter
(``jax.default_matmul_precision("highest")`` is set all the same); every
device reduction stays inside one shard, and the K partial results are
added on the host in float64 — on a mesh nothing here crosses chips, and
no temporary is larger than a (K, d) block.
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


@functools.partial(jax.jit, static_argnums=0)
def _parts(loss, x, y, mask, w, alpha):
    margins = jnp.sum(x * w, axis=-1)                       # (K, n_shard)
    primal, dual = _losses(loss, y * margins, alpha)
    coef = y * alpha * mask
    return (jnp.sum(primal * mask, axis=1), jnp.sum(dual * mask, axis=1),
            jnp.sum(x * coef[..., None], axis=1))           # (K,),(K,),(K,d)


def recompute(ds, w, alpha, lam: float, loss: str = "hinge") -> dict:
    """Objectives and w(alpha) on dataset ``ds`` (a dense
    ``ShardedDataset``, read as plain arrays)."""
    if ds.layout != "dense":
        raise ValueError(f"the plain reference reads dense rows, not the "
                         f"{ds.layout} layout")
    with jax.default_matmul_precision("highest"):
        parts = _parts(loss, ds.X, ds.labels, ds.mask, w, alpha)
    psum, asum, wparts = (np.asarray(p, np.float64) for p in parts)
    w64, a_host = np.asarray(w, np.float64), np.asarray(alpha)
    w_ref = wparts.sum(axis=0) / (lam * ds.n)
    primal = float(psum.sum() / ds.n + 0.5 * lam * (w64 @ w64))
    dual = float(asum.sum() / ds.n - 0.5 * lam * (w_ref @ w_ref))
    return dict(primal=primal, dual=dual, gap=primal - dual,
                w_err=float(np.abs(w64 - w_ref).max()),
                w_scale=float(np.abs(w_ref).max()),
                alpha_min=float(a_host.min()), alpha_max=float(a_host.max()))
