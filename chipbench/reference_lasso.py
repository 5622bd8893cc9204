"""The plain reference for the L1 family: the lasso, written out.

    P(x) = 1/2 |A x - b|^2 + lam |x|_1
    D(u) = -1/2 |u|^2 - u . b          for |A^T u|_inf <= lam
    u    = s r,  r = A x - b,  s = min(1, lam / |A^T r|_inf)

Independent of ``cocoa_tpu.solvers.prox_cocoa``, ``ops/rows.py`` and every
kernel: from the job's returned x ALONE, on the column shards (K, d_shard,
n) read as plain arrays, it recomputes r, P, A^T r, the dual-feasible
scaling, D and the gap in straight ``jax.numpy`` float32.  The shards are
the program's builder's; the generator holds them, once, against the
columns and target it made (``generators/dense_columns_planted.py``
``layout_faults``), so they are the generator's data by the time they are
read here.
Both products are multiply-and-sum on the vector unit, never a matmul, so
no bf16 pass can enter (``jax.default_matmul_precision("highest")`` is set
all the same); it goes a shard's column block at a time, so no temporary is
larger than a block, and the K partial sums of A x, the norms and the dual
are added on the host in float64.

Two counter-readings ride along, for the audit's limits to sit under
(checks/certified_gap_lasso.py): the same recomputation from x rounded once
to bfloat16 (``r_ref_bf16``), and A^T r as one bf16 pass of the matrix unit
would take it, both operands rounded once to bfloat16 and the products
added in float32 (``corr_max_bf16`` and the gap it gives, ``gap_bf16``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


@jax.jit
def _a_x(cols, x):
    """One shard's share of A x: (d_shard, n), (d_shard,) -> (n,)."""
    return jnp.sum(cols * x[:, None], axis=0)


@jax.jit
def _at_r(cols, r):
    """One shard's A^T r: (d_shard, n), (n,) -> (d_shard,)."""
    return jnp.sum(cols * r[None, :], axis=1)


def _as_bf16(a):
    """float32 values rounded once to bfloat16 (to nearest, ties to even),
    kept as float32.  On the bits, not through ``astype``: inside a jitted
    program the compiler may keep the excess precision of a cast there and
    back, and the counter-reading would read what the float32 path reads
    (it did: my chip run, PR 34)."""
    bits = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bits = (bits + jnp.uint32(0x7FFF) + ((bits >> 16) & 1)) & jnp.uint32(
        0xFFFF0000)
    return jax.lax.bitcast_convert_type(bits, jnp.float32)


@jax.jit
def _at_r_bf16(cols, r):
    """The same with both operands rounded once to bfloat16 (products and
    sums in float32): what a one-pass matmul at default precision reads."""
    return jnp.sum(_as_bf16(cols) * _as_bf16(r)[None, :], axis=1)


def _residual(ds, x, b64):
    parts = [np.asarray(_a_x(ds.X[s], x[s]), np.float64)
             for s in range(ds.k)]
    return np.sum(parts, axis=0) - b64


def _gap(r64, b64, l1_norm, corr_max, lam):
    rr = float(r64 @ r64)
    primal = 0.5 * rr + lam * l1_norm
    s = min(1.0, lam / max(corr_max, 1e-30))
    dual = -0.5 * s * s * rr - s * float(r64 @ b64)
    return primal, dual, s


def recompute(ds, x, lam: float) -> dict:
    """Objectives of the lasso at ``x`` (K, d_shard) on the column shards
    ``ds`` (a dense ``ShardedDataset`` that carries its target)."""
    if ds.layout != "dense" or ds.target is None:
        raise ValueError("the lasso reference reads dense column shards "
                         "that carry their target")
    x = jnp.asarray(x, jnp.float32)
    b64 = np.asarray(ds.target, np.float64)
    mask = np.asarray(ds.mask, np.float64)
    x64 = np.asarray(x, np.float64)
    with jax.default_matmul_precision("highest"):
        r64 = _residual(ds, x, b64)
        r_bf16 = _residual(ds, _as_bf16(x), b64)
        r32 = jnp.asarray(r64, jnp.float32)
        corr, corr_bf16 = (
            np.abs(np.stack([np.asarray(f(ds.X[s], r32), np.float64)
                             for s in range(ds.k)])) * mask
            for f in (_at_r, _at_r_bf16))
    l1_norm = float(np.abs(x64 * mask).sum())
    primal, dual, s = _gap(r64, b64, l1_norm, float(corr.max()), lam)
    _, dual_bf16, _ = _gap(r64, b64, l1_norm, float(corr_bf16.max()), lam)
    return dict(primal=primal, dual=dual, gap=primal - dual, scaling=s,
                corr_max=float(corr.max()), r_ref=r64, r_ref_bf16=r_bf16,
                corr_max_bf16=float(corr_bf16.max()),
                gap_bf16=primal - dual_bf16,
                x_nnz=int(np.count_nonzero(x64 * mask)),
                x_on_padding=int(np.count_nonzero(x64 * (1.0 - mask))),
                x_scale=float(np.abs(x64).max()))
