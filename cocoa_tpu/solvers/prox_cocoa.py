"""ProxCoCoA+ — communication-efficient L1-regularized regression (lasso /
elastic net).

No reference analogue (the reference is hinge-SVM only) — this is the
framework's follow-up-paper extension (arXiv:1512.04011 structure),
included because the reference is explicitly designed for swappable local
solvers/objectives (README.md:14, CoCoA.scala:13-14) and the L1 primal
family is the canonical "swap".

Problem:  min_x  0.5·‖A·x − b‖² + λ·‖x‖₁ (+ η/2·‖x‖²  elastic net)

Structure — the exact mirror of the dual solvers with examples↔features
swapped:

- A's **columns** are sharded (data/columns.py); worker k owns coordinate
  block x_[k] and columns A_[k].
- The replicated state is the residual r = A·x − b (an n-vector — the
  analogue of w); the shard-local state is x_[k] (the analogue of α).
- One round: each worker runs H prox coordinate-descent steps against the
  frozen r₀ with σ′-scaled reads of its accumulated Δv = A_[k]·Δx_[k]
  (exactly CoCoA+'s subproblem structure, mode="prox"), then ONE psum of
  Δv per round: r += γ·ΣΔv.  The per-step soft-threshold rule lives in
  ops/losses.py ("lasso").

Because the structure is identical, the entire SDCA-family machinery —
fast-math margins decomposition, both Pallas kernels, device-side chunked
rounds and the device-resident loop, gap-target early stop — is reused
verbatim via run_sdca_family with mode="prox" and a duality-gap
certificate for the WHOLE family (the reference's principle: every
primal-dual method certifies, OptUtils.scala:89-91 / README.md:14):

- pure lasso (η = 0): gap = P(x) − D(s·r) with the dual-feasible scaling
  s = min(1, λ/‖Aᵀr‖∞), D(u) = −½‖u‖² − uᵀb — the conjugate of λ|·| is
  the indicator of [−λ, λ], so u must be scaled into the feasible box.
- elastic net (η > 0): the l2 term smooths the conjugate —
  h(t) = λ|t| + (η/2)t² has h*(s) = ([|s| − λ]₊)²/(2η), finite
  everywhere — so the residual itself is dual-feasible and
  gap = P(x) − D(r), D(u) = −½‖u‖² − uᵀb − Σ_j ([|a_jᵀu| − λ]₊)²/(2η).
  Weak duality gives gap ≥ 0 for any x; at the optimum u* = r* makes it
  0 (validated against the NumPy oracle in tests/test_prox.py).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import ShardedDataset
from cocoa_tpu.ops.rows import shard_margins
from cocoa_tpu.parallel.fanout import fanout
from cocoa_tpu.solvers.cocoa import run_sdca_family
from cocoa_tpu.telemetry.tracing import SCOPE_EVAL


@jax.named_scope(SCOPE_EVAL)
def lasso_metrics(r, x, shard_arrays, b, l1: float, l2: float, mesh=None):
    """(primal, gap, NaN) for the elastic-net objective, as one stacked
    device array — one fan-out over the column shards (Σ|x|, Σx², the
    per-shard max |a_jᵀr| for the lasso dual-feasible scaling, and the
    Σ([|a_jᵀr| − λ]₊)² the smoothed elastic-net conjugate needs), zero
    host syncs.  The certificate is exact for both cases (module
    docstring); weak duality makes it ≥ 0 at every iterate."""
    def per_shard(rw, x_k, shard):
        m = shard["mask"]
        corr = jnp.abs(shard_margins(rw, shard)) * m
        excess = jnp.maximum(corr - l1, 0.0)
        sums = jnp.stack([
            jnp.sum(jnp.abs(x_k) * m),
            jnp.sum(x_k * x_k * m),
            jnp.sum(excess * excess),
        ])
        return sums, jnp.max(corr)

    sums, corr_max_k = fanout(per_shard, mesh, r, x, shard_arrays)
    rr = r @ r
    primal = 0.5 * rr + l1 * sums[0] + 0.5 * l2 * sums[1]
    if l2 == 0.0:
        inf_norm = jnp.max(corr_max_k)
        s = jnp.minimum(1.0, l1 / jnp.maximum(inf_norm, 1e-30))
        u = s * r
        dual = -0.5 * (u @ u) - u @ b
    else:
        # h*(s) = ([|s|-λ]₊)²/(2η): finite for any s, so u = r is feasible
        dual = -0.5 * rr - r @ b - sums[2] / (2.0 * l2)
    gap = primal - dual
    return jnp.stack([primal, gap, jnp.asarray(jnp.nan, primal.dtype)])


@functools.lru_cache(maxsize=None)
def _metrics_fn(mesh, l1: float, l2: float):
    @jax.jit
    def f(r, x, shard_arrays, b):
        return lasso_metrics(r, x, shard_arrays, b, l1, l2, mesh=mesh)

    return f


def run_prox_cocoa(
    ds: ShardedDataset,
    params: Params,
    debug: DebugParams,
    mesh=None,
    rng: str = "reference",
    x_init: Optional[jax.Array] = None,
    r_init: Optional[jax.Array] = None,
    start_round: int = 1,
    quiet: bool = False,
    gap_target: Optional[float] = None,
    scan_chunk: int = 0,
    math: str = "fast",
    pallas=None,
    block_size: int = 0,
    block_chain=None,
    device_loop: bool = False,
    sampling: str = "auto",
    divergence_guard: str = "auto",
    l2: float = 0.0,
):
    """Train; returns (x, r, Trajectory) with x (K, d_shard) the sharded
    coordinates and r = A·x − b the replicated residual (v = r + b).

    The entry takes what every solver's does, ``(ds, params, debug, ...)``.
    ``ds`` is column shards that carry the regression target b as
    ``ds.target`` (:func:`cocoa_tpu.data.columns.shard_columns`, or
    :func:`~cocoa_tpu.data.columns.shard_dense_columns` from device
    arrays); a dataset without one (the SVM solvers' row shards) is
    refused.

    ``params.lam`` is the L1 weight λ, ``params.gamma`` the aggregation γ
    (γ=1 additive, σ′ = K·γ — the CoCoA+ safe default),
    ``params.local_iters`` the per-round coordinate steps H; ``l2`` is the
    elastic-net weight η (the CLI's ``--l2``; 0 = pure lasso —
    ``params.smoothing``, the smoothed hinge's s with its default of 1, is
    not read).  ``gap_target`` stops at the duality gap (certified for
    both lasso and elastic net — module docstring).  Execution options
    (``scan_chunk``, ``math``, ``pallas``, ``device_loop``) as in
    run_sdca_family — all paths incl. both Pallas kernels work on the
    transposed layout."""
    if ds.target is None:
        raise ValueError(
            "run_prox_cocoa takes column shards that carry the regression "
            "target (data.columns.shard_columns / shard_dense_columns); "
            "this dataset has none: it is row shards, an SVM solver's")
    l1, l2 = float(params.lam), float(l2)
    # mode="prox" has no λn factor: clone with n=1 so the shared parts'
    # lam_n == λ exactly, and select the lasso prox rule (its ``smoothing``
    # is the elastic-net weight)
    parts_params = dataclasses.replace(params, n=1, loss="lasso",
                                       smoothing=l2)
    alg = ("prox", params.gamma, ds.k * params.gamma)
    b = ds.target
    dtype = b.dtype
    metrics = _metrics_fn(mesh, l1, l2)

    def eval_fn(state):
        r, x = state
        out = np.asarray(metrics(r, x, ds.shard_arrays(), b))
        primal, gap, _ = (float(v) for v in out)
        return primal, (None if np.isnan(gap) else gap), None

    def eval_kernel(state, shard_arrays, target):
        # b arrives as an ARGUMENT of the device loop (``eval_arrays``), not
        # a closure constant: device-loop executables are cached per config
        # (base._DEVICE_RUNS), and a baked-in b would make a cached
        # executable evaluate against the wrong dataset
        r, x = state
        return lasso_metrics(r, x, shard_arrays, target, l1, l2, mesh=mesh)

    # no r_init: run_sdca_family starts the residual at -b itself (x = 0),
    # in its one start program
    w_init = None if r_init is None else jnp.asarray(r_init, dtype)
    r, x, traj = run_sdca_family(
        ds, parts_params, debug, "ProxCoCoA+", alg, mesh=mesh,
        eval_arrays=b, rng=rng, w_init=w_init, alpha_init=x_init,
        start_round=start_round,
        quiet=quiet, gap_target=gap_target, scan_chunk=scan_chunk,
        math=math, pallas=pallas, block_size=block_size,
        block_chain=block_chain, device_loop=device_loop,
        eval_fn=eval_fn, eval_kernel=eval_kernel, sampling=sampling,
        divergence_guard=divergence_guard,
    )
    # the support of the returned x: what an L1 run is for (one scalar
    # fetched after the run, beside the trajectory)
    traj.meta["x_nnz"] = int(jnp.count_nonzero(x))
    traj.meta["launches"] += 1
    if not quiet:
        print(f"ProxCoCoA+: x has {traj.meta['x_nnz']} nonzero "
              f"coordinates of {ds.n}")
    return x, r, traj
