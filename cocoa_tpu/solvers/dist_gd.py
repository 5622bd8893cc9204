"""Distributed (sub)gradient descent (reference: DistGD.scala).

Per round: every worker takes one deterministic full pass over its shard
(the one inner solver with no sequential dependency — a pure MXU matvec
pair, see ops/subgradient.py), adds its −λ·w regularizer term, then the
driver applies the gradient-direction-normalized step
w += Δw·(η/‖Δw‖) with η = 1/(β·t) (DistGD.scala:35,40-41).

The η(t) schedule rides through the device-side paths as a scanned (C,)
``t`` leaf (base.TsSampler with no index table — the pass is
deterministic), so ``scan_chunk`` and ``device_loop`` work as for the
other solvers.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import ShardedDataset
from cocoa_tpu.evals import objectives
from cocoa_tpu.ops import subgradient_pass
from cocoa_tpu.solvers import base
from cocoa_tpu.telemetry import tracing as _tracing


def _gd_parts(params: Params, k: int):
    lam = params.lam
    beta = params.beta

    @jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
    def per_shard_round(w, carry, x, shard_k):
        return (
            subgradient_pass(w, shard_k, lam, loss=params.loss,
                             smoothing=params.smoothing),
            carry,
        )

    def apply_fn(w, dw_sum, x):
        eta = 1.0 / (beta * x["t"])  # DistGD.scala:35
        norm = jnp.linalg.norm(dw_sum)  # DistGD.scala:40
        return w + dw_sum * (eta / norm)  # DistGD.scala:41

    return per_shard_round, apply_fn


def make_round_step(mesh, params: Params, k: int):
    per_shard_round, apply_fn = _gd_parts(params, k)

    def per_shard(w, shard_k):
        return (per_shard_round(w, (), {}, shard_k)[0],)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def round_step(w, t, shard_arrays):
        (dw_sum,) = base.fanout(per_shard, mesh, w, shard_arrays,
                                reduce_scope=_tracing.SCOPE_DW_REDUCE)
        with jax.named_scope(_tracing.SCOPE_DW_REDUCE):
            return apply_fn(w, dw_sum, {"t": t})

    return round_step


_CHUNK_STEPS: dict = base.ExecutableCache()


def _make_chunk_kernel(mesh, params: Params, k: int):
    """(w, xs, shard_arrays) -> w'; xs = {"t": (C,)} (no index table)."""
    from cocoa_tpu.parallel.fanout import chunk_fanout

    per_shard_round, apply_fn = _gd_parts(params, k)

    def chunk_kernel(w, xs, shard_arrays):
        w2, _ = chunk_fanout(
            mesh, per_shard_round, apply_fn, w, (), xs, shard_arrays
        )
        return w2

    return chunk_kernel


def make_chunk_step(mesh, params: Params, k: int):
    key = ("distgd", mesh, k, params.lam, params.n, params.beta,
           params.loss, params.smoothing)
    step = _CHUNK_STEPS.get(key)
    if step is None:
        step = jax.jit(_make_chunk_kernel(mesh, params, k),
                       donate_argnums=(0,))
        _CHUNK_STEPS[key] = step
    return step


@_tracing.cold_entry
def run_dist_gd(
    ds: ShardedDataset,
    params: Params,
    debug: DebugParams,
    mesh=None,
    test_ds: Optional[ShardedDataset] = None,
    w_init: Optional[jax.Array] = None,
    start_round: int = 1,
    quiet: bool = False,
    scan_chunk: int = 0,
    device_loop: bool = False,
):
    """Train; returns (w, Trajectory)."""
    base.check_shards(ds, rectangle=True)
    k = ds.k
    if not quiet:
        print(f"\nRunning DistGD on {params.n} data examples, "
              f"distributed over {k} workers")

    dtype = ds.labels.dtype
    with _tracing.span("init_state"):
        w = (jnp.zeros(ds.num_features, dtype=dtype) if w_init is None
             else jnp.array(w_init, dtype=dtype, copy=True))
        if mesh is not None:
            from cocoa_tpu.parallel.mesh import primal_sharding

            w = jax.device_put(w, primal_sharding(mesh))

    ts_sampler = base.TsSampler(None, dtype, counts=ds.counts)
    shard_arrays = ds.shard_arrays()

    def eval_fn(state):
        (w,) = state
        return objectives.evaluate(ds, w, None, params.lam, test_ds=test_ds,
                                   loss=params.loss, smoothing=params.smoothing)

    if device_loop or scan_chunk > 0:
        raw_kernel = _make_chunk_kernel(mesh, params, k)

        def chunk_kernel(state, xs, shard_arrays):
            return (raw_kernel(state[0], xs, shard_arrays),)

        chunk_step = make_chunk_step(mesh, params, k)

        def chunk_fn(t0, c, state):
            return (chunk_step(state[0], ts_sampler.chunk_indices(t0, c),
                               shard_arrays),)

        cache_key = (
            "distgd", k, mesh, params.lam, params.n, params.beta,
            params.loss, params.smoothing, params.num_rounds,
            debug.debug_iter, start_round, ds.layout, str(dtype),
        )
        (w,), traj = base.drive_device_paths(
            "Dist SGD", params, debug, (w,), chunk_kernel, chunk_fn,
            eval_fn, ts_sampler, shard_arrays, alpha_in_state=False,
            mesh=mesh, test_ds=test_ds, quiet=quiet,
            start_round=start_round, scan_chunk=scan_chunk,
            device_loop=device_loop, cache_key=cache_key,
        )
        return w, traj

    step = make_round_step(mesh, params, k)

    def round_fn(t, c, state):
        (w,) = state
        return (step(w, jnp.asarray(float(t), dtype=dtype), shard_arrays),)

    (w,), traj = base.drive_chunked(
        "Dist SGD", params, debug, (w,), round_fn, eval_fn,
        quiet=quiet, start_round=start_round, chunk=1,
    )
    return w, traj
