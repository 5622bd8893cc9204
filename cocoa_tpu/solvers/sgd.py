"""Distributed SGD: local-SGD and mini-batch variants (reference: SGD.scala).

- local=True (Local SGD): workers run H Pegasos steps on a private w; the
  driver averages Δw = w_local − w_init with β/K (SGD.scala:34-37,55-56).
- local=False (mini-batch SGD): the driver pre-scales w by (1 − ηλ) with
  η = 1/(λt) (SGD.scala:44-50), workers sum raw hinge subgradients, and the
  driver applies w += Δw·η·β/(K·H) (SGD.scala:38,57-59).

No dual state → primal-objective-only trajectory (no duality-gap
certificate), as in the reference (SGD.scala:62-66).

The η(t) schedule rides through the device-side paths as a scanned (C,)
``t`` leaf in the chunk tables (parallel/fanout.py chunk_fanout,
base.TsSampler) — ``scan_chunk`` and ``device_loop`` work exactly as they
do for the SDCA family.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import ShardedDataset
from cocoa_tpu.evals import objectives
from cocoa_tpu.ops import local_sgd
from cocoa_tpu.solvers import base
from cocoa_tpu.telemetry import tracing as _tracing


def _sgd_parts(params: Params, k: int, local: bool):
    """per-shard round + driver apply shared by every execution path.

    ``x`` is the per-round input dict {"idxs": (H,), "t": scalar}."""
    h = params.local_iters
    lam = params.lam
    scaling = params.beta / k if local else params.beta / (k * h)  # SGD.scala:34-39

    def pre_scale(w, t):
        if local:
            return w
        eta = 1.0 / (lam * t)  # SGD.scala:44
        return w * (1.0 - eta * lam)  # driver-side pre-scale (SGD.scala:46-50)

    @jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
    def per_shard_round(w, carry, x, shard_k):
        t = x["t"]
        t_global = (t - 1.0) * h * k  # SGD.scala:53
        dw = local_sgd(pre_scale(w, t), shard_k, x["idxs"], lam, t_global,
                       local, loss=params.loss, smoothing=params.smoothing)
        return dw, carry

    def apply_fn(w, dw_sum, x):
        if local:
            return w + dw_sum * scaling  # SGD.scala:55-56
        t = x["t"]
        eta = 1.0 / (lam * t)
        return pre_scale(w, t) + dw_sum * (eta * scaling)  # SGD.scala:57-59

    return per_shard_round, apply_fn


def make_round_step(mesh, params: Params, k: int, local: bool):
    per_shard_round, apply_fn = _sgd_parts(params, k, local)

    def per_shard(w, idxs_k, t_k, shard_k):
        return (per_shard_round(w, (), {"idxs": idxs_k, "t": t_k}, shard_k)[0],)

    @functools.partial(jax.jit, donate_argnums=(0,))
    def round_step(w, idxs, t, shard_arrays):
        (dw_sum,) = base.fanout(
            per_shard, mesh, w, idxs, _rep(t, k), shard_arrays,
            reduce_scope=_tracing.SCOPE_DW_REDUCE,
        )
        with jax.named_scope(_tracing.SCOPE_DW_REDUCE):
            return apply_fn(w, dw_sum, {"t": t})

    return round_step


def _rep(scalar, k):
    """Broadcast a traced scalar to a (K,) sharded arg for fanout."""
    return jnp.broadcast_to(scalar, (k,))


_CHUNK_STEPS: dict = base.ExecutableCache()


def _make_chunk_kernel(mesh, params: Params, k: int, local: bool,
                       ts_sampler=None):
    """(w, xs, shard_arrays) -> w', C rounds as one ``lax.scan``; xs is the
    TsSampler table {"idxs": (C, K, H), "t": (C,)} — or just the ``t`` leaf
    in device-sampling mode, with ``idxs`` generated in-jit."""
    from cocoa_tpu.parallel.fanout import chunk_fanout

    per_shard_round, apply_fn = _sgd_parts(params, k, local)

    def chunk_kernel(w, xs, shard_arrays):
        if ts_sampler is not None:
            xs = ts_sampler.materialize(xs)
        w2, _ = chunk_fanout(
            mesh, per_shard_round, apply_fn, w, (), xs, shard_arrays
        )
        return w2

    return chunk_kernel


def make_chunk_step(mesh, params: Params, k: int, local: bool,
                    ts_sampler=None):
    key = ("sgd", mesh, k, local, params.lam, params.n, params.local_iters,
           params.beta, params.loss, params.smoothing,
           None if ts_sampler is None else ts_sampler.cache_token())
    step = _CHUNK_STEPS.get(key)
    if step is None:
        step = jax.jit(_make_chunk_kernel(mesh, params, k, local,
                                          ts_sampler=ts_sampler),
                       donate_argnums=(0,))
        _CHUNK_STEPS[key] = step
    return step


@_tracing.cold_entry
def run_sgd(
    ds: ShardedDataset,
    params: Params,
    debug: DebugParams,
    local: bool,
    mesh=None,
    test_ds: Optional[ShardedDataset] = None,
    rng: str = "reference",
    w_init: Optional[jax.Array] = None,
    start_round: int = 1,
    quiet: bool = False,
    scan_chunk: int = 0,
    device_loop: bool = False,
    sampling: str = "auto",
):
    """Train; returns (w, Trajectory).  ``scan_chunk > 0`` runs rounds
    device-side in blocks via ``lax.scan``; ``device_loop=True`` rides the
    whole run — rounds, evals — as one on-device ``lax.while_loop`` (see
    run_sdca_family for semantics; SGD has no duality gap so there is no
    gap-target early stop)."""
    base.check_shards(ds, rectangle=True)
    k = ds.k
    if not quiet:
        print(f"\nRunning SGD (with local updates = {local}) on {params.n} "
              f"data examples, distributed over {k} workers")

    dtype = ds.labels.dtype
    with _tracing.span("init_state"):
        w = (jnp.zeros(ds.num_features, dtype=dtype) if w_init is None
             else jnp.array(w_init, dtype=dtype, copy=True))
        if mesh is not None:
            from cocoa_tpu.parallel.mesh import primal_sharding

            w = jax.device_put(w, primal_sharding(mesh))

    sampler = base.IndexSampler(rng, debug.seed, params.local_iters, ds.counts)
    sampler.device = base.resolve_sampling(sampling, sampler,
                                           params.num_rounds)
    ts_sampler = base.TsSampler(sampler, dtype)
    shard_arrays = ds.shard_arrays()
    name = "Local SGD" if local else "Mini-batch SGD"

    def eval_fn(state):
        (w,) = state
        return objectives.evaluate(ds, w, None, params.lam, test_ds=test_ds,
                                   loss=params.loss, smoothing=params.smoothing)

    if device_loop or scan_chunk > 0:
        raw_kernel = _make_chunk_kernel(mesh, params, k, local,
                                        ts_sampler=ts_sampler)

        def chunk_kernel(state, xs, shard_arrays):
            return (raw_kernel(state[0], xs, shard_arrays),)

        chunk_step = make_chunk_step(mesh, params, k, local,
                                     ts_sampler=ts_sampler)

        def chunk_fn(t0, c, state):
            return (chunk_step(state[0], ts_sampler.chunk_indices(t0, c),
                               shard_arrays),)

        cache_key = (
            "sgd", local, ts_sampler.cache_token(), k, mesh,
            params.lam, params.n, params.local_iters,
            params.beta, params.loss, params.smoothing, params.num_rounds,
            debug.debug_iter, start_round, ds.layout, str(dtype),
        )
        (w,), traj = base.drive_device_paths(
            name, params, debug, (w,), chunk_kernel, chunk_fn, eval_fn,
            ts_sampler, shard_arrays, alpha_in_state=False, mesh=mesh,
            test_ds=test_ds, quiet=quiet, start_round=start_round,
            scan_chunk=scan_chunk, device_loop=device_loop,
            cache_key=cache_key,
        )
        return w, traj

    step = make_round_step(mesh, params, k, local)

    def round_fn(t, c, state):
        (w,) = state
        idxs = sampler.round_indices(t)
        return (step(w, idxs, jnp.asarray(float(t), dtype=dtype), shard_arrays),)

    (w,), traj = base.drive_chunked(
        name, params, debug, (w,), round_fn, eval_fn,
        quiet=quiet, start_round=start_round, chunk=1,
    )
    return w, traj
