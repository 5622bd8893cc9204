"""Fan-out/all-reduce execution of per-shard functions.

The communication core shared by solvers *and* evaluation: run a per-shard
function with w replicated and shard state local, then sum-reduce the first
output across shards.  Two paths with identical math:

- **mesh path**: ``shard_map`` over the dp axis; the reduce is ``lax.psum``
  over ICI.  This is the reference's ``mapPartitions`` → ``RDD.reduce``
  skeleton (CoCoA.scala:45-47) as a single XLA collective.
- **local path** (mesh=None): ``vmap`` over the leading K axis + in-device
  sum — all K logical shards resident on one chip (the analogue of the
  reference's ``local[4]`` mode), used for single-chip benchmarking.
"""

from __future__ import annotations

import contextlib
from typing import Callable, Optional

import jax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cocoa_tpu.parallel.mesh import DP_AXIS, manual_axes
from cocoa_tpu.telemetry.tracing import SCOPE_DW_REDUCE


def _to_varying(x):
    """Mark a replicated value as varying over dp (VMA cast inside shard_map)."""
    return lax.pcast(x, (DP_AXIS,), to="varying")


@jax.named_scope(SCOPE_DW_REDUCE)
def _shard_sum(dw):
    """Σ over the leading (local shards) axis of the per-shard Δw."""
    return dw.sum(axis=0)


def shards_per_device(mesh: Optional[Mesh], k: int) -> int:
    """m = logical shards per mesh position (Spark multiplexes K partitions
    onto fewer executors via ``coalesce``, OptUtils.scala:14; the mesh
    analogue stacks m = K/D shards per device and runs them under an inner
    vmap/batched kernel inside the shard_map body).  1:1 when mesh is None
    (the local path IS the all-shards-on-one-device case)."""
    if mesh is None:
        return 1
    d = mesh.shape[DP_AXIS]
    if k % d != 0:
        raise ValueError(
            f"{k} shards cannot multiplex evenly onto the {d}-device dp "
            f"axis; K must be a multiple of the mesh size"
        )
    return k // d


def fanout(
    per_shard: Callable,
    mesh: Optional[Mesh],
    w: jax.Array,
    *sharded,
    reduce_scope: Optional[str] = None,
):
    """Run ``per_shard(w, *shard_slices) -> (reduced, aux...)`` over K shards.

    ``sharded`` args are pytrees whose leaves have leading dim K.  The first
    output of ``per_shard`` is sum-reduced across shards (any shape — a Δw
    vector or a scalar partial sum); each aux output keeps its leading K dim
    (shard-local state, e.g. updated alpha).

    K may be a multiple m·D of the dp mesh size D (shard multiplexing —
    see :func:`shards_per_device`): each device then runs its m local
    shards under an inner vmap, sums their contributions in-device, and
    the cross-device combine stays ONE psum per call either way.

    ``reduce_scope``: the ``jax.named_scope`` the combine runs under — a
    round's Δw sum passes :data:`SCOPE_DW_REDUCE`; an eval's partial sums
    pass nothing and stay inside the eval's own scope.
    """
    def reducing():
        return (jax.named_scope(reduce_scope) if reduce_scope
                else contextlib.nullcontext())

    if mesh is not None:
        k = jax.tree.leaves(sharded)[0].shape[0]
        m = shards_per_device(mesh, k)

        def wrapped(w, *slices):
            # w arrives replicated (unvarying); the local solvers mix it into
            # shard-varying state, so cast it to device-varying up front to
            # keep loop-carry VMA types consistent.
            w = _to_varying(w)
            if m == 1:
                slices = jax.tree.map(lambda a: a[0], slices)
                out = per_shard(w, *slices)
                red, aux = out[0], out[1:]
                with reducing():
                    red = lax.psum(red, DP_AXIS)
                return (red, *(a[None] for a in aux))
            # multiplexed: the local (m, ...) block is the single-chip
            # "m logical shards on one device" case — vmap it, sum the
            # reduced outputs in-device, then the same single psum
            out = jax.vmap(per_shard, in_axes=(None, *([0] * len(slices))))(
                w, *slices
            )
            red, aux = out[0], out[1:]
            with reducing():
                red = lax.psum(red.sum(axis=0), DP_AXIS)
            return (red, *aux)

        in_specs = (P(), *(jax.tree.map(lambda _: P(DP_AXIS), s) for s in sharded))
        # probe output structure abstractly to build out_specs: first output
        # replicated, aux outputs sharded on their leading dim
        probe = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype), sharded
        )
        n_aux = len(jax.eval_shape(per_shard, w, *probe)) - 1
        out_specs = (P(), *([P(DP_AXIS)] * n_aux))
        # on a (dp, fp) mesh, shard_map is manual over dp only; the feature
        # axis stays GSPMD-auto (specs then only constrain the dp placement)
        return jax.shard_map(
            wrapped, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            axis_names=manual_axes(mesh),
        )(w, *sharded)

    in_axes = (None, *([0] * len(sharded)))
    out = jax.vmap(per_shard, in_axes=in_axes)(w, *sharded)
    red, aux = out[0], out[1:]
    with reducing():
        red = red.sum(axis=0)
    return (red, *aux)


def invariant_from_varying(x):
    """Recover a replicated (invariant) value from a device-varying one that
    is numerically identical on every device — exactly, via a masked psum
    that selects device 0's copy (no division, so bit-exact for any K)."""
    idx = lax.axis_index(DP_AXIS)
    import jax.numpy as jnp

    return lax.psum(jnp.where(idx == 0, x, jnp.zeros_like(x)), DP_AXIS)


def chunk_fanout(
    mesh: Optional[Mesh],
    per_round: Callable,
    apply_fn: Callable,
    w: jax.Array,
    carry_sharded,      # pytree, leaves (K, ...): shard-local carry (e.g. alpha)
    xs_sharded,         # pytree: per-round inputs, see below
    static_sharded,     # pytree, leaves (K, ...): shard data (not scanned)
    per_round_batched: Optional[Callable] = None,
    check_vma: bool = True,
):
    """Run C rounds device-side as one ``lax.scan`` (one dispatch per chunk).

    ``xs_sharded`` leaves are scanned over their leading C dim; leaves of
    ndim ≥ 2 are (C, K, ...) per-shard inputs (sliced per device on the
    mesh path), leaves of ndim == 1 are (C,) replicated per-round scalars
    (e.g. the round number t for η(t) schedules — SGD.scala:44,
    DistGD.scala:35).

    ``per_round(w, carry_k, x_k, static_k) -> (dw, carry_k')`` is one outer
    round seen from a single shard, returning its *unreduced* Δw;
    ``apply_fn(w, dw_sum, x_k) -> w'`` is the replicated driver-side update
    (``x_k`` passed so t-dependent step sizes can be applied).  Returns
    (w_final, carry_final) with the same placement semantics as ``fanout``
    (w replicated, carry keeping its leading K dim).

    ``per_round_batched(w, carry, x, static) -> (dw_sum, carry')``, when
    given, replaces the vmap on the single-chip path with one call over all
    K shards at once — required for inner solvers that manage the shard axis
    themselves (the Pallas kernels' (K, H) grids cannot sit under vmap).
    """
    def x_spec(a):
        return P(None) if a.ndim == 1 else P(None, DP_AXIS)

    if mesh is not None:
        # K from the static shard arrays — the carry can be empty (the
        # mini-batch SGD chunk carries no per-shard state)
        k = jax.tree.leaves((static_sharded, carry_sharded))[0].shape[0]
        m = shards_per_device(mesh, k)

        def wrapped(w, carry, xs, static):
            w = _to_varying(w)
            if m == 1:
                carry = jax.tree.map(lambda a: a[0], carry)
                # (C, 1, ...) → (C, ...); (C,) scalar leaves pass through
                xs = jax.tree.map(
                    lambda a: a if a.ndim == 1 else a[:, 0], xs
                )
                static = jax.tree.map(lambda a: a[0], static)

                def body(c, x):
                    w, carry_k = c
                    dw, carry2 = per_round(w, carry_k, x, static)
                    with jax.named_scope(SCOPE_DW_REDUCE):
                        w2 = apply_fn(w, lax.psum(dw, DP_AXIS), x)
                    return (w2, carry2), None
            else:
                # multiplexed (m shards per device): the local (m, ...)
                # block runs exactly like the single-chip path — batched
                # kernel or vmap — with the in-device shard sum folded
                # into the same single psum per round
                def body(c, x):
                    w, carry_k = c
                    if per_round_batched is not None:
                        dw_local, carry2 = per_round_batched(
                            w, carry_k, x, static
                        )
                    else:
                        x_axes = jax.tree.map(
                            lambda a: None if a.ndim == 0 else 0, x
                        )
                        dw, carry2 = jax.vmap(
                            per_round, in_axes=(None, 0, x_axes, 0)
                        )(w, carry_k, x, static)
                        dw_local = _shard_sum(dw)
                    with jax.named_scope(SCOPE_DW_REDUCE):
                        w2 = apply_fn(w, lax.psum(dw_local, DP_AXIS), x)
                    return (w2, carry2), None

            (w, carry), _ = lax.scan(body, (w, carry), xs)
            w_inv = invariant_from_varying(w)
            if m == 1:
                carry = jax.tree.map(lambda a: a[None], carry)
            return w_inv, carry

        in_specs = (
            P(),
            jax.tree.map(lambda _: P(DP_AXIS), carry_sharded),
            jax.tree.map(x_spec, xs_sharded),
            jax.tree.map(lambda _: P(DP_AXIS), static_sharded),
        )
        out_specs = (P(), jax.tree.map(lambda _: P(DP_AXIS), carry_sharded))
        return jax.shard_map(
            wrapped, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
            check_vma=check_vma, axis_names=manual_axes(mesh),
        )(w, carry_sharded, xs_sharded, static_sharded)

    # local path: scan over rounds; per round, vmap over shards + in-device sum
    def body(c, x):
        w, carry = c
        if per_round_batched is not None:
            dw_sum, carry2 = per_round_batched(w, carry, x, static_sharded)
        else:
            x_axes = jax.tree.map(lambda a: None if a.ndim == 0 else 0, x)
            dw, carry2 = jax.vmap(per_round, in_axes=(None, 0, x_axes, 0))(
                w, carry, x, static_sharded
            )
            dw_sum = _shard_sum(dw)
        with jax.named_scope(SCOPE_DW_REDUCE):
            w2 = apply_fn(w, dw_sum, x)
        return (w2, carry2), None

    (w, carry), _ = lax.scan(body, (w, carry_sharded), xs_sharded)
    return w, carry


def lane_fanout(per_lane: Callable, lane_exec: str = "vmap",
                idx_axis: Optional[int] = None) -> Callable:
    """Batch a per-tenant traceable over the fleet's leading tenant axis
    (solvers/base.py ``_build_fleet_run``).

    ``per_lane(state_t, chunk, data_t, scal_t) -> state_t`` sees ONE
    tenant; the returned callable takes the stacked (T, ...) pytrees.
    ``idx_axis`` names the chunk table's tenant axis (None = one table
    shared by every lane).  ``lane_exec``:

    - ``"vmap"`` — lanes batch into one vectorized body (the throughput
      mode; batched reductions may round ~1 ulp away from the solo
      executable at T > 1);
    - ``"map"`` — lanes run sequentially via ``lax.scan`` inside the
      same jit (``lax.map``): each lane's body is the solo HLO exactly —
      the bit-parity mode (same one-compile/one-dispatch amortization).
    """
    if lane_exec not in ("vmap", "map"):
        raise ValueError(f"lane_exec must be vmap|map, got {lane_exec!r}")
    if lane_exec == "vmap":
        return jax.vmap(per_lane, in_axes=(0, idx_axis, 0, 0))
    import jax.numpy as jnp

    def mapped(state, chunk, data, scal):
        if idx_axis is not None:
            ch = jnp.moveaxis(chunk, idx_axis, 0)
            return lax.map(lambda a: per_lane(*a), (state, ch, data, scal))
        return lax.map(lambda a: per_lane(a[0], chunk, a[1], a[2]),
                       (state, data, scal))

    return mapped


def mesh_of(*arrays) -> Optional[Mesh]:
    """Infer the dp mesh from array placement (None ⇒ local/vmap path).

    An array counts as mesh-placed when it carries a NamedSharding over a
    multi-device mesh with a dp axis.
    """
    for a in arrays:
        sh = getattr(a, "sharding", None)
        if (
            isinstance(sh, NamedSharding)
            and sh.mesh.size > 1
            and DP_AXIS in sh.mesh.axis_names
        ):
            return sh.mesh
    return None
