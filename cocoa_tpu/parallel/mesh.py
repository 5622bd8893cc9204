"""Device mesh and collective-communication backend.

The reference's entire communication layer is implicit Spark dataflow: the
driver broadcasts ``w`` by closure capture to K executors and sum-reduces the
per-shard ``Δw`` with ``RDD.reduce(_ + _)`` (CoCoA.scala:45-47) — one O(d)
all-reduce per outer round.  Here the same contract is carried by XLA
collectives over the ICI mesh:

- ``w`` lives **replicated** on every device: the broadcast costs nothing.
- ``Δw`` is combined with one ``lax.psum`` over the data-parallel axis.
- shard-local state (``α``, the data shard) is pinned per-device in HBM and
  never moves — the analogue of ``preservesPartitioning=true`` + per-partition
  ``α`` RDDs (CoCoA.scala:33-34,45).

Mesh axes:

- ``dp`` — data parallelism over example shards (the reference's only
  parallelism strategy; K = number of Spark partitions).
- ``fp`` — feature-dimension sharding of ``w``/``X`` columns for very large d
  (a TPU extension with no reference analogue; see SURVEY.md §2.2).  The fp
  axis is ``AxisType.Auto``: solvers shard_map manually over dp only and
  GSPMD inserts the fp collectives for every d-contraction (data/sharding.py
  places X as P('dp', None, 'fp'); w is P('fp') via :func:`primal_sharding`).
  fp is a *capacity* axis — it fits a d/F slice of the model and data columns
  per device; the sequential SDCA inner loop still pays one fp-reduction per
  coordinate step, so use it when d forces it, not for speed.

On a real pod the mesh should be built so ``dp`` rides ICI; a multi-slice
deployment puts the slowest axis on DCN.  Tests simulate K devices on CPU via
``--xla_force_host_platform_device_count`` (see tests/conftest.py).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

DP_AXIS = "dp"
FP_AXIS = "fp"
TENANT_AXIS = "tenant"   # the fleet's spare axis: independent models,
                         # not shards — no collective ever crosses it


def make_mesh(
    k: Optional[int] = None,
    fp: int = 1,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a (dp,) or (dp, fp) mesh over ``k * fp`` devices.

    ``k`` defaults to using every available device on the dp axis.  Raises if
    the device count cannot satisfy the request — shards must map 1:1 onto
    mesh positions (unlike Spark, where K partitions multiplex onto fewer
    executors; on TPU the mesh *is* the worker set).

    The fp axis is created with ``AxisType.Auto``: the solvers run
    ``shard_map`` manually over dp only and leave the feature dimension to
    GSPMD — annotate the shardings (X columns and w on fp), let XLA insert
    the collectives for every d-contraction.  dp stays Explicit/manual so the
    one Δw psum per round remains the visible communication contract.
    """
    devices = list(devices if devices is not None else jax.devices())
    if k is None:
        k = len(devices) // fp
    need = k * fp
    if need > len(devices):
        raise ValueError(
            f"mesh ({k} dp x {fp} fp) needs {need} devices, "
            f"have {len(devices)}"
        )
    if fp == 1:
        return jax.make_mesh((k,), (DP_AXIS,), devices=devices[:need])
    return jax.make_mesh(
        (k, fp), (DP_AXIS, FP_AXIS), devices=devices[:need],
        axis_types=(AxisType.Explicit, AxisType.Auto),
    )


def infer_dp_size(k: int, n_devices: int) -> int:
    """The dp mesh a K-shard run gets when nobody names one: the largest
    divisor of K that fits the device budget (m = K/D logical shards then
    multiplex per device; 1 = the single-chip vmap path)."""
    return max((d for d in range(1, min(k, n_devices) + 1) if k % d == 0),
               default=1)


def has_fp(mesh: Optional[Mesh]) -> bool:
    """True when the mesh carries a feature-parallel axis."""
    return mesh is not None and FP_AXIS in mesh.axis_names


def manual_axes(mesh: Optional[Mesh]) -> frozenset:
    """The axes shard_map runs manually over: dp only on an fp mesh (the
    feature axis is GSPMD-auto), every axis otherwise (empty set = all)."""
    return frozenset({DP_AXIS}) if has_fp(mesh) else frozenset()


def dp_local_shards(mesh: Mesh, k: int) -> list:
    """``[(device, shard_lo, shard_hi)]`` for THIS process's dp positions.

    Under ``P('dp', ...)`` sharding of a (K, ...) array on a D-device dp
    axis, dp position i holds the m = K/D consecutive logical shards
    [i·m, (i+1)·m) — the same multiplexing contract
    :func:`cocoa_tpu.parallel.fanout.shards_per_device` runs the solvers
    under.  This is the placement map the distributed dataset builders
    (whole-file and streaming ingest alike) use to materialize ONLY the
    shards whose device lives in this process.
    """
    import numpy as np

    d = mesh.shape[DP_AXIS]
    if k % d != 0:
        raise ValueError(
            f"{k} shards cannot multiplex evenly onto the {d}-device dp "
            f"axis; K must be a multiple of the mesh size (the elastic "
            f"supervisor's shrink path only ever reforms gangs whose "
            f"device count divides K — elastic.shrink_gang_size)"
        )
    m = k // d
    grid = np.asarray(mesh.devices).reshape(d, -1)
    me = jax.process_index()
    return [
        (grid[i, 0], i * m, (i + 1) * m)
        for i in range(d)
        if grid[i, 0].process_index == me
    ]


def sharded_rows(mesh: Mesh, *, extra_dims: int = 0) -> NamedSharding:
    """Sharding for per-shard stacked arrays of shape (K, ...): axis 0 on dp."""
    return NamedSharding(mesh, P(DP_AXIS, *([None] * extra_dims)))


def replicated(mesh: Mesh) -> NamedSharding:
    """Sharding for fully replicated arrays."""
    return NamedSharding(mesh, P())


def primal_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for the global primal vector w: replicated on a dp mesh,
    split over the feature axis on a (dp, fp) mesh — each device then holds
    d/fp of w (and the matching column block of X, see data/sharding.py)."""
    return NamedSharding(mesh, P(FP_AXIS) if has_fp(mesh) else P())


def x_sharding(mesh: Mesh) -> NamedSharding:
    """Sharding for the dense (K, n_shard, d) design matrix: rows over dp,
    columns over fp (when present) so each device holds the (n_shard, d/fp)
    block matching its slice of w."""
    return NamedSharding(
        mesh, P(DP_AXIS, None, FP_AXIS if has_fp(mesh) else None)
    )


# --- fleet: regex-rule partition specs over the tenant axis -----------------
#
# The fleet path (solvers/fleet.py) stacks T independent tenants on a
# leading axis of every state and data leaf.  Placement is described the
# way large-model codebases describe theirs (SNIPPETS.md [2]
# ``match_partition_rules``): an ordered list of (regex, PartitionSpec)
# rules matched against each leaf's '/'-joined tree path, first match
# wins.  Because tenants are INDEPENDENT (no collective crosses the
# tenant axis), the whole rule set is one idea — "shard the leading T
# axis, replicate the rest" — and the regex form exists so future
# composite meshes (tenant × dp) can grow per-leaf exceptions without
# touching the solver.


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def match_partition_rules(rules, tree):
    """Map every leaf of ``tree`` to the PartitionSpec of the first rule
    whose regex searches its '/'-joined path (the SNIPPETS.md [2] idiom).
    Raises on an unmatched leaf — a silent default is how a new state
    leaf ends up replicated across a thousand tenants."""
    import re

    compiled = [(re.compile(pat), spec) for pat, spec in rules]

    def assign(path, leaf):
        name = _path_str(path)
        for pat, spec in compiled:
            if pat.search(name):
                return spec
        raise ValueError(
            f"no partition rule matches tree path {name!r}; add a rule "
            f"(the catch-all '.*' usually belongs at the end)")

    return jax.tree_util.tree_map_with_path(assign, tree)


def fleet_partition_rules(tree) -> tuple:
    """The fleet rule set: every leaf with a leading tenant axis shards
    that axis; per-tenant scalars ((T,) leaves) likewise; anything else
    would be a bug — tenants share nothing."""
    del tree  # one rule covers the whole fleet state/data surface today
    return ((r".*", P(TENANT_AXIS)),)


def make_fleet_mesh(t_devices: Optional[int] = None,
                    devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """A 1-axis ``tenant`` mesh over ``t_devices`` chips: the fleet's
    (T, ...) slabs split T-major across it via
    :func:`fleet_shardings`, each chip running its T/D lanes of the one
    compiled round.  ``t_devices=1`` is the degenerate single-chip
    control — the pure-vmap path, bit-identical by construction."""
    devices = list(devices if devices is not None else jax.devices())
    t_devices = len(devices) if t_devices is None else int(t_devices)
    if t_devices > len(devices):
        raise ValueError(f"fleet mesh needs {t_devices} devices, have "
                         f"{len(devices)}")
    return jax.make_mesh((t_devices,), (TENANT_AXIS,),
                         devices=devices[:t_devices])


def fleet_shardings(mesh: Mesh, tree):
    """NamedShardings for a fleet pytree from the regex rules — the
    device_put map for state, shard slabs, and per-tenant scalars."""
    specs = match_partition_rules(fleet_partition_rules(tree), tree)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def pad_features(d: int, mesh: Optional[Mesh]) -> int:
    """d rounded up to an fp-and-sublane multiple.  The feature-parallel
    column split needs equal blocks; the Pallas SDCA kernel's folded-row
    layout needs d % 8 == 0.  Zero pad columns touch nothing — no update
    ever flows into them and w's matching entries stay exactly 0."""
    import math

    fp = mesh.shape[FP_AXIS] if has_fp(mesh) else 1
    m = math.lcm(fp, 8)
    return -(-d // m) * m
