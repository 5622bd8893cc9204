"""L2-L1_local_solve: the least time the chips could take for one round —
the larger of FLOPs over peak and row bytes over HBM bandwidth, from the shapes
(``cost_model.py``, ``peaks.json``) — over the measured ``round_ms``.
Nothing where the job ran a path the model does not count."""

import jax.numpy as jnp

from chipbench import cost_model
from chipbench.readers import round_ms


def floor_of(cell):
    """The round's floor and which peak bounds it, or None off the dense
    sequential Pallas path."""
    cfg, path = cell["config"], cell["solver_path"] or {}
    if (cfg["layout"], path.get("inner"), path.get("kernel")) != (
            "dense", "sequential", "pallas"):
        return None
    model = cost_model.sdca_round(cfg["d"], cfg["num_splits"],
                                  cell["local_iters"],
                                  jnp.dtype(cfg["dtype"]).itemsize)
    return cost_model.round_floor_s(
        model, cost_model.peaks_for(cell["device_kind"]), cell["chips"])


def read(trace, jobs, cell):
    s, floor = round_ms.per_round_s(trace, jobs), floor_of(cell)
    return None if not s or floor is None else 100.0 * floor["floor_s"] / s
