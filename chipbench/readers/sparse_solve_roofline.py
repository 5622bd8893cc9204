"""L2-L1_local_solve: the least HBM time of one round of the sparse
sequential solve (``cost_model_sparse.py``: the sampled rows' nonzeros,
w and dw at them, the steps' scalars, over ``peaks.json``'s bandwidth) over
the device time a round spends in the solve — the chain
(``cocoa_local_solve``) and the gathers, sorts and scatters that feed and
drain it (``cocoa_sparse_gather``).  Nothing off the padded-CSR
sequential Pallas path or where the trace carries no program scope.  A
path bound by the chain's latency and the scalar core reads in single
digits; over 100% means the model counts too few bytes."""

from chipbench import cost_model, cost_model_sparse
from chipbench.readers import scope_share

SCOPES = ("cocoa_local_solve", "cocoa_sparse_gather")


def floor_s(cell):
    """The round's HBM floor in seconds, or None off the path."""
    cfg, path = cell["config"], cell["solver_path"] or {}
    if (cfg["layout"], path.get("inner"), path.get("kernel")) != (
            "sparse", "sequential", "pallas") or "mean_nnz" not in cfg:
        return None
    model = cost_model_sparse.sparse_round(
        cfg["num_splits"], cell["local_iters"], cfg["mean_nnz"])
    peaks = cost_model.peaks_for(cell["device_kind"])
    return model["hbm_bytes"] / (peaks["hbm_bytes_per_s"] * cell["chips"])


def read(trace, jobs, cell):
    floor = floor_s(cell)
    parts = [scope_share.round_s(trace, jobs, cell, s) for s in SCOPES]
    if floor is None or any(p is None for p in parts) or not sum(parts):
        return None
    return 100.0 * floor / sum(parts)
