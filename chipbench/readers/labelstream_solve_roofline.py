"""L2-L1_local_solve, one-vs-rest over label sets on rows kept as a stream:
the least HBM time of one round of the local solve
(``cost_model_labelstream.py``: the sampled rows' nonzeros at the stream's
8 B, the steps' scalars and the rows' T alphas in and out, over
``peaks.json``'s bandwidth) over the device time a round spends under the
solve and gather scopes.  Nothing where the run's record states no class
axis on the lanes of a stream, or where the trace carries no program
scope.  The floor is what no implementation avoids, so the share reads the
same work whatever implements it; a chain that moves a 4 KB row of W and
of dw a nonzero reads well under 1%."""

from chipbench import cost_model, cost_model_labelstream
from chipbench.readers import scope_share

SCOPES = ("cocoa_local_solve", "cocoa_sparse_gather")


def read(trace, jobs, cell):
    cfg, path = cell["config"], cell["solver_path"] or {}
    if (path.get("storage"), path.get("class_axis")) != ("stream", "lanes") \
            or "mean_nnz" not in cfg:
        return None
    parts = [scope_share.round_s(trace, jobs, cell, s) for s in SCOPES]
    if any(p is None for p in parts) or not sum(parts):
        return None
    peaks = cost_model.peaks_for(cell["device_kind"])
    floor = cost_model_labelstream.solve_round_bytes(
        cfg["num_splits"], cell["local_iters"], cfg["mean_nnz"],
        path["classes"]) / (peaks["hbm_bytes_per_s"] * cell["chips"])
    return 100.0 * floor / sum(parts)
