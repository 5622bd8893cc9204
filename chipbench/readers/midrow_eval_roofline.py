"""L1_certificate, rows kept as a stream: the least HBM time of one pass
over every row (``cost_model_stream.py``: the nonzeros once, the rows'
scalars, w, over ``peaks.json``'s bandwidth) over the device time one
certificate evaluation takes — the self seconds under ``cocoa_eval`` in the
window over the evaluations its jobs ran (a job evaluates every
``debug_iter`` rounds and stops at one).  Nothing off the stream's Pallas
path or where the trace carries no program scope.  A pass paced by the
scalar core reads well under 1%; over 100% means the model counts too many
bytes."""

from chipbench import cost_model, cost_model_stream
from chipbench.readers import scope_share


def read(trace, jobs, cell, scope="cocoa_eval"):
    cfg, path = cell["config"], cell["solver_path"] or {}
    if (path.get("storage"), path.get("kernel")) != ("stream", "pallas") \
            or "mean_nnz" not in cfg:
        return None
    every = cell["job"].get("debug", {}).get("debug_iter")
    evals = sum(j["rounds"] // every for j in jobs) if every else 0
    s = scope_share.scope_s(trace, cell, scope)
    if not s or not evals:
        return None
    peaks = cost_model.peaks_for(cell["device_kind"])
    floor = cost_model_stream.pass_bytes(
        cfg["n"], cfg["d"], cfg["mean_nnz"]) / (
            peaks["hbm_bytes_per_s"] * cell["chips"])
    return 100.0 * floor / (s / evals)
