"""L1_certificate, one-vs-rest over label sets on rows kept as a stream: the
least HBM time of one certificate evaluation
(``cost_model_labelstream.py``: every nonzero once, every row's scalars and
T alphas, W once, over ``peaks.json``'s bandwidth) over the device time one
evaluation takes — the self seconds under ``cocoa_eval`` in the window over
the evaluations its jobs ran (a job evaluates every ``debug_iter`` rounds
and stops at one).  Nothing where the run's record states no class axis on
the lanes of a stream, or where the trace carries no program scope.  A
pass that gathers a 4 KB row of W a nonzero moves ~60 times the floor's
bytes and reads a percent or so."""

from chipbench import cost_model, cost_model_labelstream
from chipbench.readers import scope_share


def read(trace, jobs, cell, scope="cocoa_eval"):
    cfg, path = cell["config"], cell["solver_path"] or {}
    if (path.get("storage"), path.get("class_axis")) != ("stream", "lanes") \
            or "mean_nnz" not in cfg:
        return None
    every = cell["job"].get("debug", {}).get("debug_iter")
    evals = sum(j["rounds"] // every for j in jobs) if every else 0
    s = scope_share.scope_s(trace, cell, scope)
    if not s or not evals:
        return None
    peaks = cost_model.peaks_for(cell["device_kind"])
    floor = cost_model_labelstream.eval_pass_bytes(
        cfg["n"], cfg["d"], cfg["mean_nnz"], path["classes"]) / (
            peaks["hbm_bytes_per_s"] * cell["chips"])
    return 100.0 * floor / (s / evals)
