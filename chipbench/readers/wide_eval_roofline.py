"""L1_certificate, one-vs-rest with a wide class axis on dense rows: the
least time of one certificate evaluation (``cost_model_wide.py``: the larger
of 2 n d T operations over the chip's peak and every row, every alpha and
W once over its bandwidth) over the device time one evaluation takes — the
self seconds under ``cocoa_eval`` in the window over the evaluations its
jobs ran (a job evaluates every ``debug_iter`` rounds and stops at one).
Nothing where the run's record states no block solve on the lanes, or where
the trace carries no program scope.  The peak is the bfloat16 one: margins
at ``highest`` precision, six passes, read a sixth of it at the most."""

from chipbench import cost_model, cost_model_wide
from chipbench.readers import scope_share


def read(trace, jobs, cell, scope="cocoa_eval"):
    cfg, path = cell["config"], cell["solver_path"] or {}
    if (path.get("inner"), path.get("class_axis")) != ("block", "lanes"):
        return None
    every = cell["job"].get("debug", {}).get("debug_iter")
    evals = sum(j["rounds"] // every for j in jobs) if every else 0
    s = scope_share.scope_s(trace, cell, scope)
    if not s or not evals:
        return None
    floor = cost_model.round_floor_s(
        cost_model_wide.eval_pass(cfg["n"], cfg["d"], path["classes"]),
        cost_model.peaks_for(cell["device_kind"]), cell["chips"])
    return 100.0 * floor["floor_s"] / (s / evals)
