"""L2-L1_local_solve, a sparse job whose coordinate step iterates (logistic:
a Newton solve on one coordinate's 0-d values inside the chain kernel):
device time of the local-solve scope per outer round over its K x H
coordinate steps, in ns — what one step of the chain costs with its solve,
to set beside a closed-form cell's (kddb's hinge step: ``local_solve_ms`` /
(K x H)).  Nothing off the padded-CSR sequential Pallas path, or where the
trace carries no program scope."""

from chipbench.readers import scope_share


def read(trace, jobs, cell, scope="cocoa_local_solve"):
    cfg, path = cell["config"], cell["solver_path"] or {}
    if (cfg.get("layout"), path.get("inner"), path.get("kernel")) != (
            "sparse", "sequential", "pallas"):
        return None
    s = scope_share.round_s(trace, jobs, cell, scope)
    return 1e9 * s / (cfg["num_splits"] * cell["local_iters"]) if s else None
