"""L2-L1_local_solve, rows kept as a stream: device time of the local-solve
scope per outer round over the nonzeros of the rows that round samples
(K shards x H steps x the configuration's mean nonzeros a row), in ns —
what the chain pays a sampled nonzero, fetch and step's fixed work
included, to set beside the long-row cell's (``local_solve_ms`` over its
6.5e7 sampled nonzeros).  Nothing where the configuration states no mean
or the trace carries no program scope."""

from chipbench.readers import scope_share


def read(trace, jobs, cell, scope="cocoa_local_solve"):
    cfg = cell["config"]
    if "mean_nnz" not in cfg:
        return None
    s = scope_share.round_s(trace, jobs, cell, scope)
    nonzeros = cfg["num_splits"] * cell["local_iters"] * cfg["mean_nnz"]
    return 1e9 * s / nonzeros if s else None
