"""L2-L1_local_solve, a one-vs-rest job: device time of the local-solve
scope per outer round over H lockstep steps x T class models, in ns — what
one class's coordinate step costs once T of them share the sampled row, to
set beside a T = 1 cell's time a step (``local_solve_ms`` / H).  T is what
the run's record says (``solver_path.classes``); nothing where the program
states no class count."""

from chipbench.readers import scope_share


def read(trace, jobs, cell, scope="cocoa_local_solve"):
    classes = (cell["solver_path"] or {}).get("classes")
    if not classes:
        return None
    s = scope_share.round_s(trace, jobs, cell, scope)
    return 1e9 * s / (cell["local_iters"] * classes) if s else None
