"""L2-L1_local_solve: the share of busy time in the one op name that takes
most (self time; the breakdown prints the name)."""


def read(trace, jobs, cell):
    if not trace.ops or not trace.busy_s:
        return None
    return 100.0 * trace.top_ops(1)[0][1] / trace.busy_s
