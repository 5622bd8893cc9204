"""L3_round: the device's busy time in a job over the rounds that job ran,
evals included.  Median over the traced jobs, in ms."""

import statistics


def per_round_s(trace, jobs):
    pairs = [(t["busy_s"], j["rounds"]) for t, j in zip(trace.jobs, jobs)
             if j["rounds"] > 0]
    return statistics.median(b / r for b, r in pairs) if pairs else None


def read(trace, jobs, cell):
    s = per_round_s(trace, jobs)
    return None if s is None else 1e3 * s
