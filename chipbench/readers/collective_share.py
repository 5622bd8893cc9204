"""L3_round, several chips: device time in collective ops (the dw
all-reduce) over busy time; ``exposed=True`` counts only the part during
which no other op ran on that device."""


def read(trace, jobs, cell, exposed=False):
    if trace.n_devices < 2 or not trace.busy_s:
        return None
    part = trace.collective_exposed_s if exposed else trace.collective_s
    return 100.0 * part / trace.busy_s
