"""L4_drive_ladder: device program launches inside one job (median)."""

import statistics


def read(trace, jobs, cell):
    if not trace.jobs:
        return None
    return statistics.median(j["launches"] for j in trace.jobs)
