"""L4_drive_ladder: per job, wall-clock less the device's busy time inside
it — dispatch, staging, fetch and every wait the host causes.  Median over
the traced jobs."""

import statistics


def read(trace, jobs, cell):
    if not trace.jobs:
        return None
    return statistics.median(j["end_s"] - j["start_s"] - j["busy_s"]
                             for j in trace.jobs)
