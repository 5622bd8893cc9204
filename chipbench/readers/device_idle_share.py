"""L0_device: the share of the traced window in which no op ran."""


def read(trace, jobs, cell):
    return 100.0 * trace.idle_share
