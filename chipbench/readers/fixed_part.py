"""L4_drive_ladder: ``fixed_s`` split by what the host was doing.  Per job,
device 0's idle seconds under the program's span ``cocoa/<span>`` on the
job's own thread (``phases.py``), median over the traced jobs.  With
``less`` and no span: ``fixed_s`` less the medians of those spans, which is
the idle time under any other span or none.  Nothing where the trace holds
no ``cocoa/`` span."""

import statistics

from chipbench import phases
from chipbench.readers import fixed_s


def read(trace, jobs, cell, span=None, less=()):
    ph = phases.load(cell)
    if ph is None or not ph.spanned:
        return None

    def part(name):
        return statistics.median(j["by_span"].get(name, 0.0)
                                 for j in ph.jobs)

    if span is not None:
        return part(span)
    return fixed_s.read(trace, jobs, cell) - sum(part(name) for name in less)
