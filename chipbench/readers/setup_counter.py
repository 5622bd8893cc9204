"""L5_entry: a counter the harness took from the program's compile log
(``cocoa_tpu.analysis.sanitize.watch_compiles``): ``compile_s`` is the sum
of compile (or cache-load) seconds during set-up, ``compiles_in_window``
the compiles that finished inside the measured window."""


def read(trace, jobs, cell, counter):
    return float(cell["counters"][counter])
