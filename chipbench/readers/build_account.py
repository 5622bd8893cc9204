"""How the first job's build divides, and every build outside it:
``setup_s`` by stage of a build, program by program.

The program's tracer keeps one *build record* for every stage of every
build jax runs (``cocoa_tpu.telemetry.tracing``, "The build account":
``trace`` a function to a jaxpr, ``lower`` it to MLIR, ``compile`` it, or
``load`` it where the persistent cache answered), each with the solver
entry's call (``job``) and the innermost cold span (``span``) it fell in.
A build that ran inside another (a traced function's inner ``jit``s) is in
the outer's seconds and leaves no record of its own, so records add up.
Of the process's first job (the warm-up):

    cold_trace_s, cold_lower_s   trace / lower seconds under ``build_start``
                                 and ``build_loop``
    cold_load_s                  compile + load seconds there
    cold_cache_misses            programs of the job compiled and written to
                                 the cache, not loaded from it
    cold_stray_build_s           build seconds of the job under neither
                                 (eager ops, the layout's one-off programs)
    cold_unspanned_s             ``first_job``'s seconds under none of the
                                 job's other cold spans (their union by
                                 start and duration: a span nested in
                                 another counts once)

and of the process:

    setup_outside_build_s        build seconds outside every solver entry
                                 before the second entry call opened (the
                                 generator's, the reference check's)
    retraces_in_window           builds inside an entry's call that opened
                                 no cold span: a warm job that built

This reader's neighbour ``cold_account`` compiles the loop program once
more after the window, outside any entry and after the second: it lands in
none of these.  Nothing where the program keeps no build records (a tree
from before them).

No entry of BENCHMARK.json names a part yet (ROADMAP D10 p): one that does
brings ``layer_metrics/<name>.json`` with ``{"reader": "build_account",
"params": {"part": "<name>"}}``."""

BUILD = ("build_start", "build_loop")
FIRST = "first_job"


def _union_s(intervals, lo, hi):
    """Seconds of [lo, hi] that ``intervals`` (start, end) cover."""
    covered, upto = 0.0, lo
    for start, end in sorted(intervals):
        start, end = max(start, upto), min(end, hi)
        if end > start:
            covered, upto = covered + end - start, end
    return covered


def parts(cold: list, builds: list):
    """The eight numbers from the tracer's cold records and its build
    records, or None where no first job is among them."""
    job = min((r["job"] for r in cold
               if r["phase"] == FIRST and r["job"] is not None), default=None)
    if job is None:
        return None
    spans = [r for r in cold if r["job"] == job]
    first = next(r for r in spans if r["phase"] == FIRST)
    mine = [b for b in builds if b["job"] == job]

    def seconds(records, *stages):
        return sum(b["dur_s"] for b in records if b["stage"] in stages)

    built = [b for b in mine if b["span"] in BUILD]
    end = first["start_s"] + first["dur_s"]
    others = [(r["start_s"], r["start_s"] + r["dur_s"])
              for r in spans if r is not first]
    cold_jobs = {r["job"] for r in cold}
    return {
        "cold_trace_s": seconds(built, "trace"),
        "cold_lower_s": seconds(built, "lower"),
        "cold_load_s": seconds(built, "compile", "load"),
        "cold_cache_misses": sum(b.get("cache") == "miss" for b in mine),
        "cold_stray_build_s": sum(b["dur_s"] for b in mine
                                  if b["span"] not in BUILD),
        "cold_unspanned_s": first["dur_s"] - _union_s(
            others, first["start_s"], end),
        "setup_outside_build_s": sum(
            b["dur_s"] for b in builds
            if b["job"] is None and b["jobs_opened"] < 2),
        "retraces_in_window": sum(
            b["job"] is not None and b["job"] not in cold_jobs
            for b in builds),
    }


def account():
    """The account of this process, from its tracer."""
    try:
        from cocoa_tpu.telemetry import tracing
    except ImportError:
        return None
    tracer = tracing.get_tracer()
    cold, builds = getattr(tracer, "cold", None), getattr(tracer, "builds",
                                                          None)
    if cold is None or builds is None:
        return None
    return parts(list(cold), list(builds))


def read(trace, jobs, cell, part):
    if "build_account" not in cell:
        cell["build_account"] = account()
    found = cell["build_account"]
    return None if found is None else found.get(part)
