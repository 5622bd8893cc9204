"""python -m chipbench.run --workload CELL --seed N --seconds S --trace 0|1

One process, which alone touches JAX.  It refuses to start without the
chips the cell asks for (no CPU fallback), builds the cell's data on the
device from ``--seed``, runs one warm-up job (which compiles or loads the
cell's executables, counted as set-up), checks that job against the plain
reference, then runs whole jobs back to back — a trainer is one caller
that waits for each result — until ``--seconds`` have passed; the job in
flight finishes and counts.  The last line of stdout is the result.

``--trace 1`` is a run of its own: the same set-up, then ``jax.profiler``
around a short window of whole jobs, the reducer, and the cell's
per-layer metrics with a breakdown of where the device's time went.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()        # process start, as near as Python can say

import argparse                 # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import statistics               # noqa: E402
import sys                      # noqa: E402
import traceback                # noqa: E402

from chipbench import registry  # noqa: E402

TRACE_WINDOW_S = 5.0            # a traced window: whole jobs for about this
TRACE_MIN_JOBS = 2              # long, and never fewer than this many


def job_arguments(cell: dict) -> tuple:
    """``(params, debug, kwargs, H)``: what the job's file and the
    configuration's sizes hand the program's library entry."""
    from cocoa_tpu.config import DebugParams, Params

    cfg, job = cell["config"], cell["job"]
    h = max(1, int(cfg["local_iter_frac"] * cfg["n"] / cfg["num_splits"]))
    params = Params(**{**job.get("params", {}), "n": cfg["n"],
                       "local_iters": h, "lam": cfg["lambda"],
                       "loss": registry.loss_of(cell)})
    return (params, DebugParams(**job.get("debug", {})),
            dict(job.get("kwargs", {})), h)


def make_job(cell: dict, ds, mesh):
    """The cell's job as a function of nothing: one call trains from
    alpha = 0, w = 0 through the program's library entry, waits for the
    result and reads the trajectory.  The sampling seed is the job's (the
    CLI's default where its flag line names none), not ``--seed``: the
    program bakes it into its device loop, so a seed of its own for every
    run would compile that loop anew in every run."""
    import jax

    from cocoa_tpu import solvers

    entry = getattr(solvers, cell["job"]["entry"])
    params, debug, kwargs, h = job_arguments(cell)
    if mesh is not None:
        kwargs["mesh"] = mesh
    span = jax.profiler.TraceAnnotation

    def run_once() -> dict:
        with span("job"):
            t0 = time.perf_counter()
            with span("job/call"):
                out = entry(ds, params, debug, **kwargs)
            w, traj = out[0], out[-1]
            alpha = out[1] if len(out) == 3 else None
            with span("job/wait"):
                jax.block_until_ready((w, alpha))
            with span("job/fetch"):
                rounds = int(traj.records[-1].round) if traj.records else 0
            wall = time.perf_counter() - t0
        return dict(w=w, alpha=alpha, traj=traj, rounds=rounds, wall_s=wall,
                    solver_path=traj.meta.get("solver_path"))

    return run_once, h


def placement_problems(cell: dict, ds, run: dict) -> list:
    """The job ran where and how the cell says: the data spans the cell's
    chips, and the local solver resolved to the path the job names."""
    problems = []
    spans = len(ds.labels.sharding.device_set)
    if spans != cell["chips"]:
        problems.append(f"the data spans {spans} device(s), the cell asks "
                        f"for {cell['chips']}")
    if run["alpha"] is not None and \
            len(run["alpha"].sharding.device_set) != cell["chips"]:
        problems.append("alpha does not span the cell's chips")
    path = run["solver_path"] or {}
    for key, want in cell["job"].get("expect_path", {}).items():
        if path.get(key) != want:
            problems.append(f"solver_path.{key} is {path.get(key)!r}, the "
                            f"job expects {want!r} ({path})")
    if path and path.get("devices") != cell["chips"]:
        problems.append(f"solver_path.devices is {path.get('devices')}")
    return problems


def run_window(run_once, job_problem, seconds: float, min_jobs: int = 1):
    """Whole jobs, back to back, until ``seconds`` have passed."""
    jobs, failed = [], []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds or len(jobs) < min_jobs:
        try:
            run = run_once()
            why = job_problem(run)
        except Exception as e:  # the window goes on: a job that raises
            traceback.print_exc()      # is a failed job, reported as one
            run, why = dict(wall_s=None, rounds=0), repr(e)
        if why:
            failed.append(why)
        jobs.append(dict(wall_s=run["wall_s"], rounds=run["rounds"]))
    return jobs, failed, time.perf_counter() - t0


def device_record(devices, used) -> dict:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used]
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def end_to_end(jobs: list, setup_s: float, device: dict) -> dict:
    """Every end-to-end number a run can report; the cell's entries in
    BENCHMARK.json choose among them."""
    ok = [j for j in jobs if j["wall_s"] is not None]
    return {
        "job_s": statistics.median(j["wall_s"] for j in ok) if ok else None,
        "comm_rounds": (statistics.median(j["rounds"] for j in ok)
                        if ok else None),
        "peak_hbm_gb": device["memory_peak_bytes"] / 1e9,
        "setup_s": setup_s,
    }


def run_cell(bench: dict, cell: dict, *, seed: int, seconds: float,
             trace: bool, marks: list = None, out_dir: str = None) -> dict:
    """Set up, warm up, check, measure.  Returns the result line as a dict
    (``detail`` holds what the line does not).  ``marks`` are the caller's
    ``(phase that ended, perf_counter)`` reads since process start; set-up
    is timed from the first."""
    import jax

    from cocoa_tpu.analysis import sanitize
    from cocoa_tpu.parallel import make_mesh
    from cocoa_tpu.utils import compile_cache

    marks = list(marks or [("start", time.perf_counter())])

    def mark(phase: str):
        marks.append((phase, time.perf_counter()))

    cache_dir = compile_cache.enable()
    devices = jax.devices()
    chips = cell["chips"]
    if len(devices) < chips:
        raise RuntimeError(f"cell {cell['name']} needs {chips} device(s), "
                           f"JAX found {len(devices)}")
    mesh = make_mesh(chips) if chips > 1 else None
    gen = registry.load_module(bench, "generators",
                               cell["config"]["generator"])
    check = registry.load_module(bench, "checks", cell["job"]["check"])
    mark("resolve")
    with sanitize.watch_compiles() as setup_compiles:
        ds = gen.make(cell["config"], seed, mesh)
        jax.block_until_ready(ds.labels)
        mark("data")
        run_once, h = make_job(cell, ds, mesh)
        warm = run_once()
        mark("warmup_job")
        audit = check.audit(cell, ds, warm)
        mark("reference_check")
    problems = audit["problems"] + placement_problems(cell, ds, warm)
    solver_path = warm["solver_path"]
    warmup = {"wall_s": warm["wall_s"], "rounds": warm["rounds"],
              "trajectory": [[r.round, r.primal, r.gap]
                             for r in warm["traj"].records]}
    cell = {**cell, "local_iters": h, "solver_path": solver_path,
            "device_kind": devices[0].device_kind}
    del warm

    def job_problem(run):
        return check.job_problem(cell["job"], run)

    mark("rest")
    setup_s = marks[-1][1] - marks[0][1]
    setup_phases = {name + "_s": t - prev for (_, prev), (name, t)
                    in zip(marks, marks[1:])}
    trace_dir = None
    with sanitize.watch_compiles() as window_compiles:
        if trace:
            trace_dir = os.path.join(out_dir or bench["_dir"], "out",
                                     f"{cell['name']}.trace")
            jax.profiler.start_trace(trace_dir)
            try:
                with jax.profiler.TraceAnnotation("window"):
                    jobs, failed, window_s = run_window(
                        run_once, job_problem, min(seconds, TRACE_WINDOW_S),
                        TRACE_MIN_JOBS)
            finally:
                jax.profiler.stop_trace()
        else:
            jobs, failed, window_s = run_window(run_once, job_problem,
                                                seconds)
    device = device_record(devices, devices[:chips])
    counters = {
        "compile_s": sum(c.seconds for c in setup_compiles),
        "compiles_in_setup": len(setup_compiles),
        "compiles_in_window": len(window_compiles),
        "window_compiles": [c.name for c in window_compiles][:20],
        "longest_compiles": sorted(
            ((c.seconds, c.name) for c in setup_compiles), reverse=True)[:5],
    }
    cell["counters"] = counters
    values = end_to_end(jobs, setup_s, device)
    detail = {
        "cell": cell["name"], "seed": seed, "seconds": seconds,
        "window_s": window_s, "jobs": jobs, "problems": problems,
        "failed": failed[:10], "audit": audit, "warmup": warmup,
        "solver_path": solver_path, "local_iters": h, "counters": counters,
        "cache_dir": cache_dir, "end_to_end": values,
        "setup_phases": setup_phases,
        "versions": {"jax": jax.__version__},
    }
    result = {"correct": not problems and not failed,
              "attempted": len(jobs), "failed": len(failed)}
    wanted = registry.metrics_of(bench, "end_to_end", cell["name"])
    if trace:
        from chipbench import reduce_trace

        summary = reduce_trace.summarize_file(trace_dir)
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        values = {m["name"]: read(summary, jobs, cell, **params) for
                  m, read, params in registry.layer_readers(bench,
                                                            cell["name"])}
        wanted = registry.metrics_of(bench, "per_layer", cell["name"])
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in summary.gaps[:10]]}
    # a reader that found nothing to read returned None: leave it out
    result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                     "unit": m["unit"]}
                         for m in wanted if values.get(m["name"]) is not None}
    result["device"] = device
    result["detail"] = detail
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = registry.load_benchmark()
    cell = registry.resolve_cell(bench, args.workload)
    seconds = bench["run_seconds"] if args.seconds is None else args.seconds
    try:
        import jax

        import cocoa_tpu  # noqa: F401
    except ImportError as e:
        print(f"chipbench: the program under test is not here ({e}); run "
              f"from the root of a checkout of the repository",
              file=sys.stderr)
        return 2
    marks = [("start", T0), ("imports", time.perf_counter())]
    devices = jax.devices()
    marks.append(("backend", time.perf_counter()))
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"chipbench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found platform {devices[0].platform!r} "
              f"({devices[0].device_kind} x {len(devices)}).  There is no "
              f"CPU fallback: a time from another device is not this "
              f"benchmark's number.", file=sys.stderr)
        return 2
    from chipbench import cost_model

    cost_model.peaks_for(devices[0].device_kind)   # unknown chip: an error
    result = run_cell(bench, cell, seed=args.seed, seconds=seconds,
                      trace=bool(args.trace), marks=marks)
    detail = result.pop("detail")
    out = os.path.join(bench["_dir"], "out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, f"{cell['name']}.trace{args.trace}.json"),
              "w") as f:
        json.dump({**result, "detail": detail}, f, indent=1, default=str)
    print(json.dumps({"detail": detail}, default=str))
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
