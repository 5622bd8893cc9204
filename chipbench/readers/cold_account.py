"""What the process's first job (the warm-up) paid that a warm one does
not, in bytes and in seconds: ``peak_hbm_gb`` and ``setup_s`` by part.

The program's tracer keeps one record per cold span of that job
(``cocoa_tpu.telemetry.tracing``: ``first_job`` around the solver entry's
call, the layout spans ``order_rows`` / ``row_lengths`` / ``fold_rows``,
the builds ``build_start`` / ``build_loop``), each with the allocator's counters of every device at open and at close.  Of
the device that ends fullest, as ``peak_hbm_gb`` is:

    hbm_entry_gb         the peak when ``first_job`` opens (the generator's)
    hbm_rise_layout_gb   its rise across the layout spans
    hbm_rise_job_gb      the rest of its rise to ``first_job``'s close
    hbm_rise_after_gb    its rise from there to now (the check, warm jobs)
    hbm_resident_gb      ``bytes_in_use`` at ``first_job``'s close
    hbm_program_temp_gb  the loop program's temporaries, by the compiler
    cold_layout_s, cold_build_s, cold_job_s   the spans' seconds

The first four add up to the peak this reader finds when it is first
called, which it reads before anything else.  The account is made once a
run and kept on ``cell``.  Nothing where the program keeps no cold records
(a tree from before them)."""

import sys
import time

LAYOUT = ("order_rows", "row_lengths", "fold_rows")
BUILD = ("build_start", "build_loop")


def parts(records: list, final: dict, temp_bytes=None):
    """The account from the first job's cold ``records``, the peak bytes
    now by device id (``final``; None where a device has no counters) and
    the loop program's temporaries."""
    first = next((r for r in records if r["phase"] == "first_job"), None)
    if first is None:
        return None

    def seconds(phases):
        return sum(r["dur_s"] for r in records if r["phase"] in phases)

    out = {"cold_layout_s": seconds(LAYOUT), "cold_build_s": seconds(BUILD),
           "cold_job_s": first["dur_s"]}
    read = [d["device"] for d in first["hbm_close"]
            if d["peak_bytes_in_use"] is not None
            and final.get(d["device"]) is not None]
    if read:
        fullest = max(read, key=final.get)

        def at(reading, key="peak_bytes_in_use"):
            return next(d[key] for d in reading if d["device"] == fullest)

        entry, close = at(first["hbm_open"]), at(first["hbm_close"])
        layout = sum(at(r["hbm_close"]) - at(r["hbm_open"])
                     for r in records if r["phase"] in LAYOUT)
        out.update(
            hbm_entry_gb=entry / 1e9, hbm_rise_layout_gb=layout / 1e9,
            hbm_rise_job_gb=(close - entry - layout) / 1e9,
            hbm_rise_after_gb=(final[fullest] - close) / 1e9,
            hbm_resident_gb=at(first["hbm_close"], "bytes_in_use") / 1e9)
    if temp_bytes is not None:
        out["hbm_program_temp_gb"] = temp_bytes / 1e9
    return out


def account():
    """The account of this process, from its tracer and its devices."""
    try:
        from cocoa_tpu.telemetry import tracing
    except ImportError:
        return None
    cold = getattr(tracing.get_tracer(), "cold", None)
    if cold is None:
        return None
    import jax

    final = {d.id: (tracing.memory_stats(d) or {}).get("peak_bytes_in_use")
             for d in jax.local_devices()}
    # the process's first job: the warm-up, the solver entry's first call
    job = min((r["job"] for r in cold
               if r["phase"] == "first_job" and r["job"] is not None),
              default=None)
    records = [r for r in cold if job is not None and r["job"] == job]
    temp = None
    loop = next((r for r in records if r["phase"] == "build_loop"), None)
    if loop is not None:
        t0 = time.perf_counter()
        try:
            sizes = tracing.program_memory(loop)
        except Exception as e:      # a reader reports nothing, never fails
            sizes = None
            print(f"cold_account: program_memory failed: {e!r}",
                  file=sys.stderr)
        took = time.perf_counter() - t0
        if sizes is not None:
            temp = sizes["temp"]
            print(f"cold_account: program_memory of the loop took "
                  f"{took:.3f} s: {sizes}", file=sys.stderr)
    return parts(records, final, temp)


def read(trace, jobs, cell, part):
    if "cold_account" not in cell:
        cell["cold_account"] = account()
    found = cell["cold_account"]
    return None if found is None else found.get(part)
