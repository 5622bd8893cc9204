"""The cold path's spans (telemetry/tracing.py ``cold_span``): what a
process's first job pays that a warm one does not, in seconds and in
bytes, and that a warm job pays nothing for it.

A first job leaves records on ``Tracer.cold`` (one job ordinal, a parent
chain up to ``first_job``, an HBM reading at each open and close); a warm
job of the same dataset leaves none, reads no allocator and opens the
annotations it opened before this module existed.  ``memory_stats`` is a
fake that counts its calls: the CPU backend has no counters of its own.
"""

import json
import logging
import re

import jax.numpy as jnp
import numpy as np
import pytest

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data import shard_dataset
from cocoa_tpu.data.synth import synth_sparse
from cocoa_tpu.ops import rows
from cocoa_tpu.solvers import base, cocoa, dist_gd, run_cocoa, sgd
from cocoa_tpu.solvers.dist_gd import run_dist_gd
from cocoa_tpu.solvers.sgd import run_sgd
from cocoa_tpu.telemetry import events as tele_events
from cocoa_tpu.telemetry import schema as tele_schema
from cocoa_tpu.telemetry import tracing

K = 4
_DBG = DebugParams(debug_iter=5, seed=0)
_JOB = dict(quiet=True, math="fast", device_loop=True, rng="permuted")
# the annotations of a warm --deviceLoop job from nothing, in the order
# they open: what the tree before the cold spans opened (PR 35's ladder)
WARM_ANNOTATIONS = ["cocoa/init_state", "cocoa/wait_indices",
                    "cocoa/local_solve", "cocoa/dispatch", "cocoa/fetch",
                    "cocoa/decode_trajectory"]


class _Hbm:
    """Stands in for ``tracing.memory_stats``: counts its calls, and every
    call finds 1,000 bytes more in use than the last (the peak with it)."""

    def __init__(self):
        self.calls = 0

    def __call__(self, device):
        self.calls += 1
        used = 1000 * self.calls
        return {"bytes_in_use": used, "peak_bytes_in_use": used + 500,
                "bytes_limit": 1 << 30}     # more keys than a reading keeps


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: the names opened
    and closed."""

    def __init__(self):
        self.opened, self.closed = [], []

    def __call__(self, name):
        outer = self

        class _One:
            def __enter__(self):
                outer.opened.append(name)

            def __exit__(self, *exc):
                outer.closed.append(name)

        return _One()


_CACHES = (cocoa._START_PROGRAMS, cocoa._CHUNK_STEPS, base._DEVICE_RUNS,
           sgd._CHUNK_STEPS, dist_gd._CHUNK_STEPS)


@pytest.fixture(autouse=True)
def cold_process(monkeypatch):
    """Every test starts as a process that has run no job: an empty tracer
    and empty program caches (what they held is put back afterwards)."""
    tele_events.get_bus().reset()
    tracing.reset()
    kept = [dict(cache) for cache in _CACHES]
    for cache in _CACHES:
        cache.clear()
    hbm = _Hbm()
    monkeypatch.setattr(tracing, "memory_stats", hbm)
    yield hbm
    for cache, was in zip(_CACHES, kept):
        cache.clear()
        cache.update(was)
    tele_events.get_bus().reset()
    tracing.reset()


def _dense(tiny_data, dtype=jnp.float64):
    return shard_dataset(tiny_data, k=K, layout="dense", dtype=dtype)


def _svm(ds, **kw):
    params = Params(n=ds.n, num_rounds=20, local_iters=12, lam=1e-2)
    w, alpha, traj = run_cocoa(ds, params, _DBG, plus=True,
                               **{**_JOB, "gap_target": 1e-6, **kw})
    return np.asarray(w), np.asarray(alpha), traj


def _by_phase(records):
    out = {}
    for r in records:
        out.setdefault(r["phase"], []).append(r)
    return out


def _reaches(record, root, by_id):
    hops = 0
    while record is not None and hops < 16:
        if record["span_id"] == root["span_id"]:
            return True
        record, hops = by_id.get(record["parent_id"]), hops + 1
    return False


def test_first_job_of_a_dense_set_leaves_its_cold_spans(tiny_data,
                                                        cold_process):
    ds = _dense(tiny_data, jnp.float32)
    _, _, traj = _svm(ds, pallas=True)
    cold = list(tracing.get_tracer().cold)
    phases = _by_phase(cold)
    assert set(phases) == {"first_job", "fold_rows", "build_start",
                           "resolve_path", "build_loop", "first_run"}
    assert all(len(v) == 1 for v in phases.values())
    # they close in the order the job walks them
    assert [r["phase"] for r in cold] == [
        "build_start", "resolve_path", "fold_rows", "build_loop",
        "first_run", "first_job"]
    # one job, one ordinal; every span under first_job, which closes last
    assert {r["job"] for r in cold} == {1}
    (first,) = phases["first_job"]
    assert first["parent_id"] is None and cold[-1] is first
    by_id = {r["span_id"]: r for r in cold}
    assert all(_reaches(r, first, by_id) for r in cold)
    for r in cold:
        assert first["start_s"] <= r["start_s"]
        assert r["start_s"] + r["dur_s"] <= \
            first["start_s"] + first["dur_s"] + 1e-6
    # build_loop ends where first_run starts: the first dispatch's return
    (build,), (ran,) = phases["build_loop"], phases["first_run"]
    assert build["start_s"] + build["dur_s"] <= ran["start_s"]
    # an HBM reading at every open and close, of the data's one device
    assert cold_process.calls == 2 * len(cold)
    for r in cold:
        (opened,), (closed,) = r["hbm_open"], r["hbm_close"]
        assert opened["device"] == closed["device"] == \
            next(iter(ds.labels.sharding.device_set)).id
        assert set(opened) == {"device", *tracing.HBM_KEYS}
        assert closed["bytes_in_use"] > opened["bytes_in_use"]
    # the programs kept as shapes, and only where one was built and called
    assert {p for p, (r,) in phases.items() if r["program"] is not None} \
        == {"build_start", "build_loop"}
    # what the job's result says of it
    meta = {c["phase"]: c for c in traj.meta["cold"]}
    assert set(meta) == set(phases)
    assert meta["first_job"]["dur_s"] == first["dur_s"]
    assert meta["fold_rows"]["peak_rise"] == 1000


def test_warm_job_appends_nothing_reads_nothing_and_opens_what_it_did(
        tiny_data, cold_process, monkeypatch):
    ds = _dense(tiny_data)
    w1, a1, _ = _svm(ds)
    tracer = tracing.get_tracer()
    n_records, n_calls = len(tracer.cold), cold_process.calls
    assert n_records and n_calls
    notes = _Annotations()
    monkeypatch.setattr(tracing, "TraceAnnotation", notes)
    w2, a2, traj = _svm(ds)
    assert len(tracer.cold) == n_records and cold_process.calls == n_calls
    assert traj.meta["cold"] == []
    assert notes.opened == WARM_ANNOTATIONS
    np.testing.assert_array_equal(w1, w2)
    np.testing.assert_array_equal(a1, a2)
    # a second dataset of the same shapes: the programs are warm, its fold
    # is not (here XLA's fori path folds nothing: nothing is cold)
    _, _, traj3 = _svm(_dense(tiny_data))
    assert traj3.meta["cold"] == [] and len(tracer.cold) == n_records
    # and with the kernel that reads folded rows, the fold alone
    ds32 = _dense(tiny_data, jnp.float32)
    _svm(ds32, pallas=True)
    _, _, traj5 = _svm(_dense(tiny_data, jnp.float32), pallas=True)
    assert [c["phase"] for c in traj5.meta["cold"]] == ["fold_rows",
                                                        "first_job"]
    assert {r["job"] for r in tracer.cold} == {1, 4, 5}


def test_console_line_follows_the_drive_ladders(tiny_data, capsys):
    ds = _dense(tiny_data)
    _svm(ds, quiet=False)
    out = capsys.readouterr().out.splitlines()
    (at,) = [i for i, ln in enumerate(out) if ln.startswith("drive ladder:")]
    assert out[at + 1].startswith("cold path: first_job ")
    assert "build_loop" in out[at + 1] and "(peak +" in out[at + 1]
    _svm(ds, quiet=False)
    assert "cold path:" not in capsys.readouterr().out


def test_sparse_set_past_one_row_block_leaves_order_rows(monkeypatch):
    ds = shard_dataset(synth_sparse(1102, 300, nnz_mean=6, seed=3), k=K,
                       layout="sparse", dtype=jnp.float32)
    assert ds.row_order is None
    monkeypatch.setattr(rows, "GATHER_BLOCK_SLOTS",
                        ds.sp_indices.shape[-1] * 40)
    _, _, traj = _svm(ds)
    assert ds.row_order is not None
    phases = [c["phase"] for c in traj.meta["cold"]]
    assert phases[0] == "order_rows" and phases[-1] == "first_job"
    assert "fold_rows" not in phases
    _, _, warm = _svm(ds)
    assert warm.meta["cold"] == []


def test_sparse_kernel_job_leaves_row_lengths():
    ds = shard_dataset(synth_sparse(200, 64, nnz_mean=5, seed=1), k=K,
                       layout="sparse", dtype=jnp.float32)
    _, _, traj = _svm(ds, pallas=True)
    assert "row_lengths" in [c["phase"] for c in traj.meta["cold"]]
    _, _, warm = _svm(ds, pallas=True)
    assert warm.meta["cold"] == []


@pytest.mark.parametrize("entry", ["sgd", "local_sgd", "dist_gd"])
def test_primal_entries_wear_first_job_too(tiny_data, entry, cold_process):
    ds = _dense(tiny_data)
    params = Params(n=ds.n, num_rounds=10, local_iters=8, lam=1e-2)

    def job():
        if entry == "dist_gd":
            return run_dist_gd(ds, params, _DBG, quiet=True,
                               device_loop=True)
        return run_sgd(ds, params, _DBG, entry == "local_sgd", quiet=True,
                       device_loop=True, rng="permuted")

    job()
    cold = list(tracing.get_tracer().cold)
    assert [r["phase"] for r in cold] == ["build_loop", "first_run",
                                          "first_job"]
    assert {r["job"] for r in cold} == {1}
    assert all(r["parent_id"] == cold[-1]["span_id"] for r in cold[:-1])
    calls = cold_process.calls
    job()
    assert len(tracing.get_tracer().cold) == 3
    assert cold_process.calls == calls


@pytest.mark.parametrize("dies_in", ["build_start", "build_loop",
                                     "first_run"])
def test_a_job_that_raises_closes_its_cold_spans(tiny_data, monkeypatch,
                                                 dies_in):
    """A phase that died leaves its record, with ``error``, as a span that
    raised leaves its event; ``first_job`` closes with it, and nothing
    stays on the stack for the next call."""
    def boom(*a, **kw):
        raise RuntimeError("not today")

    if dies_in == "build_start":
        monkeypatch.setattr(cocoa.jax, "jit", boom)
    elif dies_in == "build_loop":
        monkeypatch.setattr(base, "_build_device_run", lambda *a, **kw: boom)
    else:
        monkeypatch.setattr(base, "fetch_loop_result", boom)
    notes = _Annotations()
    monkeypatch.setattr(tracing, "TraceAnnotation", notes)
    with pytest.raises(RuntimeError):
        _svm(_dense(tiny_data))
    cold = tracing.get_tracer().cold
    assert [r["phase"] for r in cold][-2:] == [dies_in, "first_job"]
    assert cold[-2]["error"] == cold[-1]["error"] == "RuntimeError"
    assert all("error" not in r for r in list(cold)[:-2])
    assert notes.opened.count("cocoa/" + dies_in) == notes.closed.count(
        "cocoa/" + dies_in) == 1
    assert sorted(notes.opened) == sorted(notes.closed)
    assert tracing.get_tracer()._stack() == []


def test_reset_clears_and_the_cap_drops_the_oldest():
    tracer = tracing.get_tracer()
    for i in range(tracing.COLD_CAP + 3):
        with tracing.cold_span("fold_rows", nth=i):
            pass
    assert len(tracer.cold) == tracing.COLD_CAP
    assert tracer.cold[0]["nth"] == 3
    assert tracer.cold[-1]["nth"] == tracing.COLD_CAP + 2
    assert tracer.cold[0]["job"] is None        # outside any solver entry
    tracing.reset()
    assert len(tracer.cold) == 0


def test_cold_spans_nest_by_the_stack_that_spans_use():
    with tracing.cold_span("order_rows") as outer:
        with tracing.cold_span("row_lengths") as inner:
            with tracing.cold_span("fold_rows") as leaf:
                pass
        with tracing.cold_span("build_loop") as after:
            pass
    assert outer.parent is None and inner.parent == outer.sid
    assert leaf.parent == inner.sid and after.parent == outer.sid
    assert tracing.get_tracer()._stack() == []


def test_program_memory_gives_the_compilers_sizes(tiny_data):
    ds = _dense(tiny_data, jnp.float32)
    _svm(ds)
    phases = _by_phase(tracing.get_tracer().cold)
    (loop,), (start,) = phases["build_loop"], phases["build_start"]
    sizes = tracing.program_memory(loop)
    assert set(sizes) == {"argument", "output", "alias", "temp",
                          "generated_code"}
    # the loop reads the rows and hands back (w, alpha) in their donated
    # buffers; the start program reads nothing and makes them
    assert sizes["argument"] >= ds.X.nbytes
    state = 4 * (ds.num_features + K * ds.n_shard)
    assert sizes["alias"] == state
    made = tracing.program_memory(start)
    assert made["argument"] == 0 and made["output"] >= state
    assert tracing.program_memory(phases["first_job"][0]) is None
    # kept as shapes: no array, so no device memory, rides the record
    import jax

    fn, args = loop["program"]
    leaves = jax.tree.leaves(args)
    assert not any(isinstance(a, jax.Array) for a in leaves)
    # lowered as the job's own call was, so the persistent compile cache
    # hands the job's executable back: an array that was placed keeps its
    # sharding, one the runtime put where it liked (here all of them, and
    # the NumPy spec) names none
    assert all(a.sharding is None for a in leaves)
    device = jax.devices()[0]
    placed = tracing._struct(jax.device_put(jnp.zeros(3), device))
    assert placed.sharding.device_set == {device}
    assert tracing._struct(jnp.zeros(3)).sharding is None


def test_armed_cold_job_equals_a_disarmed_one_bit_for_bit(tiny_data,
                                                          tmp_path):
    """A cold span waits and reads counters; it never touches a value: an
    armed run's (w, alpha) are the disarmed run's, and its cold spans are
    schema-valid ``span`` events that carry the records' numbers."""
    path = tmp_path / "events.jsonl"
    tele_events.get_bus().configure(jsonl_path=str(path))
    tracing.configure(enabled=True, worker=0)
    w1, a1, t1 = _svm(_dense(tiny_data, jnp.float32), pallas=True)
    tele_events.get_bus().reset()
    lines = [json.loads(ln) for ln in open(path)]
    spans = [e for e in lines if e["event"] == "span"]
    cold = [e for e in spans if "hbm_open" in e]
    assert {e["phase"] for e in cold} == {c["phase"]
                                          for c in t1.meta["cold"]}
    assert tele_schema.check_event_lines(
        list(enumerate(lines, 1))) == []
    by_id = {e["span_id"]: e for e in spans}
    (first,) = [e for e in cold if e["phase"] == "first_job"]
    # armed, the ladder's own spans are on the stack too: build_start ran
    # inside init_state, which opened before the job knew it was cold
    (start,) = [e for e in cold if e["phase"] == "build_start"]
    assert by_id[start["parent_id"]]["phase"] == "init_state"
    (fold,) = [e for e in cold if e["phase"] == "fold_rows"]
    assert fold["parent_id"] == first["span_id"] and fold["job"] == 1
    broken = dict(fold, hbm_close=[{"device": "zero"}], seq=10 ** 6)
    assert tele_schema.check_event_lines([(1, broken)])

    tracing.reset()
    for cache in _CACHES:
        cache.clear()
    w2, a2, t2 = _svm(_dense(tiny_data, jnp.float32), pallas=True)
    assert [c["phase"] for c in t2.meta["cold"]] == \
        [c["phase"] for c in t1.meta["cold"]]
    np.testing.assert_array_equal(w1, w2)
    np.testing.assert_array_equal(a1, a2)
    assert [(r.round, r.primal, r.gap) for r in t1.records] == \
        [(r.round, r.primal, r.gap) for r in t2.records]


# -- the build account (telemetry/tracing.py): one record a stage of every
# build, from jax.monitoring's listeners, on the span it fell in ---------

BACKEND = {"compile", "load"}   # the suite's compile cache may answer


def _program():
    """A jitted function no process has called: two inner ``jit``s inside
    an outer one, so the outer's trace holds theirs."""
    import jax

    inner = jax.jit(lambda x: jnp.sin(x) @ x)
    return jax.jit(lambda x: inner(x) + jnp.matmul(x, x))


def _stages(builds):
    return [b["stage"] if b["stage"] not in BACKEND else "backend"
            for b in builds]


def test_a_jit_first_called_in_a_cold_span_leaves_its_build_records():
    tracer, run = tracing.get_tracer(), _program()
    x = jnp.ones((8, 8), jnp.float32)
    run.__wrapped__.__name__ = "probe_run"
    seen = len(tracer.builds)
    with tracing.cold_span("build_loop") as cold:
        run(x).block_until_ready()
    record = tracer.cold[-1]
    mine = [b for b in record["builds"] if b["fun_name"] == "probe_run"]
    assert _stages(mine) == ["trace", "lower", "backend"]
    assert all(b["span"] == "build_loop" and b["job"] is None for b in mine)
    assert [b["order"] for b in mine] == sorted(b["order"] for b in mine)
    assert all(b in tracer.builds for b in mine)
    assert len(tracer.builds) - seen == len(record["builds"])
    # the sums are the records', stage by stage
    for stage in tracing.BUILD_STAGES:
        assert record[stage + "_s"] == pytest.approx(sum(
            b["dur_s"] for b in record["builds"] if b["stage"] == stage))
    assert record["trace_s"] > 0 and record["lower_s"] > 0
    assert record["compile_s"] + record["load_s"] > 0
    assert record["trace_s"] + record["lower_s"] + record["compile_s"] \
        + record["load_s"] <= record["dur_s"]
    assert cold.builds is record["builds"]
    # inner jits are in the outer's seconds, once: no record of their own,
    # counted on the outer's, their seconds no more than its
    (trace,) = [b for b in mine if b["stage"] == "trace"]
    assert trace["inner"] >= 2 and 0 < trace["inner_s"] <= trace["dur_s"]
    assert not any(b["fun_name"] in ("sin", "matmul", "<lambda>")
                   and b["stage"] == "trace" and b is not trace
                   and trace["start_ts"] <= b["start_ts"]
                   <= trace["start_ts"] + trace["dur_s"]
                   for b in tracer.builds)
    # a second call builds nothing: no record, no listener call
    n, calls = len(tracer.builds), tracer.listener_calls
    with tracing.cold_span("build_loop"):
        run(x).block_until_ready()
    assert tracer.cold[-1]["builds"] == []
    assert (len(tracer.builds), tracer.listener_calls) == (n, calls)
    assert tracer.listener_error is None


def test_nested_cold_spans_each_hold_the_build_and_name_the_innermost():
    run = _program()
    with tracing.cold_span("order_rows"):
        with tracing.cold_span("fold_rows"):
            run(jnp.ones((4, 4), jnp.float32))
    inner, outer = list(tracing.get_tracer().cold)[-2:]
    assert inner["phase"] == "fold_rows" and outer["phase"] == "order_rows"
    assert inner["builds"] and inner["builds"] == outer["builds"]
    assert {b["span"] for b in inner["builds"]} == {"fold_rows"}
    assert outer["trace_s"] == inner["trace_s"]


_SECOND_PROCESS = """
import json, sys
from cocoa_tpu.utils import compile_cache
compile_cache.enable()
import jax, jax.numpy as jnp
from cocoa_tpu.telemetry import tracing

def cached_probe(x):
    return jnp.tanh(x) @ x + 3.0

x = jnp.ones((16, 16), jnp.float32)        # its own eager programs: before
with tracing.cold_span("build_loop"):
    jax.jit(cached_probe)(x).block_until_ready()
r = tracing.get_tracer().cold[-1]
mine = [b for b in r["builds"] if b["fun_name"] == "cached_probe"]
print(json.dumps({"stages": [b["stage"] for b in mine],
                  "cache": [b.get("cache") for b in mine],
                  "misses": sum(b.get("cache") == "miss" for b in mine),
                  "load_s": sum(b["dur_s"] for b in mine
                                if b["stage"] == "load"),
                  "line": tracing.cold_line(tracing.cold_summary([r]))}))
"""


def test_a_second_process_loads_what_the_first_compiled(tmp_path):
    """With a persistent cache directory the first process's backend build
    is a ``compile`` that wrote an entry (a miss), the second's a ``load``
    and no miss; the console's line says ``compiled`` and ``load``."""
    import os
    import subprocess
    import sys

    env = {**os.environ, "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc"),
           "JAX_PLATFORMS": "cpu"}
    env.pop("COCOA_NO_COMPILE_CACHE", None)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    def process():
        out = subprocess.run([sys.executable, "-c", _SECOND_PROCESS],
                             env=env, cwd=root, capture_output=True,
                             text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    first, second = process(), process()
    assert first["stages"] == ["trace", "lower", "compile"]
    assert first["misses"] == 1 and first["cache"][-1] == "miss"
    assert "compiled 1 in " in first["line"] and "load" not in first["line"]
    assert second["stages"] == ["trace", "lower", "load"]
    assert second["misses"] == 0 and second["cache"][-1] == "hit"
    assert second["load_s"] > 0
    assert "load " in second["line"] and "compiled" not in second["line"]


def test_a_build_in_an_entry_under_no_cold_span_is_named_with_its_job(
        capsys):
    run = _program()
    run.__wrapped__.__name__ = "late_shape"

    @tracing.cold_entry
    def entry(ds, quiet=False):
        run(jnp.ones((3, 3), jnp.float32))
        strays = tracing.stray_builds()
        for build in strays:
            print(tracing.stray_line(build))
        return strays

    assert tracing.stray_builds() == []         # outside any entry
    entry(None)
    strays = entry(None)
    assert strays == []                         # warm: nothing built
    out = capsys.readouterr().out.splitlines()
    named = [ln for ln in out if "late_shape" in ln]
    assert [ln.split()[6] for ln in named][:2] == ["trace", "lower"]
    assert all(ln.startswith("built outside the cold path: late_shape ")
               and ln.endswith(" s") for ln in named) and len(named) == 3
    mine = [b for b in tracing.get_tracer().builds
            if b["fun_name"] == "late_shape"]
    assert [(b["job"], b["span"], b["jobs_opened"]) for b in mine] \
        == [(1, None, 1)] * 3
    assert list(tracing.get_tracer().cold) == []     # and no cold span


def test_a_listeners_exception_never_reaches_jax(monkeypatch):
    tracer = tracing.get_tracer()

    def boom(*a, **kw):
        raise RuntimeError("observer down")

    monkeypatch.setattr(tracer, "_build_closes", boom)
    monkeypatch.setattr(tracer, "_build_opens", boom)
    out = _program()(jnp.ones((5, 5), jnp.float32))
    assert float(out[0, 0]) == pytest.approx(np.sin(1.0) * 5 + 5)
    assert "observer down" in tracer.listener_error
    assert len(tracer.builds) == 0
    # a watcher that raises is the listener's to swallow too
    monkeypatch.undo()
    tracing.watch_builds(boom)
    try:
        _program()(jnp.ones((6, 6), jnp.float32))
    finally:
        tracing.unwatch_builds(boom)
    assert len(tracer.builds) > 0


def test_reset_leaves_one_registration():
    from jax._src import monitoring

    def registered():
        return [
            monitoring.get_scalar_listeners().count(tracing._on_scalar),
            monitoring.get_event_time_span_listeners().count(
                tracing._on_time_span),
            monitoring.get_event_listeners().count(tracing._on_event),
            monitoring.get_event_duration_listeners().count(
                tracing._on_duration)]

    assert registered() == [1, 1, 1, 1]
    for _ in range(3):
        tracing.reset()
        tracing.observe_builds()
    assert registered() == [1, 1, 1, 1]
    tracing.observe_builds(False)
    try:
        assert registered() == [0, 0, 0, 0]
        _program()(jnp.ones((7, 7), jnp.float32))
        assert len(tracing.get_tracer().builds) == 0
    finally:
        tracing.reset()             # puts the registration back
    assert registered() == [1, 1, 1, 1]


def test_ten_warm_jobs_call_no_listener_and_open_what_they_did(
        tiny_data, cold_process, monkeypatch):
    ds = _dense(tiny_data)
    _svm(ds)
    tracer = tracing.get_tracer()
    calls, n_builds, n_cold = (tracer.listener_calls, len(tracer.builds),
                               len(tracer.cold))
    assert calls > 0 and n_builds > 0
    notes = _Annotations()
    monkeypatch.setattr(tracing, "TraceAnnotation", notes)
    reads = cold_process.calls
    for _ in range(10):
        _, _, traj = _svm(ds)
        assert traj.meta["cold"] == []
    assert tracer.listener_calls == calls
    assert (len(tracer.builds), len(tracer.cold)) == (n_builds, n_cold)
    assert notes.opened == WARM_ANNOTATIONS * 10
    assert cold_process.calls == reads
    assert tracer.jobs_opened == 11


def test_first_job_shows_the_split_on_console_meta_and_span_event(
        tiny_data, tmp_path, capsys):
    path = tmp_path / "events.jsonl"
    tele_events.get_bus().configure(jsonl_path=str(path))
    tracing.configure(enabled=True, worker=0)
    _, _, traj = _svm(_dense(tiny_data), quiet=False)
    tele_events.get_bus().reset()
    meta = {c["phase"]: c for c in traj.meta["cold"]}
    loop, first = meta["build_loop"], meta["first_job"]
    assert loop["builds"] == 3 and loop["trace_s"] > 0 and loop["lower_s"] > 0
    assert loop["compile_s"] + loop["load_s"] > 0
    assert loop["cache_misses"] in (0, 1)
    # first_job holds its children's builds: the whole job's account
    assert first["builds"] >= loop["builds"] + meta["build_start"]["builds"]
    assert first["trace_s"] >= loop["trace_s"]
    assert meta["first_run"]["builds"] == 0
    (line,) = [ln for ln in capsys.readouterr().out.splitlines()
               if ln.startswith("cold path:")]
    assert f"build_loop {loop['dur_s']:.3f} s (trace {loop['trace_s']:.3f}, " \
           f"lower {loop['lower_s']:.3f}, " in line
    assert ("load " in line) or ("compiled " in line)
    ran = "first_run %.3f s" % meta["first_run"]["dur_s"]
    assert ran in line and ran + " (trace" not in line   # it built nothing
    # the armed tracer's span event carries the records and the sums
    lines = [json.loads(ln) for ln in open(path)]
    assert tele_schema.check_event_lines(list(enumerate(lines, 1))) == []
    (event,) = [e for e in lines if e["event"] == "span"
                and e["phase"] == "build_loop"]
    assert [b["stage"] for b in event["builds"]][:2] == ["trace", "lower"]
    assert {b["fun_name"] for b in event["builds"]} == {"run"}
    assert event["trace_s"] == loop["trace_s"]
    assert all(b["job"] == event["job"] == 1 for b in event["builds"])
    broken = dict(event, builds=[{"stage": 3}], seq=10 ** 6)
    assert tele_schema.check_event_lines([(1, broken)])
    # the bus's compile event is the same record's backend stage
    compiles = [e for e in lines if e["event"] == "compile"]
    assert any(e["name"] == "run" and e["seconds"] == pytest.approx(
        loop["compile_s"] + loop["load_s"]) for e in compiles)


class _DispatchLog(logging.Handler):
    """jax's own compile log, read as the handler this repo had read it:
    ``Finished XLA compilation of jit(<name>) in <s> sec`` at DEBUG."""

    PATTERN = re.compile(
        r"Finished XLA compilation of (?:jit\(|pmap\()?([^)]+?)\)? in "
        r"([0-9.eE+-]+) sec")

    def __init__(self):
        super().__init__(level=logging.DEBUG)
        self.seen = []

    def emit(self, record):
        m = self.PATTERN.search(record.getMessage())
        if m:
            self.seen.append((m.group(1), float(m.group(2))))


def _builds_while_traced(x):
    import jax

    with jax.ensure_compile_time_eval():
        c = jnp.arange(5.0, dtype=jnp.float32) * jnp.float32(2.5)
    return x + c


def test_watch_compiles_reads_what_the_compile_log_prints(tiny_data):
    """The build account's ``compile`` / ``load`` records beside jax's
    dispatch log, which ``watch_compiles`` scraped before: the same
    programs in the same order, the same seconds to the log's nine
    digits, over a whole first job and its eager ops."""
    import jax

    from cocoa_tpu.analysis import sanitize

    logger = logging.getLogger("jax._src.dispatch")
    handler, level = _DispatchLog(), logger.level
    muted = [h for h in logging.getLogger("jax").handlers
             if h.level == logging.NOTSET]
    for h in muted:
        h.setLevel(logging.WARNING)
    logger.addHandler(handler)
    logger.setLevel(logging.DEBUG)
    try:
        with sanitize.watch_compiles() as compiles:
            _svm(_dense(tiny_data))
            _program()(jnp.ones((9, 9), jnp.float32))
            jnp.arange(7) * 3                       # eager: its own programs
            five = jnp.ones(5, jnp.float32)
            n_builds = len(tracing.get_tracer().builds)
            jax.jit(_builds_while_traced)(five)
    finally:
        logger.removeHandler(handler)
        logger.setLevel(level)
        for h in muted:
            h.setLevel(logging.NOTSET)
    assert len(compiles) >= 4
    assert [c.name for c in compiles] == [name for name, _ in handler.seen]
    # a backend build INSIDE another build (an eager op while a function
    # is traced) leaves no record of its own, is counted on the outer's,
    # and still reaches the watch, as it reached the log
    late = list(tracing.get_tracer().builds)[n_builds:]
    assert {b["fun_name"] for b in late} == {"_builds_while_traced"}
    (trace,) = [b for b in late if b["stage"] == "trace"]
    assert trace["inner"] >= 4
    assert "multiply" in [c.name for c in compiles]
    assert len(compiles) > len(list(tracing.get_tracer().builds)) // 3
    for c, (_, seconds) in zip(compiles, handler.seen):
        assert round(c.seconds, 9) == pytest.approx(seconds, abs=2e-9)
    # and once the context closed, the list stops growing
    n = len(compiles)
    _program()(jnp.ones((10, 10), jnp.float32))
    assert len(compiles) == n


def test_first_job_span_opens_only_where_the_call_is_already_cold():
    """``resolve_path``'s form: nothing outside an entry, nothing in a call
    that took no cold branch, a cold span under ``first_job`` in one that
    did."""
    tracer = tracing.get_tracer()
    with tracing.first_job_span("resolve_path") as nothing:
        assert nothing is None
    assert len(tracer.cold) == 0

    @tracing.cold_entry
    def entry(ds, cold: bool):
        if cold:
            with tracing.cold_span("build_start"):
                pass
        with tracing.first_job_span("resolve_path") as span:
            return span

    assert entry(None, False) is None and len(tracer.cold) == 0
    span = entry(None, True)
    assert isinstance(span, tracing.ColdSpan)
    assert [(r["phase"], r["job"]) for r in tracer.cold] == [
        ("build_start", 2), ("resolve_path", 2), ("first_job", 2)]
    first = tracer.cold[-1]
    assert tracer.cold[1]["parent_id"] == first["span_id"]
