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
                           "build_loop", "first_run"}
    assert all(len(v) == 1 for v in phases.values())
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
