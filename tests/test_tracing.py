"""Gang-wide span tracing, the trace assembler, and the flight recorder
(cocoa_tpu/telemetry/tracing.py / trace_report.py / recorder.py).

What these tests pin:

- **span mechanics**: nesting/parent ids, the decorator form, the error
  attribute, and no event when the tracer or the bus is off — while the
  profiler annotation ``cocoa/<phase>`` opens either way;
- **the acceptance pin**: tracing-on ``(w, alpha)`` and the sched leaf
  are bit-identical to tracing-off — spans are host-side bookkeeping
  and may not perturb the run, exactly like the PR-4 telemetry bridge;
  and the same for the device scopes: under a profiler session, with
  the ``jax.named_scope`` names, and with every name stripped, one
  lowered computation and one ``(w, alpha, trajectory)``;
- **the scope names** in the lowered device loop: each of
  ``tracing.SCOPES`` present for hinge, logistic and a mesh, none inside
  another;
- **trace_report**: merged multi-worker streams yield a schema-valid
  Chrome/Perfetto trace, a nonempty per-round critical path over LEAF
  spans (no parent/child double counting), and a straggler table whose
  top row names the deliberately-skewed worker × phase;
- **flight recorder**: the ring is bounded, a ``divergence`` event dumps
  it, SIGTERM dumps it (real subprocess), and the supervisor-side
  ``dump_victim`` tail-reads a dead worker's stream — each dump
  validating as the schema checker's ``flightrec`` dialect;
- the satellites: ``--events`` size-capped rotation with the typed
  ``events_rotate`` record, the metrics write debounce (at most one
  rewrite per interval, trailing flush, terminal events bypass), the
  ``cocoa_phase_seconds`` gauge, and the new CLI flag validation;
- **slow, real processes**: a 2-process toy gang under the elastic
  supervisor leaves per-process span streams that trace_report merges
  into one timeline with cross-worker straggler attribution.
"""

import json
import os
import signal
import subprocess
import sys

import numpy as np
import pytest

from cocoa_tpu import checkpoint as ckpt_lib
from cocoa_tpu import elastic
from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.solvers import run_cocoa
from cocoa_tpu.telemetry import events as tele_events
from cocoa_tpu.telemetry import recorder as tele_recorder
from cocoa_tpu.telemetry import schema as tele_schema
from cocoa_tpu.telemetry import trace_report, tracing
from cocoa_tpu.telemetry.metrics import MetricsWriter
from test_divergence import _coherent_dataset

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.dirname(os.path.abspath(__file__))

K, LAM = 4, 1e-4


@pytest.fixture(autouse=True)
def clean_bus_and_tracer():
    tele_events.get_bus().reset()
    tracing.reset()
    yield tele_events.get_bus()
    tele_events.get_bus().reset()
    tracing.reset()


def _collect():
    events = []
    tele_events.get_bus().subscribe(events.append)
    return events


# --- span mechanics ----------------------------------------------------------


def test_span_nesting_parent_ids_and_attrs():
    events = _collect()
    tracing.configure(enabled=True, worker=3)
    with tracing.span("round", round=7) as outer:
        with tracing.span("kv_get", key="a") as inner:
            pass
    spans = [e for e in events if e["event"] == "span"]
    assert [s["phase"] for s in spans] == ["kv_get", "round"]  # close order
    inner_s, outer_s = spans
    assert inner_s["span_id"] == inner and outer_s["span_id"] == outer
    assert inner_s["parent_id"] == outer and outer_s["parent_id"] is None
    assert inner_s["worker"] == outer_s["worker"] == 3
    assert outer_s["round"] == 7 and inner_s["key"] == "a"
    assert 0.0 <= inner_s["dur_s"] <= outer_s["dur_s"]
    assert outer_s["start_ts"] <= inner_s["start_ts"] + 1.0


def test_span_whose_body_raises_carries_error():
    events = _collect()
    tracing.configure(enabled=True)
    with tracing.span("work", kind="unit"):
        pass
    with pytest.raises(ValueError):
        with tracing.span("doomed"):
            raise ValueError("boom")
    spans = [e for e in events if e["event"] == "span"]
    assert spans[0]["phase"] == "work" and spans[0]["kind"] == "unit"
    assert "error" not in spans[0]
    assert spans[1]["phase"] == "doomed" and spans[1]["error"] == "ValueError"


def test_disabled_tracer_and_inert_bus_emit_nothing(tmp_path):
    events = _collect()
    with tracing.span("x"):            # tracer disabled
        pass
    tele_events.get_bus().reset()      # bus inert (no subscriber/sink)
    tracing.configure(enabled=True)
    with tracing.span("y") as sid:
        pass
    assert sid is None
    assert [e for e in events if e["event"] == "span"] == []


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation``: records the names
    opened and closed."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        outer = self

        class _One:
            def __enter__(self):
                outer.log.append(("open", name))

            def __exit__(self, *exc):
                outer.log.append(("close", name))

        return _One()


@pytest.mark.parametrize("armed", [False, True])
def test_span_opens_the_profiler_annotation_armed_or_not(monkeypatch,
                                                         armed):
    """The profiler half of a span is always on: a disarmed tracer (and
    an armed one with an inert bus) opens ``cocoa/<phase>`` for the
    span's lifetime, nested like the spans, and emits no event."""
    notes = _Annotations()
    monkeypatch.setattr(tracing, "TraceAnnotation", notes)
    events = _collect()
    tele_events.get_bus().reset()               # inert bus
    if armed:
        tracing.configure(enabled=True, worker=0)
    with tracing.span("local_solve", round=3) as outer:
        with tracing.span("fetch") as inner:
            pass
    with pytest.raises(KeyError):
        with tracing.span("doomed"):
            raise KeyError("x")
    assert outer is None and inner is None and events == []
    assert notes.log == [
        ("open", "cocoa/local_solve"), ("open", "cocoa/fetch"),
        ("close", "cocoa/fetch"), ("close", "cocoa/local_solve"),
        ("open", "cocoa/doomed"), ("close", "cocoa/doomed")]


def test_armed_span_keeps_its_bus_half_inside_the_annotation(monkeypatch):
    notes = _Annotations()
    monkeypatch.setattr(tracing, "TraceAnnotation", notes)
    events = _collect()
    tracing.configure(enabled=True, worker=1)
    with tracing.span("eval", round=9) as sid:
        assert notes.log == [("open", "cocoa/eval")]
    (ev,) = [e for e in events if e["event"] == "span"]
    assert ev["span_id"] == sid and ev["phase"] == "eval"
    assert ev["round"] == 9 and ev["worker"] == 1
    assert notes.log[-1] == ("close", "cocoa/eval")


# --- the acceptance pin: tracing must not perturb the run --------------------


def _anneal_run(tmp_path, name):
    """A short σ′-anneal device-loop run with checkpoints (the sched
    leaf rides the checkpoint meta — the on/off comparison reads it
    there, like the telemetry on/off pin)."""
    ds, n = _coherent_dataset(k=K)
    params = Params(n=n, num_rounds=150, local_iters=16, lam=LAM,
                    sigma=1.0)
    debug = DebugParams(debug_iter=25, seed=0, chkpt_iter=75,
                        chkpt_dir=str(tmp_path / name))
    return run_cocoa(ds, params, debug, plus=True, quiet=True, math="fast",
                     device_loop=True, gap_target=1e-3, rng="jax",
                     sigma_schedule="anneal")


def test_tracing_on_vs_off_state_bit_identical(tmp_path):
    """Spans are host-side bookkeeping: a traced run's (w, alpha) and
    sched leaf are bit-identical to an untraced run."""
    tele_events.get_bus().configure(
        jsonl_path=str(tmp_path / "events.jsonl"))
    tracing.configure(enabled=True, worker=0)
    w1, a1, t1 = _anneal_run(tmp_path, "on")
    spans = [json.loads(ln)
             for ln in open(tmp_path / "events.jsonl")
             if json.loads(ln)["event"] == "span"]
    assert spans, "the traced run must actually have emitted spans"
    assert {s["phase"] for s in spans} >= {"local_solve", "checkpoint_save"}

    tele_events.get_bus().reset()
    tracing.reset()
    w2, a2, t2 = _anneal_run(tmp_path, "off")
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    names = sorted(os.listdir(tmp_path / "on"))
    assert names == sorted(os.listdir(tmp_path / "off"))
    for nm in names:
        if nm.endswith(".npz"):
            m1, _, _ = ckpt_lib.load(str(tmp_path / "on" / nm))
            m2, _, _ = ckpt_lib.load(str(tmp_path / "off" / nm))
            assert m1["sched"] == m2["sched"], nm


# --- the device scopes: names only -------------------------------------------


@pytest.fixture
def lowered(monkeypatch):
    """Every device loop built while the fixture is live leaves its
    lowered text here: ``(with debug info, without)``."""
    from cocoa_tpu.solvers import base

    texts = []
    build = base._build_device_run

    def capturing(*args, **kw):
        run = build(*args, **kw)

        def call(*run_args):
            low = run.lower(*run_args)
            texts.append((low.as_text(debug_info=True), low.as_text()))
            return run(*run_args)

        return call

    monkeypatch.setattr(base, "_build_device_run", capturing)
    base._DEVICE_RUNS.clear()
    yield texts
    base._DEVICE_RUNS.clear()


def _loop_run(loss="hinge", mesh=None, pallas=None, accel="off",
              sparse=False, sampling="auto", classes=1):
    import jax

    from cocoa_tpu.data.synth import synth_dense_sharded

    if sparse:
        import jax.numpy as jnp

        from cocoa_tpu.data import shard_dataset
        from cocoa_tpu.data.synth import synth_sparse

        ds = shard_dataset(synth_sparse(256, 200, nnz_mean=6, seed=1), k=K,
                           layout="sparse", dtype=jnp.float32)
    else:
        ds = synth_dense_sharded(256, 32, K, seed=1, mesh=mesh)
    if classes > 1:
        import jax.numpy as jnp

        ds.num_classes = classes
        ds.classes = jnp.asarray(np.random.default_rng(1).integers(
            0, classes, ds.labels.shape), jnp.int32)
    params = Params(n=ds.n, num_rounds=20, local_iters=16, lam=1e-2,
                    loss=loss)
    w, alpha, traj = run_cocoa(
        ds, params, DebugParams(debug_iter=5, seed=0), plus=True,
        quiet=True, math="fast", device_loop=True, rng="permuted",
        gap_target=1e-9, mesh=mesh, pallas=pallas, accel=accel,
        sampling=sampling)
    jax.block_until_ready((w, alpha))
    return (np.asarray(w), np.asarray(alpha),
            [(r.round, r.primal, r.gap) for r in traj.records])


def _loc_names(text):
    import re

    return set(re.findall(r'loc\("([^"]+)"', text))


@pytest.mark.parametrize("case", ["hinge_pallas", "logistic",
                                  "mesh_accel", "sparse_hbm",
                                  "wide_classes"])
def test_lowered_device_loop_carries_each_scope_once(lowered, case,
                                                     monkeypatch):
    """The scope names reach the lowered loop — the kernel and its glue
    under the local solve, the dw sum and apply, the certificate eval,
    the index tables, and under ``--accel`` the secant jump — on the
    batched Pallas path (interpreted here), the vmapped logistic path and
    a 4-device mesh; no name sits inside another, so an op belongs to one
    phase.  Three names have a path of their own: the jump only under
    ``--accel``, ``cocoa_sparse_gather`` only where sparse rows take
    the kernel whose state stays in HBM (a sibling of the local solve's
    scope there, never inside it), and ``cocoa_row_align`` only where a
    dense Pallas job's fold cache is not stored lane-aligned (d/8 = 4
    here: the relayout at the dispatch's entry, before the loop).  The ONE
    nesting is the block solve's (T class models on the lanes of dense
    rows, ops/block_lanes.py): its two halves, the matrix products and the
    replay, are named in the body of the scan the local solve's scope
    holds, so a compiled op's path carries the solve's name AND a half's;
    no lowered name does."""
    from cocoa_tpu.ops import pallas_sdca, pallas_sparse
    from cocoa_tpu.parallel import make_mesh

    kw = {"hinge_pallas": dict(pallas=True), "logistic": dict(
        loss="logistic"), "mesh_accel": dict(mesh=make_mesh(4),
                                             accel="on"),
          "sparse_hbm": dict(sparse=True, pallas=True),
          "wide_classes": dict(classes=17)}[case]
    if case == "sparse_hbm":     # the VMEM-resident kernel would fit here
        monkeypatch.setattr(pallas_sparse, "sparse_kernel_fits",
                            lambda *a, **k: False)
    if case == "wide_classes":   # the sublane kernel would hold the set
        monkeypatch.setattr(pallas_sdca, "CLASS_VMEM_BUDGET", 0)
    _loop_run(**kw)
    names = _loc_names(lowered[-1][0])
    halves = (tracing.SCOPE_WIDE_PRODUCTS, tracing.SCOPE_WIDE_REPLAY)
    own_case = {tracing.SCOPE_ACCEL_JUMP: "mesh_accel",
                tracing.SCOPE_SPARSE_GATHER: "sparse_hbm",
                tracing.SCOPE_ROW_ALIGN: "hinge_pallas",
                **dict.fromkeys(halves, "wide_classes")}
    for scope in tracing.SCOPES:
        assert any(scope in n for n in names) == (
            own_case.get(scope, case) == case), (scope, case)
        assert "/" not in scope
    assert [n for n in names
            if sum(n.count(sc) for sc in tracing.SCOPES) > 1] == []
    if case == "wide_classes":
        # (lowered, the halves are named in the body the solve's scan
        # calls; compiled, their ops read cocoa_local_solve/while/body/
        # closed_call/cocoa_wide_products/..: tests/test_device_layout.py)
        assert tracing.SCOPE_LOCAL_SOLVE + "/while/body/closed_call" in names
        assert any(n.startswith(tracing.SCOPE_WIDE_PRODUCTS + "/")
                   and n.endswith("dot_general") for n in names)
    if case == "hinge_pallas":      # the kernel call itself is inside
        assert any(n.startswith(tracing.SCOPE_LOCAL_SOLVE + "/jit(pallas")
                   for n in names)


def test_traced_scoped_and_plain_runs_are_one_computation(lowered,
                                                          monkeypatch,
                                                          tmp_path):
    """Scopes and annotations are names: a run under a live profiler
    session, a plain run with the scopes, and a run with every scope name
    stripped lower to the same computation and return the same bits."""
    import jax

    scoped = _loop_run()
    jax.profiler.start_trace(str(tmp_path / "prof"))
    try:
        with jax.profiler.TraceAnnotation("job"):
            traced = _loop_run()
        with jax.profiler.TraceAnnotation("job_host_tables"):
            hosted = _loop_run(sampling="host")
    finally:
        jax.profiler.stop_trace()
    # strip: named_scope's context manager, made a no-op
    cm = type(jax.named_scope("x"))
    monkeypatch.setattr(cm, "__enter__", lambda self: None)
    monkeypatch.setattr(cm, "__exit__", lambda self, *exc: None)
    from cocoa_tpu.solvers import base

    base._DEVICE_RUNS.clear()
    plain = _loop_run()
    monkeypatch.undo()
    # (the job on host tables is another loop program: it takes a table)
    (scoped_dbg, scoped_txt), (_, traced_txt), _, (plain_dbg, plain_txt) = \
        lowered
    assert all(sc in scoped_dbg for sc in tracing.SCOPES[:4])
    assert not any(sc in plain_dbg for sc in tracing.SCOPES)
    assert scoped_txt == traced_txt == plain_txt
    for a, b in ((scoped, traced), (scoped, plain), (scoped, hosted)):
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_array_equal(a[1], b[1])
        assert a[2] == b[2]
    # and the profile holds the drive ladder's spans on the job's thread;
    # a job that samples in-jit stages nothing (its spec is NumPy, built
    # under wait_indices), a job on host tables stages them on another
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(str(tmp_path / "prof" / "plugins" / "profile" / "*"
                            / "*.xplane.pb"))
    lines = [[ev for ev in line.events
              if ev.name.startswith(("job", "cocoa/"))]
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines]
    (driving,) = [evs for evs in lines
                  if any(ev.name == "job" for ev in evs)]
    (job,), (job_host,) = ([ev for ev in driving if ev.name == nm]
                           for nm in ("job", "job_host_tables"))

    def under(job, evs):
        return {ev.name for ev in evs
                if job.start_ns <= ev.start_ns < job.end_ns
                and ev.name.startswith("cocoa/")}

    for j in (job, job_host):
        assert under(j, driving) >= {
            "cocoa/init_state", "cocoa/wait_indices", "cocoa/local_solve",
            "cocoa/dispatch", "cocoa/fetch", "cocoa/decode_trajectory"}
        assert "cocoa/stage_indices" not in under(j, driving)
    staged = [evs for evs in lines if evs is not driving]
    assert not any("cocoa/stage_indices" in under(job, evs)
                   for evs in staged)
    assert any("cocoa/stage_indices" in under(job_host, evs)
               for evs in staged)


def test_span_stream_schema_valid_and_round_attributed(tmp_path):
    """The device-loop run's spans validate as events and trace_report
    attributes the ladder's spans to rounds via their own round attrs."""
    ev = str(tmp_path / "events.jsonl")
    tele_events.get_bus().configure(jsonl_path=ev)
    tracing.configure(enabled=True, worker=0)
    _anneal_run(tmp_path, "run")
    assert tele_schema.check_file(ev) == []
    spans = trace_report.load_spans([ev])
    assert spans
    # the device-resident path's super-block spans carry their nominal
    # end round (cadence-aligned blocks: multiples of debugIter=25), and
    # the checkpoint spans their exact round
    rounds = {s["_round"] for s in spans if s["phase"] == "local_solve"}
    assert rounds and all(r % 25 == 0 for r in rounds)
    assert {s["_round"] for s in spans
            if s["phase"] == "checkpoint_save"} >= {75, 150}
    path = trace_report.critical_path(spans)
    assert path and all(p["critical_s"] > 0 for p in path)


# --- trace_report unit -------------------------------------------------------


class _Clock:
    """The two clocks a span's bus half reads, moved by hand: a stream
    built on it holds exactly the durations the test wrote down, however
    loaded the machine is."""

    def __init__(self, start=1_000.0):
        self.now = start

    def time(self):
        return self.now

    def monotonic(self):
        return self.now

    def sleep(self, seconds):
        self.now += seconds


def _synthetic_streams(tmp_path, skew=0.01, rounds=(1, 2)):
    """Two workers' span streams; worker 1's kv_allgather takes ``skew``
    longer.  The tracer's clock is a :class:`_Clock` for the duration."""
    paths = []
    clock, real = _Clock(), tracing.time
    tracing.time = clock
    try:
        for w in (0, 1):
            tele_events.get_bus().reset()
            tracing.reset()
            p = str(tmp_path / f"ev{w}.jsonl")
            paths.append(p)
            tele_events.get_bus().configure(jsonl_path=p)
            tracing.configure(enabled=True, worker=w)
            for t in rounds:
                with tracing.span("round", round=t):
                    with tracing.span("kv_allgather"):
                        clock.sleep(0.002 + (skew if w == 1 else 0.0))
                    with tracing.span("local_step"):
                        clock.sleep(0.002)
    finally:
        tracing.time = real
        tele_events.get_bus().reset()
        tracing.reset()
    return paths


def test_trace_report_merge_critical_path_and_stragglers(tmp_path):
    paths = _synthetic_streams(tmp_path)
    spans = trace_report.load_spans(paths)
    assert len(spans) == 12 and len({s["pid"] for s in spans}) == 1
    # leaf-only attribution: the `round` container never shows up in the
    # critical path or the straggler table (its children carry the time)
    cp = trace_report.critical_path(spans)
    assert [c["round"] for c in cp] == [1, 2]
    for c in cp:
        phases = {e["phase"] for e in c["entries"]}
        assert phases == {"kv_allgather", "local_step"}
        assert all(e["workers"] == 2 for e in c["entries"])
        # the slowest worker per phase: 12 ms of allgather + 2 ms of step
        assert c["critical_s"] == pytest.approx(0.014)
    rows = trace_report.stragglers(spans)
    assert rows[0]["worker"] == 1 and rows[0]["phase"] == "kv_allgather"
    assert rows[0]["slack_s"] == pytest.approx(0.02)    # 10 ms a round
    assert {(r["worker"], r["phase"]) for r in rows} == {
        (0, "kv_allgather"), (0, "local_step"),
        (1, "kv_allgather"), (1, "local_step")}
    # the metrics rendering carries both gauges, labeled worker x phase
    text = trace_report.metrics_text(spans)
    assert 'cocoa_straggler_slack_seconds{worker="1",' \
           'phase="kv_allgather"}' in text
    assert 'cocoa_phase_seconds{worker="0",phase="local_step"}' in text


def _leaf(worker, phase, start, dur, round_=1, sid=[0], **attrs):
    sid[0] += 1
    return {"event": "span", "phase": phase, "span_id": sid[0],
            "parent_id": None, "worker": worker, "pid": 100 + worker,
            "start_ts": float(start), "dur_s": float(dur),
            "_round": round_, "round": round_, **attrs}


def test_critical_path_charges_overlapped_same_worker_leaves():
    """The ISSUE-12 satellite pin: leaf spans on ONE worker are no
    longer assumed disjoint — an `--overlapComm` collector's kv_get
    runs concurrently with the main thread.  Per worker each wall-clock
    second is charged to exactly one covering span (foreground beats
    the `overlapped` background collector; latest-started owns within a
    class), so hidden exchange time cannot double-count into the
    critical path or the slack table; disjoint spans keep the old
    summed values exactly."""
    # worker 0: a 1.0s local_solve [10, 11) fully hiding a 0.8s
    # background kv_get [10.1, 10.9); worker 1: sequential (sync mode)
    spans = [
        _leaf(0, "local_solve", 10.0, 1.0),
        _leaf(0, "kv_get", 10.1, 0.8, overlapped=True),   # hidden
        _leaf(1, "local_solve", 10.0, 1.0),
        _leaf(1, "kv_get", 11.0, 0.8),       # sequential: fully charged
    ]
    trace_report.attribute_rounds(spans)
    table = trace_report._per_round_phase_durs(spans)
    assert table[1]["local_solve"][0] == pytest.approx(1.0)
    assert table[1]["kv_get"][0] == pytest.approx(0.0)    # fully hidden
    assert table[1]["local_solve"][1] == pytest.approx(1.0)
    assert table[1]["kv_get"][1] == pytest.approx(0.8)
    # the critical path no longer credits worker 0 with 1.8s of a 1.0s
    # wall-clock window: kv_get's slowest worker is now worker 1
    cp = trace_report.critical_path(spans)
    by_phase = {e["phase"]: e for e in cp[0]["entries"]}
    assert by_phase["kv_get"]["worker"] == 1
    assert cp[0]["critical_s"] == pytest.approx(1.8)
    # and the slack table attributes the exchange wait to the worker
    # that actually paid it on its main thread
    rows = trace_report.stragglers(spans)
    kv = {r["worker"]: r["slack_s"] for r in rows
          if r["phase"] == "kv_get"}
    assert kv[1] == pytest.approx(0.8)
    assert kv[0] == pytest.approx(0.0)


def test_charged_same_phase_overlap_unions_not_sums():
    """Two overlapping same-phase leaves on one worker charge their
    UNION (the pre-fix sum double-counted the overlap); a third
    disjoint leaf still adds fully."""
    spans = [
        _leaf(0, "kv_get", 0.0, 1.0),
        _leaf(0, "kv_get", 0.5, 1.0),        # overlaps [0.5, 1.0)
        _leaf(0, "kv_get", 3.0, 0.25),       # disjoint
        _leaf(1, "kv_get", 0.0, 0.1),
    ]
    trace_report.attribute_rounds(spans)
    table = trace_report._per_round_phase_durs(spans)
    assert table[1]["kv_get"][0] == pytest.approx(1.75)   # union, not 2.25
    assert table[1]["kv_get"][1] == pytest.approx(0.1)
    # torn stream (no start_ts): falls back to the span's own duration
    torn = [_leaf(0, "kv_get", 0.0, 0.5)]
    torn[0].pop("start_ts")
    trace_report.attribute_rounds(torn)
    assert trace_report._per_round_phase_durs(torn)[1]["kv_get"][0] \
        == pytest.approx(0.5)


def test_trace_report_chrome_trace_valid_and_checker_has_teeth(tmp_path):
    paths = _synthetic_streams(tmp_path, rounds=(1,))
    spans = trace_report.load_spans(paths)
    trace = trace_report.chrome_trace(spans)
    assert trace_report.check_chrome_trace(trace) == []
    xs = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert {e["pid"] for e in xs} == {0, 1}   # one track per worker
    assert all(e["dur"] >= 0 and isinstance(e["name"], str) for e in xs)
    # the checker rejects what Perfetto would reject
    assert trace_report.check_chrome_trace({"traceEvents": "nope"}) != []
    assert trace_report.check_chrome_trace(
        {"traceEvents": [{"ph": "X", "name": "x", "pid": 0, "tid": 0,
                          "ts": 1.0, "dur": -5.0}]}) != []
    assert trace_report.check_chrome_trace(
        {"traceEvents": [{"ph": "Q", "name": "x", "pid": 0, "tid": 0}]}) \
        != []


def test_trace_report_cli_writes_artifacts(tmp_path, capsys):
    paths = _synthetic_streams(tmp_path, rounds=(1, 2))
    out = str(tmp_path / "trace.json")
    prom = str(tmp_path / "straggler.prom")
    rc = trace_report.main([*paths, f"--trace={out}", f"--metrics={prom}"])
    assert rc == 0
    trace = json.load(open(out))
    assert trace_report.check_chrome_trace(trace) == []
    assert "cocoa_straggler_slack_seconds" in open(prom).read()
    assert "critical path" in capsys.readouterr().out
    # no spans -> exit 1; usage -> exit 2
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert trace_report.main([str(empty)]) == 1
    assert trace_report.main([]) == 2
    assert trace_report.main(["--bogus"]) == 2


# --- events rotation ---------------------------------------------------------


def test_events_rotation_size_cap_and_typed_event(tmp_path):
    ev = str(tmp_path / "events.jsonl")
    bus = tele_events.get_bus()
    bus.configure(jsonl_path=ev, max_bytes=2048)
    for i in range(60):
        bus.emit("host_transfer", label="x" * 40)
    assert os.path.exists(ev + ".1"), "the cap must have rotated"
    assert os.path.getsize(ev + ".1") <= 4096
    head = json.loads(open(ev).readline())
    assert head["event"] == "events_rotate"       # first line of the
    assert head["rotated_to"] == ev + ".1"        # fresh file
    assert head["bytes"] >= 2048
    assert tele_schema.check_file(ev) == []
    assert tele_schema.check_file(ev + ".1") == []
    # rotation keeps exactly one predecessor (~2x the cap on disk, total)
    assert not os.path.exists(ev + ".2")


# --- metrics debounce + phase gauge ------------------------------------------


def _eval_event(t, ts):
    return {"event": "round_eval", "seq": t, "ts": ts, "algorithm": "X",
            "t": t, "primal": 1.0, "gap": 0.5, "test_error": None,
            "sigma": None, "stall": None}


def test_metrics_debounce_coalesces_and_flushes(tmp_path, monkeypatch):
    import cocoa_tpu.telemetry.metrics as metrics_mod

    writes = []
    real_replace = os.replace

    def counting_replace(a, b):
        writes.append(b)
        return real_replace(a, b)

    monkeypatch.setattr(metrics_mod.os, "replace", counting_replace)
    w = MetricsWriter(str(tmp_path / "m.prom"), flush_interval_s=30.0)
    base = len(writes)                  # the __init__ write
    for t in range(1, 21):
        w(_eval_event(t, float(t)))
    # one immediate write (interval elapsed since _last_write=0 epoch is
    # false: first event within interval of init write) — all 20 events
    # coalesce to at most one rewrite
    assert len(writes) - base <= 1
    w.flush()
    text = open(tmp_path / "m.prom").read()
    assert "cocoa_evals_total 20" in text  # the trailing flush converged
    # terminal events bypass the debounce
    before = len(writes)
    w({"event": "run_end", "seq": 99, "ts": 99.0, "algorithm": "X",
       "primal": 1.0, "stopped": "target"})
    assert len(writes) == before + 1


def test_metrics_default_interval_unchanged(tmp_path, monkeypatch):
    """flush_interval_s=0 (the default) keeps the original one-rewrite-
    per-event behavior — nothing changes for existing consumers."""
    import cocoa_tpu.telemetry.metrics as metrics_mod

    writes = []
    real_replace = os.replace
    monkeypatch.setattr(
        metrics_mod.os, "replace",
        lambda a, b: (writes.append(b), real_replace(a, b))[1])
    w = MetricsWriter(str(tmp_path / "m.prom"))
    base = len(writes)
    for t in range(1, 6):
        w(_eval_event(t, float(t)))
    assert len(writes) - base == 5


def test_metrics_phase_seconds_gauge(tmp_path):
    path = str(tmp_path / "m.prom")
    w = MetricsWriter(path)
    for ph, d in (("eval", 0.25), ("local_solve", 1.0), ("eval", 0.25)):
        w({"event": "span", "seq": 1, "ts": 1.0, "phase": ph,
           "span_id": 1, "parent_id": None, "worker": 0,
           "start_ts": 1.0, "dur_s": d})
    text = open(path).read()
    assert 'cocoa_phase_seconds{phase="eval"} 0.5' in text
    assert 'cocoa_phase_seconds{phase="local_solve"} 1.0' in text
    # the supervisor's gang-families sibling never renders phase seconds
    # (it would duplicate the worker's family for textfile collectors)
    g = MetricsWriter(str(tmp_path / "m.gang"), families="gang")
    g({"event": "span", "seq": 1, "ts": 1.0, "phase": "eval",
       "span_id": 1, "parent_id": None, "worker": None,
       "start_ts": 1.0, "dur_s": 1.0})
    assert "cocoa_phase_seconds" not in open(tmp_path / "m.gang").read()


# --- flight recorder ---------------------------------------------------------


def test_recorder_ring_bounded_and_divergence_dump(tmp_path):
    ev = str(tmp_path / "events.jsonl")
    bus = tele_events.get_bus()
    bus.configure(jsonl_path=ev)
    rec = tele_recorder.install(bus, ev, capacity=16, signals=False)
    for i in range(50):
        bus.emit("host_transfer", label=f"t{i}")
    assert len(rec.ring) == 16           # bounded
    bus.emit("divergence", algorithm="X", t=100, n_evals=12)
    assert rec.dumps and rec.dumps[-1][0] == "divergence"
    path = ev + ".flightrec"
    assert tele_schema.check_file(path) == []
    lines = [json.loads(ln) for ln in open(path)]
    man = lines[0]["flightrec_manifest"]
    assert man["reason"] == "divergence" and man["n_events"] == 16
    assert lines[-1]["event"] == "divergence"   # the trigger is on the ring
    assert lines[1]["label"] == "t35"           # oldest retained = 50-15


def test_recorder_dump_victim_tails_stream(tmp_path):
    # synthesize a dead worker-1 stream, as the per-process convention
    # lays it out, then dump on its behalf like the supervisor does
    base = str(tmp_path / "events.jsonl")
    stream = tele_recorder.worker_stream_path(base, 1)
    assert stream == base + ".p1"
    with open(stream, "w") as f:
        for t in range(1, 31):
            f.write(json.dumps(
                {"event": "checkpoint_write", "seq": t, "pid": 4242,
                 "ts": float(t), "algorithm": "Toy", "round": t,
                 "path": "x"}) + "\n")
        f.write('{"event": "span", "seq": 31, "pid": 4242, "ts": 31.0, '
                '"phase": "round", "span_id"')   # torn final line (kill)
    out = tele_recorder.dump_victim(base, 1, "worker_died", exit_code=-9,
                                    generation=2, last_n=10)
    assert out == stream + ".flightrec"
    assert tele_schema.check_file(out) == []
    lines = [json.loads(ln) for ln in open(out)]
    man = lines[0]["flightrec_manifest"]
    assert man["reason"] == "worker_died" and man["exit_code"] == -9
    assert man["victim_index"] == 1 and man["generation"] == 2
    assert len(lines) == 11 and lines[-1]["round"] == 30
    # a worker that left no stream yields no dump (and no exception)
    assert tele_recorder.dump_victim(base, 7, "worker_died") is None


def test_recorder_sigterm_dump_real_process(tmp_path):
    """A real subprocess with the recorder installed dies by SIGTERM and
    leaves a validated dump with reason 'sigterm' — and still dies with
    the termination status its supervisor expects."""
    ev = str(tmp_path / "events.jsonl")
    code = f"""
import os, signal
from cocoa_tpu.telemetry import events, recorder
bus = events.get_bus()
bus.configure(jsonl_path={ev!r})
rec = recorder.install(bus, {ev!r})
for i in range(5):
    bus.emit("host_transfer", label=f"t{{i}}")
os.kill(os.getpid(), signal.SIGTERM)
"""
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == -signal.SIGTERM
    path = ev + ".flightrec"
    assert tele_schema.check_file(path) == []
    man = json.loads(open(path).readline())["flightrec_manifest"]
    assert man["reason"] == "sigterm" and man["n_events"] == 5


def test_recorder_sigterm_honors_sig_ign(tmp_path):
    """A process that deliberately ignored SIGTERM before the recorder
    installed must still dump — and still survive the signal (the
    handler honors the previous SIG_IGN disposition)."""
    ev = str(tmp_path / "events.jsonl")
    code = f"""
import os, signal
signal.signal(signal.SIGTERM, signal.SIG_IGN)
from cocoa_tpu.telemetry import events, recorder
bus = events.get_bus()
bus.configure(jsonl_path={ev!r})
rec = recorder.install(bus, {ev!r})
bus.emit("host_transfer", label="x")
os.kill(os.getpid(), signal.SIGTERM)
print("survived")
"""
    proc = subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                          capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0 and "survived" in proc.stdout
    man = json.loads(open(ev + ".flightrec").readline())
    assert man["flightrec_manifest"]["reason"] == "sigterm"


def test_flightrec_schema_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.flightrec"
    bad.write_text(json.dumps({"flightrec_manifest": {"reason": "x"}})
                   + "\n" + json.dumps({"event": "nonsense", "seq": 1,
                                        "ts": 1.0}) + "\n")
    errs = tele_schema.check_file(str(bad))
    assert any("n_events" in e for e in errs)
    assert any("nonsense" in e for e in errs)


# --- CLI flag surface --------------------------------------------------------


def test_cli_flag_validation(tmp_path, capsys):
    from cocoa_tpu import cli

    base = [f"--trainFile={ROOT}/data/small_train.dat",
            "--numFeatures=9947", "--numSplits=4", "--numRounds=2",
            "--debugIter=2", "--localIterFrac=0.1", "--quiet"]
    assert cli.main([*base, "--trace"]) == 2            # no sink
    assert cli.main([*base, "--flightRecorder=on"]) == 2  # needs events
    assert cli.main([*base, "--flightRecorder=maybe",
                     f"--events={tmp_path}/e.jsonl"]) == 2
    assert cli.main([*base, "--eventsMaxMB=0",
                     f"--events={tmp_path}/e.jsonl"]) == 2
    assert cli.main([*base, "--eventsMaxMB=4"]) == 2    # needs events
    assert cli.main([*base, "--metricsInterval=1"]) == 2  # needs metrics
    assert cli.main([*base, "--metricsInterval=-1",
                     f"--metrics={tmp_path}/m.prom"]) == 2
    capsys.readouterr()


# --- real-process gang: span streams merge + straggler attribution -----------


def _gang_env(monkeypatch):
    monkeypatch.setenv(
        "PYTHONPATH",
        f"{ROOT}{os.pathsep}{TESTS}{os.pathsep}"
        f"{os.environ.get('PYTHONPATH', '')}")
    monkeypatch.setenv("XLA_FLAGS", " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f))


@pytest.mark.slow
def test_gang_trace_report_merges_and_names_the_straggler(tmp_path,
                                                          monkeypatch):
    """THE tracing acceptance pin: a REAL 2-process gang (toy worker:
    real rendezvous, per-round KV allgather, checkpoints) run with
    --trace leaves one span stream per process; trace_report merges them
    into a schema-valid Perfetto trace with a nonempty per-round
    critical path, and the straggler table's top row names the
    deliberately-skewed worker 1 × local_step."""
    _gang_env(monkeypatch)
    ck = tmp_path / "ck"
    ev = str(tmp_path / "events.jsonl")
    rc = elastic.supervise(
        [f"--chkptDir={ck}", "--numSplits=4", "--numRounds=8",
         "--chkptIter=4", "--stepSeconds=0.02", "--stepSkew=0.05",
         f"--events={ev}", "--trace"],
        2, module="_gang_worker", max_restarts=0, poll_s=0.05,
        backoff_base_s=0.0)
    assert rc == 0
    streams = [ev, ev + ".p1"]
    for s in streams:
        assert os.path.exists(s), s
        assert tele_schema.check_file(s) == []
    spans = trace_report.load_spans(streams)
    workers = {trace_report.worker_of(s) for s in spans}
    assert workers == {0, 1}

    trace = trace_report.chrome_trace(spans)
    assert trace_report.check_chrome_trace(trace) == []

    path = trace_report.critical_path(spans)
    assert [p["round"] for p in path] == list(range(1, 9))
    assert all(p["critical_s"] > 0 for p in path)
    # both workers reported the per-round phases the path is built from
    for p in path:
        by_phase = {e["phase"]: e for e in p["entries"]}
        assert by_phase["local_step"]["workers"] == 2
        assert by_phase["kv_get"]["workers"] == 2

    rows = trace_report.stragglers(spans)
    assert rows, "straggler table must be nonempty"
    top = rows[0]
    # worker 1 sleeps 50ms longer per round — 8 rounds of ~50ms slack
    assert top["worker"] == 1 and top["phase"] == "local_step"
    assert top["slack_s"] > 0.2


@pytest.mark.slow
def test_gang_metrics_ownership_worker0_vs_supervisor_gang_file(
        tmp_path, monkeypatch):
    """The PR-9 sibling-file contract under a REAL gang, now pinned:
    worker 0 owns `<metrics>` (worker families only — no gang series),
    the supervisor owns `<metrics>.gang` (gang families only), so a
    textfile collector globbing the directory never sees a duplicated
    family."""
    _gang_env(monkeypatch)
    ck = tmp_path / "ck"
    metrics = str(tmp_path / "metrics.prom")
    bus = tele_events.get_bus()
    bus.configure(jsonl_path=str(tmp_path / "events.jsonl"))
    bus.subscribe(MetricsWriter(metrics + ".gang", families="gang"))
    rc = elastic.supervise(
        [f"--chkptDir={ck}", "--numSplits=4", "--numRounds=6",
         "--chkptIter=3", "--stepSeconds=0.02",
         f"--events={tmp_path / 'events.jsonl'}",
         f"--metrics={metrics}"],
        2, module="_gang_worker", max_restarts=0, poll_s=0.05,
        backoff_base_s=0.0)
    assert rc == 0
    worker_text = open(metrics).read()
    gang_text = open(metrics + ".gang").read()

    def families(text):
        return {line.split(" ", 1)[0].split("{", 1)[0]
                for line in text.splitlines()
                if line and not line.startswith("#")}

    wf, gf = families(worker_text), families(gang_text)
    # worker 0 saw its own checkpoint_write events (chkptIter=3)
    assert "cocoa_rounds_total" in wf and "cocoa_evals_total" in wf
    # strictly disjoint families across the sibling files
    assert wf & gf == set(), (wf, gf)
    assert gf == {"cocoa_gang_generations_total"}  # healthy run: no
    #                                              # resize/backoff gauges
    for name in ("cocoa_gang_size", "cocoa_gang_generations_total",
                 "cocoa_restart_backoff_seconds"):
        assert name not in wf
