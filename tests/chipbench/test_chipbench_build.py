"""``chipbench/readers/build_account.py``: how the first job's build divides
between tracing, lowering and loading, and the builds outside it, from the
build records the program's tracer keeps.  On a CPU: hand-made records, a
tracer that keeps none, and one real tiny job."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import registry  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
PARTS = ["cold_trace_s", "cold_lower_s", "cold_load_s", "cold_cache_misses",
         "cold_stray_build_s", "cold_unspanned_s", "setup_outside_build_s",
         "retraces_in_window"]
# BENCHMARK.json names none of the eight yet: the driver takes a new entry
# only at the END of ``per_layer`` and ``test_chipbench_labels.py:128``
# (frozen) wants amazoncat's block last, so the entries and their
# ``layer_metrics/<name>.json`` (``{"reader": "build_account", "params":
# {"part": <name>}}``) wait for a ``benchmark`` PR (ROADMAP D10 p)


@pytest.fixture
def reader():
    return registry.load_module(BENCH, "readers", "build_account")


def _span(phase, start_s, dur_s, job=1):
    return dict(phase=phase, span_id=0, parent_id=None, job=job,
                start_s=start_s, dur_s=dur_s, hbm_open=[], hbm_close=[],
                program=None, builds=[])


def _build(stage, fun_name, dur_s, job=1, span=None, jobs_opened=1, **more):
    return dict(order=0, stage=stage, fun_name=fun_name, start_ts=0.0,
                dur_s=dur_s, job=job, span=span, jobs_opened=jobs_opened,
                inner=0, inner_s=0.0, **more)


def _process():
    """A benchmark process as the tracer leaves it: the generator builds
    before any entry, the first job (ordinal 1) under its spans, the
    reference check after it, a warm job (2) that builds nothing, a later
    job (4) that retraces ``run``, and ``cold_account``'s compile at the
    end."""
    cold = [
        _span("build_start", 100.0, 0.4),
        # fold_rows with a span nested in it: counted once
        _span("fold_rows", 100.5, 1.0),
        _span("row_lengths", 100.6, 0.2),
        _span("build_loop", 101.5, 3.0),
        _span("first_run", 104.5, 0.25),
        _span("first_job", 100.0, 5.0),
        # a later dataset's first job: not the process's first
        _span("fold_rows", 300.0, 9.0, job=3),
        _span("first_job", 300.0, 9.0, job=3),
    ]
    builds = [
        _build("trace", "gen", 0.5, job=None, jobs_opened=0),
        _build("compile", "gen", 6.0, job=None, jobs_opened=0, cache="miss"),
        _build("lower", "start", 0.1, span="build_start"),
        _build("load", "start", 0.05, span="build_start", cache="hit"),
        _build("trace", "fold", 0.02, span="fold_rows"),
        _build("compile", "fold", 0.3, span="fold_rows", cache="miss"),
        _build("trace", "convert_element_type", 0.01, span="first_job"),
        _build("trace", "run", 1.7, span="build_loop"),
        _build("lower", "run", 0.9, span="build_loop"),
        _build("compile", "run", 0.2, span="build_loop", cache="miss"),
        _build("load", "ring", 0.07, span="build_loop", cache="hit"),
        _build("trace", "_block", 0.8, job=None),
        _build("load", "_block", 0.1, job=None, cache="hit"),
        _build("trace", "fold", 0.02, job=3, span="fold_rows",
               jobs_opened=3),
        _build("trace", "run", 1.5, job=4, jobs_opened=4),
        _build("lower", "run", 0.8, job=4, jobs_opened=4),
        _build("load", "run", 0.2, job=4, jobs_opened=4, cache="hit"),
        _build("lower", "run", 0.9, job=None, jobs_opened=9),
        _build("load", "run", 0.2, job=None, jobs_opened=9, cache="hit"),
    ]
    return cold, builds


def test_parts_of_hand_made_records_gives_the_eight_numbers(reader):
    got = reader.parts(*_process())
    assert set(got) == set(PARTS)
    assert got["cold_trace_s"] == pytest.approx(1.7)
    assert got["cold_lower_s"] == pytest.approx(1.0)
    assert got["cold_load_s"] == pytest.approx(0.05 + 0.2 + 0.07)
    assert got["cold_cache_misses"] == 2            # fold and run
    assert got["cold_stray_build_s"] == pytest.approx(0.02 + 0.3 + 0.01)
    # 5.0 less build_start 0.4, fold_rows 1.0 (row_lengths inside it),
    # build_loop 3.0, first_run 0.25
    assert got["cold_unspanned_s"] == pytest.approx(0.35)
    # the generator's and the reference check's; not cold_account's
    assert got["setup_outside_build_s"] == pytest.approx(0.5 + 6.0 + 0.9)
    assert got["retraces_in_window"] == 3           # job 4's, not job 3's


def test_spans_that_overlap_or_leave_the_job_count_what_they_cover(reader):
    assert reader._union_s([(1, 3), (2, 4), (6, 9), (-5, 0.5)], 0, 8) \
        == pytest.approx(0.5 + 3 + 2)
    assert reader._union_s([], 0, 8) == 0.0


def test_no_first_job_nothing(reader):
    cold, builds = _process()
    assert reader.parts([r for r in cold if r["phase"] != "first_job"],
                        builds) is None
    assert reader.parts([], builds) is None
    # a first job and no build at all: zeros, and its seconds unspanned
    got = reader.parts([_span("first_job", 0.0, 2.0)], [])
    assert got["cold_unspanned_s"] == 2.0
    assert {v for k, v in got.items() if k != "cold_unspanned_s"} == {0}


def test_nothing_where_the_tracer_keeps_no_builds(reader, monkeypatch):
    """The parent's tree: a tracer with ``cold`` and no ``builds``."""
    from cocoa_tpu.telemetry import tracing

    monkeypatch.setattr(tracing, "get_tracer", lambda: types.SimpleNamespace(
        enabled=False, cold=_process()[0]))
    cell = {}
    assert all(reader.read(None, [], cell, part=p) is None for p in PARTS)
    assert cell["build_account"] is None
    monkeypatch.setattr(tracing, "get_tracer",
                        lambda: types.SimpleNamespace(enabled=False))
    assert reader.read(None, [], {}, part="cold_trace_s") is None


@pytest.mark.parametrize("name", PARTS)
def test_read_gives_each_part_by_name(reader, monkeypatch, name):
    """What an entry's ``params`` would pass: ``part`` picks the number."""
    from cocoa_tpu.telemetry import tracing

    cold, builds = _process()
    monkeypatch.setattr(tracing, "get_tracer", lambda: types.SimpleNamespace(
        cold=cold, builds=builds))
    assert registry.NAME_RE.match(name) and len(name) <= 64
    assert reader.read(None, [], {}, part=name) \
        == reader.parts(cold, builds)[name]


def test_one_account_a_run(reader, monkeypatch):
    """Every entry would load the reader's file anew: the account lives on
    ``cell``, made by the first part read."""
    from cocoa_tpu.telemetry import tracing

    cold, builds = _process()
    calls = []

    def get_tracer():
        calls.append(1)
        return types.SimpleNamespace(cold=cold, builds=builds)

    monkeypatch.setattr(tracing, "get_tracer", get_tracer)
    cell, values = {}, {}
    for name in PARTS:
        read = registry.load_module(BENCH, "readers", "build_account").read
        values[name] = read(None, [], cell, part=name)
    assert len(calls) == 1
    assert values == reader.parts(cold, builds)


def test_a_real_first_job_on_the_cpu(reader, tiny_data):
    """The program's records through the reader, with ``cold_account``
    read first as the harness reads it: its ``program_memory`` compile
    lands in none of the eight."""
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data import shard_dataset
    from cocoa_tpu.solvers import base, cocoa, run_cocoa
    from cocoa_tpu.telemetry import tracing

    tracing.reset()
    kept = [(c, dict(c)) for c in (cocoa._START_PROGRAMS, base._DEVICE_RUNS)]
    try:
        for cache, _ in kept:
            cache.clear()
        jnp.arange(11.0) * 2.5              # a build before any entry
        ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=jnp.float32)

        def job():
            run_cocoa(ds, Params(n=ds.n, num_rounds=10, local_iters=8,
                                 lam=1e-2),
                      DebugParams(debug_iter=5, seed=0), plus=True,
                      quiet=True, math="fast", device_loop=True,
                      rng="permuted")

        job()
        job()                               # the window's: warm
        cell = {}
        temp, _ = registry.layer_reader(BENCH, "hbm_program_temp_gb")
        assert temp(None, [], cell, part="hbm_program_temp_gb") is not None
        late = [b for b in tracing.get_tracer().builds
                if b["jobs_opened"] == 2]
        assert {b["fun_name"] for b in late} == {"run"}     # its compile
        values = {name: reader.read(None, [], cell, part=name)
                  for name in PARTS}
        build_s = cell["cold_account"]["cold_build_s"]
        job_s = cell["cold_account"]["cold_job_s"]
    finally:
        for cache, was in kept:
            cache.update(was)
        tracing.reset()
    assert all(v is not None for v in values.values())
    assert values["retraces_in_window"] == 0
    assert values["cold_cache_misses"] in (0, 1, 2)
    stages = (values["cold_trace_s"] + values["cold_lower_s"]
              + values["cold_load_s"])
    assert 0.5 * build_s < stages <= build_s
    assert 0 <= values["cold_unspanned_s"] < job_s - build_s + 1e-6
    assert values["setup_outside_build_s"] > 0
    assert values["cold_stray_build_s"] >= 0
