"""``chipbench/readers/cold_account.py``: ``peak_hbm_gb`` and ``setup_s`` by
part, from the cold records the program's tracer keeps of its first job.
On a CPU: hand-made records, a faked allocator, and one real tiny job."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import registry  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
HBM_PARTS = ["hbm_entry_gb", "hbm_rise_layout_gb", "hbm_rise_job_gb",
             "hbm_rise_after_gb"]
NEW = HBM_PARTS + ["hbm_resident_gb", "hbm_program_temp_gb", "cold_layout_s",
                   "cold_build_s", "cold_job_s"]
MB = 1_000_000
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.fixture
def reader():
    return registry.load_module(BENCH, "readers", "cold_account")


def _reading(*per_device):
    """One HBM reading: ``(in use, peak)`` in MB for device 0, 1, ..."""
    return [{"device": i, "bytes_in_use": used * MB,
             "peak_bytes_in_use": peak * MB}
            for i, (used, peak) in enumerate(per_device)]


def _record(phase, dur_s, opened, closed, job=1, **more):
    return dict(phase=phase, span_id=0, parent_id=None, job=job, start_s=0.0,
                dur_s=dur_s, hbm_open=_reading(*opened),
                hbm_close=_reading(*closed), program=None, **more)


def _dense_first_job():
    """A dense cell's first job as the spans leave it (they close in this
    order): 3,200 MB of rows at entry, a fold that holds as much again and
    peaks 80 over it, a start program of 4 MB, a loop that peaks 20 higher
    and hands its temporaries back."""
    return [
        _record("build_start", 0.4, [(3200, 3210)], [(3204, 3210)]),
        _record("fold_rows", 1.5, [(3204, 3210)], [(6484, 6564)]),
        _record("build_loop", 0.7, [(6484, 6564)], [(6500, 6584)]),
        _record("first_run", 0.3, [(6500, 6584)], [(6486, 6584)]),
        _record("first_job", 3.1, [(3200, 3210)], [(6486, 6584)]),
    ]


def test_the_four_parts_add_to_the_final_peak(reader):
    got = reader.parts(_dense_first_job(), {0: 6590 * MB},
                       temp_bytes=3280 * MB)
    assert got["hbm_entry_gb"] == pytest.approx(3.210)
    assert got["hbm_rise_layout_gb"] == pytest.approx(3.354)
    assert got["hbm_rise_job_gb"] == pytest.approx(0.020)
    assert got["hbm_rise_after_gb"] == pytest.approx(0.006)
    assert sum(got[p] for p in HBM_PARTS) == pytest.approx(6.590, abs=1e-9)
    assert got["hbm_resident_gb"] == pytest.approx(6.486)
    assert got["hbm_program_temp_gb"] == pytest.approx(3.280)
    assert got["cold_layout_s"] == pytest.approx(1.5)
    assert got["cold_build_s"] == pytest.approx(1.1)
    assert got["cold_job_s"] == pytest.approx(3.1)
    assert set(got) == set(NEW)


def test_a_sparse_first_job_sums_its_layout_spans(reader):
    records = [
        _record("order_rows", 9.5, [(10300, 10300)], [(10300, 15900)]),
        _record("build_start", 0.2, [(10300, 15900)], [(10530, 15900)]),
        _record("row_lengths", 0.1, [(10530, 15900)], [(10610, 15900)]),
        _record("build_loop", 40.0, [(10610, 15900)], [(11000, 16300)]),
        _record("first_run", 56.0, [(11000, 16300)], [(10700, 16300)]),
        _record("first_job", 106.0, [(10300, 10300)], [(10700, 16300)]),
    ]
    got = reader.parts(records, {0: 16300 * MB})
    assert got["hbm_rise_layout_gb"] == pytest.approx(5.6)
    assert got["hbm_rise_job_gb"] == pytest.approx(0.4)
    assert got["hbm_rise_after_gb"] == 0.0
    assert got["cold_layout_s"] == pytest.approx(9.6)
    assert "hbm_program_temp_gb" not in got


def test_several_devices_give_the_fullest_ones_numbers(reader):
    # device 1 ends fullest (the final reading says so), though device 0
    # was ahead when the job closed
    records = [
        _record("fold_rows", 2.0, [(5000, 5000), (5100, 5100)],
                [(10000, 10400), (10100, 10300)]),
        _record("first_job", 5.0, [(5000, 5000), (5100, 5100)],
                [(10000, 10400), (10100, 10300)]),
    ]
    got = reader.parts(records, {0: 10400 * MB, 1: 10516 * MB, 2: None})
    assert got["hbm_entry_gb"] == pytest.approx(5.1)
    assert got["hbm_rise_layout_gb"] == pytest.approx(5.2)
    assert got["hbm_rise_job_gb"] == 0.0
    assert got["hbm_rise_after_gb"] == pytest.approx(0.216)
    assert got["hbm_resident_gb"] == pytest.approx(10.1)
    assert sum(got[p] for p in HBM_PARTS) == pytest.approx(10.516)


def test_no_counters_no_bytes_and_no_first_job_nothing(reader):
    records = _dense_first_job()
    for r in records:
        for d in r["hbm_open"] + r["hbm_close"]:
            d.update(bytes_in_use=None, peak_bytes_in_use=None)
    got = reader.parts(records, {0: None})
    assert set(got) == {"cold_layout_s", "cold_build_s", "cold_job_s"}
    assert reader.parts(records[:-1], {0: 6590 * MB}) is None
    assert reader.parts([], {0: 6590 * MB}) is None


def test_nothing_on_a_tree_whose_tracer_keeps_no_cold_list(reader,
                                                           monkeypatch):
    from cocoa_tpu.telemetry import tracing

    monkeypatch.setattr(tracing, "get_tracer",
                        lambda: types.SimpleNamespace(enabled=False))
    cell = {}
    assert all(reader.read(None, [], cell, part=p) is None for p in NEW)
    assert cell["cold_account"] is None


@pytest.mark.parametrize("name", NEW)
def test_new_entries_resolve_to_the_one_reader(name):
    (metric,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    read, params = registry.layer_reader(BENCH, name)
    assert read.__module__.endswith("cold_account") and params == {
        "part": name}
    # every cell reads the account under these nine names, one entry a
    # part (PR 55): a later cell owes them for nothing
    assert "workloads" not in metric
    assert metric["source"] == "program_counter"
    assert metric["moves"] == ("setup_s" if name.startswith("cold_")
                               else "peak_hbm_gb")
    assert metric["unit"] == ("s" if name.startswith("cold_") else "GB")


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_owes_the_nine_under_the_unprefixed_names(cell):
    """One name a reading: no entry but these nine resolves to the cold
    account, and each cell owes all nine."""
    owed = registry.metrics_of(BENCH, "per_layer", cell)
    account = [m["name"] for m in owed if registry.layer_reader(
        BENCH, m["name"])[0].__module__.endswith("cold_account")]
    assert sorted(account) == sorted(NEW)


def test_one_account_a_run_its_final_reading_first(monkeypatch):
    """Every entry loads the reader's file anew (``registry.load_module``):
    the account lives on ``cell``, made by the first entry read — the final
    reading before the compile that ``program_memory`` is."""
    import jax

    from cocoa_tpu.telemetry import tracing

    log = []

    def stats(device):
        log.append("memory_stats")
        return {"peak_bytes_in_use": 6590 * MB}

    def sizes(record):
        log.append("program_memory")
        assert record["phase"] == "build_loop"
        return dict(argument=1, output=2, alias=3, temp=3280 * MB,
                    generated_code=5)

    records = _dense_first_job() + [
        _record("fold_rows", 9.0, [(0, 0)], [(9000, 9000)], job=7),
        _record("first_job", 9.0, [(0, 0)], [(9000, 9000)], job=7)]
    monkeypatch.setattr(tracing, "memory_stats", stats)
    monkeypatch.setattr(tracing, "program_memory", sizes)
    monkeypatch.setattr(tracing.get_tracer(), "cold", records)
    cell, values = {}, {}
    for name in NEW:
        read, params = registry.layer_reader(BENCH, name)
        values[name] = read(None, [], cell, **params)
    assert log == ["memory_stats"] * len(jax.local_devices()) + [
        "program_memory"]
    assert sum(values[p] for p in HBM_PARTS) == pytest.approx(6.590)
    assert values["cold_job_s"] == 3.1 and values["cold_layout_s"] == 1.5
    assert values["hbm_program_temp_gb"] == pytest.approx(3.28)


def test_a_program_the_compiler_refuses_costs_one_metric(monkeypatch,
                                                         capsys):
    from cocoa_tpu.telemetry import tracing

    def refuse(record):
        raise RuntimeError("RESOURCE_EXHAUSTED")

    monkeypatch.setattr(tracing, "memory_stats", lambda d: None)
    monkeypatch.setattr(tracing, "program_memory", refuse)
    monkeypatch.setattr(tracing.get_tracer(), "cold", _dense_first_job())
    read, params = registry.layer_reader(BENCH, "hbm_program_temp_gb")
    cell = {}
    assert read(None, [], cell, **params) is None
    assert "RESOURCE_EXHAUSTED" in capsys.readouterr().err
    assert cell["cold_account"]["cold_job_s"] == 3.1


def test_a_real_first_job_on_the_cpu(tiny_data):
    """The program's records through the reader: the seconds and the loop
    program's temporaries (the compiler's, on any backend); no bytes of
    HBM, the CPU's allocator keeps no counters."""
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
        ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=jnp.float32)
        run_cocoa(ds, Params(n=ds.n, num_rounds=10, local_iters=8, lam=1e-2),
                  DebugParams(debug_iter=5, seed=0), plus=True, quiet=True,
                  math="fast", device_loop=True, rng="permuted")
        cell, values = {}, {}
        for name in NEW:
            read, params = registry.layer_reader(BENCH, name)
            values[name] = read(None, [], cell, **params)
    finally:
        for cache, was in kept:
            cache.update(was)
        tracing.reset()
    assert {k for k, v in values.items() if v is not None} == {
        "cold_layout_s", "cold_build_s", "cold_job_s", "hbm_program_temp_gb"}
    assert 0 < values["cold_build_s"] < values["cold_job_s"]
    assert values["cold_layout_s"] == 0.0       # fori folds nothing
    assert values["hbm_program_temp_gb"] >= 0
