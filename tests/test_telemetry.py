"""The in-loop telemetry subsystem (cocoa_tpu/telemetry/).

What these tests pin:

- **event ordering + JSONL schema** on both the host-chunked and the
  device-resident drive* paths — every run leaves a seq-ordered typed
  stream that cocoa_tpu/telemetry/schema.py accepts;
- **io_callback-path vs fetch-fallback parity**: the live device stream
  (ordered io_callback inside the lax.while_loop) and the end-of-run
  fetch replay emit the SAME events with the SAME values — they decode
  the same f32 buffer through the same DeviceTap;
- **soundness**: enabling telemetry leaves the final ``(w, alpha)`` AND
  the σ′-schedule sched leaf bit-identical to a telemetry-off run (the
  bridge is side-effect-only: nothing in the loop carry reads it);
- the satellites: trajectory dumps carry a manifest header and the
  ``stopped`` reason; ``--quiet`` divergence still emits a
  machine-readable event; the metrics textfile counters; the schema
  checker accepts benchmark result rows and rejects malformed streams.
"""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from cocoa_tpu import checkpoint as ckpt_lib
from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.solvers import run_cocoa
from cocoa_tpu.telemetry import events as tele_events
from cocoa_tpu.telemetry import schema as tele_schema
from cocoa_tpu.telemetry.metrics import MetricsWriter
from cocoa_tpu.utils.logging import Trajectory
from test_divergence import _coherent_dataset

K, LAM = 4, 1e-4


@pytest.fixture(autouse=True)
def clean_bus():
    """Every test starts and ends with an inert bus (the process-global
    singleton must not leak sinks between tests)."""
    tele_events.get_bus().reset()
    yield tele_events.get_bus()
    tele_events.get_bus().reset()


def _collect():
    events = []
    tele_events.get_bus().subscribe(events.append)
    return events


def _backoff_run(device_loop, **kw):
    """The forced-backoff config (test_sigma_anneal's fixture): σ′ start
    1.0 = K·γ/4 on adversarially coherent shards, cadence 25 — the anneal
    schedule MUST back off in-loop before certifying."""
    ds, n = _coherent_dataset(k=K)
    params = Params(n=n, num_rounds=1600, local_iters=16, lam=LAM,
                    sigma=1.0)
    debug = kw.pop("debug", None) or DebugParams(debug_iter=25, seed=0)
    return run_cocoa(ds, params, debug, plus=True, quiet=True, math="fast",
                     device_loop=device_loop, gap_target=1e-3, rng="jax",
                     sigma_schedule="anneal", **kw)


def _strip(events, drop=("ts", "seq")):
    """Comparable view of an event stream: timing fields dropped, and the
    sanitizer's transport-bookkeeping events (``host_transfer``/
    ``compile``, analysis/sanitize.py) filtered out — their position is
    inherently path-dependent (the live stream emits evals BEFORE the
    end-of-run fetch; the fetch-replay bridge emits them after), while
    the parity contract here is about the decoded eval/backoff events."""
    return [{k: v for k, v in e.items() if k not in drop}
            for e in events
            if e.get("event") not in ("host_transfer", "compile")]


# --- the acceptance pin -----------------------------------------------------


def test_device_stream_matches_fetched_trajectory_bitforbit():
    """A --sigmaSchedule=anneal forced-backoff run on the device-resident
    path emits ordered round_eval and sigma_backoff events DURING the run
    (io_callback path) whose values match the end-of-run fetched
    trajectory bit-for-bit."""
    assert tele_events.io_callback_supported(), \
        "this jax must support the ordered io_callback bridge"
    events = _collect()
    w, alpha, traj = _backoff_run(device_loop=True)
    assert traj.stopped == "target"

    evals = [e for e in events if e["event"] == "round_eval"]
    backoffs = [e for e in events if e["event"] == "sigma_backoff"]
    assert len(evals) == len(traj.records)
    for e, r in zip(evals, traj.records):
        assert e["t"] == r.round
        assert e["primal"] == r.primal      # bit-for-bit: same f32 buffer
        assert e["gap"] == r.gap
        assert e["sigma"] == r.sigma
    # the schedule was FORCED to back off, and each backoff event lands
    # exactly where consecutive records change σ′
    assert len(backoffs) >= 1
    rec_transitions = [
        (b.round, a.sigma, b.sigma)
        for a, b in zip(traj.records, traj.records[1:]) if a.sigma != b.sigma
    ]
    assert [(e["t"], e["from_sigma"], e["sigma"]) for e in backoffs] \
        == rec_transitions
    # ordered: seq strictly increasing, and each backoff follows the
    # round_eval that triggered it
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    for b in backoffs:
        trigger = [e for e in evals if e["t"] == b["t"]]
        assert trigger and trigger[0]["seq"] < b["seq"]


def test_io_callback_path_vs_fetch_fallback_parity(monkeypatch):
    """Forcing the fetch-fallback bridge (io_callback 'unavailable') must
    produce the same events with the same values — and the same final
    state — as the live stream."""
    streamed = _collect()
    w1, a1, t1 = _backoff_run(device_loop=True)
    tele_events.get_bus().reset()

    monkeypatch.setattr(tele_events, "io_callback_supported", lambda: False)
    replayed = _collect()
    w2, a2, t2 = _backoff_run(device_loop=True)

    assert _strip(streamed) == _strip(replayed)
    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


def test_telemetry_on_vs_off_state_bit_identical(tmp_path):
    """Telemetry must be side-effect-only: (w, alpha) and the sched leaf
    (via the checkpoints, which carry it) are bit-identical with the bus
    active vs inert."""
    debug_on = DebugParams(debug_iter=25, seed=0, chkpt_iter=100,
                           chkpt_dir=str(tmp_path / "on"))
    debug_off = DebugParams(debug_iter=25, seed=0, chkpt_iter=100,
                            chkpt_dir=str(tmp_path / "off"))
    tele_events.get_bus().configure(
        jsonl_path=str(tmp_path / "events.jsonl"))
    w1, a1, t1 = _backoff_run(device_loop=True, debug=debug_on)
    tele_events.get_bus().reset()
    w2, a2, t2 = _backoff_run(device_loop=True, debug=debug_off)

    np.testing.assert_array_equal(np.asarray(w1), np.asarray(w2))
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))
    names = sorted(os.listdir(tmp_path / "on"))
    assert names == sorted(os.listdir(tmp_path / "off"))
    for name in names:
        if not name.endswith(".npz"):
            continue
        m1, _, _ = ckpt_lib.load(str(tmp_path / "on" / name))
        m2, _, _ = ckpt_lib.load(str(tmp_path / "off" / name))
        assert m1["sched"] == m2["sched"], name   # the sched leaf, exact


# --- host-chunked vs device-resident event streams --------------------------


def test_host_and_device_paths_emit_identical_streams():
    """The host-chunked twin makes identical schedule decisions
    (both run base.eval_boundary_update), so the two paths'
    event streams must agree on every value the math determines."""
    ev_host = _collect()
    _backoff_run(device_loop=False)
    tele_events.get_bus().reset()
    ev_dev = _collect()
    _backoff_run(device_loop=True)

    keep = ("event", "algorithm", "t", "primal", "gap", "sigma",
            "sigma_stage", "stall")
    host = [{k: e.get(k) for k in keep} for e in ev_host
            if e["event"] in ("round_eval", "sigma_backoff")]
    dev = [{k: e.get(k) for k in keep} for e in ev_dev
           if e["event"] in ("round_eval", "sigma_backoff")]
    # sigma_backoff carries no stage/stall on the host path's event? it
    # does (stage) — normalize by comparing the common projection
    assert host == dev


def test_event_jsonl_schema_both_paths(tmp_path):
    for device_loop, name in ((False, "host"), (True, "dev")):
        path = str(tmp_path / f"events.{name}.jsonl")
        tele_events.get_bus().reset()
        tele_events.get_bus().configure(jsonl_path=path)
        _backoff_run(device_loop=device_loop)
        tele_events.get_bus().emit("run_end", algorithm="CoCoA+",
                                   primal=0.0, stopped="target")
        errs = tele_schema.check_file(path)
        assert errs == [], errs


# --- satellites -------------------------------------------------------------


def test_trajectory_dump_manifest_and_stopped(tmp_path):
    w, alpha, traj = _backoff_run(device_loop=True)
    traj.meta = {"dataset": "synthetic-coherent", "config_hash": "abc123"}
    path = str(tmp_path / "traj.jsonl")
    traj.dump_jsonl(path)
    lines = [json.loads(s) for s in open(path)]
    man = lines[0]["manifest"]
    assert man["algorithm"] == "CoCoA+"
    assert man["dataset"] == "synthetic-coherent"
    assert man["config_hash"] == "abc123"
    assert "jax_version" in man and "backend" in man
    assert "stopped" not in lines[-2]       # only the FINAL record
    assert lines[-1]["stopped"] == "target"
    assert tele_schema.check_file(path) == []


def test_quiet_divergence_still_leaves_event_trace(capsys):
    """--quiet silences the console DIVERGED notice but the divergence
    event must still be emitted — the machine-readable trace of the
    bail-out is the point of the bus."""
    ds, n = _coherent_dataset(k=K)
    params = Params(n=n, num_rounds=1600, local_iters=16, lam=LAM,
                    sigma=1.0)
    debug = DebugParams(debug_iter=25, seed=0)
    events = _collect()
    w, a, traj = run_cocoa(ds, params, debug, plus=True, quiet=True,
                           math="fast", gap_target=1e-3, rng="jax")
    assert traj.stopped == "diverged"
    assert "DIVERGED" not in capsys.readouterr().out
    div = [e for e in events if e["event"] == "divergence"]
    assert len(div) == 1
    assert div[0]["algorithm"] == "CoCoA+"
    assert div[0]["t"] == traj.records[-1].round
    assert div[0]["n_evals"] >= 12


def test_checkpoint_write_events(tmp_path):
    events = _collect()
    debug = DebugParams(debug_iter=25, seed=0, chkpt_iter=100,
                        chkpt_dir=str(tmp_path))
    _backoff_run(device_loop=True, debug=debug)
    writes = [e for e in events if e["event"] == "checkpoint_write"]
    assert writes, "chkptIter=100 must have produced checkpoint events"
    for e in writes:
        assert e["algorithm"] == "CoCoA+"
        assert f"r{e['round']:06d}" in e["path"]
    # only the newest KEEP_GENERATIONS survive on disk (generation
    # pruning); every event still names the path it wrote at the time
    from cocoa_tpu import checkpoint as _ck

    for e in writes[-_ck.KEEP_GENERATIONS:]:
        assert os.path.exists(e["path"])
    for e in writes[:-_ck.KEEP_GENERATIONS]:
        assert not os.path.exists(e["path"])


def test_sigma_trial_restart_event(tmp_path, monkeypatch):
    """The --sigmaSchedule=trial rerun emits a typed restart event (the
    spy-diverged-trial fixture from test_divergence)."""
    from cocoa_tpu.solvers import cocoa as cocoa_mod
    from cocoa_tpu.utils.logging import RoundRecord

    ds, n = _coherent_dataset(k=K)
    real = cocoa_mod.run_sdca_family

    def spy(ds_, params_, debug_, name_, alg, **kw):
        if alg[2] == K / 2.0:
            t = Trajectory(name_, quiet=True)
            t.records.append(RoundRecord(round=392, wall_time=None, gap=5.0))
            t.stopped = "diverged"
            return None, None, t
        return real(ds_, params_, debug_, name_, alg, **kw)

    monkeypatch.setattr(cocoa_mod, "run_sdca_family", spy)
    events = _collect()
    params = Params(n=n, num_rounds=400, local_iters=16, lam=LAM,
                    sigma="auto")
    debug = DebugParams(debug_iter=4, seed=0)
    w, a, traj = run_cocoa(ds, params, debug, plus=True, quiet=True,
                           math="fast", gap_target=1e-3, rng="jax",
                           sigma_schedule="trial")
    restarts = [e for e in events if e["event"] == "restart"]
    assert len(restarts) == 1
    assert restarts[0]["reason"] == "sigma_trial_diverged"
    assert restarts[0]["sigma_trial"] == K / 2.0
    assert restarts[0]["sigma_safe"] == float(K)


def test_metrics_textfile(tmp_path):
    path = str(tmp_path / "metrics.prom")
    tele_events.get_bus().configure(metrics_path=path)
    w, alpha, traj = _backoff_run(device_loop=True)
    text = open(path).read()
    vals = {line.split(" ")[0]: line.split(" ")[1]
            for line in text.splitlines() if not line.startswith("#")}
    assert int(vals["cocoa_evals_total"]) == len(traj.records)
    # resume-safe counter: rounds advance by inter-eval deltas only (the
    # first observed eval anchors without crediting pre-resume history)
    assert int(vals["cocoa_rounds_total"]) \
        == traj.records[-1].round - traj.records[0].round
    assert int(vals["cocoa_sigma_backoffs_total"]) >= 1
    assert float(vals["cocoa_last_gap"]) == traj.records[-1].gap
    assert 'cocoa_round_seconds_bucket{le="+Inf"}' in text
    # atomic refresh convention: no temp litter left behind
    assert [f for f in os.listdir(tmp_path) if ".tmp." in f] == []


def test_round_window_profiler(monkeypatch):
    """The --profile=dir,start,stop windower starts at the first eval
    >= start and stops at the first >= stop — driven purely by the event
    stream, which is what makes it work mid-while_loop on the device
    path."""
    calls = []
    import jax

    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append(("start", d)))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append(("stop",)))
    from cocoa_tpu.telemetry.profiling import RoundWindowProfiler

    prof = RoundWindowProfiler("/tmp/_win", 100, 200)
    tele_events.get_bus().subscribe(prof)
    events = _collect()
    _backoff_run(device_loop=True)
    prof.close()
    assert calls[0] == ("start", "/tmp/_win") and calls[1] == ("stop",)
    assert len(calls) == 2
    # the window triggered at the right evals (cadence 25: start at 100,
    # stop at the first eval >= 200)
    evals = [e["t"] for e in events if e["event"] == "round_eval"]
    assert 100 in evals and 200 in evals


@pytest.mark.parametrize("row, mistyped", [
    # the --row artifact of benchmarks/fleet_bench.py ...
    ({"config": "fleet-256-synth", "type": "fleet", "tenants": 256,
      "certified": 256, "rounds": 80, "gap": 9.9e-3, "stopped": "target",
      "gap_target": 1e-2, "models_per_second": 1.0, "compiles": 1,
      "lane_exec": "vmap", "device": "cpu"}, ("certified", "all")),
    # ... and of benchmarks/serve_bench.py
    ({"config": "serve-cpu-synth", "type": "serve", "device": "cpu",
      "d": 512, "queries": 1000, "qps": 1.0, "p50_ms": 1.0, "p99_ms": 2.0,
      "sla_ms": 50.0, "buckets": "64/256", "compiles": 2, "swaps": 1,
      "stopped": None}, ("p99_ms", "2.0")),
], ids=["fleet", "serve"])
def test_schema_checker_results_rows(tmp_path, row, mistyped):
    good = tmp_path / "row.jsonl"
    good.write_text(json.dumps(row) + "\n")
    assert tele_schema.check_file(str(good), kind="results") == []
    assert tele_schema.check_file(str(good)) == []    # sniffed
    field, wrong = mistyped
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps({**row, field: wrong}) + "\n")
    errs = tele_schema.check_file(str(bad), kind="results")
    assert any(field in e for e in errs)
    nameless = tmp_path / "nameless.jsonl"
    nameless.write_text(json.dumps(
        {k: v for k, v in row.items() if k != "config"}) + "\n")
    assert any("config" in e for e in
               tele_schema.check_file(str(nameless), kind="results"))


def test_schema_checker_rejects_malformed(tmp_path):
    bad = tmp_path / "bad.jsonl"
    bad.write_text(
        '{"event": "round_eval", "seq": 2, "ts": 1.0, "algorithm": "X", '
        '"t": 10, "primal": 1.0, "gap": null, "test_error": null, '
        '"sigma": null, "stall": null}\n'
        '{"event": "round_eval", "seq": 1, "ts": 1.0, "algorithm": "X", '
        '"t": 20, "primal": 1.0, "gap": null, "test_error": null, '
        '"sigma": null, "stall": null}\n'
        '{"event": "nonsense", "seq": 3, "ts": 1.0}\n')
    errs = tele_schema.check_file(str(bad))
    assert any("seq" in e for e in errs)          # order violation
    assert any("nonsense" in e for e in errs)     # unknown type
    assert tele_schema.main([str(bad)]) == 1
    # a trajectory missing its manifest header is rejected too
    traj = tmp_path / "traj.jsonl"
    traj.write_text('{"algorithm": "X", "round": 1, "wall_time": null}\n')
    assert tele_schema.check_file(str(traj), kind="trajectory") != []


def test_run_start_layout_split_schema(tmp_path):
    """The run_start manifest's layout_split record (--hotCols provenance,
    ISSUE 5 satellite): a well-formed record validates; wrong-typed fields
    and a non-object record are schema violations."""
    split = {"spec": "auto", "hot_cols": 2048, "coverage": 0.75,
             "residual_mean_nnz": 18.4, "residual_max_nnz": 214,
             "panel_bytes": 166723584}
    good = tmp_path / "good.jsonl"
    good.write_text(json.dumps(
        {"event": "run_start", "seq": 1, "ts": 1.0,
         "manifest": {"config": {}, "config_hash": "x",
                      "layout_split": split}}) + "\n")
    assert tele_schema.check_file(str(good)) == []
    bad = tmp_path / "bad.jsonl"
    bad.write_text(json.dumps(
        {"event": "run_start", "seq": 1, "ts": 1.0,
         "manifest": {"layout_split": {**split, "coverage": "high",
                                       "hot_cols": 2048.5}}}) + "\n")
    errs = tele_schema.check_file(str(bad))
    assert any("coverage" in e for e in errs)
    assert any("hot_cols" in e for e in errs)
    worse = tmp_path / "worse.jsonl"
    worse.write_text(json.dumps(
        {"event": "run_start", "seq": 1, "ts": 1.0,
         "manifest": {"layout_split": [1, 2]}}) + "\n")
    assert any("layout_split" in e
               for e in tele_schema.check_file(str(worse)))


def test_cli_emits_layout_split_in_run_start(tmp_path):
    """A sparse --hotCols CLI run records the resolved split in its
    run_start manifest — machine-readable benchmark provenance."""
    from cocoa_tpu import cli
    from cocoa_tpu.data.synth import synth_sparse, write_libsvm

    path = str(tmp_path / "train.dat")
    write_libsvm(synth_sparse(120, 500, nnz_mean=10, seed=2), path)
    ev = str(tmp_path / "events.jsonl")
    rc = cli.main([
        f"--trainFile={path}", "--numFeatures=500", "--numSplits=4",
        "--numRounds=2", "--localIterFrac=0.2", "--debugIter=2",
        "--mesh=1", "--quiet", "--hotCols=128", f"--events={ev}",
    ])
    assert rc == 0
    assert tele_schema.check_file(ev) == []
    starts = [json.loads(ln) for ln in open(ev)
              if json.loads(ln)["event"] == "run_start"]
    assert len(starts) == 1
    split = starts[0]["manifest"]["layout_split"]
    assert split["hot_cols"] == 128
    assert 0.0 < split["coverage"] <= 1.0
    assert split["residual_mean_nnz"] >= 0.0
    assert split["panel_bytes"] > 0


def test_inactive_bus_is_inert():
    """With no sink configured, emit() is a no-op and solver runs stay on
    the non-streaming executable (no tap, no events, no files)."""
    bus = tele_events.get_bus()
    assert not bus.active()
    assert bus.emit("round_eval", algorithm="X", t=1, primal=0.0) is None
    w, alpha, traj = _backoff_run(device_loop=True)
    assert traj.stopped == "target"
