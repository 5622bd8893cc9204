"""The benchmark's harness, on the CPU: BENCHMARK.json has the contract's
form, every name in it resolves to a file, files added beside the others
are picked up with no code change, the command refuses to run without a
chip, and the inner ``run_cell`` produces the contract's line at tiny
shapes (Pallas kernels interpreted, the mesh path on virtual devices).
Counts and correctness only — a CPU gives no time worth keeping."""

import functools
import glob
import inspect
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from chipbench import cost_model, reduce_trace, registry, run  # noqa: E402

from owed import COLD  # noqa: E402

TINY = os.path.join(os.path.dirname(os.path.abspath(__file__)), "fixtures",
                    "tiny")
BENCH = registry.load_benchmark(ROOT)
CELLS = [w["name"] for w in BENCH["workloads"]]
LAYER_METRICS = [m["name"] for m in BENCH["per_layer"]]
E2E = [m["name"] for m in BENCH["end_to_end"]]
with open(os.path.join(TINY, "BENCHMARK.json")) as _f:
    TINY_CELLS = [w["name"] for w in json.load(_f)["workloads"]]
# a layer's name: the contract's rule (a plain name, or one that starts
# with "_"); it is the name PERF.md's table of layers gives the layer
LAYER_RE = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "head",
               "expansion", "experts_per")


def copy_benchmark(dst: str, overlay: str = None) -> dict:
    """A copy of chipbench/ under ``dst``, optionally with the files of a
    fixture laid over it (its BENCHMARK.json, configs, jobs, ...)."""
    shutil.copytree(os.path.join(ROOT, "chipbench"),
                    os.path.join(dst, "chipbench"),
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(overlay or ROOT, "BENCHMARK.json"), dst)
    if overlay:
        for sub in os.listdir(overlay):
            if os.path.isdir(os.path.join(overlay, sub)):
                shutil.copytree(os.path.join(overlay, sub),
                                os.path.join(dst, "chipbench", sub),
                                dirs_exist_ok=True)
    return registry.load_benchmark(dst)


@pytest.fixture(scope="module")
def tiny_bench(tmp_path_factory):
    return copy_benchmark(str(tmp_path_factory.mktemp("tiny")), TINY)


# --- BENCHMARK.json has the contract's form ---------------------------------


def test_benchmark_json_form():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    bench = json.loads(raw)
    assert len(raw) <= 64 << 10
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"] == ["python3", "-m", "chipbench.run"]
    assert bench["paths"] == ["chipbench", "tests/chipbench"]
    assert 2 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    names = [e["name"] for kind in ("configs", "workloads", "end_to_end",
                                    "per_layer") for e in bench[kind]]
    assert len(names) == len(set(names)), "a name is used twice"
    assert all(registry.NAME_RE.match(n) for n in names)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)
    assert {c["name"] for c in bench["configs"]} == \
        {w["config"] for w in bench["workloads"]}, "a config no cell uses"
    # a full check with all 24 cells must fit the driver's 43200 s
    s = bench["run_seconds"]
    assert isinstance(s, int) and 1 <= s <= 51
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    setup = [m for m in bench["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] == 0.1
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert "bound" not in m


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file(entry):
    cfg = registry.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["file"].startswith("chipbench/configs/")
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    assert not [k for k in cfg["reduced"]
                if k.endswith(("_dim", "_rank")) or k == "d"
                or any(w in k for w in WIDTH_WORDS)], "a width was reduced"
    for key in ("n", "d", "layout", "dtype", "loss", "lambda", "num_splits",
                "local_iter_frac", "generator", "deployment", "guarantees",
                "assumed"):
        assert key in cfg, f"{entry['file']} lacks {key!r}"
    assert len(entry["why"]) <= 200


# --- every name resolves to a file ------------------------------------------


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves(name):
    from cocoa_tpu import solvers

    cell = registry.resolve_cell(BENCH, name)
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    assert registry.NAME_RE.match(cell["config"]["name"])
    assert registry.NAME_RE.match(cell["job"]["name"])
    gen = registry.load_module(BENCH, "generators",
                               cell["config"]["generator"])
    check = registry.load_module(BENCH, "checks", cell["job"]["check"])
    assert callable(gen.make)
    assert callable(check.audit) and callable(check.job_problem)
    assert callable(getattr(solvers, cell["job"]["entry"]))
    reported = {m["name"] for m in registry.metrics_of(BENCH, "end_to_end",
                                                       name)}
    assert "setup_s" in reported and len(reported) >= 2
    assert registry.metrics_of(BENCH, "per_layer", name)


@pytest.mark.parametrize("name", LAYER_METRICS)
def test_layer_metric_resolves(name):
    (metric,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    read, params = registry.layer_reader(BENCH, name)
    assert callable(read) and isinstance(params, dict)
    assert LAYER_RE.match(metric["layer"]), metric["layer"]
    with open(os.path.join(ROOT, "PERF.md")) as f:
        assert f"`{metric['layer']}`" in f.read(), \
            f"PERF.md names no layer {metric['layer']!r}"
    assert metric["moves"] in E2E
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
        assert metric["moves"] in {
            m["name"] for m in registry.metrics_of(BENCH, "end_to_end", cell)
        }, f"{name} moves {metric['moves']}, which {cell} does not report"


@functools.lru_cache(maxsize=None)
def _reader_of(name):
    """``(reader's module, its parameters as text)``: what two entries
    have in common when they read the same thing."""
    read, params = registry.layer_reader(BENCH, name)
    return (read.__module__.rsplit("_readers_", 1)[-1],
            json.dumps(params, sort_keys=True))


LISTED = [(m["name"], cell) for m in BENCH["per_layer"]
          for cell in m.get("workloads", CELLS)]


@pytest.mark.parametrize("name,cell", LISTED,
                         ids=[f"{n}@{c}" for n, c in LISTED])
def test_what_an_entry_may_list(name, cell):
    """The ONE rule for what any per-layer entry lists, over every (entry,
    cell it exists in) of BENCHMARK.json; a cell's own test says only what
    the cell owes at least (``owed.check_cell``).

    - the cell exists, and the entry's reader resolves by the entry's name
      (``registry.layer_reader``) to a ``read(trace, jobs, cell, ...)``
      whose keyword arguments hold every one of its ``params``;
    - ONE NAME A READING (PR 55): no other entry resolves to the same
      reader and the same ``params``.  A reader never sees the entry's
      name, so two such entries would print one number twice; a cell that
      reads what another cell reads stands in that entry's ``workloads``
      and brings entries only for readers or parameters of its own;
    - an entry with NO ``workloads`` key is owed by every cell, those that
      later PRs add too: a traced line that lacks it there is refused.  The
      builder of a PR that adds such an entry shows a traced line of EVERY
      cell with a number under it, and the builder of a PR that adds a cell
      a number under every such entry; one that reads nothing somewhere
      lists the cells where it reads instead."""
    (metric,) = [m for m in BENCH["per_layer"] if m["name"] == name]
    assert cell in CELLS
    read, params = registry.layer_reader(BENCH, name)
    accepted = inspect.signature(read).parameters
    assert list(accepted)[:3] == ["trace", "jobs", "cell"]
    assert set(params) <= set(list(accepted)[3:]), \
        f"{name}: {sorted(params)} are not keyword arguments of its reader"
    twins = [m["name"] for m in BENCH["per_layer"]
             if _reader_of(m["name"]) == _reader_of(name)]
    assert twins == [name], \
        f"{twins} read one thing ({_reader_of(name)}): merge them into " \
        f"{twins[0]}, its workloads the union of theirs"


# --- the merge of PR 55 lost no reading ---------------------------------------

# the parent's 128 entries as PR 54 left them: name, the reader and the
# parameters ``registry.layer_reader`` resolved the name to, the cells it
# existed in (frozen: the 73 names that went are nowhere else)
with open(os.path.join(os.path.dirname(TINY), "per_layer_pr54.json")) as _f:
    PR54 = json.load(_f)
# the pairs PR 55 added: ilsvrc1k's cold account, which had no room
NEW_PAIRS = [(part, "ilsvrc1k.ovr_cocoa_plus") for part in COLD]


def _was(old):
    """An entry of the fixture as ``_reader_of`` would give it."""
    return old["reader"], json.dumps(old["params"], sort_keys=True)


@functools.lru_cache(maxsize=None)
def _owed_as(cell):
    """``{(reader, params as text): entry's name}`` of what a cell owes."""
    return {_reader_of(m["name"]): m["name"]
            for m in registry.metrics_of(BENCH, "per_layer", cell)}


@pytest.mark.parametrize("old", PR54, ids=lambda e: e["name"])
def test_a_reading_of_pr54_is_still_owed_where_it_was(old):
    """Every (old name, cell) pair of the parent is owed by that cell
    today under an entry that resolves to the same reader and the same
    parameters: the value is a function of (reader, params, cell, trace),
    so the merge renamed readings and lost none."""
    key = _was(old)
    assert old["cells"]
    for cell in old["cells"]:
        assert key in _owed_as(cell), \
            f"{cell} no longer reads {old['name']}'s {key}"
    survivor = {_owed_as(cell)[key] for cell in old["cells"]}
    assert len(survivor) == 1       # one name for it in every cell
    (survivor,) = survivor
    (first,) = [e for e in PR54 if e["name"] == survivor]   # no new name
    assert _was(first) == key


@pytest.mark.parametrize("name,cell", NEW_PAIRS,
                         ids=[n for n, _ in NEW_PAIRS])
def test_a_pair_pr55_added_is_the_cold_account_at_ilsvrc1k(name, cell):
    """The nine (entry, cell) pairs the parent had under no name: the
    parts of the cold account at the one cell that had no room for them.
    (That no OTHER pair of the parent's names and cells was new is PR 55's
    own count, 368 + 9 = 377: CHANGES.md; a later ``benchmark`` PR may
    list an older cell in an older entry.)"""
    key = ("cold_account", json.dumps({"part": name}))
    assert _reader_of(name) == key and (name, cell) in LISTED
    assert not [e for e in PR54 if cell in e["cells"] and _was(e) == key]


def test_no_metric_file_without_an_entry():
    """A ``layer_metrics`` file is there only for a metric of
    BENCHMARK.json whose reader takes parameters or has another name."""
    for path in glob.glob(os.path.join(BENCH["_dir"], "layer_metrics",
                                       "*.json")):
        name, spec = os.path.basename(path)[:-5], registry.load_json(path)
        assert name in LAYER_METRICS
        assert spec.get("params") or spec["reader"] != name, \
            f"{path} says nothing the metric's name does not"


def test_unknown_device_kind_is_an_error():
    assert cost_model.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device_kind 'cpu'"):
        cost_model.peaks_for("cpu")


# --- files added beside the others are found, with no code change -----------


PRIMAL_CHECK = '''"""A primal method run for a fixed budget of rounds."""


def job_problem(job, run):
    if run["rounds"] != job["stop"]["rounds"]:
        return f"ran {run['rounds']} rounds, not {job['stop']['rounds']}"
    return None


def audit(cell, ds, run):
    why = job_problem(cell["job"], run)
    return {"problems": [why] if why else [],
            "primal": run["traj"].records[-1].primal}
'''


def test_added_files_are_picked_up(tmp_path):
    """A cell on a new configuration, generator, job and job check (a
    primal method, which no file of the benchmark runs) and two per-layer
    metrics, one with a reader of its own name and one through a
    parameter file: files and appended entries only."""
    bench = copy_benchmark(str(tmp_path), TINY)
    d = bench["_dir"]

    def write(kind, name, text):
        with open(os.path.join(d, kind, name), "w") as f:
            f.write(text if isinstance(text, str) else json.dumps(text))

    write("readers", "jobs_traced.py",
          "def read(trace, jobs, cell, scale=1):\n"
          "    return scale * len(jobs)\n")
    write("layer_metrics", "jobs_traced_x10.json",
          {"reader": "jobs_traced", "params": {"scale": 10}})
    write("checks", "round_budget.py", PRIMAL_CHECK)
    shutil.copy(os.path.join(d, "generators", "dense_planted.py"),
                os.path.join(d, "generators", "dense_again.py"))
    cfg = registry.load_json(os.path.join(d, "configs", "tiny_dense.json"))
    write("configs", "tiny_wide.json", {**cfg, "name": "tiny_wide", "d": 256,
                                        "generator": "dense_again"})
    write("jobs", "tiny_mbsgd_r10.json", {
        "entry": "run_sgd", "flags": "--justCoCoA=false --deviceLoop "
        "--numRounds=10 --debugIter=10", "params": {"num_rounds": 10},
        "debug": {"debug_iter": 10}, "check": "round_budget",
        "kwargs": {"local": False, "quiet": True, "device_loop": True},
        "stop": {"rule": "round_budget", "rounds": 10}})
    raw = registry.load_json(os.path.join(str(tmp_path), "BENCHMARK.json"))
    new = "tiny_wide.mbsgd_r10"
    raw["configs"].append({"name": "tiny_wide", "source": "tests",
                           "file": "chipbench/configs/tiny_wide.json",
                           "reduced": [], "why": "added"})
    raw["workloads"].append({"name": new, "config": "tiny_wide", "chips": 1,
                             "traffic": "tiny_mbsgd_r10", "why": "added"})
    for name in ("jobs_traced", "jobs_traced_x10"):
        raw["per_layer"].append({"name": name, "unit": "jobs",
                                 "better": "higher", "layer": "L5_entry",
                                 "source": "program_counter",
                                 "moves": "job_s", "workloads": [new]})
    with open(os.path.join(str(tmp_path), "BENCHMARK.json"), "w") as f:
        json.dump(raw, f)
    bench = registry.load_benchmark(str(tmp_path))
    cell = registry.resolve_cell(bench, new)
    assert cell["config"]["d"] == 256 and cell["job"]["stop"]["rounds"] == 10
    readers = registry.layer_readers(bench, new)
    assert [m["name"] for m, _, _ in readers][-2:] == ["jobs_traced",
                                                       "jobs_traced_x10"]
    assert [read(None, [{}, {}], cell, **params)
            for _, read, params in readers[-2:]] == [2, 20]
    assert "jobs_traced" not in [
        m["name"] for m, _, _ in registry.layer_readers(
            bench, "tiny_dense.cocoa_plus")]
    result = run.run_cell(bench, cell, seed=3, seconds=0.2, trace=False)
    assert result["correct"], result["detail"]["problems"]
    assert result["detail"]["jobs"][0]["rounds"] == 10
    assert result["detail"]["solver_path"] is None       # no SDCA kernel
    # the cell reports what BENCHMARK.json lists for it: no comm_rounds
    assert set(result["metrics"]) == {"job_s", "peak_hbm_gb", "setup_s"}


# --- the command refuses to run where it cannot measure ---------------------


def _run_command(cwd, env_extra):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    return subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_chip():
    proc = _run_command(ROOT, {})
    assert proc.returncode not in (0, None)
    assert "needs 1 TPU chip(s)" in proc.stderr
    assert "platform 'cpu'" in proc.stderr
    assert '"correct"' not in proc.stdout


def test_command_refuses_without_the_program(tmp_path):
    copy_benchmark(str(tmp_path))
    proc = _run_command(str(tmp_path), {})
    assert proc.returncode not in (0, None)
    assert "the program under test is not here" in proc.stderr
    assert '"correct"' not in proc.stdout


# --- the inner run_cell, at tiny shapes -------------------------------------


@pytest.mark.parametrize("name", TINY_CELLS)
def test_run_cell_gives_the_contract_line(tiny_bench, name):
    cell = registry.resolve_cell(tiny_bench, name)
    result = run.run_cell(tiny_bench, cell, seed=5, seconds=0.3, trace=False)
    detail = result.pop("detail")
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True, detail["problems"] + detail["failed"]
    assert result["attempted"] == len(detail["jobs"]) >= 1
    assert result["failed"] == 0
    wanted = {m["name"] for m in registry.metrics_of(tiny_bench,
                                                     "end_to_end", name)}
    assert set(result["metrics"]) == wanted
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"}
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    # a second job in the same process adds no compile: every shape the
    # window uses was warmed in set-up, and the program reuses its
    # executables
    assert detail["counters"]["compiles_in_window"] == 0, \
        detail["counters"]["window_compiles"]
    path = detail["solver_path"]
    assert path["kernel"] == "pallas" and path["interpret"] is True
    assert path["devices"] == cell["chips"]
    assert abs(detail["audit"]["gap"]
               - detail["audit"]["program"]["gap"]) < 5e-4
    # set-up is split into phases that add up to it
    phases = detail["setup_phases"]
    assert {"data_s", "warmup_job_s", "reference_check_s"} <= set(phases)
    assert sum(phases.values()) == pytest.approx(
        detail["end_to_end"]["setup_s"])
    json.dumps(result)          # the line is plain JSON


def test_same_seed_same_job(tiny_bench):
    cell = registry.resolve_cell(tiny_bench, "tiny_dense.cocoa_plus")
    audits = [run.run_cell(tiny_bench, cell, seed=s, seconds=0.05,
                           trace=False)["detail"]["audit"]
              for s in (7, 7, 8)]
    assert audits[0]["primal"] == audits[1]["primal"]
    assert audits[0]["primal"] != audits[2]["primal"]


def test_a_job_without_its_certificate_is_failed(tiny_bench):
    cell = registry.resolve_cell(tiny_bench, "tiny_dense.cocoa_plus")
    cell["job"]["params"]["num_rounds"] = 5      # too few for the target
    result = run.run_cell(tiny_bench, cell, seed=5, seconds=0.05, trace=False)
    assert result["correct"] is False
    assert result["failed"] == result["attempted"] >= 1
    assert "no certificate" in result["detail"]["failed"][0]


def test_a_cell_on_the_wrong_path_is_not_correct(tiny_bench):
    cell = registry.resolve_cell(tiny_bench, "tiny_dense.cocoa_plus")
    cell["job"]["kwargs"]["pallas"] = False      # the XLA fallback
    result = run.run_cell(tiny_bench, cell, seed=5, seconds=0.05, trace=False)
    assert result["correct"] is False and result["failed"] == 0
    assert "solver_path.kernel is 'fori'" in result["detail"]["problems"][0]


def test_traced_run_reports_layer_metrics_and_breakdown(tiny_bench,
                                                        monkeypatch):
    """The traced path end to end, with the one thing a CPU lacks — device
    planes in the profile — stood in for by a summary of two jobs."""
    def fake_summary(trace_dir):
        assert os.path.isdir(trace_dir)
        return reduce_trace.TraceSummary(
            window_s=1.0, n_devices=1, busy_s=0.6,
            ops={"kernel.1": 0.5, "fusion.2": 0.1}, collective_s=0.0,
            collective_exposed_s=0.0, launches=4.0,
            jobs=[dict(start_s=0.0, end_s=0.5, busy_s=0.3, launches=2.0),
                  dict(start_s=0.5, end_s=1.0, busy_s=0.3, launches=2.0)],
            gaps=[("job/call", 0.2), ("between jobs", 0.1)])

    monkeypatch.setattr(reduce_trace, "summarize_file", fake_summary)
    cell = registry.resolve_cell(tiny_bench, "tiny_dense.cocoa_plus")
    result = run.run_cell(tiny_bench, cell, seed=5, seconds=0.05, trace=True,
                          out_dir=tiny_bench["_root"])
    assert set(result["metrics"]) == {
        m["name"] for m in registry.metrics_of(tiny_bench, "per_layer",
                                               "tiny_dense.cocoa_plus")}
    assert result["device"]["busy_s"] == 0.6
    assert result["device"]["window_s"] == 1.0
    assert result["breakdown"] == {
        "device_ops": [["kernel.1", 0.5], ["fusion.2", 0.1]],
        "idle_gaps": [["job/call", 0.2], ["between jobs", 0.1]]}
    assert result["attempted"] >= run.TRACE_MIN_JOBS
