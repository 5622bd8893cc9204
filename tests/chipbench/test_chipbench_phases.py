"""The program's phases in a trace (``chipbench/phases.py``) and the
per-layer readers built on them, on the CPU: a hand-built plane with two
host threads, overlapping spans and ops with and without a scope; the
trace PR 22 recorded, which holds no name of the program's and on which
every new reader gives nothing; and a second small trace recorded on the
chip after the program named its phases, against the numbers that run
printed."""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import phases, reduce_trace, registry  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
US = 1_000_000      # picoseconds in a microsecond
NEW = ["fixed_init_s", "fixed_stage_s", "fixed_dispatch_s", "fixed_fetch_s",
       "fixed_unspanned_s", "eval_share", "local_solve_ms",
       "local_solve_roofline", "unscoped_share", "accel_jump_share"]


def xspace(planes: dict, paths: dict = (), by_ref: tuple = ()) -> bytes:
    """A serialized XSpace: ``{plane: {line: [(name, start_us, dur_us)]}}``,
    every line starting at 1 ms on the trace's clock; ``paths`` gives an
    op's scope path, stored as the ``tf_op`` stat of its event metadata —
    a string, or for the ops in ``by_ref`` a reference to a stat
    metadata's name, the two forms the profiler writes."""
    from jax.profiler import ProfileData

    out = []
    for pi, (plane, lines) in enumerate(planes.items(), 1):
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        body, stat_meta = [], ['stat_metadata { key: 1 value { id: 1 name: '
                               '"tf_op" } }\n']
        for li, (line, evs) in enumerate(lines.items(), 1):
            events = "".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * US)} "
                f"duration_ps: {int(d * US)} }}\n" for n, s, d in evs)
            body.append(f'lines {{ id: {li} name: "{line}" '
                        f"timestamp_ns: 1000000\n{events}}}\n")
        meta = []
        for n, i in ids.items():
            stat = ""
            if n in dict(paths) and n in by_ref:
                ref = 100 + i
                stat_meta.append(f"stat_metadata {{ key: {ref} value {{ id: "
                                 f'{ref} name: "{paths[n]}" }} }}\n')
                stat = f"stats {{ metadata_id: 1 ref_value: {ref} }}"
            elif n in dict(paths):
                stat = f'stats {{ metadata_id: 1 str_value: "{paths[n]}" }}'
            meta.append(f"event_metadata {{ key: {i} value {{ id: {i} name: "
                        f'"%{n} = f32[8]{{0}} fusion(f32[8]{{0}} %p)" '
                        f'display_name: "{n}" {stat} }} }}\n'
                        if plane.startswith("/device") else
                        f"event_metadata {{ key: {i} value {{ id: {i} name: "
                        f'"{n}" }} }}\n')
        out.append(f'planes {{ id: {pi} name: "{plane}"\n{"".join(body)}'
                   f'{"".join(meta)}{"".join(stat_meta)}}}\n')
    return ProfileData.text_proto_to_serialized_xspace("".join(out))


# the driving thread: a 400 us job whose ladder is init 0-50, wait 50-100,
# local_solve 100-390 (dispatch 100-120, fetch 120-390), decode 390-395;
# the staging thread samples 40-95, overlapping init and wait
DRIVER = [("window", 0, 1000), ("job", 0, 400), ("job/call", 0, 398),
          ("cocoa/init_state", 0, 50), ("cocoa/wait_indices", 50, 50),
          ("cocoa/local_solve", 100, 290), ("cocoa/dispatch", 100, 20),
          ("cocoa/fetch", 120, 270), ("cocoa/decode_trajectory", 390, 5),
          ("job", 500, 400), ("cocoa/init_state", 500, 100),
          ("cocoa/local_solve", 600, 300), ("cocoa/fetch", 620, 280),
          ("unrelated", 0, 2000)]
STAGER = [("cocoa/stage_indices", 40, 55), ("cocoa/stage_indices", 480, 200)]
# device 0 is busy 110-300 in the first job and 650-850 in the second
OPS = [("while.1", 110, 190), ("kernel.7", 110, 100), ("copy.6", 210, 20),
       ("eval_fusion.9", 230, 60), ("psum.3", 290, 10),
       ("kernel.7", 650, 150), ("eval_fusion.9", 800, 50)]
PATHS = {
    "kernel.7": "jit(run)/while/body/while/body/closed_call/"
                "cocoa_local_solve/jit(pallas_sdca_round)/pallas_call:",
    "eval_fusion.9": "jit(run)/while/body/vmap(cocoa_eval)/dot_general:",
    "psum.3": "jit(run)/while/body/cocoa_dw_reduce/psum:",
    "while.1": "jit(run)/while:",
    # copy.6 carries no path at all: the compiler made it
}
PLANES = {"/device:TPU:0": {"XLA Ops": OPS,
                            "XLA Modules": [("jit_run(1)", 105, 200),
                                            ("jit_run(1)", 645, 210)]},
          "/host:CPU": {"python": DRIVER, "python ": STAGER}}


@pytest.fixture(scope="module")
def built():
    raw = xspace(PLANES, PATHS, by_ref=("psum.3",))
    from jax.profiler import ProfileData

    return phases.read_bytes(raw), reduce_trace.summarize(
        ProfileData.from_serialized_xspace(raw))


def test_every_idle_instant_goes_to_the_innermost_span_of_the_jobs_thread(
        built):
    ph, _ = built
    assert ph.spanned and len(ph.jobs) == 2
    first, second = (j["by_span"] for j in ph.jobs)
    # idle 0-110 and 300-400: init 50, wait 50, dispatch 100-110 (inside
    # local_solve, the inner span wins), fetch 300-390, decode 390-395,
    # nothing 395-400.  The staging thread's span labels no instant.
    assert first == pytest.approx({
        "init_state": 50e-6, "wait_indices": 50e-6, "dispatch": 10e-6,
        "fetch": 90e-6, "decode_trajectory": 5e-6, None: 5e-6})
    assert "stage_indices" not in first and "stage_indices" not in second
    # idle 500-650 and 850-900: init 100, local_solve's own 600-620, fetch
    assert second == pytest.approx({
        "init_state": 100e-6, "local_solve": 20e-6, "fetch": 80e-6})
    for job, want in zip(ph.jobs, (210e-6, 200e-6)):
        assert job["idle_s"] == pytest.approx(want)
        assert sum(job["by_span"].values()) == pytest.approx(want)
    # both threads' spans are there for whoever wants them
    assert sorted(len(line) for line in ph.spans) == [2, 11]


def test_an_op_belongs_to_the_innermost_program_scope_of_its_path(built):
    ph, trace = built
    assert ph.scoped
    want = {"kernel.7": "cocoa_local_solve", "eval_fusion.9": "cocoa_eval",
            "psum.3": "cocoa_dw_reduce",        # stored by reference
            "while.1": None, "copy.6": None}
    assert {op: ph.scopes[op] for op in want} == want
    by_scope = phases.scope_seconds(ph, trace.ops)
    assert by_scope == pytest.approx({
        "cocoa_local_solve": 250e-6, "cocoa_eval": 110e-6,
        "cocoa_dw_reduce": 10e-6, None: 20e-6})      # the shell adds 0
    assert sum(by_scope.values()) == pytest.approx(trace.busy_s)


@pytest.mark.parametrize("path, scope", [
    ("jit(run)/while/body/cocoa_eval/mul:", "cocoa_eval"),
    ("jit(run)/while/body/vmap(cocoa_local_solve)/while/body/add",
     "cocoa_local_solve"),
    ("jit(run)/shard_map/transpose(jvp(cocoa_dw_reduce))/psum:",
     "cocoa_dw_reduce"),
    ("jit(run)/cocoa_eval/while/body/cocoa_indices/iota", "cocoa_indices"),
    ("jit(cocoa_not_a_scope)/add:", None),
    ("jit(run)/while/body/vmap()/dot_general:", None),
    ("", None),
])
def test_scope_of_a_path(path, scope):
    assert phases.scope_of(path) == scope


def in_out(tmp_path, monkeypatch, cell_name: str, raw: bytes):
    """``raw`` where ``run.py`` would have left the cell's trace."""
    d = tmp_path / "out" / f"{cell_name}.trace" / "plugins" / "profile" / "t"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(raw)
    monkeypatch.setattr(phases, "OUT", str(tmp_path / "out"))
    phases.read_file.cache_clear()


def test_readers_on_the_built_trace(built, tmp_path, monkeypatch):
    _, trace = built
    in_out(tmp_path, monkeypatch, "epsilon.cocoa_plus",
           xspace(PLANES, PATHS, by_ref=("psum.3",)))
    cell = {**registry.resolve_cell(BENCH, "epsilon.cocoa_plus"),
            "local_iters": 5000, "device_kind": "TPU v5 lite",
            "solver_path": {"inner": "sequential", "kernel": "pallas"}}
    jobs = [{"wall_s": 400e-6, "rounds": 2}, {"wall_s": 400e-6, "rounds": 3}]
    got = {name: read(trace, jobs, cell, **params) for name in NEW
           for read, params in [registry.layer_reader(BENCH, name)]}
    assert got["fixed_init_s"] == pytest.approx(75e-6)       # median of 2
    assert got["fixed_stage_s"] == pytest.approx(25e-6)
    assert got["fixed_dispatch_s"] == pytest.approx(5e-6)
    assert got["fixed_fetch_s"] == pytest.approx(85e-6)
    fixed = registry.layer_reader(BENCH, "fixed_s")[0](trace, jobs, cell)
    assert fixed == pytest.approx(205e-6)
    assert sum(got[n] for n in NEW[:5]) == pytest.approx(fixed, abs=1e-12)
    assert got["eval_share"] == pytest.approx(100 * 110 / 390)
    assert got["unscoped_share"] == pytest.approx(100 * 20 / 390)
    assert got["accel_jump_share"] == 0.0        # scoped trace, no jump ran
    assert got["local_solve_ms"] == pytest.approx(1e3 * 250e-6 / 5)
    # 40,000 rows of 8 KB a round at 819 GB/s over 50 us a round
    assert got["local_solve_roofline"] == pytest.approx(
        100 * (3.2e8 / 819e9) / 50e-6)
    # off the path the cost model counts there is no floor to compare with
    off = {**cell, "solver_path": {"inner": "block", "kernel": "pallas"}}
    read, params = registry.layer_reader(BENCH, "local_solve_roofline")
    assert read(trace, jobs, off, **params) is None


def test_a_cell_that_left_no_trace_reads_nothing(tmp_path, monkeypatch):
    monkeypatch.setattr(phases, "OUT", str(tmp_path / "nothing"))
    assert phases.load({"name": "epsilon.cocoa_plus"}) is None


# --- the traces recorded on the chip ----------------------------------------


def recorded(stem: str, tmp_path, monkeypatch):
    with open(os.path.join(HERE, "fixtures", stem + ".expected.json")) as f:
        expected = json.load(f)
    with gzip.open(os.path.join(HERE, "fixtures", stem + ".xplane.pb.gz"),
                   "rb") as f:
        raw = f.read()
    in_out(tmp_path, monkeypatch, expected["cell"], raw)
    from jax.profiler import ProfileData

    trace = reduce_trace.summarize(ProfileData.from_serialized_xspace(raw))
    cell = {**registry.resolve_cell(BENCH, expected["cell"]),
            **expected["cell_state"]}
    return expected, trace, cell


@pytest.mark.parametrize("name", NEW)
def test_new_readers_give_nothing_on_the_trace_of_a_program_without_names(
        name, tmp_path, monkeypatch):
    """PR 22's trace: no ``cocoa/`` span, no ``cocoa_`` scope.  The parent
    of the PR that brought them reads the same way."""
    expected, trace, cell = recorded("epsilon_cocoa_plus", tmp_path,
                                     monkeypatch)
    ph = phases.load(cell)
    assert not ph.spanned and not ph.scoped and len(ph.jobs) == 2
    assert ph.scopes["pallas_sdca_round.6"] is None
    read, params = registry.layer_reader(BENCH, name)
    assert read(trace, expected["jobs"], cell, **params) is None


def test_the_scope_path_is_read_from_the_recorded_file(tmp_path,
                                                       monkeypatch):
    """The wire-format read on a file the chip's profiler wrote."""
    with gzip.open(os.path.join(HERE, "fixtures",
                                "epsilon_cocoa_plus.xplane.pb.gz")) as f:
        paths = phases.op_paths(f.read())
    assert paths["pallas_sdca_round.6"] == (
        "jit(run)/while/body/while/body/closed_call/"
        "jit(pallas_sdca_round)/pallas_call:")
    assert paths["multiply_reduce_fusion.9"] == \
        "jit(run)/while/body/vmap()/dot_general:"
    assert paths["while.174"] == ""              # a shell has none


PHASES = "epsilon_cocoa_plus_phases"
with open(os.path.join(HERE, "fixtures", PHASES + ".expected.json")) as _f:
    PHASES_EXPECTED = json.load(_f)


@pytest.mark.parametrize("name", sorted(PHASES_EXPECTED["metrics"]))
def test_readers_on_the_trace_recorded_after_the_change(name, tmp_path,
                                                        monkeypatch):
    """Every per-layer reader, old and new, fed the trace a chip run of the
    program with its phases named left behind, gives what that run
    printed."""
    expected, trace, cell = recorded(PHASES, tmp_path, monkeypatch)
    read, params = registry.layer_reader(BENCH, name)
    got = read(trace, expected["jobs"], cell, **params)
    assert got == pytest.approx(expected["metrics"][name], rel=1e-9,
                                abs=1e-12)


def test_recorded_parts_add_up(tmp_path, monkeypatch):
    expected, trace, cell = recorded(PHASES, tmp_path, monkeypatch)
    m = expected["metrics"]
    assert set(NEW) <= set(m)
    assert sum(m[n] for n in NEW[:5]) == pytest.approx(m["fixed_s"],
                                                       abs=1e-6)
    ph = phases.load(cell)
    assert ph.spanned and ph.scoped
    by_scope = phases.scope_seconds(ph, trace.ops)
    assert sum(by_scope.values()) == pytest.approx(trace.busy_s, rel=1e-9)
    assert set(by_scope) >= {"cocoa_local_solve", "cocoa_eval", None}
    assert 0 < m["local_solve_roofline"] <= 100
