"""The trace reducer and the cost model, on the CPU: a hand-built plane
with overlapping and nested ops, the small trace recorded on the chip
(``fixtures/``) against the numbers written down beside it, and the
FLOP/byte arithmetic against numbers worked out by hand."""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from chipbench import cost_model, reduce_trace, registry  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
US = 1_000_000      # picoseconds in a microsecond


def xspace(planes: dict) -> str:
    """A text-format XSpace: ``{plane: {line: [(name, start_us, dur_us)]}}``
    with every line starting at 1 ms on the trace's clock."""
    out = []
    for pi, (plane, lines) in enumerate(planes.items(), 1):
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        ids = {n: i for i, n in enumerate(names, 1)}
        body = []
        for li, (line, evs) in enumerate(lines.items(), 1):
            events = "".join(
                f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * US)} "
                f"duration_ps: {int(d * US)} }}\n" for n, s, d in evs)
            body.append(f'lines {{ id: {li} name: "{line}" '
                        f"timestamp_ns: 1000000\n{events}}}\n")
        meta = "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: '
                       f'"{n}" }} }}\n' for n, i in ids.items())
        out.append(f'planes {{ id: {pi} name: "{plane}"\n'
                   f"{''.join(body)}{meta}}}\n")
    return "".join(out)


def summarize(planes: dict) -> reduce_trace.TraceSummary:
    from jax.profiler import ProfileData

    return reduce_trace.summarize(ProfileData.from_text_proto(xspace(planes)))


HOST = {"python": [("window", 0, 1000), ("job", 0, 400), ("job/call", 0, 390),
                   ("job/wait", 390, 5), ("job/fetch", 395, 5),
                   ("job", 500, 400), ("job/call", 500, 395),
                   ("unrelated", 0, 2000)]}
# device 0: a while shell 100-300 us around two fusions with a 20 us hole,
# then, in the second job, a kernel and an all-reduce that half overlap
DEV0 = {"XLA Ops": [("while.1", 100, 200), ("fusion.1", 100, 80),
                    ("fusion.2", 200, 100), ("kernel.7", 600, 100),
                    ("all-reduce.3", 650, 100)],
        "XLA Modules": [("jit_run(1)", 90, 220), ("jit_run(1)", 590, 170)]}


def test_overlapping_and_nested_ops_are_counted_once():
    s = summarize({"/device:TPU:0": DEV0, "/host:CPU": HOST})
    assert s.n_devices == 1
    assert s.window_s == pytest.approx(1000e-6)
    # 100-300 (the shell covers its hole) and 600-750 (the overlap once)
    assert s.busy_s == pytest.approx(350e-6)
    assert s.idle_share == pytest.approx(0.65)
    # every busy instant belongs to exactly one op: the totals add up to it
    assert sum(s.ops.values()) == pytest.approx(350e-6)
    assert s.ops["kernel.7"] == pytest.approx(50e-6)     # 600-650
    assert s.ops["all-reduce.3"] == pytest.approx(100e-6)


def test_a_while_shell_keeps_only_its_self_time():
    s = summarize({"/device:TPU:0": DEV0, "/host:CPU": HOST})
    assert s.ops["while.1"] == pytest.approx(20e-6)      # the hole alone
    assert s.ops["fusion.1"] == pytest.approx(80e-6)
    assert s.ops["fusion.2"] == pytest.approx(100e-6)
    assert s.top_ops(1)[0][0] in ("fusion.2", "kernel.7", "all-reduce.3")
    assert "while.1" not in [n for n, _ in s.top_ops(3)]


def test_collective_time_and_the_exposed_part():
    s = summarize({"/device:TPU:0": DEV0, "/host:CPU": HOST})
    assert s.collective_s == pytest.approx(100e-6)
    assert s.collective_exposed_s == pytest.approx(50e-6)   # 700-750


def test_jobs_launches_and_labelled_gaps():
    s = summarize({"/device:TPU:0": DEV0, "/host:CPU": HOST})
    assert s.launches == 2
    assert [j["launches"] for j in s.jobs] == [1, 1]
    assert s.jobs[0]["busy_s"] == pytest.approx(200e-6)
    assert s.jobs[1]["busy_s"] == pytest.approx(150e-6)
    assert s.jobs[0]["end_s"] - s.jobs[0]["start_s"] == pytest.approx(400e-6)
    gaps = dict()
    for label, sec in s.gaps:
        gaps.setdefault(label, []).append(round(sec * 1e6))
    # idle 0-100, 300-600 and 750-1000: each takes the label of the
    # innermost span open where it starts — all three inside a job's call
    assert s.gaps[0] == ("job/call", pytest.approx(300e-6))
    assert sorted(gaps["job/call"]) == [100, 250, 300]
    assert sum(sec for _, sec in s.gaps) == pytest.approx(650e-6)


def test_devices_are_averaged_and_ops_outside_the_window_dropped():
    dev1 = {"XLA Ops": [("fusion.1", 100, 100), ("fusion.9", 1500, 100)],
            "XLA Modules": [("jit_run(1)", 90, 120)]}
    s = summarize({"/device:TPU:0": DEV0, "/device:TPU:1": dev1,
                   "/host:CPU": HOST})
    assert s.n_devices == 2
    assert s.busy_s == pytest.approx((350e-6 + 100e-6) / 2)
    assert "fusion.9" not in s.ops
    assert s.launches == pytest.approx(1.5)
    assert s.ops["fusion.1"] == pytest.approx((80e-6 + 100e-6) / 2)


def test_a_trace_without_device_ops_is_an_error():
    with pytest.raises(ValueError, match="no device operation"):
        summarize({"/host:CPU": HOST})


def test_without_a_window_span_the_ops_bound_the_window():
    s = summarize({"/device:TPU:0": DEV0})
    assert s.window_s == pytest.approx(650e-6)           # 100 .. 750
    assert s.jobs == [] and s.gaps[0][0] == "between jobs"


# --- the trace recorded on the chip -----------------------------------------

RECORDED = os.path.join(HERE, "fixtures", "epsilon_cocoa_plus.xplane.pb.gz")
with open(os.path.join(HERE, "fixtures", "epsilon_cocoa_plus.expected.json")
          ) as _f:
    EXPECTED = json.load(_f)


@pytest.fixture(scope="module")
def recorded():
    from jax.profiler import ProfileData

    with gzip.open(RECORDED, "rb") as f:
        return reduce_trace.summarize(
            ProfileData.from_serialized_xspace(f.read()))


@pytest.mark.parametrize("key", sorted(EXPECTED["summary"]))
def test_recorded_trace_reduces_to_the_recorded_numbers(recorded, key):
    want = EXPECTED["summary"][key]
    (top_op, top_op_s), (gap_label, gap_s) = recorded.top_ops(1)[0], \
        recorded.gaps[0]
    derived = {"idle_share": recorded.idle_share, "top_op": top_op,
               "top_op_s": top_op_s, "n_jobs": len(recorded.jobs),
               "job_busy_s": [j["busy_s"] for j in recorded.jobs],
               "job_launches": [j["launches"] for j in recorded.jobs],
               "longest_gap_label": gap_label, "longest_gap_s": gap_s}
    got = derived[key] if key in derived else getattr(recorded, key)
    assert got == (want if isinstance(want, str)
                   else pytest.approx(want, rel=1e-9))


@pytest.mark.parametrize("name", sorted(EXPECTED["metrics"]))
def test_readers_on_the_recorded_trace(recorded, name):
    """Each per-layer reader, fed the recorded trace and the jobs and cell
    of the run that recorded it, gives the value that run reported."""
    read, params = registry.layer_reader(BENCH, name)
    cell = {**registry.resolve_cell(BENCH, EXPECTED["cell"]),
            **EXPECTED["cell_state"]}
    got = read(recorded, EXPECTED["jobs"], cell, **params)
    assert got == pytest.approx(EXPECTED["metrics"][name], rel=1e-9)


# --- the cost model, against numbers worked out by hand ----------------------

V5E = dict(flops_per_s=197e12, hbm_bytes_per_s=819e9)
# K * H steps a round, each 6 d FLOPs (row.w, row.(w + sigma' dw), the
# axpy) over one row of d * itemsize bytes:
#   400,000 x 2,000, K=8, H=5,000: 40,000 steps x 12,000 = 4.8e8 FLOPs,
#     x 8,000 B = 3.2e8 B
#   32,751 x 160,000, K=8, H=409: 3,272 steps x 960,000 = 3.14112e9 FLOPs,
#     x 640,000 B = 2.09408e9 B
#   the first in bfloat16: the same FLOPs, half the bytes
ROUNDS = {
    "400000x2000.f32": (dict(d=2000, k=8, h=5000), 4.8e8, 3.2e8),
    "32751x160000.f32": (dict(d=160_000, k=8, h=409), 3.14112e9, 2.09408e9),
    "400000x2000.bf16": (dict(d=2000, k=8, h=5000, itemsize=2), 4.8e8,
                         1.6e8),
}


@pytest.mark.parametrize("shape", sorted(ROUNDS))
def test_cost_model_against_hand_computed_rounds(shape):
    kw, flops, hbm_bytes = ROUNDS[shape]
    model = cost_model.sdca_round(**kw)
    assert model == {"flops": flops, "hbm_bytes": hbm_bytes}
    floor = cost_model.round_floor_s(model, V5E)
    # 6 FLOPs per 4 bytes against 240 FLOPs per byte of machine balance
    assert floor["bound"] == "hbm"
    assert floor["floor_s"] == floor["hbm_s"] == pytest.approx(
        hbm_bytes / 819e9)
    assert floor["flop_s"] == pytest.approx(flops / 197e12)


def test_floor_divides_by_the_chips_and_names_the_larger_side():
    model = dict(flops=197e12, hbm_bytes=819e9 / 2)
    one = cost_model.round_floor_s(model, V5E)
    assert one == {"floor_s": 1.0, "flop_s": 1.0, "hbm_s": 0.5,
                   "bound": "flops"}
    four = cost_model.round_floor_s(model, V5E, 4)
    assert four["floor_s"] == pytest.approx(0.25)
    assert cost_model.peaks_for("TPU v5 lite")["flops_per_s"] == 197e12


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_round_floor_follows_from_the_cells_own_files(name):
    """``round_roofline``'s floor for each cell, from nothing but the
    cell's configuration file: the rows one round reads over the HBM
    bandwidth of the cell's chips — or nothing, off the path it models."""
    from chipbench.readers import round_roofline

    cell = registry.resolve_cell(BENCH, name)
    cfg = cell["config"]
    h = max(1, int(cfg["local_iter_frac"] * cfg["n"] / cfg["num_splits"]))
    state = dict(local_iters=h, device_kind="TPU v5 lite",
                 solver_path={**cell["job"].get("expect_path", {}),
                              "layout": cfg["layout"]})
    floor = round_roofline.floor_of({**cell, **state})
    expect = cell["job"].get("expect_path", {})
    if (cfg["layout"], expect.get("inner"), expect.get("kernel")) == (
            "dense", "sequential", "pallas"):
        import jax.numpy as jnp

        row_bytes = cfg["d"] * jnp.dtype(cfg["dtype"]).itemsize
        assert floor["hbm_s"] == pytest.approx(
            cfg["num_splits"] * h * row_bytes / (819e9 * cell["chips"]))
    else:
        assert floor is None
    assert round_roofline.floor_of(
        {**cell, **state, "solver_path": None}) is None
