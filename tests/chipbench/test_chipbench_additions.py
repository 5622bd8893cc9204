"""The benchmark takes additions: with a configuration, a cell on it, the
cell's own block of per-layer entries, one generic per-layer entry and one
end-to-end entry APPENDED (in memory, as the driver takes them: at the end
of each list) and the cell appended to a shared entry's list, every older
cell's own test still holds; an entry pushed INSIDE an older cell's block,
an older cell dropped from an entry of its block or from a shared entry's
list, does not.

A cell's test module is found from the files: every
``tests/chipbench/test_chipbench_*.py`` that states a ``CELL`` is that
cell's, with the ``BLOCK``, ``SHARED`` and ``GENERIC`` it states beside it.
A new cell registers nowhere."""

import copy
import glob
import importlib
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, ROOT)
sys.path.insert(0, HERE)

from chipbench import registry  # noqa: E402
from owed import EVERY_CELL, GENERIC, check_cell  # noqa: E402

BENCH = registry.load_benchmark(ROOT)
CELL_RE = re.compile(r'^CELL = "([^"]+)"$', re.M)


def modules_of(directory):
    """``{cell: module name}`` of the test modules in ``directory`` that
    state a ``CELL``; two that state one cell are an error."""
    found = {}
    for path in sorted(glob.glob(os.path.join(directory,
                                              "test_chipbench_*.py"))):
        with open(path) as f:
            stated = CELL_RE.findall(f.read())
        for cell in stated:
            name = os.path.basename(path)[:-3]
            if cell in found:
                raise ValueError(f"{found[cell]} and {name} both state "
                                 f"CELL = {cell!r}")
            found[cell] = name
    return found


# the cells that brought a test module of their own
MODULES = modules_of(HERE)
# the three dense SVM cells came with the benchmark: no module and no block
# of one cell's own, the entries they share list all three
DENSE = ["epsilon.cocoa_plus", "epsilon.logistic", "imagenet.cocoa_plus.x4"]
DENSE_SHARED = EVERY_CELL + [
    "round_roofline", "fixed_init_s", "fixed_stage_s", "fixed_dispatch_s",
    "fixed_fetch_s", "fixed_unspanned_s", "local_solve_ms",
    "local_solve_roofline", "accel_jump_share", "indices_share"]
NEW_CELL = "added.cocoa_plus"
# what the new cell reads in common with older cells through a list
NEW_SHARED = ["local_solve_ms", "indices_share"]


def lists_of(cell):
    """``(block, generic, shared)`` as the cell's own test module passes
    them."""
    if cell in DENSE:
        return [], GENERIC, DENSE_SHARED
    mod = importlib.import_module(MODULES[cell])
    assert mod.CELL == cell
    return mod.BLOCK, mod.GENERIC, mod.SHARED


def with_a_block(least=1):
    """The cells whose module states a block of ``least`` entries or
    more."""
    return [c for c in sorted(MODULES) if len(lists_of(c)[0]) >= least]


def appended():
    """A copy of the benchmark with what a later PR brings: entries at the
    end of each list, its cell at the end of the shared lists it reads."""
    bench = copy.deepcopy(BENCH)
    bench["configs"].append({
        "name": "added", "source": "tests", "reduced": [], "why": "added",
        "file": "chipbench/configs/added.json"})
    bench["workloads"].append({
        "name": NEW_CELL, "config": "added", "chips": 1,
        "traffic": "cocoa_plus_gap1e-4", "why": "added"})
    for name in ("added_solve_roofline", "added_step_ns"):
        bench["per_layer"].append({
            "name": name, "unit": "ns", "better": "lower", "moves": "job_s",
            "source": "device_trace", "layer": "L2-L1_local_solve",
            "workloads": [NEW_CELL]})
    bench["per_layer"].append({
        "name": "added_everywhere", "unit": "s", "better": "lower",
        "source": "program_counter", "layer": "L5_entry", "moves": "setup_s"})
    for m in bench["per_layer"]:
        if m["name"] in NEW_SHARED:
            m["workloads"].append(NEW_CELL)
    bench["end_to_end"].append({
        "name": "added_p90_s", "unit": "s", "better": "lower", "bound": 0.03,
        "source": "host_clock", "workloads": [NEW_CELL]})
    return bench


def test_every_cell_has_a_module_or_came_with_the_benchmark():
    assert set(MODULES) | set(DENSE) == {w["name"]
                                         for w in BENCH["workloads"]}
    assert not set(MODULES) & set(DENSE)


def test_an_appended_cells_module_is_found_by_its_cell(tmp_path):
    """The next deployment's PR adds ``test_chipbench_<its own>.py`` that
    states its ``CELL``: nothing else names the file."""
    (tmp_path / "test_chipbench_added.py").write_text(
        f'import os\n\nCELL = "{NEW_CELL}"\nBLOCK = ["added_step_ns"]\n')
    (tmp_path / "test_chipbench_harnesslike.py").write_text(
        'CELLS = ["a.b"]\n\ndef test_nothing():\n    CELL = "not.at.top"\n')
    assert modules_of(str(tmp_path)) == {NEW_CELL: "test_chipbench_added"}
    (tmp_path / "test_chipbench_again.py").write_text(
        f'CELL = "{NEW_CELL}"\n')
    with pytest.raises(ValueError, match="both state CELL"):
        modules_of(str(tmp_path))


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_an_older_cells_test_holds_after_an_addition(cell):
    block, generic, shared = lists_of(cell)
    check_cell(BENCH, cell, block, generic, shared)
    bench = appended()
    check_cell(bench, cell, block, generic, shared)
    # and the new cell's own test would call the same function
    check_cell(bench, NEW_CELL, ["added_solve_roofline", "added_step_ns"],
               GENERIC + EVERY_CELL + ["added_everywhere"], NEW_SHARED,
               end_to_end=("job_s", "peak_hbm_gb", "setup_s", "added_p90_s"))


@pytest.mark.parametrize("cell", with_a_block(least=2))
def test_an_entry_inside_an_older_block_is_refused(cell):
    block, generic, shared = lists_of(cell)
    bench = appended()
    names = [m["name"] for m in bench["per_layer"]]
    at = names.index(block[0]) + 1
    bench["per_layer"].insert(at, bench["per_layer"].pop())
    with pytest.raises(AssertionError, match=r"^\(ii\) "):
        check_cell(bench, cell, block, generic, shared)


@pytest.mark.parametrize("cell", with_a_block())
def test_an_older_cell_dropped_from_its_block_is_refused(cell):
    block, generic, shared = lists_of(cell)
    bench = appended()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == block[-1]]
    entry["workloads"] = [NEW_CELL]
    with pytest.raises(AssertionError, match=r"^\(iii\) "):
        check_cell(bench, cell, block, generic, shared)


@pytest.mark.parametrize("cell", with_a_block())
def test_a_second_cell_written_into_an_older_block_is_refused(cell):
    """A block's entry lists exactly the cell it was written for: a later
    cell that reads the same thing with the same reader and parameters is
    no block entry but a shared one, and says so in its own module."""
    block, generic, shared = lists_of(cell)
    bench = appended()
    (entry,) = [m for m in bench["per_layer"] if m["name"] == block[0]]
    entry["workloads"] = [cell, NEW_CELL]
    with pytest.raises(AssertionError, match=r"^\(iii\) .* alone"):
        check_cell(bench, cell, block, generic, shared)


@pytest.mark.parametrize("cell", [w["name"] for w in BENCH["workloads"]])
def test_an_older_cell_dropped_from_a_shared_entry_is_refused(cell):
    """Every shared entry a cell's module names, one at a time: the cell
    taken off its list (or, where the entry has no ``workloads`` key, a
    list written that leaves the cell out) fails that cell's test."""
    block, generic, shared = lists_of(cell)
    assert shared
    for name in shared:
        bench = appended()
        (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
        entry["workloads"] = [c for c in entry.get(
            "workloads", [w["name"] for w in bench["workloads"]])
            if c != cell]
        with pytest.raises(AssertionError,
                           match=rf"^\(iii\) .*no longer owes \['{name}'\]"):
            check_cell(bench, cell, block, generic, shared)


def test_a_cell_or_a_configuration_twice_is_refused():
    bench = appended()
    bench["workloads"].append(dict(bench["workloads"][0]))
    with pytest.raises(ValueError):
        check_cell(bench, bench["workloads"][0]["name"], [], GENERIC)
