"""What a cell's own test holds of ``BENCHMARK.json``: the cell's block of
per-layer entries where it stands, and what the cell owes AT LEAST.

The driver takes a new entry only at the end of a list, so whatever a
test says of the END of ``workloads``, ``configs`` or ``per_layer``, or of
the WHOLE of what a cell owes, is broken by the next PR that appends a
cell, a configuration or a metric.  A cell's test calls ``check_cell``
instead; it says nothing of what follows the block or of what else the
cell owes (``test_chipbench_additions.py`` appends and proves it).

One name a reading (PR 55): no two entries of ``per_layer`` resolve to the
same ``(reader, params)``.  A cell's ``block`` is what ONLY it reads (its
own kernels' readers); a reading it has in common with another cell it
reads under that reading's one entry, by standing in the entry's
``workloads`` (``shared``) or because the entry has no such key and every
cell owes it (``EVERY_CELL``, ``GENERIC``).  A test module that states a
``CELL`` states ``BLOCK``, ``SHARED`` and ``GENERIC`` beside it:
``test_chipbench_additions.py`` finds it by that name."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import registry  # noqa: E402

END_TO_END = ("job_s", "peak_hbm_gb", "setup_s")
# the per-layer entries with no ``workloads`` key that every cell has owed
# since the benchmark began
GENERIC = ["device_idle_share", "fixed_s", "launches_per_job", "round_ms",
           "top_op_share", "compile_s", "compiles_in_window"]
# ``peak_hbm_gb`` and ``setup_s`` by part (``readers/cold_account.py``)
COLD = ["cold_layout_s", "cold_build_s", "cold_job_s", "hbm_entry_gb",
        "hbm_rise_layout_gb", "hbm_rise_job_gb", "hbm_rise_after_gb",
        "hbm_resident_gb", "hbm_program_temp_gb"]
# the readings whose merged entries list every cell and so carry no
# ``workloads`` key since PR 55: a later cell owes them for nothing
EVERY_CELL = ["eval_share", "unscoped_share"] + COLD


def check_cell(bench, cell, block, generic, shared=(), end_to_end=END_TO_END):
    """``cell`` of ``bench`` stands as its PR wrote it:

    (i)   the cell and its configuration are there exactly once;
    (ii)  ``block``, the per-layer entries only this cell reads, stands
          contiguously and in order from wherever its first name is;
    (iii) the cell owes at least ``block + generic + shared`` per layer and
          at least ``end_to_end``; every entry of ``block`` lists the cell
          alone, as when it was written; an entry of ``shared`` lists the
          cell among others, or has no ``workloads`` key."""
    (work,) = [w for w in bench["workloads"] if w["name"] == cell]
    (_,) = [c for c in bench["configs"] if c["name"] == work["config"]]
    block = list(block)
    names = [m["name"] for m in bench["per_layer"]]
    if block:
        at = names.index(block[0])
        assert names[at:at + len(block)] == block, \
            f"(ii) {cell}'s block does not stand whole at {at}: " \
            f"{names[at:at + len(block)]}"
    owed = {m["name"] for m in registry.metrics_of(bench, "per_layer", cell)}
    wanted = set(block) | set(generic) | set(shared)
    assert wanted <= owed, f"(iii) {cell} no longer owes {sorted(wanted - owed)}"
    reported = {m["name"] for m in registry.metrics_of(bench, "end_to_end",
                                                       cell)}
    assert set(end_to_end) <= reported, \
        f"(iii) {cell} no longer reports {sorted(set(end_to_end) - reported)}"
    for m in bench["per_layer"]:
        if m["name"] in block:
            assert m.get("workloads") == [cell], \
                f"(iii) {m['name']} lists {m.get('workloads')}, not {cell} alone"
