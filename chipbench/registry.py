"""Find a cell's files by the names BENCHMARK.json gives them.

A later PR adds a cell, a configuration, a job, a per-layer metric, a
generator or a job check by adding files and appending entries to
BENCHMARK.json; nothing here holds a table of their names.

    workloads[].config   -> configs[].file            (sizes of the problem)
    workloads[].traffic  -> <bench>/jobs/<name>.json  (what one job runs)
    per_layer[].name     -> <bench>/readers/<name>.py, or the reader and
                            parameters <bench>/layer_metrics/<name>.json names
    config["generator"]  -> <bench>/generators/<name>.py
    job["check"]         -> <bench>/checks/<name>.py
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME_RE = re.compile(r"^[A-Za-z0-9][A-Za-z0-9_.-]{0,63}$")


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    bench["_root"] = root
    bench["_dir"] = os.path.join(root, bench["paths"][0])
    return bench


def _named(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise KeyError(f"BENCHMARK.json has no {what} named {name!r}; it has "
                   f"{[e['name'] for e in entries]}")


def resolve_cell(bench: dict, name: str) -> dict:
    """The cell ``name`` with its configuration and job files read in."""
    cell = dict(_named(bench["workloads"], name, "workload"))
    entry = _named(bench["configs"], cell["config"], "config")
    cell["config"] = {**load_json(os.path.join(bench["_root"], entry["file"])),
                      "name": entry["name"]}
    cell["job"] = {**load_json(os.path.join(bench["_dir"], "jobs",
                                            cell["traffic"] + ".json")),
                   "name": cell["traffic"]}
    return cell


def loss_of(cell: dict) -> str:
    """The loss a cell trains: the job's where it names one (``--loss``),
    else the configuration's."""
    return cell["job"].get("params", {}).get("loss", cell["config"]["loss"])


def metrics_of(bench: dict, kind: str, cell_name: str) -> list:
    """The ``end_to_end`` or ``per_layer`` entries that exist in a cell (an
    entry with no ``workloads`` key exists in every cell)."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def load_module(bench: dict, kind: str, name: str):
    """``<bench>/<kind>/<name>.py``, loaded from the file so that a copy of
    the benchmark elsewhere finds its own."""
    if not NAME_RE.match(name):
        raise ValueError(f"{kind} name {name!r} is not a plain name")
    path = os.path.join(bench["_dir"], kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{re.sub('[^A-Za-z0-9_]', '_', name)}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def layer_reader(bench: dict, metric: str) -> tuple:
    """A per-layer metric's reader function and its parameters: the reader
    of the metric's own name, unless ``layer_metrics/<metric>.json`` names
    another (one reader serving several metrics through ``params``)."""
    path = os.path.join(bench["_dir"], "layer_metrics", metric + ".json")
    spec = load_json(path) if os.path.exists(path) else {}
    mod = load_module(bench, "readers", spec.get("reader", metric))
    return mod.read, spec.get("params", {})


def layer_readers(bench: dict, cell_name: str) -> list:
    """``[(metric entry, reader function, its parameters)]`` for a cell."""
    return [(m, *layer_reader(bench, m["name"]))
            for m in metrics_of(bench, "per_layer", cell_name)]
