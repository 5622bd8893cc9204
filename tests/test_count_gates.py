"""Round counts to the certified gap, held where the tests are run.

A count (rounds to the certificate, tenants certified) is the same on a
CPU as on the chip, so it can be gated here; a time cannot, and none is.
Each case is one configuration run end to end: it must stop at its gap
target, in no more rounds than the committed count plus its tolerance.
The committed counts are the last ones recorded before the CPU stopwatch
harnesses went (PR 28); a change that needs more rounds than the bound
raises the number here deliberately, in the same PR.
"""

import importlib.util
import json
import os

import pytest

from _gang_worker import supervise_gang

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TESTS = os.path.join(ROOT, "tests")

_DEMO = ["--trainFile=" + os.path.join(ROOT, "data", "small_train.dat"),
         "--numFeatures=9947", "--numSplits=4", "--numRounds=600",
         "--debugIter=10", "--localIterFrac=0.1", "--lambda=0.001",
         "--layout=dense", "--math=fast", "--deviceLoop",
         "--gapTarget=1e-4", "--justCoCoA=true", "--quiet"]
# a real 2-process host-exchange CoCoA+ gang: round-keyed sampling and
# round-indexed join windows make its count exact
_GANG = ["--real=cocoa", "--numSplits=2", "--numRounds=400", "--debugIter=5",
         "--gapTarget=1e-4", "--lambda=0.01", "--rowsPerShard=64",
         "--numFeatures=32", "--localIters=16"]


def _run_cli(flags, tmp_path, monkeypatch):
    from cocoa_tpu import cli

    base = str(tmp_path / "traj")
    assert cli.main(flags + [f"--trajOut={base}"]) == 0
    with open(f"{base}.CoCoA+.jsonl") as f:
        last = json.loads(f.readlines()[-1])     # line 0 is the manifest
    return last["round"], last["stopped"]


def _run_gang(flags, tmp_path, monkeypatch):
    # the supervisor starts the workers with the ambient environment: they
    # need the repo and tests/ importable and use no virtual devices
    monkeypatch.setenv("PYTHONPATH", os.pathsep.join(
        p for p in (ROOT, TESTS, os.environ.get("PYTHONPATH", "")) if p))
    monkeypatch.setenv("XLA_FLAGS", " ".join(
        f for f in os.environ.get("XLA_FLAGS", "").split()
        if "host_platform_device_count" not in f))
    rc, records = supervise_gang(flags, events=str(tmp_path / "ev.jsonl"))
    assert rc == 0
    evals = [r for r in records if r.get("event") == "round_eval"]
    end = [r for r in records if r.get("event") == "run_end"]
    return evals[-1]["t"], end[-1]["stopped"]


def _run_fleet(flags, tmp_path, monkeypatch):
    """256 tenants (a log-spaced λ path) through the one compiled vmapped
    round, as benchmarks/fleet_bench.py builds them: every tenant must
    certify."""
    spec = importlib.util.spec_from_file_location(
        "fleet_bench", os.path.join(ROOT, "benchmarks", "fleet_bench.py"))
    fleet_bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fleet_bench)
    fleet, params, debug = fleet_bench.build(256)
    res, _, compiles = fleet_bench.run_fleet(fleet, params, debug, "vmap")
    assert compiles == 1
    certified = int(res.certified.sum())
    return int(res.rounds_run), ("target" if certified == fleet.t else
                                 f"{certified}/{fleet.t} certified")


@pytest.mark.parametrize("run, flags, committed_rounds, rounds_tol", [
    (_run_cli, _DEMO, 440, 0.15),
    (_run_cli, _DEMO + ["--rng=permuted"], 340, 0.15),
    (_run_gang, _GANG + ["--overlapComm=off", "--staleRounds=0"], 130, 0.15),
    (_run_gang, _GANG + ["--overlapComm=on", "--staleRounds=1"], 130, 0.15),
    (_run_fleet, None, 80, 0.25),
], ids=["demo-cocoa+", "demo-cocoa+(permuted)", "gang-cocoa+sync",
        "gang-cocoa+overlap-stale1", "fleet-256-synth"])
def test_rounds_to_certified_gap(run, flags, committed_rounds, rounds_tol,
                                 tmp_path, monkeypatch):
    rounds, stopped = run(flags, tmp_path, monkeypatch)
    assert stopped == "target"
    assert rounds <= int(committed_rounds * (1.0 + rounds_tol))
