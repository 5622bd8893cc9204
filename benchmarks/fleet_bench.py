"""The fleet benchmark: models certified per second, fleet vs serial.

The headline claim of the --fleet path (docs/DESIGN.md §16): T
independent tenant problems (a log-spaced λ regularization path over T
distinct synthetic datasets — every tenant a DIFFERENT jit cache key on
the solo path) certify through ONE compiled vmapped round at ≥ 10× the
models-per-second of the same tenants run serially through the solo
device loop on CPU, from compile/dispatch amortization alone: the serial
control pays a fresh XLA compile per tenant (λ is baked into every solo
executable) plus a dispatch + fetch per super-block per tenant, while
the fleet pays one compile and one dispatch for everything.

    python benchmarks/fleet_bench.py                  # fleet + serial A/B
    python benchmarks/fleet_bench.py --fleet-only     # skip the serial control
    python benchmarks/fleet_bench.py --row=out.jsonl  # write the results row

Rounds and certified counts are backend-independent (the per-tenant
math is the solo math bit-for-bit in map mode and to float ulps in vmap
mode); the wallclock/speedup columns are CPU-measured and re-measured by
``--row`` runs.  tests/test_count_gates.py holds the fleet-only rounds and
full certification.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CONFIG = "fleet-256-synth"
# the canonical fleet workload: T tenants, n=128 x d=64 planted-separator
# problems, λ log-spaced over two decades, one 1e-2 certificate target
N, D, K, FRAC = 128, 64, 2, 0.25
LAM_LO, LAM_HI = 3e-3, 1e-1
GAP_TARGET = 1e-2
ROUNDS, CADENCE = 400, 20


def build(tenants: int):
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.fleet import build_fleet, synth_fleet_specs

    specs = synth_fleet_specs(tenants, n=N, d=D, lam_lo=LAM_LO,
                              lam_hi=LAM_HI, gap_target=GAP_TARGET)
    fleet = build_fleet(specs, k=K, local_iter_frac=FRAC)
    params = Params(n=0, num_rounds=ROUNDS, local_iters=fleet.local_iters,
                    gamma=1.0, loss="hinge")
    debug = DebugParams(debug_iter=CADENCE, seed=0, chkpt_iter=ROUNDS + 1,
                        chkpt_dir="")
    return fleet, params, debug


def run_fleet(fleet, params, debug, lane_exec: str):
    from cocoa_tpu.analysis import sanitize
    from cocoa_tpu.solvers.fleet import run_cocoa_fleet

    t0 = time.perf_counter()
    with sanitize.sanitizer(strict=False) as stats:
        res = run_cocoa_fleet(fleet, params, debug, plus=True,
                              drive_mode="plain", lane_exec=lane_exec,
                              quiet=True)
    wall = time.perf_counter() - t0
    return res, wall, stats.compile_count("run")


def run_serial(fleet, params, debug):
    """The same tenants through the solo device loop, one at a time —
    the per-tenant compile + per-block dispatch/fetch cost the fleet
    amortizes away.  (The per-tenant λ is part of every solo executable's
    cache key, so each tenant pays a fresh XLA compile — exactly the
    production cost of a λ-path sweep today.)"""
    import dataclasses

    from cocoa_tpu.solvers import run_cocoa

    t0 = time.perf_counter()
    certified = 0
    total_rounds = 0
    # jaxlint: allow=fleet-hygiene -- this serial tenant loop IS the
    # measured anti-pattern (the A/B control the fleet is gated against)
    for ti in range(fleet.t):
        ds = fleet.tenant_ds(ti)
        sp = dataclasses.replace(params, n=ds.n,
                                 lam=float(fleet.lams[ti]))
        _, _, traj = run_cocoa(ds, sp, debug, plus=True,
                               gap_target=GAP_TARGET, device_loop=True,
                               quiet=True)
        if traj.stopped == "target":
            certified += 1
        total_rounds += traj.records[-1].round if traj.records else ROUNDS
    wall = time.perf_counter() - t0
    return certified, total_rounds, wall


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tenants", type=int, default=256)
    ap.add_argument("--fleet-only", action="store_true",
                    help="skip the serial control")
    ap.add_argument("--lanes", default="vmap", choices=("vmap", "map"))
    ap.add_argument("--row", default=None,
                    help="write the benchmarks-results row here")
    args = ap.parse_args(argv)

    fleet, params, debug = build(args.tenants)
    res, fleet_wall, compiles = run_fleet(fleet, params, debug, args.lanes)
    certified = int(res.certified.sum())
    fleet_mps = certified / max(fleet_wall, 1e-9)
    print(f"fleet:  {certified}/{fleet.t} certified, "
          f"{res.rounds_run} rounds, {fleet_wall:.1f}s, "
          f"{fleet_mps:.2f} models/s, {compiles} compile(s)")

    row = {
        "config": CONFIG, "type": "fleet",
        "tenants": int(fleet.t), "certified": certified,
        "rounds": int(res.rounds_run),
        "gap": float(res.final_gap.max()),
        "stopped": "target" if certified == fleet.t else None,
        "gap_target": GAP_TARGET,
        "models_per_second": round(fleet_mps, 3),
        "wallclock_s": round(fleet_wall, 3),
        "compiles": int(compiles),
        "lam_lo": LAM_LO, "lam_hi": LAM_HI,
        "drive_mode": "plain", "lane_exec": args.lanes,
        "n": N, "d": D, "k": K,
        "device": "cpu",
    }
    if not args.fleet_only:
        s_cert, s_rounds, s_wall = run_serial(fleet, params, debug)
        serial_mps = s_cert / max(s_wall, 1e-9)
        row["serial_models_per_second"] = round(serial_mps, 3)
        row["speedup"] = round(fleet_mps / max(serial_mps, 1e-9), 2)
        print(f"serial: {s_cert}/{fleet.t} certified, {s_rounds} total "
              f"rounds, {s_wall:.1f}s, {serial_mps:.2f} models/s")
        print(f"speedup: {row['speedup']}x models/s "
              f"(fleet {fleet_mps:.2f} vs serial {serial_mps:.2f})")

    if args.row:
        with open(args.row, "w") as f:
            f.write(json.dumps(row) + "\n")
        from cocoa_tpu.telemetry import schema as tele_schema

        errs = tele_schema.check_file(args.row, kind="results")
        if errs:
            print(f"results row failed schema: {errs}", file=sys.stderr)
            return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
