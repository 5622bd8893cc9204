"""Capture and summarize a device profiler trace of the hot rounds.

VERDICT r3 item 8: the roofline table (perf.py) ATTRIBUTES round time from
an analytic FLOP/byte model; this records what the hardware actually did.
``python benchmarks/trace.py`` runs a few chunks of the two flagship
configs — the fused block kernel at epsilon scale and the grouped sparse
kernel at rcv1 scale — under ``jax.profiler.trace``, parses the captured
Perfetto trace, and writes the per-op device-time table to
benchmarks/TRACE.md (the committed artifact).

The capture directory itself (hundreds of MB of .xplane.pb) is not
committed; TRACE.md carries the summarized table plus enough provenance
(device, config, date, total device time vs wall) to re-check the
latency-bound claim.
"""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from cocoa_tpu.utils import compile_cache

compile_cache.enable()   # persistent XLA cache: regen compiles once, ever

# the capture/summarize core moved to cocoa_tpu/telemetry/profiling.py so
# production runs (--profile) and this benchmark driver share ONE
# implementation; re-exported here for existing importers
from cocoa_tpu.telemetry.profiling import (  # noqa: E402,F401
    capture, device_table, parse_trace,
)


def main():
    import time

    import jax.numpy as jnp
    import numpy as np

    from cocoa_tpu.config import Params
    from cocoa_tpu.data.sharding import shard_dataset
    from cocoa_tpu.data.synth import synth_dense_sharded, synth_sparse
    from cocoa_tpu.ops.pallas_sdca import fold_rows
    from cocoa_tpu.ops.pallas_sparse import row_lengths
    from cocoa_tpu.solvers.base import IndexSampler
    from cocoa_tpu.solvers.cocoa import _alg_config, make_chunk_step

    out_root = os.environ.get("COCOA_TRACE_DIR", "/tmp/cocoa_traces")
    sections = []

    def chunked_runner(ds, params, k, n_rounds, rng="reference", **kw):
        alg = _alg_config(params, k, True)
        sampler = IndexSampler(rng, 0, params.local_iters,
                               ds.counts, device=True)
        step = make_chunk_step(None, params, k, alg, sampler=sampler,
                               math="fast", **kw)
        sa = ds.shard_arrays()
        if kw.get("pallas") and ds.layout == "dense":
            sa = {**sa, "X_folded": fold_rows(sa["X"])}
        if kw.get("pallas") and ds.layout == "sparse":
            sa = {**sa, "sp_row_len": row_lengths(sa["sp_values"])}
        spec = sampler.chunk_indices(1, n_rounds)

        def run():
            w = jnp.zeros(ds.num_features, jnp.float32)
            a = jnp.zeros((k, ds.n_shard), jnp.float32)
            w, a = step(w, a, spec, sa)
            return float(w.sum())

        run()  # compile OUTSIDE the trace
        return run

    # epsilon fused block round
    n, d, k = 400_000, 2000, 8
    eps = synth_dense_sharded(n, d, k, seed=0)
    p_eps = Params(n=n, num_rounds=400, local_iters=n // k // 10, lam=1e-3)
    # the shipped flagship mode: permuted sampling licenses the distinct
    # one-scatter-per-round fused path (docs/DESIGN.md §3b-iii) — the
    # license the production gate (run_sdca_family) checks, asserted here
    # so a config edit cannot silently profile an unsound path
    assert np.all(np.asarray(eps.counts) % p_eps.local_iters == 0), \
        "distinct fused path needs counts % H == 0 (one epoch per round)"
    run_eps = chunked_runner(eps, p_eps, k, 20, rng="permuted",
                             pallas=False, block=128,
                             block_chain="pallas", block_distinct=True)
    t0 = time.perf_counter()
    tdir = capture("epsilon_block128", run_eps, out_root)
    wall = time.perf_counter() - t0
    sections.append(("epsilon block128 (20 rounds, fused kernel, "
                     "permuted/distinct)", parse_trace(tdir), wall, 20))

    # rcv1 grouped sparse round
    n2, d2 = 20242, 47236
    data = synth_sparse(n2, d2, nnz_mean=75, seed=0)
    rc = shard_dataset(data, k=k, layout="sparse", dtype=jnp.float32)
    p_rc = Params(n=n2, num_rounds=1500, local_iters=n2 // k // 10, lam=1e-4)
    run_rc = chunked_runner(rc, p_rc, k, 50, pallas=True)
    t0 = time.perf_counter()
    tdir = capture("rcv1_sparse", run_rc, out_root)
    wall = time.perf_counter() - t0
    sections.append(("rcv1 sparse (50 rounds, grouped SMEM kernel)",
                     parse_trace(tdir), wall, 50))

    md = os.path.join(os.path.dirname(os.path.abspath(__file__)), "TRACE.md")
    import datetime

    with open(md, "w") as f:
        f.write(
            "# Device profiler traces — hot-round attribution\n\n"
            "Produced by `python benchmarks/trace.py` on the attached TPU "
            "(jax.profiler capture of a warm fixed-round chunk; compile "
            "excluded).  Hardware-counter companion to the analytic "
            "roofline in RESULTS.md: per-op total device time over the "
            "traced chunk, top ops first.  Caveat: a capture can "
            "emit overlapping op streams, so ABSOLUTE totals can "
            "double-count (~2x vs the slope-measured round times, which "
            "remain the ground truth); the per-op SHARES within a table "
            "are what this artifact pins.  Captured "
            f"{datetime.date.today().isoformat()}.\n")
        for title, tracks, wall, rounds in sections:
            rows, total_us = device_table(tracks)
            f.write(f"\n## {title}\n\n")
            f.write(f"wall {wall:.2f} s for {rounds} rounds; device-op "
                    f"time {total_us / 1e6:.3f} s "
                    f"({total_us / 1e3 / rounds:.2f} ms/round)\n\n")
            f.write("| op | device ms | ms/round | % of device time |\n")
            f.write("|---|---|---|---|\n")
            for track, name, us in rows:
                f.write(f"| `{name[:60]}` | {us / 1e3:.2f} | "
                        f"{us / 1e3 / rounds:.3f} | "
                        f"{100 * us / max(total_us, 1e-9):.1f}% |\n")
            if not rows:
                f.write("| (no device op track captured) | | | |\n")
                # keep the raw track names for debugging capture problems
                f.write("\ncaptured tracks: "
                        + ", ".join(sorted(tracks)) + "\n")
    print(f"wrote {md}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
