"""Mini-batch SDCA / dual coordinate descent (reference: MinibatchCD.scala).

Same skeleton as CoCoA but the local solver runs against a *frozen* w
(mode="frozen"; MinibatchCD.scala:104) and both the dual and primal updates
are scaled by β/(K·H) (MinibatchCD.scala:32,43,128).

Implemented as the ``mode="frozen"`` member of the shared SDCA family
driver (solvers/cocoa.py ``run_sdca_family``), which gives mini-batch CD
the same execution paths as CoCoA: fast-math margins decomposition, the
Pallas dense/sparse kernels, device-side chunked rounds (``scan_chunk``),
the fully device-resident loop (``device_loop``), gap-target early stop,
and checkpoint/resume.
"""

from __future__ import annotations

from typing import Optional

import jax

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import ShardedDataset
from cocoa_tpu.solvers.cocoa import _alg_config, run_sdca_family


def run_minibatch_cd(
    ds: ShardedDataset,
    params: Params,
    debug: DebugParams,
    mesh=None,
    test_ds: Optional[ShardedDataset] = None,
    rng: str = "reference",
    w_init: Optional[jax.Array] = None,
    alpha_init: Optional[jax.Array] = None,
    start_round: int = 1,
    quiet: bool = False,
    gap_target: Optional[float] = None,
    scan_chunk: int = 0,
    math: str = "exact",
    pallas=None,
    block_size: int = 0,
    block_chain=None,
    device_loop: bool = False,
    sampling: str = "auto",
    divergence_guard: str = "auto",
):
    """Train; returns (w, alpha, Trajectory)."""
    alg = _alg_config(params, ds.k, None, mode="frozen")
    return run_sdca_family(
        ds, params, debug, "Mini-batch CD", alg, mesh=mesh, test_ds=test_ds,
        rng=rng, w_init=w_init, alpha_init=alpha_init,
        start_round=start_round, quiet=quiet, gap_target=gap_target,
        scan_chunk=scan_chunk, math=math, pallas=pallas,
        block_size=block_size, block_chain=block_chain,
        device_loop=device_loop, sampling=sampling,
        divergence_guard=divergence_guard,
    )
