"""Fleet training: thousands of tenant models through one compiled round.

The solo path pays a full compile + dispatch + eval round-trip per
problem; a regularization-path sweep or a per-tenant model fleet pays it
T times.  This module runs the whole fleet as ONE vmapped drive* ladder
(solvers/base.py ``drive_fleet_on_device``): per-tenant λ·n and σ′ enter
the SAME local-SDCA kernels the solo path runs — as traced scalars
instead of baked-in constants — so one executable serves every tenant,
every σ′ stage, and every round, and the per-tenant duality-gap
certificate stays the solo certificate evaluated lane-wise.

Three drive modes (the fleet mirror of the solo ladder):

- ``plain``  — fixed σ′ (the safe K·γ, or an explicit override);
- ``anneal`` — the per-tenant σ′ schedule: each tenant's sched leaf
  carries its own stage/stall/best, and σ′ = levels[stage_t] is read
  from the static ladder as DATA (a vmapped ``lax.switch`` would
  execute every branch for every lane — docs/DESIGN.md §16);
- ``accel``  — the per-tenant secant (Anderson-1) outer loop: each
  tenant banks its own dual windows, arms and takes its own jumps, and
  restarts on its own gap rises (fixed-Θ; the adaptive-Θ ladder slices
  static index-table widths and stays solo-only).

A T=1 fleet run is bit-identical to the solo path in all three modes
(pinned by tests/test_fleet.py); a certified tenant's (w, α) is
bitwise-frozen from its certifying eval while the rest of the fleet
trains on (the masking contract, solvers/base.py).
"""

from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.fleet import FleetDataset
from cocoa_tpu.evals import objectives
from cocoa_tpu.ops.local_sdca import local_sdca, local_sdca_fast
from cocoa_tpu.solvers import base
from cocoa_tpu.solvers.cocoa import secant_jump
from cocoa_tpu.telemetry import tracing as _tracing

DRIVE_MODES = ("plain", "anneal", "accel")


@dataclasses.dataclass
class FleetResult:
    """One fleet run's outcome, per tenant and aggregate."""

    algorithm: str
    tenants: list                 # T tenant ids
    certified: np.ndarray         # (T,) bool — gap target reached
    stalled: np.ndarray           # (T,) bool — divergence watch fired
    cert_round: np.ndarray        # (T,) int — certifying round, 0 = never
    final_primal: np.ndarray      # (T,)
    final_gap: np.ndarray         # (T,)
    rounds_run: int               # rounds the loop actually executed
    evals: int
    wall_s: float                 # dispatch-to-fetch wall-clock
    w: "jax.Array"                # (T, d) final primal iterates
    alpha: "jax.Array"            # (T, K, n_shard) final duals
    traj: np.ndarray              # (evals, T, base.FLEET_N_COLS)

    @property
    def models_per_second(self) -> float:
        return float(self.certified.sum()) / max(self.wall_s, 1e-9)


def _tenant_chunk_parts(params: Params, mode: str, scaling: float,
                        math: str):
    """The per-shard update + driver apply with TRACED λ·n / σ′ — the
    fleet twin of ``solvers/cocoa._sdca_round_parts`` (exact/fast math
    only; the Pallas and block kernels own their shard axes and cannot
    ride the tenant vmap).  Returns ``make(lam_n, sigma) ->
    (per_shard, apply_fn)`` so the vmapped kernel can close over its
    lane's scalars."""
    if math not in ("exact", "fast"):
        raise ValueError(f"fleet math must be 'exact' or 'fast', got "
                         f"{math!r}")

    def make(lam_n, sigma):
        def apply_fn(w, dw_sum, x=None):
            return w + scaling * dw_sum

        if math == "exact":
            @jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
            def per_shard(w, alpha_k, idxs_k, shard_k):
                da, dw = local_sdca(
                    w, alpha_k, shard_k, idxs_k, 0.0, 0, mode=mode,
                    sigma=sigma, loss=params.loss,
                    smoothing=params.smoothing, lam_n=lam_n)
                return dw, alpha_k + scaling * da
        else:
            from cocoa_tpu.ops.rows import shard_margins

            @jax.named_scope(_tracing.SCOPE_LOCAL_SOLVE)
            def per_shard(w, alpha_k, idxs_k, shard_k):
                m0 = shard_margins(w, shard_k)
                da, dw = local_sdca_fast(
                    m0, alpha_k, shard_k, idxs_k, 0.0, 0,
                    jnp.zeros_like(w), mode=mode, sigma=sigma,
                    loss=params.loss, smoothing=params.smoothing,
                    lam_n=lam_n)
                return dw, alpha_k + scaling * da
        return per_shard, apply_fn

    return make


def run_cocoa_fleet(
    fleet: FleetDataset,
    params: Params,
    debug: DebugParams,
    plus: bool = True,
    drive_mode: str = "plain",
    rng: str = "reference",
    math: str = "exact",
    lane_exec: str = "vmap",
    quiet: bool = False,
    divergence_guard: str = "auto",
    start_round: int = 1,
) -> FleetResult:
    """Train every tenant of ``fleet`` through one compiled vmapped
    round loop.  ``params.lam`` is ignored — λ is per-tenant
    (``fleet.lams``); ``params.local_iters`` must equal the fleet's
    common H.  ``debug.debug_iter`` is the eval/chunk cadence and must
    divide ``params.num_rounds`` (the fleet loop has no sub-cadence
    tail).  Returns a :class:`FleetResult`; also emits the typed
    ``fleet_progress`` / ``tenant_certified`` events when the telemetry
    bus is active."""
    from cocoa_tpu.parallel.fanout import chunk_fanout
    from cocoa_tpu.telemetry import events as _tele

    if drive_mode not in DRIVE_MODES:
        raise ValueError(f"fleet drive mode must be one of {DRIVE_MODES}, "
                         f"got {drive_mode!r}")
    if lane_exec not in ("vmap", "map"):
        raise ValueError(f"fleet lane_exec must be vmap|map, got "
                         f"{lane_exec!r}")
    c = debug.debug_iter
    if c <= 0:
        raise ValueError("the fleet loop requires debugIter > 0 (the eval "
                         "cadence is its chunk axis)")
    if params.num_rounds % c != 0:
        raise ValueError(
            f"fleet numRounds ({params.num_rounds}) must be a multiple of "
            f"debugIter ({c}) — the vmapped loop has no sub-cadence tail")
    if params.local_iters != fleet.local_iters:
        raise ValueError(
            f"params.local_iters ({params.local_iters}) disagrees with "
            f"the fleet's common H ({fleet.local_iters})")
    t_fleet, k, h = fleet.t, fleet.k, fleet.local_iters
    dtype = fleet.dtype
    mode = "plus" if plus else "cocoa"
    name = ("CoCoA+" if plus else "CoCoA") + " fleet"
    scaling = params.gamma if plus else params.beta / k
    safe = k * params.gamma
    sigma_fixed = safe
    if params.sigma is not None and params.sigma != "auto":
        sigma_fixed = float(params.sigma)

    # jaxlint: allow=f64 -- host-side EXACT per-tenant scalar staging:
    # float32(float64(λ)·n) is bitwise the value the solo kernels bake
    # in as a constant, which is what the T=1 ≡ solo pin rests on
    lam_n64 = fleet.lams.astype(np.float64) * fleet.n.astype(np.float64)
    scal = {
        "lam_n": jnp.asarray(lam_n64.astype(np.float32)),
        "lam": jnp.asarray(fleet.lams.astype(np.dtype(dtype))),
        # the eval's /n as the f32 reciprocal the solo jit folds it into
        # (eval_metrics inv_n contract — bit-identity with the solo
        # certificate)
        "inv_n": jnp.asarray(np.float32(1.0)
                             / fleet.n.astype(np.float32)),
        # the accel jump's 1/(λn), host-f64 then cast — exactly the
        # constant the solo loop kernel bakes in
        "inv_lam_n": jnp.asarray((1.0 / lam_n64).astype(np.float32)),
    }
    tgts_np = np.where(np.isnan(fleet.gap_targets), -np.inf,
                       fleet.gap_targets).astype(np.dtype(dtype))
    gap_targets = jnp.asarray(tgts_np)
    has_targets = bool(np.all(np.isfinite(tgts_np)))

    levels = None
    n_stages = 0
    if drive_mode == "anneal":
        if not has_targets:
            raise ValueError(
                "fleet drive_mode='anneal' needs a gap target for every "
                "tenant (the backoff rides the per-tenant stall watch, "
                "which runs on the gap-target path)")
        start = (sigma_fixed if sigma_fixed < safe else safe / 2.0)
        levels = base.anneal_levels(start, safe)
        n_stages = len(levels)
    if drive_mode == "accel" and not has_targets:
        raise ValueError(
            "fleet drive_mode='accel' needs a gap target for every tenant "
            "(the momentum restart rule monitors each lane's gap)")
    guard_on = (n_stages > 1) or base.resolve_divergence_guard(
        divergence_guard, mode, sigma_fixed, k, params.gamma)

    # --- index tables: host-sampled, shared across tenants whenever the
    # per-tenant (seed, counts) streams coincide (equal-sized tenants —
    # the common fleet shape); otherwise stacked per tenant on axis 2
    n_chunks = params.num_rounds // c
    counts0 = fleet.counts[0]
    shared_tables = bool(np.all(fleet.counts == counts0[None]))
    per_round_ints = (1 if shared_tables else t_fleet) * k * h
    table_bytes = 4 * params.num_rounds * per_round_ints
    if table_bytes > base.MAX_IDX_TABLE_BYTES:
        raise ValueError(
            f"fleet index tables would need {table_bytes >> 20} MiB "
            f"(> {base.MAX_IDX_TABLE_BYTES >> 20} MiB): lower numRounds "
            f"or localIterFrac, or split the fleet")

    def tenant_tables(counts):
        sampler = base.IndexSampler(rng, debug.seed, h, counts)
        tab = sampler.chunk_indices(start_round, params.num_rounds)
        return np.asarray(tab).reshape(n_chunks, c, k, h)

    if shared_tables:
        idxs_all = jnp.asarray(tenant_tables(counts0))
        per_tenant_idxs = False
    else:
        stacked = np.stack([tenant_tables(fleet.counts[ti])
                            for ti in range(t_fleet)], axis=2)
        idxs_all = jnp.asarray(stacked)    # (n_chunks, C, T, K, H)
        per_tenant_idxs = True

    # --- the per-tenant kernels (vmapped by the driver) ----------------
    # σ′ stays a STATIC per-branch constant, exactly as on the solo path:
    # the per-stage lax.switch grows a leading T axis under the driver's
    # vmap (a batched branch index runs every branch and selects per
    # lane — each branch is then the bit-stable batched fixed-σ′ kernel,
    # so an anneal fleet lane is bit-identical to the solo branch it
    # selects).  λ·n is the one traced scalar (local_sdca's lam_n
    # contract).
    make_parts = _tenant_chunk_parts(params, mode, scaling, math)

    def run_chunk(w, alpha, idxs_ckh, data, lam_n, sigma):
        per_shard, apply_fn = make_parts(lam_n, sigma)
        return chunk_fanout(None, per_shard, apply_fn, w, alpha,
                            idxs_ckh, data)

    if drive_mode == "plain":
        def chunk_kernel(state, idxs_ckh, data, scal_t):
            w, alpha = run_chunk(state[0], state[1], idxs_ckh, data,
                                 scal_t["lam_n"], sigma_fixed)
            return (w, alpha)

        state0 = ()
    elif drive_mode == "anneal":
        branches = [
            (lambda w, a, idxs, data, lam_n, lv=lv:
             run_chunk(w, a, idxs, data, lam_n, lv))
            for lv in levels
        ]

        def chunk_kernel(state, idxs_ckh, data, scal_t):
            w, alpha, sched = state
            c_len = idxs_ckh.shape[0]
            br = jnp.clip(sched[0].astype(jnp.int32), 0, n_stages - 1)
            w2, a2 = jax.lax.switch(br, branches, w, alpha, idxs_ckh,
                                    data, scal_t["lam_n"])
            return (w2, a2, sched.at[4].add(jnp.float32(c_len)))

        state0 = (np.tile(
            base.sched_init_values(start_round)[None], (t_fleet, 1)),)
    else:   # accel
        def chunk_kernel(state, idxs_ckh, data, scal_t):
            w, alpha, hist, sched = state
            c_len = idxs_ckh.shape[0]
            w2, a2 = run_chunk(w, alpha, idxs_ckh, data, scal_t["lam_n"],
                               sigma_fixed)
            return (w2, a2, hist, sched.at[4].add(jnp.float32(c_len)))

        def jump_kernel(state, data, scal_t):
            # the solo loop's chunk-head secant jump, lane-local (run
            # through lax.map by the driver so its einsums lower exactly
            # as the solo executable's — base._build_fleet_run)
            w, alpha, hist, sched = state
            w, alpha = jax.lax.cond(
                sched[base.A_JUMP] > 0,
                lambda w, a: secant_jump(w, a, hist, data, None,
                                         scal_t["inv_lam_n"]),
                lambda w, a: (w, a), w, alpha)
            return (w, alpha, hist,
                    sched.at[base.A_JUMP].set(jnp.float32(0)))

        state0 = (
            np.zeros((t_fleet, 2, k, fleet.n_shard), np.dtype(dtype)),
            np.tile(base.sched_init_values(
                start_round, accel=True)[None], (t_fleet, 1)),
        )

    def eval_kernel(state, data, scal_t):
        return objectives.eval_metrics(
            state[0], state[1], data, scal_t["lam"], 0,
            mesh=None, loss=params.loss, smoothing=params.smoothing,
            inv_n=scal_t["inv_n"])

    w0 = jnp.zeros((t_fleet, fleet.num_features), dtype=dtype)
    alpha0 = jnp.zeros((t_fleet, k, fleet.n_shard), dtype=dtype)
    state = (w0, alpha0, *(jnp.asarray(s) for s in state0))
    shard_arrays = fleet.shard_arrays()

    cache_key = (
        "cocoa-fleet", mode, drive_mode, math, rng, t_fleet, k,
        fleet.n_shard, fleet.num_features, h, c, n_chunks,
        params.loss, params.smoothing, scaling, sigma_fixed, levels,
        guard_on, str(dtype), per_tenant_idxs, lane_exec,
    )
    if not quiet:
        print(f"\nRunning {name}: {t_fleet} tenants x (K={k}, "
              f"n_shard={fleet.n_shard}, d={fleet.num_features}, H={h}) "
              f"— one compiled round, drive_mode={drive_mode}")
    t0 = time.perf_counter()
    state, carry, n_done, traj_host = base.drive_fleet_on_device(
        name, state, chunk_kernel, eval_kernel, idxs_all, shard_arrays,
        scal, gap_targets, quiet=quiet, start_round=start_round,
        cache_key=cache_key, stall_evals=base.stall_window(c),
        divergence_guard=guard_on, n_stages=n_stages,
        accel=(drive_mode == "accel"),
        per_tenant_idxs=per_tenant_idxs,
        jump_kernel=(jump_kernel if drive_mode == "accel" else None),
        lane_exec=lane_exec)
    wall_s = time.perf_counter() - t0

    from cocoa_tpu.analysis import sanitize as _sanitize

    with _sanitize.intended_fetch("fleet_result_fetch"):
        certified = np.asarray(carry.done_tgt)
        stalled = np.asarray(carry.done_stall)
        cert_chunk = np.asarray(carry.cert_chunk)
        stall_chunk = np.asarray(carry.stall_chunk)
    cert_round = np.where(cert_chunk > 0,
                          start_round - 1 + cert_chunk * c, 0)
    last = traj_host[n_done - 1] if n_done else np.full(
        (t_fleet, base.FLEET_N_COLS), np.nan)
    result = FleetResult(
        algorithm=name, tenants=list(fleet.tenants), certified=certified,
        stalled=stalled, cert_round=cert_round.astype(np.int64),
        final_primal=last[:, 0].copy(), final_gap=last[:, 1].copy(),
        rounds_run=n_done * c, evals=n_done, wall_s=wall_s,
        w=state[0], alpha=state[1], traj=traj_host)

    bus = _tele.get_bus()
    if bus.active():
        for j in range(n_done):
            t_round = start_round - 1 + (j + 1) * c
            cum = int(((cert_chunk > 0) & (cert_chunk <= j + 1)).sum())
            # active = lanes still UPDATING: certified and stalled-out
            # lanes are both masked frozen from their done eval on
            inactive = int((((cert_chunk > 0) & (cert_chunk <= j + 1))
                            | ((stall_chunk > 0)
                               & (stall_chunk <= j + 1))).sum())
            newly = np.nonzero(cert_chunk == j + 1)[0]
            for ti in newly:
                bus.emit("tenant_certified", algorithm=name,
                         tenant=fleet.tenants[int(ti)], t=t_round,
                         gap=float(traj_host[j, int(ti), 1]))
            bus.emit(
                "fleet_progress", algorithm=name, t=t_round,
                active=t_fleet - inactive, certified_total=cum,
                models_per_second=(result.models_per_second
                                   if j == n_done - 1 else None))
    if not quiet:
        done_n = int(certified.sum())
        print(f"{name}: {done_n}/{t_fleet} tenants certified in "
              f"{result.rounds_run} rounds, {wall_s:.2f}s wall — "
              f"{result.models_per_second:.1f} models/s")
    return result
