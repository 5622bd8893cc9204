"""jaxlint ``pallas-budget`` — the numeric half of the Pallas memory
accounting (rules.py holds the AST half).

The kernels in ``ops/pallas_*.py`` never ask the hardware how much VMEM /
SMEM they may use: each module DECLARES a budget constant and sizes its
blocks with an estimate function that a ``*_fits`` gate compares against
the budget before dispatch commits to the kernel.  That contract has two
statically checkable failure modes:

1. a declared budget exceeding the physical memory (the gate then
   happily admits kernels Mosaic will kill at compile or runtime), and
2. estimate/gate drift — someone widens a scratch buffer or BlockSpec
   and updates the estimate but not the gate (or vice versa), so the
   gate's verdict no longer tracks the bytes the estimate accounts.

This module imports the ops modules (pure Python on CPU; importing does
not build kernels) and checks both: budget constants against the
physical caps from the TPU programming model (~16 MiB VMEM per core,
SMEM far smaller — we cap the repo's scalar-stream budget at 1 MiB), and
gate-vs-estimate agreement swept over a grid of dispatch-realistic
shapes (rcv1 production geometry, the CI synth shapes, and adversarial
corners around each gate's boundary).
"""

from __future__ import annotations

import importlib

from cocoa_tpu.analysis.core import Finding

# caps.  PHYS_VMEM is what a kernel gets when it sets no limit: Mosaic's
# default scoped VMEM, 16 MiB.  A v5e core has 128 MiB (measured, PR 26:
# a kernel with an 84 MB scratch compiles and runs there under
# ``vmem_limit_bytes`` = 100 MiB); a module that asks for more than the
# default declares the limit it passes beside its budget
# (``<NAME>_VMEM_LIMIT`` for ``<NAME>_VMEM_BUDGET``:
# ops/pallas_sparse_hbm.py sets ``HBM_VMEM_LIMIT`` = 100 MiB for
# ``HBM_VMEM_BUDGET`` = 88 MiB), and the budget is then held to that
# limit, the limit to the core.  SMEM is 1 MiB in all (a kernel whose
# tables take 1,026 KB does not compile).
PHYS_VMEM = 16 << 20
PHYS_VMEM_CORE = 128 << 20
PHYS_SMEM = 1 << 20

_OPS_MODULES = ("cocoa_tpu.ops.pallas_sdca", "cocoa_tpu.ops.pallas_sparse",
                "cocoa_tpu.ops.pallas_chain",
                "cocoa_tpu.ops.pallas_sparse_hbm")

# dispatch-realistic sweep: (k, n_shard, d, max_nnz, b, n_hot) covering
# rcv1 production geometry (d=47236, ~170k rows over K=4, row width 548
# / residual ~214 after the hot split), the CI synth shapes, and corners
_SHAPES = (
    # k, n_shard,   d, max_nnz,   b, n_hot
    (4, 169350, 47236,     548, 256,     0),   # rcv1, stream path
    (4, 169350, 47236,     214, 256,  2048),   # rcv1, hybrid hot/cold
    (4,   2048,  9947,      64, 128,     0),   # CI small_train shape
    (4,   2048,  9947,      48, 128,   256),
    (8,  65536, 16384,     128, 512,   512),
    (1,    128,   256,       8,  64,     0),   # single-shard corner
    (16, 32768, 47236,    1024, 128,     0),   # fat rows: should NOT fit
    (8, 2408016, 29890095,  64, 512,     0),   # kddb: past VMEM, the
                                               # HBM-state kernel's shape
)
# (d, max_nnz, h) of the HBM-state sparse kernel's plan: kddb at
# localIterFrac 0.1 and 1, rcv1-full, and rows as wide as SMEM allows
_HBM_SHAPES = (
    (29890095,   64,  240801),
    (29890095,   64, 2408013),
    (47236,     548,    8467),
    (3231961,   414,   29951),
    (16609143, 4096,    2187),
    (16609143, 16384,   2187),                 # too wide: no plan
)


def _mod_findings(modname):
    findings = []

    def flag(line, message, severity="error"):
        findings.append(Finding(
            rule="pallas-budget", severity=severity,
            path=modname.replace(".", "/") + ".py", line=line, col=0,
            message=message))

    return findings, flag


def check_budget_constants() -> list:
    """Every *_BUDGET constant in the ops modules stays under its
    physical cap — a budget over the hardware turns the fits gates into
    rubber stamps."""
    findings = []
    for modname in _OPS_MODULES:
        mod = importlib.import_module(modname)
        out, flag = _mod_findings(modname)
        for name in dir(mod):
            if not name.endswith("BUDGET"):
                continue
            val = getattr(mod, name)
            if not isinstance(val, int):
                continue
            cap = PHYS_SMEM if "SMEM" in name else PHYS_VMEM
            kind = "SMEM" if "SMEM" in name else "VMEM"
            limit = getattr(mod, name.replace("BUDGET", "LIMIT"), None)
            if kind == "VMEM" and isinstance(limit, int):
                # the module asks Mosaic for more than the default
                if limit > PHYS_VMEM_CORE - (16 << 20):
                    flag(1, f"{name.replace('BUDGET', 'LIMIT')} = {limit} "
                            f"bytes leaves under 16 MiB of a core's "
                            f"{PHYS_VMEM_CORE} bytes of VMEM")
                cap = limit - (8 << 20)     # spills, semaphores, buffers
            if val > cap:
                flag(1, f"{name} = {val} bytes exceeds the physical "
                        f"{kind} cap ({cap}) — the fits gates admit "
                        f"kernels the hardware cannot hold")
            elif "SMEM" not in name and limit is None \
                    and val > PHYS_VMEM - (1 << 20):
                flag(1, f"{name} = {val} bytes leaves under 1 MiB of "
                        f"VMEM headroom for Mosaic spills/semaphores",
                     severity="warning")
        findings += out
    return findings


def check_gate_estimate_agreement() -> list:
    """Sweep the fits gates against their own estimates: wherever a gate
    says True, the matching estimate must be within the budget (drift
    in either direction makes overflow a runtime surprise again)."""
    findings = []
    sdca = importlib.import_module("cocoa_tpu.ops.pallas_sdca")
    sparse = importlib.import_module("cocoa_tpu.ops.pallas_sparse")
    chain = importlib.import_module("cocoa_tpu.ops.pallas_chain")
    hbm = importlib.import_module("cocoa_tpu.ops.pallas_sparse_hbm")
    itemsize = 4  # f32, the TPU compute dtype (DESIGN.md §6)

    def flag(modname, message):
        findings.append(Finding(
            rule="pallas-budget", severity="error",
            path=modname.replace(".", "/") + ".py", line=1, col=0,
            message=message))

    for (d, max_nnz, h) in _HBM_SHAPES:
        # the HBM-state sparse kernel: a plan's working sets (the VMEM
        # scratch and output, the SMEM step tables) stay inside the
        # budgets the gate compares, and its segments cover the round
        plan = hbm.hbm_plan(d, max_nnz, h, itemsize)
        if hbm.sparse_hbm_fits(d, max_nnz, h, itemsize) != (plan is not
                                                            None):
            flag("cocoa_tpu.ops.pallas_sparse_hbm",
                 f"sparse_hbm_fits and hbm_plan disagree at d={d} "
                 f"W={max_nnz} H={h}")
        if plan is None:
            continue
        est = hbm.hbm_vmem_estimate(plan.s, plan.m, itemsize)
        if est > hbm.HBM_VMEM_BUDGET:
            flag("cocoa_tpu.ops.pallas_sparse_hbm",
                 f"hbm_plan(d={d}, W={max_nnz}, H={h}) = {plan} but "
                 f"hbm_vmem_estimate={est} exceeds HBM_VMEM_BUDGET")
        if hbm.hbm_smem_estimate(plan.chunk, plan.w_r) > \
                hbm.HBM_SMEM_BUDGET:
            flag("cocoa_tpu.ops.pallas_sparse_hbm",
                 f"hbm_plan(d={d}, W={max_nnz}, H={h}) = {plan} "
                 f"overflows HBM_SMEM_BUDGET")
        if plan.t * plan.s < h or plan.m < min(
                -(-d // 1024) * 1024, plan.s * plan.w_r) \
                or plan.s % plan.chunk or (
                    not plan.direct and plan.m % plan.column_chunk):
            flag("cocoa_tpu.ops.pallas_sparse_hbm",
                 f"hbm_plan(d={d}, W={max_nnz}, H={h}) = {plan} does not "
                 f"cover the round or its columns")
    for size in (4, 8):
        # the relayout of a fold cache that is not stored lane-aligned: its
        # blocks depend on the dtype alone (f64: interpret mode), and the
        # rows it picks keep its four buffers inside the budget
        rows = sdca.pick_align_rows(size)
        if rows not in sdca.ALIGN_ROWS or sdca.align_vmem_estimate(
                rows, size) > sdca.VMEM_BUDGET:
            flag("cocoa_tpu.ops.pallas_sdca",
                 f"pick_align_rows({size}) chose {rows} rows a step, whose "
                 f"estimate exceeds VMEM_BUDGET")
    for (k, n_shard, d, max_nnz, b, n_hot) in _SHAPES:
        # sequential sparse kernel: fits ⇒ estimate under budget AND the
        # SMEM segment split leaves at least one step per invocation
        if sparse.sparse_kernel_fits(k, n_shard, d, max_nnz, h=b,
                                     itemsize=itemsize, n_hot=n_hot):
            est = sparse.sparse_vmem_estimate(n_shard, d, max_nnz,
                                              itemsize, k, n_hot)
            if est > sparse.VMEM_BUDGET:
                flag("cocoa_tpu.ops.pallas_sparse",
                     f"sparse_kernel_fits admits shape k={k} "
                     f"n_shard={n_shard} d={d} W={max_nnz} n_hot={n_hot} "
                     f"but sparse_vmem_estimate={est} exceeds "
                     f"VMEM_BUDGET={sparse.VMEM_BUDGET}")
            if sparse.segment_len(k, max_nnz) < 1:
                flag("cocoa_tpu.ops.pallas_sparse",
                     f"sparse_kernel_fits admits k={k} W={max_nnz} but "
                     f"segment_len < 1 — the SMEM stream cannot hold "
                     f"even one step")
        # the SMEM accounting identity: a segment's two (K, S, W) streams
        # (int32 idx + f32 vals = 8 bytes/slot) must fit the SMEM budget
        s = sparse.segment_len(k, max_nnz)
        if s >= 1 and 8 * k * s * max_nnz > sparse.SMEM_IDX_BUDGET:
            flag("cocoa_tpu.ops.pallas_sparse",
                 f"segment_len({k}, {max_nnz}) = {s} overflows "
                 f"SMEM_IDX_BUDGET: {8 * k * s * max_nnz} bytes")
        # block-chain kernels
        if chain.chain_fits(k, b, itemsize):
            est = chain.chain_vmem_estimate(k, b, itemsize)
            if est > chain.CHAIN_VMEM_BUDGET:
                flag("cocoa_tpu.ops.pallas_chain",
                     f"chain_fits admits k={k} B={b} but estimate={est} "
                     f"exceeds CHAIN_VMEM_BUDGET")
        if chain.fused_fits(k, b, d, itemsize):
            est = chain.fused_vmem_estimate(k, b, d, itemsize)
            if est > chain.FUSED_VMEM_BUDGET:
                flag("cocoa_tpu.ops.pallas_chain",
                     f"fused_fits admits k={k} B={b} d={d} but "
                     f"estimate={est} exceeds FUSED_VMEM_BUDGET")
        # dense folded-layout SDCA kernel: the pickers must only ever
        # choose a group size (shard-major) or a ring depth (interleaved)
        # whose estimate respects its budget
        s = sdca.pick_unroll(n_shard, d, itemsize, h=b)
        if s > 0 and sdca.vmem_estimate(n_shard, d, itemsize, s) > \
                sdca.VMEM_BUDGET:
            flag("cocoa_tpu.ops.pallas_sdca",
                 f"pick_unroll({n_shard}, {d}) chose S={s} whose "
                 f"estimate exceeds VMEM_BUDGET")
        s = sdca.pick_interleave(k, n_shard, d, itemsize, h=b)
        if s > 0 and sdca.interleave_vmem_estimate(
                k, n_shard, d, itemsize, s) > sdca.INTERLEAVE_BUDGET:
            flag("cocoa_tpu.ops.pallas_sdca",
                 f"pick_interleave(k={k}, {n_shard}, {d}) chose a ring "
                 f"{s} deep whose estimate exceeds INTERLEAVE_BUDGET")
        # sparse block-chain Gram/apply path: fits ⇒ the segment pair's
        # SMEM streams and the Gram tile's VMEM stay inside budget
        if sparse.sparse_chain_fits(k, n_shard, d, max_nnz, b, itemsize):
            sb = sparse.seg_rows(b, max_nnz)
            group = min(sparse.GROUP, max(1, max_nnz))
            w_r = -(-max_nnz // group) * group
            if sb < 8 or 16 * sb * w_r > sparse.SMEM_IDX_BUDGET:
                flag("cocoa_tpu.ops.pallas_sparse",
                     f"sparse_chain_fits admits B={b} W={max_nnz} but "
                     f"seg_rows={sb} overflows SMEM_IDX_BUDGET")
            if sparse.sparse_block_vmem(d, b, sb, itemsize) > \
                    sparse.VMEM_BUDGET:
                flag("cocoa_tpu.ops.pallas_sparse",
                     f"sparse_chain_fits admits d={d} B={b} but the "
                     f"Gram tile estimate exceeds VMEM_BUDGET")
        if n_hot > 0 and sparse.hybrid_fits(k, n_shard, d, max_nnz, b,
                                            n_hot, itemsize) and \
                n_hot % 128 != 0:
            flag("cocoa_tpu.ops.pallas_sparse",
                 f"hybrid_fits admits a non-lane-aligned hot panel "
                 f"(n_hot={n_hot})")
    return findings


def run_budget_checks() -> list:
    """The full numeric pallas-budget pass; import failures degrade to a
    lint error rather than a crash (CI must see them either way)."""
    try:
        findings = check_budget_constants()
        findings += check_gate_estimate_agreement()
        return findings
    except Exception as e:  # pragma: no cover - only on API drift
        return [Finding(
            rule="pallas-budget", severity="error",
            path="cocoa_tpu/ops", line=1, col=0,
            message=(f"budget cross-check could not run ({type(e).__name__}:"
                     f" {e}) — the ops accounting API drifted out from "
                     f"under the analyzer; update pallas_budget.py"))]
