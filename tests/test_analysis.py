"""jaxlint (cocoa_tpu/analysis): per-rule known-good/known-bad fixtures,
the PR-2 donation-miss regression, the baseline/suppression machinery,
and the dynamic sanitizer smoke on the CPU drive loop (compile-once +
zero unintended device→host transfers, telemetry-on and -off)."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cocoa_tpu import analysis
from cocoa_tpu.analysis import core, pallas_budget, rules, sanitize
from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import shard_dataset
from cocoa_tpu.solvers import run_cocoa
from cocoa_tpu.telemetry import events as tele
from cocoa_tpu.telemetry import schema

K = 4


# --- fixture-lint helper ----------------------------------------------------


def lint(tmp_path, code, relpath="fixture.py", rule=None):
    """Lint one source fixture; returns findings (optionally one rule's)."""
    ab = tmp_path / relpath
    ab.parent.mkdir(parents=True, exist_ok=True)
    ab.write_text(code)
    src = core.load_source(str(tmp_path), relpath)
    assert src is not None, "fixture failed to parse"
    sources = {src.path: src}
    found = rules.run_static_rules(sources)
    core.fingerprint_findings(found, sources)
    core.apply_suppressions(found, sources)
    if rule is not None:
        found = [f for f in found if f.rule == rule]
    return found


# --- donation rule ----------------------------------------------------------

PR2_SHAPE = """
import functools
import jax
import jax.numpy as jnp

@functools.partial(jax.jit, donate_argnums=(0, 1))
def round_step(w, alpha, idxs, delta):
    # the PR-2 bug: the donated alpha is read both through .at and bare,
    # so the output cannot alias the donated buffer -> silent full copy
    da = alpha.at[idxs].add(delta) - alpha
    return w + da.sum(), alpha + da
"""

PR2_FIXED = """
import functools
import jax
import jax.numpy as jnp

@functools.partial(jax.jit, donate_argnums=(0, 1))
def round_step(w, alpha, idxs, delta):
    # the PR-2 fix shape: scatter (a0 + d) - a0 into zeros
    da = jnp.zeros_like(alpha).at[idxs].add(delta)
    return w + da.sum(), alpha + da
"""

PR2_NESTED = """
import functools
import jax
from cocoa_tpu.solvers import base

def make_round_step(mesh):
    def per_shard(w, alpha_k, idxs_k):
        delta = w[idxs_k]
        return delta.sum(), alpha_k.at[idxs_k].add(delta) - alpha_k

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def round_step(w, alpha, idxs):
        dw, alpha = base.fanout(per_shard, mesh, w, alpha, idxs)
        return w + dw, alpha

    return round_step
"""


def test_donation_pr2_regression_caught(tmp_path):
    """The exact PR-2 α donation-miss shape is a lint error."""
    found = lint(tmp_path, PR2_SHAPE, rule="donation")
    assert len(found) == 1
    assert "full copy" in found[0].message
    assert "alpha" in found[0].message


def test_donation_pr2_fixed_shape_clean(tmp_path):
    assert lint(tmp_path, PR2_FIXED, rule="donation") == []


def test_donation_pr2_nested_per_shard_caught(tmp_path):
    """The shape as it actually occurred: inside a per_shard fn passed to
    fanout, not lexically inside the jitted def."""
    found = lint(tmp_path, PR2_NESTED, rule="donation")
    assert len(found) == 1
    assert "alpha_k" in found[0].message


def test_donation_index_out_of_range(tmp_path):
    code = """
import jax

def f(w):
    return w * 2

g = jax.jit(f, donate_argnums=(3,))
"""
    found = lint(tmp_path, code, rule="donation")
    assert len(found) == 1
    assert "out of range" in found[0].message


def test_donation_unused_donated_arg(tmp_path):
    code = """
import functools
import jax

@functools.partial(jax.jit, donate_argnums=(1,))
def f(w, alpha):
    return w * 2
"""
    found = lint(tmp_path, code, rule="donation")
    assert len(found) == 1
    assert "never reads" in found[0].message


def test_donation_step_in_solvers_must_donate(tmp_path):
    code = """
import jax

def make_step():
    def round_step(w, idxs):
        return w + idxs.sum()
    return jax.jit(round_step)
"""
    found = lint(tmp_path, code, relpath="cocoa_tpu/solvers/x.py",
                 rule="donation")
    assert len(found) == 1
    assert "donates nothing" in found[0].message
    # the same jit site outside solvers/ is not step-shaped policy
    assert lint(tmp_path, code, relpath="cocoa_tpu/evalsx/x.py",
                rule="donation") == []


def test_donation_good_steps_clean(tmp_path):
    code = """
import functools
import jax

@functools.partial(jax.jit, donate_argnums=(0,))
def round_step(w, idxs):
    return w + idxs.sum()

def make(kernel):
    return jax.jit(kernel, donate_argnums=(0, 1))
"""
    assert lint(tmp_path, code, relpath="cocoa_tpu/solvers/x.py",
                rule="donation") == []


# --- host-sync rule ---------------------------------------------------------

HOST_SYNC_BAD = """
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

@jax.jit
def f(x):
    v = float(x)                 # scalar sync
    a = np.asarray(x)            # host materialization
    return v + a.sum()

@jax.jit
def g(state):
    def body(s):
        return s + jnp.float32(s.item())   # sync per loop iteration
    return lax.while_loop(lambda s: s < 3, body, state)

@jax.jit
def h(x):
    if x:                        # implicit bool()
        return x
    return -x
"""


def test_host_sync_bad_shapes_caught(tmp_path):
    found = lint(tmp_path, HOST_SYNC_BAD, rule="host-sync")
    msgs = sorted(f.message for f in found)
    assert len(found) == 4, msgs
    assert any("float()" in m for m in msgs)
    assert any("asarray" in m for m in msgs)
    assert any(".item()" in m for m in msgs)
    assert any("implicit bool" in m for m in msgs)


HOST_SYNC_GOOD = """
import functools
import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import io_callback

def tap(i, row):
    # host side: the sanctioned io_callback target may sync freely
    print(int(i), float(row[0]))

@jax.jit
def f(x):
    def body(s):
        io_callback(tap, None, s, x, ordered=True)
        return s + 1
    return lax.while_loop(lambda s: s < 3, body, jnp.int32(0))

@functools.partial(jax.jit, static_argnames=("n", "lam"))
def k(x, n, lam):
    # static args are trace-time python: float()/if are legal
    scale = float(lam * n)
    if n > 4:
        scale = scale * 2.0
    return x * scale + float(x.shape[0])   # shape metadata is static
"""


def test_host_sync_sanctioned_shapes_clean(tmp_path):
    assert lint(tmp_path, HOST_SYNC_GOOD, rule="host-sync") == []


def test_host_sync_repo_drivers_clean():
    """The production drivers/kernels carry no stray host syncs (what
    PR 6's first full-tree run established; keep it true)."""
    findings, _, _ = analysis.run_analysis(with_budget_checks=False)
    bad = [f for f in findings if f.rule == "host-sync" and f.actionable]
    assert bad == [], [f.location() for f in bad]


# --- f64 rule ---------------------------------------------------------------


def test_f64_leak_caught_outside_evals(tmp_path):
    code = """
import jax.numpy as jnp
import numpy as np

def f(x):
    return jnp.asarray(x, dtype=jnp.float64)

def g(x):
    return x.astype("float64")
"""
    found = lint(tmp_path, code, relpath="cocoa_tpu/ops/x.py", rule="f64")
    assert len(found) == 2
    # the same code under evals/ is certificate math — allowed
    assert lint(tmp_path, code, relpath="cocoa_tpu/evals/x.py",
                rule="f64") == []


def test_f64_inline_allow(tmp_path):
    code = """
import numpy as np

def parse(tokens):
    # jaxlint: allow=f64 -- host-side exact parse fixture
    return np.asarray(tokens, dtype=np.float64)
"""
    found = lint(tmp_path, code, relpath="cocoa_tpu/data/x.py", rule="f64")
    assert len(found) == 1
    assert found[0].suppressed
    assert "exact parse" in found[0].suppression_reason


# --- pallas-budget ----------------------------------------------------------


def test_pallas_budget_missing_gate_caught(tmp_path):
    code = """
from jax.experimental import pallas as pl

def kernel(ref, out):
    out[...] = ref[...]

def run(x):
    return pl.pallas_call(kernel, out_shape=x)(x)
"""
    found = lint(tmp_path, code, relpath="cocoa_tpu/ops/x.py",
                 rule="pallas-budget")
    msgs = [f.message for f in found]
    assert any("no *_BUDGET constant" in m for m in msgs)
    assert any("no *_fits gate" in m for m in msgs)


def test_pallas_budget_numeric_checks_clean():
    """The shipped ops modules: budgets under the physical caps, gates
    agreeing with their estimates over the dispatch-realistic sweep."""
    assert pallas_budget.run_budget_checks() == []


def test_pallas_budget_detects_gate_estimate_drift(monkeypatch):
    """Widen the sparse estimate out from under its gate — the sweep must
    notice (this is the 'overflow becomes a lint error' contract)."""
    from cocoa_tpu.ops import pallas_sparse

    # a gate that stops consulting its estimate (the drift shape: a new
    # scratch buffer accounted in the estimate but not gated on)
    monkeypatch.setattr(pallas_sparse, "sparse_kernel_fits",
                        lambda *a, **k: True)
    found = pallas_budget.check_gate_estimate_agreement()
    assert any("exceeds VMEM_BUDGET" in f.message for f in found)


# --- span-hygiene rule ------------------------------------------------------

SPAN_IN_JIT = """
import jax
from cocoa_tpu.telemetry import tracing

@jax.jit
def step(w, alpha):
    with tracing.span("round"):
        return w + alpha.sum(), alpha
"""

SPAN_IN_LAX_BODY = """
import jax
from jax import lax
from cocoa_tpu.telemetry import tracing as _tracing

def run(w):
    def body(s):
        with _tracing.span("chunk"):
            return s + 1.0
    return lax.while_loop(lambda s: s < 10.0, body, w)
"""

SPAN_READS_TRACED_VALUE = """
import jax
from jax import lax
from jax.experimental import io_callback
from cocoa_tpu.telemetry import tracing

@jax.jit
def run(w):
    def tap(row):
        # host-side by construction (io_callback target), so spanning is
        # legal — but tagging the enclosing TRACED w syncs it at emit
        with tracing.span("eval", w_now=w):
            pass
    def body(s):
        io_callback(tap, None, s, ordered=True)
        return s + 1.0
    return lax.while_loop(lambda s: s < 3.0, body, w)
"""

SPAN_ON_HOST_CLEAN = """
import jax
from cocoa_tpu.telemetry import tracing

@jax.jit
def step(w):
    return w + 1.0

def drive(w, rounds):
    for t in range(rounds):
        with tracing.span("local_solve", round=t):
            w = step(w)
    with tracing.span("eval", round=rounds):
        gap = float(w.sum())
    return w, gap
"""

SPAN_IN_CALLBACK_CLEAN = """
import jax
from jax import lax
from jax.experimental import io_callback
from cocoa_tpu.telemetry import tracing

def run(w):
    def tap(row):
        # io_callback targets run on the HOST — spans are fine here
        with tracing.span("decode"):
            pass
    def body(s):
        io_callback(tap, None, s, ordered=True)
        return s + 1.0
    return lax.while_loop(lambda s: s < 3.0, body, w)
"""


def test_span_hygiene_span_in_jit_caught(tmp_path):
    found = lint(tmp_path, SPAN_IN_JIT, rule="span-hygiene")
    assert len(found) == 1
    assert "times the trace" in found[0].message


COLD_SPAN_IN_JIT = """
import jax
from cocoa_tpu.telemetry import tracing as _tracing

@jax.jit
def fold(x):
    with _tracing.cold_span("fold_rows") as cold:
        y = x.reshape(-1, 8)
        cold.made(y)
        return y
"""


def test_span_hygiene_cold_span_in_jit_caught(tmp_path):
    """A cold span reads the allocator and waits for the device: inside a
    jit body it is an error like a span there."""
    found = lint(tmp_path, COLD_SPAN_IN_JIT, rule="span-hygiene")
    assert len(found) == 1 and found[0].severity == "error"
    assert "`cold_span(...)` inside traced code" in found[0].message


def test_span_hygiene_span_in_lax_body_caught(tmp_path):
    found = lint(tmp_path, SPAN_IN_LAX_BODY, rule="span-hygiene")
    assert len(found) == 1 and found[0].severity == "error"


def test_span_hygiene_traced_attr_in_callback_caught(tmp_path):
    """An io_callback target runs on the host and may span freely — but
    a span attribute reading a value traced in the ENCLOSING scope is a
    silent device sync at emit time."""
    found = lint(tmp_path, SPAN_READS_TRACED_VALUE, rule="span-hygiene")
    assert len(found) == 1
    assert "traced value" in found[0].message


def test_span_hygiene_host_and_callback_spans_clean(tmp_path):
    assert lint(tmp_path, SPAN_ON_HOST_CLEAN, rule="span-hygiene") == []
    assert lint(tmp_path, SPAN_IN_CALLBACK_CLEAN,
                rule="span-hygiene") == []


UNRELATED_SPAN_METHOD = """
import re
import jax

@jax.jit
def step(w, names):
    # trace-time host work: re.Match.span() is NOT the tracing API —
    # the rule must key on the tracing receiver / string phase arg
    m = re.match(r"w(\\d+)", "w3")
    lo, hi = m.span()
    spans = [m.span(0)]
    return w[lo:hi]
"""


def test_span_hygiene_ignores_unrelated_span_methods(tmp_path):
    assert lint(tmp_path, UNRELATED_SPAN_METHOD,
                rule="span-hygiene") == []


# --- overlap-hygiene rule ---------------------------------------------------

ASYNC_IN_JIT = """
import jax
from cocoa_tpu.parallel.distributed import async_host_allgather_bytes

@jax.jit
def step(w):
    h = async_host_allgather_bytes("dw", w)   # traced value escapes
    return w
"""

ASYNC_IN_LAX_BODY = """
from jax import lax
from cocoa_tpu.parallel import distributed

def run(w):
    def body(i, w):
        distributed.async_kv_get(None, "k")
        return w
    return lax.fori_loop(0, 3, body, w)
"""

HANDLE_NEVER_JOINED = """
from cocoa_tpu.parallel.distributed import async_host_allgather_bytes

def round_exchange(payload, dispatch):
    h = async_host_allgather_bytes("dw", payload)
    dispatch()          # the super-block crosses an un-joined exchange
    return None
"""

HANDLE_JOINED = """
from cocoa_tpu.parallel.distributed import async_host_allgather_bytes

def round_exchange(payload, dispatch):
    h = async_host_allgather_bytes("dw", payload)
    dispatch()
    return h.join()     # joined at the barrier: clean
"""

HANDLE_ESCAPES = """
from cocoa_tpu.parallel.distributed import async_host_allgather_bytes

def round_exchange(payload, window, t):
    h = async_host_allgather_bytes(f"dw{t}", payload)
    window.admit(t, h)  # handed to the join window: its job to join
"""


def test_overlap_hygiene_async_launch_in_jit_caught(tmp_path):
    found = lint(tmp_path, ASYNC_IN_JIT, rule="overlap-hygiene")
    assert len(found) == 1 and "exchange thread" in found[0].message


def test_overlap_hygiene_async_launch_in_lax_body_caught(tmp_path):
    found = lint(tmp_path, ASYNC_IN_LAX_BODY, rule="overlap-hygiene")
    assert len(found) == 1


def test_overlap_hygiene_unjoined_handle_caught(tmp_path):
    found = lint(tmp_path, HANDLE_NEVER_JOINED, rule="overlap-hygiene")
    assert len(found) == 1 and "never joined" in found[0].message


def test_overlap_hygiene_joined_or_escaping_clean(tmp_path):
    assert lint(tmp_path, HANDLE_JOINED, rule="overlap-hygiene") == []
    assert lint(tmp_path, HANDLE_ESCAPES, rule="overlap-hygiene") == []


# --- fleet-hygiene rule -----------------------------------------------------

TENANT_LOOP_IN_JIT = """
import jax
import jax.numpy as jnp

@jax.jit
def fleet_round(states, tables, n_tenants):
    # the anti-pattern the fleet path replaces: T kernels unrolled into
    # one graph, one compiled round PER TENANT
    out = []
    for t in range(n_tenants):
        out.append(states[t] + tables[t])
    return jnp.stack(out)

@jax.jit
def fleet_round2(tenants, tables):
    acc = jnp.zeros_like(tables[0])
    for tenant in tenants:
        acc = acc + tenant
    return acc
"""

TENANT_LOOP_IN_LAX_BODY = """
import jax.numpy as jnp
from jax import lax

def drive(state, tenants):
    def body(i, s):
        for tenant in tenants:
            s = s + tenant
        return s
    return lax.fori_loop(0, 10, body, state)
"""

TENANT_FETCH_IN_HOST_LOOP = """
import numpy as np

def report(fleet_w, tenants):
    out = []
    for t, tenant in enumerate(tenants):
        out.append(float(np.asarray(fleet_w[t])[0]))  # T d2h round-trips
    return out
"""

TENANT_LOOP_CLEAN = """
import jax
import numpy as np

def fleet_kernel(chunk_kernel, states):
    return jax.vmap(chunk_kernel)(states)   # the tenant axis rides vmap

def report(fleet_w, tenants):
    w_host = np.asarray(fleet_w)            # ONE fetch before the loop
    return [float(w_host[t, 0]) for t, tenant in enumerate(tenants)]
"""


def test_fleet_hygiene_tenant_loop_in_jit_caught(tmp_path):
    found = lint(tmp_path, TENANT_LOOP_IN_JIT, rule="fleet-hygiene")
    assert len(found) == 2 and all("unrolls" in f.message for f in found)


def test_fleet_hygiene_tenant_loop_in_lax_body_caught(tmp_path):
    found = lint(tmp_path, TENANT_LOOP_IN_LAX_BODY, rule="fleet-hygiene")
    assert len(found) == 1


def test_fleet_hygiene_per_tenant_fetch_caught(tmp_path):
    found = lint(tmp_path, TENANT_FETCH_IN_HOST_LOOP, rule="fleet-hygiene")
    assert len(found) == 1 and "ONCE before the loop" in found[0].message


def test_fleet_hygiene_vmap_and_prefetched_loop_clean(tmp_path):
    assert lint(tmp_path, TENANT_LOOP_CLEAN, rule="fleet-hygiene") == []


def test_fleet_hygiene_full_tree_clean():
    """The real tree carries ZERO fleet-hygiene findings — the rule's
    contract is that the shipped fleet path itself is the reference
    implementation of its own hygiene."""
    root = core.repo_root()
    sources = {}
    for rel in core.iter_py_files(root):
        src = core.load_source(root, rel)
        if src is not None:
            sources[src.path] = src
    found = [f for f in rules.run_static_rules(sources)
             if f.rule == "fleet-hygiene"]
    core.fingerprint_findings(found, sources)
    core.apply_suppressions(found, sources)
    assert [f for f in found if f.actionable] == []


# --- fingerprints / baseline / report --------------------------------------


def test_fingerprints_survive_unrelated_edits(tmp_path):
    found1 = lint(tmp_path, PR2_SHAPE, relpath="a.py")
    shifted = PR2_SHAPE.replace(
        "import functools", "# an unrelated comment\nimport functools")
    found2 = lint(tmp_path, shifted, relpath="a.py")
    fp1 = {f.fingerprint for f in found1}
    fp2 = {f.fingerprint for f in found2}
    assert fp1 == fp2 and fp1


def test_baseline_roundtrip(tmp_path):
    ab = tmp_path / "a.py"
    ab.write_text(PR2_SHAPE)
    src = core.load_source(str(tmp_path), "a.py")
    sources = {src.path: src}
    findings = rules.run_static_rules(sources)
    core.fingerprint_findings(findings, sources)
    bl_path = str(tmp_path / "baseline.json")
    core.write_baseline(findings, bl_path)
    bl = core.load_baseline(bl_path)
    stale = core.apply_baseline(findings, bl)
    assert stale == []
    assert all(f.baselined and not f.actionable for f in findings)
    # fixing the finding leaves a stale entry behind
    stale2 = core.apply_baseline([], bl)
    assert len(stale2) == len(bl)


def test_scoped_run_keeps_out_of_scope_baseline(tmp_path):
    """A targeted run (explicit path subset) must treat baseline entries
    for unscanned files as out-of-scope — not stale — and a path-scoped
    --update-baseline must carry them over untouched instead of wiping
    the repo's justified baseline."""
    findings, sources, stale = analysis.run_analysis(
        targets=["cocoa_tpu/solvers"], with_budget_checks=False)
    assert stale == [], [e["fingerprint"] for e in stale]
    # path-scoped rewrite: out-of-scope entries survive verbatim
    before = core.load_baseline()
    assert before, "repo baseline expected to be non-empty"
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps(
        {"entries": list(before.values())}))
    core.write_baseline(
        [f for f in findings if not f.suppressed], str(bl),
        scanned_paths=set(sources))
    after = core.load_baseline(str(bl))
    assert after == before


def test_compile_bridge_survives_watch_teardown(tmp_path):
    """install_compile_events during an open watch_compiles context must
    keep counting after the context exits (the watch teardown must not
    restore the logger level out from under the process-lifetime
    bridge)."""
    if sanitize._BUS_BRIDGE is None:
        bus = tele.get_bus()
        bus.configure(jsonl_path=str(tmp_path / "ev.jsonl"))
        bus.reset()
    assert sanitize._BUS_BRIDGE is not None
    with sanitize.watch_compiles():
        pass
    seen = []
    bus = tele.get_bus()
    bus.subscribe(seen.append)
    try:
        jax.jit(lambda x: x * 3.5)(jnp.float32(2.0)).block_until_ready()
    finally:
        bus.reset()
    assert any(e.get("event") == "compile" for e in seen), seen


def test_report_jsonl_validates_against_schema(tmp_path):
    findings = lint(tmp_path, PR2_SHAPE, relpath="a.py")
    report = tmp_path / "report.jsonl"
    core.write_report(str(report), findings, files_scanned=1,
                      rules=analysis.RULES)
    assert schema.check_file(str(report)) == []
    # a corrupted finding line must trip the checker
    lines = report.read_text().splitlines()
    bad = json.loads(lines[1])
    del bad["fingerprint"]
    bad["severity"] = "catastrophic"
    report.write_text("\n".join([lines[0], json.dumps(bad)]) + "\n")
    errs = schema.check_file(str(report))
    assert any("fingerprint" in e for e in errs)
    assert any("catastrophic" in e for e in errs)


def test_repo_is_lint_clean():
    """The acceptance gate: `python -m cocoa_tpu.analysis` exits clean on
    this tree — every finding fixed, inline-justified, or baselined with
    a justification (never a TODO placeholder)."""
    findings, _, stale = analysis.run_analysis()
    new = [f for f in findings if f.actionable]
    assert new == [], [f"{f.location()}: {f.message}" for f in new]
    assert stale == [], stale
    for f in findings:
        if f.baselined:
            assert f.justification and "TODO" not in f.justification, \
                f.location()


# --- dynamic sanitizer on the CPU drive loop --------------------------------


@pytest.fixture()
def small_ds(tiny_data):
    return shard_dataset(tiny_data, k=K, layout="dense", dtype=jnp.float32)


_PARAMS = dict(num_rounds=12, lam=0.01, local_iters=15, beta=1.0, gamma=1.0)
_DBG = DebugParams(debug_iter=4, seed=0)


def test_transfer_guard_has_teeth():
    """An un-sanctioned scalar sync under the strict guard raises — the
    'zero unintended transfers' assertion is not vacuous.  (On CPU the
    device→host half of ``float(x[i])`` is zero-copy; what trips is the
    host→device upload of the index constant — on TPU both halves do.)"""
    x = jax.device_put(jnp.arange(3.0))
    with pytest.raises(Exception, match="[Dd]isallowed.*transfer"):
        with sanitize.no_transfers():
            float(x[0])
    # the sanctioned path through intended_fetch stays open
    with sanitize.no_transfers():
        with sanitize.intended_fetch("test"):
            assert float(x[0]) == 0.0


def test_sanitizer_drive_loop_compile_once_and_no_syncs(small_ds, tiny_data):
    """THE sanitizer contract (ISSUE 6 acceptance): the device-resident
    drive loop compiles exactly once per config, performs zero unintended
    device→host transfers inside the round loop, and a second identical
    run reuses the executable (zero compiles)."""
    params = Params(n=tiny_data.n, **_PARAMS)
    with sanitize.sanitizer() as s1:
        w1, a1, traj1 = run_cocoa(small_ds, params, _DBG, plus=True,
                                  quiet=True, device_loop=True)
    assert s1.compile_count("run") == 1, [c.name for c in s1.compiles]
    assert s1.intended_fetches >= 1
    with sanitize.sanitizer() as s2:
        w2, a2, traj2 = run_cocoa(small_ds, params, _DBG, plus=True,
                                  quiet=True, device_loop=True)
    assert s2.compiles == [], [c.name for c in s2.compiles]
    assert jnp.array_equal(w1, w2) and jnp.array_equal(a1, a2)
    assert len(traj2.records) == len(traj1.records)


def test_sanitizer_drive_loop_telemetry_on(small_ds, tiny_data, tmp_path):
    """Same invariants with every telemetry sink attached: the
    io_callback tap must not introduce unintended transfers, and the
    metrics textfile exposes compiles_total / host_transfers_total."""
    params = Params(n=tiny_data.n, **_PARAMS)
    ev = str(tmp_path / "events.jsonl")
    mp = str(tmp_path / "metrics.prom")
    bus = tele.get_bus()
    bus.configure(jsonl_path=ev, metrics_path=mp)
    try:
        with sanitize.sanitizer() as s:
            w, a, _ = run_cocoa(small_ds, params, _DBG, plus=True,
                                quiet=True, device_loop=True)
        assert s.compile_count("run") <= 1
        assert s.intended_fetches >= 1
    finally:
        bus.reset()
    # telemetry-off reference run must match bit-for-bit
    w0, a0, _ = run_cocoa(small_ds, params, _DBG, plus=True, quiet=True,
                          device_loop=True)
    assert jnp.array_equal(w, w0) and jnp.array_equal(a, a0)
    assert schema.check_file(ev) == []
    evs = [json.loads(l) for l in open(ev)]
    kinds = {e["event"] for e in evs}
    assert "host_transfer" in kinds
    text = open(mp).read()
    assert "cocoa_compiles_total" in text
    assert "cocoa_host_transfers_total" in text
    ht = int([l for l in text.splitlines()
              if l.startswith("cocoa_host_transfers_total")][0].split()[1])
    assert ht == sum(1 for e in evs if e["event"] == "host_transfer")


def test_host_stepped_eval_fetch_is_sanctioned(small_ds, tiny_data):
    """The chunked (host-stepped) driver's per-eval fetch rides
    intended_fetch too — the sanitizer passes on the scan_chunk path."""
    params = Params(n=tiny_data.n, **_PARAMS)
    with sanitize.sanitizer(strict="d2h") as s:
        w, a, traj = run_cocoa(small_ds, params, _DBG, plus=True,
                               quiet=True, scan_chunk=4)
    assert s.intended_fetches >= len(traj.records)


def test_metrics_writer_counts_sanitizer_events(tmp_path):
    from cocoa_tpu.telemetry.metrics import MetricsWriter

    mp = str(tmp_path / "m.prom")
    w = MetricsWriter(mp)
    w({"event": "compile", "name": "run", "seconds": 0.5, "ts": 1.0})
    w({"event": "compile", "name": "eval", "seconds": 0.1, "ts": 2.0})
    w({"event": "host_transfer", "label": "device_loop_fetch", "ts": 3.0})
    text = open(mp).read()
    assert "cocoa_compiles_total 2" in text
    assert "cocoa_host_transfers_total 1" in text


def test_analysis_cli_exits_clean(tmp_path):
    """`python -m cocoa_tpu.analysis` (the CI gate) exits 0 on this tree
    and writes a schema-valid report."""
    from cocoa_tpu.analysis.__main__ import main

    report = str(tmp_path / "report.jsonl")
    rc = main([f"--report={report}"])
    assert rc == 0
    assert schema.check_file(report) == []


# --- serve-hygiene rule ------------------------------------------------------

SERVE_JIT_IN_HOT_PATH = """
import jax

def score_batch(w, idx, val):
    fn = jax.jit(lambda w, i, v: (w[i] * v).sum(-1))
    return fn(w, idx, val)
"""

SERVE_LEN_SHAPE = """
import numpy as np

def drain_requests(requests, width):
    idx = np.zeros((len(requests), width), np.int32)
    return idx
"""

SERVE_CLOCK_IN_TRACED = """
import time
import jax

@jax.jit
def serve_margins(w, idx, val):
    t0 = time.monotonic()
    return (w[idx] * val).sum(-1)
"""

SERVE_SYNC_IN_TRACED = """
import jax

@jax.jit
def serve_margins(w, idx, val):
    out = (w[idx] * val).sum(-1)
    out.block_until_ready()
    return out
"""

SERVE_CLEAN = """
import time
import jax
import numpy as np

class Scorer:
    def __init__(self):
        # builder scope: the one sanctioned place to create the jit
        self._jit = jax.jit(lambda w, i, v: (w[i] * v).sum(-1))

    def assemble(self, queries, bucket, width):
        # static bucket shape, never len(queries)
        idx = np.zeros((bucket, width), np.int32)
        return idx

    def score(self, w, idx, val):
        t0 = time.monotonic()   # host boundary: clocks are fine here
        return self._jit(w, idx, val)
"""


def test_serve_hygiene_jit_in_hot_path_caught(tmp_path):
    found = lint(tmp_path, SERVE_JIT_IN_HOT_PATH,
                 relpath="cocoa_tpu/serving/fixture.py",
                 rule="serve-hygiene")
    assert len(found) == 1 and "fresh" in found[0].message


def test_serve_hygiene_request_dependent_shape_caught(tmp_path):
    found = lint(tmp_path, SERVE_LEN_SHAPE,
                 relpath="cocoa_tpu/serving/fixture.py",
                 rule="serve-hygiene")
    assert len(found) == 1
    assert "static bucket" in found[0].message


def test_serve_hygiene_clock_in_traced_caught(tmp_path):
    found = lint(tmp_path, SERVE_CLOCK_IN_TRACED,
                 relpath="cocoa_tpu/serving/fixture.py",
                 rule="serve-hygiene")
    assert len(found) == 1 and "TRACE time" in found[0].message


def test_serve_hygiene_device_sync_in_traced_caught(tmp_path):
    found = lint(tmp_path, SERVE_SYNC_IN_TRACED,
                 relpath="cocoa_tpu/serving/fixture.py",
                 rule="serve-hygiene")
    assert len(found) == 1 and "block_until_ready" in found[0].message


def test_serve_hygiene_builder_scopes_clean(tmp_path):
    found = lint(tmp_path, SERVE_CLEAN,
                 relpath="cocoa_tpu/serving/fixture.py",
                 rule="serve-hygiene")
    assert found == []


def test_serve_hygiene_scoped_to_serving(tmp_path):
    # the same shapes OUTSIDE serving/ are not this rule's business
    # (host-sync and friends still apply on their own terms)
    found = lint(tmp_path, SERVE_JIT_IN_HOT_PATH,
                 relpath="cocoa_tpu/solvers/fixture.py",
                 rule="serve-hygiene")
    assert found == []


SERVE_QUANT_IN_TRACED = """
import jax
import jax.numpy as jnp

@jax.jit
def serve_margins(w, idx, val):
    scale = jnp.abs(w).max() / 127.0
    wq = (w / scale).astype(jnp.int8)
    return (wq[idx].astype(jnp.float32) * scale * val).sum(-1)
"""

SERVE_QUANT_ON_HOST = """
import jax
import jax.numpy as jnp
import numpy as np

def quantize(w):
    # host-side swap-time quantization: abs-max scale and a narrowing
    # cast are exactly where they belong (no jit anywhere near)
    scale = np.abs(w).max() / 127.0
    return (w / scale).astype(np.int8), scale

@jax.jit
def serve_margins(wq, scale, idx, val):
    # widening back to f32 on the gathered rows is the legal direction
    return (wq[idx].astype(jnp.float32) * scale * val).sum(-1)
"""


def test_serve_hygiene_quantize_in_traced_caught(tmp_path):
    found = lint(tmp_path, SERVE_QUANT_IN_TRACED,
                 relpath="cocoa_tpu/serving/fixture.py",
                 rule="serve-hygiene")
    # one finding per half of the in-graph quantize: the abs-max scale
    # and the narrowing cast (the widening astype(float32) stays clean)
    assert len(found) == 2, [(f.line, f.message) for f in found]
    assert any("max-of-abs" in f.message for f in found)
    assert any("astype(int8)" in f.message
               and "quantize ONCE on the host" in f.message
               for f in found)


def test_serve_hygiene_host_quantize_and_widening_clean(tmp_path):
    found = lint(tmp_path, SERVE_QUANT_ON_HOST,
                 relpath="cocoa_tpu/serving/fixture.py",
                 rule="serve-hygiene")
    assert found == [], [(f.line, f.message) for f in found]


def test_serve_hygiene_full_serving_tree_clean():
    """The shipped serving subsystem passes its own rule (and every
    other rule) with zero new findings."""
    findings, _, _ = analysis.run_analysis(
        targets=["cocoa_tpu/serving"], with_budget_checks=False)
    actionable = [f for f in findings if f.actionable]
    assert actionable == [], [(f.rule, f.path, f.line, f.message)
                              for f in actionable]
