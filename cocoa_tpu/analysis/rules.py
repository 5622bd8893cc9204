"""jaxlint static rules — this repo's proven JAX failure classes, as AST
checks.

Rules (ids are what ``jaxlint: allow=<rule>`` and the baseline key on):

- ``donation`` — donation audit.  Every ``donate_argnums`` must name a
  positional argument the jitted fn actually consumes; step-shaped jit
  sites in ``solvers/`` must donate their loop-carried state; and the
  PR-2 bug shape — ``x.at[...].op(...) ± x`` on a loop-carried buffer,
  which forces XLA to keep both the old and new buffer live and silently
  defeats donation with a full copy — is an error anywhere in traced
  code (the fix shape: scatter the delta into ``zeros_like`` instead).
- ``host-sync`` — device→host syncs inside traced code: ``float()`` /
  ``int()`` / ``bool()`` on traced values, ``.item()`` / ``.tolist()`` /
  ``jax.device_get``, ``np.asarray``/``np.array`` of traced values, bare
  ``if``/``while`` on a traced value, and host ``print`` of traced
  values.  The sanctioned escape hatch — the ordered ``io_callback``
  telemetry tap (telemetry/events.py) — is allowlisted by construction:
  callbacks passed to ``io_callback``/``pure_callback``/``jax.debug.*``
  run on the host and are never treated as traced.
- ``f64`` — float64 leaks.  Repo policy (DESIGN.md §6): compute dtype is
  f32; float64 belongs only in parity tests and ``evals`` certificate
  math.  Anything else is either a bug or needs a justified
  ``jaxlint: allow=f64`` (host-side exact parsing in the data loaders).
- ``pallas-budget`` — the AST half of the Pallas memory accounting
  (``pallas_budget.py`` holds the numeric half): every ``pl.pallas_call``
  must live in a module that declares a VMEM budget constant and a
  ``*_fits`` gate, and every gate must actually be consulted outside its
  own module (a gate nobody calls protects nothing).
- ``span-hygiene`` — the tracing contract (telemetry/tracing.py): a span
  enter/exit (``span(...)`` context manager or ``@traced`` decorator)
  must never appear inside jit/lax bodies — there it times the TRACE,
  not the execution, and fires once per compile — and span attributes
  must never read traced values (emitting one materializes the array on
  the host: a silent device sync).  Rides the host-sync rule's
  traced-context machinery.
- ``fleet-hygiene`` — the fleet execution contract (solvers/fleet.py):
  a Python-level loop over tenants inside a jit/lax body is an error
  (it unrolls T kernel copies — one compiled round per tenant is
  exactly what the fleet path exists to avoid; the tenant axis rides
  vmap/lax.map), and a per-tenant device fetch inside a host-side
  tenant loop is an error (T host↔device round trips is
  the serial-path cost the fleet amortizes; fetch the stacked result
  once).  Rides the host-sync rule's traced-context machinery.
- ``overlap-hygiene`` — the overlapped-exchange contract
  (parallel/distributed.py, docs/DESIGN.md §15): launching an async
  exchange (``async_host_allgather_bytes`` / ``async_kv_get``) inside
  traced code is an error (a traced value escaping into the collector
  thread races the dispatch that produces it — the runtime twin is
  ``_require_host_bytes``), and an exchange handle that is never
  ``.join()``ed — and never escapes the function (returned, stored, or
  passed on, e.g. into a ``StaleJoinWindow``) — is an error: its
  payload is unsynchronized with every dispatch it crosses, and its
  bounded-KV budget leaks onto a daemon thread nobody will ever
  account.  Rides the host-sync rule's traced-context machinery.
- ``serve-hygiene`` — the serving hot-path contract (cocoa_tpu/serving/,
  docs/DESIGN.md §17): a ``jax.jit`` built inside a hot-path def is an
  error (compile-per-request — executables are built once at startup),
  an array allocation whose shape derives from ``len(...)`` in the hot
  path is an error (request-dependent shapes compile one executable per
  batch size; pad UP to a static bucket), and inside the compiled
  scoring functions a host clock read or ``.block_until_ready()`` is an
  error (it times/syncs the trace, not the request).  Quantization
  belongs at swap time on the host (serving/quantize.py, DESIGN.md
  §20): a narrowing ``.astype(...)`` (bf16/f16/int8/…) or a
  max-of-abs scale compute inside a traced scoring def is an error —
  the compiled path serves a published form, it never re-derives one.
  Rides the host-sync rule's traced-context machinery.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Optional

from cocoa_tpu.analysis.core import Finding, SourceFile

# --- shared AST infrastructure ---------------------------------------------

# callees whose function-valued arguments are traced (control flow and this
# repo's own fan-out combinators)
_TRACED_ARG_CALLEES = {
    "while_loop", "scan", "fori_loop", "cond", "switch", "associative_scan",
    "fanout", "chunk_fanout", "vmap", "pmap", "shard_map", "grad",
    "value_and_grad", "checkpoint", "remat", "custom_vjp", "custom_jvp",
}

# callees whose function-valued arguments run on the HOST (the sanctioned
# device→host escape hatches; the io_callback telemetry tap rides these)
_CALLBACK_CALLEES = {
    "io_callback", "pure_callback", "debug_callback", "callback",
}

_STEP_NAME_RE = re.compile(r"^(round_step|chunk_step|step|run)$")

_NP_MODULES = {"np", "numpy", "onp"}


def _attr_chain(node: ast.AST) -> Optional[str]:
    """'jax.lax.while_loop' for nested Attribute/Name chains, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _callee_tail(call: ast.Call) -> str:
    chain = _attr_chain(call.func)
    return chain.rsplit(".", 1)[-1] if chain else ""


def _is_jax_jit(node: ast.AST) -> bool:
    return _attr_chain(node) in ("jax.jit", "jit")


def _const_int_tuple(node: ast.AST) -> Optional[tuple]:
    """Evaluate a donate_argnums value when it is a literal; None when the
    expression is dynamic (e.g. ``tuple(range(n_state))``)."""
    if isinstance(node, ast.Constant) and isinstance(node.value, int) \
            and not isinstance(node.value, bool):
        return (node.value,)
    if isinstance(node, (ast.Tuple, ast.List)):
        vals = []
        for e in node.elts:
            if isinstance(e, ast.Constant) and isinstance(e.value, int) \
                    and not isinstance(e.value, bool):
                vals.append(e.value)
            else:
                return None
        return tuple(vals)
    return None


class JitSite:
    """One jax.jit application with a resolvable target function."""

    def __init__(self, node: ast.AST, target: Optional[ast.AST],
                 donate: Optional[tuple], has_donate_kw: bool,
                 assigned_name: Optional[str], static_names=frozenset()):
        self.node = node                # the Call / decorated FunctionDef
        self.target = target            # FunctionDef | Lambda | None
        self.donate = donate            # tuple of ints | None (dynamic)
        self.has_donate_kw = has_donate_kw
        self.assigned_name = assigned_name
        self.static_names = static_names  # static_argnames/argnums params


class ModuleIndex(ast.NodeVisitor):
    """One pass over a module: def tables per scope, parent links, jit
    sites, traced-context seeds, callback targets."""

    def __init__(self, src: SourceFile):
        self.src = src
        self.parent_def: dict = {}      # def node -> enclosing def | None
        self.defs: list = []            # every FunctionDef/Lambda
        self.scope_defs: dict = {}      # scope node (def|Module) -> {name: def}
        self.jit_sites: list = []
        self.traced_seeds: set = set()  # def ids seeded traced (lax/combinators)
        self.callback_targets: set = set()  # def ids that run on the host
        self.static_params: dict = {}   # def id -> static (untraced) params
        self._scope_stack: list = []
        self._assign_target: Optional[str] = None

    # -- scope bookkeeping

    def index(self):
        self.scope_defs[self.src.tree] = {}
        self._scope_stack = [self.src.tree]
        self.visit(self.src.tree)
        return self

    def _current_scope(self):
        return self._scope_stack[-1]

    def _resolve(self, name: str) -> Optional[ast.AST]:
        for scope in reversed(self._scope_stack):
            d = self.scope_defs.get(scope, {})
            if name in d:
                return d[name]
        return None

    def _resolve_fn_arg(self, node: ast.AST) -> Optional[ast.AST]:
        if isinstance(node, ast.Name):
            return self._resolve(node.id)
        if isinstance(node, ast.Lambda):
            return node
        if isinstance(node, ast.Call):
            # functools.partial(f, ...) — resolve through to f
            if _callee_tail(node) == "partial" and node.args:
                return self._resolve_fn_arg(node.args[0])
        return None

    # -- visitors

    def visit_FunctionDef(self, node):
        self._handle_def(node, node.name)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        self._handle_def(node, None)

    def _handle_def(self, node, name):
        parent = self._scope_stack[-1]
        self.parent_def[node] = parent if parent is not self.src.tree else None
        self.defs.append(node)
        if name is not None:
            self.scope_defs.setdefault(parent, {})[name] = node
        self.scope_defs.setdefault(node, {})
        # jit decorators
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                site = self._jit_from_decorator(dec, node)
                if site is not None:
                    self.jit_sites.append(site)
        self._scope_stack.append(node)
        for child in ast.iter_child_nodes(node):
            self.visit(child)
        self._scope_stack.pop()

    def _jit_from_decorator(self, dec, fn) -> Optional[JitSite]:
        if _is_jax_jit(dec):
            return self._make_site(fn, fn, None, fn.name)
        if isinstance(dec, ast.Call):
            # @jax.jit(...) or @functools.partial(jax.jit, ...)
            if not (_is_jax_jit(dec.func)
                    or (_callee_tail(dec) == "partial" and dec.args
                        and _is_jax_jit(dec.args[0]))):
                return None
            return self._make_site(fn, fn, dec, fn.name)
        return None

    def _make_site(self, node, target, call: Optional[ast.Call],
                   assigned_name) -> JitSite:
        donate, has_kw = (self._donate_of(call) if call is not None
                          else ((), False))
        static = (self._static_of(call, target) if call is not None
                  else frozenset())
        site = JitSite(node, target, donate=donate, has_donate_kw=has_kw,
                       assigned_name=assigned_name, static_names=static)
        if target is not None and static:
            prev = self.static_params.setdefault(id(target), set())
            prev |= static
        return site

    @staticmethod
    def _donate_of(call: ast.Call):
        for kw in call.keywords:
            if kw.arg in ("donate_argnums", "donate_argnames"):
                if kw.arg == "donate_argnames":
                    return None, True  # names not modeled; presence counts
                return _const_int_tuple(kw.value), True
        return (), False

    @staticmethod
    def _static_of(call: ast.Call, target) -> frozenset:
        """Parameter names the jit treats as compile-time constants —
        host-sync and donation checks must not treat them as traced."""
        names: set = set()
        params = (_params_of(target)
                  if isinstance(target, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)) else [])
        for kw in call.keywords:
            if kw.arg == "static_argnames":
                v = kw.value
                elts = (v.elts if isinstance(v, (ast.Tuple, ast.List))
                        else [v])
                for e in elts:
                    if isinstance(e, ast.Constant) and isinstance(
                            e.value, str):
                        names.add(e.value)
            elif kw.arg == "static_argnums":
                idxs = _const_int_tuple(kw.value) or ()
                for i in idxs:
                    if 0 <= i < len(params):
                        names.add(params[i])
        return frozenset(names)

    def visit_Assign(self, node):
        prev = self._assign_target
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
            self._assign_target = node.targets[0].id
        self.generic_visit(node)
        self._assign_target = prev

    def visit_Call(self, node):
        tail = _callee_tail(node)
        if _is_jax_jit(node.func) and node.args:
            target = self._resolve_fn_arg(node.args[0])
            self.jit_sites.append(self._make_site(
                node, target, node, self._assign_target))
        elif tail in _CALLBACK_CALLEES and node.args:
            t = self._resolve_fn_arg(node.args[0])
            if t is not None:
                self.callback_targets.add(id(t))
        elif tail in _TRACED_ARG_CALLEES:
            for a in list(node.args) + [kw.value for kw in node.keywords]:
                t = self._resolve_fn_arg(a)
                if t is not None:
                    self.traced_seeds.add(id(t))
        self.generic_visit(node)

    # -- traced-context resolution

    def traced_defs(self) -> set:
        """ids of defs whose bodies are traced: jit targets and
        control-flow/combinator callees, plus everything lexically nested
        in a traced def — minus host-callback targets."""
        traced = set(self.traced_seeds)
        for site in self.jit_sites:
            if site.target is not None:
                traced.add(id(site.target))
        traced -= self.callback_targets
        changed = True
        while changed:
            changed = False
            for d in self.defs:
                if id(d) in traced or id(d) in self.callback_targets:
                    continue
                p = self.parent_def.get(d)
                if p is not None and id(p) in traced:
                    traced.add(id(d))
                    changed = True
        return traced

    def traced_params(self, node, traced: set) -> set:
        """Parameter names of ``node`` and every TRACED enclosing def —
        the first-order 'this value is traced here' name set.  The walk
        stops at the first non-traced ancestor: a host-side builder's
        params (mesh, params, flags) are trace-time constants, and
        ``float(params.lam)`` in a kernel it builds is legal."""
        names: set = set()
        d = node
        while d is not None:
            a = d.args
            for arg in (a.posonlyargs + a.args + a.kwonlyargs
                        + ([a.vararg] if a.vararg else [])
                        + ([a.kwarg] if a.kwarg else [])):
                names.add(arg.arg)
            names -= self.static_params.get(id(d), set())
            d = self.parent_def.get(d)
            if d is not None and id(d) not in traced:
                break
        return names


def _params_of(fn) -> list:
    a = fn.args
    return [arg.arg for arg in a.posonlyargs + a.args]


_STATIC_ATTRS = {"shape", "ndim", "size", "dtype", "itemsize", "sharding"}


def _mentions(expr: ast.AST, names: set) -> bool:
    """Whether ``expr`` reads a traced VALUE from ``names`` — mentions
    under static metadata attributes (``x.shape``, ``x.dtype``, ...) are
    trace-time Python and don't count."""
    def walk(node):
        if isinstance(node, ast.Attribute) and node.attr in _STATIC_ATTRS:
            return False
        if isinstance(node, ast.Name) and node.id in names:
            return True
        return any(walk(c) for c in ast.iter_child_nodes(node))

    return walk(expr)


def _nearest_def(node, parents) -> Optional[ast.AST]:
    p = parents.get(node)
    while p is not None and not isinstance(
            p, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        p = parents.get(p)
    return p


def _build_parents(tree) -> dict:
    parents = {}
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            parents[child] = node
    return parents


# --- rule: donation ---------------------------------------------------------


def _at_update_root(expr: ast.AST) -> Optional[str]:
    """The name X when ``expr`` is an ``X.at[...].meth(...)`` chain (with
    any number of trailing method calls), else None."""
    node = expr
    while True:
        if isinstance(node, ast.Call):
            node = node.func
        elif isinstance(node, ast.Attribute):
            if node.attr == "at" and isinstance(node.value, ast.Name):
                return node.value.id
            node = node.value
        elif isinstance(node, ast.Subscript):
            node = node.value
        else:
            return None


def check_donation(src: SourceFile, index: ModuleIndex) -> list:
    findings = []
    in_solvers = "/solvers/" in f"/{src.path}"

    for site in index.jit_sites:
        fn = site.target
        loc = fn if fn is not None else site.node
        if fn is None:
            continue
        params = _params_of(fn) if not isinstance(fn, ast.Lambda) else \
            [a.arg for a in fn.args.args]
        name = site.assigned_name or getattr(fn, "name", None) or "<lambda>"
        if (in_solvers and not site.has_donate_kw
                and _STEP_NAME_RE.match(name or "")):
            findings.append(Finding(
                rule="donation", severity="error", path=src.path,
                line=loc.lineno, col=loc.col_offset,
                message=(
                    f"jit step `{name}` in solvers/ donates nothing — "
                    f"loop-carried solver state in the drive* ladder must "
                    f"ride donate_argnums (every round otherwise pays a "
                    f"full-state copy in HBM)")))
        if site.donate:
            for idx in site.donate:
                if idx >= len(params) or idx < 0:
                    findings.append(Finding(
                        rule="donation", severity="error", path=src.path,
                        line=loc.lineno, col=loc.col_offset,
                        message=(
                            f"donate_argnums index {idx} is out of range "
                            f"for `{name}` ({len(params)} positional "
                            f"args) — donation silently misses")))
                    continue
                pname = params[idx]
                body = fn.body if isinstance(fn.body, list) else [fn.body]
                used = sum(
                    1 for stmt in body for n in ast.walk(stmt)
                    if isinstance(n, ast.Name) and n.id == pname)
                if used == 0:
                    findings.append(Finding(
                        rule="donation", severity="error", path=src.path,
                        line=loc.lineno, col=loc.col_offset,
                        message=(
                            f"`{name}` donates arg {idx} (`{pname}`) but "
                            f"never reads it — the donated buffer cannot "
                            f"be the one the output aliases, so the "
                            f"donation is a no-op")))

    # the PR-2 shape, anywhere traced: X.at[...].op(...) ± X forces XLA to
    # keep old and new X live at once — the output cannot alias the input
    # buffer, so donation silently degrades to a full copy
    traced = index.traced_defs()
    parents = _build_parents(src.tree)
    for d in index.defs:
        if id(d) not in traced:
            continue
        pnames = index.traced_params(d, traced)
        body = d.body if isinstance(d.body, list) else [d.body]
        for stmt in body:
            for node in ast.walk(stmt):
                nd = _nearest_def(node, parents)
                if nd is not d:
                    continue
                if not isinstance(node, ast.BinOp) or not isinstance(
                        node.op, (ast.Add, ast.Sub)):
                    continue
                for a, b in ((node.left, node.right),
                             (node.right, node.left)):
                    x = _at_update_root(a)
                    if x is not None and x in pnames and _mentions(
                            b, {x}):
                        findings.append(Finding(
                            rule="donation", severity="error",
                            path=src.path, line=node.lineno,
                            col=node.col_offset,
                            message=(
                                f"`{x}.at[...] ± {x}` keeps both the old "
                                f"and new `{x}` live — donation of the "
                                f"buffer silently becomes a full copy "
                                f"(the PR-2 α bug shape); scatter the "
                                f"delta into `jnp.zeros_like({x})` "
                                f"instead")))
                        break
    return findings


# --- rule: host-sync --------------------------------------------------------

_SYNC_METHODS = {"item", "tolist"}


def check_host_sync(src: SourceFile, index: ModuleIndex) -> list:
    findings = []
    traced = index.traced_defs()
    parents = _build_parents(src.tree)

    def flag(node, msg, severity="error"):
        findings.append(Finding(
            rule="host-sync", severity=severity, path=src.path,
            line=node.lineno, col=node.col_offset, message=msg))

    for d in index.defs:
        if id(d) not in traced:
            continue
        pnames = index.traced_params(d, traced)
        body = d.body if isinstance(d.body, list) else [d.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if _nearest_def(node, parents) is not d:
                    continue  # nested defs are visited as themselves
                if isinstance(node, ast.Call):
                    chain = _attr_chain(node.func) or ""
                    tail = chain.rsplit(".", 1)[-1]
                    if isinstance(node.func, ast.Attribute) \
                            and node.func.attr in _SYNC_METHODS:
                        flag(node,
                             f"`.{node.func.attr}()` inside traced code is "
                             f"a device→host sync per call — fetch once on "
                             f"the host after the dispatch, or route "
                             f"through the io_callback tap")
                    elif chain in ("jax.device_get", "device_get"):
                        flag(node,
                             "`jax.device_get` inside traced code syncs "
                             "the device every call — hoist the fetch to "
                             "the driver")
                    elif tail in ("asarray", "array") and \
                            chain.split(".")[0] in _NP_MODULES and \
                            any(_mentions(a, pnames) for a in node.args):
                        flag(node,
                             f"`{chain}` of a traced value materializes it "
                             f"on the host (silent sync + recompile "
                             f"hazard) — use jnp, or fetch after the "
                             f"dispatch")
                    elif isinstance(node.func, ast.Name) and \
                            node.func.id in ("float", "int", "bool") and \
                            node.args and _mentions(node.args[0], pnames):
                        flag(node,
                             f"`{node.func.id}()` of a traced value blocks "
                             f"on the device (one host round trip per "
                             f"call) — keep it as "
                             f"an array, or fetch once after the dispatch")
                    elif isinstance(node.func, ast.Name) and \
                            node.func.id == "print" and \
                            any(_mentions(a, pnames) for a in node.args):
                        flag(node,
                             "`print` of a traced value syncs and runs "
                             "only at trace time — use jax.debug.print "
                             "or the telemetry event stream",
                             severity="warning")
                elif isinstance(node, (ast.If, ast.While)):
                    test = node.test
                    if isinstance(test, ast.UnaryOp) and isinstance(
                            test.op, ast.Not):
                        test = test.operand
                    if isinstance(test, ast.Name) and test.id in pnames:
                        flag(node,
                             f"`if {test.id}:` on a traced value is an "
                             f"implicit bool() sync (TracerBoolConversion "
                             f"at best, a silent host round-trip at "
                             f"worst) — use lax.cond/jnp.where")
    return findings


# --- rule: f64 --------------------------------------------------------------

# float64 is policy-legal only here (DESIGN.md §6): exact certificate
# arithmetic and the parity tests.  tests/ is outside the scan surface.
_F64_ALLOWED_PREFIXES = ("cocoa_tpu/evals/",)


def check_f64(src: SourceFile, index: ModuleIndex) -> list:
    if src.path.startswith(_F64_ALLOWED_PREFIXES):
        return []
    findings = []

    def flag(node, what):
        findings.append(Finding(
            rule="f64", severity="error", path=src.path, line=node.lineno,
            col=node.col_offset,
            message=(
                f"{what} — repo numerics policy keeps float64 in parity "
                f"tests and evals/ certificate math only (DESIGN.md §6); "
                f"fix the dtype or add a justified `jaxlint: allow=f64`")))

    for node in ast.walk(src.tree):
        if isinstance(node, ast.Attribute) and node.attr == "float64":
            root = _attr_chain(node)
            if root:
                flag(node, f"`{root}`")
        elif isinstance(node, ast.Call):
            chain = _attr_chain(node.func) or ""
            args = list(node.args) + [kw.value for kw in node.keywords]
            if chain.endswith("config.update") and node.args and \
                    isinstance(node.args[0], ast.Constant) and \
                    node.args[0].value == "jax_enable_x64":
                flag(node, "`jax_enable_x64` flipped at runtime")
            elif any(isinstance(a, ast.Constant) and a.value == "float64"
                     for a in args):
                flag(node, '"float64" dtype argument')
    return findings


# --- rule: pallas-budget (AST half) ----------------------------------------


def check_pallas_budget_ast(src: SourceFile, index: ModuleIndex,
                            all_sources: dict) -> list:
    """Every ``pl.pallas_call`` module must declare a VMEM budget constant
    and a ``*_fits`` gate; every gate must be consulted outside its own
    module.  The numeric half (estimates vs budgets vs physical caps)
    lives in pallas_budget.py."""
    calls = [n for n in ast.walk(src.tree)
             if isinstance(n, ast.Call)
             and (_attr_chain(n.func) or "").endswith("pallas_call")]
    if not calls:
        return []
    findings = []
    budget_names = set()
    fits_names = set()
    for node in ast.walk(src.tree):
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if isinstance(t, ast.Name) and "BUDGET" in t.id:
                    budget_names.add(t.id)
        elif isinstance(node, ast.FunctionDef) and (
                # both gate spellings this repo uses: boolean *_fits
                # gates, and pick_* sizers whose 0 return means "does
                # not fit" (pallas_sdca's unroll/interleave pickers)
                node.name.endswith("_fits") or node.name.startswith(
                    "pick_")):
            fits_names.add(node.name)
    if not budget_names:
        findings.append(Finding(
            rule="pallas-budget", severity="error", path=src.path,
            line=calls[0].lineno, col=calls[0].col_offset,
            message=("module calls pl.pallas_call but declares no "
                     "*_BUDGET constant — SMEM/VMEM overflows become "
                     "runtime surprises instead of lint errors")))
    if not fits_names:
        findings.append(Finding(
            rule="pallas-budget", severity="error", path=src.path,
            line=calls[0].lineno, col=calls[0].col_offset,
            message=("module calls pl.pallas_call but exposes no *_fits "
                     "gate — dispatch cannot account the kernel's "
                     "memory before committing to it")))
    # gates must be consulted by the dispatch layer, not just declared
    for gate in sorted(fits_names):
        consulted = False
        for other_path, other in all_sources.items():
            if other_path == src.path:
                continue
            for n in ast.walk(other.tree):
                if isinstance(n, ast.Name) and n.id == gate:
                    consulted = True
                    break
                if isinstance(n, ast.Attribute) and n.attr == gate:
                    consulted = True
                    break
            if consulted:
                break
        if not consulted:
            gate_def = next(
                n for n in ast.walk(src.tree)
                if isinstance(n, ast.FunctionDef) and n.name == gate)
            findings.append(Finding(
                rule="pallas-budget", severity="warning", path=src.path,
                line=gate_def.lineno, col=gate_def.col_offset,
                message=(f"fits gate `{gate}` is never consulted outside "
                         f"{os.path.basename(src.path)} — a gate the "
                         f"dispatch does not call protects nothing")))
    return findings


# --- rule: span-hygiene -----------------------------------------------------

# the tracing surface (telemetry/tracing.py): the context-manager forms
# (a span, and a cold span, which besides reads every device's allocator
# and waits for what it made), module-level or on a Tracer instance
_SPAN_CALLEES = {"span", "cold_span"}

# receiver names that identify the tracing module/object — required for
# the attribute form so ``re.Match.span()`` and other unrelated ``span``
# methods in traced host code are never flagged
_TRACING_RECEIVERS = ("tracing", "tracer")


def _is_span_call(node: ast.Call) -> Optional[str]:
    """'span'/'cold_span' when ``node`` is a TRACING call, else None.
    Matches ``tracing.span(...)`` / ``_tracing.span(...)`` /
    ``get_tracer().span(...)`` (receiver names the tracing surface), a
    bare imported ``span("phase", ...)`` (string phase argument — what
    distinguishes it from e.g. ``m.span()``)."""
    tail = _callee_tail(node)
    if tail not in _SPAN_CALLEES:
        return None
    phase_is_str = bool(node.args) and isinstance(
        node.args[0], ast.Constant) and isinstance(node.args[0].value, str)
    if isinstance(node.func, ast.Name):
        return tail if phase_is_str else None
    if isinstance(node.func, ast.Attribute):
        recv = node.func.value
        chain = (_attr_chain(recv) or "").lower()
        if any(r in chain for r in _TRACING_RECEIVERS):
            return tail
        # get_tracer().span(...) — receiver is a call to get_tracer
        if isinstance(recv, ast.Call) and \
                _callee_tail(recv) == "get_tracer":
            return tail
        return tail if phase_is_str else None
    return None


def check_span_hygiene(src: SourceFile, index: ModuleIndex) -> list:
    """Span enter/exit must stay on the host (telemetry/tracing.py
    contract): inside jit/lax bodies a span is a trace-time no-op at
    best (it would time the TRACE, not the execution, and emit once per
    compile instead of once per run) and a host sync at worst (a traced
    value in the span attrs materializes on the host at emit).  Reuses
    the host-sync machinery's traced-context resolution: jit targets,
    control-flow/combinator callees, everything lexically nested —
    minus host-callback targets (an io_callback target may span freely;
    it runs on the host by construction)."""
    findings = []
    traced = index.traced_defs()
    parents = _build_parents(src.tree)

    def flag(node, msg):
        findings.append(Finding(
            rule="span-hygiene", severity="error", path=src.path,
            line=node.lineno, col=node.col_offset, message=msg))

    for d in index.defs:
        if id(d) not in traced:
            continue
        body = d.body if isinstance(d.body, list) else [d.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if _nearest_def(node, parents) is not d:
                    continue
                if not isinstance(node, ast.Call):
                    continue
                form = _is_span_call(node)
                if form is None:
                    continue
                flag(node,
                     f"tracing `{form}(...)` inside traced code — a span "
                     f"enter/exit in a jit/lax body times the trace, not "
                     f"the execution, and fires once per COMPILE; hoist "
                     f"it to the host boundary (the dispatch/fetch site, "
                     f"solvers/base.py pattern)")
                continue
    # span attrs that read traced values from an ENCLOSING traced scope:
    # a host-side closure built inside a kernel builder may legally span,
    # but passing a traced array as an attribute materializes it on the
    # host at emit time (a silent device sync on the hot path)
    for d in index.defs:
        if id(d) in traced:
            continue  # already flagged wholesale above
        p = index.parent_def.get(d)
        if p is None or id(p) not in traced:
            continue
        pnames = index.traced_params(p, traced)
        body = d.body if isinstance(d.body, list) else [d.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call) or \
                        _is_span_call(node) is None:
                    continue
                args = list(node.args) + [kw.value for kw in node.keywords]
                if any(_mentions(a, pnames) for a in args):
                    flag(node,
                         "span attribute reads a traced value — emitting "
                         "it materializes the array on the host (silent "
                         "device sync); tag scalars the host already "
                         "holds, or fetch after the dispatch")
    return findings


# --- rule: overlap-hygiene ---------------------------------------------------

# the async-exchange surface (parallel/distributed.py)
_EXCHANGE_CALLEES = {"async_host_allgather_bytes", "async_kv_get"}


def check_overlap_hygiene(src: SourceFile, index: ModuleIndex) -> list:
    """The overlapped-exchange contract (see the module docstring):

    1. launching an async exchange inside traced code is an error —
       traced values must not escape into the collector thread (the
       runtime twin is ``distributed._require_host_bytes``, which only
       accepts host bytes; this catches the shape statically, before a
       run ever reaches it);
    2. a handle bound to a local name that is never ``.join()``ed and
       never escapes (returned/yielded, passed to a call — e.g. a
       ``StaleJoinWindow.admit`` — stored into a container/attribute/
       subscript, or re-exported) is an error: the exchange's payload
       is then read by nobody and synchronized with nothing, so any
       super-block dispatch it crosses runs against an un-joined
       exchange."""
    findings = []
    traced = index.traced_defs()
    parents = _build_parents(src.tree)

    def flag(node, msg):
        findings.append(Finding(
            rule="overlap-hygiene", severity="error", path=src.path,
            line=node.lineno, col=node.col_offset, message=msg))

    # (1) async launch inside traced code
    for d in index.defs:
        if id(d) not in traced:
            continue
        body = d.body if isinstance(d.body, list) else [d.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if _nearest_def(node, parents) is not d:
                    continue
                if isinstance(node, ast.Call) and \
                        _callee_tail(node) in _EXCHANGE_CALLEES:
                    flag(node,
                         f"`{_callee_tail(node)}` inside traced code — "
                         f"traced values must not escape into the "
                         f"exchange thread (the collector would race the "
                         f"dispatch producing them); launch the exchange "
                         f"at the host boundary and pass host bytes "
                         f"(np.asarray(x).tobytes())")

    # (2) handles that are never joined and never escape, per scope
    scopes = [src.tree] + list(index.defs)
    for scope in scopes:
        if scope is not src.tree and id(scope) in traced:
            continue  # already flagged wholesale by (1)
        body = scope.body if isinstance(getattr(scope, "body", None), list) \
            else [scope.body] if hasattr(scope, "body") else []
        handles: dict = {}   # name -> the Assign node that bound it
        uses: dict = {}      # name -> [non-binding Name mentions]
        joined: set = set()
        for stmt in body:
            for node in ast.walk(stmt):
                nd = _nearest_def(node, parents)
                at_scope = (nd is scope or (scope is src.tree
                                            and nd is None))
                if not at_scope:
                    continue
                if isinstance(node, ast.Assign) and len(node.targets) == 1 \
                        and isinstance(node.targets[0], ast.Name) \
                        and isinstance(node.value, ast.Call) \
                        and _callee_tail(node.value) in _EXCHANGE_CALLEES:
                    handles[node.targets[0].id] = node
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr == "join" \
                        and isinstance(node.func.value, ast.Name):
                    joined.add(node.func.value.id)
        if not handles:
            continue
        # any OTHER mention of the name (beyond its binding target and
        # the .join receiver) counts as an escape — conservatively: a
        # handle handed to anyone else is their responsibility to join
        for stmt in body:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Name) or \
                        not isinstance(node.ctx, ast.Load):
                    continue
                if node.id not in handles:
                    continue
                p = parents.get(node)
                if isinstance(p, ast.Attribute) and p.attr == "join":
                    continue
                uses.setdefault(node.id, []).append(node)
        for name, assign in handles.items():
            if name in joined or uses.get(name):
                continue
            flag(assign,
                 f"exchange handle `{name}` is never joined and never "
                 f"escapes this scope — its payload is read by nobody "
                 f"and any super-block dispatch it crosses runs against "
                 f"an un-joined exchange; call `{name}.join()` at the "
                 f"barrier (or hand it to a StaleJoinWindow)")
    return findings


# --- rule: fleet-hygiene -----------------------------------------------------

# names that identify tenant/fleet iteration (the --fleet surface,
# solvers/fleet.py): matched against a for-loop's target and iterable
_FLEET_NAME_RE = re.compile(r"(^|_)(tenants?|fleet|lanes?)(_|$|\d)",
                            re.IGNORECASE)

# host-side device-fetch callees: each one synchronizes (or stages) a
# device value — paid PER TENANT when it sits inside a tenant loop,
# which is exactly the per-model round-trip cost the fleet path exists
# to amortize away
_FLEET_FETCH_CALLEES = {"asarray", "array", "device_get",
                        "block_until_ready", "item", "tolist"}


def _fleet_named(node: ast.For) -> bool:
    """Whether a for-loop iterates over tenants/the fleet — its target
    or iterable names say so."""
    names = []
    for sub in ast.walk(node.target):
        if isinstance(sub, ast.Name):
            names.append(sub.id)
    for sub in ast.walk(node.iter):
        if isinstance(sub, ast.Name):
            names.append(sub.id)
        elif isinstance(sub, ast.Attribute):
            names.append(sub.attr)
    return any(_FLEET_NAME_RE.search(n) for n in names)


def check_fleet_hygiene(src: SourceFile, index: ModuleIndex) -> list:
    """The fleet execution contract (solvers/fleet.py, docs/DESIGN.md
    §16): the whole point of the fleet path is ONE dispatch for T
    tenants, so

    1. a Python-level ``for`` loop over tenants inside a jit/lax body is
       an error — it unrolls T copies of the kernel into the graph
       (compile time and code size scale with T, and a manifest change
       retraces everything); the tenant axis rides ``vmap``/``lax.map``
       (parallel/fanout.lane_fanout);
    2. a per-tenant device fetch (``np.asarray`` / ``jax.device_get`` /
       ``.block_until_ready()`` / ``.item()`` / ``.tolist()``) inside a
       HOST-side tenant loop is an error — T host↔device round trips
       is the serial-path cost the fleet amortizes;
       fetch the stacked result ONCE before the loop (the
       run_cocoa_fleet pattern).

    Rides the host-sync rule's traced-context machinery."""
    findings = []
    traced = index.traced_defs()
    parents = _build_parents(src.tree)

    def flag(node, msg):
        findings.append(Finding(
            rule="fleet-hygiene", severity="error", path=src.path,
            line=node.lineno, col=node.col_offset, message=msg))

    # (1) tenant loops inside traced code
    for d in index.defs:
        if id(d) not in traced:
            continue
        body = d.body if isinstance(d.body, list) else [d.body]
        for stmt in body:
            for node in ast.walk(stmt):
                if _nearest_def(node, parents) is not d:
                    continue
                if isinstance(node, ast.For) and _fleet_named(node):
                    flag(node,
                         "Python loop over tenants inside traced code — "
                         "this unrolls T kernel copies into the graph "
                         "(one compiled round per tenant is exactly what "
                         "the fleet path exists to avoid); batch the "
                         "tenant axis with vmap/lax.map "
                         "(parallel/fanout.lane_fanout)")

    # (2) per-tenant fetches inside host-side tenant loops
    scopes = [src.tree] + [d for d in index.defs if id(d) not in traced]
    for scope in scopes:
        body = scope.body if isinstance(getattr(scope, "body", None), list) \
            else [scope.body] if hasattr(scope, "body") else []
        for stmt in body:
            for node in ast.walk(stmt):
                nd = _nearest_def(node, parents)
                at_scope = (nd is scope or (scope is src.tree
                                            and nd is None))
                if not at_scope or not isinstance(node, ast.For) \
                        or not _fleet_named(node):
                    continue
                for sub in ast.walk(node):
                    if isinstance(sub, ast.Call) and \
                            _callee_tail(sub) in _FLEET_FETCH_CALLEES:
                        flag(sub,
                             f"per-tenant `{_callee_tail(sub)}` inside a "
                             f"fleet/tenant loop — T device round-trips "
                             f"is the serial-path cost the fleet "
                             f"amortizes away; fetch the stacked result "
                             f"ONCE before the loop (the run_cocoa_fleet "
                             f"pattern)")
    return findings


# --- rule: serve-hygiene -----------------------------------------------------

# the rule applies to the serving subsystem only (and to fixtures that
# put themselves under a serving/ path)
_SERVING_PATH_RE = re.compile(r"(^|/)serving/")

# defs that legitimately BUILD executables / static buffers: module
# level, construction, and explicit build/warmup helpers — everything
# else in a serving module is the hot path
_SERVE_BUILDER_RE = re.compile(r"^(__init__|_?build\w*|make_\w+|warmup)$")

# np/jnp array constructors whose shape argument the rule inspects
_SERVE_ALLOC_CALLEES = {"zeros", "ones", "empty", "full"}

# host clock reads: inside traced code they read the TRACE's wall clock
# once per compile, not the request's
_SERVE_CLOCK_CHAINS = {"time.time", "time.monotonic",
                       "time.perf_counter", "time.perf_counter_ns",
                       "time.monotonic_ns"}

# dtypes whose appearance as an `.astype(...)` target inside a TRACED
# scoring def marks in-graph quantization.  All narrowing happens on
# the host at swap time (serving/quantize.py) where the error
# certificate can see it; the compiled path only ever consumes the
# published form.  Widening casts (float32/int32/uint32/…) and
# bitcast_convert_type (the packed-bf16 reinterpretation) stay legal.
_SERVE_NARROW_DTYPES = {"bfloat16", "float16", "int8", "uint8",
                        "int16", "uint16", "int4", "uint4",
                        "float8_e4m3fn", "float8_e5m2"}

# max/amax spellings that, applied over an abs(), form the symmetric
# quantization scale (max|w|) — the other half of an in-graph quantize
_SERVE_SCALE_REDUCERS = {"max", "amax"}


def _narrow_dtype_name(expr: ast.AST) -> Optional[str]:
    """The narrow dtype an ``.astype(...)`` argument names, else None.
    Recognizes attribute spellings (``jnp.bfloat16``,
    ``ml_dtypes.bfloat16``, ``np.int8``) and string literals."""
    chain = _attr_chain(expr)
    if chain:
        tail = chain.rsplit(".", 1)[-1]
        if tail in _SERVE_NARROW_DTYPES:
            return tail
    if isinstance(expr, ast.Constant) and isinstance(expr.value, str) \
            and expr.value in _SERVE_NARROW_DTYPES:
        return expr.value
    return None


def _contains_abs_call(expr: ast.AST) -> bool:
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Call) and \
                _callee_tail(sub) in ("abs", "absolute"):
            return True
    return False


def _contains_len_call(expr: ast.AST) -> bool:
    for sub in ast.walk(expr):
        if isinstance(sub, ast.Call) and \
                isinstance(sub.func, ast.Name) and sub.func.id == "len":
            return True
    return False


def check_serve_hygiene(src: SourceFile, index: ModuleIndex) -> list:
    """The serving hot-path contract (cocoa_tpu/serving/, docs/DESIGN.md
    §17): the scoring path must compile once per static bucket and never
    sync per request.

    1. ``jax.jit`` built inside a hot-path def is an error — a jit
       created per call builds a fresh executable per request (the
       compile-per-request leak the one-compile-per-bucket pin exists
       to prevent); build it once at startup (``__init__`` / ``build_*``
       / ``warmup`` are the sanctioned builder scopes).
    2. an array allocation whose shape derives from ``len(...)`` inside
       a hot-path def is an error — a request-dependent shape retraces
       and recompiles on every distinct batch size; pad UP to a static
       bucket (serving/scorer.pick_bucket) instead.
    3. inside TRACED defs (the compiled scoring functions): a host
       clock read (``time.time``/``monotonic``/``perf_counter``) or a
       ``.block_until_ready()`` is an error — it times (or syncs) the
       TRACE, once per compile, not the request; latency accounting
       belongs at the host boundary (the batcher's spans).  Rides the
       host-sync rule's traced-context machinery.
    4. inside TRACED defs: a narrowing ``.astype(...)`` (bf16 / f16 /
       int8 / …) or a max-of-abs scale compute is an error — in-graph
       quantization bypasses the per-swap error certificate and burns
       the cast into every dispatch.  Quantize ONCE on the host at
       swap time (serving/quantize.quantize, DESIGN.md §20); the
       compiled scorer consumes the published form.  Widening casts
       (``astype(jnp.float32)`` on a dequantized gather) and
       ``lax.bitcast_convert_type`` (the packed-bf16 view) stay legal.
    """
    if not _SERVING_PATH_RE.search(src.path.replace(os.sep, "/")):
        return []
    findings = []
    traced = index.traced_defs()
    parents = _build_parents(src.tree)

    def flag(node, msg):
        findings.append(Finding(
            rule="serve-hygiene", severity="error", path=src.path,
            line=node.lineno, col=node.col_offset, message=msg))

    def hot_path(d) -> bool:
        name = getattr(d, "name", "")
        return not _SERVE_BUILDER_RE.match(name or "")

    for d in index.defs:
        body = d.body if isinstance(d.body, list) else [d.body]
        is_traced = id(d) in traced
        is_hot = hot_path(d)
        for stmt in body:
            for node in ast.walk(stmt):
                if _nearest_def(node, parents) is not d:
                    continue
                if not isinstance(node, ast.Call):
                    continue
                if is_hot and _is_jax_jit(node.func):
                    flag(node,
                         "jit built in the serving hot path — every "
                         "call builds (and compiles) a fresh "
                         "executable; build the jit once at startup "
                         "(__init__/build_*/warmup) and call the built "
                         "function per batch")
                elif is_hot and _callee_tail(node) in \
                        _SERVE_ALLOC_CALLEES and node.args and \
                        (_attr_chain(node.func) or "").split(".")[0] in \
                        (_NP_MODULES | {"jnp"}) and \
                        _contains_len_call(node.args[0]):
                    flag(node,
                         f"request-dependent shape in the serving hot "
                         f"path — `{_callee_tail(node)}` sized by "
                         f"`len(...)` compiles one executable per "
                         f"distinct batch size; pad UP to a static "
                         f"bucket (serving/scorer.pick_bucket)")
                if is_traced:
                    chain = _attr_chain(node.func) or ""
                    if chain in _SERVE_CLOCK_CHAINS:
                        flag(node,
                             f"`{chain}()` inside the compiled scoring "
                             f"path reads the clock at TRACE time, "
                             f"once per compile — time requests at the "
                             f"host boundary (the batcher's "
                             f"serve_admit/serve_score spans)")
                    elif isinstance(node.func, ast.Attribute) and \
                            node.func.attr == "block_until_ready":
                        flag(node,
                             "`.block_until_ready()` inside the "
                             "compiled scoring path is a device sync "
                             "per call — fetch once on the host after "
                             "the dispatch (the batcher's single "
                             "intended_fetch)")
                    elif isinstance(node.func, ast.Attribute) and \
                            node.func.attr == "astype" and node.args \
                            and _narrow_dtype_name(node.args[0]):
                        flag(node,
                             f"narrowing `.astype("
                             f"{_narrow_dtype_name(node.args[0])})` "
                             f"inside the compiled scoring path — "
                             f"in-graph quantization bypasses the "
                             f"per-swap error certificate and re-casts "
                             f"on every dispatch; quantize ONCE on the "
                             f"host at swap time "
                             f"(serving/quantize.quantize) and publish "
                             f"the narrow form")
                    elif (node.func.attr if isinstance(
                            node.func, ast.Attribute) else
                            _callee_tail(node)) in \
                            _SERVE_SCALE_REDUCERS \
                            and (any(_contains_abs_call(a)
                                     for a in node.args)
                                 or (isinstance(node.func,
                                                ast.Attribute)
                                     and _contains_abs_call(
                                         node.func.value))):
                        flag(node,
                             "max-of-abs inside the compiled scoring "
                             "path — this is the symmetric "
                             "quantization scale (max|w|) being "
                             "derived in-graph, per dispatch; the "
                             "scale is computed once on the host at "
                             "swap time (serving/quantize.quantize) "
                             "and published alongside the model")
    return findings


# --- registry ---------------------------------------------------------------

RULES = ("donation", "host-sync", "f64", "pallas-budget", "span-hygiene",
         "overlap-hygiene", "fleet-hygiene",
         "serve-hygiene")


def run_static_rules(sources: dict) -> list:
    """Run every AST rule over {path: SourceFile}; returns findings."""
    findings = []
    for path, src in sources.items():
        index = ModuleIndex(src).index()
        findings += check_donation(src, index)
        findings += check_host_sync(src, index)
        findings += check_f64(src, index)
        findings += check_pallas_budget_ast(src, index, sources)
        findings += check_span_hygiene(src, index)
        findings += check_overlap_hygiene(src, index)
        findings += check_fleet_hygiene(src, index)
        findings += check_serve_hygiene(src, index)
    return findings
