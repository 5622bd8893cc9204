"""Pluggable-loss layer (ops/losses.py): analytic identities, the
Fenchel-Young inequality behind the duality-gap certificate, coordinate-step
optimality, and end-to-end convergence of every solver under each loss.

The reference is hinge-only; these losses are the extension BASELINE.md's
evaluation configs call for (the reference's local-solver boundary is
explicitly designed for swapping objectives — README.md:14, CoCoA.scala:13-14).
"""

import jax.numpy as jnp
import numpy as np
import pytest

from cocoa_tpu.config import DebugParams, Params
from cocoa_tpu.data.sharding import shard_dataset
from cocoa_tpu.evals import objectives
from cocoa_tpu.ops import losses
from cocoa_tpu.solvers import run_cocoa, run_dist_gd, run_minibatch_cd, run_sgd

ALL = list(losses.LOSSES)
S = 0.7  # smooth_hinge smoothing used throughout


def _params(data, **kw):
    kw.setdefault("num_rounds", 30)
    kw.setdefault("local_iters", 24)
    kw.setdefault("lam", 0.01)
    return Params(n=data.n, **kw)


def _debug(**kw):
    kw.setdefault("debug_iter", 5)
    kw.setdefault("seed", 3)
    return DebugParams(**kw)


# ---------------------------------------------------------------- analytic

@pytest.mark.parametrize("loss", ALL)
def test_dual_term_finite_at_box_corners_f32(loss):
    # regression: in f32 an eps-clip rounds 1−1e-12 to exactly 1.0, and the
    # logistic entropy hit 0·log(0) = NaN once a coordinate saturated —
    # poisoning the duality gap and any --gapTarget early stop
    a = jnp.asarray([0.0, 1.0, 0.5], dtype=jnp.float32)
    out = losses.dual_term(loss, a, S)
    assert out.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(out)))
    if loss == "logistic":  # entropy is exactly 0 at both corners
        np.testing.assert_allclose(np.asarray(out[:2]), [0.0, 0.0])


@pytest.mark.parametrize("loss", ALL)
def test_grad_factor_is_negative_derivative(loss):
    """g(z) = −ℓ'(z) by central finite differences (away from kinks)."""
    z = np.array([-2.3, -0.4, 0.1, 0.77, 1.9, 3.2])
    if loss == "hinge":
        z = z[np.abs(z - 1.0) > 1e-3]  # kink at z=1
    if loss == "smooth_hinge":
        z = z[(np.abs(z - 1.0) > 1e-3) & (np.abs(z - (1.0 - S)) > 1e-3)]
    eps = 1e-6
    lp = np.asarray(losses.primal(loss, jnp.asarray(z + eps), smoothing=S))
    lm = np.asarray(losses.primal(loss, jnp.asarray(z - eps), smoothing=S))
    g = np.asarray(losses.grad_factor(loss, jnp.asarray(z), smoothing=S))
    np.testing.assert_allclose(-(lp - lm) / (2 * eps), g, atol=1e-5)
    assert np.all(g >= 0.0) and np.all(g <= 1.0)


def test_smooth_hinge_limits():
    """s→0 recovers the hinge everywhere; value sits between the hinge and
    the hinge minus s/2."""
    z = jnp.asarray(np.linspace(-3, 3, 61))
    hinge = np.asarray(losses.primal("hinge", z))
    tiny = np.asarray(losses.primal("smooth_hinge", z, smoothing=1e-9))
    np.testing.assert_allclose(tiny, hinge, atol=1e-8)
    sm = np.asarray(losses.primal("smooth_hinge", z, smoothing=S))
    assert np.all(sm <= hinge + 1e-12)
    assert np.all(sm >= hinge - 0.5 * S - 1e-12)


@pytest.mark.parametrize("loss", ALL)
def test_fenchel_young(loss):
    """ℓ(z) − (−ℓ*(−α)) + z·α ≥ 0 for all α ∈ [0,1] — the inequality that
    makes the duality gap a valid (non-negative) certificate."""
    rng = np.random.default_rng(0)
    z = jnp.asarray(rng.normal(size=200) * 3)
    a = jnp.asarray(rng.random(200))
    lhs = (np.asarray(losses.primal(loss, z, smoothing=S))
           - np.asarray(losses.dual_term(loss, a, smoothing=S))
           + np.asarray(z) * np.asarray(a))
    assert np.all(lhs >= -1e-10)


@pytest.mark.parametrize("loss", ALL)
def test_alpha_step_maximizes_coordinate_dual(loss):
    """The SDCA update maximizes (to clipping) the scalar dual
    D(δ) = dual_term(α+δ) − z·δ/… − qii·δ²/(2λn·λn)… — verified directly:
    the returned α beats ±perturbations of itself on the subproblem."""
    rng = np.random.default_rng(1)
    lam_n = 7.3

    def coord_dual(a_new, a0, z, qii):
        # change in the global dual from moving this coordinate, ×λn·n:
        # n·Δ(−ℓ*(−α))  −  z·Δα  −  qii·Δα²/(2λn)   (derivation in losses.py)
        da = a_new - a0
        return (float(losses.dual_term(loss, jnp.asarray(a_new), smoothing=S))
                - float(losses.dual_term(loss, jnp.asarray(a0), smoothing=S))
                - (z * da + qii * da * da / (2 * lam_n)))

    for _ in range(50):
        a0 = float(rng.random())
        z = float(rng.normal() * 2)
        qii = float(rng.random() * 4 + 0.1)
        a_new = float(losses.alpha_step(
            loss, jnp.asarray(a0), jnp.asarray(z), jnp.asarray(qii), lam_n,
            smoothing=S,
        ))
        assert 0.0 <= a_new <= 1.0
        best = coord_dual(a_new, a0, z, qii)
        for eps in (1e-4, 1e-2, 0.1):
            for cand in (a_new - eps, a_new + eps):
                if 0.0 <= cand <= 1.0:
                    assert coord_dual(cand, a0, z, qii) <= best + 1e-9, (
                        f"{loss}: α={a_new} not optimal vs {cand} "
                        f"(a0={a0}, z={z}, qii={qii})"
                    )


# ---------------------------------------------------------- end-to-end

# ------------------------------------- the step solved for K chains at once

def _step_grid():
    """(α, z, qii) over the box's corners and their neighbours, margins out
    to |z| = 50 and curvatures from none to 10³·λn: 126 triples."""
    return [(a, z, q)
            for a in (0.0, 1e-12, 0.3, 0.5, 1.0 - 1e-12, 1.0)
            for z in (-50.0, -5.0, -0.5, 0.0, 0.5, 5.0, 50.0)
            for q in (0.0, 1e-3, 1e3)]


@pytest.mark.parametrize("dtype, n", [("float64", 126), ("float32", 126),
                                      ("float64", 3), ("float64", 130)])
def test_lane_packed_solve_is_the_scalar_solve(dtype, n):
    """``alpha_step`` is elementwise: n chains' logistic steps solved in
    the lanes of one (1, 128) vector (one (8, 128) past 128 chains) are
    the n single steps, value for value — to 1e-12 in f64; to 4 ulp in
    f32, where the CPU's vector and scalar ``exp`` differ in the last bit.
    A chain's values go in and come back as (1, 1) vectors, as the kernel
    holds them."""
    from cocoa_tpu.ops.pallas_sdca import _solve_in_lanes

    lam_n = 2.0
    grid = (_step_grid() * 2)[:n]
    triples = [tuple(jnp.full((1, 1), v, dtype) for v in (a, z, q * lam_n))
               for a, z, q in grid]
    new = _solve_in_lanes("logistic", triples, lam_n, 1.0)
    assert {v.shape for v in new} == {(1, 1)}
    packed = np.asarray(jnp.stack(new)).ravel()
    each = np.asarray(jnp.stack(
        [losses.alpha_step("logistic", a, z, qii, lam_n)
         for a, z, qii in triples])).ravel()
    assert packed.dtype == each.dtype == np.dtype(dtype)
    assert np.all((each >= 0.0) & (each <= 1.0))
    tol = 1e-12 if dtype == "float64" else 4 * np.spacing(each)
    assert np.all(np.abs(packed - each) <= tol), np.abs(packed - each).max()


@pytest.mark.parametrize("dtype", ["float32", "float64"])
def test_filler_lanes_stay_finite(dtype):
    """Lanes past the last chain hold α = ½, z = 0, qii = 0, where the
    Newton iteration stays put: no intermediate anywhere in the vector is
    ever non-finite, whatever the chains' own values are."""
    import jax

    from cocoa_tpu.ops.pallas_sdca import _solve_in_lanes

    triples = [tuple(jnp.full((1, 1), v, dtype) for v in t)
               for t in [(0.0, 50.0, 0.0), (1.0, -50.0, 1e3), (0.5, 0.0, 0.0)]]
    with jax.debug_nans(True):
        new = _solve_in_lanes("logistic", triples, 2.0, 1.0)
    assert float(new[2][0, 0]) == 0.5
    # the filler's own values, alone: not so much as an inf on the way
    # (a chain at α = 1 in f32 does pass one: log(1/0), clipped to _U_MAX)
    half = jnp.full((1, 128), 0.5, dtype)
    zero = jnp.zeros((1, 128), dtype)
    with jax.debug_nans(True), jax.debug_infs(True):
        rest = losses.alpha_step("logistic", half, zero, zero, 2.0)
    assert np.all(np.asarray(rest) == 0.5)


def _traced_round(loss, k, interleave, mode="plus", h=4):
    import jax

    from cocoa_tpu.ops.pallas_sdca import pallas_sdca_round

    n_shard, d = 256, 16           # two lane blocks: no (1, 128) state
    args = (jnp.zeros(d), jnp.zeros((k, n_shard)), jnp.zeros((k, n_shard, d)),
            jnp.ones((k, n_shard)), jnp.ones((k, n_shard)),
            jnp.zeros((k, h), jnp.int32))
    return jax.make_jaxpr(lambda *a: pallas_sdca_round(
        *a, 0.01, 1000, mode=mode, sigma=3.0, loss=loss, smoothing=S,
        interleave=interleave, unroll=2, depth=2))(*args)


def _kernel_jaxpr(loss, k, interleave):
    return str(_traced_round(loss, k, interleave))


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (tuple, list)) else (v,)):
            j = getattr(j, "jaxpr", j)
            if hasattr(j, "eqns"):
                yield j


def _walk(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in _sub_jaxprs(eqn):
            yield from _walk(sub)


def _round_body(traced):
    """The jaxpr of a traced round's ``pallas_call`` (the wrapper's
    relayout of these rows, d/8 = 2 lanes, is a kernel of its own)."""
    (call,) = [e for e in _walk(traced.jaxpr)
               if e.primitive.name == "pallas_call"
               and e.params["name"] != "pallas_row_align"]
    return call.params["jaxpr"]


def _kernel_body(*args, **kw):
    return _round_body(_traced_round(*args, **kw))


# primitives whose operands map 1:1 onto their body's inputs (a cond's past
# its index)
_POSITIONAL = ("pjit", "jit", "closed_call", "cond")


def _data_flow(jaxpr, data):
    """``(found, outs)``: the equations of ``jaxpr`` (sub-jaxprs included)
    that yield a 0-d float computed from data, and which of its outputs
    carry data, given which of its inputs do (``data``).  What derives from
    literals alone (the 0.0 and 1.0 handed to ``clip``, variables inside
    its jaxpr) is a constant, not a value of the step."""
    import jax.extend

    found = []
    env = dict(zip(jaxpr.invars, data))
    env.update({v: True for v in jaxpr.constvars})

    def carries(v):
        return isinstance(v, jax.extend.core.Var) and env[v]

    for eqn in jaxpr.eqns:
        ins = [carries(v) for v in eqn.invars]
        outs = [any(ins)] * len(eqn.outvars)
        subs = list(_sub_jaxprs(eqn))
        if subs and eqn.primitive.name in _POSITIONAL:
            outs = [False] * len(outs)
            for sub in subs:
                inner, sub_outs = _data_flow(
                    sub, ins[len(ins) - len(sub.invars):])
                found += inner
                outs = [a or b for a, b in zip(outs, sub_outs)]
        elif subs:                  # a loop: everything in it may carry
            for sub in subs:
                found += _data_flow(sub, [any(ins)] * len(sub.invars))[0]
        elif any(ins):
            found += [eqn for v in eqn.outvars
                      if v.aval.shape == ()
                      and jnp.issubdtype(v.aval.dtype, jnp.floating)]
        env.update(zip(eqn.outvars, outs))
    return found, [carries(v) for v in jaxpr.outvars]


def _scalar_floats(jaxpr):
    """Equations of a kernel body that yield a 0-d floating-point value
    computed from the kernel's data: a value of the step that the scalar
    core holds."""
    return [str(eqn)[:120] for eqn in _scalar_float_eqns(jaxpr)]


def _scalar_float_eqns(jaxpr):
    return _data_flow(jaxpr, [True] * len(jaxpr.invars))[0]


@pytest.mark.parametrize("interleave", [True, False])
@pytest.mark.parametrize("loss", ALL)
def test_only_an_iterative_step_is_solved_in_lanes(loss, interleave):
    """Read off the traced kernel: a closed-form loss's program holds no
    transcendental and no (1, 128) packing vector (the chain-by-chain
    branch, on (1, 1) vectors); logistic's holds ONE Newton chain per
    lockstep step on such a vector, whatever K is — not K of them, a chain
    each."""
    import re

    # lockstep steps the traced body holds: the shard-major kernel's group
    # of 2; the interleaved kernel's one (its ring's group is a loop
    # unrolled at lowering, traced once whatever the depth)
    k, unroll = 3, 1 if interleave else 2
    text = _kernel_jaxpr(loss, k, interleave)
    exps = re.findall(r":(\w+)\[([\d,]*)\] = exp ", text)
    packed = re.findall(r"\[1,128\]", text)
    if not losses.step_is_iterative(loss):
        assert not exps and not packed and " log " not in text
        return
    assert loss == "logistic"
    assert len(exps) == unroll * (losses._NEWTON_ITERS + 1)
    assert {shape for _, shape in exps} == {"1,128"}
    assert text.count(" = log ") == unroll


@pytest.mark.parametrize("interleave", [True, False])
@pytest.mark.parametrize("loss, mode, h", [
    (loss, mode, h) for loss in ALL
    for mode, h in (("plus", 4), ("frozen", 4), ("plus", 5))   # 5: a tail
] + [(rule, "prox", h) for rule in losses.PROX_RULES for h in (4, 5)])
def test_no_value_of_a_step_is_a_scalar(loss, mode, h, interleave):
    """Read off the traced kernel body, both forms and all four rules: no
    floating-point value in it is 0-d.  y, the norm, alpha and the margin
    are (1, 1) vectors from reduces that keep their axes, ``alpha_step``
    runs elementwise on them, and coef and the new alpha broadcast into the
    row update and the state write: a later edit that reduces to a scalar
    (``jnp.sum(v)``), and so sends a value to the scalar core and back,
    eight chains a lockstep step, fails here (PERF.md section 6, PR 39)."""
    body = _kernel_body(loss, 3, interleave, mode=mode, h=h)
    assert any(e.primitive.name == "reduce_sum" for e in _walk(body))
    assert _scalar_floats(body) == []


@pytest.mark.parametrize("depth, h", [(2, 4), (2, 5), (4, 8), (4, 3)])
@pytest.mark.parametrize("loss", ALL)
def test_no_value_of_a_class_step_is_a_scalar(loss, depth, h):
    """The same reading of the T-class kernel's body, whose rows come by
    the ring too: no 0-d float at any depth, in a round of whole ring
    turns or one whose last group runs masked steps past H (the ``live``
    mask is a comparison of integers, and zeroes the update as a select
    on vectors)."""
    import jax

    from cocoa_tpu.ops.pallas_sdca import pallas_sdca_round_classes

    k, n_shard, d, t = 3, 16, 16, 3
    args = (jnp.zeros((t, d)), jnp.zeros((t, k, n_shard)),
            jnp.ones((k, n_shard, d)), jnp.zeros((k, n_shard), jnp.int32),
            jnp.ones((k, n_shard)), jnp.zeros((k, h), jnp.int32))
    traced = jax.make_jaxpr(lambda *a: pallas_sdca_round_classes(
        *a, 0.01, 1000, mode="plus", sigma=3.0, loss=loss, smoothing=S,
        depth=depth))(*args)
    body = _round_body(traced)
    assert any(e.primitive.name == "reduce_sum" for e in _walk(body))
    assert _scalar_floats(body) == []


def _assert_floats_stay_vectors(body, loads):
    """The only 0-d floats of ``body`` computed from its data are ``loads``
    loads of SMEM tables, and no reduce of it drops its last axis away;
    returns its reduces' shapes."""
    scalars = _scalar_float_eqns(body)
    assert {e.primitive.name for e in scalars} <= {"get"}, [
        str(e)[:120] for e in scalars if e.primitive.name != "get"]
    assert len(scalars) == loads
    sums = [e.outvars[0].aval.shape for e in _walk(body)
            if e.primitive.name == "reduce_sum"]
    assert () not in sums
    return sums


def _assert_step_on_vectors(body, loss, loads):
    """A chain's step in ``body``: SMEM loads its only 0-d floats, both
    reduces there (the pick of a repeated row's alpha, the margin's
    total), and under logistic every ``exp`` (1, 1), ``_NEWTON_ITERS`` + 1
    of them and one ``log``; no transcendental under a closed form."""
    assert len(_assert_floats_stay_vectors(body, loads)) >= 2
    prims = list(_walk(body))
    exps = [e.outvars[0].aval.shape for e in prims
            if e.primitive.name == "exp"]
    logs = [e for e in prims if e.primitive.name in ("log", "logistic")]
    if not losses.step_is_iterative(loss):
        assert not exps and not logs
        return
    assert exps == [(1, 1)] * (losses._NEWTON_ITERS + 1)
    assert [e.primitive.name for e in logs] == ["log"]


def _chain_body(loss, mode, width, unrolled):
    """The jaxpr of the HBM-state sparse round's chain kernel
    (``pallas_sparse_hbm._chain_kernel``), traced at a toy size: the
    kernel's body does not depend on the sizes but through the slot
    loops' bodies (``width`` slots a pass, written out where ``unrolled``,
    else a 32-slot and an 8-slot trip body)."""
    import jax

    from cocoa_tpu.ops.pallas_sparse_hbm import (hbm_plan,
                                                 pallas_sparse_hbm_round)

    k, n_shard, d, h = 2, 64, 512, 16
    rows = jnp.ones((k, n_shard))
    args = (jnp.zeros(d), jnp.zeros((k, n_shard)),
            jnp.zeros((k, n_shard, width), jnp.int32),
            jnp.ones((k, n_shard, width)), rows, rows,
            jnp.zeros((k, h), jnp.int32))
    traced = jax.make_jaxpr(lambda *a: pallas_sparse_hbm_round(
        *a, 0.01, 1000, mode=mode, sigma=3.0, loss=loss, smoothing=S,
        interpret=True,
        plan=hbm_plan(d, width, h, one_length=unrolled)))(*args)
    (call,) = [e for e in _walk(traced.jaxpr)
               if e.primitive.name == "pallas_call"
               and e.params["name"] == "pallas_sparse_hbm_round"]
    return call.params["jaxpr"]


@pytest.mark.parametrize("walk", ["grouped", "unrolled"])
@pytest.mark.parametrize("mode", ["plus", "frozen"])
@pytest.mark.parametrize("loss", ALL)
def test_no_value_of_a_sparse_hbm_step_is_computed_as_a_scalar(loss, mode,
                                                               walk):
    """Read off the traced chain kernel of the HBM-state sparse round: the
    only 0-d floats in it are loads of its SMEM step table — y, the scaled
    norm and alpha, splatted to (1, 1) at once, and a nonzero's value in
    the margin's and the scatter's slot loops, splatted into a multiply.
    No reduce, divide, transcendental, product, sum or select yields a 0-d
    float: the margin's total and a repeated row's alpha are reduces that
    keep their axes and ``alpha_step`` runs elementwise on (1, 1) vectors,
    so no float goes to the scalar core and comes back (PERF.md section 6,
    PR 46: the ten Newton iterations on 0-d values were 1.79 us of
    criteo's 2.95 us step).  Under logistic every ``exp`` of the traced
    step is (1, 1): ``_NEWTON_ITERS`` + 1 of them and one ``log``.  Both
    walks of a step's slots (PR 47): 40 slots written out, and a 32-slot
    and an 8-slot trip body."""
    width = 40
    body = _chain_body(loss, mode, width, walk == "unrolled")
    # (y, q, alpha) once a step; a value a slot in each of the two loops
    _assert_step_on_vectors(body, loss, 3 + 2 * width)


def _stream_bodies(kind, loss="hinge", mode="plus"):
    """``{kernel name: jaxpr}`` of the stream's kernels
    (``pallas_longrows._kernel``) as one pass traces them at a toy size —
    ``dots`` (the certificate's margins), ``axpy`` (the ``--accel`` jump)
    or ``chain`` (a round: under ``frozen`` its ``dots`` pass too).  The
    body does not depend on the sizes: a slot group is 8 slots written
    out, once in the margin's loop and once in the update's."""
    import jax

    from cocoa_tpu.ops import pallas_longrows as plr

    k, n_shard, d, h, pieces = 2, 16, 2048, 8, 16
    f32 = jnp.float32
    rows, irows = jnp.ones((k, n_shard), f32), jnp.zeros((k, n_shard),
                                                         jnp.int32)
    shard = dict(sp_indices=jnp.zeros((k, pieces, plr.PIECE), jnp.int32),
                 sp_values=jnp.ones((k, pieces, plr.PIECE), f32),
                 sp_row_ptr=irows, sp_row_len=irows)
    w = jnp.zeros(d, f32)
    if kind == "dots":
        traced = jax.make_jaxpr(
            lambda w, sh: plr.shard_margins(w, sh, True))(w, shard)
    elif kind == "axpy":
        traced = jax.make_jaxpr(
            lambda c, sh, w: plr.shards_axpy(c, sh, w, True))(rows, shard, w)
    else:
        traced = jax.make_jaxpr(lambda w, a, sh, i: plr.pallas_longrows_round(
            w, a, sh["sp_indices"], sh["sp_values"], sh["sp_row_ptr"],
            sh["sp_row_len"], rows, rows, i, 0.01, 1000, mode=mode,
            sigma=3.0, loss=loss, smoothing=S, interpret=True))(
                w, jnp.zeros((k, n_shard), f32), shard,
                jnp.zeros((k, h), jnp.int32))
    return {e.params["name"]: e.params["jaxpr"] for e in _walk(traced.jaxpr)
            if e.primitive.name == "pallas_call"}


@pytest.mark.parametrize("mode", ["plus", "frozen"])
@pytest.mark.parametrize("loss", ALL)
def test_no_value_of_a_stream_step_is_computed_as_a_scalar(loss, mode):
    """Read off the traced chain of rows kept as a stream
    (``pallas_longrows_chain``), both margin forms (``plus``: combined,
    against the resident v = w + sigma' dw_k; ``frozen``: split, x . w
    from a table): the only 0-d floats in it are loads of SMEM — y, the
    scaled norm, alpha and (split) the table's x . w, splatted to (1, 1)
    at once, and a nonzero's value, 8 slots written out in the margin's
    group and 8 in the update's, splatted into a multiply.  The margin's
    total and a repeated row's alpha are reduces that keep their axes,
    ``alpha_step`` runs elementwise on (1, 1) vectors and ``coef``
    broadcasts into the update's multiply-add: no float of a step goes to
    the scalar core and comes back (PERF.md section 6, PR 49; the
    rectangle's chain has been so since PR 46).  Under logistic every
    ``exp`` of the step is (1, 1): ``_NEWTON_ITERS`` + 1 and one ``log``."""
    from cocoa_tpu.ops.pallas_longrows import GROUP, margin_form

    split = margin_form(mode) == "split"
    bodies = _stream_bodies("chain", loss, mode)
    assert set(bodies) == {"pallas_longrows_chain"} | (
        {"pallas_longrows_dots"} if split else set())
    _assert_step_on_vectors(bodies["pallas_longrows_chain"], loss,
                            3 + split + 2 * GROUP)


@pytest.mark.parametrize("kind", ["dots", "axpy"])
def test_no_value_of_a_stream_pass_is_computed_as_a_scalar(kind):
    """The same reading of the stream's all-rows passes: ``dots`` (every
    row's x . w: the certificate's margins) keeps a row's total a (1, 1)
    vector from its one reduce to its lane of the output block; ``axpy``
    (the ``--accel`` jump) splats a row's coefficient once and multiplies
    it with each nonzero's splatted value on the vector unit.  The loads
    of SMEM — a nonzero's value, 8 slots a group, and axpy's coefficient
    — are their only 0-d floats."""
    from cocoa_tpu.ops.pallas_longrows import GROUP

    (body,) = _stream_bodies(kind).values()
    sums = _assert_floats_stay_vectors(
        body, GROUP + (1 if kind == "axpy" else 0))
    assert bool(sums) == (kind == "dots")     # a row's total; axpy sums none


def test_the_scalar_reader_sees_a_scalar_trip():
    """The check above is not vacuous: a kernel body that reduces to 0-d
    and splats the value back is reported, a keepdims reduce is not, and
    neither is a literal handed to ``clip``."""
    import jax

    def body(keep):
        def f(x):
            m = (jnp.sum(jnp.sum(x, axis=1, keepdims=True), axis=0,
                         keepdims=True) if keep else jnp.sum(x))
            return jnp.clip(x * m, 0.0, 1.0)
        return jax.make_jaxpr(f)(jnp.ones((8, 16), jnp.float32)).jaxpr

    assert _scalar_floats(body(True)) == []
    found = _scalar_floats(body(False))
    assert found and "reduce_sum" in found[0]


def test_iterative_steps_are_declared_by_the_loss():
    assert losses.ITERATIVE_STEPS <= set(losses.LOSSES + losses.PROX_RULES)
    assert [name for name in losses.LOSSES + losses.PROX_RULES
            if losses.step_is_iterative(name)] == ["logistic"]


@pytest.mark.parametrize("loss", ["smooth_hinge", "logistic"])
@pytest.mark.parametrize("plus", [True, False])
def test_cocoa_converges_each_loss(tiny_data, loss, plus):
    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=np.float64)
    p = _params(tiny_data, loss=loss, smoothing=S)
    w, alpha, traj = run_cocoa(ds, p, _debug(), plus=plus, quiet=True)
    gaps = [r.gap for r in traj.records]
    assert all(g >= -1e-10 for g in gaps), gaps
    assert gaps[-1] < 0.3 * gaps[0], gaps
    assert np.all(np.asarray(alpha) >= 0.0) and np.all(np.asarray(alpha) <= 1.0)
    # primal-dual correspondence w = (1/λn)·Σ yᵢαᵢxᵢ holds for any loss
    X = tiny_data.to_dense()
    y, av = np.asarray(ds.labels).ravel(), np.asarray(alpha).ravel()
    mask = np.asarray(ds.mask).ravel().astype(bool)
    Xp = np.zeros((mask.size, X.shape[1]))
    Xp[np.flatnonzero(mask)] = X  # undo shard padding row-by-row
    w_re = (y[mask] * av[mask]) @ Xp[mask] / (p.lam * p.n)
    np.testing.assert_allclose(np.asarray(w), w_re, atol=1e-10)


@pytest.mark.parametrize("loss", ["smooth_hinge", "logistic"])
def test_fast_math_matches_exact_each_loss(tiny_data, loss):
    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=np.float64)
    p = _params(tiny_data, num_rounds=8, loss=loss, smoothing=S)
    w_e, a_e, _ = run_cocoa(ds, p, _debug(), plus=True, quiet=True,
                            math="exact")
    w_f, a_f, _ = run_cocoa(ds, p, _debug(), plus=True, quiet=True,
                            math="fast")
    np.testing.assert_allclose(np.asarray(w_f), np.asarray(w_e), atol=1e-8)
    np.testing.assert_allclose(np.asarray(a_f), np.asarray(a_e), atol=1e-8)


@pytest.mark.slow
@pytest.mark.parametrize("loss", ["smooth_hinge", "logistic"])
def test_pallas_interpret_matches_fast_each_loss(tiny_data, loss):
    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=np.float64)
    p = _params(tiny_data, num_rounds=5, loss=loss, smoothing=S)
    w_f, a_f, _ = run_cocoa(ds, p, _debug(), plus=True, quiet=True,
                            math="fast", pallas=False, scan_chunk=5)
    w_p, a_p, _ = run_cocoa(ds, p, _debug(), plus=True, quiet=True,
                            math="fast", pallas=True, scan_chunk=5)
    np.testing.assert_allclose(np.asarray(w_p), np.asarray(w_f), atol=1e-12)
    np.testing.assert_allclose(np.asarray(a_p), np.asarray(a_f), atol=1e-12)


@pytest.mark.parametrize("loss", ["smooth_hinge", "logistic"])
def test_minibatch_cd_converges_each_loss(tiny_data, loss):
    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=np.float64)
    p = _params(tiny_data, num_rounds=40, loss=loss, smoothing=S)
    w, alpha, traj = run_minibatch_cd(ds, p, _debug(), quiet=True)
    gaps = [r.gap for r in traj.records]
    assert all(g >= -1e-10 for g in gaps)
    assert gaps[-1] < gaps[0]


@pytest.mark.parametrize("loss", ["smooth_hinge", "logistic"])
@pytest.mark.parametrize("local", [True, False])
def test_sgd_decreases_primal_each_loss(tiny_data, loss, local):
    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=np.float64)
    p = _params(tiny_data, num_rounds=40, loss=loss, smoothing=S)
    w, traj = run_sgd(ds, p, _debug(), local=local, quiet=True)
    primals = [r.primal for r in traj.records]
    assert primals[-1] < primals[0]


@pytest.mark.parametrize("loss", ["smooth_hinge", "logistic"])
def test_dist_gd_decreases_primal_each_loss(tiny_data, loss):
    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=np.float64)
    p = _params(tiny_data, num_rounds=40, loss=loss, smoothing=S)
    w, traj = run_dist_gd(ds, p, _debug(), quiet=True)
    primals = [r.primal for r in traj.records]
    assert primals[-1] < primals[0]


def test_logistic_gap_reaches_small_values(tiny_data):
    """The Newton coordinate step must be accurate enough to certify tight
    gaps — the whole point of a primal-dual method."""
    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=np.float64)
    p = _params(tiny_data, num_rounds=200, local_iters=24, loss="logistic")
    w, alpha, traj = run_cocoa(ds, p, _debug(debug_iter=20), plus=True,
                               quiet=True, gap_target=1e-8)
    assert traj.records[-1].gap <= 1e-8


def test_unknown_loss_rejected(tiny_data):
    ds = shard_dataset(tiny_data, k=2, layout="dense", dtype=np.float64)
    p = _params(tiny_data, loss="squared")
    with pytest.raises(ValueError, match="loss must be one of"):
        run_cocoa(ds, p, _debug(), plus=True, quiet=True)
