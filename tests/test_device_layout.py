"""What the chip's compiler does with the dense rows at the device loop's
entry, read with no chip.

``libtpu`` compiles for a TPU that is described and not attached, so the
program's own ``run`` (solvers/base.py ``_build_device_run``, captured from
``run_cocoa`` on the dense Pallas path) is lowered here for one chip of a
``v5e:2x2`` and its optimised HLO searched for whole-array copies of the
rows: a ``copy(`` of an entry parameter is a relayout of the whole dataset
that every dispatch — every job — pays before the loop starts, and holds
as a program temporary for the loop's lifetime.  Three such copies sat in
the benchmark's cells unseen until a trace on the chip showed them (PERF.md
§5); this file would have shown them on a machine without one.

An array nobody gave a layout gets the device's default, which on a TPU is
the dimension order that pads least under the (8, 128) tile: at d = 2,000
the row index goes on the lanes (2,000 -> 2,048 would pad 2.4%), and the
Pallas kernels take row-major rows of whole lane tiles only.  XLA made
those of such a cache in two whole-array ops, a ``copy`` (the transpose)
and a ``pad`` (250 -> 256 lanes), 6.55 GB of temporaries at epsilon; since
PR 43 the program does it in one pass of its own (``SolverPath.row_align
== "kernel"``): the cache reaches the ``cocoa_row_align`` kernel through a
``bitcast`` (the stored bytes, renamed), and the kernel's result is the
ring kernel's operand (:func:`_assert_one_pass_over_the_fold_cache`).
Nothing may copy ``X``, and at a width whose lane padding is small the fold
cache is stored lane-padded, which makes row-major the device's own layout
for it, and nothing is copied or relaid at all.

All topology work happens inside fixtures and tests: only one process at a
time may load the TPU's library, and every xdist worker imports this file.
"""

import dataclasses
import functools
import os
import re

import numpy as np
import pytest

K, N_SHARD, H = 8, 1280, 128
# lane padding 2.4% / 0.39% / 30% (mnist8m's width, one model)
WIDTHS = {"rows_on_lanes": 2000, "row_major": 2040, "narrow_rows": 784}


@pytest.fixture(scope="module")
def four_chips():
    """The devices of a described ``v5e:2x2``; the compile cache is off
    while the module runs (an ahead-of-time executable is written to it but
    cannot be read back without a chip)."""
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", cache_was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(four_chips):
    """A ``SingleDeviceSharding`` on one described v5e chip."""
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(four_chips[0])


class _Captured(Exception):
    pass


def _arm_capture(monkeypatch, told=(("pallas", True),)):
    """Stop the next job at its dispatch: the returned dict gets ``run``,
    its ``args`` and the resolved ``path`` (Pallas forced on — ``told``:
    what the resolver is told — and held at compiled: this process's
    platform is cpu), and the entry raises :class:`_Captured`."""
    from cocoa_tpu.ops import pallas_sdca
    from cocoa_tpu.solvers import base
    from cocoa_tpu.solvers import cocoa as cocoa_mod

    got = {}
    build = base._build_device_run

    def capturing(*args, **kw):
        run = build(*args, **kw)

        def call(*run_args):
            got["run"], got["args"] = run, run_args
            raise _Captured

        return call

    resolve = cocoa_mod.resolve_solver_path

    def compiled_pallas(*args, **kw):
        got["path"] = dataclasses.replace(
            resolve(*args, **{**kw, **dict(told)}), interpret=False)
        return got["path"]

    monkeypatch.setattr(base, "_build_device_run", capturing)
    monkeypatch.setattr(cocoa_mod, "resolve_solver_path", compiled_pallas)
    # the relayout at the dispatch's entry too (it asks the platform)
    monkeypatch.setattr(pallas_sdca, "_interpreted", lambda: False)
    base._DEVICE_RUNS.clear()
    return got


def _capture_run(monkeypatch, d, accel, loss="hinge", classes=1):
    """``(run, its arguments, the resolved SolverPath)`` of one CoCoA+ job
    on the dense Pallas path, stopped at the dispatch: the kernels are held
    at compiled (``interpret=False``; this process's platform is cpu), so
    nothing here could run."""
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.sharding import ShardedDataset
    from cocoa_tpu.solvers import base, run_cocoa

    got = _arm_capture(monkeypatch)
    ones = jnp.ones((K, N_SHARD), jnp.float32)
    ds = ShardedDataset(
        layout="dense", n=K * N_SHARD, num_features=d,
        counts=np.full(K, N_SHARD, np.int64), labels=ones, mask=ones,
        sq_norms=ones, X=jnp.zeros((K, N_SHARD, d), jnp.float32),
        classes=(jnp.zeros((K, N_SHARD), jnp.int32) if classes > 1
                 else None), num_classes=classes)
    with pytest.raises(_Captured):
        run_cocoa(ds, Params(n=ds.n, num_rounds=600, local_iters=H,
                             lam=1e-3, loss=loss),
                  DebugParams(debug_iter=10, seed=0), plus=True, quiet=True,
                  math="fast", device_loop=True, rng="permuted",
                  gap_target=1e-4, accel=accel)
    base._DEVICE_RUNS.clear()
    return got["run"], got["args"], got["path"]


def _on_chip(args, chip):
    """The arguments as shapes on the described chip, each in the device's
    default layout: what an array nobody gave a layout gets."""
    import jax

    def spec(a):
        a = a if hasattr(a, "shape") else np.asarray(a)
        return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip)

    return jax.tree.map(spec, args)


def _shape_on(chip, shape, dtype="float32"):
    """One kernel argument as a shape on the described chip."""
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _assert_one_pass_over_the_fold_cache(compiled, k, n_shard, d):
    """The fold cache (K, n_shard, 8, d/8), d/8 no whole lane tile, becomes
    the kernels' rows in ONE pass of the program's own: no ``copy(`` and no
    ``pad(`` anywhere in the optimised HLO makes an array of the cache's or
    of the aligned rows' shape; in the entry computation the one
    ``cocoa_row_align`` custom call takes nothing but ``bitcast``s of the
    cache to (K, 8 d/8, n_shard), the order the device stores it in (one
    an operand: each brings 128 rows of a grid step), and writes
    (K, 8 n_shard, lanes), the aligned rows under another name; the loop's
    body holds no such call; and the program's temporaries are the aligned
    rows and 5% (``copy`` + ``pad`` held two such arrays)."""
    hlo = compiled.as_text()
    d8 = d // 8
    lanes = -(-d8 // 128) * 128
    rows = rf"f32\[{k},(?:{n_shard},8|{8 * n_shard}),(?:{d8}|{lanes})\]"
    whole = [line.strip()[:160] for line in hlo.splitlines()
             if re.search(rf"= {rows}\S* (?:copy|pad)\(", line)]
    assert whole == [], whole
    entry = hlo[hlo.index("ENTRY"):]
    (cache,) = re.findall(
        rf"(%\S+) = f32\[{k},{n_shard},8,{d8}\]\S* parameter\(", entry)
    stored = re.findall(
        rf"(%\S+) = f32\[{k},{8 * d8},{n_shard}\]\S* bitcast\("
        + re.escape(cache) + r"\)", entry)
    calls = [line for line in hlo.splitlines()
             if "custom-call(" in line and "cocoa_row_align" in line]
    assert len(calls) == 1 and calls[0] in entry, calls
    call = re.search(rf"= f32\[{k},{8 * n_shard},{lanes}\]\S* "
                     r"custom-call\(([^)]*)\)", calls[0])
    assert call and stored, calls[0][:300]
    assert set(call.group(1).split(", ")) == set(stored), call.group(1)
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp <= 1.05 * k * n_shard * 8 * lanes * 4, temp


def _row_copies(hlo: str, d: int) -> list:
    """Names of the entry parameters of the rows' shapes that some
    ``copy(`` takes as its operand."""
    shapes = (f"f32[{K},{N_SHARD},{d}]", f"f32[{K},{N_SHARD},8,{d // 8}]",
              f"f32[{K},{N_SHARD},8,{-(-d // 1024) * 128}]")
    entry = hlo[hlo.index("ENTRY"):]
    params = {m.group(1): m.group(2) for m in re.finditer(
        r"(%\S+) = (f32\[[\d,]+\])\S* parameter\(", entry)}
    rows = {name for name, shape in params.items() if shape in shapes}
    assert len(rows) == 2, params       # X and X_folded, both found
    return sorted(m.group(1) for m in re.finditer(r" copy\((%[^),]+)", hlo)
                  if m.group(1) in rows)


@pytest.mark.parametrize("accel", ["auto", None])
@pytest.mark.parametrize("case", list(WIDTHS))
def test_device_loop_opens_with_no_copy_of_the_rows(monkeypatch, one_chip,
                                                    case, accel):
    import jax

    d = WIDTHS[case]
    with jax.enable_x64(False):     # as on the chip; the suite runs with x64
        run, args, path = _capture_run(monkeypatch, d, accel)
        assert (path.rows, path.row_align) == (
            ("row_major", "stored") if case == "row_major"
            else ("device_default", "kernel"))
        compiled = run.lower(*_on_chip(args, one_chip)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo         # the kernel is in the program
    # neither X nor the fold cache: stored lane-padded it is read as it is,
    # stored with the row index on the lanes it is relaid by the program's
    # own kernel, from the bytes as they lie
    assert _row_copies(hlo, d) == []
    if case == "row_major":
        assert "cocoa_row_align" not in hlo
    else:
        _assert_one_pass_over_the_fold_cache(compiled, K, N_SHARD, d)


def test_relayout_of_a_sharded_cache_stays_on_its_chip(four_chips):
    """epsilon's fold cache across the 2x2's dp mesh, two shards a chip
    (no cell: x4's cache is stored lane-padded).  Under the run's mesh the
    relayout goes through ``shard_map`` and every chip relays the shards it
    holds, from the bytes as they lie, with no collective; without the mesh
    Mosaic refuses the call ("cannot be automatically partitioned") rather
    than gather the rows."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from cocoa_tpu.ops import pallas_sdca
    from cocoa_tpu.parallel.mesh import DP_AXIS

    mesh = Mesh(np.array(four_chips), (DP_AXIS,))
    cache = jax.ShapeDtypeStruct((8, 12500, 8, 250), jnp.float32,
                                 sharding=NamedSharding(mesh, P(DP_AXIS)))

    def relay(mesh):
        return jax.jit(functools.partial(
            pallas_sdca.lane_aligned, interpret=False,
            mesh=mesh)).lower(cache).compile()

    with jax.enable_x64(False):
        compiled = relay(mesh)
        with pytest.raises(Exception, match="shard_map"):
            relay(None)
    hlo = compiled.as_text()
    entry = hlo[hlo.index("ENTRY"):]
    assert "f32[2,2000,12500]{2,1,0:T(8,128)} bitcast(" in entry
    assert re.search(r"f32\[2,100000,256\]\S* custom-call\(", entry)
    assert not re.search(r"all-gather|all-reduce|collective-permute| copy\(",
                         hlo)
    assert compiled.memory_analysis().temp_size_in_bytes == 0


# --- one-vs-rest: T = 10 class models over the one copy of the rows ---------

@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_ten_class_job_reads_one_copy_of_the_rows(monkeypatch, one_chip,
                                                  loss):
    """The mnist8m-shaped job (d = 784: a fold of (8, 98), neither a
    multiple of 1,024 nor long; T = 10) compiled for the chip as the
    program's own ``run``: the class kernel lowers through Mosaic inside
    it; no whole-array copy is left, of X or of the fold cache (stored as
    the device lays it out at this width, ``SolverPath.rows`` says so, and
    relaid by the program's own kernel in one pass, ``row_align``);
    nothing holds the rows once per class, and no op works on a one-row
    (1, d) matrix (PR 37's trap), per class or not."""
    import jax

    d, t = 784, 10
    with jax.enable_x64(False):
        run, args, path = _capture_run(monkeypatch, d, "off", loss=loss,
                                       classes=t)
        assert (path.classes, path.kernel, path.form, path.rows) == (
            t, "pallas", "interleaved", "device_default")
        assert (path.lane_fill, path.row_align) == (10 / 16, "kernel")
        compiled = run.lower(*_on_chip(args, one_chip)).compile()
    hlo = compiled.as_text()
    assert "pallas_sdca_classes" in hlo and "tpu_custom_call" in hlo
    assert _row_copies(hlo, d) == []
    _assert_one_pass_over_the_fold_cache(compiled, K, N_SHARD, d)
    per_class = re.findall(rf"f32\[{t},{K},{N_SHARD},(?:{d}|8,{d // 8})\]",
                           hlo)
    assert not per_class, per_class
    assert not re.findall(rf"f32\[(?:{t},)?1,{d}\]", hlo)


def _computations(hlo: str) -> dict:
    """The optimised HLO's computations, name -> instruction lines."""
    comps, name = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) \(.*\) -> .*\{$", line)
        if head:
            name = head.group(1)
            comps[name] = []
        elif line.startswith("}"):
            name = None
        elif name:
            comps[name].append(line.strip())
    return comps


def _called_from(comps: dict, root: str) -> set:
    """``root`` and every computation it reaches (fusions, nested loops)."""
    seen, todo = set(), [root]
    while todo:
        name = todo.pop()
        if name in seen:
            continue
        seen.add(name)
        for line in comps[name]:
            todo += [c for c in re.findall(
                r"(?:calls|to_apply|body|condition)=(%[\w.\-]+)", line)
                if c in comps]
    return seen


def test_class_job_relays_its_state_once_a_chunk(monkeypatch, one_chip):
    """The class kernel's state stays in tile form across a chunk's rounds
    (PR 51).  In the program's own ``run`` compiled for the chip, the
    ``pallas_sdca_classes`` call sits in the body of the INNER ``while`` —
    the scan over a chunk's rounds, itself in the body of the device
    loop's — and nothing that inner loop reaches transposes, copies,
    concatenates, pads or slices an array of the tiles' (K, n_blocks, R,
    128) or of alpha's (T, K, n_shard) kind: the round's call takes the
    loop's carry as it is.  The pack and its inverse are in the device
    loop's body, beside the eval, under the solve's scope.  (On the tree
    before PR 51 the inner loop held three pads, two copies and a slice
    of these shapes, every round.)"""
    import jax

    d, t = 784, 10
    with jax.enable_x64(False):
        run, args, path = _capture_run(monkeypatch, d, "off", classes=t)
        assert (path.class_axis, path.class_state) == ("sublanes", "tiles")
        compiled = run.lower(*_on_chip(args, one_chip)).compile()
    comps = _computations(compiled.as_text())
    (inner,) = [name for name, lines in comps.items() if any(
        "custom-call(" in line and "pallas_sdca_classes" in line
        for line in lines)]
    loops = [name for name, lines in comps.items() if any(
        " while(" in line and f"body={inner}," in line for line in lines)]
    assert len(loops) == 1, (inner, loops)
    (outer,) = loops
    assert any(" while(" in line and f"body={outer}," in line
               for lines in comps.values() for line in lines), outer
    n_blocks, rows = N_SHARD // 128, 16
    kinds = "|".join([f"{K},{n_blocks},(?:{rows}|{t}|1),128",   # the tiles
                      f"{t},{K},(?:{N_SHARD}|{n_blocks},128)"])  # alpha
    relays = re.compile(
        rf"= f32\[(?:{kinds})\]\S* "
        r"(transpose|copy|concatenate|pad|slice)\(")
    in_rounds = [line[:140] for name in _called_from(comps, inner)
                 for line in comps[name] if relays.search(line)]
    assert in_rounds == [], in_rounds
    once_a_chunk = [m.group(1) for line in comps[outer]
                    + [ln for name in _called_from(comps, outer)
                       - _called_from(comps, inner) for ln in comps[name]]
                    for m in [relays.search(line)]
                    if m and "cocoa_local_solve" in line]
    assert "pad" in once_a_chunk and "slice" in once_a_chunk, once_a_chunk


def test_class_kernel_compiles_at_the_cells_size(one_chip):
    """The kernel alone at the benchmark's shapes (8 x 126,576 x 784, H =
    12,656, T = 10: 62 MB of VMEM under the limit it asks for), and not at
    the quarter share's, whose state no v5e core holds: the resolver's
    ``classes_fit`` draws the line where the chip's compiler does."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.ops import pallas_sdca

    k, d, t = 8, 784, 10
    on_chip = functools.partial(_shape_on, one_chip)

    def lower(n_shard, h):
        return pallas_sdca.pallas_sdca_round_classes.lower(
            on_chip((t, d)), on_chip((t, k, n_shard)),
            on_chip((k, n_shard, 8, d // 8)), on_chip((k, n_shard),
                                                      jnp.int32),
            on_chip((k, n_shard)), on_chip((k, h), jnp.int32), 1e-4,
            k * n_shard, mode="plus", sigma=float(k))

    with jax.enable_x64(False):
        assert pallas_sdca.classes_fit(k, 126576, d, t, 4)
        assert "tpu_custom_call" in lower(126576, 12656).compile().as_text()
        assert not pallas_sdca.classes_fit(k, 253136, d, t, 4)
        with pytest.raises(Exception, match="vmem"):
            lower(253136, 25312).compile()


# --- the logistic step solved in lanes: Mosaic takes it, with no chip ------

def test_logistic_job_compiles_with_its_steps_solved_in_lanes(monkeypatch,
                                                              one_chip):
    """The epsilon-shaped logistic job (K = 8 interleaved shards, a row
    ring 2 steps deep: H = 128): the packed Newton solve of ops/pallas_sdca.py
    ``_solve_in_lanes`` — lane-iota selects into (1, 128) vectors, one
    ``alpha_step`` on them, masked lane reduces back to (1, 1) vectors — lowers
    through Mosaic inside the program's own ``run``, and the run's record
    says ``lanes``; the fold cache reaches its ring in one pass, as under
    hinge."""
    import jax

    from cocoa_tpu.ops import pallas_sdca

    d = WIDTHS["rows_on_lanes"]
    assert pallas_sdca.pick_interleave(K, N_SHARD, d, 4, H) == 2
    with jax.enable_x64(False):
        run, args, path = _capture_run(monkeypatch, d, "auto",
                                       loss="logistic")
        assert (path.kernel, path.state, path.step_solve) == (
            "pallas", "vmem", "lanes")
        compiled = run.lower(*_on_chip(args, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    _assert_one_pass_over_the_fold_cache(compiled, K, N_SHARD, d)


def test_logistic_kernel_compiles_at_epsilon_size(one_chip):
    """The kernel alone at the benchmark's shapes (8 x 50,000 x 2,000, H =
    5,000: 9.9 MB of the 14 MB the interleaved kernel may hold): the
    packed solve adds no VMEM the chip's compiler refuses."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.ops import pallas_sdca

    k, n_shard, d, h = 8, 50000, 2000, 5000
    assert pallas_sdca.pick_interleave(k, n_shard, d, 4, h) == 4
    on_chip = functools.partial(_shape_on, one_chip)
    rows = on_chip((k, n_shard))
    with jax.enable_x64(False):
        hlo = pallas_sdca.pallas_sdca_round.lower(
            on_chip((d,)), rows, on_chip((k, n_shard, 8, d // 8)), rows,
            rows, on_chip((k, h), jnp.int32), 1e-3, k * n_shard,
            mode="plus", sigma=float(k), loss="logistic").compile().as_text()
    assert "tpu_custom_call" in hlo


# --- the dense kernel hands back ONE summed dw: nothing relays K of them ----

# (k, n_shard, the shared vector's length, d/8 as stored, H, the form, the
# step's keywords): the lasso's column shards (rows of 1.6 MB, lane-padded:
# shard-major), epsilon's and one chip's of x4 (interleaved), as PERF.md §4
# has them
SUMMED_DW = {
    "epsilon_lasso": (8, 256, 400_000, 50048, 25, "shard_major",
                      dict(mode="prox", sigma=8.0, loss="lasso",
                           smoothing=0.0)),
    # (the ring's rows are lane-aligned: 250 -> 256 lanes in the kernel)
    "epsilon": (8, 50000, 2000, 256, 5000, "interleaved",
                dict(mode="plus", sigma=8.0)),
    # imagenet.cocoa_plus.x4, one chip's two shards of the mesh's eight
    "imagenet_x4_chip": (2, 4094, 160_000, 20096, 409, "interleaved",
                         dict(mode="plus", sigma=8.0)),
}


@pytest.mark.parametrize("name", list(SUMMED_DW))
def test_dense_kernel_returns_one_dw_and_nothing_sums_k(one_chip, name):
    """The round as its caller takes it (``w + dw.sum(axis=0)`` of the
    kernel's result), compiled for the chip: the ``pallas_call``'s first
    result is ONE (1, 8, d/8) block; no op of the program holds K folded
    vectors — the K blocks written, relaid (``copy``) and summed
    (``reduce``) every round until PR 37; and none holds the vector as a
    one-row (1, d) matrix, which the chip tiles a sublane a vreg and cuts
    to length in 21 us at the lasso whatever K was (PERF.md §6, PR 37:
    the kernel's result is unfolded and cut as a 1-D vector)."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.ops import pallas_sdca

    k, n_shard, d, d8, h, form, step = SUMMED_DW[name]
    assert pallas_sdca.dense_form(k, n_shard, 8 * d8, 4, h) == form
    on_chip = functools.partial(_shape_on, one_chip)

    def round_apply(w, alpha, X, labels, sq_norms, idxs):
        dw, a = pallas_sdca.pallas_sdca_round(
            w, alpha, X, labels, sq_norms, idxs, 1e-3, 1, **step)
        return w + dw.sum(axis=0), a

    rows = on_chip((k, n_shard))
    with jax.enable_x64(False):
        hlo = jax.jit(round_apply).lower(
            on_chip((d,)), rows, on_chip((k, n_shard, 8, d8)), rows, rows,
            on_chip((k, h), jnp.int32)).compile().as_text()
    call = re.search(r"= \((f32\[[\d,]+\])\S*, f32\[[\d,]+\]\S*\) "
                     r"custom-call\(.*custom_call_target=\"tpu_custom_call\"",
                     hlo)
    assert call and call.group(1) == f"f32[1,8,{d8}]", call
    k_vectors = re.compile(rf"f32\[(1,)?{k},8,{d8}\]")
    one_row = re.compile(rf"f32\[1,({d}|{8 * d8})\]")
    held = [line.strip()[:160] for line in hlo.splitlines()
            if k_vectors.search(line) or one_row.search(line)]
    assert held == [], held


# --- the dense cells' row fetch (PR 42), with no chip -----------------------

# the benchmark's dense SVM cells as one chip holds them: (k shards on the
# chip, rows a shard, d, H, lambda, the job's loss, classes) -> the form, how
# the rows are stored, the ring's depth, and the most the loop program's
# arguments and temporaries may come to (GB; 13.2 and 15.2 at epsilon and
# mnist8m while the fold cache came by XLA's copy + pad, until PR 43)
DENSE_CELLS = {
    "epsilon.cocoa_plus": ((8, 50000, 2000, 5000, 1e-3, "hinge", 1),
                           "interleaved", "device_default", 4, 9.9),
    "epsilon.logistic": ((8, 50000, 2000, 5000, 1e-3, "logistic", 1),
                         "interleaved", "device_default", 4, 9.9),
    # one chip's two shards of imagenet.cocoa_plus.x4's eight
    "imagenet.cocoa_plus.x4": ((2, 4094, 160000, 409, 1e-5, "hinge", 1),
                               "interleaved", "row_major", 2, 10.7),
    "mnist8m.ovr_cocoa_plus": ((8, 126563, 784, 12656, 1e-4, "hinge", 10),
                               "interleaved", "device_default", 2, 10.9),
}


def _capture_dense_cell(monkeypatch, k, n_shard, d, h, lam, loss, classes):
    """``(run, its arguments, the SolverPath)`` of one CoCoA+ job at a dense
    cell's shape with the cell's flags, stopped at the dispatch; the rows
    and their fold cache are shapes only."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.sharding import ShardedDataset
    from cocoa_tpu.ops import pallas_sdca
    from cocoa_tpu.solvers import base, run_cocoa

    got = _arm_capture(monkeypatch)
    here = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    ones = jnp.ones((k, n_shard), jnp.float32)
    ds = ShardedDataset(
        layout="dense", n=k * n_shard, num_features=d,
        counts=np.full(k, n_shard, np.int64), labels=ones, mask=ones,
        sq_norms=ones,
        X=jax.ShapeDtypeStruct((k, n_shard, d), jnp.float32, sharding=here),
        classes=(jnp.zeros((k, n_shard), jnp.int32) if classes > 1
                 else None), num_classes=classes)
    lanes = (-(-d // 1024) * 128 if pallas_sdca.stores_row_major(d)
             else -(-d // 8))
    ds._x_folded_cache = jax.ShapeDtypeStruct(
        (k, n_shard, 8, lanes), jnp.float32, sharding=here)
    with pytest.raises(_Captured):
        run_cocoa(ds, Params(n=ds.n, num_rounds=600, local_iters=h, lam=lam,
                             loss=loss),
                  DebugParams(debug_iter=10, seed=0), plus=True, quiet=True,
                  math="fast", device_loop=True, rng="permuted",
                  gap_target=1e-4, accel="off" if classes > 1 else "auto")
    base._DEVICE_RUNS.clear()
    return got["run"], got["args"], got["path"]


@pytest.mark.parametrize("cell", list(DENSE_CELLS))
def test_dense_cell_compiles_with_its_rows_fetched_by_the_ring(
        monkeypatch, one_chip, cell):
    """The whole device loop of each dense SVM cell at its real shape,
    compiled for one described v5e: Mosaic takes the kernel whose rows come
    by its own DMA ring (an HBM operand sliced a row at a time: whole lane
    tiles only, which is why the loop opens with ONE pass over the fold
    cache where its last axis is not: ops/pallas_sdca.lane_aligned, the
    program's own relayout kernel), the run's
    record says the form the cell had before the ring and the depth the fit
    gives, and the loop's arguments and temporaries fit the chip's 15.75 GB
    with the room a process needs beside them.  (The lasso's cell, whose
    shard-major kernel keeps the pipelined fetch, is compiled in
    test_lasso_job_compiles_at_epsilon_size_with_a_float32_certificate.)"""
    import jax

    shape, form, rows, depth, held_gb = DENSE_CELLS[cell]
    k, n_shard, d = shape[:3]
    with jax.enable_x64(False):
        run, args, path = _capture_dense_cell(monkeypatch, *shape)
        assert (path.kernel, path.state, path.form, path.rows) == (
            "pallas", "vmem", form, rows)
        assert (path.row_fetch, path.ring_depth) == ("ring", depth)
        compiled = run.lower(*_on_chip(args, one_chip)).compile()
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    stats = compiled.memory_analysis()
    held = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert held <= held_gb * 1e9, (stats.argument_size_in_bytes,
                                   stats.temp_size_in_bytes)
    # the fold cache is relaid at the entry, once, by the program's own
    # kernel, and never in the loop; stored lane-padded it is not relaid
    if rows == "row_major":
        assert (path.row_align, "cocoa_row_align" in hlo) == ("stored",
                                                              False)
    else:
        assert path.row_align == "kernel"
        _assert_one_pass_over_the_fold_cache(compiled, k, n_shard, d)


# --- the sparse deployment that fills a chip (kddb), with no chip -----------

KDDB = dict(n=19264097, d=29890095, k=8, width=64, frac=0.1, lam=1e-5)


# a quarter of criteo as the benchmark holds it (chipbench/configs/
# criteo.json): every row 39 nonzeros, the rectangle as wide as the loader
# stores that (data/sharding.rectangle_width)
CRITEO = dict(n=11460154, d=1000000, k=8, width=39, frac=0.1, lam=1e-5)


def _capture_sparse_run(monkeypatch, shape=KDDB, loss="hinge", width=None):
    """``(run, its arguments, the SolverPath)`` of one CoCoA+ job at the
    kddb shape (or ``shape``) with the cell's flags, stopped at the
    dispatch.  The dataset is shapes only (nothing is made: 10 GB), so the
    one array the program derives from the rows eagerly, the per-row
    lengths, is given as a shape too.  kddb's rows are as if in length
    order; rows of one length (criteo) are as built and say so."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.sharding import (ShardedDataset, pad_rows,
                                         split_sizes)
    from cocoa_tpu.solvers import base, run_cocoa

    got = _arm_capture(monkeypatch)
    k, width = shape["k"], width or shape["width"]
    sizes = split_sizes(shape["n"], k)
    n_shard = pad_rows(int(sizes.max()))
    here = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=here)

    rows = sds((k, n_shard), jnp.float32)
    ds = ShardedDataset(
        layout="sparse", n=shape["n"], num_features=shape["d"],
        counts=sizes.astype(np.int64), labels=rows, mask=rows,
        sq_norms=rows, sp_indices=sds((k, n_shard, width), jnp.int32),
        sp_values=sds((k, n_shard, width), jnp.float32))
    ds._row_len_cache = sds((k, n_shard), jnp.int32)
    if shape is KDDB:
        ds.row_order = sds((k, n_shard), jnp.int32)  # as if in length order
    else:
        ds._one_length = True           # data/sharding.note_row_lengths
    h = int(shape["frac"] * shape["n"] / k)
    with pytest.raises(_Captured):
        run_cocoa(ds, Params(n=ds.n, num_rounds=300, local_iters=h,
                             lam=shape["lam"], loss=loss),
                  DebugParams(debug_iter=5, seed=0), plus=True, quiet=True,
                  math="fast", device_loop=True, rng="permuted",
                  gap_target=1e-2, accel="auto")
    base._DEVICE_RUNS.clear()
    return got["run"], got["args"], got["path"], n_shard


def test_kddb_job_fits_one_chip_and_copies_nothing_large(monkeypatch,
                                                         one_chip):
    """The whole device loop of a kddb job — rounds on the kernel whose
    state stays in HBM, the certificate eval in row blocks, the ``--accel``
    jump — compiled for one described v5e: arguments plus program
    temporaries stay under 13.4 GB of the chip's 15.75, and no ``copy(`` in
    the entry computation makes a d-sized or (K, n_shard, W)-sized array
    (a gather of whole rows would: the rows are stored with the row index
    on the lanes, and layout assignment copies both 9.2 GB arrays
    row-major first — found here before any chip run)."""
    import jax

    with jax.enable_x64(False):
        run, args, path, n_shard = _capture_sparse_run(monkeypatch)
        assert (path.kernel, path.state) == ("pallas", "hbm")
        compiled = run.lower(*_on_chip(args, one_chip)).compile()
    stats = compiled.memory_analysis()
    held = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert 10e9 < stats.argument_size_in_bytes < 11e9    # the deployment
    assert held <= 13.4e9, (stats.argument_size_in_bytes,    # 10.52 + 1.38
                            stats.temp_size_in_bytes)
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") >= 2    # the fetch and the chain
    k, width, d = KDDB["k"], KDDB["width"], KDDB["d"]
    large = re.compile(rf"\[{k},{n_shard},{width}\]|\[{k},{width},"
                       rf"{n_shard}\]|\[{d}\]|\[{k},{d}\]")
    entry = hlo[hlo.index("ENTRY"):]
    copies = [line.strip()[:160] for line in entry.splitlines()
              if " copy(" in line and large.search(line.split(" copy(")[0])]
    assert copies == []
    # the all-rows passes gather and scatter a slot group of a row block at
    # a time ((K, 8, 16384): rows on the lanes, as stored), never a whole
    # (K, 16384, 64) block of slots any more
    from cocoa_tpu.ops import rows

    block, group = rows.row_block(n_shard, width), rows.SLOT_GROUP
    ops = [line for line in hlo.splitlines()
           if re.search(r" (gather|scatter)\(", line)]
    assert any(f"[{k},{group},{block}]" in line for line in ops)
    whole = re.compile(rf"\[{k},{block},{width}\]|\[{k},{width},{block}\]|"
                       rf"\[{k * block * width}\]")
    assert [line.strip()[:160] for line in ops if whole.search(line)] == []


@pytest.mark.parametrize("stored", ["as_the_loader_stores_39", "at_39"])
def test_criteo_quarter_job_copies_no_rows_at_the_loaders_width(
        monkeypatch, one_chip, stored):
    """The whole device loop of a logistic job on a quarter of criteo
    (shapes only; every row 39 nonzeros) compiled for one described v5e.
    As the loader stores those rows (``rectangle_width``: 40 slots, whole
    sublane tiles) the row index is on the lanes, the row fetch reads the
    arrays as they are stored, and no ``copy(`` makes a (K, n_shard,
    W)-sized array in any order: arguments plus temporaries stay under 4.6
    GB, the program is the fetch and the chain (two ``tpu_custom_call``s)
    on the ``direct`` plan.  At W = 39 itself (what the loader built until
    PR 45) the device puts K on the sublanes and the same program opens
    with two copies of all the rows: 3.9 GB of arguments and 4.0 GB of
    temporaries — the reading the rule was made from, kept so that a
    change to either side shows."""
    import jax

    from cocoa_tpu.data.sharding import rectangle_width

    width = rectangle_width(CRITEO["width"]) if stored != "at_39" else 39
    assert rectangle_width(CRITEO["width"]) == 40
    with jax.enable_x64(False):
        run, args, path, n_shard = _capture_sparse_run(
            monkeypatch, CRITEO, "logistic", width)
        assert (path.kernel, path.state, path.step_solve) == (
            "pallas", "hbm", "vector")
        assert (path.local_ids, path.segments, path.table_width) == (
            "direct", 1, 40)
        assert (path.slot_walk, path.slots_walked) == ("unrolled", 40.0)
        compiled = run.lower(*_on_chip(args, one_chip)).compile()
    stats = compiled.memory_analysis()
    held = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    hlo = compiled.as_text()
    assert hlo.count("tpu_custom_call") == 2    # the fetch and the chain
    k = CRITEO["k"]
    assert n_shard == 1432528
    rows = re.compile(rf"\[{k},{n_shard},{width}\]|\[{k},{width},"
                      rf"{n_shard}\]|\[{width},{k},{n_shard}\]|"
                      rf"\[{n_shard},{k},{width}\]")
    copies = [line.strip()[:160] for line in hlo.splitlines()
              if " copy(" in line and rows.search(line.split(" copy(")[0])]
    if stored == "at_39":
        assert len(copies) == 2 and held > 7.5e9, (copies, held)
        return
    assert copies == []
    assert 3.8e9 < stats.argument_size_in_bytes < 4.1e9   # the deployment
    assert held <= 4.6e9, (stats.argument_size_in_bytes,
                           stats.temp_size_in_bytes)


AMAZONCAT = dict(n=1186239, d=203882, k=8, width=256, classes=1000, slots=8,
                 frac=0.1, lam=1e-4)


def test_amazoncat_job_fits_one_chip_and_copies_no_state(monkeypatch,
                                                        one_chip):
    """The whole device loop of a one-vs-rest job over label sets at
    amazoncat13k's shapes (shapes only: 8 GB) — rounds on the chain whose
    class axis rides the lanes, the certificate in row blocks — compiled
    for one described v5e.  W (d, 8, 128) and alpha (K, n_shard, 8, 128)
    are held AS tiles and alpha is aliased through the chain's call, so
    nothing copies an alpha-sized array (held as (K, n_shard, 1024) the
    reshape to tiles was a copy of all 4.86 GB each way: 6.6 GB of
    temporaries a round); the rectangle is row-major at W = 256 and every
    pass slices it as stored (the kddb form of the block pass, rows on the
    lanes, cost two whole-array copies: 16.2 GB held); the label ids are
    read with the row on the lanes, as stored (a gather of whole (L,) rows
    copied them all into a 16-fold padded form, 0.6 GB).  Arguments plus temporaries
    stay under 11.0 GB of the chip's 15.75."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.sharding import (ShardedDataset, pad_rows,
                                         split_sizes)
    from cocoa_tpu.solvers import base, run_cocoa

    shape = AMAZONCAT
    with jax.enable_x64(False):
        got = _arm_capture(monkeypatch)
        k, width = shape["k"], shape["width"]
        sizes = split_sizes(shape["n"], k)
        n_shard = pad_rows(int(sizes.max()))
        here = jax.sharding.SingleDeviceSharding(jax.devices()[0])

        def sds(dims, dt):
            return jax.ShapeDtypeStruct(dims, dt, sharding=here)

        rows = sds((k, n_shard), jnp.float32)
        ds = ShardedDataset(
            layout="sparse", n=shape["n"], num_features=shape["d"],
            counts=sizes.astype(np.int64), labels=rows, mask=rows,
            sq_norms=rows, sp_indices=sds((k, n_shard, width), jnp.int32),
            sp_values=sds((k, n_shard, width), jnp.float32),
            classes=sds((k, n_shard, shape["slots"]), jnp.int32),
            num_classes=shape["classes"])
        ds._row_len_cache = sds((k, n_shard), jnp.int32)
        ds.row_order = sds((k, n_shard), jnp.int32)    # as if in length order
        h = int(shape["frac"] * shape["n"] / k)
        with pytest.raises(_Captured):
            run_cocoa(ds, Params(n=ds.n, num_rounds=300, local_iters=h,
                                 lam=shape["lam"]),
                      DebugParams(debug_iter=5, seed=0), plus=True,
                      quiet=True, math="fast", device_loop=True,
                      rng="permuted", gap_target=1e-2, accel="off")
        base._DEVICE_RUNS.clear()
        path = got["path"]
        assert (path.kernel, path.state, path.class_axis, path.class_tiles,
                path.label_slots) == ("pallas", "hbm", "lanes", 1, 8)
        assert (path.local_ids, path.segments, path.table_width,
                path.ids_per_segment, h) == ("direct", 1, 256, 256, 14827)
        compiled = got["run"].lower(*_on_chip(got["args"],
                                              one_chip)).compile()
    stats = compiled.memory_analysis()
    held = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert 8.1e9 < stats.argument_size_in_bytes < 8.3e9    # the deployment
    assert held <= 11.0e9, (stats.argument_size_in_bytes,   # 8.18 + 2.54
                            stats.temp_size_in_bytes)
    hlo = compiled.as_text()
    assert "pallas_sparse_lanes_round" in hlo
    assert n_shard == 148288
    large = re.compile(rf"\[{k},{n_shard},(8,128|1024|{width})\]|"
                       rf"\[{k},{width},{n_shard}\]|"
                       rf"\[{k},{n_shard},{shape['slots']}\]")
    copies = [line.strip()[:160] for line in hlo.splitlines()
              if " copy(" in line and large.search(line.split(" copy(")[0])]
    assert copies == []


DELICIOUS = dict(n=196606, d=782585, k=8, pieces=65536, longest=8192,
                 classes=1000, slots=8, frac=0.1, lam=1e-4)


@pytest.mark.parametrize("loss", ["hinge", "logistic"])
def test_delicious_job_fits_one_chip_and_copies_no_state(monkeypatch,
                                                        one_chip, loss):
    """The whole device loop of a one-vs-rest job over label sets on rows
    kept as a STREAM at delicious200k's shapes (shapes only: 4.5 GB) —
    rounds on the chain that walks the stream with the class axis on the
    lanes, the certificate in blocks cut by the rows' starts — compiled for
    one described v5e, Mosaic included, under the hinge and under logistic
    (whose Newton steps run on the (R, 128) tiles).  W (d, 8, 128) is 3.21
    GB here, so every W-sized temporary counts, and there are TWO: dW of
    the shard in hand and of the round, 6.41 GB, which is what the
    compiler's own peak holds beside the arguments (``peak_memory_in_bytes``
    less the arguments: the buffer assignment's ``preallocated-temp``,
    6,413,042,176 B, as its dump gives it; a job ran beside a ballast that
    leaves room for two and not for three, PERF.md section 6, PR 57).
    ``temp_size_in_bytes`` reads a THIRD W-sized array, 9.62 GB, that no
    buffer is assigned to: it counts the donated W the loops carry once
    more (a two-loop toy program with a donated carry reads the same one
    array too many), so it is held here only to what it read when this was
    written; and nothing copies W, alpha or the stream."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.sharding import (ShardedDataset, pad_rows,
                                         split_sizes)
    from cocoa_tpu.solvers import base, run_cocoa

    shape = DELICIOUS
    with jax.enable_x64(False):
        got = _arm_capture(monkeypatch)
        k, pieces = shape["k"], shape["pieces"]
        sizes = split_sizes(shape["n"], k)
        n_shard = pad_rows(int(sizes.max()))
        here = jax.sharding.SingleDeviceSharding(jax.devices()[0])

        def sds(dims, dt):
            return jax.ShapeDtypeStruct(dims, dt, sharding=here)

        rows = sds((k, n_shard), jnp.float32)
        irows = sds((k, n_shard), jnp.int32)
        ds = ShardedDataset(
            layout="sparse", n=shape["n"], num_features=shape["d"],
            counts=sizes.astype(np.int64), labels=rows, mask=rows,
            sq_norms=rows, sp_indices=sds((k, pieces, 128), jnp.int32),
            sp_values=sds((k, pieces, 128), jnp.float32),
            sp_row_ptr=irows, sp_row_len=irows,
            sp_row_iota=sds((k, shape["longest"]), jnp.int32),
            classes=sds((k, n_shard, shape["slots"]), jnp.int32),
            num_classes=shape["classes"])
        h = int(shape["frac"] * shape["n"] / k)
        with pytest.raises(_Captured):
            run_cocoa(ds, Params(n=ds.n, num_rounds=300, local_iters=h,
                                 lam=shape["lam"], loss=loss),
                      DebugParams(debug_iter=5, seed=0), plus=True,
                      quiet=True, math="fast", device_loop=True,
                      rng="permuted", gap_target=1e-2, accel="off")
        base._DEVICE_RUNS.clear()
        path = got["path"]
        assert (path.kernel, path.state, path.storage, path.class_axis,
                path.class_tiles, path.label_slots, path.step_solve) == (
            "pallas", "hbm", "stream", "lanes", 1, 8, "lanes")
        assert (path.plan.ring, path.plan.steps, path.plan.row_block, h,
                n_shard) == (512, 2560, 256, 2457, 24576)
        compiled = got["run"].lower(*_on_chip(got["args"],
                                              one_chip)).compile()
    stats = compiled.memory_analysis()
    w_bytes = shape["d"] * 1024 * 4
    assert 4.5e9 < stats.argument_size_in_bytes < 4.6e9    # the deployment
    # two W-sized temporaries and 0.1 GB beside the arguments, not three
    held = stats.peak_memory_in_bytes - stats.argument_size_in_bytes
    assert 2 * w_bytes <= held <= 2 * w_bytes + 0.1e9, (
        stats.peak_memory_in_bytes, stats.argument_size_in_bytes)
    assert stats.temp_size_in_bytes <= 3 * w_bytes + 0.1e9, (
        stats.temp_size_in_bytes)
    hlo = compiled.as_text()
    assert "pallas_longrows_lanes_round" in hlo
    large = re.compile(rf"\[{shape['d']},8,128\]|"
                       rf"\[{k},{n_shard},(8,128|1024)\]|"
                       rf"\[{k},{pieces},128\]|\[{k * pieces},1,128\]|"
                       rf"\[{k},{n_shard},{shape['slots']}\]")
    copies = [line.strip()[:160] for line in hlo.splitlines()
              if " copy(" in line and large.search(line.split(" copy(")[0])]
    assert copies == []


ILSVRC1K = dict(n=320292, d=4096, k=8, classes=1000, frac=0.1, lam=1e-4)


def test_ilsvrc_job_resolves_to_the_lanes_and_fits_one_chip(monkeypatch,
                                                            one_chip):
    """The whole device loop of a one-vs-rest job at ilsvrc1k's shapes
    (shapes only: 6.6 GB) compiled for one described v5e: from the shapes
    alone — no flag, the resolver's own answer but for its replay held at
    the compiled Pallas kernel, which this process's platform (cpu) would
    not pick — the job resolves to a block of 256 rows a step with the
    class axis on the lanes, 16 blocks a round, a block replayed in eight
    sub-blocks of 32 steps (what the earlier ones owe a sub-block: a matrix
    product at ``highest``, in the program with the other three).  W (d, 8,
    128) and alpha (K, n_shard, 8, 128) are held AS tiles; the rows are read
    as stored (d = 4,096 is 32 whole lane tiles: no fold cache, nothing
    relaid) and alpha is scattered into in place: nothing copies a rows- or
    alpha-sized array, the K running vectors V_k (134 MB) are the loop's
    largest temporary, and the certificate's row blocks hold 64 MB at a time
    where one product over all rows would hold 1.3 GB three times over.
    Arguments plus temporaries stay under 6.9 GB of the chip's 15.75."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.sharding import (ShardedDataset, pad_rows,
                                         split_sizes)
    from cocoa_tpu.solvers import base, run_cocoa

    shape = ILSVRC1K
    with jax.enable_x64(False):
        got = _arm_capture(monkeypatch, told=(("block_chain", "pallas"),))
        k, d = shape["k"], shape["d"]
        sizes = split_sizes(shape["n"], k)
        n_shard = pad_rows(int(sizes.max()))
        here = jax.sharding.SingleDeviceSharding(jax.devices()[0])

        def sds(dims, dt):
            return jax.ShapeDtypeStruct(dims, dt, sharding=here)

        rows = sds((k, n_shard), jnp.float32)
        ds = ShardedDataset(
            layout="dense", n=shape["n"], num_features=d,
            counts=sizes.astype(np.int64), labels=rows, mask=rows,
            sq_norms=rows, X=sds((k, n_shard, d), jnp.float32),
            classes=sds((k, n_shard), jnp.int32),
            num_classes=shape["classes"])
        h = int(shape["frac"] * shape["n"] / k)
        with pytest.raises(_Captured):
            run_cocoa(ds, Params(n=ds.n, num_rounds=600, local_iters=h,
                                 lam=shape["lam"]),
                      DebugParams(debug_iter=10, seed=0), plus=True,
                      quiet=True, math="fast", device_loop=True,
                      rng="permuted", gap_target=1e-3, accel="off")
        base._DEVICE_RUNS.clear()
        path = got["path"]
        assert (path.inner, path.kernel, path.chain, path.interpret,
                path.class_axis, path.class_tiles, path.classes) == (
            "block", "products", "pallas", False, "lanes", 1, 1000)
        assert (path.plan.block, path.plan.blocks, h, n_shard) == (
            256, 16, 4003, 40048)
        assert (path.plan.sub, path.plan.cross) == (32, "highest")
        compiled = got["run"].lower(*_on_chip(got["args"],
                                              one_chip)).compile()
    stats = compiled.memory_analysis()
    held = stats.argument_size_in_bytes + stats.temp_size_in_bytes
    assert 6.5e9 < stats.argument_size_in_bytes < 6.7e9    # the deployment
    assert held <= 6.9e9, (stats.argument_size_in_bytes,    # 6.58 + 0.17
                           stats.temp_size_in_bytes)
    # (the two-level replay holds no more than the one-level one did: a
    # block's M0, C and alpha rows once, a sub-block's pieces of 1 MB)
    assert stats.temp_size_in_bytes <= 0.18e9, stats.temp_size_in_bytes
    hlo = compiled.as_text()
    # the replay kernel is there, under the replay's scope INSIDE the solve's
    assert re.search(r'op_name="[^"]*cocoa_local_solve/[^"]*cocoa_wide_replay/'
                     r'pallas_block_lanes_replay', hlo)
    # (Mosaic took it at b = 32 steps a call: the loop over a block's eight
    # sub-blocks is XLA's, inside the scan over the round's blocks)
    # the four products of the solve under the products' scope, each at the
    # precision the plan states; the sub-blocks' cross product among them
    said = re.findall(
        r'operand_precision=\{\w+,(\w+)\}, metadata=\{op_name="[^"]*'
        r'cocoa_local_solve/[^"]*cocoa_wide_products/([^"/]*)/dot_general"',
        hlo)
    assert {name: precision for precision, name in said} == {
        "kbd,kdt->kbt": path.plan.margins, "kbd,kcd->kbc": path.plan.gram,
        "kbc,kct->kbt": path.plan.cross, "kbd,kbt->kdt": path.plan.update}
    # nothing of the solve is rounded to bfloat16, and no op of its on the
    # matrix unit is left at the one-pass default, which would round its
    # operands there (ROADMAP S11)
    solve = [line for line in hlo.splitlines() if "cocoa_local_solve" in line]
    assert not [line[:160] for line in solve if "bf16[" in line]
    mxu = [line for line in solve if re.search(r" (dot|convolution)\(", line)]
    assert len(mxu) == 4 and all("operand_precision={hi" in m for m in mxu)
    large = re.compile(rf"\[{k},{n_shard},(8,128|1024|{d})\]|"
                       rf"\[{k * n_shard},(8,128|1024|{d})\]")
    copies = [line.strip()[:160] for line in hlo.splitlines()
              if (" copy(" in line or " transpose(" in line)
              and large.search(line.split("(")[0])]
    assert copies == []


def test_ordering_a_kddb_shard_happens_in_its_donated_rows(one_chip):
    """``data.sharding._order_rows`` at kddb's shapes, a shard at a time:
    the 4.9 GB row array comes back in the buffer it was donated in (the
    whole set is never held twice), in the layout it came in, and the
    sort's transient — a shard's rows, their keys and the tie-breaking
    iota — is 1.85 GB: ds (10.3 GB) + 1.85 < the job's own 13.4."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.data import sharding
    from cocoa_tpu.data.sharding import pad_rows, split_sizes

    k, width = KDDB["k"], KDDB["width"]
    n_shard = pad_rows(int(split_sizes(KDDB["n"], k).max()))
    rows_bytes = k * n_shard * width * 4
    assert rows_bytes > sharding.ORDER_AT_ONCE_BYTES     # a shard at a time

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    with jax.enable_x64(False):
        compiled = sharding._order_rows.lower(
            sds((k, n_shard, width), jnp.float32),
            sds((1, n_shard), jnp.int32), sds((), jnp.int32), 1).compile()
    stats = compiled.memory_analysis()
    assert stats.alias_size_in_bytes == stats.output_size_in_bytes
    assert rows_bytes <= stats.output_size_in_bytes < 1.01 * rows_bytes
    assert stats.temp_size_in_bytes < 2.0e9, stats.temp_size_in_bytes
    hlo = compiled.as_text()
    assert "input_output_alias={ {}: (0, {}, may-alias) }" in hlo
    layout = re.compile(rf"f32\[{k},{n_shard},{width}\]\{{1,2,0")
    assert len(layout.findall(hlo.split("\n", 1)[0])) == 2   # in and out
    assert not re.search(rf"\[{k},{n_shard},{width}\][^ ]* copy\(", hlo)


# webspam as the benchmark holds it (chipbench/configs/webspam.json: one of
# two chips' share): 8 shards of 21,875 rows, five 2^24-slot windows each
WEBSPAM = dict(k=8, n=175000, d=16609143, h=2187, pieces=5 * (1 << 17))


@pytest.mark.parametrize("what", ["margins", "axpy", "round",
                                  "round_frozen"])
def test_stream_kernels_compile_at_webspam_size(one_chip, what):
    """The kernels of ops/pallas_longrows.py at webspam's shapes: one 66 MB
    d-vector in VMEM beside the two-chunk SMEM ring compiles under the 100
    MB the kernels ask Mosaic for; the stream is read where it is stored
    (no whole-array copy: the (K, pieces, 128) arrays seen piece by piece
    are the same bytes), and a pass's temporaries are a d-vector or two,
    not the rows again.  CoCoA+'s round is the chain alone, run from w
    (three int and three float tables, the stream, w lane-blocked in HBM);
    mini-batch CD's keeps x . w as a ``dots`` pass before its chain."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.data.sharding import pad_rows, split_sizes
    from cocoa_tpu.ops import pallas_longrows as plr

    k, d, h, pieces = (WEBSPAM[x] for x in ("k", "d", "h", "pieces"))
    n_shard = pad_rows(int(split_sizes(WEBSPAM["n"], k).max()))

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rows, irows = sds((k, n_shard)), sds((k, n_shard), jnp.int32)
    stream = dict(sp_indices=sds((k, pieces, 128), jnp.int32),
                  sp_values=sds((k, pieces, 128)), sp_row_ptr=irows,
                  sp_row_len=irows)
    assert plr.longrows_fits(d) and not plr.longrows_fits(2 * d)
    with jax.enable_x64(False):
        if what == "margins":
            fn = lambda w, sh: plr.shard_margins(w, sh, False)  # noqa: E731
            args = (sds((d,)), stream)
        elif what == "axpy":
            fn = lambda c, sh, w: plr.shards_axpy(c, sh, w, False)  # noqa: E731
            args = (rows, stream, sds((d,)))
        else:
            fn = lambda w, a, sh, y, q, i: plr.pallas_longrows_round(  # noqa: E731
                w, a, sh["sp_indices"], sh["sp_values"], sh["sp_row_ptr"],
                sh["sp_row_len"], y, q, i, 1e-4, WEBSPAM["n"],
                mode="frozen" if what == "round_frozen" else "plus",
                sigma=float(k))
            args = (sds((d,)), rows, stream, rows, rows,
                    sds((k, h), jnp.int32))
        compiled = jax.jit(fn).lower(*args).compile()
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 4 * d * 4, stats.temp_size_in_bytes
    hlo = compiled.as_text()
    ran = {p for p in ("dots", "axpy", "chain")
           if f"pallas_longrows_{p}" in hlo}
    assert ran == {"margins": {"dots"}, "axpy": {"axpy"}, "round": {"chain"},
                   "round_frozen": {"dots", "chain"}}[what]
    if what == "round":
        rows, vec = plr._pad_rows(h), plr.vec_rows(d)
        assert vec == 129760
        operands = ([f"s32[1,{rows}]{{1,0}}"] * 3
                    + [f"f32[1,{rows}]{{1,0}}"] * 3
                    + [f"s32[{k * pieces},1,128]{{2,1,0}}",
                       f"f32[{k * pieces},1,128]{{2,1,0}}",
                       f"f32[{vec},128]{{1,0}}"])
        assert ("operand_layout_constraints={" + ", ".join(operands) + "}"
                in hlo)
        assert f'"size":"{plr.VMEM_LIMIT}"' in hlo
    assert not re.search(rf"\[{k},{pieces},128\][^ ]* copy\(", hlo)
    assert not re.search(rf"\[{k * pieces},1,128\][^ ]* copy\(", hlo)


# url as the benchmark holds it (chipbench/configs/url.json: the whole
# published set): 8 shards of 299,517 rows, three 2^24-slot windows each
URL = dict(k=8, n=2396130, d=3231961, h=29951, pieces=3 * (1 << 17))


@pytest.mark.parametrize("what", ["margins", "axpy", "round"])
def test_stream_kernels_compile_at_url_size(one_chip, what):
    """The kernels of ops/pallas_longrows.py at url's shapes: Mosaic takes
    the ring that runs across rows (a DMA started in one row's loop and
    waited for in the next row's), and the stream is read where it is
    stored."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.data.sharding import pad_rows, split_sizes
    from cocoa_tpu.ops import pallas_longrows as plr

    k, d, h, n_pieces = (URL[x] for x in ("k", "d", "h", "pieces"))
    n_shard = pad_rows(int(split_sizes(URL["n"], k).max()))

    def sds(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    rows, irows = sds((k, n_shard)), sds((k, n_shard), jnp.int32)
    stream = dict(sp_indices=sds((k, n_pieces, 128), jnp.int32),
                  sp_values=sds((k, n_pieces, 128)), sp_row_ptr=irows,
                  sp_row_len=irows)
    assert plr.longrows_fits(d)
    with jax.enable_x64(False):
        if what == "margins":
            fn = lambda w, sh: plr.shard_margins(w, sh, False)  # noqa: E731
            args = (sds((d,)), stream)
        elif what == "axpy":
            fn = lambda c, sh, w: plr.shards_axpy(c, sh, w, False)  # noqa: E731
            args = (rows, stream, sds((d,)))
        else:
            fn = lambda w, a, sh, y, q, i: plr.pallas_longrows_round(  # noqa: E731
                w, a, sh["sp_indices"], sh["sp_values"], sh["sp_row_ptr"],
                sh["sp_row_len"], y, q, i, 1e-5, URL["n"], mode="plus",
                sigma=float(k))
            args = (sds((d,)), rows, stream, rows, rows,
                    sds((k, h), jnp.int32))
        compiled = jax.jit(fn).lower(*args).compile()
    stats = compiled.memory_analysis()
    assert stats.temp_size_in_bytes < 8 * d * 4, stats.temp_size_in_bytes
    hlo = compiled.as_text()
    name = {"margins": "dots", "axpy": "axpy", "round": "chain"}[what]
    assert f"pallas_longrows_{name}" in hlo
    assert not re.search(rf"\[{k},{n_pieces},128\][^ ]* copy\(", hlo)
    assert not re.search(rf"\[{k * n_pieces},1,128\][^ ]* copy\(", hlo)


# --- epsilon's lasso (the prox family's cell), with no chip -----------------

LASSO = dict(k=8, cols=2000, n=400000, h=25, lam=72.0)


def _capture_prox_run(monkeypatch):
    """``(run, its arguments, the SolverPath)`` of one ProxCoCoA+ job at
    epsilon-lasso's shape with the cell's flags, stopped at the dispatch.
    The columns (3.28 GB) and their folded copy are shapes only; the target
    and the per-column vectors are arrays (the entry negates the target)."""
    import jax
    import jax.numpy as jnp

    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data.sharding import ShardedDataset, split_sizes
    from cocoa_tpu.solvers import base, run_prox_cocoa

    got = _arm_capture(monkeypatch)
    k, n = LASSO["k"], LASSO["n"]
    sizes = split_sizes(LASSO["cols"], k)
    d_shard = -(-int(sizes.max()) // 16) * 16
    here = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    cols = (jnp.arange(d_shard)[None, :]
            < jnp.asarray(sizes)[:, None]).astype(jnp.float32)
    ds = ShardedDataset(
        layout="dense", n=LASSO["cols"], num_features=n,
        counts=sizes.astype(np.int64), labels=cols, mask=cols,
        sq_norms=200.0 * cols,
        X=jax.ShapeDtypeStruct((k, d_shard, n), jnp.float32, sharding=here),
        target=jnp.ones(n, jnp.float32))
    ds._x_folded_cache = jax.ShapeDtypeStruct(
        (k, d_shard, 8, -(-n // 1024) * 128), jnp.float32, sharding=here)
    with pytest.raises(_Captured):
        run_prox_cocoa(
            ds, Params(n=ds.n, num_rounds=10000, local_iters=LASSO["h"],
                       lam=LASSO["lam"], loss="lasso"),
            DebugParams(debug_iter=50, seed=0), quiet=True, math="fast",
            device_loop=True, rng="permuted", gap_target=20.0)
    base._DEVICE_RUNS.clear()
    return got["run"], got["args"], got["path"], d_shard


def test_lasso_job_compiles_at_epsilon_size_with_a_float32_certificate(
        monkeypatch, one_chip):
    """The whole device loop of an epsilon-lasso job compiled for one
    described v5e: the prox rule (a soft threshold on an unbounded
    coordinate, no label factor) lowers through Mosaic at a 400,000-long
    shared vector, in the shard-major kernel the fit picks (1.6 MB rows:
    all eight shards' blocks do not fit beside each other); the columns are
    read where they are stored (no whole-array copy at the loop's entry);
    arguments and temporaries are the two copies of A and little else; and
    nothing is rounded to bfloat16 on the way into the certificate's
    A^T r (a bf16 A^T r scales the dual point wrongly, PERF.md §7)."""
    import jax

    from cocoa_tpu.ops import pallas_sdca

    k, n, h = LASSO["k"], LASSO["n"], LASSO["h"]
    with jax.enable_x64(False):
        run, args, path, d_shard = _capture_prox_run(monkeypatch)
        assert pallas_sdca.pick_interleave(k, d_shard, n, 4, h) == 0
        assert pallas_sdca.pick_unroll(d_shard, n, 4, h) == 1
        assert (path.kernel, path.state, path.form, path.rows) == (
            "pallas", "vmem", "shard_major", "row_major")
        # the one kernel whose rows still come by Pallas's pipeline
        assert (path.row_fetch, path.ring_depth) == ("pipelined", None)
        compiled = run.lower(*_on_chip(args, one_chip)).compile()
    stats = compiled.memory_analysis()
    assert 6.5e9 < stats.argument_size_in_bytes < 6.7e9   # A, twice
    assert stats.temp_size_in_bytes < 0.1e9, stats.temp_size_in_bytes
    hlo = compiled.as_text()
    assert "tpu_custom_call" in hlo
    big = re.compile(rf"f32\[{k},{d_shard},(8,)?\d+\][^ ]* copy\(")
    assert not big.search(hlo)
    # A^T r is a float32 multiply-and-reduce on the vector unit: no MXU op
    # (whose default precision rounds its operands to bf16) and no bf16
    # value anywhere in the program
    assert "bf16[" not in hlo
    assert not re.search(r" (dot|convolution)\(", hlo)
