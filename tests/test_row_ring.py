"""The row ring of the dense lockstep kernels (ops/pallas_sdca.py
``_ring_steps``): whatever its depth, a round is the round.

The interleaved and the class kernel fetch each lockstep step's K rows by a
DMA ring of their own, ``depth`` steps deep.  Depth moves bytes, never
arithmetic, so (dw, alpha) of one round must not depend on it: at every
depth of the fit's candidates and at 1 (each step's rows fetched as it
starts), for a round that is a whole number of ring turns, one that is not,
and one shorter than the ring (one group, all of it but a step past the
round's end and masked); for one chain and for eight; with a row sampled in two consecutive
steps (the second fetch of it lands while the first is being used).  And
the round is the ``fori`` path's, as the other kernel tests compare.

Interpret mode, float64.  Across depths the comparison is to the last bit
of a double but one (``atol`` 1e-13, as tests/test_fast_math.py compares
step groups): XLA's CPU backend may contract a multiply-add differently in
a loop body unrolled eight times than in one unrolled twice (47 of the 48
cases here are equal to the bit, one is not).  On the chip the results are
equal bit for bit (PERF.md section 6, PR 42).
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from cocoa_tpu.ops import pallas_sdca
from cocoa_tpu.ops.local_sdca import local_sdca_fast

N_SHARD, D, T = 16, 12, 2       # d/8 = 1.5: the fold pads, the ring aligns
LAM = 0.05
DEPTHS = (1,) + tuple(sorted(pallas_sdca.RING_DEPTHS))
ROUNDS = {"whole_turns": 8, "part_turn": 11, "shorter_than_ring": 1}


@functools.lru_cache(maxsize=None)
def _problem(k, h):
    rng = np.random.default_rng(100 * k + h)
    X = rng.normal(size=(k, N_SHARD, D))
    X /= np.linalg.norm(X, axis=-1, keepdims=True)
    classes = rng.integers(0, T, size=(k, N_SHARD))
    idxs = np.stack([rng.permutation(N_SHARD)[:h] for _ in range(k)])
    if h > 1:
        idxs[:, 1] = idxs[:, 0]     # one row, two consecutive steps
    w = rng.normal(size=(T, D)) * 0.1
    alpha = np.clip(rng.normal(size=(T, k, N_SHARD)) * 0.3 + 0.3, 0, 1)
    return dict(
        X=jnp.asarray(X), classes=jnp.asarray(classes, jnp.int32),
        labels=jnp.asarray(np.where(classes == 0, 1.0, -1.0)),
        sq_norms=jnp.asarray(np.sum(X * X, axis=-1)),
        idxs=jnp.asarray(idxs, jnp.int32), w=jnp.asarray(w),
        alpha=jnp.asarray(alpha), n=k * N_SHARD)


def _round(form, k, h, depth):
    """(dw, alpha) of one round of ``form``'s kernel at ring depth
    ``depth``; the interleaved kernel trains class 0 against the rest."""
    p = _problem(k, h)
    step = dict(mode="plus", sigma=float(k), interpret=True, depth=depth)
    if form == "classes":
        return pallas_sdca.pallas_sdca_round_classes(
            p["w"], p["alpha"], p["X"], p["classes"], p["sq_norms"],
            p["idxs"], LAM, p["n"], **step)
    return pallas_sdca.pallas_sdca_round(
        p["w"][0], p["alpha"][0], p["X"], p["labels"], p["sq_norms"],
        p["idxs"], LAM, p["n"], interleave=True, **step)


@functools.lru_cache(maxsize=None)
def _reference(form, k, h):
    """The same round on the ``fori`` path (ops/local_sdca.py), a class and
    a shard at a time, the shards' dw summed as the kernels hand it back;
    and the round at ring depth 1."""
    p = _problem(k, h)
    dws, alphas = [], []
    for t in range(T if form == "classes" else 1):
        y = jnp.where(p["classes"] == t, 1.0, -1.0)
        fast = jax.vmap(lambda m0, a, x, lab, sq, ix: local_sdca_fast(
            m0, a, dict(X=x, labels=lab, sq_norms=sq), ix, LAM, p["n"],
            jnp.zeros(D), mode="plus", sigma=float(k)))
        da, dw = fast(jnp.einsum("knd,d->kn", p["X"], p["w"][t]),
                      p["alpha"][t], p["X"], y, p["sq_norms"], p["idxs"])
        dws.append(dw.sum(axis=0))
        alphas.append(p["alpha"][t] + da)
    dw, alpha = jnp.stack(dws), jnp.stack(alphas)
    if form != "classes":
        dw, alpha = dw[:1], alpha[0]
    return (np.asarray(dw), np.asarray(alpha)), tuple(
        np.asarray(v) for v in _round(form, k, h, 1))


@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("rounds", list(ROUNDS))
@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("form", ["interleaved", "classes"])
def test_a_round_does_not_depend_on_the_rings_depth(form, depth, rounds, k):
    h = ROUNDS[rounds]
    (dw_ref, alpha_ref), (dw_1, alpha_1) = _reference(form, k, h)
    dw, alpha = (np.asarray(v) for v in _round(form, k, h, depth))
    assert dw.shape == dw_ref.shape and alpha.shape == alpha_ref.shape
    assert np.all(np.isfinite(alpha))
    # across depths: the same operations in the same order
    np.testing.assert_allclose(dw, dw_1, rtol=0, atol=1e-13)
    np.testing.assert_allclose(alpha, alpha_1, rtol=0, atol=1e-13)
    # against the fori path: its margins are reduced in another order
    np.testing.assert_allclose(dw, dw_ref, atol=1e-12)
    np.testing.assert_allclose(alpha, alpha_ref, atol=1e-12)
    # the round stepped: equality is not 0 == 0
    assert float(np.max(np.abs(alpha - np.asarray(
        _problem(k, h)["alpha"] if form == "classes"
        else _problem(k, h)["alpha"][0])))) > 1e-4


def test_depth_is_read_from_the_shape():
    """The deepest ring whose rows fit VMEM beside the state, whose loop
    group (depth x K chains x T class models, lowered one after another)
    stays under ``RING_GROUP_MAX`` and whose look-ahead is under a
    ``RING_TAIL``-th of the round: epsilon's eight chains of 5,000 steps
    take 4 (8 is 64 chain-steps a group); x4's two chains would take 8 and
    their 409 steps allow 2; mnist8m's eight chains of ten class models
    the shallowest; the lasso's 1.6 MB columns fit no ring of eight chains
    and run shard-major."""
    assert (pallas_sdca.RING_GROUP_MAX, pallas_sdca.RING_TAIL) == (32, 200)
    assert pallas_sdca.pick_interleave(8, 50000, 2000, 4, 5000) == 4
    assert pallas_sdca.pick_interleave(2, 4094, 160000, 4, 409) == 2
    assert pallas_sdca.pick_interleave(2, 4094, 160000, 4, 1400) == 8
    assert pallas_sdca.pick_interleave(2, 4094, 160000, 4, 1399) == 4
    assert pallas_sdca.class_ring_depth(8, 126563, 784, 10, 4, 12656) == 2
    assert pallas_sdca.class_ring_depth(2, 126563, 784, 2, 4, 12656) == 8
    assert pallas_sdca.pick_interleave(8, 256, 400000, 4, 25) == 0
    assert pallas_sdca.pick_interleave(1, 4096, 160000, 4, 409) == 0
    assert pallas_sdca.class_ring_depth(8, 253136, 784, 10, 4, 25312) == 0
    # four chains of x4's rows: VMEM holds only the shallowest ring
    assert pallas_sdca.interleave_vmem_estimate(4, 4094, 160000, 4, 4) \
        > pallas_sdca.INTERLEAVE_BUDGET
    assert pallas_sdca.pick_interleave(4, 4094, 160000, 4, 409) == 2


@pytest.mark.parametrize("d, lanes, fold", [
    (2000, 250, 250), (2000, 256, 250),       # plain; plain, lane-aligned
    (160000, 20096, 20096), (2040, 256, 256),     # stored row-major
    (784, 98, 98), (784, 128, 98), (1024, 128, 128)])
def test_fold_lanes_of_the_three_ways_rows_come_folded(d, lanes, fold):
    assert pallas_sdca.fold_lanes(d, lanes) == fold
    v = jnp.arange(d, dtype=jnp.float32)
    folded = pallas_sdca._fold_vec(v, fold, -(-lanes // 128) * 128)
    assert folded.shape == (8, -(-lanes // 128) * 128)
    np.testing.assert_array_equal(
        np.asarray(pallas_sdca.unfold_vec(folded, d)), np.asarray(v))


def test_lane_aligned_pads_only_what_is_not_whole_tiles():
    X = jnp.ones((2, 4, 8, 250), jnp.float32)
    out = pallas_sdca.lane_aligned(X)       # interpreted: the platform's
    assert out.shape == (2, 4, 8, 256)
    assert float(out[..., 250:].sum()) == 0.0
    whole = jnp.ones((2, 4, 8, 256), jnp.float32)
    assert pallas_sdca.lane_aligned(whole) is whole
    with pytest.raises(ValueError, match="hold no"):
        pallas_sdca.fold_lanes(2000, 384)


# --- the relayout kernel: the fold cache to lane-aligned rows (PR 43) -------

def _pad_of(folded):
    """What XLA's ``copy`` + ``pad`` made of the fold cache until PR 43."""
    return jnp.pad(folded, ((0, 0),) * 3 + ((0, -folded.shape[-1] % 128),))


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("k", [1, 8])
@pytest.mark.parametrize("d", [2000, 784, 1024, 10400])
def test_relayout_kernel_equals_the_pad_to_the_bit(d, k, dtype):
    """``lane_aligned`` of a fold cache whose last axis is no whole lane
    tile (epsilon's d/8 = 250, mnist8m's 98; 1,300: eleven tiles, a tile a
    grid step as at every width) is ``jnp.pad`` of it, bit for bit, from
    ONE ``pallas_call``: the last row block is partial (300 rows, blocks of
    512 or 256, whose parts past the rows re-read the last), the last lane
    tile's input block is taller than the stored array (8 x 256 against
    2,000) and the lanes past d/8 come out zero; f64 as f32.  A cache that
    is aligned as stored (d = 1,024) comes back as it is and nothing is
    traced."""
    n_shard = 300
    X = jax.random.normal(jax.random.PRNGKey(d + k), (k, n_shard, d),
                          jnp.dtype(dtype))
    folded = pallas_sdca.fold_rows(X)
    aligned = jax.jit(functools.partial(pallas_sdca.lane_aligned,
                                        interpret=True))
    traced = str(jax.make_jaxpr(aligned)(folded))
    if d == 1024:
        assert pallas_sdca.lane_aligned(folded, True) is folded
        assert "pallas_call" not in traced
        return
    assert traced.count("pallas_call") == 1 and " pad" not in traced
    out = aligned(folded)
    assert out.dtype == folded.dtype
    assert out.shape == (k, n_shard, 8, -(-d // 1024) * 128)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(_pad_of(folded)))
    assert float(jnp.abs(out[..., :d // 8]).min()) > 0      # not 0 == 0


def test_relayout_kernel_under_a_mesh_runs_a_shard_a_device():
    """With the dp mesh of the run the kernel goes under ``shard_map``:
    every device relays its own shards (a ``pallas_call`` on a sharded
    array outside it would be handed the whole array), and the result is
    the pad again, sharded as the cache was."""
    from cocoa_tpu.parallel import make_mesh
    from cocoa_tpu.parallel.mesh import DP_AXIS
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = make_mesh(4)
    rows = NamedSharding(mesh, P(DP_AXIS))
    folded = jax.device_put(jax.random.normal(
        jax.random.PRNGKey(0), (8, 200, 8, 98), jnp.float32), rows)
    out = jax.jit(functools.partial(pallas_sdca.lane_aligned, interpret=True,
                                    mesh=mesh))(folded)
    assert out.sharding.is_equivalent_to(rows, out.ndim)
    np.testing.assert_array_equal(np.asarray(out),
                                  np.asarray(_pad_of(folded)))


@pytest.mark.parametrize("cell, d, layout, pallas, want", [
    ("epsilon", 2000, "dense", True, "kernel"),
    ("mnist8m", 784, "dense", True, "kernel"),
    ("imagenet", 160000, "dense", True, "stored"),
    ("epsilon_lasso", 400000, "dense", True, "stored"),
    ("kddb", 29890095, "sparse", True, None),
    ("epsilon_fori", 2000, "dense", False, None),
])
def test_solver_path_says_how_the_rows_come_aligned(cell, d, layout, pallas,
                                                    want):
    """``SolverPath.row_align``: ``kernel`` where a dispatch relays the
    fold cache, ``stored`` where the cache is kept lane-padded, None where
    there is no fold cache (a sparse set, the ``fori`` path)."""
    import types

    from cocoa_tpu.solvers.cocoa import resolve_solver_path

    labels = jnp.zeros((2, 128), jnp.float32)
    ds = types.SimpleNamespace(
        k=2, labels=labels, layout=layout, n_hot=0, n_shard=128,
        num_features=d, sp_indices=jnp.zeros((2, 128, 4), jnp.int32),
        sp_row_ptr=None)
    path = resolve_solver_path(ds, 8, math="fast", pallas=pallas)
    assert path.row_align == want
    assert path.as_dict()["row_align"] == want
    assert (path.rows == "row_major") == (want == "stored")
    assert ("relaid by a kernel once a dispatch" in path.describe()) == (
        want == "kernel")


@pytest.mark.parametrize("itemsize, rows", [(4, 512), (8, 256)])
def test_relayout_block_is_sized_from_its_vmem_estimate(itemsize, rows):
    """The kernel's blocks (two buffers in, two out) are what
    ``align_vmem_estimate`` counts, and ``pick_align_rows`` takes the most
    rows a step that stay under ``VMEM_BUDGET``: 512 at f32 (8 MiB), 256 at
    f64 (interpret mode only)."""
    assert pallas_sdca.pick_align_rows(itemsize) == rows
    est = pallas_sdca.align_vmem_estimate(rows, itemsize)
    assert est == (2 * (rows // 128) * 8 * 128 * 128
                   + 2 * 8 * rows * 128) * itemsize
    assert est <= pallas_sdca.VMEM_BUDGET
    assert all(pallas_sdca.align_vmem_estimate(more, itemsize)
               > pallas_sdca.VMEM_BUDGET
               for more in pallas_sdca.ALIGN_ROWS if more > rows)
