"""Device-side (in-jit) index-table generation vs the host samplers.

Per-round (K, H) index tables built on the host cost an h2d copy and a
dispatch dependency every round — more to SHIP than the fused kernel round
costs to RUN.  The fix is the
reference's own structure — draw indices inside the worker
(CoCoA.scala:144,151) — as in-jit generation (utils/prng.py
device_sample_per_shard, base.IndexSampler.tables_from_ts).  These tests pin
the device tables to the host tables bit-for-bit:

- ``reference``: the 48-bit java.util.Random LCG replayed on 12-bit int32
  limbs, including the modulo-rejection filtering (exercised here with
  bounds just above a power of two, where ~half of all draws reject —
  far harsher than any real shard size).
- ``jax``: the same counter-hash stream (utils/prng.py) expanded host-side
  or in-jit — one integer-arithmetic implementation, so host ≡ device by
  construction (jax.random's batched-key threefry was abandoned for this
  path: it dispatched once per key batch).
- ``permuted``: the same per-(seed, shard, epoch) Feistel-bijection
  permutations either way; also re-pins the reshuffling invariants
  (coverage, chunk invariance, continuity) on that stream.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cocoa_tpu.solvers.base import IndexSampler
from cocoa_tpu.utils.prng import (
    device_replay_ok,
    device_sample_per_shard,
    sample_indices_per_shard,
)


def _host(seed, t0, c, h, counts):
    tab = sample_indices_per_shard(seed, range(t0, t0 + c), h, counts)
    return np.swapaxes(tab, 0, 1)  # (C, K, H)


@pytest.mark.parametrize("seed,t0,c,h,counts", [
    (0, 1, 5, 17, [33]),
    (5, 1, 3, 10, [33, 64, 100]),            # mixed pow2 / non-pow2
    (99, 7, 4, 64, [50000, 2531, 1, 7]),     # big, tiny, and n=1 shards
    (123456, 1000, 2, 128, [20242]),
])
def test_reference_device_tables_bit_exact(seed, t0, c, h, counts):
    ts = jnp.arange(t0, t0 + c, dtype=jnp.int32)
    dev = np.asarray(jax.jit(
        lambda ts: device_sample_per_shard(seed, ts, h, counts)
    )(ts))
    np.testing.assert_array_equal(dev, _host(seed, t0, c, h, counts))


def test_reference_device_tables_heavy_rejection():
    # bound just above 2^30: java's nextInt rejects ~50% of raw draws, so
    # every lane exercises the in-jit compaction path
    counts = [(1 << 30) + 1, (1 << 30) + 3]
    dev = np.asarray(device_sample_per_shard(
        3, jnp.arange(1, 4, dtype=jnp.int32), 40, counts))
    np.testing.assert_array_equal(dev, _host(3, 1, 3, 40, counts))


def test_reference_device_replay_guard():
    assert device_replay_ok(0, 1000)
    assert not device_replay_ok(-1, 10)
    assert not device_replay_ok((1 << 31) - 5, 10)


@pytest.mark.parametrize("mode", ["reference", "jax", "permuted"])
def test_sampler_device_equals_host(mode):
    counts = np.array([13, 16, 9])
    host = IndexSampler(mode, seed=5, h=7, counts=counts, device=False)
    dev = IndexSampler(mode, seed=5, h=7, counts=counts, device=True)
    want = np.asarray(host.chunk_indices(3, 6))
    spec = dev.chunk_indices(3, 6)
    assert set(spec) == {"t"} and spec["t"].shape == (6,)
    got = np.asarray(jax.jit(dev.tables_from_ts)(spec["t"]))
    np.testing.assert_array_equal(got, want)
    # and all values in range
    for s, cnt in enumerate(counts):
        assert got[:, s, :].min() >= 0 and got[:, s, :].max() < cnt


def test_permuted_epoch_coverage_and_continuity():
    counts = np.array([10, 35, 5])
    s = IndexSampler("permuted", seed=3, h=5, counts=counts)
    tab = np.asarray(s.chunk_indices(1, 40))          # (40, 3, 5) = 200 steps
    for k, cnt in enumerate(counts):
        stream = tab[:, k, :].reshape(-1)
        for e in range(len(stream) // cnt):
            epoch = stream[e * cnt:(e + 1) * cnt]
            assert sorted(epoch.tolist()) == list(range(cnt))


def test_permuted_chunk_invariance():
    counts = np.array([11, 8])
    s1 = IndexSampler("permuted", seed=5, h=7, counts=counts)
    s2 = IndexSampler("permuted", seed=5, h=7, counts=counts)
    whole = np.asarray(s1.chunk_indices(1, 12))
    parts = np.concatenate([
        np.asarray(s2.chunk_indices(1, 5)),
        np.asarray(s2.chunk_indices(6, 4)),
        np.asarray(s2.chunk_indices(10, 3)),
    ])
    np.testing.assert_array_equal(whole, parts)
    # different seed ⇒ different stream
    s3 = IndexSampler("permuted", seed=6, h=7, counts=counts)
    assert not np.array_equal(np.asarray(s3.chunk_indices(1, 12)), whole)


def test_ints_per_round_and_cache_token():
    s = IndexSampler("reference", 0, 50, np.array([100, 100]))
    assert s.ints_per_round() == 100
    s.device = True
    assert s.ints_per_round() == 1
    t1 = s.cache_token()
    s2 = IndexSampler("reference", 0, 50, np.array([100, 100]), device=True)
    assert s2.cache_token() == t1
    s3 = IndexSampler("reference", 1, 50, np.array([100, 100]), device=True)
    assert s3.cache_token() != t1


def test_solver_trajectory_device_vs_host_sampling(tiny_data):
    """End to end: CoCoA+ chunked with device sampling == host sampling,
    for every rng mode (bit-identical tables ⇒ bit-identical runs)."""
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data import shard_dataset
    from cocoa_tpu.solvers import run_cocoa

    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=jnp.float64)
    params = Params(n=tiny_data.n, num_rounds=8, local_iters=12, lam=1e-2)
    debug = DebugParams(debug_iter=4, seed=0)
    for mode in ("reference", "jax", "permuted"):
        runs = {}
        for sampling in ("host", "device"):
            w, a, traj = run_cocoa(
                ds, params, debug, plus=True, quiet=True, scan_chunk=4,
                rng=mode, sampling=sampling,
            )
            runs[sampling] = (np.asarray(w), np.asarray(a),
                              [r.gap for r in traj.records])
        np.testing.assert_array_equal(runs["host"][0], runs["device"][0])
        np.testing.assert_array_equal(runs["host"][1], runs["device"][1])
        assert runs["host"][2] == runs["device"][2]


def test_sgd_device_sampling(tiny_data):
    """η(t) solvers: the TsSampler spec path generates idxs in-jit."""
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data import shard_dataset
    from cocoa_tpu.solvers import run_sgd

    ds = shard_dataset(tiny_data, k=4, layout="dense", dtype=jnp.float64)
    params = Params(n=tiny_data.n, num_rounds=6, local_iters=10, lam=1e-2)
    debug = DebugParams(debug_iter=3, seed=0)
    outs = {}
    for sampling in ("host", "device"):
        w, traj = run_sgd(ds, params, debug, local=True, quiet=True,
                          scan_chunk=3, sampling=sampling)
        outs[sampling] = np.asarray(w)
    np.testing.assert_array_equal(outs["host"], outs["device"])


def test_sampling_flag_validation(tiny_data):
    from cocoa_tpu.config import DebugParams, Params
    from cocoa_tpu.data import shard_dataset
    from cocoa_tpu.solvers import run_cocoa
    from cocoa_tpu.solvers.base import resolve_sampling

    ds = shard_dataset(tiny_data, k=2, layout="dense", dtype=jnp.float64)
    params = Params(n=tiny_data.n, num_rounds=2, local_iters=4, lam=1e-2)
    debug = DebugParams(debug_iter=2, seed=0)
    with pytest.raises(ValueError, match="sampling"):
        run_cocoa(ds, params, debug, plus=True, quiet=True,
                  sampling="bogus")
    # device replay outside the int32 seed range must refuse explicitly...
    s = IndexSampler("reference", (1 << 31) - 1, 4, ds.counts)
    with pytest.raises(ValueError, match="device sampling"):
        resolve_sampling("device", s, 10)
    # ...and fall back silently under auto
    assert resolve_sampling("auto", s, 10) is False
