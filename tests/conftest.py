"""Test configuration.

Distributed-without-a-cluster: the reference validates multi-worker behavior
with local-mode Spark + 4 partitions (run-demo-local.sh, hingeDriver.scala:22);
the JAX translation of that trick is a virtual 8-device CPU backend via
``--xla_force_host_platform_device_count`` — the same shard_map/psum code path
as a real TPU mesh.  x64 is enabled so tests can validate against the float64
NumPy oracle (the reference is float64 Breeze throughout).
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"  # tests never take the chip
# the suite's wall clock is mostly XLA compiles (~860 s cold against ~350 s
# warm on this 1-vCPU container, with an 870 s tier-1 budget), so the tests
# place the persistent compile cache — for themselves and every subprocess
# they start — at one path that survives between runs and checkouts.  The
# program sets no directory in code when the variable is set
# (utils/compile_cache.py); an outer setting wins.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(tempfile.gettempdir(), "cocoa_jax_cache"))
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# belt and braces with the variable above: backend init is lazy, so this
# (before any jax.devices() call) lands on the virtual 8-CPU mesh even if
# something imported earlier changed the platform config
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


# the reference checkout's data files when present, else the identical
# copies committed under data/ (CI and reference-less containers); probed
# PER FILE so a partial reference checkout falls back too
_REF_DATA = "/root/reference/data"
_REPO_DATA = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "data")


def _data_file(name):
    ref = os.path.join(_REF_DATA, name)
    return ref if os.path.exists(ref) else os.path.join(_REPO_DATA, name)


SMALL_TRAIN = _data_file("small_train.dat")
SMALL_TEST = _data_file("small_test.dat")
DEMO_NUM_FEATURES = 9947  # run-demo-local.sh:4


@pytest.fixture(scope="session")
def small_train():
    from cocoa_tpu.data import load_libsvm

    return load_libsvm(SMALL_TRAIN, DEMO_NUM_FEATURES)


@pytest.fixture(scope="session")
def small_test():
    from cocoa_tpu.data import load_libsvm

    return load_libsvm(SMALL_TEST, DEMO_NUM_FEATURES)


@pytest.fixture(scope="session")
def tiny_data():
    """Small synthetic separable-ish dataset for fast solver tests."""
    rng = np.random.default_rng(7)
    n, d = 96, 24
    w_true = rng.normal(size=d)
    X = rng.normal(size=(n, d)) * (rng.random(size=(n, d)) < 0.4)
    y = np.where(X @ w_true + 0.1 * rng.normal(size=n) > 0, 1.0, -1.0)
    from cocoa_tpu.data.libsvm import LibsvmData

    dense_rows = []
    indptr = [0]
    indices = []
    values = []
    for i in range(n):
        nz = np.nonzero(X[i])[0]
        indices.append(nz.astype(np.int32))
        values.append(X[i, nz])
        indptr.append(indptr[-1] + len(nz))
        dense_rows.append(X[i])
    return LibsvmData(
        labels=y.astype(np.float64),
        indptr=np.asarray(indptr, dtype=np.int64),
        indices=np.concatenate(indices),
        values=np.concatenate(values),
        num_features=d,
    )
