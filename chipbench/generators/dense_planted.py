"""Dense unit-row data with a planted separator, made on the device.

The yardstick's own version of ``cocoa_tpu.data.synth.synth_dense_sharded``
(the benchmark's data must not move when the program's generator does): a
(K, n_shard, d) normal matrix with unit rows, labels sign(x . w*) with
``flip`` label noise, padded rows zeroed — one jitted call from the seed,
already in the program's sharded layout, never on the host.  The same
``(n, d, K, seed)`` gives the same shards on one device or on a dp mesh.

Two things differ from the program's generator.  The seed is an argument
of the jitted call, not a constant inside it, so every seed runs the one
compiled program.  And on a mesh every device makes its own shards under
``shard_map``: left to the partitioner, each device made all K shards and
kept its share, which for a dataset that needs four chips is the whole 21
GB on each.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from cocoa_tpu.data.sharding import ShardedDataset, pad_rows, split_sizes
from cocoa_tpu.parallel.mesh import DP_AXIS


def make(config: dict, seed: int, mesh=None) -> ShardedDataset:
    n, d, k = config["n"], config["d"], config["num_splits"]
    flip = config.get("generator_args", {}).get("flip", 0.02)
    dtype = jnp.dtype(config.get("dtype", "float32"))
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))

    def gen_shard(key, s, count):
        k_w, k_x, k_f = jax.random.split(key, 3)
        x = jax.random.normal(jax.random.fold_in(k_x, s), (n_shard, d),
                              dtype=jnp.float32)
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        w_star = jax.random.normal(k_w, (d,), dtype=jnp.float32) / np.sqrt(d)
        flips = jax.random.bernoulli(jax.random.fold_in(k_f, s), flip,
                                     (n_shard,))
        y = jnp.where(x @ w_star >= 0, 1.0, -1.0)
        y = jnp.where(flips, -y, y)
        m = (jnp.arange(n_shard) < count).astype(dtype)
        x = (x * m[:, None]).astype(dtype)
        return x, y.astype(dtype) * m, m, jnp.sum(x * x, axis=-1)

    gen = jax.vmap(gen_shard, in_axes=(None, 0, 0))
    if mesh is not None:
        rows = P(DP_AXIS, None)
        gen = jax.shard_map(gen, mesh=mesh, in_specs=(P(), P(DP_AXIS),
                                                      P(DP_AXIS)),
                            out_specs=(P(DP_AXIS, None, None), rows, rows,
                                       rows))
    x, labels, mask, sq_norms = jax.jit(gen)(
        jax.random.key(seed), jnp.arange(k),
        jnp.asarray(sizes, dtype=jnp.int32))
    return ShardedDataset(layout="dense", n=n, num_features=d,
                          counts=sizes.astype(np.int64), labels=labels,
                          mask=mask, sq_norms=sq_norms, X=x)
