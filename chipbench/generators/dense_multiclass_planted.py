"""Dense unit-row data with T planted classes, made on the device.

``dense_planted`` with a class axis: a (K, n_shard, d) normal matrix with
unit rows, T unit directions u_t drawn from the seed, the class of a row
argmax_t x . u_t — so the classes are exchangeable and each holds about
1/T of the rows — and ``flip`` of the rows relabelled uniformly to ANOTHER
class.  One jitted call from the seed, the seed an argument of it, already
in the program's sharded layout, never on the host.  The dataset carries
the class ids and the count T beside the +-1 labels (class 0 against the
rest, the labels a binary reading of the same rows would have); a program
whose datasets carry no class axis is refused before anything is made.

One chip only: the deployment this stands in for trains its chip's share
alone (configs/mnist8m.json).
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from cocoa_tpu.data.sharding import ShardedDataset, pad_rows, split_sizes


def make(config: dict, seed: int, mesh=None) -> ShardedDataset:
    if "num_classes" not in {f.name for f in
                             dataclasses.fields(ShardedDataset)}:
        raise RuntimeError("this program's datasets carry no class axis "
                           "(ShardedDataset.num_classes): it cannot run a "
                           "one-vs-rest cell")
    if mesh is not None:
        raise ValueError("the multi-class stand-in is made on one chip")
    n, d, k = config["n"], config["d"], config["num_splits"]
    t = config["num_classes"]
    flip = config.get("generator_args", {}).get("flip", 0.02)
    dtype = jnp.dtype(config.get("dtype", "float32"))
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))

    def gen_shard(key, s, count):
        k_u, k_x, k_f, k_c = jax.random.split(key, 4)
        x = jax.random.normal(jax.random.fold_in(k_x, s), (n_shard, d),
                              dtype=jnp.float32)
        x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
        u = jax.random.normal(k_u, (t, d), dtype=jnp.float32)
        u = u / jnp.linalg.norm(u, axis=1, keepdims=True)
        # the scores on the vector unit: a matmul at default precision
        # would round the rows to bfloat16 and move the near ties
        scores = jnp.stack([jnp.sum(x * u[c], axis=1) for c in range(t)])
        cls = jnp.argmax(scores, axis=0).astype(jnp.int32)
        flips = jax.random.bernoulli(jax.random.fold_in(k_f, s), flip,
                                     (n_shard,))
        other = jax.random.randint(jax.random.fold_in(k_c, s), (n_shard,),
                                   1, t, dtype=jnp.int32)
        cls = jnp.where(flips, (cls + other) % t, cls)
        live = jnp.arange(n_shard) < count
        m = live.astype(dtype)
        x = (x * m[:, None]).astype(dtype)
        y = jnp.where(cls == 0, 1.0, -1.0).astype(dtype) * m
        return (x, y, m, jnp.sum(x * x, axis=-1),
                jnp.where(live, cls, 0))

    x, labels, mask, sq_norms, classes = jax.jit(
        jax.vmap(gen_shard, in_axes=(None, 0, 0)))(
        jax.random.key(seed), jnp.arange(k),
        jnp.asarray(sizes, dtype=jnp.int32))
    return ShardedDataset(layout="dense", n=n, num_features=d,
                          counts=sizes.astype(np.int64), labels=labels,
                          mask=mask, sq_norms=sq_norms, X=x,
                          classes=classes, num_classes=t)
