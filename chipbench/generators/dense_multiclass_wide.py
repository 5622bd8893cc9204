"""Dense unit rows with a WIDE class axis: ``dense_multiclass_planted``'s
law (the generator beside this file, frozen: it unrolls a Python loop over
the T classes) at T = 1,000 and d = 4,096, made block of rows by block on
the device, in the program's sharded layout, from the seed.

A row is d unit-normal values scaled to unit length.  T unit directions u_t
are drawn from the seed; a row's class is argmax_t x . u_t — exchangeable
classes, each about 1/T of the rows — its scores one (block, d) . (d, T)
product at ``highest`` precision (a product at the default precision would
round the rows to bfloat16 and move the near ties); ``flip`` of the rows
are relabelled uniformly to ANOTHER class.  The dataset carries the class
ids and the count T beside the +-1 labels (class 0 against the rest).
Rows are made ``ROW_BLOCK`` at a time and written in place: the whole
(K, n_shard, d) array exists once, and a block's normals, scores and
one-hot never more than a block's worth.

**Pre-flight.**  Before it makes anything, ``make`` asks the program which
local solver a job on these shapes would run: only a program that takes a
block of rows a step with the class axis on the lanes (``inner`` ``block``,
``class_axis`` ``lanes``: ops/block_lanes.py) goes on.  One that would run
the T = 1 step under a ``vmap`` over a thousand classes (``fori``) is
refused here, in seconds, with what it resolved to.

One chip only: the deployment this stands in for trains its chip's share
alone (configs/ilsvrc1k.json).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from cocoa_tpu.data.sharding import ShardedDataset, pad_rows, split_sizes

ROW_BLOCK = 4096                # rows made per step: 64 MB of normals


def shapes_only(config: dict) -> ShardedDataset:
    """The dataset's shapes and dtypes with no array behind them: what the
    resolver reads."""
    n, d, k = config["n"], config["d"], config["num_splits"]
    dtype = jnp.dtype(config.get("dtype", "float32"))
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    here = jax.sharding.SingleDeviceSharding(jax.devices()[0])

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=here)

    rows = sds((k, n_shard), dtype)
    return ShardedDataset(
        layout="dense", n=n, num_features=d, counts=sizes.astype(np.int64),
        labels=rows, mask=rows, sq_norms=rows, X=sds((k, n_shard, d), dtype),
        classes=sds((k, n_shard), jnp.int32),
        num_classes=config["num_classes"])


def preflight(config: dict, resolve=None) -> dict:
    """Which local solver the program would run on these shapes; raises
    where it is not the block solve with the class axis on the lanes."""
    if resolve is None:
        from cocoa_tpu.solvers.cocoa import resolve_solver_path as resolve
    h = max(1, int(config["local_iter_frac"] * config["n"]
                   / config["num_splits"]))
    path = resolve(shapes_only(config), h, None, math="fast").as_dict()
    if (path.get("inner"), path.get("class_axis")) != ("block", "lanes"):
        raise RuntimeError(
            f"the program would run the local solve of {config['name']} "
            f"(n = {config['n']}, d = {config['d']}, T = "
            f"{config['num_classes']}, H = {h}) as inner="
            f"{path.get('inner')!r}, kernel={path.get('kernel')!r}, "
            f"class_axis={path.get('class_axis')!r}: not a block of rows a "
            f"step with the class axis on the lanes.  Resolved path: {path}")
    return path


def make(config: dict, seed: int, mesh=None) -> ShardedDataset:
    if mesh is not None:
        raise ValueError("the wide multi-class stand-in is made on one chip")
    preflight(config)
    n, d, k = config["n"], config["d"], config["num_splits"]
    t = config["num_classes"]
    flip = config.get("generator_args", {}).get("flip", 0.02)
    dtype = jnp.dtype(config.get("dtype", "float32"))
    sizes = split_sizes(n, k)
    n_shard = pad_rows(int(sizes.max()))
    block = min(ROW_BLOCK, n_shard)
    n_blocks = -(-n_shard // block)

    def gen(key, counts):
        k_u, k_rows = jax.random.split(key)
        u = jax.random.normal(k_u, (t, d), dtype=jnp.float32)
        u = u / jnp.linalg.norm(u, axis=1, keepdims=True)

        def put(i, bufs):
            # block b of shard s, written in place; the last block starts
            # early enough to end on the shard's last row, and what it
            # shares with its neighbour it makes anew, rows and ids alike
            s, b = i // n_blocks, i % n_blocks
            start = jnp.minimum(b * block, n_shard - block)
            k_x, k_f, k_c = jax.random.split(
                jax.random.fold_in(jax.random.fold_in(k_rows, s), b), 3)
            x = jax.random.normal(k_x, (block, d), dtype=jnp.float32)
            x = x / jnp.linalg.norm(x, axis=1, keepdims=True)
            scores = jnp.dot(x, u.T, precision=jax.lax.Precision.HIGHEST)
            cls = jnp.argmax(scores, axis=1).astype(jnp.int32)
            other = jax.random.randint(k_c, (block,), 1, t, dtype=jnp.int32)
            cls = jnp.where(jax.random.bernoulli(k_f, flip, (block,)),
                            (cls + other) % t, cls)
            live = start + jnp.arange(block) < counts[s]
            m = live.astype(dtype)
            x = (x * m[:, None]).astype(dtype)
            new = (x, jnp.where(cls == 0, 1.0, -1.0).astype(dtype) * m, m,
                   jnp.sum(x * x, axis=-1), jnp.where(live, cls, 0))
            return tuple(
                jax.lax.dynamic_update_slice(
                    buf, a[None].astype(buf.dtype),
                    (s, start) + (0,) * (a.ndim - 1))
                for buf, a in zip(bufs, new))

        rows = jnp.zeros((k, n_shard), dtype)
        return jax.lax.fori_loop(
            0, k * n_blocks, put,
            (jnp.zeros((k, n_shard, d), dtype), rows, rows, rows,
             jnp.zeros((k, n_shard), jnp.int32)))

    x, labels, mask, sq_norms, classes = jax.jit(gen)(
        jax.random.key(seed), jnp.asarray(sizes, dtype=jnp.int32))
    return ShardedDataset(layout="dense", n=n, num_features=d,
                          counts=sizes.astype(np.int64), labels=labels,
                          mask=mask, sq_norms=sq_norms, X=x,
                          classes=classes, num_classes=t)
